"""Median queue wait (submit to dispatch) of the window's requests, as the
engine stamps it on each response (`queue_wait_s`, the quantity its
stats.Collector keeps in `queue_waits_s`)."""

import statistics


def read(r):
    waits = r.counters.get("queue_waits_s")
    if not waits:
        return None
    return 1e3 * statistics.median(waits)
