"""Share of device busy time in collective operations on the cholinv mesh
(all-gathers, all-reduces and kin, matched by name in
trace_reduce.bucket whatever ``CI::*`` scope issued them), from own
times."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    s = r.trace.bucket_s("collective")
    return 100.0 * s / r.trace.busy_s if s > 0 else None
