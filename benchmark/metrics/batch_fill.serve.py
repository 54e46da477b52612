"""Mean batch occupancy (filled slots over capacity) of the batches the
engine dispatched in the window: its stats.Collector `occupancies`."""


def read(r):
    occ = r.counters.get("occupancies")
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ)
