"""Mean correction sweeps per solve in the window: the program's own count
(RefineInfo.iters of each solve, summed by the driver) over the solves."""


def read(r):
    solves = r.counters.get("solves")
    if not solves or "sweeps" not in r.counters:
        return None
    return r.counters["sweeps"] / solves
