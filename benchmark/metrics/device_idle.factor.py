"""Device idle share of the traced window: 1 − (union of the op intervals
on each chip's XLA Ops line) / (window), averaged over the cell's chips."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * r.trace.idle_share()
