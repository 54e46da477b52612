"""The program's own build spans, for the per-layer readers of the build layer.

The program keeps its spans in memory, on the profiler's clock
(capital_tpu/obs/spans.py: ``SPAN_LOG``; ``build.trace``, ``build.lower``
and ``build.compile`` spans from JAX's own build events, a ``build.compile``
tagged ``cache`` "load" or "compile", and ``profiled`` when a profiler trace
was recording).  A program without that log gives None from every function
here, never an error.

The program cannot see where the benchmark's window opened, so it is found
from the builds themselves: a driver builds everything before its window
and its reference builds after it, so the window lies in the first stretch
of at least the window's length in which no build ran.  A build made while
the profiler recorded lies in the window, and bounds it from above.
"""

from __future__ import annotations

import trace_reduce


def build_records():
    """The program's build spans in the order they closed, or None when the
    program keeps no span log."""
    try:
        from capital_tpu.obs import spans
    except ImportError:
        return None
    log = getattr(spans, "SPAN_LOG", None)
    return None if log is None else log.records("build.")


def window_open_ns(records, window_s: float) -> float:
    """A bound from above on where the window opened, on the span clock:
    the first build made while the profiler recorded, else the start of the
    first build-free stretch of at least `window_s`, else after every
    build."""
    profiled = [r.start_ns for r in records if r.tags.get("profiled")]
    if profiled:
        return min(profiled)
    end = None
    for s, e in sorted((r.start_ns, r.end_ns) for r in records):
        if end is not None and s - end >= window_s * 1e9:
            return end
        end = e if end is None else max(end, e)
    return float("inf")


def setup_build_s(kind: str, window_s: float):
    """Seconds of the union of the `kind` build spans that closed before the
    window opened (nested jits overlap: the union, never the sum)."""
    recs = build_records()
    if recs is None:
        return None
    cut = window_open_ns(recs, window_s)
    total, _ = trace_reduce._union(
        [(r.start_ns, r.end_ns) for r in recs
         if r.name == kind and r.end_ns <= cut
         and not r.tags.get("profiled")])
    return total * 1e-9


def builds_in_window():
    """Backend builds (persistent-cache loads and compiles) the program made
    while the profiler recorded, that is inside the traced window."""
    recs = build_records()
    if recs is None:
        return None
    return sum(1 for r in recs
               if r.name == "build.compile" and r.tags.get("profiled"))
