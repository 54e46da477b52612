"""Own time per Pallas kernel in a traced window, keyed by the kernel's stable
name.

The program names every ``pallas_call`` ``<phase>.<kernel>``
(capital_tpu/utils/tracing.kernel_name, e.g. ``CI.inv.trmm_left``); the
compiled HLO names the custom call ``<phase>.<kernel>.<k>`` and the device
trace names the op after it.  So a kernel's name is its op's own name
without the numeric suffix, wherever that stem is a registered phase tag
followed by a kernel part.  Ops without one (XLA fusions, copies, library
custom calls) are left out.  Own times and the window are trace_reduce's.
"""

from __future__ import annotations

import re

import trace_reduce as tr

_SUFFIX = re.compile(r"\.\d+$")


def kernel_of(name: str, tags) -> str | None:
    """The stable kernel name of a trace op, or None."""
    stem = _SUFFIX.sub("", tr.own_name(name))
    for t in tags:
        if stem.startswith(t + ".") and len(stem) > len(t) + 1:
            return stem
    return None


def kernel_seconds(trace_dir: str, tags, select=tr.tpu_ops_line,
                   keep=None) -> dict:
    """{kernel name: own seconds in the window}, the mean over the devices,
    for every xplane under `trace_dir` (`tags`, `select` and `keep` as in
    trace_reduce.reduce)."""
    window = None
    raw: dict[int, list] = {}
    for pd in tr.load(trace_dir):
        for plane in pd.planes:
            for line in plane.lines:
                dev = select(plane, line)
                for ev in line.events:
                    if dev is not None:
                        if keep is None or keep(tr._stats(ev)):
                            raw.setdefault(dev, []).append(
                                (ev.start_ns, ev.duration_ns, ev.name))
                    elif (window is None and ev.name == tr.WINDOW
                          and plane.name.startswith("/host")):
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        raise RuntimeError("trace has no host span named 'window'")
    w0, w1 = window
    out: dict[str, float] = {}
    for evs in raw.values():
        clipped = [(max(s, w0), min(s + d, w1) - max(s, w0), nm)
                   for s, d, nm in evs if s < w1 and s + d > w0]
        for name, own in tr.own_times(clipped):
            k = kernel_of(name, tags)
            if k is not None:
                out[k] = out.get(k, 0.0) + own * 1e-9 / len(raw)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
