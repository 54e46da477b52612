"""Reduce a JAX profiler trace to busy time, phase buckets and idle gaps.

The trace is read with ``jax.profiler.ProfileData`` (no TensorFlow).  Device
operations come from the "XLA Ops" line of each ``/device:TPU:<i>`` plane.
The measured window is the benchmark's own host ``TraceAnnotation`` named
``window``; every device interval is clipped to it.

* busy: the union of the op intervals inside the window, per device.
* own time: an op's duration minus the ops it directly contains (the XLA Ops
  line is hierarchical: a ``while`` spans its body).  The sweep is copied
  from capital_tpu/bench/trace.py ``_own_times``.
* buckets: a collective's own time goes to ``collective``; any other op's
  to the phase of its name in the program's HLO (``hlo_phase_map``: the
  longest tag in its op_name), else to the longest phase tag (``CI.tmu``...)
  its own name mentions, else to a kind (``copy``, ``fusion``,
  ``custom-call``, ``other``).
  The longest-tag-wins rule is copied from capital_tpu/bench/trace.py
  ``_bucket`` and ``hlo_phase_map``.
* idle gaps: the stretches of the window in which device 0 ran nothing; the
  longest ten are named by the benchmark's host span that overlaps them
  most.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "window"
TOP = 10  # idle gaps named
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reduce_scatter|psum")
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Device:
    busy_s: float = 0.0
    own_s: dict = dataclasses.field(default_factory=dict)  # bucket -> s
    gaps: list = dataclasses.field(default_factory=list)  # (start, end) ns


@dataclasses.dataclass
class Reduced:
    window_s: float
    devices: list  # [Device], in device order
    idle_gaps: list  # [(name, seconds)], the TOP longest of device 0

    @property
    def busy_s(self) -> float:
        """Mean over the devices of the busy seconds in the window."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def bucket_s(self, *names: str) -> float:
        """Mean over the devices of the own seconds in these buckets."""
        return sum(d.own_s.get(b, 0.0) for d in self.devices
                   for b in names) / len(self.devices)

    def top_ops(self, k: int = 10) -> list:
        tot: dict[str, float] = {}
        for d in self.devices:
            for b, s in d.own_s.items():
                tot[b] = tot.get(b, 0.0) + s / len(self.devices)
        return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def tpu_ops_line(plane, line):
    """Default selector: (device index) for a TPU plane's XLA Ops line."""
    m = _TPU_PLANE.match(plane.name)
    if m and line.name == "XLA Ops":
        return int(m.group(1))
    return None


def cpu_ops_line(plane, line):
    """Selector for the CPU backend, which tests use: its XLA client
    threads run the ops (keep only events with an `hlo_op` stat)."""
    if plane.name == "/host:CPU" and line.name.startswith("tf_XLA"):
        return 0
    return None


def cpu_keep(stats: dict) -> bool:
    return "hlo_op" in stats


def load(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return [ProfileData.from_file(p) for p in paths]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 — a stat the binding cannot convert
        return {}


def own_times(events):
    """[(event, own_ns)] for (start_ns, dur_ns, event) triples."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    stack: list = []  # [end, event, dur, child_sum]

    def close():
        fin = stack.pop()
        if stack:
            stack[-1][3] += fin[2]
        out.append((fin[1], fin[2] - fin[3]))

    for start, dur, ev in evs:
        while stack and stack[-1][0] <= start:
            close()
        while stack and start + dur > stack[-1][0]:
            close()  # overlapping, not nested: close every outlasted one
        stack.append([start + dur, ev, dur, 0.0])
    while stack:
        close()
    return out


#: one optimized-HLO instruction with its op_name metadata (copied from
#: capital_tpu/bench/trace.py `_HLO_OP_RE`)
_HLO_OP = re.compile(
    r"%?([A-Za-z0-9_.\-]+)\s*=\s*[^\n]*metadata=\{[^}\n]*op_name=\"([^\"]*)\"")


def _longest(hay: str, tags):
    best = None
    for t in tags:
        if t in hay and (best is None or len(t) > len(best)):
            best = t
    return best


def hlo_phase_map(hlo_text: str, tags) -> dict:
    """{instruction name: phase tag} from a compiled program's HLO text:
    the longest tag in each instruction's op_name (its named-scope path).
    Custom calls such as the base case's potrf keep their scope there and
    nowhere in the trace."""
    out = {}
    for m in _HLO_OP.finditer(hlo_text):
        best = _longest(m.group(2), tags)
        if best is not None:
            out[m.group(1)] = best.replace(".", "::")
    return out


def own_name(name: str) -> str:
    """'%custom-call.770 = f32[...] custom-call(...)' -> 'custom-call.770'
    (a TPU trace names an op by its whole HLO line; only the part before
    ' = ' is the op's own)."""
    return name.split(" = ")[0].lstrip("%").strip()


def bucket(name: str, tags, phases=None) -> str:
    own = own_name(name)
    if COLLECTIVE.search(own.lower()):
        return "collective"  # whatever scope it was issued under
    if phases and own in phases:
        return phases[own]
    best = _longest(own, tags)
    if best is not None:
        return best.replace(".", "::")
    for kind in ("copy", "fusion", "custom-call"):
        if kind in own:
            return kind
    return "other"


def _union(intervals):
    total, cur_s, cur_e, merged = 0, None, None, []
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                merged.append((cur_s, cur_e))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        merged.append((cur_s, cur_e))
    for s, e in merged:
        total += e - s
    return total, merged


def reduce(trace_dir: str, tags=(), spans=(), select=tpu_ops_line,
           keep=None, phases=None) -> Reduced:
    """Reduce every xplane under `trace_dir`.  `tags` are the phase tags in
    named-scope form; `spans` the host span names that may name an idle gap;
    `select(plane, line)` returns a device index for a line of device ops;
    `keep(stats)` may drop bookkeeping events of such a line; `phases`
    maps op names to phase tags (hlo_phase_map)."""
    window = None
    host: list = []  # (start, end, name)
    raw: dict[int, list] = {}
    for pd in load(trace_dir):
        for plane in pd.planes:
            for line in plane.lines:
                dev = select(plane, line)
                for ev in line.events:
                    start, dur = ev.start_ns, ev.duration_ns
                    if dev is not None:
                        if keep is None or keep(_stats(ev)):
                            raw.setdefault(dev, []).append(
                                (start, dur, ev.name))
                    elif plane.name.startswith("/host"):
                        if ev.name == WINDOW and window is None:
                            window = (start, start + dur)
                        elif ev.name in spans:
                            host.append((start, start + dur, ev.name))
    if window is None:
        raise RuntimeError("trace has no host span named 'window'")
    if not raw:
        raise RuntimeError("trace has no device operations")
    w0, w1 = window
    devices = []
    for dev in sorted(raw):
        d = Device()
        clipped = [(max(s, w0), min(s + du, w1), ev) for s, du, ev in raw[dev]
                   if s < w1 and s + du > w0]
        busy, merged = _union([(s, e) for s, e, _ in clipped])
        d.busy_s = busy * 1e-9
        for name, own in own_times([(s, e - s, nm) for s, e, nm in clipped]):
            b = bucket(name, tags, phases)
            d.own_s[b] = d.own_s.get(b, 0.0) + own * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        d.gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                  if edges[i + 1] > edges[i]]
        devices.append(d)
    gaps = []
    longest = sorted(devices[0].gaps, key=lambda g: g[0] - g[1])[:TOP]
    for g0, g1 in longest:
        best, name = 0, "host_other"
        for s, e, nm in host:
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                best, name = ov, nm
        gaps.append((name, (g1 - g0) * 1e-9))
    gaps.sort(key=lambda x: -x[1])
    return Reduced(window_s=(w1 - w0) * 1e-9, devices=devices,
                   idle_gaps=gaps)
