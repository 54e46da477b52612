"""What the harness finds by name, and the helpers every driver shares.

Everything that belongs to one configuration, traffic mix, driver kind or
per-layer metric sits in a file of its own and is found by its name:

* ``configs/<config>.json``: the deployment's sizes; ``configs/<config>.py``:
  its plain reference (it imports nothing of the program);
* ``traffic/<traffic>.json``: a traffic mix's parameters, read by
  ``traffic_gen.py``;
* ``workloads/<cell>.json``: config, traffic, driver kind, chips, why, and
  the limits of the numbers that decide ``correct``;
* ``drivers/<kind>.py``: one kind of measured window (``run(ctx)``);
* ``metrics/<metric>.py``: one per-layer metric (``read(reading)``).

A `Catalog` looks in its roots in order, so a test can add files from a
temporary directory without touching those here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: fixed paths inside the checkout (benchmark/.gitignore lists them)
CACHE_DIR = os.path.join(HERE, ".cache")
JAX_CACHE = os.path.join(CACHE_DIR, "jax")
TRACE_DIR = os.path.join(CACHE_DIR, "trace")

#: host spans the benchmark opens around its calls into the program
SPANS = ("dispatch", "block", "submit", "pump", "drain")


def _load_module(path: str, tag: str):
    name = f"_bench_{tag}_{os.path.basename(path)[:-3].replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


class Catalog:
    def __init__(self, roots=(), spec_path: str | None = None):
        self.roots = tuple(roots) + (HERE,)
        self.spec_path = spec_path or os.path.join(ROOT, "BENCHMARK.json")
        self._mods: dict = {}

    def _find(self, sub: str, name: str, ext: str) -> str:
        for r in self.roots:
            p = os.path.join(r, sub, name + ext)
            if os.path.isfile(p):
                return p
        raise FileNotFoundError(f"no {sub}/{name}{ext} under {self.roots}")

    def _json(self, sub: str, name: str) -> dict:
        with open(self._find(sub, name, ".json")) as f:
            return json.load(f)

    def _mod(self, sub: str, name: str):
        key = (sub, name)
        if key not in self._mods:
            self._mods[key] = _load_module(self._find(sub, name, ".py"), sub)
        return self._mods[key]

    def workload(self, name: str) -> dict:
        return self._json("workloads", name)

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def reference(self, config: str):
        """configs/<name>.py, where <name> is the config's `reference` key
        (a test's small copy of a configuration names the real one) or the
        config's own name."""
        return self._mod("configs", self.config(config).get("reference",
                                                            config))

    def driver(self, kind: str):
        return self._mod("drivers", kind)

    def reader(self, metric: str):
        return self._mod("metrics", metric)

    def spec(self) -> dict:
        with open(self.spec_path) as f:
            return json.load(f)

    def metrics_for(self, cell: str, section: str) -> list:
        """The `section` metrics ('end_to_end' or 'per_layer') that this
        cell reports."""
        return [m for m in self.spec()[section]
                if cell in m.get("workloads", (cell,))]


def generator_module():
    """traffic_gen.py, the one general generator (imported by drivers and
    references alike)."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    import traffic_gen

    return traffic_gen


def mix(seed: int, *words: int) -> int:
    """A 32-bit value from a seed of any size and some small integers
    (splitmix64 finaliser): different seeds give unrelated streams."""
    x = (int(seed) * 0x9E3779B97F4A7C15) & (2**64 - 1)
    for w in words:
        x = (x ^ (int(w) + 0x632BE59BD9B4E019)) & (2**64 - 1)
        x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & (2**64 - 1)
        x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & (2**64 - 1)
        x ^= x >> 31
    return x & 0xFFFFFFFF


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (copied from capital_tpu/bench/harness.py
    `percentiles`)."""
    import math

    s = sorted(values)
    if not s:
        raise ValueError("percentile of nothing")
    return s[max(1, math.ceil(q / 100.0 * len(s))) - 1]


def relgap(x, ref) -> float:
    """‖x − ref‖ / ‖ref‖ in float64."""
    import numpy as np

    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    den = float(np.linalg.norm(ref))
    return float(np.linalg.norm(x - ref)) / den if den else float("inf")


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""

    setup_s: float
    attempted: int
    failed: int
    e2e: dict  # end-to-end metric name -> value
    counters: dict  # per-layer inputs the window counted
    checks: dict  # compared number -> (value, limit)
    memory_peak_bytes: int

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v == v and v <= lim for v, lim in self.checks.values())


@dataclasses.dataclass
class Ctx:
    """What the harness hands a driver."""

    cell: str
    workload: dict
    config: dict
    traffic: dict
    reference: object
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_process: float  # perf_counter() at the start of the process
    trace_dir: str

    @contextlib.contextmanager
    def window(self):
        """The measured window: the profiler runs around it in a traced
        run, and a host span named 'window' marks it on the trace's clock."""
        import jax

        if not self.trace:
            yield
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def span(self, name: str):
        """A host span around one call into the program (traced run only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class Builds:
    """Counts, through jax.monitoring, the programs the process builds:
    each compile request (a persistent-cache hit or a compile) and each
    backend compile.  A driver reads it around its window, where both
    should stay 0."""

    _counts = {"requests": 0, "compiles": 0}
    _on = False

    @classmethod
    def start(cls) -> None:
        if cls._on:
            return
        from jax import monitoring

        def event(name, **kw):
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                cls._counts["requests"] += 1

        def duration(name, secs, **kw):
            if name == "/jax/core/compile/backend_compile_duration":
                cls._counts["compiles"] += 1

        monitoring.register_event_listener(event)
        monitoring.register_event_duration_secs_listener(duration)
        cls._on = True

    @classmethod
    def now(cls) -> dict:
        return dict(cls._counts)

    @classmethod
    def since(cls, before: dict) -> dict:
        return {k: cls._counts[k] - before[k] for k in before}


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, AttributeError):
            pass
    return max(peaks) if peaks else 0


def elapsed(t0: float) -> float:
    return time.perf_counter() - t0
