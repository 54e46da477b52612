"""Program spans (obs/spans.py): one recorder on the profiler's clock, the
build spans and counter JAX's own events feed, a stable name for every
Pallas kernel, and every op of the cholinv program under a phase.

The clock tests read a real CPU profiler trace through
``jax.profiler.ProfileData``: the trace keeps each host event as an offset
from its session's ``profile_start_time``, and that anchor plus the offset
must land within 1 ms of the span's own stamp.
"""

import ast
import glob
import os
import re
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import cholesky
from capital_tpu.obs import spans
from capital_tpu.ops import batched_small
from capital_tpu.parallel.topology import Grid
from capital_tpu.serve import ServeConfig, SolveEngine
from capital_tpu.utils import tracing

OPS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "capital_tpu", "ops")
TAGS = tuple(t.replace("::", ".") for t in tracing.PHASE_REGISTRY)


def _host_events(trace_dir):
    """{name: [(start_ns on the span clock, duration_ns)]} of the host
    events of the one xplane under `trace_dir`."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    anchor = spans.profile_anchor_ns(pd)
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (anchor + ev.start_ns, ev.duration_ns))
    return out


def _unique_fn():
    """A fresh function object with a unique name: no trace or compile
    cache can already hold it."""
    name = f"probe_{uuid.uuid4().hex[:8]}"

    def f(x):
        return jnp.sin(x) * 2.0 + 1.0

    f.__name__ = f.__qualname__ = name
    return f, name


# ---------------------------------------------------------------------------
# the recorder and its clock
# ---------------------------------------------------------------------------


def test_span_start_matches_its_trace_annotation(tmp_path):
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("probe.clock", kind="test") as sp:
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (rec,) = [r for r in spans.SPAN_LOG.records("probe.clock")
              if r.span_id == sp.span_id]
    assert rec.tags == {"kind": "test"}
    (start, dur), = _host_events(str(tmp_path))["probe.clock"]
    assert abs(rec.start_ns - start) < 1_000_000
    assert abs(rec.end_ns - (start + dur)) < 1_000_000


def test_span_nests_and_records_without_the_profiler():
    assert not spans.profiling()
    t0 = spans.now_ns()
    with spans.span("probe.outer") as outer:
        with spans.span("probe.inner") as inner:
            pass
    t1 = spans.now_ns()
    recs = {r.span_id: r for r in spans.SPAN_LOG.records("probe.")}
    assert recs[inner.span_id].parent == outer.span_id
    assert recs[outer.span_id].parent is None
    o, i = recs[outer.span_id], recs[inner.span_id]
    assert t0 <= o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns <= t1
    # the span clock is the wall clock
    assert abs(spans.now() - spans.now_ns() * 1e-9) < 0.01


def test_span_log_bounds_memory_and_counts_drops():
    log = spans.SpanLog(cap=3)
    for i in range(5):
        log.add(spans.SpanRecord(i, f"s{i}", i, i + 1))
    assert len(log) == 3 and log.total == 5 and log.dropped == 2
    assert [r.name for r in log.records()] == ["s2", "s3", "s4"]
    assert [r.name for r in log.records("s4")] == ["s4"]
    with pytest.raises(ValueError):
        spans.SpanLog(cap=0)


def test_request_chain_stamps_share_the_span_clock(tmp_path):
    """A served request's chain, its staging spans and the profiler's host
    events sit on one clock: the staging spans fall inside the chain's
    admit span, and their trace events within 1 ms of their stamps."""
    rng = np.random.default_rng(3)
    eng = SolveEngine(cfg=ServeConfig(max_batch=2))
    A = rng.standard_normal((8, 8)).astype(np.float32)
    A = A @ A.T + 8 * np.eye(8, dtype=np.float32)
    B = rng.standard_normal((8, 1)).astype(np.float32)
    eng.solve("posv", A, B)  # builds every program first
    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = spans.now()
        r = eng.solve("posv", A, B)
        t1 = spans.now()
    finally:
        jax.profiler.stop_trace()
    assert r.ok
    tr = r.trace
    assert t0 <= tr.t_enq <= tr.last_end <= t1
    admit = next(sp for sp in tr.spans if sp.name == "admit")
    staged = [s for s in spans.SPAN_LOG.records("SV::stage")
              if s.start_ns >= tr.t_enq * 1e9 - 1e6]
    assert len(staged) >= 2  # the operands' asarray and device_put
    for s in staged:
        assert admit.t_start * 1e9 - 1e6 <= s.start_ns
        assert s.end_ns <= admit.t_end * 1e9 + 1e6
    events = sorted(_host_events(str(tmp_path))["SV::stage"])
    for s, (start, _) in zip(sorted(staged, key=lambda s: s.start_ns),
                             events):
        assert abs(s.start_ns - start) < 1_000_000


def test_submit_staging_runs_under_its_scope(monkeypatch):
    """The eager staging of SolveEngine.submit — asarray, bucket padding,
    device_put — runs under registered scopes that are also program spans,
    so a profiler trace shows its ops under them."""
    from capital_tpu.serve import batching

    seen = {}
    real_pad = batching.pad_operands
    real_put = jax.device_put

    def pad(*a, **k):
        seen["pad"] = tracing.current_scope()
        return real_pad(*a, **k)

    def put(*a, **k):
        seen.setdefault("put", tracing.current_scope())
        return real_put(*a, **k)

    monkeypatch.setattr(batching, "pad_operands", pad)
    monkeypatch.setattr(jax, "device_put", put)
    eng = SolveEngine(cfg=ServeConfig(max_batch=4))
    before = spans.now_ns()
    t = eng.submit("posv", np.eye(8, dtype=np.float32),
                   np.ones((8, 1), np.float32))
    assert seen["pad"] is None  # pad_operands opens serve::pad itself
    assert seen["put"] == "SV::stage"
    names = [r.name for r in spans.SPAN_LOG.records()
             if r.start_ns >= before]
    assert names.count("SV::stage") == 2 and "serve::pad" in names
    eng.drain()
    assert t.result().ok


# ---------------------------------------------------------------------------
# build spans and the build counter
# ---------------------------------------------------------------------------


def test_fresh_jit_records_build_spans():
    f, name = _unique_fn()
    before = spans.BUILDS.snapshot()
    t0 = spans.now_ns()
    jax.jit(f)(jnp.ones(4)).block_until_ready()
    recs = [r for r in spans.SPAN_LOG.records("build.")
            if r.start_ns >= t0 - 1_000_000]
    kinds = {r.name for r in recs}
    assert {"build.trace", "build.lower", "build.compile"} <= kinds
    assert any(r.name == "build.trace" and r.tags.get("fun_name") == name
               for r in recs)
    for r in recs:
        assert r.end_ns >= r.start_ns
        if r.name == "build.compile":
            assert r.tags["cache"] in ("load", "compile")
    after = spans.BUILDS.snapshot()
    assert sum(after.values()) > sum(before.values())


@pytest.fixture
def tmp_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_build_counter_tells_a_cache_load_from_a_compile(tmp_cache):
    f, _ = _unique_fn()
    x = jnp.ones(16)
    s0 = spans.BUILDS.snapshot()
    jax.jit(f).lower(x).compile()
    s1 = spans.BUILDS.snapshot()
    assert s1["compiles"] == s0["compiles"] + 1
    assert s1["cache_loads"] == s0["cache_loads"]
    assert os.listdir(tmp_cache)  # written to the persistent cache
    jax.clear_caches()
    t0 = spans.now_ns()
    jax.jit(f).lower(x).compile()
    s2 = spans.BUILDS.snapshot()
    assert s2["cache_loads"] == s1["cache_loads"] + 1
    assert s2["compiles"] == s1["compiles"]
    (rec,) = [r for r in spans.SPAN_LOG.records("build.compile")
              if r.start_ns >= t0]
    assert rec.tags["cache"] == "load"


def test_union_of_nested_build_spans_is_not_their_sum():
    """A jit traced inside another one opens its build.trace span inside
    the outer span: set-up time is their union."""
    inner, inner_name = _unique_fn()
    outer_inner = jax.jit(inner)

    def outer(x):
        return outer_inner(x) + outer_inner(2 * x)

    outer.__name__ = f"outer_{inner_name}"
    t0 = spans.now_ns()
    jax.jit(outer)(jnp.ones(4)).block_until_ready()
    recs = {r.tags.get("fun_name"): r
            for r in spans.SPAN_LOG.records("build.trace")
            if r.start_ns >= t0 - 1_000_000}
    o, i = recs[outer.__name__], recs[inner_name]
    assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
    ivs = sorted((r.start_ns, r.end_ns) for r in (o, i))
    union = max(e for _, e in ivs) - ivs[0][0]
    assert union == o.end_ns - o.start_ns
    assert union < (o.end_ns - o.start_ns) + (i.end_ns - i.start_ns)


# ---------------------------------------------------------------------------
# kernel names and phase coverage
# ---------------------------------------------------------------------------


def test_every_pallas_call_site_passes_a_name():
    sites = {}
    for path in sorted(glob.glob(os.path.join(OPS, "*.py"))):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                kw = {k.arg: k.value for k in node.keywords}
                sites[(os.path.basename(path), node.lineno)] = kw
    assert len(sites) == 15
    for site, kw in sites.items():
        assert "name" in kw, site
        call = kw["name"]
        assert (isinstance(call, ast.Call)
                and ast.unparse(call.func) == "tracing.kernel_name"), site


def _pallas_names(jaxpr, out):
    """The `name` of every pallas_call in a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for p in eqn.params.values():
            for j in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(j, jax.extend.core.ClosedJaxpr):
                    _pallas_names(j.jaxpr, out)
                elif isinstance(j, jax.extend.core.Jaxpr):
                    _pallas_names(j, out)
    return out


_KERNEL = re.compile(r"^(?P<phase>.+)\.(?P<kernel>[a-z][a-z0-9_]*)$")


def test_kernel_names_are_registered_phase_dot_kernel():
    g = Grid.square(c=1, devices=jax.devices()[:1])
    cfg = cholesky.CholinvConfig(base_case_dim=128, mode="pallas")
    A = jnp.eye(512, dtype=jnp.float32)
    names = _pallas_names(jax.make_jaxpr(
        lambda a: cholesky.factor(g, a, cfg))(A).jaxpr, [])
    B = jnp.ones((2, 16, 16), jnp.float32)
    names += _pallas_names(jax.make_jaxpr(
        lambda a, b: batched_small.posv(a, b[..., :2]))(
            B, B).jaxpr, [])
    assert names
    for name in names:
        m = _KERNEL.match(name)
        assert m and m["phase"] in TAGS, name
    assert {"CI.inv.trmm_left", "CI.tmu.syrk", "CI.trsm.trmm_left",
            "CI.factor_diag.transpose_pair",
            "SV.fused_posv.posv"} <= set(names)
    with pytest.raises(ValueError):
        tracing.kernel_name("x", "NOT::a_phase")
    with tracing.scope("CI::inv"):
        assert tracing.kernel_name("trmm_left", "CI::tmu") == \
            "CI.inv.trmm_left"
    assert tracing.kernel_name("syrk", "CI::tmu") == "CI.tmu.syrk"


@pytest.mark.parametrize("n,mode,robust", [(512, "pallas", False),
                                           (500, "xla", False),
                                           (500, "pallas", True)])
def test_small_cholinv_compiled_hlo_is_fully_phased(n, mode, robust):
    """Every op the program puts in the compiled cholinv carries a
    registered phase in its op_name: padding, crop and the breakdown scan
    (CI::io) included.  What XLA makes itself carries no op_name at all:
    copies, constants and broadcasts of constants.  Fused and reducer
    computations are not ops of their own: the fusion instruction is."""
    from capital_tpu.robust.config import RobustConfig

    g = Grid.square(c=1, devices=jax.devices()[:1])
    cfg = cholesky.CholinvConfig(base_case_dim=128, mode=mode,
                                 robust=RobustConfig() if robust else None)
    A = jax.ShapeDtypeStruct((n, n), jnp.float32)
    txt = jax.jit(lambda a: cholesky.factor(g, a, cfg)).lower(
        A).compile().as_text()
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", txt))
    comp, untagged = None, []
    for line in txt.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = head[1]
            continue
        m = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\(", line)
        if not m or comp in inner or m[2] in (
                "parameter", "constant", "get-tuple-element", "tuple",
                "bitcast"):
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        if op_name is None:
            assert m[2] in ("copy", "fusion", "broadcast"), line[:200]
            continue
        if op_name[1] != "a" and not any(t in op_name[1] for t in TAGS):
            untagged.append(line[:200])
    assert untagged == []
