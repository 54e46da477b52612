"""trace_reduce and every per-layer reader on a small trace recorded on the
CPU (tests/data/trace, made by `record`; run this file as a script to make
it again)."""

import glob
import os
import sys

import pytest

import common
import peaks
import trace_reduce as tr
from conftest import DATA

TRACE = os.path.join(DATA, "trace")
READERS = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(common.HERE, "metrics", "*.py")))


def record(out=TRACE):
    """Two jitted programs, one with an all-reduce over four devices, run
    three times inside a 'window' span with dispatch/block spans between."""
    import shutil

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    x = jax.device_put(jnp.ones((512, 256)), NamedSharding(mesh, P("x")))
    gram = jax.jit(lambda a: a.T @ a)  # all-reduce of the partial Grams
    work = jax.jit(lambda a: jnp.tanh(a @ a.T).sum())
    gram(x).block_until_ready()
    work(x).block_until_ready()
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("dispatch"):
                g, w = gram(x), work(x)
            with jax.profiler.TraceAnnotation("block"):
                g.block_until_ready()
                w.block_until_ready()
    jax.profiler.stop_trace()
    for p in glob.glob(os.path.join(out, "**", "*.json.gz"), recursive=True):
        os.remove(p)  # the reduction reads only the xplane


@pytest.fixture(scope="module")
def red():
    return tr.reduce(TRACE, spans=common.SPANS, select=tr.cpu_ops_line,
                     keep=tr.cpu_keep)


def test_window_busy_and_gaps(red):
    assert 0 < red.busy_s <= red.window_s
    assert 0 <= red.idle_share() < 1
    assert red.idle_gaps and all(s > 0 for _, s in red.idle_gaps)
    assert {n for n, _ in red.idle_gaps} <= set(common.SPANS) | {"host_other"}
    total_gaps = sum(e - s for s, e in red.devices[0].gaps) * 1e-9
    assert total_gaps == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
    assert red.bucket_s("collective") > 0  # the Gram's all-reduce


def test_own_times_nesting():
    evs = [(0, 100, "while"), (10, 20, "a"), (40, 30, "b"), (200, 5, "c")]
    own = dict(tr.own_times(evs))
    assert own == {"while": 50, "a": 20, "b": 30, "c": 5}


def test_bucket_longest_tag_wins():
    tags = ("CI.trsm", "CI.tmu", "CI.factor_diag", "CQR.gram")
    assert tr.bucket("%CI.tmu.90 = bf16[8] custom-call(%CI.trsm.2)",
                     tags) == "CI::tmu"
    assert tr.bucket("%fusion.3 = f32[4] fusion(%CI.trsm.2)", tags) == "fusion"
    hlo = ('%custom-call.7 = f32[128,128]{1,0} custom-call(f32[128,128]{1,0} '
           '%p), custom_call_target="Cholesky", metadata={op_name="jit(step)'
           '/CI.factor_diag/CI.trsm_no/cholesky" source_file="x.py"}')
    phases = tr.hlo_phase_map(hlo, tags)
    assert phases == {"custom-call.7": "CI::factor_diag"}
    assert tr.bucket("%custom-call.7 = f32[128,128] custom-call(%a)", tags,
                     phases) == "CI::factor_diag"
    assert tr.bucket("%all-reduce.1 = f32[8] all-reduce(%a)", tags) == \
        "collective"
    assert tr.bucket("%copy.4 = f32[8] copy(%all-reduce.1)", tags) == "copy"


class _Reading:
    def __init__(self, trace, counters, chips=1):
        self.trace, self.counters, self.chips = trace, counters, chips
        self.peak = peaks.PEAKS["TPU v5 lite"]


@pytest.mark.parametrize("metric", READERS)
def test_reader(metric, red):
    counters = {"window_flops": 3 * 2 * 512 * 256 * 256.0, "factors": 3,
                "occupancies": [0.5, 1.0, 0.25],
                "queue_waits_s": [0.001, 0.003, 0.002]}
    v = common.Catalog().reader(metric).read(_Reading(red, counters))
    if metric in ("base_case_share.cholinv",):
        assert v is None  # no CI scope on the CPU trace: nothing to read
    else:
        assert v is not None and v >= 0
    empty = common.Catalog().reader(metric).read(_Reading(red, {}))
    if metric in ("batch_fill.serve", "queue_wait_ms.serve",
                  "factor_roofline"):
        assert empty is None


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.exit(record())
