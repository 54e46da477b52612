"""The cholinv mesh cell: its files load by name, its two readers against
hand computations on synthetic readings (None where there is nothing to
read), the plain reference's stripes against a NumPy Cholesky, and a small
copy of the cell on four CPU devices, traced and with its control."""

import numpy as np
import pytest

import common
import peaks
import trace_reduce as tr
from conftest import SMALL, run_cell

N = 65536  # configs/cholinv-mesh.json
CELL = "cholinv.n65536.x4"
V5E = peaks.PEAKS["TPU v5 lite"]
NEW = ("collective_share.mesh", "dense_shard_sites.mesh")
APPENDED = ("factor_tflops", "device_idle.factor", "factor_roofline",
            "builds_in_window.factor", "xla_chol_sites.factor",
            "base_case_share.cholinv", "build_trace_s.factor",
            "build_lower_s.factor", "build_compile_s.factor",
            "kernel_builds.factor")
# the shared ``tiny`` fixture maps each cell to its small copy
SMALL.setdefault(CELL, "cholinv.mesh-tiny.x4")
SMALL.setdefault("mxp.n32768", "mxp.tiny")


class _Reading:
    def __init__(self, trace, counters=None):
        self.trace, self.chips, self.peak = trace, 4, V5E
        self.counters = counters or {}


def _trace(**own):
    devs = [tr.Device(busy_s=8.0, own_s={b: s[i] for b, s in own.items()})
            for i in range(4)]
    return tr.Reduced(window_s=10.0, devices=devs, idle_gaps=[])


MESH = _trace(**{"CI::trsm": (2.0, 2.0, 2.0, 2.0),
                 "CI::tmu": (2.0, 2.1, 1.9, 2.0),
                 "collective": (0.9, 1.1, 1.0, 1.0)})


def _read(metric, reading):
    return common.Catalog().reader(metric).read(reading)


def test_the_cell_loads_by_name():
    cat = common.Catalog()
    w = cat.workload(CELL)
    cfg = cat.config(w["config"])
    assert (w["config"], w["traffic"], w["driver"], w["chips"]) == (
        "cholinv-mesh", "fresh_operand", "mesh_factor", 4)
    assert set(w["limits"]) == {"R_gap", "Rinv_gap"}
    assert (cfg["n"], cfg["dtype"], cfg["grid"], cfg["precision"]) == (
        N, "bfloat16", "2x2x1", None)
    assert set(cfg["reduced"]) == {"ranks"}
    spec = cat.spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in NEW + APPENDED:
            assert CELL in m["workloads"], m["name"]
    assert cat.reference("cholinv-mesh").flops(cfg) == 2.0 * N**3 / 3


def test_the_mesh_base_case_is_the_programs_rule():
    from capital_tpu.models import cholesky

    cfg = common.Catalog().config("cholinv-mesh")
    assert cfg["base_case_dim"] == cholesky.pick_base_case(N)


def test_collective_share_by_hand():
    assert _read("collective_share.mesh", _Reading(MESH)) == pytest.approx(
        100 * 1.0 / 8.0)
    bare = _trace(**{"CI::trsm": (2.0, 2.0, 2.0, 2.0)})
    assert _read("collective_share.mesh", _Reading(bare)) is None
    assert _read("collective_share.mesh", _Reading(None)) is None


@pytest.fixture
def routes(monkeypatch):
    from capital_tpu.obs import spans

    fresh = spans.RouteCounter()
    monkeypatch.setattr(spans, "SUMMA_ROUTES", fresh)
    return fresh


def test_dense_shard_sites_by_hand(routes):
    r = _Reading(MESH)
    assert _read("dense_shard_sites.mesh", r) is None  # nothing traced
    tags = dict(M=1024, K=1024, N=1024, dx=2, dy=2, c=1)
    routes.take("trmm/explicit_block/live_tiles", **tags)
    routes.take("syrk/xla/gspmd", **tags)
    assert _read("dense_shard_sites.mesh", r) == 0
    routes.take("syrk/explicit_block/dense", **tags)
    routes.take("syrk/explicit_block/dense", **tags)
    routes.take("trmm/explicit_persistent/dense", **tags)
    assert _read("dense_shard_sites.mesh", r) == 3


def test_dense_shard_sites_without_the_counter(monkeypatch):
    """An older program keeps no such counter: None, and nothing raises."""
    from capital_tpu.obs import spans

    monkeypatch.delattr(spans, "SUMMA_ROUTES", raising=False)
    assert _read("dense_shard_sites.mesh", _Reading(MESH)) is None


def test_the_reference_against_numpy():
    """The striped left-looking factor and its back substitution agree with
    a NumPy float64 Cholesky of the generator's operand; the float8 control
    does not."""
    import jax.numpy as jnp

    ref = common.Catalog().reference("cholinv-mesh")
    gen = common.generator_module()
    n, b = 512, 64
    cfg = {"n": n, "ref_block": b, "ref_cols": 16, "grid": "2x2x1"}
    A = np.asarray(gen.spd_hash(n, jnp.float32, jnp.uint32(7)), np.float64)
    R = np.linalg.cholesky(A).T
    cols = ref.sample_cols(n, 11, 16)
    Ri = np.linalg.inv(R)[:, cols]
    st = ref._stripes(cfg, 7, jnp.float32, None)
    X = ref._inverse_cols(st, cols, n, b, None)
    w = n // 4
    Rs = np.zeros((n, n))
    for s, L in enumerate(st):
        Rs[s * w:(s + 1) * w, s * w:] = np.asarray(L, np.float64).T
    assert common.relgap(Rs, R) < 1e-6
    assert common.relgap(X, Ri) < 1e-6
    outs = [jnp.asarray(R, jnp.bfloat16), jnp.asarray(Ri, jnp.float32)]
    gaps = ref.compare(cfg, 7, outs, 11)
    assert gaps["R_gap"] < 4e-3 and gaps["Rinv_gap"] < 4e-3
    ctl = ref.compare(cfg, 7, ref.control(cfg, 7, 11), 11)
    assert ctl["R_gap"] > 1e-2 or ctl["Rinv_gap"] > 1e-2


def test_the_small_cell_runs_traced(tiny):
    line = run_cell(tiny, "cholinv.mesh-tiny.x4", trace=1)
    assert line["correct"] and line["failed"] == 0
    assert line["device"]["count"] == 4
    m = line["metrics"]
    assert m["dense_shard_sites.mesh"]["value"] >= 0
    assert m["xla_chol_sites.factor"]["value"] >= 1
    assert m["builds_in_window.factor"]["value"] == 0


def test_the_small_cell_and_its_control(tiny):
    line = run_cell(tiny, "cholinv.mesh-tiny.x4", trace=0)
    assert line["correct"]
    assert set(line["metrics"]) == {"setup_s", "factor_tflops"}
    ctl = run_cell(tiny, "cholinv.mesh-tiny.x4", trace=0, control=True)
    assert not ctl["correct"]
