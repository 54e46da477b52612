"""The mxp cell: its files load by name, its three readers against hand
computations on a synthetic reduced trace, the plain reference's check and
flop count, and a traced run of a small copy of the cell on the CPU."""

import numpy as np
import pytest

import common
import mxp_work
import peaks
import trace_reduce as tr
from conftest import SMALL, run_cell

N = 32768  # configs/hplmxp-spd.json
V5E = peaks.PEAKS["TPU v5 lite"]
NEW = ("refine_share.mxp", "refine_sweeps.mxp", "residual_roofline.mxp")
APPENDED = ("device_idle.factor", "builds_in_window.factor",
            "xla_chol_sites.factor")
# the shared ``tiny`` fixture maps each cell to its small copy
SMALL.setdefault("mxp.n32768", "mxp.tiny")


class _Reading:
    def __init__(self, trace, counters, peak=V5E):
        self.trace, self.chips, self.peak = trace, 1, peak
        self.counters = counters


def _trace(**own):
    d = tr.Device(busy_s=9.9, own_s=dict(own))
    return tr.Reduced(window_s=10.0, devices=[d], idle_gaps=[])


MXP = _trace(**{"CI::trsm": 2.0, "CI::tmu": 2.0, "CI::inv": 1.0,
                "IR::residual": 2.5, "IR::correct": 1.2})
COUNTS = {"solves": 40, "sweeps": 220, "residuals": 260}


def _read(metric, reading):
    return common.Catalog().reader(metric).read(reading)


def test_the_cell_loads_by_name():
    cat = common.Catalog()
    w = cat.workload("mxp.n32768")
    cfg = cat.config(w["config"])
    assert (w["config"], w["traffic"], w["driver"], w["chips"]) == (
        "hplmxp-spd", "resident_system", "mxp_solve", 1)
    assert w["limits"] == {"hpl_resid": 16.0}
    assert (cfg["n"], cfg["dtype"], cfg["base_case_dim"]) == (
        N, "bfloat16", 512)
    spec = cat.spec()
    for m in spec["per_layer"]:
        if m["name"] in NEW + APPENDED:
            assert "mxp.n32768" in m["workloads"], m["name"]
    assert [m["name"] for m in cat.metrics_for("mxp.n32768",
                                               "end_to_end")] == [
        "setup_s", "factor_tflops"]


def test_flops_and_residual_bytes():
    cat = common.Catalog()
    cfg = cat.config("hplmxp-spd")
    assert cat.reference("hplmxp-spd").flops(cfg) == N**3 / 3 + 2 * N**2
    a = 2.0 * N * N
    assert mxp_work.residual_bytes(1, 7) == a + 7 * (a + 16 * N)


def test_readers_by_hand():
    r = _Reading(MXP, COUNTS)
    assert _read("refine_share.mxp", r) == pytest.approx(100 * 3.7 / 9.9)
    assert _read("refine_sweeps.mxp", r) == pytest.approx(5.5)
    want = 100 * mxp_work.residual_bytes(40, 260) / (2.5 * 819e9)
    assert _read("residual_roofline.mxp", r) == pytest.approx(want)
    assert want == pytest.approx(100 * (40 + 260) * 2.0 * N * N
                                 / (2.5 * 819e9), rel=1e-3)


def test_readers_read_none_without_their_inputs():
    bare = _trace(**{"CI::trsm": 2.0})
    assert _read("refine_share.mxp", _Reading(bare, COUNTS)) is None
    assert _read("residual_roofline.mxp", _Reading(bare, COUNTS)) is None
    assert _read("residual_roofline.mxp",
                 _Reading(MXP, COUNTS, peak=None)) is None
    assert _read("refine_sweeps.mxp", _Reading(MXP, {})) is None
    assert _read("refine_share.mxp", _Reading(None, COUNTS)) is None


def test_the_check_of_an_exact_and_a_rounded_solution():
    """The reference's hpl_resid is far below 16 for a float64 solve of the
    system the salts make, and far above it for that solve rounded to bf16
    (the order of the control's miss)."""
    import jax.numpy as jnp

    ref = common.Catalog().reference("hplmxp-spd")
    cfg = {"n": 256, "dtype": "bfloat16", "rhs_dtype": "float32",
           "ref_block": 64}
    A = np.concatenate([np.asarray(ref._rows(
        jnp.uint32(11), jnp.uint32(r), n=256, h=64,
        dtype=jnp.bfloat16)).astype(np.float64) for r in range(0, 256, 64)])
    b = np.asarray(ref._rhs(jnp.uint32(12), n=256, dtype=jnp.float32),
                   np.float64)
    x = np.linalg.solve(A, b)
    zero = np.zeros_like(x)
    assert ref.compare(cfg, 11, 12, x, zero)["hpl_resid"] < 1.0
    xb = np.asarray(jnp.asarray(x, jnp.bfloat16), np.float64)
    assert ref.compare(cfg, 11, 12, xb, zero)["hpl_resid"] > 1e6


def test_the_small_cell_runs_traced(tiny):
    line = run_cell(tiny, "mxp.tiny", trace=1)
    assert line["correct"] and line["failed"] == 0
    assert line["checks"]["hpl_resid"]["value"] < 16.0
    m = line["metrics"]
    assert 0 < m["refine_share.mxp"]["value"] < 100
    assert 1 <= m["refine_sweeps.mxp"]["value"] <= 8
    # the CPU has no row in peaks.py: no roofline there
    assert "residual_roofline.mxp" not in m
    assert m["builds_in_window.factor"]["value"] == 0


def test_the_small_cell_and_its_control(tiny):
    line = run_cell(tiny, "mxp.tiny", trace=0)
    assert line["correct"]
    assert set(line["metrics"]) == {"setup_s", "factor_tflops"}
    ctl = run_cell(tiny, "mxp.tiny", trace=0, control=True)
    assert not ctl["correct"]
    assert ctl["checks"]["hpl_resid"]["value"] > 1e6
