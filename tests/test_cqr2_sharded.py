"""CholeskyQR2 with its rows over four devices, as the cacqr-tall deployment
runs it (`qr.factor(Grid.flat(4 devices), A, CacqrConfig(num_iter=2,
regime="1d", mode="pallas"))`, bf16), at m = 4·2048 and n = 1024: the
fused kernels' column split is then g = 8 and the plan "full", as at
2,097,152 × 1024.  The kernels run per shard in interpret mode.

Each build records its route in `spans.ROUTES` and under a ``qr.route``
span; the answers are held to a plain NumPy float64 CholeskyQR2 of the same
bf16 operand and to Householder QR with R's diagonal made positive.

Tolerances: the program rounds each Gram to bf16 before its Cholesky factor
(2^-9 relative per entry) and stores Q in bf16 (2^-9 per entry), so Q and R
land about 3e-3 from the float64 answer (measured 2.8e-3 and 3.2e-3 on
these operands).  6e-3 leaves twice that.  An operand rounded to float8
e4m3 (2^-4 per entry) lands at 2.5e-2 and 9e-3, outside it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import qr
from capital_tpu.obs import spans
from capital_tpu.ops import qr_fused
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust.config import RobustConfig

M, N = 4 * 2048, 1024
CFG = qr.CacqrConfig(num_iter=2, regime="1d", mode="pallas")
TOL = 6e-3
SEEDS = (0, 1)
# the row blocks the fused kernels are built with on a 2048-row shard
# (qr_fused.tall_bm): the Gram and the final scale as deep as the shard,
# scale_gram at 1024
BMS = {"bm": 2048, "bm_scale_gram": 1024}


@pytest.fixture(scope="module")
def grid4():
    return Grid.flat(jax.devices("cpu")[:4])


@pytest.fixture
def routes(monkeypatch):
    """A fresh route counter in place of the process's."""
    fresh = spans.RouteCounter()
    monkeypatch.setattr(spans, "ROUTES", fresh)
    return fresh


def _operand(seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(-1.0, 1.0, (M, N)), jnp.bfloat16)


def _cqr2_f64(A):
    """CholeskyQR2 in float64, nothing of the program."""
    A = np.asarray(A, np.float64)
    R1 = np.linalg.cholesky(A.T @ A).T
    Q1 = np.linalg.solve(R1.T, A.T).T
    R2 = np.linalg.cholesky(Q1.T @ Q1).T
    return np.linalg.solve(R2.T, Q1.T).T, R2 @ R1


def _householder(A):
    Q, R = np.linalg.qr(np.asarray(A, np.float64))
    s = np.sign(np.diag(R))
    return Q * s, R * s[:, None]


def _gap(x, ref):
    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def _factor(grid, A):
    A = jax.device_put(A, grid.rows_sharding())
    return jax.jit(lambda a: qr.factor(grid, a, CFG))(A)


@pytest.mark.parametrize("seed", SEEDS)
def test_cell_route_matches_float64(grid4, routes, seed):
    A = _operand(seed)
    Q, R = _factor(grid4, A)
    assert routes.snapshot() == {
        "fused_sharded/full": {"builds": 1, "rows": 2048, "g": 8, **BMS}}
    assert Q.sharding.is_equivalent_to(grid4.rows_sharding(), 2)
    for Qref, Rref in (_cqr2_f64(A), _householder(A)):
        assert _gap(Q, Qref) < TOL
        assert _gap(R, Rref) < TOL


def test_route_span_carries_the_tags(grid4, routes, monkeypatch):
    log = spans.SpanLog()
    monkeypatch.setattr(spans, "SPAN_LOG", log)
    _factor(grid4, _operand(0))
    (rec,) = log.records("qr.route")
    assert rec.tags == {"route": "fused_sharded/full", "rows": 2048, "g": 8,
                        **BMS}


def test_float8_operand_fails_the_tolerance(grid4):
    """The comparison above is tight enough to see the precision below
    bf16: the same factor of the operand rounded to float8 e4m3."""
    A = _operand(0)
    Q, R = _factor(grid4, A.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16))
    Qref, Rref = _cqr2_f64(A)
    assert max(_gap(Q, Qref), _gap(R, Rref)) > TOL


def test_forced_fallback_takes_the_unfused_sweeps(grid4, routes, monkeypatch):
    monkeypatch.setattr(qr_fused, "fused_plan", lambda *a, **k: None)
    A = _operand(1)
    Q, R = _factor(grid4, A)
    assert routes.snapshot() == {
        "sweeps_1d": {"builds": 1, "rows": 2048, "g": 2, "bm": None}}
    Qref, Rref = _cqr2_f64(A)
    assert _gap(Q, Qref) < TOL and _gap(R, Rref) < TOL


@pytest.mark.parametrize("case,route,tags", [
    ("flat4", "fused_sharded/full", {"rows": 2048, "g": 8, **BMS}),
    ("one", "fused/full", {"rows": 8192, "g": 8, "bm": 4096,
                           "bm_scale_gram": 1024}),
    ("flat4_robust", "sweeps_1d", {"rows": 2048, "g": 2, "bm": None}),
    ("flat4_xla", "sweeps_1d", {"rows": 2048, "g": 2, "bm": None}),
    ("one_wide", "panels", {"rows": 8192, "g": 8, "bm": None}),
    ("square_dist", "dist", {"rows": 2048, "g": 0, "bm": None}),
])
def test_route_table(case, route, tags, monkeypatch):
    """The route each kind of build takes, decided without tracing."""
    devs = jax.devices("cpu")
    grid = Grid.square(c=1, devices=devs[:4]) if case == "square_dist" else \
        Grid.flat(devs[:1] if case.startswith("one") else devs[:4])
    cfg = CFG
    if case == "flat4_xla":
        cfg = qr.CacqrConfig(num_iter=2, regime="1d", mode="xla")
    if case == "square_dist":
        cfg = qr.CacqrConfig(num_iter=2, regime="dist", mode="pallas")
    if case == "one_wide":
        # past every fused kernel's VMEM envelope (what v5e sees at n=4096
        # and more; interpret mode has no envelope of its own)
        monkeypatch.setattr(qr_fused, "fused_plan", lambda *a, **k: "panels")
    if case == "flat4_robust":
        monkeypatch.setattr(qr, "_ROBUST", [object()])
    n = 4096 if case == "one_wide" else N
    assert qr.route(grid, M, n, jnp.bfloat16, cfg, cfg.regime) == (route,
                                                                      tags)
