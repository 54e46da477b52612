"""The one-kernel factor and inverse of an upper-valid panel
(ops/pallas_tpu.potrf_trtri_upper, interpret mode on the CPU rig), and the
shape rule by which ops/lapack.potrf_trtri_upper and cholinv's leaf choose
it over XLA's Cholesky and triangular solve (lapack.pallas_chol_fits).

The kernel's operands carry NaN in their lower half: it must never read it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import cholesky
from capital_tpu.obs import spans
from capital_tpu.ops import lapack, pallas_tpu
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust import detect
from capital_tpu.utils import residual


class _Chip:
    """A described device, enough for pallas_tpu.device_scope."""

    platform, device_kind = "tpu", "TPU v5 lite"


def _spd(n: int, seed: int = 0) -> np.ndarray:
    G = np.random.default_rng(seed).standard_normal((4 * n, n))
    return (G.T @ G / (4 * n)).astype(np.float32)


def _poisoned(A: np.ndarray) -> np.ndarray:
    """A's upper triangle over a NaN lower half."""
    return np.where(np.triu(np.ones(A.shape, bool)), A, np.nan).astype(A.dtype)


def _rel(x, y) -> float:
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    return float(np.linalg.norm(x - y) / np.linalg.norm(y))


@pytest.fixture
def routes(monkeypatch):
    fresh = spans.RouteCounter()
    monkeypatch.setattr(spans, "CHOL_ROUTES", fresh)
    return fresh


@pytest.mark.parametrize("n", [128, 384, 1024])
def test_matches_the_xla_pair(n):
    A = _spd(n, seed=n)
    R, Rinv = pallas_tpu.potrf_trtri_upper(jnp.asarray(_poisoned(A)))
    Rx, Rinvx = lapack.potrf_trtri(jnp.asarray(A), uplo="U")
    assert R.dtype == Rinv.dtype == jnp.float32
    R, Rinv = np.asarray(R), np.asarray(Rinv)
    assert np.all(np.tril(R, -1) == 0) and np.all(np.tril(Rinv, -1) == 0)
    assert _rel(R, Rx) < 2e-6 and _rel(Rinv, Rinvx) < 2e-6
    R64 = R.astype(np.float64)
    assert _rel(R64.T @ R64, A) < 1e-6
    assert _rel(R64 @ Rinv, np.eye(n)) < 1e-6


def test_windows_in_place():
    """The leaf form: read the (off, off, n, n) window of a larger buffer,
    write both results into (dest, dest, n, n) windows of bf16 buffers, and
    leave every other entry of them as it was."""
    n, N = 128, 384
    A = _spd(n, seed=1)
    buf = np.full((N, N), np.nan, np.float32)
    buf[n:2 * n, n:2 * n] = _poisoned(A)
    Rp = jnp.full((N, N), 7.0, jnp.bfloat16)
    RIp = jnp.full((N, N), 5.0, jnp.bfloat16)
    Rp, RIp = pallas_tpu.potrf_trtri_upper(
        jnp.asarray(buf), off=n, n=n, Rp=Rp, RIp=RIp, dest=2 * n)
    Rx, Rinvx = lapack.potrf_trtri(jnp.asarray(A), uplo="U")
    Rp, RIp = np.asarray(Rp, np.float32), np.asarray(RIp, np.float32)
    win = np.zeros((N, N), bool)
    win[2 * n:, 2 * n:] = True
    assert np.all(Rp[~win] == 7.0) and np.all(RIp[~win] == 5.0)
    assert _rel(Rp[win].reshape(n, n), Rx) < 1e-2  # bf16 storage
    assert _rel(RIp[win].reshape(n, n), Rinvx) < 1e-2
    with pytest.raises(ValueError):
        pallas_tpu.potrf_trtri_upper(jnp.asarray(buf), off=64, n=n)


def test_info_on_an_indefinite_panel(monkeypatch):
    """with_info through lapack.potrf_trtri_upper on the kernel's path: the
    potrf convention, pivot p + 1 where the leading p minor is fine."""
    n, p = 128, 37
    A = _spd(n, seed=2)
    A[p, p] = -1.0
    P = jnp.asarray(_poisoned(A))
    monkeypatch.setattr(pallas_tpu, "_interpret_default", lambda: True)
    with pallas_tpu.device_scope(_Chip()):
        R, _, info = lapack.potrf_trtri_upper(P, with_info=True)
    assert int(info) == p + 1
    assert np.all(np.isfinite(np.asarray(R)[:p]))
    _, _, info_x = lapack.potrf_trtri_upper(P, with_info=True)  # XLA's pair
    assert int(info_x) != 0
    clean = jnp.asarray(_poisoned(_spd(n, seed=2)))
    with pallas_tpu.device_scope(_Chip()):
        assert int(lapack.potrf_trtri_upper(clean, with_info=True)[2]) == 0
    assert int(detect.factor_info(R)) == p + 1


def test_shape_rule_and_route_counter(routes):
    """The kernel only on a TPU, at an f32 compute dtype, for n a multiple
    of its panel within [PALLAS_CHOL_MIN, PALLAS_CHOL_MAX] with every
    offset aligned; each traced site counts the path it took."""
    lo, hi = lapack.PALLAS_CHOL_MIN, lapack.PALLAS_CHOL_MAX
    cpu = jax.devices("cpu")[0]
    with pallas_tpu.device_scope(_Chip()):
        assert lapack.pallas_chol_fits(hi, jnp.bfloat16)
        assert lapack.pallas_chol_fits(lo, jnp.float32, 0, 4 * lo)
        assert not lapack.pallas_chol_fits(lo, jnp.float32, lo // 2)
        assert not lapack.pallas_chol_fits(hi, jnp.float64)
        assert not lapack.pallas_chol_fits(hi + 128, jnp.float32)
        assert not lapack.pallas_chol_fits(lo + 64, jnp.float32)
        if lo > 128:
            assert not lapack.pallas_chol_fits(lo - 128, jnp.float32)
    with pallas_tpu.device_scope(cpu):
        assert not lapack.pallas_chol_fits(hi, jnp.float32)

    def trace(n, dtype, dev):
        with pallas_tpu.device_scope(dev):
            jax.eval_shape(lapack.potrf_trtri_upper,
                           jax.ShapeDtypeStruct((n, n), dtype))

    # eval_shape caches traces by function and shapes, not by the scope:
    # each shape here is new
    trace(hi, jnp.bfloat16, _Chip())
    trace(hi, jnp.float32, _Chip())
    trace(lo, jnp.float64, _Chip())
    trace(hi + 64, jnp.float32, cpu)
    assert routes.snapshot() == {
        "potrf_trtri/pallas": {"builds": 2, "n": hi},
        "potrf_trtri/xla": {"builds": 2, "n": hi + 64},
    }


def test_cholinv_leaf_takes_the_kernel_where_it_fits(routes, monkeypatch):
    """cholinv's one-device leaf on the kernel (forced here, as a TPU would
    choose it) factors as the XLA leaf does, and counts each leaf."""
    monkeypatch.setattr(lapack, "pallas_chol_fits", lambda *a: True)
    grid = Grid.square(c=1, devices=jax.devices("cpu")[:1])
    A = jax.device_put(_spd(256, seed=3).astype(np.float32) + np.eye(
        256, dtype=np.float32), grid.face_sharding())
    cfg = cholesky.CholinvConfig(base_case_dim=128, mode="pallas")
    R, Rinv = jax.jit(lambda a: cholesky.factor(grid, a, cfg))(A)
    assert float(residual.cholesky_residual(A, R)) < 1e-5
    assert float(residual.cholesky_inverse_residual(R, Rinv)) < 1e-5
    assert routes.snapshot() == {"potrf_trtri/pallas": {"builds": 2, "n": 128}}
