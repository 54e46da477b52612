"""The FP64-grade residual kernel of the dense refined solve
(ops/pallas_tpu.residual_bf16) and the rule that routes
refine.posv_dense's residual to it.

The kernel runs in interpret mode here.  Against a NumPy residual in long
double (float64 where the platform has no wider type) at n in {128, 512,
2048} and k in {1, 2}, every entry of r = B − A·(Xh + Xl) is within
HPL-MxP's ε-scale n·2⁻⁵³·‖A‖∞‖x‖∞, plus the f32 rounding of r itself,
for x the f64 solve (r cancels to about ε), random x, x spread over
2⁻²⁰…2⁰, a nonzero Xl, and an A whose entries span 2⁻³⁰…2⁰.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.obs import spans
from capital_tpu.ops import pallas_tpu
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust import refine

GRID = Grid.square(c=1, devices=jax.devices("cpu")[:1])
TPU = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
CPU = types.SimpleNamespace(platform="cpu", device_kind="cpu")
CASES = ("solve", "random", "spread", "lo", "wide")


@jax.jit
def _kernel(A, B, Xh, Xl, anorm):
    return pallas_tpu.residual_bf16(A, B, Xh, Xl, anorm=anorm)


def _operand(n, k, case, seed):
    """(A bf16, B, Xh, Xl f32): A is U[-1, 1)/√n plus 3I, symmetric, its
    off-diagonal entries scaled by 2^-U[0, 30) in the 'wide' case."""
    rng = np.random.default_rng(seed)
    G = rng.uniform(-1.0, 1.0, (n, n))
    if case == "wide":
        G = G * 2.0 ** -rng.uniform(0.0, 30.0, (n, n))
    S = (np.triu(G) + np.triu(G, 1).T) / np.sqrt(n) + 3.0 * np.eye(n)
    A = jnp.asarray(S, jnp.bfloat16)
    B = rng.uniform(-1.0, 1.0, (n, k)).astype(np.float32)
    A64 = np.asarray(A.astype(jnp.float32), np.float64)
    if case == "solve":
        x = np.linalg.solve(A64, B.astype(np.float64))
    elif case == "spread":
        x = np.sign(rng.uniform(-1.0, 1.0, (n, k))) * 2.0 ** -rng.uniform(
            0.0, 20.0, (n, k))
    else:
        x = rng.uniform(-1.0, 1.0, (n, k))
    Xh = x.astype(np.float32)
    Xl = (x - Xh).astype(np.float32)  # the f64 tail of x, below ulp(Xh)/2
    if case in ("lo", "wide"):
        Xl = (Xh * rng.uniform(-1.0, 1.0, (n, k)) * 2.0**-25).astype(
            np.float32)
    return A, B, Xh, Xl, A64


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("n", [128, 512, 2048])
def test_matches_a_wide_residual(n, k, case):
    A, B, Xh, Xl, A64 = _operand(n, k, case, seed=n + 7 * k + CASES.index(case))
    anorm = np.max(np.sum(np.abs(A64), axis=1))
    r = np.asarray(_kernel(A, B, Xh, Xl, np.float32(anorm)))
    L = np.longdouble
    ref = B.astype(L) - A64.astype(L) @ (Xh.astype(L) + Xl.astype(L))
    x = Xh.astype(np.float64) + Xl
    scale = n * 2.0**-53 * anorm * np.max(np.abs(x))
    assert r.shape == (n, k) and r.dtype == np.float32
    # the ε-scale, plus half an ulp of r's own f32 rounding
    gap = np.abs(r.astype(L) - ref)
    assert np.all(gap <= scale + 2.0**-24 * np.abs(ref)), float(np.max(gap))
    if case == "solve":  # full cancellation: r is ε-small, and so the gap
        assert float(np.max(gap)) <= scale


def test_agrees_with_the_xla_route():
    A, B, Xh, Xl, A64 = _operand(512, 2, "lo", seed=3)
    anorm = np.float32(np.max(np.sum(np.abs(A64), axis=1)))
    r = np.asarray(_kernel(A, B, Xh, Xl, anorm), np.float64)
    rx = np.asarray(jax.jit(refine._residual_f64)(A, B, Xh, Xl), np.float64)
    x = Xh.astype(np.float64) + Xl
    tol = 512 * 2.0**-53 * float(anorm) * np.max(np.abs(x))
    assert np.all(np.abs(r - rx) <= tol + 2.0**-23 * np.abs(rx))


def test_refuses_what_it_cannot_run():
    A, B, Xh, Xl, _ = _operand(128, 1, "random", seed=1)
    with pytest.raises(ValueError, match="bf16"):
        pallas_tpu.residual_bf16(A.astype(jnp.float32), B, Xh, Xl, anorm=4.0)
    with pytest.raises(ValueError, match="multiple of 128"):
        pallas_tpu.residual_bf16(A[:64, :64], B[:64], Xh[:64], Xl[:64],
                                 anorm=4.0)
    B3 = jnp.tile(B, (1, 3))
    with pytest.raises(ValueError, match="k ≤ 2"):
        pallas_tpu.residual_bf16(A, B3, B3, B3, anorm=4.0)


V4 = types.SimpleNamespace(platform="tpu", device_kind="TPU v4")


@pytest.mark.parametrize("dev,n,k,blocks", [
    (TPU, 128, 1, (128, 128)), (TPU, 512, 1, (512, 512)),
    (TPU, 2048, 1, (1024, 2048)), (TPU, 3072, 1, (1024, 1024)),
    (TPU, 32768, 1, (1024, 4096)), (TPU, 32768, 2, (1024, 4096)),
    (TPU, 1152, 1, (128, 128)), (TPU, 500, 1, None), (TPU, 1000, 1, None),
    # more columns than the kernel keeps in registers
    (TPU, 32768, 3, None), (TPU, 4096, 8, None), (TPU, 4096, 64, None),
    # Mosaic's own 16 MiB: narrower steps
    (V4, 32768, 1, (1024, 2048)), (V4, 32768, 2, (1024, 1024)),
    (V4, 4096, 1, (1024, 2048)), (V4, 4096, 8, None),
])
def test_tiles_fit_n(dev, n, k, blocks):
    with pallas_tpu.device_scope(dev):
        assert pallas_tpu.residual_blocks(n, k) == blocks
        if blocks is not None:
            room = pallas_tpu._device_budget()[1] or (
                pallas_tpu.MOSAIC_VMEM_DEFAULT)
            assert pallas_tpu._residual_vmem(*blocks, k) <= room * 3 // 4


@pytest.mark.parametrize("dev,dtype,n,k,pallas", [
    (TPU, jnp.bfloat16, 512, 1, True),
    (TPU, jnp.bfloat16, 32768, 1, True),
    (TPU, jnp.bfloat16, 512, 2, True),
    (TPU, jnp.float32, 512, 1, False),
    (TPU, jnp.float64, 512, 1, False),
    (CPU, jnp.bfloat16, 512, 1, False),
    (TPU, jnp.bfloat16, 1152, 1, True),
    (TPU, jnp.bfloat16, 500, 1, False),
    (TPU, jnp.bfloat16, 1000, 1, False),
    (TPU, jnp.bfloat16, 4096, 8, False),
    (TPU, jnp.bfloat16, 4096, 64, False),
    (V4, jnp.bfloat16, 32768, 1, True),
])
def test_routing_rule(dev, dtype, n, k, pallas):
    A = jax.ShapeDtypeStruct((n, n), dtype)
    B = jax.ShapeDtypeStruct((n, k), jnp.float32)
    with pallas_tpu.device_scope(dev):
        assert refine._pallas_residual(A, B) is pallas


def _force_kernel(monkeypatch):
    """The kernel's route on this CPU, in interpret mode."""
    monkeypatch.setattr(refine, "_pallas_residual",
                        lambda A, B: A.dtype == jnp.bfloat16)


def test_the_kernel_route_is_counted(monkeypatch):
    routes = spans.RouteCounter()
    monkeypatch.setattr(spans, "REFINE_ROUTES", routes)
    _force_kernel(monkeypatch)
    A, B, _, _, _ = _operand(128, 1, "random", seed=2)
    jax.jit(lambda a, v: refine.posv_dense(GRID, a, v)).lower(A, B[:, 0])
    snap = routes.snapshot()
    assert list(snap) == ["refine/pallas"]
    assert snap["refine/pallas"]["n"] == 128
    assert snap["refine/pallas"]["builds"] >= 1


def _hpl_resid(A64, X, b):
    x = np.asarray(X[0], np.float64) + np.asarray(X[1], np.float64)
    r = np.max(np.abs(b - A64 @ x))
    den = (np.max(np.sum(np.abs(A64), axis=1)) * np.max(np.abs(x))
           + np.max(np.abs(b)))
    return float(r / (den * A64.shape[0] * refine.HPL_EPS))


def test_dense_solve_converges_alike_on_both_routes(monkeypatch):
    A, B, _, _, A64 = _operand(512, 1, "solve", seed=512)
    b = B[:, 0]
    X, info, ri = jax.jit(functools.partial(refine.posv_dense, GRID))(A, b)
    _force_kernel(monkeypatch)
    Xk, infok, rik = jax.jit(functools.partial(refine.posv_dense, GRID))(A, b)
    assert int(info) == int(infok) == 0
    assert int(rik.converged[0]) == 1
    assert int(rik.iters[0]) == int(ri.iters[0])
    assert _hpl_resid(A64, Xk, b) <= refine.DENSE_TOL
    assert float(rik.resid[0]) <= refine.DENSE_TOL
