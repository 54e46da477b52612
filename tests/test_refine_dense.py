"""The dense refined SPD solve (robust/refine.posv_dense): a factor at low
precision refined to HPL-MxP's FP64 scaled-residual check.

Against a float64 NumPy solve at n = 256 and 512 (base case 128) on seeded
operands shaped like the benchmark's ``spd_hash`` (bf16 entries, U[-1, 1)/√n
off the diagonal plus 3I): the scaled residual passes HPL-MxP's threshold of
16 within the sweep cap, while the answer before any sweep misses it by
orders of magnitude; a non-SPD operand comes back with converged == 0; the
serve engine's oversize 'guaranteed' posv runs the same entry; the residual
route is counted.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.obs import spans
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust import refine
from capital_tpu.serve import ServeConfig, SolveEngine

GRID = Grid.square(c=1, devices=jax.devices("cpu")[:1])


@functools.lru_cache(maxsize=None)
def _solve(max_iters=refine.DEFAULT_MAX_ITERS):
    return jax.jit(functools.partial(refine.posv_dense, GRID,
                                     max_iters=max_iters))


def _operand(n, seed, k=None):
    """(A bf16, b f32): symmetric U[-1, 1)/√n plus 3I, and U[-1, 1)."""
    rng = np.random.default_rng(seed)
    G = rng.uniform(-1.0, 1.0, (n, n))
    S = (np.triu(G) + np.triu(G, 1).T) / np.sqrt(n) + 3.0 * np.eye(n)
    shape = (n,) if k is None else (n, k)
    b = rng.uniform(-1.0, 1.0, shape).astype(np.float32)
    return jnp.asarray(S, jnp.bfloat16), b


def _x(X):
    hi, lo = X
    return np.asarray(hi, np.float64) + np.asarray(lo, np.float64)


def hpl_resid(A, x, b):
    """HPL-MxP's scaled residual in float64, worst over the columns."""
    A = np.asarray(jnp.asarray(A, jnp.float32), np.float64)
    x = np.asarray(x, np.float64).reshape(A.shape[0], -1)
    b = np.asarray(b, np.float64).reshape(A.shape[0], -1)
    r = np.max(np.abs(b - A @ x), axis=0)
    den = (np.max(np.sum(np.abs(A), axis=1)) * np.max(np.abs(x), axis=0)
           + np.max(np.abs(b), axis=0))
    return float(np.max(r / (den * A.shape[0] * refine.HPL_EPS)))


@pytest.mark.parametrize("n,k", [(256, None), (512, None), (256, 3)])
def test_matches_a_float64_solve(n, k):
    A, b = _operand(n, seed=n + (k or 0), k=k)
    X, info, ri = _solve()(A, b)
    x = _x(X)
    assert int(info) == 0
    assert int(ri.converged[0]) == 1
    assert 1 <= int(ri.iters[0]) <= refine.DEFAULT_MAX_ITERS
    assert hpl_resid(A, x, b) < refine.HPL_THRESHOLD
    # the program's own scaled residual is the one the check computes
    assert float(ri.resid[0]) == pytest.approx(hpl_resid(A, x, b), rel=0.2)
    # forward error within what the stop promises: ‖A⁻¹‖∞ times the
    # residual a scaled residual of DENSE_TOL allows
    A64 = np.asarray(jnp.asarray(A, jnp.float32), np.float64)
    ref = np.linalg.solve(A64, np.asarray(b, np.float64))
    rmax = refine.DENSE_TOL * n * refine.HPL_EPS * (
        np.max(np.sum(np.abs(A64), axis=1)) * np.max(np.abs(x))
        + np.max(np.abs(b)))
    bound = np.linalg.norm(np.linalg.inv(A64), np.inf) * rmax
    assert np.max(np.abs(x - ref)) <= bound
    assert X[0].shape == b.shape and X[0].dtype == jnp.float32


def test_the_unrefined_answer_fails_the_check():
    """max_iters=0 is the low-precision factor's own solve: the benchmark's
    control, which must miss the threshold by orders of magnitude."""
    A, b = _operand(256, seed=256)
    X, info, ri = _solve(0)(A, b)
    assert int(ri.iters[0]) == 0 and int(ri.converged[0]) == 0
    assert hpl_resid(A, _x(X), b) > 1e6 * refine.HPL_THRESHOLD


def test_a_non_spd_operand_comes_back_loud():
    A, b = _operand(256, seed=7)
    A = A.at[100, 100].set(-3.0)  # an indefinite leading minor of order 101
    X, info, ri = _solve()(A, b)
    assert int(ri.converged[0]) == 0
    assert int(info) != 0
    assert not np.isfinite(float(ri.resid[0])) or float(
        ri.resid[0]) > refine.DENSE_TOL


def test_the_residual_route_is_counted(monkeypatch):
    routes = spans.RouteCounter()
    monkeypatch.setattr(spans, "REFINE_ROUTES", routes)
    A, b = _operand(128, seed=1)
    jax.jit(lambda a, v: refine.posv_dense(GRID, a, v)).lower(A, b)
    snap = routes.snapshot()
    assert list(snap) == ["refine/xla_f64"]
    assert snap["refine/xla_f64"]["n"] == 128
    assert snap["refine/xla_f64"]["builds"] >= 1


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_engine_oversize_guaranteed_posv_runs_the_entry(dtype, monkeypatch):
    routes = spans.RouteCounter()
    monkeypatch.setattr(spans, "REFINE_ROUTES", routes)
    eng = SolveEngine(cfg=ServeConfig(buckets=(16,), rows_buckets=(64,),
                                      nrhs_buckets=(2,), max_batch=2,
                                      max_delay_s=0.0, small_n_impl="vmap"))
    n = 96  # beyond the (16,) ladder
    A, b = _operand(n, seed=5, k=2)
    A, b = np.asarray(jnp.asarray(A, jnp.float32)).astype(dtype), b.astype(dtype)
    r = eng.solve("posv", A, b, accuracy_tier="guaranteed")
    assert r.ok, r.error
    assert not r.batched and np.asarray(r.x).dtype == dtype
    assert [sp.name for sp in r.trace.spans] == [
        "admit", "cache_lookup", "device", "refine", "respond"]
    assert r.trace.problems() == []
    assert routes.snapshot()["refine/xla_f64"]["n"] == n  # the entry ran
    X, _, ri = jax.jit(functools.partial(refine.posv_dense, eng.grid))(
        jnp.asarray(A), jnp.asarray(b))
    # the same program: the library's answer, rounded to the request dtype
    want = np.asarray(X[0].astype(dtype) + X[1].astype(dtype))
    np.testing.assert_array_equal(np.asarray(r.x), want)
    ref = np.linalg.solve(A.astype(np.float64), b.astype(np.float64))
    tol = 1e-12 if dtype == np.float64 else 1e-6
    assert np.max(np.abs(np.asarray(r.x) - ref)) <= tol * np.max(np.abs(ref))
    blk = eng.emit_stats()["request_stats"]["refine"]
    assert blk["requests"] == blk["converged"] == 1


def test_engine_oversize_guaranteed_failure_is_loud():
    eng = SolveEngine(cfg=ServeConfig(buckets=(16,), rows_buckets=(64,),
                                      nrhs_buckets=(2,), max_batch=2,
                                      max_delay_s=0.0, small_n_impl="vmap"))
    A, b = _operand(96, seed=6, k=2)
    A = np.array(jnp.asarray(A, jnp.float32))
    A[40, 40] = -3.0
    r = eng.solve("posv", A, b, accuracy_tier="guaranteed")
    assert not r.ok and r.x is None
    assert "did not converge" in r.error
    assert r.trace.problems() == []
    blk = eng.emit_stats()["request_stats"]["refine"]
    assert blk["nonconverged"] == 1
