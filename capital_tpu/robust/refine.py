"""Mixed-precision iterative refinement: f64-grade answers at bf16/f32
factor throughput.

Single-chip dense throughput is saturated (PERF.md round 13), so the next
hot-path win does the O(n³) work in a CHEAPER precision and buys the
accuracy back with O(n²) sweeps: factor once at a low dtype, then iterate

    r = B − A·X          (residual, HIGH precision — IR::residual)
    d = solve(factor, r) (correction against the resident factor — IR::correct)
    X = X + d

Classic Wilkinson iterative refinement: each sweep contracts the error by
~cond(A)·u_factor, so whenever cond(A) is inside the factor dtype's
envelope a handful of sweeps reach the CORRECTION dtype's backward error —
the f32-factor + f64-correction combo lands f64-grade residuals at f32
factor cost (the `make bench-refine` gate).  The cond≈2e4 point where f32
sCQR3 stalls (docs/ROBUSTNESS.md) is comfortably inside this envelope:
contraction per sweep there is ~2e-3.

Everything is jit-friendly: the sweep loop is a `lax.while_loop` with an
IN-PROGRAM convergence test (per-problem normwise backward error
``‖r‖ / (‖A‖·‖X‖ + ‖B‖)`` against a dtype-derived tolerance), a fixed
iteration cap, and a progress guard — a problem whose error stops halving
freezes immediately, so divergence (cond beyond the factor envelope, or a
broken factor) costs at most one wasted sweep and comes back LOUD as
``RefineInfo.converged == 0`` with the measured final error.  All dtype
resolution is static (trace-time), so serve's zero-recompile invariant
holds; per-problem iteration counts come back as arrays for the stats
layer (serve/stats.Collector `refine` block).

Three flagship drivers, all batched (leading batch axis, the serve bucket
layout):

* ``posv`` — dense SPD; factor rides the PR 6 batched-grid potrf behind
  the dispatch-gate resolver, corrections are two triangular sweeps
  against the VMEM-resident-convention factor.
* ``lstsq`` — tall-skinny least squares via the CQR seam: the gram
  Cholesky R (= A's R factor) plus SEMI-NORMAL-EQUATION corrections
  (Björck): d = R⁻¹R⁻ᵀ·Aᵀr.
* ``posv_blocktri`` — the chain factors once (or reuses a RESIDENT factor
  from PR 12's residency cache via ``factor=``) and each correction sweep
  is the O(n·b²) block-bidiagonal substitution, not a refactor.

The serve tier vocabulary (``accuracy_tier`` ∈ fast/balanced/guaranteed)
resolves here (`plan`): balanced keeps today's program byte-identical,
fast downgrades the factor dtype one notch without refinement (the cheap
tier under overload, ROADMAP item 3), guaranteed pairs a low factor dtype
with an upgraded correction dtype and a sweep cap.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from capital_tpu.utils import tracing

TIERS = ("fast", "balanced", "guaranteed")

#: Sweep cap of the guaranteed tier: IR inside the envelope converges in
#: 2-4 sweeps (contraction ~cond·u_factor per sweep); 8 leaves margin for
#: near-envelope cond without letting a divergent problem spin.
DEFAULT_MAX_ITERS = 8


class RefineInfo(NamedTuple):
    """Per-problem refinement outcome (a pytree of (batch,) arrays,
    jit/vmap-safe — rides the executor's extras slot between X and the
    trailing info, so every request lands with its own counts)."""

    iters: object  # int32: correction sweeps executed
    converged: object  # int32: 1 = backward error met tolerance
    resid: object  # float32: final normwise backward-error estimate


class TierPlan(NamedTuple):
    """Static resolution of one accuracy tier at one request dtype."""

    factor_dtype: object
    correction_dtype: object
    max_iters: int  # 0 = no refinement (the factor answer ships as-is)


def _down1(dtype):
    """One notch down the factor ladder: f64→f32, f32→bf16, bf16 floors."""
    dt = jnp.dtype(dtype)
    if dt == jnp.float64:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(jnp.bfloat16)


def _up(dtype):
    """One notch up for corrections: bf16→f32, f32→f64 (where x64 is
    live — canonicalize_dtype reports what the runtime represents, so the
    resolution stays static AND honest on x64-disabled rigs), f64 ceils."""
    dt = jnp.dtype(dtype)
    if dt.itemsize < 4:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(jax.dtypes.canonicalize_dtype(jnp.float64))


def plan(tier: str, dtype) -> TierPlan:
    """Resolve accuracy_tier → (factor dtype, correction dtype, sweep cap)
    for one request dtype.  Pure static function of (tier, dtype): the
    serve engine hashes the tier into the bucket key and executable
    cfg-hash, and every downstream dispatch reads only these dtypes — the
    zero-recompile invariant survives the precision knob.

    * balanced — today's program, byte-identical (no refinement).
    * fast — factor one notch down, no refinement: the cheap tier the
      SLO-aware scheduler sheds to under overload.
    * guaranteed — low factor + upgraded correction + sweep cap: f64
      requests factor in f32 and correct in f64 (the bench flagship),
      f32 factors in f32 and corrects in f64, bf16 factors in bf16 and
      corrects in f32.
    """
    dt = jnp.dtype(dtype)
    if tier not in TIERS:
        raise ValueError(f"accuracy_tier must be one of {TIERS}, got {tier!r}")
    if tier == "balanced":
        return TierPlan(dt, dt, 0)
    if tier == "fast":
        fd = _down1(dt)
        return TierPlan(fd, fd, 0)
    fd = jnp.dtype(jnp.float32) if dt == jnp.float64 else dt
    return TierPlan(fd, _up(dt), DEFAULT_MAX_ITERS)


def tolerance(n: int, correction_dtype) -> float:
    """Default convergence tolerance on the normwise backward error:
    0.5·sqrt(n)·u at the CORRECTION dtype.  The measured floor of the
    refined error is ~0.02·sqrt(n)·u (residual rounding is a random walk
    over the n·k contraction terms, and the ‖A‖·‖X‖ scale sits in the
    denominator), so this demands a genuinely correction-dtype-grade
    answer — the bench gate compares against a straight f64 factor and
    this tolerance lands within ~1x of it — while keeping ~25x headroom
    above the floor so the progress guard doesn't fire loud false
    failures at the last sweep."""
    return 0.5 * float(n) ** 0.5 * float(
        jnp.finfo(jnp.dtype(correction_dtype)).eps
    )


def _pnorm(X):
    """Per-problem Frobenius norm of a (batch, ...) stack, as f32."""
    flat = X.reshape(X.shape[0], -1)
    return jnp.sqrt(jnp.sum(jnp.square(flat), axis=-1)).astype(jnp.float32)


def _refine_loop(X0, resid_fn, err_fn, correct_fn, *, max_iters: int,
                 tol: float, update_fn=None):
    """The shared sweep loop.  resid_fn(X) -> r at the correction dtype;
    err_fn(X, r) -> per-problem (batch,) f32 backward error; correct_fn(r)
    -> d; update_fn(X, d, act) -> X with d added to the problems where the
    (batch,) mask `act` is set (default: the masked X + d; X may be any
    pytree the three functions agree on).  Per-problem freezing: a problem
    stops the moment it converges, stops improving (error not halved —
    divergence comes back loud, not spun on), or hits the cap; the
    while_loop runs until every problem froze.  Returns (X, RefineInfo)."""
    r0 = resid_fn(X0)
    e0 = err_fn(X0, r0)
    batch = e0.shape[0]

    def _mask(act, x):
        return act.reshape((batch,) + (1,) * (x.ndim - 1))

    if update_fn is None:
        def update_fn(X, d, act):
            return X + jnp.where(_mask(act, X), d, jnp.zeros_like(d))

    def _active(e, prev, it):
        return (e > tol) & (e < 0.5 * prev) & (it < max_iters)

    def cond(carry):
        _, _, e, prev, it = carry
        return jnp.any(_active(e, prev, it))

    def body(carry):
        X, r, e, prev, it = carry
        act = _active(e, prev, it)
        d = correct_fn(r)
        Xn = update_fn(X, d, act)
        rn = resid_fn(Xn)
        en = err_fn(Xn, rn)
        return (
            Xn,
            jnp.where(_mask(act, r), rn, r),
            jnp.where(act, en, e),
            jnp.where(act, e, prev),
            it + act.astype(jnp.int32),
        )

    X, _, e, _, it = lax.while_loop(
        cond, body,
        (X0, r0, e0, jnp.full((batch,), jnp.inf, jnp.float32),
         jnp.zeros((batch,), jnp.int32)),
    )
    info = RefineInfo(
        iters=it, converged=(e <= tol).astype(jnp.int32), resid=e
    )
    return X, info


# --------------------------------------------------------------------------
# factor/solve routing: the PR 6 dispatch gate, at the FACTOR dtype
# --------------------------------------------------------------------------


def _potrf_route(Af, k: int, impl: str, precision, interpret):
    """Batched potrf at the factor dtype behind the batched_small
    dispatch-gate resolver: (R, info) with R upper.  Static resolution —
    f64 factors always ride the vmap/LAPACK seam (dtype_capable)."""
    from capital_tpu.ops import batched_small, lapack

    batch, n, _ = Af.shape
    pick = impl
    if impl == "auto":
        pick = batched_small.default_impl(
            "posv", Af.shape, (batch, n, k), Af.dtype, interpret=interpret
        )
    elif impl in ("pallas", "pallas_split") and not batched_small.dtype_capable(
        Af.dtype
    ):
        pick = "vmap"
    if pick in ("pallas", "pallas_split"):
        R, info = batched_small.potrf(
            Af, uplo="U", precision=precision, interpret=interpret
        )
        solve = lambda rr, bb: batched_small.potrs(
            rr, bb, uplo="U", precision=precision, interpret=interpret
        )
        return R, info, solve
    with tracing.scope("serve::solve"):
        R, info = jax.vmap(
            lambda a: lapack.potrf(a, uplo="U", with_info=True)
        )(Af)
    return R, info, lambda rr, bb: lapack.potrs(rr, bb, uplo="U")


# --------------------------------------------------------------------------
# the three flagship drivers
# --------------------------------------------------------------------------


def posv(A, B, *, factor_dtype, correction_dtype,
         max_iters: int = DEFAULT_MAX_ITERS, tol: float | None = None,
         impl: str = "auto", precision: str | None = "highest",
         interpret: bool | None = None):
    """Refined batched SPD solve: (batch, n, n) × (batch, n, k) →
    (X, info, RefineInfo) with X at B.dtype, info the (batch,) int32
    factor status (potrf convention — refinement cannot repair a broken
    factor, it reports it)."""
    batch, n, _ = A.shape
    k = B.shape[-1]
    fd, cd = jnp.dtype(factor_dtype), jnp.dtype(correction_dtype)
    if tol is None:
        tol = tolerance(n, cd)

    R, info, solve = _potrf_route(A.astype(fd), k, impl, precision, interpret)
    Ac, Bc = A.astype(cd), B.astype(cd)
    anorm = _pnorm(Ac)
    bnorm = _pnorm(Bc)
    tiny = jnp.float32(jnp.finfo(jnp.float32).tiny)

    with tracing.scope("IR::residual"):
        tracing.emit(flops=batch * 2.0 * n * n * k)
    with tracing.scope("IR::correct"):
        tracing.emit(
            flops=batch * (tracing.refine_sweep_flops(n, k)
                           - 2.0 * n * n * k)
        )

    def resid(X):
        with tracing.scope("IR::residual"):
            return Bc - jnp.matmul(Ac, X, precision=precision)

    def err(X, r):
        return _pnorm(r) / (anorm * _pnorm(X) + bnorm + tiny)

    def correct(r):
        with tracing.scope("IR::correct"):
            return solve(R, r.astype(fd)).astype(cd)

    X0 = correct(Bc - jnp.zeros_like(Bc))  # first solve IS a correction of 0
    X, rinfo = _refine_loop(X0, resid, err, correct,
                            max_iters=max_iters, tol=tol)
    return X.astype(B.dtype), info, rinfo


# --------------------------------------------------------------------------
# one large dense SPD system, refined to HPL-MxP's FP64 residual check
# --------------------------------------------------------------------------

#: HPL-MxP's acceptance threshold on the scaled residual
#: ‖b − Ax‖∞ / ((‖A‖∞‖x‖∞ + ‖b‖∞) · n · ε)
HPL_THRESHOLD = 16.0
#: the ε of that check: the unit roundoff of float64, 2⁻⁵³ (LAPACK
#: dlamch('E'), as HPL computes it)
HPL_EPS = 2.0**-53
#: posv_dense's default stopping point, half the threshold.  The program's
#: own scaled residual is FP64-grade like the check made elsewhere on the
#: returned solution (they agree to about 1% on a v5e at n = 32768), so
#: the factor of two covers their difference with room; a tighter stop
#: buys nothing the check can see and costs a sweep (~100x a sweep there)
DENSE_TOL = HPL_THRESHOLD / 2.0


def _ff_add(hi, lo, d):
    """(hi, lo) + d for float-float pairs of f32 (hi + lo to about 48
    bits): Knuth's TwoSum of hi and d, the error folded into lo, then
    renormalised so that |lo| stays under half an ulp of hi."""
    from capital_tpu.ops import pallas_tpu

    s, err = pallas_tpu.two_sum(hi, d)
    lo = lo + err
    hi = s + lo
    return hi, lo - (hi - s)


def _residual_f64(A, B, Xh, Xl):
    """FP64-grade residual B − A·X of the float-float solution X = Xh + Xl,
    rounded to f32 (its own f32 rounding is far below what a correction
    or the ∞-norm of r needs), in XLA's float64 under a scoped
    `jax.enable_x64`, written as a product and a row sum (no dot): on a
    TPU, which has no f64 unit, XLA emulates both in pairs of f32 and
    fuses them into one pass over A; its emulated f64 dot would instead
    split A into many n² pieces.  Route ``refine/xla_f64`` of `_residual`:
    any A dtype, any n, any platform."""
    with tracing.scope("IR::residual"), jax.enable_x64(True):
        f64 = jnp.float64
        x = Xh.astype(f64) + Xl.astype(f64)
        ax = jnp.sum(A.astype(f64)[:, :, None] * x[None, :, :], axis=1)
        return (B.astype(f64) - ax).astype(jnp.float32)


def _pallas_residual(A, B) -> bool:
    """Whether `_residual` takes the Pallas kernel for A and B (n, k): the
    scoped platform is a TPU (pallas_tpu.device_scope), A is bf16 (the
    kernel's exact products need A's 8-bit significand), n is a multiple
    of 128, k ≤ `pallas_tpu.RESID_MAX_K` and a grid step fits the
    device's VMEM (`pallas_tpu.residual_blocks`)."""
    from capital_tpu.ops import pallas_tpu

    return (pallas_tpu._platform() == "tpu" and A.dtype == jnp.bfloat16
            and pallas_tpu.residual_blocks(*B.shape) is not None)


def _residual(A, B, Xh, Xl, anorm):
    """The FP64-grade residual B − A·(Xh + Xl), rounded to f32, under
    ``IR::residual``, by one of two routes, chosen at trace time and
    counted in `spans.REFINE_ROUTES` with the system's ``n``:

    * ``refine/pallas`` where `_pallas_residual` holds (bf16 A on a TPU, n
      a multiple of 128, at most `pallas_tpu.RESID_MAX_K` columns):
      `pallas_tpu.residual_bf16`, one read of A with exact products and
      error-free sums in f32, scaled by `anorm` (‖A‖∞, from above);
    * ``refine/xla_f64`` otherwise (f32 or f64 A, the CPU, any other n or
      more columns): `_residual_f64`."""
    from capital_tpu.obs import spans
    from capital_tpu.ops import pallas_tpu

    n = int(A.shape[0])
    if _pallas_residual(A, B):
        spans.REFINE_ROUTES.take("refine/pallas", n=n)
        with tracing.scope("IR::residual"):
            return pallas_tpu.residual_bf16(A, B, Xh, Xl, anorm=anorm)
    spans.REFINE_ROUTES.take("refine/xla_f64", n=n)
    return _residual_f64(A, B, Xh, Xl)


def _split_mv(M, v, *, trans: bool = False):
    """M·v (or Mᵀ·v) in f32, one pass over M.  Against a bf16 M, v is split
    into a bf16 head and tail so that the product keeps v's f32 accuracy
    on an MXU that rounds its operands to bf16; a wider M runs at
    'highest'."""
    dims = (((0,), (0,)), ((), ())) if trans else (((1,), (0,)), ((), ()))
    if M.dtype.itemsize >= 4:
        return lax.dot_general(M, v.astype(M.dtype), dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    head = v.astype(jnp.bfloat16)
    tail = (v - head.astype(v.dtype)).astype(jnp.bfloat16)
    V = jnp.concatenate([head, tail], axis=1).astype(M.dtype)
    out = lax.dot_general(M, V, dims, preferred_element_type=jnp.float32)
    k = v.shape[1]
    return out[:, :k] + out[:, k:]


def posv_dense(grid, A, B, *, max_iters: int = DEFAULT_MAX_ITERS):
    """One dense SPD system A·X = B on one device, factored at low
    precision and refined to an FP64-grade answer (HPL-MxP's protocol).

    A (n, n) stays resident and unchanged; B is (n,) or (n, k).  The
    factor is `cholesky.factor` at the `guaranteed` tier's factor dtype
    (`plan`: A's own dtype, f32 for f64), `pick_base_case(n)`, the SUMMA
    mode for the grid (`summa.resolve_mode`: the Pallas kernels on one
    TPU), Schur complements in fresh trailing windows, so that A is only
    read (in place they would need a working copy of A beside it: a v5e
    compile at n = 32768 reserves 4.1 n² bf16 of temporaries that way
    against 2.6 n² this way), and ``complete_inv=False``: the correction
    d = R⁻¹R⁻ᵀr is a one-level block substitution, with R12 and the two
    diagonal inverse blocks of R⁻¹ the factor leaves (`_split_mv`), under
    ``IR::correct``.  The residual is FP64-grade (`_residual`, under
    ``IR::residual``): for bf16 A on a TPU, with n a multiple of 128 and
    at most `pallas_tpu.RESID_MAX_K` columns in B, one Pallas kernel
    that reads A once
    (`pallas_tpu.residual_bf16`, route ``refine/pallas``), else XLA's
    emulated f64 (`_residual_f64`, route ``refine/xla_f64``); X is a
    float-float pair of f32.  The loop
    (`_refine_loop`: the in-program test, the progress guard, the sweep
    cap `max_iters`; 0 returns the factor's own solve) stops when the
    scaled residual ‖b − Ax‖∞ / ((‖A‖∞‖x‖∞ + ‖b‖∞) · n · `HPL_EPS`),
    worst over the columns, is at most `DENSE_TOL`.

    Returns ((X_hi, X_lo), info, RefineInfo): X_hi + X_lo shaped like B
    (f32 each; their sum in f64 is the answer), info the int32 status of
    the factor's diagonal (`detect.diag_info`), and RefineInfo of one
    problem ((1,) arrays; `resid` the scaled residual).  A factor that
    breaks or a refinement that stalls comes back with converged == 0."""
    from capital_tpu.models import cholesky
    from capital_tpu.ops import pallas_tpu
    from capital_tpu.parallel import summa
    from capital_tpu.robust import detect

    n = A.shape[0]
    if A.shape != (n, n) or B.shape[0] != n or B.ndim not in (1, 2):
        raise ValueError(f"posv_dense needs A (n, n) and B (n,) or (n, k), "
                         f"got {A.shape}, {B.shape}")
    fd = plan("guaranteed", A.dtype).factor_dtype
    cfg = cholesky.CholinvConfig(
        complete_inv=False, base_case_dim=cholesky.pick_base_case(n),
        mode=summa.resolve_mode("auto", grid),
        precision=summa.default_precision(fd))
    R, Rinv = cholesky.factor(grid, A.astype(fd), cfg)
    info = detect.diag_info(jnp.diagonal(R))
    h = cholesky.top_split(n, cfg)

    Bm = (B[:, None] if B.ndim == 1 else B).astype(jnp.float32)
    k = Bm.shape[1]
    f32 = jnp.float32
    with tracing.scope("IR::residual"):
        # the check's scale, one pass over A a solve; the model prices one
        # residual (the sweeps are counted where they run, RefineInfo)
        anorm = jnp.max(jnp.sum(jnp.abs(A.astype(f32)), axis=1))
        tracing.emit(flops=2.0 * n * n * k)
    bnorm = jnp.max(jnp.abs(Bm), axis=0)
    scale = f32(n * HPL_EPS)
    with tracing.scope("IR::correct"):
        tracing.emit(flops=tracing.refine_sweep_flops(n, k) - 2.0 * n * n * k)

    def correct(r):
        r = r[0]
        with tracing.scope("IR::correct"):
            if h == n:  # one base-case window: R⁻¹ is whole
                d = _split_mv(Rinv, _split_mv(Rinv, r, trans=True))
                return d[None]
            R11i, R22i, R12 = Rinv[:h, :h], Rinv[h:, h:], R[:h, h:]
            z1 = _split_mv(R11i, r[:h], trans=True)
            z2 = _split_mv(R22i, r[h:] - _split_mv(R12, z1, trans=True),
                           trans=True)
            d2 = _split_mv(R22i, z2)
            d1 = _split_mv(R11i, z1 - _split_mv(R12, d2))
            return jnp.concatenate([d1, d2])[None]

    def resid(X):
        with pallas_tpu.device_scope(grid.mesh.devices.flat[0]):
            return _residual(A, Bm, X[0][0], X[1][0], anorm)[None]

    def err(X, r):
        rmax = jnp.max(jnp.abs(r[0]), axis=0)
        xmax = jnp.max(jnp.abs(X[0][0]), axis=0)
        # a NaN stays NaN: a broken factor never reads as converged
        return jnp.max(rmax / ((anorm * xmax + bnorm) * scale))[None]

    def update(X, d, act):
        return _ff_add(X[0], X[1], jnp.where(act[0], d, 0.0))

    x0 = correct(Bm[None])
    (Xh, Xl), rinfo = _refine_loop(
        (x0, jnp.zeros_like(x0)), resid, err, correct,
        max_iters=max_iters, tol=DENSE_TOL, update_fn=update)
    Xh, Xl = Xh[0], Xl[0]
    if B.ndim == 1:
        Xh, Xl = Xh[:, 0], Xl[:, 0]
    return (Xh, Xl), info, rinfo


def lstsq(A, B, *, factor_dtype, correction_dtype,
          max_iters: int = DEFAULT_MAX_ITERS, tol: float | None = None,
          impl: str = "auto", precision: str | None = "highest",
          interpret: bool | None = None):
    """Refined batched least squares via the CQR seam + semi-normal
    corrections: the gram Cholesky R (A's triangular factor up to signs)
    is computed ONCE at the factor dtype, then every sweep solves
    d = R⁻¹R⁻ᵀ·Aᵀr at factor cost O(mnk + n²k) — no re-factorization.
    Convergence is measured on the NORMAL-equation residual Aᵀ(B − AX)
    (the quantity lstsq actually zeroes; the plain residual floors at the
    data's distance from range(A))."""
    batch, m, n = A.shape
    k = B.shape[-1]
    fd, cd = jnp.dtype(factor_dtype), jnp.dtype(correction_dtype)
    if tol is None:
        tol = tolerance(n, cd)

    from capital_tpu.ops import batched_small  # noqa: F401  (route below)

    Af = A.astype(fd)
    with tracing.scope("CQR::gram"):
        G = jnp.matmul(jnp.swapaxes(Af, -1, -2), Af, precision=precision)
    R, info, solve = _potrf_route(G, k, impl, precision, interpret)

    Ac, Bc = A.astype(cd), B.astype(cd)
    At = jnp.swapaxes(Ac, -1, -2)
    C0 = jnp.matmul(At, Bc, precision=precision)  # AᵀB at corr dtype
    anorm2 = jnp.square(_pnorm(Ac))
    cnorm = _pnorm(C0)
    tiny = jnp.float32(jnp.finfo(jnp.float32).tiny)

    with tracing.scope("IR::residual"):
        tracing.emit(flops=batch * 4.0 * m * n * k)
    with tracing.scope("IR::correct"):
        tracing.emit(
            flops=batch * (tracing.refine_lstsq_sweep_flops(m, n, k)
                           - 4.0 * m * n * k)
        )

    def resid(X):
        # the semi-normal residual g = Aᵀ(B − A·X), at the corr dtype
        with tracing.scope("IR::residual"):
            r = Bc - jnp.matmul(Ac, X, precision=precision)
            return jnp.matmul(At, r, precision=precision)

    def err(X, g):
        return _pnorm(g) / (anorm2 * _pnorm(X) + cnorm + tiny)

    def correct(g):
        with tracing.scope("IR::correct"):
            return solve(R, g.astype(fd)).astype(cd)

    X0 = correct(C0)
    X, rinfo = _refine_loop(X0, resid, err, correct,
                            max_iters=max_iters, tol=tol)
    return X.astype(B.dtype), info, rinfo


def _chain_matvec(D, Cz, X, precision):
    """y = A·X for the block-tridiagonal chain (D diagonal blocks, Cz
    sub-diagonal blocks with block 0 ZEROED — the blocktri packing
    convention): y_i = D_i·X_i + C_i·X_{i−1} + C_{i+1}ᵀ·X_{i+1}."""
    y = jnp.matmul(D, X, precision=precision)
    Xdown = jnp.concatenate([jnp.zeros_like(X[:, :1]), X[:, :-1]], axis=1)
    y = y + jnp.matmul(Cz, Xdown, precision=precision)
    CzT = jnp.swapaxes(Cz, -1, -2)
    CzTup = jnp.concatenate(
        [CzT[:, 1:], jnp.zeros_like(CzT[:, :1])], axis=1
    )
    Xup = jnp.concatenate([X[:, 1:], jnp.zeros_like(X[:, :1])], axis=1)
    return y + jnp.matmul(CzTup, Xup, precision=precision)


def posv_blocktri(D, C, B, *, factor_dtype, correction_dtype,
                  max_iters: int = DEFAULT_MAX_ITERS,
                  tol: float | None = None, impl: str = "auto",
                  precision: str | None = "highest",
                  interpret: bool | None = None, factor=None):
    """Refined block-tridiagonal SPD solve: the chain factors ONCE at the
    factor dtype (or reuses a RESIDENT (L, Wt) factor via ``factor=`` —
    the PR 12 residency-cache composition: refinement then never
    refactors at all) and every correction sweep is the O(n·b²)
    block-bidiagonal substitution (models/blocktri.solve, BT::solve).
    Shapes per models/blocktri: D, C (batch, nblocks, b, b), B (batch,
    nblocks, b, k)."""
    from capital_tpu.models import blocktri

    batch, nblocks, b, _ = D.shape
    k = B.shape[-1]
    n = nblocks * b
    fd, cd = jnp.dtype(factor_dtype), jnp.dtype(correction_dtype)
    if tol is None:
        tol = tolerance(n, cd)
    mapped = {"auto": "auto", "pallas": "pallas", "pallas_split": "pallas",
              "vmap": "xla", "xla": "xla"}[impl]

    if factor is None:
        L, Wt, info = blocktri.factor(
            D.astype(fd), C.astype(fd), precision=precision, impl=mapped,
            interpret=interpret,
        )
    else:
        L, Wt = factor
        info = jnp.zeros((batch,), jnp.int32)  # resident factors install clean

    Dc, Cc = D.astype(cd), C.astype(cd)
    # zero the (meaningless) first coupling block at the corr dtype too —
    # the factor path does this internally (blocktri._zero_first_coupling)
    Cz = jnp.concatenate([jnp.zeros_like(Cc[:, :1]), Cc[:, 1:]], axis=1)
    Bc = B.astype(cd)
    anorm = jnp.sqrt(
        jnp.square(_pnorm(Dc)) + 2.0 * jnp.square(_pnorm(Cz))
    )
    bnorm = _pnorm(Bc)
    tiny = jnp.float32(jnp.finfo(jnp.float32).tiny)

    with tracing.scope("IR::residual"):
        tracing.emit(flops=batch * nblocks * (2.0 * b * b * k * 3.0))
    with tracing.scope("IR::correct"):
        tracing.emit(
            flops=batch * 2.0 * tracing.blocktri_solve_flops(nblocks, b, k)
        )

    def resid(X):
        with tracing.scope("IR::residual"):
            return Bc - _chain_matvec(Dc, Cz, X, precision)

    def err(X, r):
        return _pnorm(r) / (anorm * _pnorm(X) + bnorm + tiny)

    def correct(r):
        with tracing.scope("IR::correct"):
            d = blocktri.solve(L, Wt, r.astype(fd), precision=precision,
                               impl=mapped, interpret=interpret)
            return d.astype(cd)

    X0 = correct(Bc)
    X, rinfo = _refine_loop(X0, resid, err, correct,
                            max_iters=max_iters, tol=tol)
    return X.astype(B.dtype), info, rinfo
