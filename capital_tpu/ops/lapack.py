"""Local factorization kernels — the LAPACK seam of the framework.

TPU-native equivalent of the reference's LAPACK engine
(src/lapack/interface.hpp:30-89), which funnels every local factorization
through four wrappers: potrf, trtri, geqrf, orgqr.  Here the same seam maps
to lax.linalg primitives, which XLA compiles to MXU-friendly blocked
routines:

    LAPACKE_dpotrf  ->  potrf   (lax.linalg.cholesky)
    LAPACKE_dtrtri  ->  trtri   (lax.linalg.triangular_solve vs identity)
    LAPACKE_dgeqrf  ->  geqrf   (jnp.linalg.qr)   [reference wrappers exist
    LAPACKE_dorgqr  ->  orgqr   (jnp.linalg.qr)    but no algorithm calls
                                                   them — kept for parity]

These operate on *local/replicated* values: distributed algorithms gather or
replicate a panel first (see models/cholesky.py base case), exactly where the
reference gathers panels across the slice communicator before its local
LAPACK call (cholinv policy.h:160-224).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# robust.{detect,faultinject} depend only on jax + tracing, so this import
# cannot cycle back here.  The taps are identity when no fault plan is
# active; with_info=False keeps every wrapper's signature unchanged.
from capital_tpu.obs import spans
from capital_tpu.robust import detect, faultinject


def _compute_dtype(dtype):
    """Panel factorizations run at >= f32: sub-f32 inputs (bf16/f16) are the
    numerically fragile case for potrf/trtri (cholinv's base_case_dtype
    principle, models/cholesky.py), and the CPU backend's LAPACK custom
    calls reject them outright — observed as NotImplementedError from a bf16
    gram in cacqr's 1d sweep on the test rig.  Results cast back to the
    input dtype."""
    return jnp.float32 if jnp.dtype(dtype).itemsize < 4 else jnp.dtype(dtype)


def potrf(A: jnp.ndarray, uplo: str = "U", with_info: bool = False):
    """Cholesky factor of SPD A: upper R with A = RᵀR (uplo='U') or lower L
    with A = LLᵀ (uplo='L').  Reference lapack::engine::_potrf
    (interface.hpp:30-44).

    with_info=True additionally returns the LAPACK-style int32 status of
    the factor (robust/detect.factor_info; 0 = clean) — lax.linalg.cholesky
    itself NaN-fills silently on breakdown."""
    A = faultinject.tap(A)
    L = lax.linalg.cholesky(A.astype(_compute_dtype(A.dtype)))
    L = L.astype(A.dtype)
    T = L.T if uplo == "U" else L
    return (T, detect.factor_info(T)) if with_info else T


def potrs(T: jnp.ndarray, B: jnp.ndarray, uplo: str = "U") -> jnp.ndarray:
    """SPD solve A·X = B from an EXISTING Cholesky factor via two triangular
    (trsm) sweeps — LAPACKE_dpotrs for this seam.  With uplo='U'
    (A = RᵀR, T = R): solve Rᵀ·Y = B then R·X = Y; with uplo='L'
    (A = LLᵀ, T = L): L·Y = B then Lᵀ·X = Y.

    Leading batch dimensions of (T, B) solve as a stack (both sweeps are one
    batched triangular_solve each), which is what serve's vmap micro-batching
    rides.  Runs at the >= f32 compute dtype like the factor itself and casts
    back once."""
    if uplo not in ("U", "L"):
        raise ValueError(f"uplo must be 'U' or 'L', got {uplo!r}")
    ct = _compute_dtype(T.dtype)
    Tc, Bc = T.astype(ct), B.astype(ct)
    lower = uplo == "L"
    # the transposed sweep comes first for 'U' (Rᵀ then R), second for 'L'
    # (L then Lᵀ); `lower` describes the stored triangle of T in both.
    Y = lax.linalg.triangular_solve(
        Tc, Bc, left_side=True, lower=lower, transpose_a=not lower
    )
    X = lax.linalg.triangular_solve(
        Tc, Y, left_side=True, lower=lower, transpose_a=lower
    )
    return X.astype(B.dtype)


def trtri(T: jnp.ndarray, uplo: str = "U", unit_diag: bool = False) -> jnp.ndarray:
    """Inverse of a triangular matrix.  Reference lapack::engine::_trtri
    (interface.hpp:46-59).  Leading batch dimensions invert as a stack in
    one batched solve (the TRSM diaginvert leaf's precompute)."""
    ct = _compute_dtype(T.dtype)
    eye = jnp.broadcast_to(jnp.eye(T.shape[-1], dtype=ct), T.shape)
    out = lax.linalg.triangular_solve(
        T.astype(ct), eye, left_side=True, lower=(uplo == "L"),
        unit_diagonal=unit_diag,
    )
    return out.astype(T.dtype)


def trtri_newton(
    D: jnp.ndarray,
    unit_diag: bool = False,
    precision: str | None = "highest",
) -> jnp.ndarray:
    """EXACT inverse of a (..., s, s) LOWER-triangular stack by the
    finite-termination Newton iteration — all batched MXU matmuls, no
    XLA:TPU triangular_solve custom call (which serializes its batch: a
    384-stack of 512-blocks runs as 384 sequential solves, ~3.9 ms at the
    rectri 49152 row vs ~0.2 ms for this spelling).

    With X₀ = diag(D)⁻¹, the residual I − D·X₀ is STRICTLY lower
    triangular, hence nilpotent of index s; the Newton step
    X ← X·(2I − D·X) squares the residual, so ⌈log₂ s⌉ steps terminate
    with the exact inverse (in exact arithmetic — in floats, to the same
    roundoff class as substitution).  Products of lower triangles are
    lower triangles even in floating point, so the structural zeros hold
    without masking.  Runs at the >= f32 compute dtype, casts back once."""
    ct = _compute_dtype(D.dtype)
    s = D.shape[-1]
    if unit_diag:
        # never read the stored diagonal (by unit-diag convention it is
        # meaningless and may be inf/nan)
        Dm = jnp.tril(D, -1).astype(ct) + jnp.eye(s, dtype=ct)
        d = jnp.ones(D.shape[:-1], dtype=ct)
    else:
        Dm = jnp.tril(D).astype(ct)
        d = jnp.diagonal(Dm, axis1=-2, axis2=-1)
    X = (1.0 / d)[..., :, None] * jnp.eye(s, dtype=ct)
    two_eye = 2.0 * jnp.eye(s, dtype=ct)
    steps = max(1, (s - 1).bit_length())
    for _ in range(steps):
        DX = jnp.matmul(Dm, X, precision=precision)
        X = jnp.matmul(X, two_eye - DX, precision=precision)
    return X.astype(D.dtype)


def diag_block_stack(X: jnp.ndarray, o: int, s: int, stride: int) -> jnp.ndarray:
    """(count, s, s) stack of the diagonal-band blocks
    ``X[..., i*stride + o : i*stride + o + s, i*stride : i*stride + s]``,
    flattened over any leading batch dim (o=0 gives the diagonal blocks
    themselves; o=s, stride=2s gives the per-pair subdiagonal blocks of a
    merge level).  Built from static lax.slice per block, NOT
    reshape+fancy-indexing: the gather form lowers to a scan of the WHOLE
    operand (measured ~2.6 ms scanning a 2.1 GB matrix for 33 MB of
    blocks — the trsm TS::dinv lesson, docs/PERF.md).  Shared by
    trtri_stack, the trsm diaginvert precompute, and the rectri batched
    prefix so the lowering fix cannot drift apart."""
    count = X.shape[-2] // stride
    lo = (0,) * (X.ndim - 2)
    parts = [
        lax.slice(
            X,
            lo + (i * stride + o, i * stride),
            X.shape[:-2] + (i * stride + o + s, i * stride + s),
        )
        for i in range(count)
    ]
    return jnp.stack(parts, axis=X.ndim - 2).reshape((-1, s, s))


def trtri_stack(
    D: jnp.ndarray,
    uplo: str = "L",
    unit_diag: bool = False,
    inner: int = 128,
    precision: str | None = None,
) -> jnp.ndarray:
    """Inverse of a (nb, bc, bc) stack of triangular blocks.

    XLA:TPU's batched triangular_solve custom call serializes its batch
    internally (measured: a batch-32 trtri of 512-blocks costs the same as
    32 sequential calls — docs/PERF.md "rectri round 4: batched-prefix
    negative result"), so the custom call is confined to `inner`-sized
    sub-blocks (16x less serialized work at bc=512/inner=128) and the
    bc-block inverses are assembled by batched MXU matmul merge levels:

        [A11  0 ]^-1   [   A11inv     0   ]
        [A21 A22]    = [-A22inv·A21·A11inv A22inv]

    `inner` is a ceiling, not an exact size: the call uses the largest
    bc/2^j <= inner (bc=384 -> 96, bc=512 -> 128), falling back to the
    plain batched trtri when halving cannot reach the ceiling (odd bc
    above it).  unit_diag applies to the stored diagonal of the inner
    blocks (Diag::AblasUnit semantics, engine.h:23-52)."""
    nb, bc = D.shape[0], D.shape[-1]
    d = bc
    while inner > 0 and d > inner and d % 2 == 0:
        d //= 2
    k = bc // d if 0 < d <= inner else 0
    inner = d
    if k <= 1:
        return trtri(D, uplo=uplo, unit_diag=unit_diag)
    lower = uplo == "L"
    if not lower:
        # one transpose each way keeps a single (lower) merge body
        return jnp.swapaxes(
            trtri_stack(
                jnp.swapaxes(D, -1, -2), "L", unit_diag, inner, precision
            ),
            -1, -2,
        )
    # the whole chain runs at the >= f32 compute dtype and casts back ONCE
    # (the module invariant): rounding W to a sub-f32 input dtype between
    # merge levels measurably compounds (1.7x the plain-trtri error on a
    # bf16 bc=256 stack).  Sub-f32 inputs also force >= 3-pass merge
    # products — the upcast buys nothing if the matmuls drop back to
    # 1-pass bf16.
    ct = _compute_dtype(D.dtype)
    if precision is None:
        # never let the merge products run at TPU-default (one-pass bf16)
        # grade — that silently degrades the block inverses below what the
        # plain batched trtri delivers (ADVICE r4).  Callers wanting speed
        # over accuracy must opt in explicitly.
        precision = "highest"
    Dm = jnp.tril(D).astype(ct)

    # inner blocks via the exact-termination Newton iteration: the batched
    # triangular_solve custom call serializes even the inner batch (round 5
    # — it was the remaining serial term of the rectri/trsm base phase)
    W = trtri_newton(
        diag_block_stack(Dm, 0, inner, inner), unit_diag=unit_diag,
        precision=precision,
    )
    s = inner
    while s < bc:
        A21 = diag_block_stack(Dm, s, s, 2 * s)
        A11i, A22i = W[0::2], W[1::2]
        M = jnp.matmul(A21, A11i, precision=precision)
        B21 = -jnp.matmul(A22i, M, precision=precision)
        W = jnp.concatenate(
            [
                jnp.concatenate([A11i, jnp.zeros_like(A11i)], axis=2),
                jnp.concatenate([B21, A22i], axis=2),
            ],
            axis=1,
        )
        s *= 2
    return W.astype(D.dtype)


def potrf_trtri(A: jnp.ndarray, uplo: str = "U", with_info: bool = False):
    """Fused base-case pair: factor + triangular inverse in one call — the
    reference base case always computes both back to back
    (cholinv policy.h:197-201).  The factor stays at the compute dtype
    between the two steps (no intermediate downcast).

    with_info=True appends the int32 breakdown status of the factor."""
    A = faultinject.tap(A)
    ct = _compute_dtype(A.dtype)
    L = lax.linalg.cholesky(A.astype(ct))
    T = L.T if uplo == "U" else L
    eye = jnp.eye(A.shape[-1], dtype=ct)
    Tinv = lax.linalg.triangular_solve(
        T, eye, left_side=True, lower=(uplo == "L")
    )
    T, Tinv = T.astype(A.dtype), Tinv.astype(A.dtype)
    return (T, Tinv, detect.factor_info(T)) if with_info else (T, Tinv)


#: The panel sizes `potrf_trtri_upper` gives the one-kernel factor and
#: inverse (pallas_tpu.potrf_trtri_upper) on a TPU.  Above the top, five
#: n×n f32 arrays no longer sit well inside the kernel's VMEM budget (and
#: one-device grams of 2048 and more go to cholinv, qr._gram_chol).
PALLAS_CHOL_MIN, PALLAS_CHOL_MAX = 128, 1024


def pallas_chol_fits(n: int, dtype, *aligned: int) -> bool:
    """Whether an n×n upper-valid panel of `dtype` is factored and inverted
    by the Pallas kernel: a TPU platform (the scoped one,
    pallas_tpu.device_scope), an f32 compute dtype, n a multiple of the
    kernel's panel within [PALLAS_CHOL_MIN, PALLAS_CHOL_MAX], and every
    window offset or buffer dim in `aligned` a multiple of n.  Anything else
    (the CPU rig, f64, odd n) takes XLA's Cholesky and triangular solve."""
    from capital_tpu.ops import pallas_tpu

    return (
        pallas_tpu._platform() == "tpu"
        and _compute_dtype(dtype) == jnp.float32
        and n % pallas_tpu.CHOL_PANEL == 0
        and PALLAS_CHOL_MIN <= n <= PALLAS_CHOL_MAX
        and all(a % n == 0 for a in aligned)
    )


def count_chol_route(pallas: bool, n: int) -> None:
    """Count a factor-and-invert site's path, at trace time
    (`spans.CHOL_ROUTES`)."""
    spans.CHOL_ROUTES.take(
        "potrf_trtri/pallas" if pallas else "potrf_trtri/xla", n=n)


def potrf_trtri_upper(P: jnp.ndarray, with_info: bool = False):
    """(R, R⁻¹) upper-triangular from a symmetric panel whose **upper**
    triangle holds the valid content (the lower half may be garbage — e.g. a
    Schur window produced by an uplo='U' syrk).

    Where `pallas_chol_fits`, one Pallas kernel factors and inverts the
    panel in VMEM (pallas_tpu.potrf_trtri_upper).  Elsewhere it is
    functionally potrf_trtri(symmetrize_from(P, 'U')), but with every
    transpose routed through the layout-opaque Pallas kernel
    (ops/pallas_tpu.transpose): the naive spelling plants `.T` ops at every
    recursion leaf, and XLA layout assignment answers leaf-sized transposes
    with whole-graph column-major flips + full-matrix relayout copies
    (~4.7ms/iter at n=16k on v5e).  Here cholesky/triangular_solve run in
    their native lower form (no symmetrize pass: cholesky with
    symmetrize_input=False reads only the lower triangle) and the three
    transposes stay panel-sized.  Each call counts its path
    (`count_chol_route`).

    with_info=True appends the int32 breakdown status of R."""
    from capital_tpu.ops import pallas_tpu

    P = faultinject.tap(P)
    n = P.shape[-1]
    pallas = pallas_chol_fits(n, P.dtype)
    count_chol_route(pallas, n)
    if pallas:
        R, Rinv = pallas_tpu.potrf_trtri_upper(P)
    else:
        ct = _compute_dtype(P.dtype)
        P_low = pallas_tpu.transpose(P, out_uplo="L", out_dtype=ct)
        L = lax.linalg.cholesky(P_low, symmetrize_input=False)
        eye = jnp.eye(n, dtype=ct)
        Linv = lax.linalg.triangular_solve(L, eye, left_side=True, lower=True)
        R = pallas_tpu.transpose(L, out_uplo="U", out_dtype=P.dtype)
        Rinv = pallas_tpu.transpose(Linv, out_uplo="U", out_dtype=P.dtype)
    return (R, Rinv, detect.factor_info(R)) if with_info else (R, Rinv)


def geqrf(A: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Householder QR returning (Q, R) — the combined geqrf+orgqr capability
    (reference interface.hpp:61-89; upstream never calls these, see
    SURVEY §2 row 9)."""
    Q, R = jnp.linalg.qr(A.astype(_compute_dtype(A.dtype)), mode="reduced")
    return Q.astype(A.dtype), R.astype(A.dtype)


def orgqr(A: jnp.ndarray) -> jnp.ndarray:
    """Explicit Q from a Householder factorization (parity wrapper)."""
    return geqrf(A)[0]
