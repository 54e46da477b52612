"""Residual / validation norms — the framework's correctness gates.

TPU-native equivalent of the reference's validation layer
(test/cholesky/validate.hpp, test/qr/validate.hpp, src/util/util.hpp:3-53):
relative Frobenius residuals computed *in the distributed layout*.  The
reference accumulates local squared errors and combines them with
``MPI_Allreduce`` over the slice communicator (util.hpp:25-53); here the same
computation is a global jnp reduction — XLA inserts the cross-device psum
automatically from the operands' shardings, so one implementation serves both
the single-chip and the multi-chip mesh cases.

The norms accept (possibly sharded) jax Arrays and return scalars; the
gate (`tolerance`) and the test operands they are checked on
(`spd_operand`, `tri_operand`) live here with them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Validation matmuls run at precision='highest' unconditionally: on TPU the
# default f32 matmul uses bf16-grade MXU passes, which floors the measurable
# residual near 1e-4 and would mask a genuinely bad factor (observed: a
# correct n=1024 f32 factor 'failing' at 4.6e-4 purely from the gate's own
# product).  Gates are not on the timed path; full precision is free here.
_PREC = "highest"


def tolerance(dtype) -> float:
    """Residual gate by dtype: the reference's f64/MPI runs sit at ~1e-14
    (SURVEY §4); scaled to the working precision here."""
    return {2: 5e-2, 4: 5e-5, 8: 1e-13}[jnp.dtype(dtype).itemsize]


def spd_operand(n: int, dtype, seed: int = 0) -> jnp.ndarray:
    """Well-conditioned SPD test matrix, built on device (Wigner + dominant
    diagonal — same spectrum family as the reference's distribute_symmetric
    diagonal dominance, structure.hpp:87-89)."""
    @jax.jit
    def make(key):
        M = jax.random.normal(key, (n, n), dtype=jnp.float32)
        A = (M + M.T) / jnp.sqrt(2.0 * n)
        # 3I, not 2I: the Wigner semicircle edge sits at exactly 2, so a
        # 2I shift leaves lambda_min grazing zero and f32 cholesky can NaN
        # depending on the RNG stream
        return (A + 3.0 * jnp.eye(n, dtype=M.dtype)).astype(dtype)

    return jax.block_until_ready(make(jax.random.key(seed)))


def tri_operand(n: int, dtype, seed: int = 0) -> jnp.ndarray:
    """Well-conditioned lower-triangular test matrix, built DIRECTLY at
    dtype (no chol-of-SPD setup — its two extra f32 n² staging buffers
    OOM'd n=32768 on one v5e).  Off-diagonal scale 1/sqrt(n): kappa ~ 2 at
    every n (measured 1.9-2.0 at 512-8192 in f64) while the off-diagonal
    part carries ~23% of the matrix norm, so a residual gate still SEES
    off-diagonal bugs — a 1/n scale would shrink them ~sqrt(n)x below the
    bf16 tolerance."""

    @jax.jit
    def make(key):
        G = jax.random.normal(key, (n, n), dtype=jnp.float32)
        L = jnp.tril(G, -1) / jnp.sqrt(
            jnp.asarray(n, jnp.float32)
        ) + 3.0 * jnp.eye(n, dtype=jnp.float32)
        return L.astype(dtype)

    return jax.block_until_ready(make(jax.random.key(seed)))


def rel_fro(err: jnp.ndarray, ref: jnp.ndarray) -> jnp.ndarray:
    """sqrt(sum(err^2)) / sqrt(sum(ref^2)) — reference util::residual_local
    (util.hpp:25-53) without the lambda indirection."""
    num = jnp.sqrt(jnp.sum(jnp.square(err)))
    den = jnp.sqrt(jnp.sum(jnp.square(ref)))
    return num / den


def cholesky_residual(A: jnp.ndarray, R: jnp.ndarray) -> jnp.ndarray:
    """‖A − RᵀR‖_F / ‖A‖_F for upper-triangular R.

    Reference: cholesky::validate::residual (test/cholesky/validate.hpp:7-49),
    which forms RᵀR−A via a SUMMA gemm with beta=−1.  Here the matmul is a
    plain jnp.dot: under jit with sharded operands XLA plans the same
    distributed contraction.
    """
    return rel_fro(A - jnp.matmul(R.T, R, precision=_PREC), A)


def cholesky_inverse_residual(R: jnp.ndarray, Rinv: jnp.ndarray) -> jnp.ndarray:
    """‖I − R·R⁻¹‖_F / ‖I‖_F — reference util::get_identity_residual
    (util.hpp:3-23)."""
    n = R.shape[0]
    eye = jnp.eye(n, dtype=R.dtype)
    return rel_fro(eye - jnp.matmul(R, Rinv, precision=_PREC), eye)


def qr_orthogonality(Q: jnp.ndarray) -> jnp.ndarray:
    """‖I − QᵀQ‖_F / ‖I‖_F — reference qr::validate::orthogonality
    (test/qr/validate.hpp:7-32)."""
    n = Q.shape[1]
    eye = jnp.eye(n, dtype=Q.dtype)
    return rel_fro(eye - jnp.matmul(Q.T, Q, precision=_PREC), eye)


def qr_residual(A: jnp.ndarray, Q: jnp.ndarray, R: jnp.ndarray) -> jnp.ndarray:
    """‖A − QR‖_F / ‖A‖_F — reference qr::validate::residual
    (test/qr/validate.hpp:37-52).  Computed at the f32-floor dtype so the
    gate's own accumulation noise (a bf16 sum over m·n squares) cannot
    mask or manufacture a failure — same arithmetic as the blocked form,
    so the two gates agree for any m."""
    ct = jnp.promote_types(A.dtype, jnp.float32)
    err = A.astype(ct) - jnp.matmul(
        Q.astype(ct), R.astype(ct), precision=_PREC
    )
    return rel_fro(err, A.astype(ct))


def qr_residual_blocked(
    A: jnp.ndarray, Q: jnp.ndarray, R: jnp.ndarray, block_rows: int = 65536
) -> jnp.ndarray:
    """qr_residual accumulated over row blocks with a lax.scan: O(block·n)
    extra memory instead of several m x n f32 temporaries — the dense form
    OOMs validating the 2M x 1024 BASELINE shape on one v5e (the
    FACTORIZATION fits; the residual's f32 err/QR buffers did not).
    Falls back to the dense form when block_rows does not tile m."""
    m, n = A.shape
    if m % block_rows or m == block_rows:
        return qr_residual(A, Q, R)
    ct = jnp.promote_types(A.dtype, jnp.float32)  # f32 floor, f64 kept
    Rt = R.astype(ct)  # R as given, like the dense form (no silent triu)
    Ab = A.reshape(m // block_rows, block_rows, n)
    Qb = Q.reshape(m // block_rows, block_rows, n)

    def step(carry, ab_qb):
        ab, qb = ab_qb
        ab = ab.astype(ct)
        err = ab - jnp.matmul(qb.astype(ct), Rt, precision=_PREC)
        return (
            (carry[0] + jnp.sum(jnp.square(err)),
             carry[1] + jnp.sum(jnp.square(ab))),
            None,
        )

    zero = jnp.zeros((), ct)
    (num, den), _ = jax.lax.scan(step, (zero, zero), (Ab, Qb))
    return jnp.sqrt(num) / jnp.sqrt(den)


def inverse_residual(A: jnp.ndarray, Ainv: jnp.ndarray) -> jnp.ndarray:
    """‖I − A·A⁻¹‖_F / ‖I‖_F — reference test/inverse/validate.hpp:12-24
    (that file is bit-rotted upstream; this is the working equivalent).
    Error and norm accumulate at the f32 floor (same arithmetic as the
    blocked form below, so the two gates agree for any n — the qr pair's
    alignment rule)."""
    n = A.shape[0]
    ct = jnp.promote_types(A.dtype, jnp.float32)
    eye = jnp.eye(n, dtype=ct)
    prod = jnp.matmul(A, Ainv, precision=_PREC, preferred_element_type=ct)
    return rel_fro(eye - prod, eye)


def inverse_residual_blocked(
    A: jnp.ndarray, Ainv: jnp.ndarray, block_rows: int = 4096
) -> jnp.ndarray:
    """inverse_residual accumulated over row blocks with a lax.scan:
    O(block·n) extra memory instead of the n x n f32 product — the dense
    form OOMs validating the n=49152 rectri row on one v5e (two 4.8 GB
    bf16 operands fit; the 9.7 GB f32 I−A·A⁻¹ did not).  Same qr_residual
    pattern (qr_residual_blocked above).  Operands enter the contraction
    at their own dtype with an f32-floor accumulator (no upcast copy of
    Ainv — bf16 inputs are exact into f32, so values match the dense
    form).  When block_rows does not tile n, the largest divisor of n
    <= block_rows is used instead (no silent dense cliff at large
    unaligned n); only n <= block_rows takes the dense form."""
    n = A.shape[0]
    if n <= block_rows:
        return inverse_residual(A, Ainv)
    br = next(b for b in range(min(block_rows, n), 0, -1) if n % b == 0)
    ct = jnp.promote_types(A.dtype, jnp.float32)
    Ab = A.reshape(n // br, br, n)

    def step(carry, ab_i):
        ab, i = ab_i
        prod = jnp.matmul(ab, Ainv, precision=_PREC, preferred_element_type=ct)
        # subtract this block's slice of the identity: rows
        # [i*br, (i+1)*br) have their ones at the same global columns
        r = jax.lax.broadcasted_iota(jnp.int32, (br, n), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (br, n), 1)
        err = jnp.where(c == r + i * br, prod - 1.0, prod)
        return carry + jnp.sum(jnp.square(err)), None

    num, _ = jax.lax.scan(
        step, jnp.zeros((), ct), (Ab, jnp.arange(n // br))
    )
    return jnp.sqrt(num) / jnp.sqrt(jnp.asarray(n, ct))
