"""Rank-k Cholesky update / downdate kernels: online factor maintenance.

A served workload that repeatedly modifies a matrix it already factored
(Kalman smoothers, online GPs, recursive least-squares — ROADMAP item 4)
should not pay the O(n³/3) refactor on every step: given the upper factor
R of A = RᵀR and a rank-k perturbation A' = A ± V·Vᵀ, the factor R' of A'
is reachable in O(kn²) by a sweep of (hyperbolic) rotations — the
structural latency win on top of PR 6's kernel-level one.

Two implementations behind the PR 6 dispatch contract:

* ``impl='pallas'`` — the batched-grid rotation sweep, ONE ``pallas_call``
  over ``grid=(batch,)`` (batch axis on the grid, one problem per grid
  step, f32 compute).  Per rank q and column j the classic scalar
  recurrence runs as full-width one-hot contractions (the Mosaic-safe
  idiom of ops/batched_small, whose helpers this module reuses):

      t  = v_j / R_jj
      c  = sqrt(1 + σ·t²)            σ = +1 update, −1 downdate
      R'_j,: = (R_j,: + σ·t·v) / c
      v' = (v − t·R_j,:) / c

  A downdate loses positive-definiteness exactly where c² = 1 − t² ≤ 0;
  the in-kernel info follows the potrf convention — 0 healthy, j (1-based
  column) at the first bad rotation, n+1 for off-diagonal contamination —
  and the guarded divisor keeps the sweep total so info flags, NaNs tell
  (the ops/batched_small `_chol` discipline).

* ``impl='xla'`` — a blocked J-orthogonal panel scan in the operand's own
  dtype (the f64 route: `dtype_capable` gates f64 OUT of the pallas
  kernels unconditionally, and a forced ``impl='pallas'`` falls back here
  rather than silently downgrading the precision the caller paid for —
  the no-silent-downgrade dispatch contract).  Instead of n·k explicit
  rotations, each row-panel of width p is transformed at once: with
  P = R[j:j+p, j:j+p] the pivot block and Pv = Vᵀ[:, j:j+p],

      M  = PᵀP + σ·PvᵀPv            (the updated panel gram)
      R'[j:j+p, :] = chol(M)⁻ᵀ · (Pᵀ·R[j:j+p, :] + σ·Pvᵀ·Vᵀ)
      K  = I_k − σ·Pv·M⁻¹·Pvᵀ
      Vᵀ' = chol(K)⁻¹ · (Vᵀ − (M⁻¹Pvᵀ)ᵀ·(Pᵀ·R[j:j+p, :] + σ·Pvᵀ·Vᵀ))

  Any J-orthogonal completion of the panel transform yields the same R'
  (Vᵀ' is unique up to a k×k orthogonal rotation, which the recurrence
  never observes), so the panel form is exact — and it is all level-3
  matmuls, ~(4p + 4k + 2k²/p)·n² flops at panel width p ≈ k.  Breakdown
  surfaces through chol(M)/chol(K) (robust/detect.factor_info per panel,
  min-combined to a global potrf index at panel resolution).

Serve threads these through `serve/factorcache.py` residency (the ops
become `chol_update`/`chol_downdate` bucket programs against resident
factors — docs/SERVING.md "Factor residency"); a failed downdate degrades
to a fresh refactor at the landing hook, never a silent wrong answer
(docs/ROBUSTNESS.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from capital_tpu.ops.batched_small import (
    SMALL_N_MAX,
    _any_nonfinite,
    _batched_call,
    _fori,
    _gdot,
    _info_shape,
    _iota,
    _oh_row,
    _oh_col,
    _resolve_block,
    _safe_div,
    _store_info,
    _triu,
    dtype_capable,
)
from capital_tpu.ops.pallas_tpu import _device_budget, _interpret_default
from capital_tpu.robust import detect
from capital_tpu.utils import tracing

IMPLS = ("auto", "pallas", "xla")

__all__ = [
    "IMPLS",
    "chol_update",
    "chol_downdate",
    "eligible",
    "default_impl",
    "resolve_panel",
    "dtype_capable",
]


def eligible(n: int, k: int, dtype, *,
             interpret: bool | None = None) -> bool:
    """VMEM-envelope gate for ONE problem of the rotation-sweep kernel:
    R in/out at dtype + V at dtype + the f32 working set (live factor,
    carried v row, one-hot temporaries).  Same 0.85x budget headroom and
    interpret-mode bypass as batched_small.eligible."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        return True
    limit = 0.85 * (_device_budget()[1] or (16 << 20))
    item = jnp.dtype(dtype).itemsize
    need = (2 * n * n + n * k) * item + 4 * (2 * n * n + 3 * n)
    return need <= limit


def default_impl(n: int, k: int, dtype, *,
                 interpret: bool | None = None) -> str:
    """Resolve impl='auto': 'pallas' where the batched-grid sweep owns the
    latency (small n, VMEM-eligible, f32-or-narrower), else 'xla'.  f64
    ALWAYS takes xla (dtype_capable) — exact dtype, no downgrade."""
    if not dtype_capable(dtype):
        return "xla"
    if n > SMALL_N_MAX:
        return "xla"
    return "pallas" if eligible(n, k, dtype, interpret=interpret) else "xla"


def resolve_panel(n: int, k: int, panel: int = 0) -> int:
    """Panel width for the blocked XLA path: ~2k rows per panel (the flop
    count is (2p + 4k + 2k²/p)n² but the trsm/cholesky dispatch overhead
    per panel pushes the measured optimum above the flop optimum of k),
    clamped to [4, 64] and decremented to the nearest divisor of n so the
    scan is rectangular — the knob the update autotune space sweeps."""
    p = min(panel or max(4, min(64, 2 * k)), n)
    while n % p:
        p -= 1
    return max(p, 1)


def _check_update(R, V, op):
    if R.ndim != 3 or R.shape[1] != R.shape[2]:
        raise ValueError(
            f"{op}: factor batch must be (batch, n, n), got {R.shape}")
    if V.ndim != 3 or V.shape[:2] != R.shape[:2]:
        raise ValueError(
            f"{op}: rank-k batch must be (batch, n, k) riding factor "
            f"{R.shape}, got {V.shape}")


def _resolve_impl(impl: str, dtype, n: int, k: int, interpret) -> str:
    if impl not in IMPLS:
        raise ValueError(f"update impl must be one of {IMPLS}, got {impl!r}")
    if impl == "auto":
        return default_impl(n, k, dtype, interpret=interpret)
    if impl == "pallas" and not dtype_capable(dtype):
        # the no-silent-downgrade dispatch contract (PR 6): the kernels
        # compute in f32, so honoring a forced 'pallas' for f64 would
        # silently downgrade the precision the caller paid for
        return "xla"
    return impl


# --------------------------------------------------------------------------
# pallas rotation sweep
# --------------------------------------------------------------------------


def _pallas_sweep(R, V, sign: float, *, block, precision, interpret):
    batch, n, _ = R.shape
    k = V.shape[-1]
    bs = _resolve_block(n, block)
    s = float(sign)  # python scalar: weak-typed in-kernel, no captured const

    def kernel(r_ref, v_ref, out_ref, info_ref):
        Rm = r_ref[0].astype(jnp.float32)
        Vm = v_ref[0].astype(jnp.float32)

        def col_step(j, carry):
            Rc, v, info = carry
            ohr = _oh_row(j, n)
            ohc = _oh_col(j, n)
            rrow = _gdot(ohr, Rc, 1, 0, precision)  # R[j, :] as (1, n)
            d = jnp.sum(rrow * ohr)
            vj = jnp.sum(v * ohr)
            t = vj / _safe_div(d)
            c2 = 1.0 + s * t * t
            good = jnp.isfinite(d) & (d > 0) & jnp.isfinite(c2) & (c2 > 0)
            info = jnp.where((info == 0) & ~good,
                             jnp.asarray(j + 1, jnp.int32), info)
            cinv = jax.lax.rsqrt(jnp.where(good, c2, jnp.float32(1.0)))
            # row j lives in columns >= j; mask the rotation's sub-diagonal
            # roundoff residue so the factor stays exactly upper
            after = (_iota((1, n), 1) >= j).astype(jnp.float32)
            newrow = (rrow + (s * t) * v) * cinv * after
            vnew = (v - t * rrow) * cinv
            Rc = Rc + _gdot(ohc, newrow - rrow, 1, 0, precision)
            return Rc, vnew, info

        def col_block(p, carry):
            for t in range(bs):
                carry = col_step(p * bs + t, carry)
            return carry

        def rank_step(q, carry):
            Rc, info = carry
            v = _gdot(_oh_row(q, k), Vm, 1, 1, precision)  # V[:, q] as row
            Rc, _, info = _fori(n // bs, col_block, (Rc, v, info))
            return Rc, info

        Rm, info = _fori(k, rank_step, (Rm, jnp.int32(0)))
        off_bad = _any_nonfinite(Rm)
        info = jnp.where((info == 0) & off_bad, jnp.int32(n + 1), info)
        out_ref[0] = _triu(Rm).astype(r_ref.dtype)
        _store_info(info_ref, info)

    R2, info = _batched_call(
        kernel, [R, V],
        [((batch, n, n), R.dtype), _info_shape(batch)],
        name="rotation_sweep", interpret=interpret,
        flops=batch * tracing.chol_update_flops(n, k),
        bytes_accessed=batch * (2 * n * n + n * k)
        * jnp.dtype(R.dtype).itemsize,
    )
    return R2, info.reshape(batch)


# --------------------------------------------------------------------------
# XLA blocked J-orthogonal panel scan (exact dtype — the f64 path)
# --------------------------------------------------------------------------


def _tri_lsolve(L, B):
    """Batched lower-triangular left solve L·X = B.  Unlike the long-n
    solves in models/blocktri (where XLA:CPU's batched triangular_solve
    degrades to an in-HLO loop), the (p, p)/(k, k) operands here are small
    enough that the batched trsm custom call wins — measured ~1.6x over
    the whole sweep vs. routing the same solves through batched LU."""
    return jax.lax.linalg.triangular_solve(
        L, B, left_side=True, lower=True, transpose_a=False)


def _xla_panel_scan(R, V, sign: float, *, panel, precision):
    batch, n, _ = R.shape
    k = V.shape[-1]
    p = resolve_panel(n, k, panel)
    npan = n // p
    s = jnp.asarray(sign, R.dtype)
    Vt0 = jnp.swapaxes(V, 1, 2)  # (batch, k, n)
    # row-panels of R; panel i's rows are untouched until the scan reaches
    # it (each rotation only modifies the current row and v), so the
    # original panels ARE the scan xs
    Rp = jnp.moveaxis(R.reshape(batch, npan, p, n), 1, 0)
    j0s = jnp.arange(npan, dtype=jnp.int32) * p

    def body(carry, xs):
        Vt, info = carry
        rp, j0 = xs  # (batch, p, n), scalar panel offset
        Pp = jax.lax.dynamic_slice_in_dim(rp, j0, p, axis=2)
        Pv = jax.lax.dynamic_slice_in_dim(Vt, j0, p, axis=2)
        M = (jnp.einsum("zij,zil->zjl", Pp, Pp, precision=precision)
             + s * jnp.einsum("zkj,zkl->zjl", Pv, Pv, precision=precision))
        Lm = jnp.linalg.cholesky(M)
        li = jax.vmap(detect.factor_info)(Lm)
        Z = (jnp.einsum("zij,zin->zjn", Pp, rp, precision=precision)
             + s * jnp.einsum("zkj,zkn->zjn", Pv, Vt, precision=precision))
        newrows = _tri_lsolve(Lm, Z)
        # Reuse Lm instead of a second factorization of M: with
        # Q = Lm⁻¹Pvᵀ the capacitance K = I − σ·PvM⁻¹Pvᵀ = I − σ·QᵀQ and
        # the carry correction WᵀZ = Pv·M⁻¹·Z = Qᵀ·newrows.
        Q = _tri_lsolve(Lm, jnp.swapaxes(Pv, 1, 2))  # (batch, p, k)
        K = (jnp.eye(k, dtype=R.dtype)
             - s * jnp.einsum("zjk,zjl->zkl", Q, Q, precision=precision))
        Lk = jnp.linalg.cholesky(K)
        ki = jax.vmap(detect.factor_info)(Lk)
        Vt = _tri_lsolve(Lk, Vt - jnp.einsum("zjk,zjn->zkn", Q, newrows,
                                             precision=precision))
        # panel-resolution breakdown info: chol(M)'s local pivot maps to
        # the exact global column j0+li; a chol(K) failure implicates the
        # whole panel and reports its first column.  First failure wins
        # (the sweep order is the rotation order).
        gi = jnp.where(li == 0, 0,
                       jnp.where(li <= p, j0 + li, jnp.int32(n + 1)))
        gi = jnp.where((gi == 0) & (ki != 0), j0 + 1, gi)
        info = jnp.where((info == 0) & (gi != 0), gi.astype(jnp.int32),
                         info)
        return (Vt, info), newrows

    (_, info), rows = jax.lax.scan(
        body, (Vt0, jnp.zeros((batch,), jnp.int32)), (Rp, j0s))
    R2 = jnp.moveaxis(rows, 0, 1).reshape(batch, n, n)
    tri = _iota((n, n), 0) <= _iota((n, n), 1)
    R2 = jnp.where(tri, R2, jnp.zeros((), R.dtype))
    off_bad = ~jnp.all(jnp.isfinite(R2), axis=(1, 2))
    info = jnp.where((info == 0) & off_bad, jnp.int32(n + 1), info)
    return R2, info


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------


def _apply(R, V, sign: float, tag: str, op: str, *, block, panel,
           precision, impl, interpret):
    _check_update(R, V, op)
    batch, n, _ = R.shape
    k = V.shape[-1]
    if interpret is None:
        interpret = _interpret_default()
    impl = _resolve_impl(impl, R.dtype, n, k, interpret)
    with tracing.scope(tag):
        tracing.emit(flops=batch * tracing.chol_update_flops(n, k))
        if impl == "pallas":
            return _pallas_sweep(R, V, sign, block=block,
                                 precision=precision, interpret=interpret)
        return _xla_panel_scan(R, V, sign, panel=panel,
                               precision=precision)


def chol_update(R, V, *, block: int = 0, panel: int = 0,
                precision: str | None = "highest", impl: str = "auto",
                interpret: bool | None = None):
    """Rank-k Cholesky UPDATE: given upper R with A = RᵀR, return
    (R', info) with R'ᵀR' = A + V·Vᵀ.  R (batch, n, n) upper, V
    (batch, n, k).  info (batch,) int32 potrf convention — an update of a
    healthy factor cannot break down, so nonzero info here means the
    input factor was already bad (non-positive diagonal)."""
    return _apply(R, V, +1.0, "UP::update", "chol_update", block=block,
                  panel=panel, precision=precision, impl=impl,
                  interpret=interpret)


def chol_downdate(R, V, *, block: int = 0, panel: int = 0,
                  precision: str | None = "highest", impl: str = "auto",
                  interpret: bool | None = None):
    """Rank-k Cholesky DOWNDATE: (R', info) with R'ᵀR' = A − V·Vᵀ.  Loses
    positive-definiteness when A − V·Vᵀ is not SPD: info flags the first
    bad rotation column (pallas) or panel pivot (xla) in the potrf
    convention, and R' is flagged garbage there — the serve landing hook
    degrades a flagged downdate to a fresh refactor from the still-intact
    resident factor (docs/ROBUSTNESS.md), never a silent wrong answer."""
    return _apply(R, V, -1.0, "UP::downdate", "chol_downdate", block=block,
                  panel=panel, precision=precision, impl=impl,
                  interpret=interpret)
