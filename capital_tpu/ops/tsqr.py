"""Blocked Householder TSQR: the unconditionally stable tall-skinny QR.

CholeskyQR-family methods square the condition number through the gram
(models/qr.py; CA-CQR2 arXiv:1710.08471), so past cond(A) ~ u^{-1/2} even
the shifted sCQR3 ladder stalls and the robust path returns the honest
`info = n + 2` sentinel (docs/ROBUSTNESS.md).  TSQR (Demmel, Grigori,
Hoemmen, Langou, "Communication-optimal parallel and sequential QR",
arXiv:0809.2407) never forms a gram: a tree of small Householder QRs is
backward stable for ANY cond(A) the dtype can represent, at ~2x the flops
of one CholeskyQR sweep.  This is the escalation target that retires the
sentinel for matrices the compute dtype can handle at all
(robust/recovery.tsqr_escalate).

Shape of the computation:

* **leaves** — A's rows are padded with zero rows to `leaves * panel`
  (leaves a power of two) and split into (panel, n) row panels; each panel
  gets an independent Householder QR.  Zero-row padding is exact: a padded
  row of A = Q·R forces the matching Q rows to zero (R is invertible for
  full-rank A), so the unpadded Q is a plain slice.
* **reduction** — pairs of (n, n) R factors stack into (2n, n) panels and
  re-factor, halving the count per level; ``log2(leaves)`` levels leave ONE
  R.  Each level's thin-Q blocks multiply into the per-leaf Q accumulators
  (a batched gemm), so the final Q assembles top-down without ever
  materializing an (m, m) factor.

Leaf/reduction panel QRs have two interchangeable implementations behind
the PR 6 dispatch-gate resolver (`default_impl`, mirroring
ops/batched_small): a batched-grid Pallas Householder kernel (batch of
panels on the grid, each panel VMEM-resident through both the reflector
sweep and the thin-Q assembly — f32 compute, one-hot contractions and
iota masks only) for small f32/bf16 panels, and a batched
``lax.linalg.qr`` fallback.  f64 ALWAYS takes the XLA route — the Pallas
kernels compute in f32, and honoring a forced impl='pallas' on f64 input
would silently downgrade precision behind f64-labeled outputs
(batched_small.dtype_capable, the PR 6 contract).  All resolution reads
static shapes/dtypes only, so callers keep the zero-recompile invariant.

Like the other Pallas ops the kernels run in interpret mode off-TPU and
the VMEM gate is bypassed there (CPU CI rides the same route the hardware
does).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from capital_tpu.ops import batched_small
from capital_tpu.ops.batched_small import (
    _batched_call,
    _fori,
    _gdot,
    _iota,
    _oh_row,
    _resolve_block,
)
from capital_tpu.ops.pallas_tpu import _device_budget, _interpret_default
from capital_tpu.utils import tracing

IMPLS = ("auto", "pallas", "xla")

#: Largest panel column count the auto resolver routes to the Pallas leaf
#: kernel — the same boundary as the batched small-N solves (above it the
#: reflector sweep's executed-flop overhead outweighs the launch saving).
SMALL_N_MAX = batched_small.SMALL_N_MAX


def _compute_dtype(dtype):
    # panel QRs run at >= f32 exactly like the LAPACK seam
    # (ops/lapack._compute_dtype; restated to keep this module free of the
    # lapack -> robust import chain)
    return jnp.float32 if jnp.dtype(dtype).itemsize < 4 else jnp.dtype(dtype)


def resolve_panel(m: int, n: int, panel: int = 0) -> int:
    """Leaf panel row count: requested `panel` clamped to >= n (a leaf must
    be at least square to produce an (n, n) R), default 2n rounded up to
    128 — tall enough that the reduction tree stays shallow, small enough
    that a leaf panel is VMEM-resident at serve's bucket sizes."""
    if panel:
        return max(panel, n)
    return max(2 * n, 128)


def resolve_leaves(m: int, n: int, panel: int = 0) -> int:
    """Leaf count: ceil(m / panel) rounded UP to a power of two, so the
    pairwise reduction closes without remainder handling (the extra
    leaves are all-zero pads, whose R factors are exact zeros)."""
    p = resolve_panel(m, n, panel)
    raw = max(-(-m // p), 1)
    return 1 << (raw - 1).bit_length()


def eligible(rows: int, n: int, dtype, *,
             interpret: bool | None = None) -> bool:
    """VMEM-envelope gate for ONE (rows, n) panel of the batched-grid
    kernel: the panel at `dtype` plus the f32 working set (live panel W,
    reflector store V, thin-Q accumulator E, and the sweep temporaries).
    Interpret mode bypasses — batched_small.eligible discipline."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        return True
    limit = 0.85 * (_device_budget()[1] or (16 << 20))
    item = jnp.dtype(dtype).itemsize
    need = 2 * rows * n * item + 4 * (4 * rows * n + n * n)
    return need <= limit


def default_impl(rows: int, n: int, dtype, *,
                 interpret: bool | None = None) -> str:
    """Resolve impl='auto' for one batch of (rows, n) panels: 'pallas'
    where the batched-grid kernel owns the latency (narrow dtype, small n,
    VMEM-eligible), else 'xla'.  f64 ALWAYS takes xla (dtype_capable)."""
    if not batched_small.dtype_capable(dtype):
        return "xla"
    if n > SMALL_N_MAX:
        return "xla"
    return ("pallas" if eligible(rows, n, dtype, interpret=interpret)
            else "xla")


# --------------------------------------------------------------------------
# panel QR: batched XLA reference + batched-grid Pallas kernel
# --------------------------------------------------------------------------


def _qr_xla(P, precision):
    """Batched thin Householder QR via lax.linalg.qr — the exact-dtype
    reference and the mandatory f64 route."""
    del precision  # lax.linalg.qr has no precision knob
    Q, R = lax.linalg.qr(P, full_matrices=False)
    return Q, jnp.triu(R)


def _house_panel(a, *, block: int, precision):
    """In-kernel Householder QR of ONE f32 (p, n) panel VALUE: ascending
    reflector sweep (column j's below-diagonal part -> unit v_j, stored in
    column j of V; H_j = I − 2·v_j·v_jᵀ applied to the live panel), then a
    descending sweep applies the stored reflectors to I_{p×n} for the thin
    Q.  Every step is a one-hot contraction or an iota-masked elementwise
    op (the batched_small Mosaic discipline — no dynamic lane slicing).
    A zero column below the diagonal yields v = 0 (H = identity), so
    zero-padded panels factor EXACTLY to (Q=anything·0-safe, R=0) and
    rank deficiency degrades like LAPACK's (zero R diagonal, no NaN)."""
    p, n = a.shape
    W0, V0 = a, jnp.zeros_like(a)

    def col_step(j, W, V):
        colw = _gdot(W, _oh_row(j, n), 1, 1, precision)  # W[:, j] as (p, 1)
        rows = _iota((p, 1), 0)
        x = colw * (rows >= j).astype(jnp.float32)
        ohj = (rows == j).astype(jnp.float32)
        xj = jnp.sum(x * ohj)
        sig = jnp.sqrt(jnp.sum(x * x))
        alpha = -jnp.where(xj >= 0, jnp.float32(1), jnp.float32(-1)) * sig
        v = x - alpha * ohj
        vn2 = jnp.sum(v * v)
        v = v * jnp.where(
            vn2 > 0, lax.rsqrt(jnp.where(vn2 > 0, vn2, jnp.float32(1.0))),
            jnp.float32(0.0),
        )
        vtW = _gdot(v, W, 0, 0, precision)  # (1, n)
        W = W - 2.0 * _gdot(v, vtW, 1, 0, precision)
        V = V + _gdot(v, _oh_row(j, n), 1, 0, precision)  # place v at col j
        return W, V

    def sweep_body(q, carry):
        W, V = carry
        for t in range(block):
            W, V = col_step(q * block + t, W, V)
        return W, V

    W, V = _fori(n // block, sweep_body, (W0, V0))

    # R = top n rows of the swept panel, upper-masked (sub-diagonal residue
    # is reflector roundoff, exactly like geqrf's packed storage)
    sel = (_iota((n, p), 0) == _iota((n, p), 1)).astype(jnp.float32)
    R = _gdot(sel, W, 1, 0, precision)
    R = jnp.where(_iota((n, n), 0) <= _iota((n, n), 1), R, 0.0)

    # thin Q: apply H_{n-1}..H_0 to the first n columns of I_p
    E0 = (_iota((p, n), 0) == _iota((p, n), 1)).astype(jnp.float32)

    def q_step(j, E):
        v = _gdot(V, _oh_row(j, n), 1, 1, precision)  # (p, 1)
        vtE = _gdot(v, E, 0, 0, precision)
        return E - 2.0 * _gdot(v, vtE, 1, 0, precision)

    def q_body(q, E):
        for t in range(block):
            E = q_step(n - 1 - (q * block + t), E)
        return E

    Q = _fori(n // block, q_body, E0)
    return Q, R


def _qr_pallas(P, *, block: int, precision, interpret):
    """Batched-grid panel QR: ONE pallas_call with the panel batch on the
    grid; each grid step's panel stays VMEM-resident through the reflector
    sweep, the R extraction, and the thin-Q assembly."""
    batch, p, n = P.shape
    bs = _resolve_block(n, block)

    def kernel(a_ref, q_ref, r_ref):
        a = a_ref[0].astype(jnp.float32)
        Q, R = _house_panel(a, block=bs, precision=precision)
        q_ref[0] = Q.astype(a_ref.dtype)
        r_ref[0] = R.astype(a_ref.dtype)

    Q, R = _batched_call(
        kernel, [P],
        [((batch, p, n), P.dtype), ((batch, n, n), P.dtype)],
        name="panel_qr", interpret=interpret,
        flops=batch * 6.0 * p * n * n,
        bytes_accessed=batch * (2 * p * n + n * n)
        * jnp.dtype(P.dtype).itemsize,
    )
    return Q, R


def _qr_batch(P, impl: str, *, block: int, precision, interpret):
    """One batch of (rows, n) panels through the resolved route.  A forced
    'pallas' on an incapable dtype (f64) still takes xla — never a silent
    precision downgrade (the batched_small fallback contract)."""
    rows, n = P.shape[-2], P.shape[-1]
    pick = impl
    if impl == "auto":
        pick = default_impl(rows, n, P.dtype, interpret=interpret)
    elif impl == "pallas" and not batched_small.dtype_capable(P.dtype):
        pick = "xla"
    if pick == "pallas":
        return _qr_pallas(P, block=block, precision=precision,
                          interpret=interpret)
    return _qr_xla(P, precision)


# --------------------------------------------------------------------------
# the tree
# --------------------------------------------------------------------------


def tsqr(A, *, panel: int = 0, block: int = 0,
         precision: str | None = "highest", impl: str = "auto",
         interpret: bool | None = None):
    """Blocked Householder TSQR of tall-skinny A: returns (Q, R) with
    A = Q·R, Q (m, n) with orthonormal columns to working precision at ANY
    cond(A), R (n, n) upper triangular.  Computes at the >= f32 dtype and
    casts back once (the ops-layer convention); callers needing the
    always-f64 escalation grade go through robust/recovery.tsqr_escalate,
    which upcasts BEFORE calling."""
    if A.ndim != 2 or A.shape[0] < A.shape[1]:
        raise ValueError(f"tsqr expects one tall-skinny matrix, got {A.shape}")
    if impl not in IMPLS:
        raise ValueError(f"tsqr impl must be one of {IMPLS}, got {impl!r}")
    m, n = A.shape
    if interpret is None:
        interpret = _interpret_default()
    p = resolve_panel(m, n, panel)
    leaves = resolve_leaves(m, n, panel)

    with tracing.scope("QR::tsqr"):
        tracing.emit(flops=tracing.tsqr_flops(m, n, leaves))
        ct = _compute_dtype(A.dtype)
        Ap = A.astype(ct)
        mp = leaves * p
        if mp > m:
            Ap = jnp.pad(Ap, ((0, mp - m), (0, 0)))
        panels = Ap.reshape(leaves, p, n)
        Qacc, Rs = _qr_batch(panels, impl, block=block,
                             precision=precision, interpret=interpret)
        level_count = leaves
        while level_count > 1:
            S = jnp.concatenate([Rs[0::2], Rs[1::2]], axis=1)  # (L/2, 2n, n)
            Qp, Rs = _qr_batch(S, impl, block=block,
                               precision=precision, interpret=interpret)
            # per-child (n, n) factor: node i's top block belongs to child
            # 2i, bottom block to child 2i+1 — every ORIGINAL leaf under a
            # child multiplies its accumulator by that child's factor
            F = jnp.stack([Qp[:, :n], Qp[:, n:]], axis=1)
            F = F.reshape(level_count, n, n)
            group = leaves // level_count
            Qacc = jnp.matmul(
                Qacc.reshape(level_count, group, p, n), F[:, None],
                precision=precision,
            ).reshape(leaves, p, n)
            level_count //= 2
        Q = Qacc.reshape(mp, n)[:m]
        R = Rs[0]
    return Q.astype(A.dtype), R.astype(A.dtype)


def ortho_gate(Q, precision: str | None = "highest"):
    """The ladder's orthogonality measurement ||I − QᵀQ||_F / sqrt(n) at
    Q's own dtype — shared by the escalation wiring and the bench gate so
    the two can never drift apart."""
    n = Q.shape[-1]
    G = jnp.matmul(Q.T, Q, precision=precision)
    return (jnp.linalg.norm(G - jnp.eye(n, dtype=G.dtype))
            / jnp.sqrt(jnp.asarray(n, G.dtype))).astype(jnp.float32)
