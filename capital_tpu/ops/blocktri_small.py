"""Block-tridiagonal scan-step Pallas kernels: SEG chain blocks per launch.

`models/blocktri.py` factors a block-tridiagonal SPD chain

    A = [[D_1, C_2ᵀ            ],
         [C_2, D_2, C_3ᵀ       ],
         [     C_3, D_3, ...   ],
         [          ...        ]]

as A = L̃·L̃ᵀ with L̃_ii = L_i (lower Cholesky of the Schur complement
S_i = D_i − W_i·W_iᵀ) and L̃_{i,i−1} = W_i = C_i·L_{i−1}⁻ᵀ — O(nblocks·b³)
work against the dense O((nblocks·b)³).  The chain is inherently
sequential, so the models layer drives it as a `lax.scan`; THESE kernels
are the scan body: ONE ``pallas_call`` over ``grid=(batch,)`` processes
``seg`` consecutive chain blocks per problem, with the running diagonal
factor (and, fused, the running forward solution) carried in VMEM across
the in-kernel block loop — block i's factor is born in VMEM and consumed
by block i+1's triangular solve without an HBM round-trip.  ``seg`` is
the scan-segment-length knob the blocktri autotune space sweeps
(launches-per-chain vs VMEM residency); ``block`` is the same column
unroll `batched_small` sweeps.

Carried representation: the kernels store and carry **Wt = Wᵀ**, not W.
Wt_i solves the FORWARD system L_{i−1}·Wt_i = C_iᵀ (`_fwd_solve`, no
transposed-operand solve needed), the Schur update is the one-hot-safe
contraction Wtᵀ·Wt = W·Wᵀ, the forward coupling is Wtᵀ·y = W·y, and the
backward coupling is the plain product Wt_{i+1}·x_{i+1} = W_{i+1}ᵀ·x_{i+1}
— every step is a `_gdot` contraction; the single explicit transpose per
block (C_i → C_iᵀ) is an identity-matrix contraction, the one transpose
spelling Mosaic lowers well.

Uniformity contract (models layer): C_1 must be zero and the carry into
the first block is (L_0 = I, y_0 = 0), so step one computes Wt_1 = 0 and
S_1 = D_1 exactly — no special-cased first iteration, which is what lets
bucket padding prepend/append identity blocks bitwise-inertly.

Like `batched_small`, compute is f32 (sub-f32 operands upcast on VMEM
load, outputs round back on store), f64 is gated out by `dtype_capable`,
the kernels run in interpret mode off-TPU, and each problem owns its grid
step's VMEM blocks — an injected NaN corrupts exactly one problem, and
within a problem the chain only propagates it FORWARD (blocks before the
injection stay bitwise-correct).  Per-block potrf info (0 / k / b+1) is
computed in-kernel; the models layer min-combines it to a global pivot
index via `robust.detect.combine_block_infos`.

These kernels carry NO tracing scopes or emits: they run inside a
`lax.scan` body, where an emit would fire once at trace time while the
kernel executes `nsteps` times — the models layer prices the whole chain
(`tracing.blocktri_chol_flops` / `blocktri_solve_flops`) outside the scan
instead.  Only the per-call `CostEstimate` lives here.
"""

from __future__ import annotations

import jax.numpy as jnp

from capital_tpu.utils import tracing
from capital_tpu.ops.pallas_tpu import _device_budget, _interpret_default
from capital_tpu.ops.batched_small import (
    _batched_call,
    _bwd_solve,
    _chol,
    _fwd_solve,
    _gdot,
    _info_shape,
    _iota,
    _resolve_block,
    _store_info,
    dtype_capable,
)

__all__ = [
    "step_eligible",
    "default_impl",
    "partition_inner_impl",
    "fused_forward_step",
    "factor_step",
    "forward_solve_step",
    "solve_backward_step",
    "dtype_capable",
]


def step_eligible(b: int, k: int, seg: int, dtype,
                  *, interpret: bool | None = None) -> bool:
    """VMEM-envelope gate for ONE problem of a scan-step kernel: the step's
    `seg` blocks of operands and outputs, the (b, b)/(b, k) carries, and
    the f32 working set of one block iteration (Schur complement, live
    factor, Wt, coupling temporaries) must fit the device budget.  Same
    0.85x headroom and interpret-mode bypass as `batched_small.eligible`
    — the CPU rig must ride the same route hardware does."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        return True
    limit = 0.85 * (_device_budget()[1] or (16 << 20))
    item = jnp.dtype(dtype).itemsize
    per_block = 2 * b * b + b * k          # D + C + B of one chain block
    need = (
        item * (2 * seg * per_block + b * b + b * k)  # in + out + carries
        + 4 * (6 * b * b + 3 * b * k)                 # f32 working set
    )
    return need <= limit


def default_impl(b: int, k: int, seg: int, dtype,
                 *, interpret: bool | None = None) -> str:
    """Resolve impl='auto' for a blocktri chain: 'pallas' where the
    scan-step kernels own the latency (f32-or-narrower, VMEM-eligible),
    else 'xla' (scan of lax.linalg primitives — the f64 fallback, same
    dispatch-gate shape as PR 6's batched_small.default_impl)."""
    if not dtype_capable(dtype):
        return "xla"
    return ("pallas"
            if step_eligible(b, k, seg, dtype, interpret=interpret)
            else "xla")


def partition_inner_impl(b: int, k: int, seg: int, dtype,
                         *, interpret: bool | None = None) -> str:
    """Resolve the INNER impl of the partitioned (Spike) chain driver:
    its interior chains substitute a widened RHS [B | F | G] of k + 2b
    columns (the two spike column-blocks ride the same sweep as the local
    solutions), so the VMEM step envelope must be checked at that width —
    a chain whose sequential posv is pallas-eligible at width k can still
    overflow the step budget once the spikes widen it.  Same f64 → xla
    gate as `default_impl`; the partition axis folds into the batch axis
    of the grid, which costs no VMEM per step."""
    return default_impl(b, k + 2 * b, seg, dtype, interpret=interpret)


# --------------------------------------------------------------------------
# in-kernel block recurrence
# --------------------------------------------------------------------------


def _eye_f32(b: int):
    return (_iota((b, b), 0) == _iota((b, b), 1)).astype(jnp.float32)


def _lower(M):
    b = M.shape[0]
    return jnp.where(_iota((b, b), 0) >= _iota((b, b), 1), M, 0.0)


def _factor_block(d, c, Lp, *, bs: int, precision):
    """One chain block of the factor recurrence, all f32 VALUES:
    Wt = Lp⁻¹·cᵀ, S = d − Wtᵀ·Wt, (L, info) = chol(S) masked lower."""
    b = d.shape[0]
    ct = _gdot(c, _eye_f32(b), 0, 0, precision)        # cᵀ via identity dot
    wt = _fwd_solve(Lp, ct, from_upper=False, block=bs, precision=precision)
    s = d - _gdot(wt, wt, 0, 0, precision)             # Wtᵀ·Wt = W·Wᵀ
    L, info = _chol(s, uplo="L", block=bs, precision=precision)
    return _lower(L), wt, info


def _set_info(infos, s: int, info):
    """Lane `s` of the (1, seg) per-block info row := info (a vector
    select: Mosaic stores the row once, never a scalar)."""
    return jnp.where(_iota(infos.shape, 1) == s, info, infos)


def _check_steps(name, seg_operands, carries, b, k=None):
    for nm, x, nd in seg_operands:
        if x.ndim != 4 or x.shape[2:] != (b, b):
            raise ValueError(f"{name}: {nm} must be (batch, seg, b, b), "
                             f"got {x.shape}")
    for nm, x, shape in carries:
        if x.shape != shape:
            raise ValueError(f"{name}: carry {nm} must be {shape}, "
                             f"got {x.shape}")


# --------------------------------------------------------------------------
# scan-step kernels
# --------------------------------------------------------------------------


def fused_forward_step(D, C, B, Lc, yc, *, block: int = 0,
                       precision: str | None = "highest",
                       interpret: bool | None = None):
    """FUSED factor + forward-solve scan step: for each of `seg` chain
    blocks, factor S_i and immediately consume L_i for the forward sweep
    y_i = L_i⁻¹(b_i − Wtᵀ_i·y_{i−1}) while it is VMEM-resident — the
    factor→solve boundary of `posv_blocktri` never touches HBM.

    D, C: (batch, seg, b, b) chain blocks; B: (batch, seg, b, k) RHS;
    Lc: (batch, b, b) carried factor (I before block 1); yc: (batch, b, k)
    carried forward solution (0 before block 1).  Returns
    (L, Wt, y, info): per-block factors (batch, seg, b, b), transposed
    subdiagonal factors, forward solutions (batch, seg, b, k), and
    per-block potrf info (batch, seg) int32."""
    batch, seg, b, _ = D.shape
    k = B.shape[-1]
    _check_steps("fused_forward_step",
                 [("D", D, 4), ("C", C, 4)],
                 [("Lc", Lc, (batch, b, b)), ("yc", yc, (batch, b, k))], b)
    if B.shape != (batch, seg, b, k):
        raise ValueError(f"fused_forward_step: B must be (batch, seg, b, k),"
                         f" got {B.shape}")
    bs = _resolve_block(b, block)
    if interpret is None:
        interpret = _interpret_default()

    def kernel(d_ref, c_ref, b_ref, lc_ref, yc_ref,
               l_ref, wt_ref, y_ref, info_ref):
        Lp = lc_ref[0].astype(jnp.float32)
        yp = yc_ref[0].astype(jnp.float32)
        infos = jnp.zeros((1, seg), jnp.int32)
        for s in range(seg):
            d = d_ref[0, s].astype(jnp.float32)
            c = c_ref[0, s].astype(jnp.float32)
            rhs = b_ref[0, s].astype(jnp.float32)
            L, wt, info = _factor_block(d, c, Lp, bs=bs, precision=precision)
            r = rhs - _gdot(wt, yp, 0, 0, precision)   # Wtᵀ·y_{i−1}
            y = _fwd_solve(L, r, from_upper=False, block=bs,
                           precision=precision)
            l_ref[0, s] = L.astype(d_ref.dtype)
            wt_ref[0, s] = wt.astype(d_ref.dtype)
            y_ref[0, s] = y.astype(b_ref.dtype)
            infos = _set_info(infos, s, info)
            Lp, yp = L, y
        _store_info(info_ref, infos)

    item = jnp.dtype(B.dtype).itemsize
    L, Wt, y, info = _batched_call(
        kernel, [D, C, B, Lc, yc],
        [((batch, seg, b, b), D.dtype), ((batch, seg, b, b), D.dtype),
         ((batch, seg, b, k), B.dtype), _info_shape(batch, seg)],
        name="chain_fused_forward", interpret=interpret,
        flops=batch * (tracing.blocktri_chol_flops(seg, b)
                       + tracing.blocktri_solve_flops(seg, b, k)),
        bytes_accessed=batch * item
        * (2 * seg * (2 * b * b + b * k) + b * b + b * k),
    )
    return L, Wt, y, info.reshape(batch, seg)


def factor_step(D, C, Lc, *, block: int = 0,
                precision: str | None = "highest",
                interpret: bool | None = None):
    """Factor-only scan step (the unfused reference the autotune space
    measures the fusion win against): `seg` blocks of the Schur-complement
    Cholesky recurrence.  Returns (L, Wt, info) shaped as in
    `fused_forward_step`."""
    batch, seg, b, _ = D.shape
    _check_steps("factor_step", [("D", D, 4), ("C", C, 4)],
                 [("Lc", Lc, (batch, b, b))], b)
    bs = _resolve_block(b, block)
    if interpret is None:
        interpret = _interpret_default()

    def kernel(d_ref, c_ref, lc_ref, l_ref, wt_ref, info_ref):
        Lp = lc_ref[0].astype(jnp.float32)
        infos = jnp.zeros((1, seg), jnp.int32)
        for s in range(seg):
            d = d_ref[0, s].astype(jnp.float32)
            c = c_ref[0, s].astype(jnp.float32)
            L, wt, info = _factor_block(d, c, Lp, bs=bs, precision=precision)
            l_ref[0, s] = L.astype(d_ref.dtype)
            wt_ref[0, s] = wt.astype(d_ref.dtype)
            infos = _set_info(infos, s, info)
            Lp = L
        _store_info(info_ref, infos)

    item = jnp.dtype(D.dtype).itemsize
    L, Wt, info = _batched_call(
        kernel, [D, C, Lc],
        [((batch, seg, b, b), D.dtype), ((batch, seg, b, b), D.dtype),
         _info_shape(batch, seg)],
        name="chain_factor", interpret=interpret,
        flops=batch * tracing.blocktri_chol_flops(seg, b),
        bytes_accessed=batch * item * (4 * seg * b * b + b * b),
    )
    return L, Wt, info.reshape(batch, seg)


def forward_solve_step(L, Wt, B, yc, *, block: int = 0,
                       precision: str | None = "highest",
                       interpret: bool | None = None):
    """Forward block-bidiagonal sweep from a ready factor: for each of
    `seg` blocks, y_i = L_i⁻¹(b_i − Wtᵀ_i·y_{i−1}).  Returns y
    (batch, seg, b, k)."""
    batch, seg, b, _ = L.shape
    k = B.shape[-1]
    _check_steps("forward_solve_step", [("L", L, 4), ("Wt", Wt, 4)],
                 [("yc", yc, (batch, b, k))], b)
    bs = _resolve_block(b, block)
    if interpret is None:
        interpret = _interpret_default()

    def kernel(l_ref, wt_ref, b_ref, yc_ref, y_ref):
        yp = yc_ref[0].astype(jnp.float32)
        for s in range(seg):
            Lf = l_ref[0, s].astype(jnp.float32)
            wt = wt_ref[0, s].astype(jnp.float32)
            rhs = b_ref[0, s].astype(jnp.float32)
            r = rhs - _gdot(wt, yp, 0, 0, precision)
            y = _fwd_solve(Lf, r, from_upper=False, block=bs,
                           precision=precision)
            y_ref[0, s] = y.astype(b_ref.dtype)
            yp = y

    item = jnp.dtype(B.dtype).itemsize
    (y,) = _batched_call(
        kernel, [L, Wt, B, yc],
        [((batch, seg, b, k), B.dtype)],
        name="chain_forward", interpret=interpret,
        flops=batch * tracing.blocktri_solve_flops(seg, b, k),
        bytes_accessed=batch * item
        * (seg * (2 * b * b + 2 * b * k) + b * k),
    )
    return y


def solve_backward_step(L, Wtn, Y, xc, *, block: int = 0,
                        precision: str | None = "highest",
                        interpret: bool | None = None):
    """Backward block-bidiagonal sweep, blocks processed in DESCENDING
    chain order inside the step (the models layer scans steps with
    ``reverse=True``): x_i = L_i⁻ᵀ(y_i − Wt_{i+1}·x_{i+1}).  `Wtn` is Wt
    shifted down one block (Wtn[:, s] = Wt of chain block s+1; the final
    chain block gets zeros, models layer contract).  `xc` carries
    x_{i+1} of the block after this step's last (0 past the chain end).
    Returns x (batch, seg, b, k)."""
    batch, seg, b, _ = L.shape
    k = Y.shape[-1]
    _check_steps("solve_backward_step", [("L", L, 4), ("Wtn", Wtn, 4)],
                 [("xc", xc, (batch, b, k))], b)
    bs = _resolve_block(b, block)
    if interpret is None:
        interpret = _interpret_default()

    def kernel(l_ref, wtn_ref, y_ref, xc_ref, x_ref):
        xn = xc_ref[0].astype(jnp.float32)
        for s in reversed(range(seg)):
            Lf = l_ref[0, s].astype(jnp.float32)
            wtn = wtn_ref[0, s].astype(jnp.float32)
            y = y_ref[0, s].astype(jnp.float32)
            r = y - _gdot(wtn, xn, 1, 0, precision)    # Wt_{i+1}·x_{i+1}
            x = _bwd_solve(Lf, r, from_upper=False, block=bs,
                           precision=precision)
            x_ref[0, s] = x.astype(y_ref.dtype)
            xn = x

    item = jnp.dtype(Y.dtype).itemsize
    (x,) = _batched_call(
        kernel, [L, Wtn, Y, xc],
        [((batch, seg, b, k), Y.dtype)],
        name="chain_backward", interpret=interpret,
        flops=batch * tracing.blocktri_solve_flops(seg, b, k),
        bytes_accessed=batch * item
        * (seg * (2 * b * b + 2 * b * k) + b * k),
    )
    return x
