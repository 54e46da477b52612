"""Batched small-problem Pallas kernels: the BATCH axis on the grid.

serve's bucketed requests (n <= ~1024, latency-bound — ROADMAP item 5) ran
as a `jax.vmap` over the single-problem LAPACK seam (serve/api.py): every
problem of a bucket pays its own kernel dispatch, and every *phase*
(factor, then solve) round-trips the factor through HBM between two
launches.  At bench's flagship n=49152 that overhead is noise; at n=64 it
IS the latency.  These kernels invert the layout:

* **batch axis on the grid** — ONE ``pallas_call`` with ``grid=(batch,)``
  processes every problem of a bucket; grid step b owns problem b's VMEM
  blocks, so problems never read each other's data (an injected NaN in one
  problem corrupts exactly that grid step — the serve fault-containment
  contract survives fusion for free).
* **fused factor+solve** — ``posv`` runs the Cholesky factor AND both
  triangular-solve sweeps inside one grid step: the factor is born in
  VMEM, is consumed in VMEM, and never exists in HBM at all.  ``lstsq``
  fuses the whole CholeskyQR2 normal-equations pipeline (gram, two
  Cholesky sweeps, four triangular sweeps) the same way.  The standalone
  ``potrf`` / ``trsm`` / ``potrs`` kernels are the unfused batched-grid
  reference the autotune latency space measures the fusion win against.

In-kernel factorization strategy: the problems are small enough that a
whole (n, n) matrix is VMEM-resident, so the factor is a column sweep of
rank-1 outer-product updates over the full matrix — every step is a
one-hot contraction (``precision_dot``) or an iota-masked elementwise op,
the two families Mosaic lowers without dynamic lane slicing.  The sweep
executes ~6n³ flops against the n³/3 useful count; that trade is the
point: at small n the kernel is dispatch/HBM-bound, not MXU-bound, and
the sweep keeps every operand in VMEM.  ``block`` (columns per
``fori_loop`` iteration, a static unroll) is the tile knob the latency
autotune space sweeps (autotune/sweep.py::tune_small).

Numerics: compute is f32 (sub-f32 operands upcast on VMEM load, outputs
round back on store), contractions ride ``pallas_tpu.precision_dot`` (the
one Mosaic-safe precision rule set).  Identity problems — and the
identity-tail blocks ``masking.embed_identity_tail`` pads real problems
with — factor and solve EXACTLY (all products are 0·x or 1·x, all
divisors 1.0), so bucket padding stays invisible: zero-RHS tails solve to
exact zeros, fill problems report info=0.  Each problem carries a LAPACK
``potrf``-convention int32 info (robust/detect.factor_info: 0 healthy,
k for the first bad pivot, n+1 for off-diagonal contamination), computed
in-program — O(n²) against the O(n³) solve, always on.

Like ops/qr_fused.py, the kernels run in interpret mode off-TPU (the
tier-1 CPU rig executes the same programs) and the VMEM envelope gate
(`eligible`) is bypassed there — interpret mode has no VMEM, and routing
CPU CI differently from hardware would silently drop coverage.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from capital_tpu.utils import tracing
from capital_tpu.ops.pallas_tpu import (
    _I0,
    _device_budget,
    _interpret_default,
    precision_dot,
)

#: Largest bucket n the "auto" impl routes to these kernels.  Above it the
#: column sweep's executed-flop overhead (~18x useful) outweighs the
#: launch/HBM saving and the vmap-over-LAPACK path wins; below it the
#: problem is dispatch-bound and one fused launch owns the latency.  The
#: serve config can force either side (ServeConfig.small_n_impl).
SMALL_N_MAX = 128

IMPLS = ("auto", "vmap", "pallas", "pallas_split")


def pick_block(n: int) -> int:
    """Default column-block unroll: largest power of two <= 8 dividing n
    (bucket ladders are powers of two, so this is 8 in practice)."""
    for b in (8, 4, 2):
        if n % b == 0:
            return b
    return 1


def _resolve_block(n: int, block: int) -> int:
    b = block or pick_block(n)
    while n % b:
        b -= 1
    return max(b, 1)


def eligible(op: str, a_shape: tuple, b_shape: tuple | None, dtype,
             *, interpret: bool | None = None) -> bool:
    """VMEM-envelope gate for ONE problem of a batched-grid kernel: the
    operands plus the f32 working set of one grid step must fit the device
    budget.  Shapes are the BATCHED (batch, m, n) / (batch, n, k) bucket
    shapes every caller (api.batched 'auto', engine._small_route) holds;
    only the trailing two dims feed the per-problem footprint — the batch
    axis lives on the grid, one problem resident at a time.  Interpret mode
    bypasses (no VMEM to exhaust; CPU CI must run the same route the
    hardware does — qr_fused.fused_plan discipline)."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        return True
    limit = 0.85 * (_device_budget()[1] or (16 << 20))
    item = jnp.dtype(dtype).itemsize
    n = a_shape[-1]
    k = b_shape[-1] if b_shape is not None else n
    if op == "lstsq":
        m = a_shape[-2]
        # A + B blocks at dtype; gram/factor/solve working set in f32
        need = m * (n + k) * item + 4 * (4 * n * n + 3 * n * k)
    else:
        need = n * (n + k) * item + 4 * (3 * n * n + 2 * n * k)
    return need <= limit


def tail_eligible(n: int, dtype, *, interpret: bool | None = None) -> bool:
    """VMEM-envelope gate for the fused recursion-tail megakernel
    (pallas_tpu.fused_tail): one (n, n) window at `dtype` in, two (n, n)
    windows out, plus the f32 working set of the in-kernel sweep — the
    symmetrized copy, the live factor, its inverse, and the fori_loop's
    rank-1 temporaries (~5 f32 matrices, conservatively).  Same 0.85x
    budget headroom and interpret-mode bypass as `eligible` — CPU CI must
    ride the same fused route the hardware does."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        return True
    limit = 0.85 * (_device_budget()[1] or (16 << 20))
    item = jnp.dtype(dtype).itemsize
    need = 3 * n * n * item + 4 * (5 * n * n)
    return need <= limit


def dtype_capable(dtype) -> bool:
    """Whether the batched-grid kernels can serve this dtype without
    precision loss.  They compute in f32 (Mosaic's accumulator width), so
    f64 is OUT — unconditionally, even under a forced impl='pallas':
    routing an f64 request through them would silently downgrade the
    precision the caller paid for behind f64-labeled outputs."""
    return jnp.dtype(dtype).itemsize <= 4


def default_impl(op: str, a_shape: tuple, b_shape: tuple | None, dtype,
                 *, interpret: bool | None = None) -> str:
    """Resolve impl='auto' for one bucket from its BATCHED (batch, m, n)
    shapes: 'pallas' where the batched-grid kernels own the latency (small
    n, VMEM-eligible, f32-or-narrower), else 'vmap'.  f64 buckets ALWAYS
    take vmap (dtype_capable).  `interpret` threads to the VMEM gate —
    tests force interpret=False to exercise the hardware resolution the
    CPU rig's interpret bypass would otherwise skip."""
    if op not in ("posv", "lstsq"):
        return "vmap"
    if not dtype_capable(dtype):
        return "vmap"
    if a_shape[-1] > SMALL_N_MAX:
        return "vmap"
    return ("pallas"
            if eligible(op, a_shape, b_shape, dtype, interpret=interpret)
            else "vmap")


# --------------------------------------------------------------------------
# in-kernel building blocks.  All state is a VALUE (fori_loop carries), all
# contractions are one-hot dot_generals, all masks are 2D broadcasted_iota —
# no dynamic lane slicing, no transposes, nothing Mosaic lowers poorly.
# --------------------------------------------------------------------------


def _gdot(a, b, ca: int, cb: int, precision):
    """f32-accumulating contraction of dims (ca of a) x (cb of b) through
    the one Mosaic-safe precision rule set (pallas_tpu.precision_dot)."""
    return precision_dot(
        a, b, (((ca,), (cb,)), ((), ())), jnp.float32, precision
    )


def _fori(steps: int, body, init):
    """In-kernel fori_loop over [0, steps) with an int32 index: under
    jax_enable_x64 a Python-int bound makes the index int64, which the
    Mosaic lowering cannot convert (it recurses until RecursionError)."""
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(steps), body, init)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _oh_row(j, n):
    """One-hot (1, n) f32 row selecting column j."""
    return (_iota((1, n), 1) == j).astype(jnp.float32)


def _oh_col(j, n):
    """One-hot (n, 1) f32 column selecting row j."""
    return (_iota((n, 1), 0) == j).astype(jnp.float32)


def _triu(M):
    n = M.shape[0]
    return jnp.where(_iota((n, n), 0) <= _iota((n, n), 1), M, 0.0)


def _chol(S, *, uplo: str, block: int, precision):
    """Column-sweep Cholesky of a symmetric f32 (n, n) VALUE: at column j,
    u = S[:, j]·rsqrt(S[j, j]) becomes row j of R (uplo='U'; column j of L
    for 'L') and the full rank-1 update S -= u·uᵀ zeroes row/column j, so
    leading entries of later pivots are already ~0 and the factor comes out
    triangular without masking.  Both triangles of S are read (the serve
    buckets embed exactly-symmetric operands).  Returns (factor, info) with
    the LAPACK potrf info convention; on a bad pivot the divisor is
    guarded to 1.0 and the contaminated values propagate like the raw
    lax.linalg.cholesky path would — info flags, NaNs tell.

    ``block`` columns run per fori_loop iteration (static unroll) — the
    latency-autotune tile knob (loop overhead vs program size)."""
    n = S.shape[0]

    def col_step(j, S, R, info):
        oh = _oh_row(j, n)
        ohc = _oh_col(j, n)
        col = _gdot(S, oh, 1, 1, precision)  # S[:, j] as (n, 1)
        d = jnp.sum(col * ohc)
        good = jnp.isfinite(d) & (d > 0)
        bad_at = jnp.asarray(j + 1, jnp.int32)  # 1-based potrf convention
        info = jnp.where((info == 0) & ~good, bad_at, info)
        u = col * jax.lax.rsqrt(jnp.where(good, d, jnp.float32(1.0)))
        if uplo == "U":
            R = R + _gdot(ohc, u, 1, 1, precision)  # row j := uᵀ
        else:
            R = R + _gdot(u, ohc, 1, 1, precision)  # col j := u
        S = S - _gdot(u, u, 1, 1, precision)
        return S, R, info

    def body(p, carry):
        S, R, info = carry
        for t in range(block):
            S, R, info = col_step(p * block + t, S, R, info)
        return S, R, info

    S, R, info = _fori(
        n // block, body, (S, jnp.zeros_like(S), jnp.int32(0))
    )
    # off-diagonal contamination with a clean diagonal: the factor_info
    # n+1 convention (robust/detect.py)
    off_bad = _any_nonfinite(R)
    info = jnp.where((info == 0) & off_bad, jnp.int32(n + 1), info)
    return R, info


def _any_nonfinite(M):
    """Whether any entry of M is NaN/inf, as an f32 max — a boolean
    reduction traces through a float64 under jax_enable_x64, which Mosaic
    cannot lower."""
    one, zero = jnp.float32(1), jnp.float32(0)
    return jnp.max(jnp.where(jnp.isfinite(M), zero, one)) > zero


def _safe_div(d):
    return jnp.where((d != 0) & jnp.isfinite(d), d, jnp.float32(1.0))


def _fwd_solve(T, B, *, from_upper: bool, block: int, precision):
    """Forward substitution L·Y = B where L is Tᵀ (T stored upper,
    from_upper=True) or T itself (stored lower).  Column j's multipliers
    are a one-hot row/column extraction of T, strictly-below-diagonal
    masked, so dead-triangle roundoff residue in T never participates."""
    n = T.shape[0]

    def col_step(j, Y):
        oh = _oh_row(j, n)
        ohc = _oh_col(j, n)
        # Tᵀ[:, j] = T[j, :] (row as column) when upper-stored, else T[:, j]
        lcol = _gdot(T, oh, 0 if from_upper else 1, 1, precision)
        d = jnp.sum(lcol * ohc)
        yrow = _gdot(oh, Y, 1, 0, precision) / _safe_div(d)  # (1, k)
        below = (_iota((n, 1), 0) > j).astype(jnp.float32)
        upd = _gdot(lcol * below, yrow, 1, 0, precision)
        return jnp.where(_iota((n, 1), 0) == j, yrow, Y - upd)

    def body(p, Y):
        for t in range(block):
            Y = col_step(p * block + t, Y)
        return Y

    return _fori(n // block, body, B)


def _bwd_solve(T, Y, *, from_upper: bool, block: int, precision):
    """Back substitution U·X = Y where U is T (stored upper) or Tᵀ
    (stored lower)."""
    n = T.shape[0]

    def col_step(j, Y):
        oh = _oh_row(j, n)
        ohc = _oh_col(j, n)
        ucol = _gdot(T, oh, 1 if from_upper else 0, 1, precision)
        d = jnp.sum(ucol * ohc)
        xrow = _gdot(oh, Y, 1, 0, precision) / _safe_div(d)
        above = (_iota((n, 1), 0) < j).astype(jnp.float32)
        upd = _gdot(ucol * above, xrow, 1, 0, precision)
        return jnp.where(_iota((n, 1), 0) == j, xrow, Y - upd)

    def body(p, Y):
        for t in range(block):
            Y = col_step(n - 1 - (p * block + t), Y)
        return Y

    return _fori(n // block, body, Y)


def _rsolve_upper(R, V, *, block: int, precision):
    """Right-side solve W·R = V for upper-triangular R (column sweep
    ascending: W[:, j] = V'[:, j]/R[j, j], then V'[:, l>j] -= W[:, j]·R[j, l])."""
    n = R.shape[0]

    def col_step(j, W):
        oh = _oh_row(j, n)
        ohc = _oh_col(j, n)
        d = jnp.sum(_gdot(R, oh, 1, 1, precision) * ohc)  # R[j, j]
        wcol = _gdot(W, oh, 1, 1, precision) / _safe_div(d)  # (n, 1)
        rrow = _gdot(oh, R, 1, 0, precision)  # R[j, :] as (1, n)
        after = (_iota((1, n), 1) > j).astype(jnp.float32)
        upd = _gdot(wcol, rrow * after, 1, 0, precision)
        return jnp.where(_iota((1, n), 1) == j, wcol, W - upd)

    def body(p, W):
        for t in range(block):
            W = col_step(p * block + t, W)
        return W

    return _fori(n // block, body, V)


# --------------------------------------------------------------------------
# pallas_call plumbing
# --------------------------------------------------------------------------


def _out_struct(shape, dtype, *operands):
    """qr_fused discipline: outputs carry the union of the operands'
    varying mesh axes so the kernels stay legal inside shard_map bodies."""
    vma: frozenset = frozenset()
    for r in operands:
        vma |= jax.typeof(r).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _info_shape(batch: int, width: int = 1):
    """Per-problem int32 info output laid out as (batch, 1, width): its
    (1, 1, width) block spans the array's last two dims, the form Mosaic
    accepts for a batch-indexed block (a (1, 1) block of a (batch, 1)
    array is refused).  Kernels store it whole with `_store_info` —
    Mosaic cannot store a scalar to VMEM."""
    return (batch, 1, width), jnp.int32


def _store_info(ref, info):
    ref[0] = jnp.broadcast_to(info, ref.shape[1:]).astype(jnp.int32)


def _bspec(shape):
    """Per-problem BlockSpec: block (1, *problem) at batch index b."""
    nd = len(shape)
    return pl.BlockSpec(
        (1,) + tuple(shape[1:]),
        lambda b, _nd=nd: (b,) + (_I0,) * (_nd - 1),
        memory_space=pltpu.VMEM,
    )


def _batched_call(kernel, inputs, out_shapes, *, name, interpret, flops,
                  bytes_accessed, alias_rhs=False):
    """One pallas_call over grid=(batch,): grid step b reads/writes ONLY
    problem b's blocks.  `name` is the kernel's part of its stable name
    (tracing.kernel_name; home phase OP::batched_small).  alias_rhs
    declares input 1 -> output 0 in-place reuse (posv/trsm: the RHS batch
    becomes the solution batch — the real buffer behind the engine's
    TPU-side RHS donation); skipped in interpret mode, which has no buffer
    assignment to alias."""
    batch = inputs[0].shape[0]
    kw = {}
    if alias_rhs and not interpret:
        kw["input_output_aliases"] = {1: 0}
    return pl.pallas_call(
        kernel,
        name=tracing.kernel_name(name, "OP::batched_small"),
        grid=(batch,),
        in_specs=[_bspec(a.shape) for a in inputs],
        out_specs=[_bspec(s) for s, _ in out_shapes],
        out_shape=[_out_struct(s, d, *inputs) for s, d in out_shapes],
        compiler_params=pltpu.CompilerParams(
            # problems are independent: the batch dimension is parallel
            # (no cross-step VMEM state — each step's blocks are its own)
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_device_budget()[1],
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(flops), bytes_accessed=int(bytes_accessed),
            transcendentals=0,
        ),
        interpret=interpret,
        **kw,
    )(*inputs)


def _check_batched(A, B=None, *, square=True, op="batched_small"):
    if A.ndim != 3 or (square and A.shape[1] != A.shape[2]):
        raise ValueError(
            f"{op}: operand batch must be (batch, n, n), got {A.shape}"
        )
    if B is not None:
        if B.ndim != 3 or B.shape[0] != A.shape[0] or B.shape[1] != A.shape[1]:
            raise ValueError(
                f"{op}: RHS batch {B.shape} does not ride operand batch "
                f"{A.shape}"
            )


# --------------------------------------------------------------------------
# public kernels
# --------------------------------------------------------------------------


def potrf(A, *, uplo: str = "U", block: int = 0,
          precision: str | None = "highest", interpret: bool | None = None):
    """Batched Cholesky: (batch, n, n) symmetric SPD -> (R, info) with R
    (batch, n, n) triangular per `uplo` (dead triangle exactly zero) and
    info (batch,) int32 in the potrf convention.  ONE pallas_call."""
    _check_batched(A, op="batched potrf")
    if uplo not in ("U", "L"):
        raise ValueError(f"uplo must be 'U' or 'L', got {uplo!r}")
    batch, n, _ = A.shape
    bs = _resolve_block(n, block)
    if interpret is None:
        interpret = _interpret_default()

    def kernel(a_ref, r_ref, info_ref):
        a = a_ref[0].astype(jnp.float32)
        R, info = _chol(a, uplo=uplo, block=bs, precision=precision)
        mask = (_iota((n, n), 0) <= _iota((n, n), 1)) if uplo == "U" else (
            _iota((n, n), 0) >= _iota((n, n), 1))
        r_ref[0] = jnp.where(mask, R, 0.0).astype(a_ref.dtype)
        _store_info(info_ref, info)

    with tracing.scope("OP::batched_small"):
        tracing.emit(flops=batch * tracing.batched_chol_flops(n))
        R, info = _batched_call(
            kernel, [A],
            [((batch, n, n), A.dtype), _info_shape(batch)],
            name="potrf", interpret=interpret,
            flops=batch * tracing.batched_chol_flops(n),
            bytes_accessed=batch * 2 * n * n * jnp.dtype(A.dtype).itemsize,
        )
    return R, info.reshape(batch)


def trsm(T, B, *, uplo: str = "U", trans: bool = False, block: int = 0,
         precision: str | None = "highest", interpret: bool | None = None):
    """Batched triangular solve op(T)·X = B over (batch, n, n) factors and
    (batch, n, k) RHS: op is T (trans=False) or Tᵀ.  ONE pallas_call."""
    _check_batched(T, B, op="batched trsm")
    if uplo not in ("U", "L"):
        raise ValueError(f"uplo must be 'U' or 'L', got {uplo!r}")
    batch, n, _ = T.shape
    k = B.shape[-1]
    bs = _resolve_block(n, block)
    if interpret is None:
        interpret = _interpret_default()
    # effective structure of op(T): upper·X = B back-substitutes
    forward = (uplo == "L") ^ trans

    def kernel(t_ref, b_ref, x_ref):
        t = t_ref[0].astype(jnp.float32)
        b = b_ref[0].astype(jnp.float32)
        if forward:
            x = _fwd_solve(t, b, from_upper=(uplo == "U"), block=bs,
                           precision=precision)
        else:
            x = _bwd_solve(t, b, from_upper=(uplo == "U"), block=bs,
                           precision=precision)
        x_ref[0] = x.astype(b_ref.dtype)

    with tracing.scope("OP::batched_small"):
        tracing.emit(flops=batch * tracing.batched_trsm_flops(n, k))
        (X,) = _batched_call(
            kernel, [T, B],
            [((batch, n, k), B.dtype)],
            name="trsm", interpret=interpret, alias_rhs=True,
            flops=batch * tracing.batched_trsm_flops(n, k),
            bytes_accessed=batch * (n * n + 2 * n * k)
            * jnp.dtype(B.dtype).itemsize,
        )
    return X


def potrs(T, B, *, uplo: str = "U", block: int = 0,
          precision: str | None = "highest", interpret: bool | None = None):
    """Batched SPD solve from a ready factor: both triangular sweeps in ONE
    pallas_call (the factor is read into VMEM once, both sweeps consume it
    there).  T per `uplo` convention: A = RᵀR ('U') or L·Lᵀ ('L')."""
    _check_batched(T, B, op="batched potrs")
    if uplo not in ("U", "L"):
        raise ValueError(f"uplo must be 'U' or 'L', got {uplo!r}")
    batch, n, _ = T.shape
    k = B.shape[-1]
    bs = _resolve_block(n, block)
    if interpret is None:
        interpret = _interpret_default()

    def kernel(t_ref, b_ref, x_ref):
        t = t_ref[0].astype(jnp.float32)
        b = b_ref[0].astype(jnp.float32)
        y = _fwd_solve(t, b, from_upper=(uplo == "U"), block=bs,
                       precision=precision)
        x = _bwd_solve(t, y, from_upper=(uplo == "U"), block=bs,
                       precision=precision)
        x_ref[0] = x.astype(b_ref.dtype)

    with tracing.scope("OP::batched_small"):
        tracing.emit(flops=batch * 2 * tracing.batched_trsm_flops(n, k))
        (X,) = _batched_call(
            kernel, [T, B],
            [((batch, n, k), B.dtype)],
            name="potrs", interpret=interpret, alias_rhs=True,
            flops=batch * 2 * tracing.batched_trsm_flops(n, k),
            bytes_accessed=batch * (n * n + 2 * n * k)
            * jnp.dtype(B.dtype).itemsize,
        )
    return X


def posv(A, B, *, uplo: str = "U", block: int = 0,
         precision: str | None = "highest", interpret: bool | None = None):
    """FUSED batched SPD solve: factor + both substitution sweeps in ONE
    pallas_call per bucket batch.  The factor never exists in HBM — it is
    produced and consumed inside grid step b's VMEM residency, which is
    the inter-phase round-trip the vmap-over-LAPACK path pays twice per
    problem.  Returns (X, info): X (batch, n, k), info (batch,) int32."""
    _check_batched(A, B, op="batched posv")
    if uplo not in ("U", "L"):
        raise ValueError(f"uplo must be 'U' or 'L', got {uplo!r}")
    batch, n, _ = A.shape
    k = B.shape[-1]
    bs = _resolve_block(n, block)
    if interpret is None:
        interpret = _interpret_default()

    def kernel(a_ref, b_ref, x_ref, info_ref):
        a = a_ref[0].astype(jnp.float32)
        b = b_ref[0].astype(jnp.float32)
        R, info = _chol(a, uplo=uplo, block=bs, precision=precision)
        y = _fwd_solve(R, b, from_upper=(uplo == "U"), block=bs,
                       precision=precision)
        x = _bwd_solve(R, y, from_upper=(uplo == "U"), block=bs,
                       precision=precision)
        x_ref[0] = x.astype(b_ref.dtype)
        _store_info(info_ref, info)

    with tracing.scope("SV::fused_posv"):
        tracing.emit(flops=batch * tracing.fused_posv_flops(n, k))
        X, info = _batched_call(
            kernel, [A, B],
            [((batch, n, k), B.dtype), _info_shape(batch)],
            name="posv", interpret=interpret, alias_rhs=True,
            flops=batch * tracing.fused_posv_flops(n, k),
            bytes_accessed=batch * (n * n + 2 * n * k)
            * jnp.dtype(B.dtype).itemsize,
        )
    return X, info.reshape(batch)


def lstsq(A, B, *, block: int = 0, precision: str | None = "highest",
          interpret: bool | None = None):
    """FUSED batched CholeskyQR2 least squares in ONE pallas_call: per grid
    step, gram G = AᵀA and C = AᵀB are taken once from the VMEM-resident
    operand, then the whole CQR2 correction runs on (n, n) state without
    touching HBM: R1 = chol(G), G2 = R1⁻ᵀ·G·R1⁻¹ (algebraically Q1ᵀQ1 —
    A is never re-read), R2 = chol(G2), X = (R2·R1)⁻¹·R2⁻ᵀ·R1⁻ᵀ·C.
    Returns (X, info): X (batch, n, k), info = max(info1, info2)."""
    _check_batched(A, B, square=False, op="batched lstsq")
    if A.shape[1] < A.shape[2]:
        raise ValueError(
            f"batched lstsq wants tall problems, got {A.shape[1:]}"
        )
    batch, m, n = A.shape
    k = B.shape[-1]
    bs = _resolve_block(n, block)
    if interpret is None:
        interpret = _interpret_default()

    def kernel(a_ref, b_ref, x_ref, info_ref):
        a = a_ref[0].astype(jnp.float32)
        b = b_ref[0].astype(jnp.float32)
        G = _gdot(a, a, 0, 0, precision)  # AᵀA
        C = _gdot(a, b, 0, 0, precision)  # AᵀB
        R1, i1 = _chol(G, uplo="U", block=bs, precision=precision)
        V = _fwd_solve(R1, G, from_upper=True, block=bs, precision=precision)
        G2 = _rsolve_upper(R1, V, block=bs, precision=precision)
        R2, i2 = _chol(G2, uplo="U", block=bs, precision=precision)
        t1 = _fwd_solve(R1, C, from_upper=True, block=bs, precision=precision)
        t2 = _fwd_solve(R2, t1, from_upper=True, block=bs,
                        precision=precision)
        R = _gdot(_triu(R2), _triu(R1), 1, 0, precision)  # R2·R1, upper
        x = _bwd_solve(R, t2, from_upper=True, block=bs, precision=precision)
        x_ref[0] = x.astype(b_ref.dtype)
        _store_info(info_ref, jnp.maximum(i1, i2))

    with tracing.scope("SV::fused_lstsq"):
        tracing.emit(flops=batch * tracing.fused_lstsq_flops(m, n, k))
        X, info = _batched_call(
            kernel, [A, B],
            [((batch, n, k), B.dtype), _info_shape(batch)],
            name="lstsq", interpret=interpret,
            flops=batch * tracing.fused_lstsq_flops(m, n, k),
            bytes_accessed=batch * (m * n + m * k + n * k)
            * jnp.dtype(B.dtype).itemsize,
        )
    return X, info.reshape(batch)
