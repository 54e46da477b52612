"""Fused Pallas kernels for CholeskyQR2's tall-skinny passes.

The 1d CQR2 pipeline (models/qr.py:_sweep_1d, reference cacqr.hpp:82-116)
is HBM-bound around three tall passes over the m x n operand:

    G1 = AᵀA          (gram, sweep 1)
    Q1 = A·R1⁻¹       (scale, sweep 1)
    G2 = Q1ᵀQ1        (gram, sweep 2)
    Q  = Q1·R2⁻¹      (scale, sweep 2)

Round-2 ran these as separate XLA/pallas products: the g=2 block-row gram
reads 1.5x the operand (the [*, nb:] trailing slab overlaps the [*, :nb]
head), and sweep 2's gram re-reads all of Q1 from HBM right after the scale
wrote it.  These kernels remove both redundancies (VERDICT r2 #3 — the
"fused gram+scaling kernel" docs/PERF.md names as the remaining lever):

* ``gram_blocked`` — one pass over A per gram: each (bm, n) row block is
  read ONCE into VMEM, transposed once, and the g lower block-column
  products are taken from it (G[jc:, jc:(j+1)c] += A_blkᵀ[jc:, :]·
  A_blk[:, jc:(j+1)c]), accumulating into a VMEM-resident f32 (n, n)
  output revisited by every grid step; the caller gets its transpose, the
  upper block-row form.  HBM traffic: m·n reads exactly (was 1.5 m·n).
* ``scale_gram`` — sweep 1's scale and sweep 2's gram in ONE pass: read a
  row block of A, Q_blk = A_blk·R⁻¹ via g column-block products (the
  zero lower blocks of the upper-triangular R⁻¹ are never touched:
  (g+1)/2g of dense flops), round Q_blk to the output dtype, write it, and
  accumulate G2 += Q_blkᵀQ_blk (upper block-rows) from the registers —
  sweep 2's gram costs ZERO extra HBM traffic (was a full m·n read of Q1).

The column split ``g`` is an IN-KERNEL knob (round-4, VERDICT r3 #1): all
operands of every sub-product are already VMEM-resident, so finer splits
reduce executed flops — (g+1)/2g of dense: 0.75 at g=2, 0.625 at g=4,
0.5625 at g=8 — at zero extra HBM traffic, unlike the measured XLA-level
g=4 loser (5x A reads + relayout copies, models/qr.py:_col_blocks).  The
per-dot shapes stay MXU-aligned (every block dim a 128-multiple >= 128).
The MXU holds one operand of a product as stationary 128×128 tiles and
streams the other's rows through them: the Gram's products are oriented so
that n − jc rows stream through each (bm, c) slab, not c = 128 rows
through each tile of A_blk[:, jc:].  Both kernels that accumulate a Gram
zero it at step 0 before they load the row block: a block value live
across that branch cost the old Gram kernel 0.32 ms of its 3.66 a call on
v5e at 524,288 × 1024 bf16, g=8, and the orientation with the deeper row
block the next 0.14 (3.20 ms, 193 TF/s executed; scale_gram 6.49 → 6.41
ms; PERF.md §6).

The row block of each kernel is `tall_bm`'s: the deepest that divides m and
fits the device's VMEM, up to a per-kernel depth measured on v5e.

Kernels require n % (g*128) == 0 and bm | m; callers fall back to the
unfused path otherwise.  The gram accumulates over row blocks in f32 (same
reduction values as the unfused blocked gram, different association order:
bitwise parity is NOT guaranteed, agreement is to roundoff —
tests/test_qr_fused.py).  The gram is taken from the ROUNDED Q_blk, exactly
like the unfused pipeline which re-reads the written bf16 Q1, so
fused/unfused see the same operand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from capital_tpu.ops.pallas_tpu import (
    _I0,
    _device_budget,
    _interpret_default,
    _platform,
    device_scope,
)
from capital_tpu.utils import tracing


def _acc_dtype(dtype):
    """f32 accumulation for sub-f32 operands; wider operands keep their
    width (clamped to f32 on real TPU hardware, like pallas_tpu)."""
    acc = jnp.promote_types(dtype, jnp.float32)
    if jnp.dtype(acc).itemsize > 4 and _platform() == "tpu":
        acc = jnp.float32
    return acc


def _dot(a, b, acc, *, trans_a=False, precision=None):
    # the Mosaic-safe precision rules (bf16x3 for f32 'high', round-up,
    # sub-f32 drop) live in ONE place: pallas_tpu.precision_dot.  The
    # per-call bf16 split is O(bm·n) VPU work against O(bm·n²) of MXU
    # flops (~0.1% of kernel time) — hoisting it out of the g-loop is
    # deliberately not done.
    from capital_tpu.ops.pallas_tpu import precision_dot

    dn = (((0 if trans_a else 1,), (0,)), ((), ()))
    return precision_dot(a, b, dn, acc, precision)


def _out_struct(shape, dtype, *operands):
    """Out-shape struct carrying the union of the operands' varying mesh
    axes: pallas_call outputs inside a shard_map body must declare their
    vma under replication checking (check_vma) — outside shard_map the vma
    set is empty and this is a plain ShapeDtypeStruct."""
    vma: frozenset = frozenset()
    for r in operands:
        vma |= jax.typeof(r).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def live_fraction(g: int) -> float:
    """Executed fraction of the dense contraction at column split g."""
    return (g + 1) / (2.0 * g) if g > 1 else 1.0


def _split_ok(n: int, g: int) -> bool:
    """The ONE column-split rule for every fused tall-pass kernel: every
    block a 128-multiple of at least 128 (g=2 additionally demands n/2 >=
    256 — at n = 512 the split's saving measured below its bookkeeping)."""
    return g >= 2 and n % (g * 128) == 0 and not (g == 2 and n // 2 < 256)


#: The deepest row block each kernel takes: own time per call on v5e at
#: 524,288 × 1024 bf16, g=8, bm 1024/2048/4096/8192 (PERF.md §6):
#: gram 3.31/3.24/3.20/not measured ms, scale 3.39/3.31/3.30/3.31,
#: scale_gram 6.41/6.69/6.69/not measured (deeper blocks only slow it).
_BM_MAX = {"gram": 4096, "scale_gram": 1024, "scale": 4096}


def _vmem_bytes(kernel: str, bm: int, n: int, item: int) -> int:
    """VMEM of one `kernel` call: Mosaic's scoped allocation — each
    streamed (bm, n) block double-buffered (A for the Gram; A and Q for the
    scales), the Gram's loaded block and its transpose, the resident
    operands once (R⁻¹ in A's dtype, the f32 Gram) — and, for 4-byte
    operands, the copies their bf16 MXU passes make of the block: up to 16
    bytes a block element more at precision "highest" (compiled for v5e at
    n = 1024-4096, PERF.md §6)."""
    streamed = 1 if kernel == "gram" else 2
    values = 2 * item if kernel == "gram" else 0
    resident = {"gram": 4, "scale": item, "scale_gram": item + 4}[kernel]
    passes = 16 if item >= 4 else 0
    return (2 * streamed * item + values + passes) * bm * n + resident * n * n


def tall_bm(kernel: str, m: int, n: int, dtype) -> int:
    """The row block `kernel` ("gram", "scale_gram" or "scale") runs at over
    m rows of width n: the deepest power of two from _BM_MAX[kernel] down to
    128 that divides m and whose VMEM (_vmem_bytes) fits 0.85 of the limit
    the kernels compile with, resolved against the scoped device.
    Interpret mode has no VMEM.  0 when no block fits."""
    limit = None
    if not _interpret_default():
        limit = 0.85 * (_device_budget()[1] or (16 << 20))
    item = jnp.dtype(dtype).itemsize
    bm = _BM_MAX[kernel]
    while bm >= 128:
        if m % bm == 0 and (
            limit is None or _vmem_bytes(kernel, bm, n, item) <= limit
        ):
            return bm
        bm //= 2
    return 0


def row_blocks(grid, rows: int, n: int, dtype) -> dict:
    """{kernel: tall_bm(kernel, rows, n, dtype)}, resolved against the
    GRID's platform, not the process default: callers outside a scoped
    entry point (the multichip dryrun probing eligibility, the route
    counter) must not touch the default backend."""
    with device_scope(grid.mesh.devices.flat[0]):
        return {k: tall_bm(k, rows, n, dtype) for k in _BM_MAX}


def _shape_gate(name: str, kernel: str, A, bm: int | None, g: int) -> int:
    m, n = A.shape
    if bm is None:
        bm = tall_bm(kernel, m, n, A.dtype)
    if not (_split_ok(n, g) and bm and m % bm == 0):
        raise ValueError(
            f"{name} needs bm | m and a {g}-way 128-aligned column split "
            f"(n % {g * 128} == 0), got {(m, n)} at bm {bm}"
        )
    return bm


def pick_g(n: int, override: int = 0) -> int:
    """Column-split auto-pick for the fused kernels: the largest g whose
    blocks stay 128-wide.  Measured on v5e (docs/PERF.md round-4 table):
    executed flops drop with g ((g+1)/2g) and the curve stays monotone to
    the 128-wide eligibility limit — 1M x 1024: 39.05/33.42/30.91 ms for
    g=2/4/8 (g=16 ineligible); 512k x 2048: 62.27 (g=8) vs 55.09 (g=16)
    ms.  Power-of-two n >= 256 take g = n/128 via the same rule; the gain
    per doubling shrinks ((g+1)/2g -> 1/2) while per-dot shapes hold at
    128, so 'largest eligible' stays right.  The executed rate that round
    recorded falling with g (191 → 169 TF/s at g=8) was not the 128-wide
    blocks: it was the Gram kernel's row block held live across its step-0
    zeroing (0.32 ms of 3.66 a call) and its orientation, c = 128 rows
    streamed per stationary MXU tile; at g=8 the Gram runs at 193 TF/s
    executed (PERF.md §6)."""
    if override:
        return override if _split_ok(n, override) else 0
    g = 2
    while n % (2 * g * 128) == 0:  # divisibility implies 128-wide blocks
        g *= 2
    return g if _split_ok(n, g) else 0


def gram_blocked(
    A: jnp.ndarray,
    *,
    bm: int | None = None,
    g: int = 2,
    precision: str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Upper-block-row gram of tall-skinny A at the g-way split: returns
    f32 (n, n) with block row j valid from column j·(n/g) (the strictly
    lower block triangle is zero — callers assemble the symmetric gram
    with assemble_sym).  One HBM read of A total.  `bm` defaults to
    tall_bm's."""
    if interpret is None:
        interpret = _interpret_default()
    m, n = A.shape
    c = n // g
    bm = _shape_gate("gram_blocked", "gram", A, bm, g)
    nsteps = m // bm
    acc = _acc_dtype(A.dtype)

    def kernel(a_ref, g_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            g_ref[:] = jnp.zeros_like(g_ref)

        # loaded and transposed after the zeroing, not live across it (on
        # v5e at 524,288 × 1024 bf16, g=8: 3.20 ms a call, 3.66 before it)
        a = a_ref[:]
        at = a.T  # once a step, so every product streams n − jc rows
        for j in range(g):
            rows = at[j * c:, :]
            g_ref[j * c:, j * c:(j + 1) * c] += _dot(
                rows, a[:, j * c:(j + 1) * c], acc, precision=precision,
            )

    # the kernel accumulates the lower block columns: an n² transpose
    return pl.pallas_call(
        kernel,
        name=tracing.kernel_name("gram", "CQR::gram"),
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, _I0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((n, n), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
        out_shape=_out_struct((n, n), acc, A),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_device_budget()[1],
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * m * n * n * live_fraction(g)),
            bytes_accessed=m * n * jnp.dtype(A.dtype).itemsize + 4 * n * n,
            transcendentals=0,
        ),
        interpret=interpret,
    )(A).T


def scale_gram(
    A: jnp.ndarray,
    Rinv: jnp.ndarray,
    *,
    bm: int | None = None,
    g: int = 2,
    precision: str | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(Q, G) = (A @ Rinv, upper-block-row gram of Q) in one pass over A.

    Rinv must be upper triangular with true zeros below the diagonal (the
    kernel exploits the zero lower column-blocks structurally; pass it
    through jnp.triu if unsure).  Q has A's dtype (rounded before the gram
    — the operand sweep 2 would otherwise re-read); G is f32 with the same
    valid region as gram_blocked."""
    if interpret is None:
        interpret = _interpret_default()
    m, n = A.shape
    if Rinv.shape != (n, n):
        raise ValueError(f"Rinv {Rinv.shape} does not match A {A.shape}")
    c = n // g
    bm = _shape_gate("scale_gram", "scale_gram", A, bm, g)
    nsteps = m // bm
    acc = _acc_dtype(A.dtype)

    def kernel(a_ref, r_ref, q_ref, g_ref):
        # zeroed first, so no block value is live across the branch (as in
        # gram_blocked: 6.41 ms a call at 524,288 × 1024 bf16 on v5e, 6.49
        # with the zeroing after Q)
        @pl.when(pl.program_id(0) == 0)
        def _():
            g_ref[:] = jnp.zeros_like(g_ref)

        a = a_ref[:]
        # Q = A @ Rinv with the g-way structure: column block j of
        # upper-triangular Rinv has zeros below row (j+1)c, so it sees
        # only A's leading (j+1)c columns — (g+1)/2g of dense flops,
        # no masking
        q = jnp.concatenate(
            [
                _dot(
                    a[:, : (j + 1) * c],
                    r_ref[0:(j + 1) * c, j * c:(j + 1) * c],
                    acc, precision=precision,
                )
                for j in range(g)
            ],
            axis=1,
        ).astype(q_ref.dtype)
        q_ref[:] = q
        # sweep-2 gram from the rounded block, straight from registers
        for j in range(g):
            g_ref[j * c:(j + 1) * c, j * c:] += _dot(
                q[:, j * c:(j + 1) * c], q[:, j * c:], acc,
                trans_a=True, precision=precision,
            )

    Q, G = pl.pallas_call(
        kernel,
        name=tracing.kernel_name("scale_gram", "CQR::fused"),
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, _I0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, n), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, _I0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, n), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct((m, n), A.dtype, A, Rinv),
            _out_struct((n, n), acc, A, Rinv),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_device_budget()[1],
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * m * n * n * 2 * live_fraction(g)),  # scale + gram
            bytes_accessed=2 * m * n * jnp.dtype(A.dtype).itemsize + 4 * n * n,
            transcendentals=0,
        ),
        interpret=interpret,
    )(A, Rinv)
    return Q, G


def scale_blocked(
    A: jnp.ndarray,
    Rinv: jnp.ndarray,
    *,
    bm: int | None = None,
    g: int = 2,
    precision: str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Q = A @ Rinv (upper-triangular Rinv with true zeros below, g-way
    structure) — the scale half of scale_gram without the gram.  Used for
    CQR2's FINAL scale: same column-block-dot structure that measures
    191 TF/s executed on v5e at g=2, vs 153 for the live-tile trmm kernel
    at (1024, 512, 512) blocks on the same math (the trmm kernel pays
    per-pair bookkeeping and a bk=512 K-split; this shape needs neither)."""
    if interpret is None:
        interpret = _interpret_default()
    m, n = A.shape
    if Rinv.shape != (n, n):
        raise ValueError(f"Rinv {Rinv.shape} does not match A {A.shape}")
    c = n // g
    bm = _shape_gate("scale_blocked", "scale", A, bm, g)
    acc = _acc_dtype(A.dtype)

    def kernel(a_ref, r_ref, q_ref):
        a = a_ref[:]
        q_ref[:] = jnp.concatenate(
            [
                _dot(
                    a[:, : (j + 1) * c],
                    r_ref[0:(j + 1) * c, j * c:(j + 1) * c],
                    acc, precision=precision,
                )
                for j in range(g)
            ],
            axis=1,
        ).astype(q_ref.dtype)

    return pl.pallas_call(
        kernel,
        name=tracing.kernel_name("scale", "CQR::formR"),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, _I0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, n), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, _I0), memory_space=pltpu.VMEM),
        out_shape=_out_struct((m, n), A.dtype, A, Rinv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_device_budget()[1],
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * m * n * n * live_fraction(g)),
            bytes_accessed=2 * m * n * jnp.dtype(A.dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(A, Rinv)


def assemble_sym(Gu: jnp.ndarray, c: int) -> jnp.ndarray:
    """Symmetric gram from the upper-block-row form with block width c
    (every strictly lower block is the transpose of its mirror) — n²
    elementwise, negligible next to the tall passes."""
    n = Gu.shape[0]
    for i in range(1, n // c):
        Gu = Gu.at[i * c:(i + 1) * c, : i * c].set(Gu[: i * c, i * c:(i + 1) * c].T)
    return Gu


def fused_plan(grid, m: int, n: int, mode: str, g: int = 2,
               *, dtype) -> str | None:
    """Which fused CQR2 pipeline can run?  Returns

      'full'  — the three-kernel pipeline with scale_gram (sweep 1's scale
                and sweep 2's gram share one pass; 5 HBM passes total);
      'split' — the wide-n streaming tier (round 5, VERDICT r4 #3):
                scale_gram's envelope (A block + Rinv + Q block + f32 gram
                all VMEM-resident, ~112 MB at n=4096 bf16 — a compile-time
                vmem OOM) is exceeded, but gram_blocked's (one row block +
                the gram) and scale_blocked's (two row blocks + Rinv) still
                fit, so sweep 2's gram runs as its own gram_blocked pass
                over the written Q1.  Costs ONE extra read of Q1 (6 passes
                instead of 5) and keeps every in-kernel g-way flop saving —
                at wide n the pipeline is MXU-bound (arithmetic intensity
                ~n/6 flops/byte), so the extra pass is noise next to the
                (g+1)/2g executed-flop drop;
      'panels' — past every kernel's envelope, n % 512 == 0;
      None    — fall back to the unfused blocked sweeps.

    Gating: pallas mode, the shared column-split rule (_split_ok), and a
    row block of each kernel (row_blocks: tall_bm, the rule the kernels
    are built with) on the PER-SHARD row extent (on a mesh the kernels run
    per shard inside shard_map — models/qr.py _cqr2_fused_sharded — so
    eligibility is about each device's m/p rows).  Interpret mode has no
    VMEM, so the CPU test rig takes the fused tiers wherever v5e's
    divisibility allows."""
    p = grid.num_devices
    if p > 1 and m % p:
        return None  # shard_map needs the row axis to divide evenly
    rows = m // p
    if not (mode == "pallas" and _split_ok(n, g) and rows % 128 == 0):
        return None
    bms = row_blocks(grid, rows, n, dtype)
    if all(bms.values()):
        return "full"
    if bms["gram"] and bms["scale"]:
        return "split"
    if n % 512 == 0:
        # beyond every kernel envelope: the XLA-level panel pipeline
        # (models/qr.py _cqr2_panels) — same (g+1)/2g saving, no VMEM
        # constraint; at these widths the pipeline is MXU-bound
        # (arithmetic intensity ~n/(g+1) flops/byte), so the extra
        # panel reads the round-4 n=1024 measurement rejected are
        # noise here
        return "panels"
    return None


def fused_ok(grid, m: int, n: int, mode: str, g: int = 2, *, dtype) -> bool:
    """True when ANY fused pipeline tier can run (see fused_plan)."""
    return fused_plan(grid, m, n, mode, g, dtype=dtype) is not None
