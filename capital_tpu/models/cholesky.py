"""cholinv: communication-optimal recursive Cholesky + triangular inverse.

The flagship algorithm (reference src/alg/cholesky/cholinv/), re-designed for
TPU.  For SPD A it computes the upper-triangular factor R (A = RᵀR) and,
simultaneously, R⁻¹ — the pair that lets CholeskyQR2 and the SPD inverse
avoid distributed triangular solves.

Reference schedule (cholinv.hpp:87-165), preserved here:

    recurse(A):
      1. R11, R11inv = recurse(A11)                       # top-left
      2. R12 = R11⁻ᵀ · A12                                # TRSM phase (trmm)
      3. A22' = A22 − R12ᵀ·R12                            # Schur update (syrk)
      4. R22, R22inv = recurse(A22')
      5. R12inv = −R11inv · R12 · R22inv                  # inverse completion
         (skipped at the top level when complete_inv=False)

TPU re-design decisions (SURVEY §7.1):

* The reference's runtime window recursion over matrix views
  (`_restrict_`/cursor arithmetic, cholinv.hpp:107-142) becomes **trace-time
  Python recursion over static slices**: each (n, config) pair traces once
  and compiles to a single XLA program.  The reference's two-pass
  simulate/execute split (allocation dry-run at cholinv.hpp:22-26) maps to
  plan (host Python, `plan()`) vs execute (the traced `factor()`).
* Power-of-two padding (reference get_next_power2, util.hpp:249-264, and the
  trueLocalDimension plumbing) becomes one SPD-safe global pad: embed A in
  [[A, 0], [0, I]], factor, crop — the identity block factors to itself and
  never pollutes the A block.
* Base-case gather over the slice communicator + block↔cyclic repack + local
  LAPACK (policy.h:160-224) becomes a sharding constraint (XLA emits the
  all_gather) + lax.linalg on the replicated panel.  See
  utils/config.py:BaseCasePolicy for how the reference's four replication
  policies map.
* Mixed precision: trailing updates run in the input dtype (bf16-friendly);
  the base-case factorization runs in `base_case_dtype` (default f32 for
  low-precision inputs) — panel factorizations are the numerically fragile
  step, trailing matmuls are not.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from capital_tpu.ops import lapack, pallas_tpu
from capital_tpu.parallel import summa
from capital_tpu.robust import detect
from capital_tpu.robust.config import RobustConfig
from capital_tpu.parallel.summa import SyrkArgs, TrmmArgs
from capital_tpu.parallel.topology import Grid
from capital_tpu.utils import tracing
from capital_tpu.utils.config import BaseCasePolicy


@dataclasses.dataclass(frozen=True)
class CholinvConfig:
    """User configuration — mirrors cholesky::cholinv::info inputs
    (reference cholinv.h:16-44).

    complete_inv: compute the full R⁻¹ (True) or leave the off-diagonal
        block of the top-level inverse zero (False) — callers like cacqr's
        blocked solve use the diagonal inverse blocks + R12 instead
        (cacqr.hpp:46-73).
    split: recursion split shift — the top window is n >> split, so split=1
        halves (reference cholinv.hpp:15-18 semantics).
    base_case_dim: recursion bottoms out at windows <= this size.  Replaces
        the reference's sign/multiplier encoding (bc_mult_dim) with the size
        itself.
    policy: base-case replication strategy (see BaseCasePolicy).
    mode: SUMMA execution mode for the trmm/syrk phases
        ('xla'|'explicit'|'pallas' — 'pallas' skips dead triangular blocks
        on the MXU for single-device grids, parallel/summa.py).
    base_case_dtype: dtype for the base-case potrf+trtri; None means f32
        when the input is narrower than f32, else the input dtype.
    """

    complete_inv: bool = True
    split: int = 1
    base_case_dim: int = 256
    policy: BaseCasePolicy = BaseCasePolicy.REPLICATE_COMM_COMP
    mode: str = "xla"
    base_case_dtype: Optional[jnp.dtype] = None
    precision: Optional[str] = "highest"  # matmul precision for f32 inputs on
    # TPU: 'highest' keeps the trmm/syrk phases at full f32 (the MXU default
    # of bf16 passes costs ~3 decimal digits in the factor); set None to
    # inherit the context default when chasing raw throughput
    balance: str = "block"  # 'tile_cyclic' routes the EXPLICIT-mode
    # trmm/syrk phases through the tile-cyclic balanced schedules
    # (parallel/summa.py) for windows >= balance_min_window: the
    # critical-path device then executes ~the volumetric mean instead of
    # the full dense contraction.  Per-call row-shuffles are O(window²)
    # against O(window³) compute, so only large windows net positive —
    # small ones keep the block schedule (and side-R completion trmms
    # always do; the balanced form is side-L/syrk only).  No effect
    # outside explicit mode.
    # 'tile_cyclic_persistent' instead permutes the WHOLE matrix into the
    # symmetric tile-cyclic layout ONCE at factor entry (tile =
    # base_case_dim // d, so every recursion window stays aligned) and
    # un-permutes R / Rinv once at exit: three lifetime shuffles replace
    # the 2-3 per trmm/syrk call of 'tile_cyclic', every phase (including
    # the side-R completion trmms and the base-case windows) runs
    # balanced, and the per-call min_window economics disappear — so
    # balance_min_window is ignored.  Requires mode='explicit'; topologies
    # the layout cannot cover (d==1, c>1, non-square faces, base_case_dim
    # not divisible by d, or an unaligned split plan) fall back to the
    # block schedule with a 'cholinv::persistent_fallback' tracing note.
    balance_min_window: int = 8192
    schur_in_place: bool = False  # write each Schur complement back into the
    # input buffer (summa.syrk in_place) instead of materializing the
    # Σ(n/2ᵏ)² ≈ n²/3 chain of fresh trailing windows.  Peak memory drops
    # from ~3.35·n² to 3·n² — the knob that fits the n=49152 flagship on one
    # v5e (the reference's FlushIntermediates policy, policy.h:21-156,
    # re-imagined as buffer aliasing).  CONSUMES the caller's A: only safe
    # when A has no later use in the enclosing jit — if it does (e.g. the
    # standard bench loop carrying A across iterations, or a validation
    # reading A afterwards), XLA inserts a full-buffer copy that costs the
    # memory back plus an HBM pass, which is why this is opt-in.
    tail_fuse_depth: int = 0  # fuse recursion-tail subtrees into ONE pallas
    # megakernel (ops/pallas_tpu.fused_tail): any plan() window of size
    # <= base_case_dim << tail_fuse_depth that passes the trace-time gate
    # (_tail_fusible: single device, 128-aligned window, VMEM envelope via
    # batched_small.tail_eligible, f32-or-narrower dtype — f64 always
    # falls back to the unfused recursion) runs potrf, trsm, syrk and the
    # inverse-completion trmms as one launch with the panel VMEM-resident
    # across phases.  0 disables (the default: the fused sweep trades
    # ~12x executed flops for zero inter-phase HBM/launch cost, a win only
    # where the tail is latency-bound — autotune sweeps the depth).
    # depth=1 fuses base-case leaves (5 launches -> 1); each +1 fuses one
    # more recursion level.  Applies in every mode including the d=1
    # explicit path; ignored on multi-device grids and under the
    # persistent tile-cyclic layout.
    robust: Optional[RobustConfig] = None  # breakdown DETECTION: factor()
    # returns (R, Rinv, info) with a LAPACK-style int32 status of R
    # (robust/detect.factor_info) instead of NaN-filling silently on a
    # non-SPD input.  Detection only — no shifted rescue here: shifting a
    # user's gram inside cholinv would change the problem being solved;
    # the shifted-CholeskyQR recovery lives in models/qr.factor where the
    # shift is an internal implementation detail of the sweep.


# --------------------------------------------------------------------------
# plan: the host-side schedule (reference `simulate`, cholinv.hpp:50-83)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanNode:
    """One recursion window: [off, off+n) on the diagonal."""

    off: int
    n: int
    is_base: bool
    top: tuple["PlanNode", "PlanNode"] | None = None  # (A11-node, A22-node)


def padded_dim(n: int, base_case_dim: int) -> int:
    """Smallest base_case_dim * 2^k >= n (reference pads to a power of two,
    util.hpp:249-264; anchoring at the base-case size keeps every window an
    exact multiple of it)."""
    p = min(base_case_dim, n)
    while p < n:
        p *= 2
    return p


def pick_base_case(n: int, override: int = 0, cholinv_family: bool = True) -> int:
    """Padding-aware base-case pick for an n x n factor (`override` wins
    when nonzero).  The cholinv family's leaf potrf chain is the latency
    floor at small n, so finer leaves win below the measured crossovers
    (docs/PERF.md "Small-N — round 5": at n=4096, 128/256/512 measure
    25.3/24.7/23.5 TF/s; at n=8192, 57.5/60.3/59.1; 512 holds from 16384
    up within drift).  Candidates that tile n exactly are preferred, so
    n=49152 takes 384; when none does, the same preference order breaks
    ties among the least-padding candidates.  Other algorithms
    (cholinv_family=False) keep 512."""
    if override:
        return override
    if not cholinv_family:
        return 512
    if n <= 4096:
        order = (128, 256, 512, 384)
    elif n <= 8192:
        order = (256, 512, 384, 128)
    else:
        order = (512, 384, 256)
    for cand in order:
        if padded_dim(n, cand) == n:
            return cand
    return min(order, key=lambda c: (padded_dim(n, c), order.index(c)))


def pad_embed_identity(X: jnp.ndarray, n: int, p: int) -> jnp.ndarray:
    """Embed the n x n matrix X in diag(X, I) of size p — the structure-safe
    pad (reference pads to a power of two, util.hpp:249-264): SPD stays SPD
    and factors to diag(R, I); triangular stays triangular and inverts to
    diag(X⁻¹, I).  Shared by cholinv and rectri so padding policy cannot
    drift between them."""
    if p == n:
        return X
    Xp = jnp.pad(X, ((0, p - n), (0, p - n)))
    ii = jnp.arange(p)
    return Xp + jnp.diag((ii >= n).astype(X.dtype))


def top_split(n: int, cfg: CholinvConfig) -> int:
    """Column index where factor()'s top-level recursion splits the (cropped)
    n x n output — i.e. the boundary of the zeroed off-diagonal block of Rinv
    when complete_inv=False.  Shared by cacqr's blocked solve so the two
    modules cannot drift apart on padding/plan details.  Returns n when the
    whole matrix is a single base-case window (no split)."""
    node = plan(padded_dim(n, cfg.base_case_dim), cfg)
    return n if node.is_base else min(node.top[0].n, n)


def _zeros_plan(grid: Grid, node: PlanNode, cfg: CholinvConfig) -> int:
    """The buffer-initialization decision shared by factor() and
    factor_buffers(): returns the zeros_dead_lower tile size when the
    aligned sparse-init path applies (single device, every leaf window a
    tile multiple), else 0 (plain jnp.zeros).  One function so the two
    callers cannot drift — factor assumes out_buffers satisfy exactly the
    contract factor_buffers built them under."""

    def aligned(nd: PlanNode, tile: int) -> bool:
        if nd.is_base:
            return nd.off % tile == 0 and nd.n % tile == 0
        return all(aligned(c, tile) for c in nd.top)

    tile = min(512, cfg.base_case_dim)
    return tile if grid.num_devices == 1 and aligned(node, tile) else 0


def plan(n: int, cfg: CholinvConfig, off: int = 0) -> PlanNode:
    """Build the recursion schedule for a (padded) window of size n.

    Pure host computation — this is the analog of the reference's simulate
    pass: everything shape-dependent is decided here, once, before tracing.
    """
    if cfg.split < 1:
        raise ValueError(f"split must be >= 1 (split={cfg.split} would not shrink the window)")
    if n <= cfg.base_case_dim:
        return PlanNode(off=off, n=n, is_base=True)
    n1 = max(cfg.base_case_dim, n >> cfg.split)
    left = plan(n1, cfg, off)
    right = plan(n - n1, cfg, off + n1)
    return PlanNode(off=off, n=n, is_base=False, top=(left, right))


def persistent_tile(grid: Grid, node: PlanNode, cfg: CholinvConfig) -> int:
    """The layout tile for balance='tile_cyclic_persistent', or 0 when the
    topology/plan cannot hold the layout.  t = base_case_dim // d makes the
    layout's alignment quantum d*t == base_case_dim, and since every window
    of an aligned plan sits on a base_case_dim boundary, EVERY view of the
    recursion extracts/updates cleanly (parallel/summa.cyclic_window) —
    this is what lets one entry permute serve the whole factorization."""
    d = grid.dx
    if not (
        cfg.mode == "explicit"
        and grid.c == 1
        and grid.dy == d
        and d > 1
        and max(1, grid.num_chunks) == 1
        and cfg.base_case_dim % d == 0
    ):
        return 0

    bc = cfg.base_case_dim

    def aligned(nd: PlanNode) -> bool:
        if nd.off % bc or nd.n % bc:
            return False
        return nd.is_base or all(aligned(c) for c in nd.top)

    return bc // d if aligned(node) else 0


# --------------------------------------------------------------------------
# execute: the traced recursion (reference `invoke`, cholinv.hpp:87-165)
# --------------------------------------------------------------------------


def _base_case_into(
    grid: Grid,
    buf: jnp.ndarray,
    off: int,
    n: int,
    dest: int,
    cfg: CholinvConfig,
    Rp: jnp.ndarray,
    RIp: jnp.ndarray,
    ptile: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Leaf factorization: gather + local potrf/trtri (policy.h:160-224),
    reading the window (off, off, n, n) of `buf` (upper triangle valid) and
    writing the R / R⁻¹ blocks into Rp / RIp at diagonal offset `dest`.

    ptile != 0 (balance='tile_cyclic_persistent'): all three buffers are in
    the symmetric tile-cyclic storage layout — the window is extracted with
    a chunk-local reshape (summa.cyclic_window), locally un-permuted on the
    replicated panel (a bc x bc gather, free next to the potrf), factored,
    re-permuted, and written back in layout (band-sized update, no
    whole-buffer dus).

    The panel is replicated (XLA emits one all_gather over the mesh); which
    devices then FACTOR it is the policy (see _scoped_base_factor): every
    chip redundantly (REPLICATE_COMM_COMP, the TPU-optimal default), the
    z=0 layer + depth broadcast (REPLICATE_COMP), or the root device + mesh
    broadcast (NO_REPLICATION[_OVERLAP]).

    Single-device path: where `lapack.pallas_chol_fits`, one Pallas kernel
    reads the window, factors and inverts it, and writes both results in
    place (pallas_tpu.potrf_trtri_upper).  Otherwise the window read, the
    symmetric-panel rebuild, and both output writes run through the
    layout-opaque Pallas transpose kernel with views/in-place aliasing (no
    slice or scatter materialization, and no XLA-visible `.T` — see
    ops/lapack.py:potrf_trtri_upper for why that matters) around XLA's
    Cholesky and triangular solve.  Either way the leaf counts its path
    (`lapack.count_chol_route`).  Multi-device grids materialize the window
    (the panel is being replicated across the mesh anyway).
    """
    bc_dtype = cfg.base_case_dtype
    if bc_dtype is None:
        bc_dtype = buf.dtype if jnp.dtype(buf.dtype).itemsize >= 4 else jnp.float32
    # phase tag CI::factor_diag (reference cholinv.hpp:94-99)
    with tracing.scope("CI::factor_diag"):
        scope_ = cfg.policy.compute_scope
        comm, ncoll = tracing.replicate_cost(grid, n, n, bc_dtype)
        if grid.num_devices > 1 and scope_ != "all":
            # result broadcast: psum of the masked pair over 'z' (layer) or
            # the whole mesh (root)
            p = grid.c if scope_ == "layer" else grid.num_devices
            bcomm, bcoll = tracing.allreduce_cost(
                grid, n, n, bc_dtype, axes="z" if scope_ == "layer" else "all"
            )
            if p > 1:
                comm, ncoll = comm + 2 * bcomm, ncoll + 2 * bcoll
        tracing.emit(
            flops=tracing.potrf_trtri_flops(n), comm_bytes=comm, collectives=ncoll
        )
        if grid.num_devices == 1:
            pallas = jnp.dtype(bc_dtype) == jnp.float32 and (
                lapack.pallas_chol_fits(n, bc_dtype, off, dest, *buf.shape,
                                        *Rp.shape))
            lapack.count_chol_route(pallas, n)
            if pallas:
                # one kernel reads the window and writes both results in place
                return pallas_tpu.potrf_trtri_upper(
                    buf, off=off, n=n, Rp=Rp, RIp=RIp, dest=dest)
            # cholesky reads only the lower triangle (symmetrize_input=False)
            # = the transpose of the window's valid upper half
            P_low = pallas_tpu.transpose(
                buf, in_view=(off, off, n, n), out_uplo="L", out_dtype=bc_dtype
            )
            L = lax.linalg.cholesky(P_low, symmetrize_input=False)
            Linv = lax.linalg.triangular_solve(
                L, jnp.eye(n, dtype=bc_dtype), left_side=True, lower=True
            )
            # double-buffered write-back: both transposes in one launch,
            # two aliased output streams in flight per tile step
            return pallas_tpu.transpose_pair(L, Linv, Rp, RIp, dest=dest)
        # a mesh's leaf is XLA's potrf + trtri on the replicated panel
        lapack.count_chol_route(False, n)
        if ptile:
            wperm, winv = summa.tile_cyclic_perm(n, grid.dx, ptile)
            window = summa.cyclic_window(
                buf, (off, off, n, n), grid.dx, ptile
            ).astype(bc_dtype)
            window = lax.with_sharding_constraint(
                window, grid.replicated_sharding()
            )
            # the panel is replicated: index gathers stay on each chip
            # (summa.cyclic_permute's transposes would be partitioned
            # toward the sharded buffers they feed)
            iw = jnp.asarray(winv)
            R, Rinv = _scoped_base_factor(grid, window[iw][:, iw], scope_)
            pw = jnp.asarray(wperm)
            Rp = summa.cyclic_window_update(
                Rp, R.astype(Rp.dtype)[pw][:, pw], (dest, dest, n, n),
                grid.dx, ptile,
            )
            RIp = summa.cyclic_window_update(
                RIp, Rinv.astype(RIp.dtype)[pw][:, pw], (dest, dest, n, n),
                grid.dx, ptile,
            )
            return grid.pin(Rp), grid.pin(RIp)
        window = lax.slice(buf, (off, off), (off + n, off + n)).astype(bc_dtype)
        window = lax.with_sharding_constraint(window, grid.replicated_sharding())
        R, Rinv = _scoped_base_factor(grid, window, scope_)
        # i32 start indices: under x64 a Python-int index lowers as s64 and
        # the SPMD partitioner compares it against its own s32 shard offsets
        # (hlo-verifier rejection on the 0.4.x line)
        d32 = jnp.int32(dest)
        Rp = lax.dynamic_update_slice(Rp, R.astype(Rp.dtype), (d32, d32))
        RIp = lax.dynamic_update_slice(RIp, Rinv.astype(RIp.dtype), (d32, d32))
        return grid.pin(Rp), grid.pin(RIp)


def _scoped_base_factor(
    grid: Grid, window: jnp.ndarray, scope_: str
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """potrf+trtri of a replicated panel, executed by the devices the policy
    names (reference cholinv policy.h:160-514):

      'all'   — every device factors redundantly (no further collective)
      'layer' — only the z=0 depth layer factors; the pair is broadcast down
                'z' as a psum of the layer-masked value (≙ the reference's
                MPI_Bcast over the depth comm, policy.h:288-305)
      'root'  — only device (0,0,0) factors; the pair is broadcast over the
                whole mesh (≙ gather-to-root compute + scatter + bcast,
                policy.h:307-414; the OVERLAP variant's hand-rolled
                communication/compute overlap belongs to XLA's scheduler)

    The cond guards only local compute, never a collective; the zero branch
    is pcast to the varying type the psum needs.
    """
    if grid.num_devices == 1:
        return lapack.potrf_trtri_upper(window)
    if scope_ == "all" or (scope_ == "layer" and grid.c == 1):
        # multi-device redundant factorization: the XLA spelling, not the
        # Pallas-transpose one — Mosaic custom calls cannot be partitioned
        # by GSPMD over a replicated multi-device panel (found by the
        # round-4 AOT compile against a deviceless v5e-8 topology; the CPU
        # mesh hid it because interpret-mode pallas lowers to plain HLO),
        # and the layout-cascade rationale for the kernel is a single-chip
        # flagship concern
        from capital_tpu.ops import masking

        return lapack.potrf_trtri(masking.symmetrize_from(window, "U"), uplo="U")

    axes = ("z",) if scope_ == "layer" else ("x", "y", "z")

    def kernel(w):
        on = jnp.asarray(True)
        for a in axes:
            on = jnp.logical_and(on, lax.axis_index(a) == 0)

        def compute():
            # no pallas inside the shard_map body (vma annotations) — the
            # panel is a small replicated bc x bc block, so the jnp-level
            # symmetrize is fine here
            from capital_tpu.ops import masking

            R, Rinv = lapack.potrf_trtri(
                masking.symmetrize_from(w, "U"), uplo="U"
            )
            return (
                lax.pcast(R, axes, to="varying"),
                lax.pcast(Rinv, axes, to="varying"),
            )

        def zeros():
            z = jnp.zeros_like(w)
            return (
                lax.pcast(z, axes, to="varying"),
                lax.pcast(z, axes, to="varying"),
            )

        R, Rinv = lax.cond(on, compute, zeros)
        return lax.psum(R, axes), lax.psum(Rinv, axes)

    return jax.shard_map(
        kernel,
        mesh=grid.mesh,
        in_specs=P(),
        out_specs=(P(), P()),
    )(window)


def _tail_fusible(
    grid: Grid,
    buf: jnp.ndarray,
    off: int,
    node: PlanNode,
    cfg: CholinvConfig,
    top: bool,
    Rp: jnp.ndarray,
    ptile: int,
) -> bool:
    """Trace-time gate for collapsing this plan() subtree into the fused
    megakernel (pallas_tpu.fused_tail).  Every condition is static:

    * the knob is on and the window is within the fused size budget;
    * single device, block layout (the kernel addresses flat buffers);
    * a top-level window with complete_inv=False stays unfused (the fused
      kernel always assembles the full window inverse, which would fill
      the block the contract promises stays zero);
    * the window and both destination buffers are 128-lane aligned and
      whole-block addressable (power-of-two split=1 plans always are;
      split>=2 subtrees mis-align and fall back — correctly);
    * dtype within the kernel's f32 compute envelope — f64 falls back to
      the unfused path AT TRACE TIME, the PR 6 dispatch-gate lesson;
    * the working set fits VMEM (batched_small.tail_eligible)."""
    from capital_tpu.ops import batched_small

    if cfg.tail_fuse_depth <= 0:
        return False
    if node.n > cfg.base_case_dim << cfg.tail_fuse_depth:
        return False
    if grid.num_devices != 1 or ptile:
        return False
    if top and not cfg.complete_inv:
        return False
    if node.n % 128:
        return False
    if (off % node.n or node.off % node.n or buf.shape[0] % node.n
            or buf.shape[1] % node.n or Rp.shape[0] % node.n):
        return False
    if not batched_small.dtype_capable(buf.dtype):
        return False
    return batched_small.tail_eligible(node.n, buf.dtype)


def _recurse(
    grid: Grid,
    buf: jnp.ndarray,
    off: int,
    node: PlanNode,
    cfg: CholinvConfig,
    top: bool,
    Rp: jnp.ndarray,
    RIp: jnp.ndarray,
    ptile: int = 0,
    tail_infos: list | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One recursion window: input is the (off, off, node.n, node.n) window
    of `buf` (upper triangle valid — Schur windows from the uplo='U' syrk
    carry only that half), output blocks land in the preallocated p x p
    factor buffers Rp / RIp at the window's *absolute* diagonal offset
    node.off.  Returns the updated (buf, Rp, RIp); ALL passed-in values are
    consumed (in-place aliased writes on the pallas path — with
    schur_in_place the returned buf carries this window's Schur updates,
    and continuing from the pre-call value would force XLA to copy the
    whole buffer; see step 1 below).

    Working against two flat buffers instead of assembling per-level is a
    deliberate departure from the reference's per-window serialize calls: a
    per-level `jnp.block` of Rinv plus a final scatter of R cost ~5ms/iter
    of pure HBM traffic at n=16k on v5e (concatenate fusions + pad +
    dynamic-update-slice chains); with buffer views every block is written
    exactly once, in place, and the trmm/syrk operands read straight from
    the buffers through offset index maps (parallel/summa.py views).
    """
    if _tail_fusible(grid, buf, off, node, cfg, top, Rp, ptile):
        # the whole subtree — potrf panels, trsm, syrk, inverse-completion
        # trmms (and for a base node the leaf's five launches) — as ONE
        # pallas_call with the panel VMEM-resident across phases
        with tracing.scope("CI::tail_fused"):
            tracing.emit(flops=tracing.fused_tail_flops(node.n))
            Rp, RIp, kinfo = pallas_tpu.fused_tail(
                buf, Rp, RIp, off=off, n=node.n, dest=node.off,
                precision=cfg.precision,
            )
        if tail_infos is not None:
            tail_infos.append((node.off, node.n, kinfo))
        return buf, Rp, RIp

    if node.is_base:
        Rp, RIp = _base_case_into(
            grid, buf, off, node.n, node.off, cfg, Rp, RIp, ptile
        )
        return buf, Rp, RIp

    left, right = node.top
    n1, n2 = left.n, right.n
    d0 = node.off

    # 1. recurse on the top-left window (cholinv.hpp:108-111).  The child's
    # returned buf (identical unless schur_in_place wrote deeper Schur
    # updates into it) MUST replace ours: continuing from the pre-recursion
    # value would give that value a second use after the child's aliased
    # write consumed it, and XLA would restore single-assignment with a
    # full-buffer copy per spine level (measured: compile-time OOM at
    # n=49152 — 27.02G of 15.75G — from exactly this).
    buf, Rp, RIp = _recurse(
        grid, buf, off, left, cfg, False, Rp, RIp, ptile, tail_infos
    )

    # balanced schedules for the large explicit-mode windows (see
    # CholinvConfig.balance); summa falls back with a note where the
    # balanced form does not apply.  With the persistent layout there is no
    # per-window choice: the buffers ARE tile-cyclic, so every call states
    # the storage contract (min_window economics vanished with the
    # per-call shuffles)
    def _bal(win: int) -> str:
        if ptile:
            return "tile_cyclic_persistent"
        return (
            "tile_cyclic"
            if (
                cfg.balance == "tile_cyclic"
                and cfg.mode == "explicit"
                and win >= cfg.balance_min_window
            )
            else "block"
        )

    # 2. TRSM phase: R12 = R11⁻ᵀ · A12 (cholinv.hpp:116-123, tag CI::trsm).
    # The reference grid-transposes R11inv then trmms; here the transpose is
    # an argument flag and XLA plans the data motion.
    with tracing.scope("CI::trsm"):
        Rp = summa.trmm(
            grid, RIp, buf,
            TrmmArgs(side="L", uplo="U", trans_a=True, precision=cfg.precision),
            mode=cfg.mode,
            a_view=(d0, d0, n1, n1),
            b_view=(off, off + n1, n1, n2),
            out=Rp, out_off=(d0, d0 + n1),
            balance=_bal(n1), cyclic_tile=ptile,
        )

    # 3. Schur complement: A22' = A22 − R12ᵀR12 (cholinv.hpp:131-134, CI::tmu).
    # schur_in_place writes the update back into buf's own trailing window
    # (no fresh (n2, n2) buffer) and step 4 recurses on that window; the
    # default materializes the update and recurses on it at offset 0.
    with tracing.scope("CI::tmu"):
        S = summa.syrk(
            grid, Rp, buf,
            SyrkArgs(trans=True, alpha=-1.0, beta=1.0, precision=cfg.precision),
            mode=cfg.mode,
            a_view=(d0, d0 + n1, n1, n2),
            c_view=(off + n1, off + n1, n2, n2),
            in_place=cfg.schur_in_place,
            balance=_bal(n2), cyclic_tile=ptile,
        )

    # 4. recurse on the trailing window (cholinv.hpp:139-142).  In-place
    # mode: S IS the updated buf (the Schur update landed in buf's trailing
    # window), so thread it onward as this node's buffer value.
    s_off = off + n1 if cfg.schur_in_place else 0
    S, Rp, RIp = _recurse(
        grid, S, s_off, right, cfg, False, Rp, RIp, ptile, tail_infos
    )
    if cfg.schur_in_place:
        buf = S

    # 5. inverse completion: R⁻¹12 = −R11inv·R12·R22inv (cholinv.hpp:147-156),
    # skipped at the top level when complete_inv=False (the block stays the
    # zeros the buffer was initialized with, matching the reference contract).
    if cfg.complete_inv or not top:
        with tracing.scope("CI::inv"):
            T = summa.trmm(
                grid, RIp, Rp,
                TrmmArgs(side="L", uplo="U", precision=cfg.precision),
                mode=cfg.mode,
                a_view=(d0, d0, n1, n1),
                b_view=(d0, d0 + n1, n1, n2),
                balance=_bal(n1), cyclic_tile=ptile,
            )
            RIp = summa.trmm(
                grid, RIp, T,
                TrmmArgs(side="R", uplo="U", alpha=-1.0, precision=cfg.precision),
                mode=cfg.mode,
                a_view=(right.off, right.off, n2, n2),
                out=RIp, out_off=(d0, d0 + n1),
                # the side-R completion trmm never takes the per-call
                # balanced schedule (see CholinvConfig.balance), but under
                # the persistent layout it MUST state the storage contract
                balance="tile_cyclic_persistent" if ptile else "block",
                cyclic_tile=ptile,
            )
    return buf, Rp, RIp


# The fused-tail info min-combine lives in robust/detect.combine_block_infos
# — shared with the per-chain-block infos of models/blocktri.py.


@pallas_tpu.scoped_by_grid
def factor(
    grid: Grid,
    A: jnp.ndarray,
    cfg: CholinvConfig = CholinvConfig(),
    out_buffers: tuple[jnp.ndarray, jnp.ndarray] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Factor SPD A into (R, Rinv): A = RᵀR, Rinv = R⁻¹ (upper triangular).

    Equivalent of cholesky::cholinv::factor (cholinv.hpp:6-28); jit-friendly.
    When complete_inv=False the returned Rinv has its top-level off-diagonal
    block zeroed (only the two diagonal inverse blocks are valid), matching
    the reference's contract.

    out_buffers: optional (Rp, RIp) p x p working buffers to factor INTO
    (consumed — aliased writes).  Contract: their strictly-lower halves are
    zero and p == padded_dim(n, bc) with complete_inv=True.  The intended
    source is a PREVIOUS factor's outputs (a timed loop carrying them):
    the recursion rewrites every upper tile and never touches the dead
    lower zeros, so last iteration's results are exactly the
    initialization the next one needs — without this, XLA hoists the
    loop-invariant zero-init out of a benchmark loop and re-COPIES the
    buffers every iteration before the first aliased write (measured 2 x
    3.27 ms/iter at n=49152).

    With cfg.robust set the return is (R, Rinv, info): info is the int32
    breakdown status of the (cropped) factor — 0 clean, else the LAPACK
    potrf convention (robust/detect.factor_info)."""
    n = A.shape[0]
    if A.shape[0] != A.shape[1]:
        raise ValueError(f"cholinv needs a square matrix, got {A.shape}")
    if cfg.balance not in ("block", "tile_cyclic", "tile_cyclic_persistent"):
        raise ValueError(f"unknown balance {cfg.balance!r}")
    if cfg.balance.startswith("tile_cyclic") and cfg.mode != "explicit":
        # the balanced schedules exist only in the explicit schedule; a
        # silent block fallback here would mis-attribute a whole
        # load-balance experiment
        raise ValueError(f"balance={cfg.balance!r} requires mode='explicit'")
    p = padded_dim(n, cfg.base_case_dim)
    with tracing.scope("CI::io"):
        # SPD-safe pad: diag(A, I) factors to diag(R, I) without cross-talk.
        Ap = grid.pin(pad_embed_identity(A, n, p))
    node = plan(p, cfg)
    # fused-tail windows report breakdown through in-kernel info scalars
    # (collected at trace time, combined with the post-hoc scan below —
    # the guarded sweep produces no NaNs for factor_info to catch)
    tail_infos: list | None = [] if cfg.robust is not None else None

    # persistent tile-cyclic layout: permute ONCE here (V = Ap[perm][:, perm]
    # — a symmetric permutation, so SPD and the triangular-R contract of the
    # unchanged elimination order survive), run the whole recursion in
    # layout, un-permute R / Rinv once at exit.  Three lifetime shuffles
    # priced as grid transposes (the entry shuffle here, the two exit
    # shuffles below) replace the 2-3 shuffles PER trmm/syrk call of
    # balance='tile_cyclic'.
    ptile = 0
    if cfg.balance == "tile_cyclic_persistent":
        ptile = persistent_tile(grid, node, cfg)
        if ptile:
            with tracing.scope("CI::io"):
                Ap = grid.pin(summa.cyclic_permute(Ap, grid.dx, ptile))
                cbytes, ncoll = tracing.transpose_cost(grid, p, p, Ap.dtype)
                tracing.emit(comm_bytes=3 * cbytes, collectives=3 * ncoll)
        else:
            tracing.note("cholinv::persistent_fallback")

    if out_buffers is not None:
        Rp, RIp = out_buffers
        if Rp.shape != (p, p) or RIp.shape != (p, p):
            raise ValueError(
                f"out_buffers must be ({p}, {p}) for n={n}, "
                f"bc={cfg.base_case_dim}; got {Rp.shape}, {RIp.shape}"
            )
        if not cfg.complete_inv:
            raise ValueError(
                "out_buffers requires complete_inv=True (the skipped "
                "off-diagonal window would keep the previous contents)"
            )
        if ptile:
            # out_buffers arrive in ORIGINAL order (factor returns
            # un-permuted results); bring them into storage layout like Ap.
            # Zeros are permutation-invariant and every live cell is
            # rewritten, so the reuse contract holds — at the price of two
            # extra shuffles, which is why the flagship out_buffers loop
            # and the persistent layout are documented as an either/or
            # (docs/DISTRIBUTED.md).
            with tracing.scope("CI::io"):
                Rp = grid.pin(summa.cyclic_permute(Rp, grid.dx, ptile))
                RIp = grid.pin(summa.cyclic_permute(RIp, grid.dx, ptile))
                cbytes, ncoll = tracing.transpose_cost(grid, p, p, Rp.dtype)
                tracing.emit(comm_bytes=2 * cbytes, collectives=2 * ncoll)
        _, R, Rinv = _recurse(
            grid, Ap, 0, node, cfg, True, Rp, RIp, ptile, tail_infos
        )
        return _exit(grid, R, Rinv, n, ptile, cfg, tail_infos)

    tile = _zeros_plan(grid, node, cfg)
    if tile:
        # every tile of the upper triangle (diag leaf windows + TRSM /
        # inverse-completion panels) is written exactly once by the
        # recursion, on the aligned-pallas AND fallback paths alike — only
        # the dead lower half (plus the skipped top-right Rinv window when
        # complete_inv=False) needs actual zeros.  Gated on leaf/tile
        # alignment (_zeros_plan): split>=2 plans produce leaves smaller
        # than the tile, a diagonal tile then contains sub-diagonal area
        # outside every leaf window, and skipping jnp.zeros would return
        # hardware garbage there (invisible on CPU interpret, which
        # zero-fills unvisited blocks).
        with tracing.scope("CI::buffers"):
            Rp = pallas_tpu.zeros_dead_lower(p, A.dtype, tile)
            extra = (
                ()
                if cfg.complete_inv or node.is_base
                else ((0, node.top[0].n, node.top[0].n, p - node.top[0].n),)
            )
            RIp = pallas_tpu.zeros_dead_lower(p, A.dtype, tile, extra=extra)
    else:
        with tracing.scope("CI::buffers"):
            Rp = grid.pin(jnp.zeros((p, p), dtype=A.dtype))
            RIp = grid.pin(jnp.zeros((p, p), dtype=A.dtype))
    _, R, Rinv = _recurse(
        grid, Ap, 0, node, cfg, True, Rp, RIp, ptile, tail_infos
    )
    return _exit(grid, R, Rinv, n, ptile, cfg, tail_infos)


def _exit(grid, R, Rinv, n, ptile, cfg, tail_infos):
    """factor's outputs, under CI::io: back in the original order (the
    persistent layout of tile `ptile`, when nonzero), the padding cropped,
    and with cfg.robust the breakdown status of the cropped factor."""
    with tracing.scope("CI::io"):
        if ptile:
            R = summa.cyclic_permute(R, grid.dx, ptile, inverse=True)
            Rinv = summa.cyclic_permute(Rinv, grid.dx, ptile, inverse=True)
        R, Rinv = grid.pin(R), grid.pin(Rinv)
        if R.shape[0] != n:
            R, Rinv = R[:n, :n], Rinv[:n, :n]
        if cfg.robust is None:
            return R, Rinv
        info = detect.factor_info(R)
        if tail_infos:
            info = detect.combine_block_infos(info, tail_infos, n)
        return R, Rinv, info


def factor_buffers(
    grid: Grid, n: int, dtype, cfg: CholinvConfig = CholinvConfig()
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Freshly-initialized (Rp, RIp) working buffers satisfying factor's
    out_buffers contract — build ONCE outside a timed loop, then thread
    each iteration's outputs back in as the next iteration's buffers."""
    p = padded_dim(n, cfg.base_case_dim)
    node = plan(p, cfg)
    tile = _zeros_plan(grid, node, cfg)
    with pallas_tpu.device_scope(grid.mesh.devices.flat[0]):
        if tile:
            with tracing.scope("CI::buffers"):
                return (
                    pallas_tpu.zeros_dead_lower(p, dtype, tile),
                    pallas_tpu.zeros_dead_lower(p, dtype, tile),
                )
    # two DISTINCT buffers: sharing one value between two aliased consumer
    # chains would be the multi-use copy hazard this API exists to avoid
    with tracing.scope("CI::buffers"):
        return (
            grid.pin(jnp.zeros((p, p), dtype=dtype)),
            grid.pin(jnp.zeros((p, p), dtype=dtype)),
        )


def solve(
    grid: Grid,
    A: jnp.ndarray,
    B: jnp.ndarray,
    cfg: CholinvConfig = CholinvConfig(),
):
    """SPD solve A·X = B: cholinv factor + the two-trsm potrs sweeps
    (ops/lapack.potrs) — the posv capability serve.api rides (docs/SERVING.md).

    Runs the factorization with complete_inv=False: the solve consumes only
    R (potrs back-substitutes), so the inverse-completion trmms of the full
    R⁻¹ are skipped work here.  With cfg.robust set the return is (X, info)
    — info the int32 breakdown status of the factor (0 clean); X is
    garbage when info != 0 and must not be trusted.  Callers that already
    hold a factor should call lapack.potrs directly."""
    if B.shape[0] != A.shape[0]:
        raise ValueError(f"shape mismatch: A {A.shape} vs B {B.shape}")
    ccfg = dataclasses.replace(cfg, complete_inv=False)
    if cfg.robust is not None:
        R, _, info = factor(grid, A, ccfg)
        return lapack.potrs(R, B, uplo="U"), info
    R, _ = factor(grid, A, ccfg)
    return lapack.potrs(R, B, uplo="U")


def spd_inverse(
    grid: Grid, A: jnp.ndarray, cfg: CholinvConfig = CholinvConfig()
) -> jnp.ndarray:
    """A⁻¹ = R⁻¹·R⁻ᵀ for SPD A — the 'SPD inverse via Cholesky' capability
    (BASELINE.md config row 5)."""
    cfg = dataclasses.replace(cfg, complete_inv=True, robust=None)
    _, Rinv = factor(grid, A, cfg)
    return summa.gemm(
        grid, Rinv, Rinv,
        args=summa.GemmArgs(trans_b=True, precision=cfg.precision), mode=cfg.mode
    )
