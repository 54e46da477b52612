"""SUMMA: distributed matrix multiplication on the device grid.

TPU-native re-design of the reference's 3D SUMMA (src/alg/matmult/summa/
summa.hpp).  The reference implements C = alpha*op(A)op(B) + beta*C on a
d x d x c process grid by broadcasting A-panels along the row communicator and
B-panels along the column communicator from depth-dependent roots, running a
local MKL gemm, and allreducing partial C over the depth communicator
(summa.hpp:177-249), with an optional chunked Ibcast/Iallreduce pipeline
(num_chunks, summa.hpp:196-215).  Overloads cover gemm, in-place triangular
trmm, and syrk-via-transpose (summa.hpp:7-161).

Here the same capability is expressed two ways, selectable per call:

* ``mode='xla'`` (default): the contraction is written as a plain jnp matmul
  with sharding constraints pinning operands and result to the grid face; the
  XLA SPMD partitioner plans the panel gathers and the depth psum itself.
  This is the idiomatic TPU path — GSPMD already implements SUMMA-family
  schedules, and the latency-hiding scheduler overlaps the collectives the
  way the reference's chunked pipeline does by hand.

* ``mode='explicit'``: a shard_map kernel that owns the schedule exactly like
  the reference owns its MPI calls: ring all_gathers realize the row/column
  panel broadcasts (amortized — same (d-1)/d bytes as d ring bcasts, one
  collective per operand per chunk), K-segments partitioned over the depth
  axis 'z' (the 2.5D flop split), per-segment dead-block skipping for
  triangular operands/outputs, and a chunked psum over 'z' (the reference's
  MPI_Iallreduce collect, summa.hpp:236-248).  This path is the control
  knob for communication research and is benchmarked against 'xla'.

* ``mode='pallas'``: trmm/syrk route through the live-tile-enumerated Pallas
  kernels (ops/pallas_tpu.py), which skip the dead triangle's blocks on the
  MXU — the ~2x flop saving the reference gets from BLAS trmm/syrk, measured
  1.4-1.65x on v5e at 8192^2.  Currently single-device grids only (the local
  compute of a distributed call; triangular structure does not tile cleanly
  over block-distributed shards), so distributed calls and gemm (where XLA's
  dense matmul is already optimal) fall back to 'xla'.

Triangular structure (trmm) and symmetric rank-k updates (syrk) are expressed
as masked gemms: dense tiles + elementwise masks fuse into the matmul and keep
the MXU full, replacing the reference's packed-storage policies (SURVEY §7.1).

All functions take and return **global** jax Arrays (any sharding; they pin
layouts internally) and are jit-compatible.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from capital_tpu.ops import masking, pallas_tpu
from capital_tpu.parallel.topology import Grid
from capital_tpu.utils import tracing


@dataclasses.dataclass(frozen=True)
class GemmArgs:
    """Mirror of blas::ArgPack_gemm (reference src/blas/engine.h:72-94)."""

    alpha: float = 1.0
    beta: float = 0.0
    trans_a: bool = False
    trans_b: bool = False
    precision: str | None = None  # None = context default; 'highest' = f32 MXU


@dataclasses.dataclass(frozen=True)
class TrmmArgs:
    """Mirror of blas::ArgPack_trmm (reference src/blas/engine.h:96-112)."""

    side: str = "L"  # 'L': B <- alpha*op(A)B ; 'R': B <- alpha*B*op(A)
    uplo: str = "U"
    trans_a: bool = False
    diag: str = "N"  # 'N' non-unit, 'U' unit diagonal
    alpha: float = 1.0
    precision: str | None = None


@dataclasses.dataclass(frozen=True)
class SyrkArgs:
    """Mirror of blas::ArgPack_syrk (reference src/blas/engine.h:114-130)."""

    uplo: str = "U"
    trans: bool = False  # False: C = a*A*Aᵀ + b*C ; True: C = a*AᵀA + b*C
    alpha: float = 1.0
    beta: float = 0.0
    precision: str | None = None


def resolve_mode(mode: str, grid: Grid) -> str:
    """'auto' picks the SUMMA mode for the topology: the dead-block-skipping
    pallas kernels on a single TPU (mode='xla' leaves ~40% of cholinv
    throughput on the table there), GSPMD planning on a mesh (pallas is
    single-device-only and would fall back anyway).  Off-TPU, pallas means
    the interpreter — orders of magnitude slower than xla — so CPU runs stay
    on xla.  Any other mode passes through."""
    if mode != "auto":
        return mode
    one_tpu = grid.num_devices == 1 and grid.platform == "tpu"
    return "pallas" if one_tpu else "xla"


def default_precision(dtype) -> str | None:
    """The matmul precision for operands of `dtype`: 'highest' keeps f32 and
    wider at full accuracy on the MXU; narrower operands take the context
    default (the kernels drop 'highest' for them anyway)."""
    return None if jnp.dtype(dtype).itemsize < 4 else "highest"


# --------------------------------------------------------------------------
# explicit shard_map schedule
# --------------------------------------------------------------------------


def _seg_live_a_global(xi, s, ch, mb, lk, w, a_uplo):
    # A columns of (segment s, chunk ch): [s*lk + ch*w, +w); rows of this
    # device's block: [xi*mb, +mb).  Live = intersects the stored triangle.
    lo = s * lk + ch * w
    if a_uplo == "U":
        return xi * mb < lo + w  # ∃ row <= col
    return (xi + 1) * mb - 1 >= lo  # 'L': ∃ row >= col


def _seg_live_b_global(yi, s, ch, nb, lk, w, b_uplo):
    # B rows of (segment s, chunk ch); cols of this block: [yi*nb, +nb)
    lo = s * lk + ch * w
    if b_uplo == "U":
        return lo < (yi + 1) * nb
    return lo + w - 1 >= yi * nb


def tile_cyclic_perm(m: int, d: int, tile: int):
    """Row permutation realizing block-cyclic-over-tiles distribution on a
    d-row face: original row-tile g lands on device row g % d, local slot
    g // d — the reference's element-cyclic balancing idea
    (structure.hpp:80-85) at MXU-tile granularity, so whole tiles stay
    dead/alive and remain skippable.  Returns (perm, inv) as numpy index
    arrays: X[perm] is the cyclic layout, Y[inv] undoes it."""
    import numpy as np

    if m % (d * tile):
        raise ValueError(f"tile_cyclic_perm: {d} devices x tile {tile} must tile {m}")
    nt = m // tile
    order = [g for xi in range(d) for g in range(xi, nt, d)]
    perm = np.concatenate([np.arange(g * tile, (g + 1) * tile) for g in order])
    inv = np.empty_like(perm)
    inv[perm] = np.arange(m)
    return perm, inv


def cyclic_window(V: jnp.ndarray, view, d: int, tile: int) -> jnp.ndarray:
    """Extract the LOGICAL window ``view = (r0, c0, rows, cols)`` of a buffer
    stored in the PERSISTENT symmetric tile-cyclic layout V = X[perm][:, perm]
    (perm = tile_cyclic_perm(p, d, tile)) — without un-permuting.

    The layout is d contiguous device chunks per axis, chunk s holding
    original tiles ≡ s (mod d) ascending; a window aligned to d*tile is a
    CONTIGUOUS slice of every chunk, so extraction is reshape + static slice
    (shard-local under P('x','y'): the sliced axes are the unsharded
    within-chunk ones).  The result is itself in window-local tile-cyclic
    layout on both axes, and that local perm depends only on (extent, d,
    tile) — never on the offset — which is what lets every aligned window of
    the recursion interoperate (models/cholesky.py threads whole factors
    through this)."""
    r0, c0, rows, cols = view
    p, pc = V.shape
    g = d * tile
    if r0 % g or c0 % g or rows % g or cols % g or p % g or pc % g:
        raise ValueError(
            f"cyclic_window: view {view} of {(p, pc)} must align to "
            f"d*tile = {g}"
        )
    W = V.reshape(d, p // g, tile, pc)[:, r0 // g : (r0 + rows) // g]
    W = W.reshape(rows, pc)
    W = W.reshape(rows, d, pc // g, tile)[:, :, c0 // g : (c0 + cols) // g]
    return W.reshape(rows, cols)


def cyclic_window_update(
    V: jnp.ndarray, W: jnp.ndarray, view, d: int, tile: int
) -> jnp.ndarray:
    """Write a window-local tile-cyclic result W back into the window `view`
    of the persistent-layout buffer V (inverse of cyclic_window; V is
    consumed).  Touches only the window's chunk slices — the read-modify-
    write is band-sized, not buffer-sized (the whole-buffer dus round-trip
    this layout exists to remove)."""
    r0, c0, rows, cols = view
    p, pc = V.shape
    g = d * tile
    if r0 % g or c0 % g or rows % g or cols % g or p % g or pc % g:
        raise ValueError(
            f"cyclic_window_update: view {view} of {(p, pc)} must align to "
            f"d*tile = {g}"
        )
    a, b = r0 // g, (r0 + rows) // g
    e, f = c0 // g, (c0 + cols) // g
    V4 = V.reshape(d, p // g, tile, pc)
    band = V4[:, a:b].reshape(rows, pc)
    band = (
        band.reshape(rows, d, pc // g, tile)
        .at[:, :, e:f]
        .set(W.astype(V.dtype).reshape(rows, d, f - e, tile))
        .reshape(rows, pc)
    )
    return V4.at[:, a:b].set(band.reshape(d, b - a, tile, pc)).reshape(p, pc)


def _pick_cyclic_tile(grid: Grid, dim: int, override: int) -> int:
    """The ONE tile auto-pick + eligibility rule for balance='tile_cyclic'
    (trmm rows / syrk output): ~4 local tiles per device unless overridden;
    returns 0 when the topology/shape cannot take the cyclic schedule
    (c==1 square faces with d>1, tile tiling the global dim)."""
    d = grid.dx
    tile = override
    if tile == 0 and d > 1:
        base = dim // d // 4
        if dim // d >= 128:
            # MXU granularity: the schedule's skipping premise is whole
            # 128-aligned tiles, so the auto-pick must be a 128 multiple
            # (ragged sub-128 row slices waste the MXU and misalign the
            # cost model's granularity).  Search DOWN from ~4 tiles/device
            # for one that tiles the dim, and require more tiles than
            # devices — at nt == d the "cyclic" permutation is the
            # identity: zero balancing but two priced row-shuffles.
            t = max(base // 128 * 128, 128)
            while t >= 128 and (dim % (d * t) or dim // t <= d):
                t -= 128
            if t >= 128:
                tile = t
        elif base > 0 and (dim // d) % 4 == 0:
            # sub-MXU shapes (CPU-mesh tests, tiny problems): alignment is
            # moot; keep the 4-tiles-per-device heuristic
            tile = base
    ok = (
        grid.c == 1
        and grid.dx == grid.dy
        and d > 1
        and tile > 0
        and dim % (d * tile) == 0
    )
    return tile if ok else 0


def tri_fractions(
    grid: Grid,
    M: int,
    K: int,
    N: int,
    a_uplo: str | None = None,
    b_uplo: str | None = None,
    out_uplo: str | None = None,
    cyclic_rows: int = 0,
    cyclic_out: int = 0,
) -> tuple[float, float]:
    """(mean_frac, max_frac) of the dense per-device contraction that the
    explicit schedule actually EXECUTES under dead-segment/dead-output
    skipping, by enumerating the same liveness predicates the schedule
    compiles in (the functions above — one source of truth).

    mean = volumetric view; max = the critical-path device.  With block
    distribution a triangular operand leaves the fullest block row
    executing every segment (max_frac = 1.0) while the emptiest runs ~1/d
    — the load imbalance the reference's element-cyclic distribution
    (structure.hpp:80-85) avoids by construction.  cyclic_rows models the
    tile-cyclic balanced schedule instead (balance='tile_cyclic' on trmm):
    per-row-tile skipping makes max ≈ mean.  Used for the
    flops_vol/flops_max columns of the cost model (VERDICT r2 #4)."""
    d, c = grid.dx, grid.c
    if grid.num_devices == 1 or (a_uplo is None and b_uplo is None and out_uplo is None):
        return 1.0, 1.0
    if grid.dy != d or d % max(1, c) or M % d or K % d or N % d:
        return 1.0, 1.0  # shapes the explicit schedule would reject: dense model
    q = max(1, grid.num_chunks)
    lk = K // d
    if lk % q:
        return 1.0, 1.0
    w = lk // q
    mb, nb = M // d, N // d
    spl = d // c
    if cyclic_rows:
        # balanced schedule: per (local row-tile, segment, chunk) liveness
        # against the ORIGINAL tile index g = t*d + xi — same predicate as
        # the compiled schedule (_seg_live_a_global at tile granularity)
        tile = cyclic_rows
        if c != 1 or a_uplo is None or tile > mb or mb % tile:
            return 1.0, 1.0  # shapes the cyclic schedule would reject
        ntl = mb // tile
        fracs = []
        for xi in range(d):
            live = 0
            for t in range(ntl):
                g = t * d + xi
                for s in range(d):
                    for ch in range(q):
                        live += bool(
                            _seg_live_a_global(g, s, ch, tile, lk, w, a_uplo)
                        )
            fracs.append(live / (ntl * d * q))
        return sum(fracs) / len(fracs), max(fracs)
    if cyclic_out:
        # balanced tri-output (syrk): per local output TILE PAIR liveness
        # against original tile indices (gi, gj) — same predicate as the
        # compiled cyclic_out schedule
        tile = cyclic_out
        if (
            c != 1 or out_uplo is None or a_uplo is not None
            or b_uplo is not None or M != N or mb % tile
        ):
            return 1.0, 1.0
        ntl = mb // tile
        fracs = []
        for xi in range(d):
            for yi in range(d):
                live = sum(
                    (ti * d + xi <= tj * d + yi)
                    if out_uplo == "U"
                    else (ti * d + xi >= tj * d + yi)
                    for ti in range(ntl)
                    for tj in range(ntl)
                )
                fracs.append(live / (ntl * ntl))
        return sum(fracs) / len(fracs), max(fracs)
    fracs = []
    for zi in range(c):
        segs = (
            range(d) if c == 1 else [zi * spl + i for i in range(spl)]
        )
        denom = len(segs) * q
        for xi in range(d):
            for yi in range(d):
                if out_uplo is not None:
                    o_live = (
                        xi * mb < (yi + 1) * nb
                        if out_uplo == "U"
                        else (xi + 1) * mb - 1 >= yi * nb
                    )
                    if not o_live:
                        fracs.append(0.0)
                        continue
                live = 0
                for s in segs:
                    for ch in range(q):
                        la = (
                            _seg_live_a_global(xi, s, ch, mb, lk, w, a_uplo)
                            if a_uplo is not None
                            else True
                        )
                        lb = (
                            _seg_live_b_global(yi, s, ch, nb, lk, w, b_uplo)
                            if b_uplo is not None
                            else True
                        )
                        live += bool(la and lb)
                fracs.append(live / denom)
    return sum(fracs) / len(fracs), max(fracs)


def _shard_kernels_gate(
    grid: Grid,
    M: int,
    K: int,
    N: int,
    a_uplo: str | None,
    b_uplo: str | None,
    out_uplo: str | None,
    cyclic_rows: int = 0,
    cyclic_out: int = 0,
) -> bool:
    """Does the explicit schedule route its local compute through the
    live-tile Mosaic kernels per shard?  (round 5 — d == 1 grids with
    128-aligned blocks and static liveness; see _explicit_matmul.)  ONE
    predicate shared by the router and the cost model, so the executed
    view (flops_vol/flops_max) prices the tile skipping exactly when it
    happens."""
    d, c = grid.dx, grid.c
    q = max(1, grid.num_chunks)
    structured = (
        a_uplo is not None or b_uplo is not None or out_uplo is not None
    )
    if not (structured and d == 1 and grid.dy == 1 and c == 1 and q == 1):
        return False
    if cyclic_rows or cyclic_out:
        return False
    if M % d or K % d or N % d:
        return False
    mb, nb, lk = M // d, N // d, K // d
    return mb % 128 == 0 and nb % 128 == 0 and lk % 128 == 0


def _sched_blocks(mb: int, K: int, nb: int) -> tuple[int, int, int]:
    """(bm, bk, bn) tile sizes for the runtime-scheduled route: the largest
    of 512/256/128 dividing the extent AND leaving >= 4 tiles (skipping
    granularity — a single whole-extent tile can never be skipped), else
    the SMALLEST divisor (maximum granularity), else 0 (cannot tile)."""

    def pick(x: int) -> int:
        for b in (512, 256, 128):
            if x % b == 0 and x // b >= 4:
                return b
        for b in (128, 256, 512):
            if x % b == 0:
                return b
        return 0

    return pick(mb), pick(K), pick(nb)


def _sched_pairs(grid, M, K, N, a_uplo, b_uplo):
    """Per-device tile schedules for the d > 1 scheduled-kernel trmm route
    (round 5): (TO, KO, FI, LA) int32 arrays of shape (d, L) — device i's
    live (tile, k-tile) pairs, padded to the maximum by repeating the last
    pair with first=last=0 (safe no-ops, pallas_tpu.sched_matmul) — plus
    the executed fraction L/(nt*nk) and the block sizes.  None when the
    shapes cannot tile.  Every device runs L steps (SPMD lockstep makes
    the fullest device the wall time regardless), so the padded schedule
    costs nothing over the ideal."""
    import numpy as _np

    d = grid.dx
    mb, nb = M // d, N // d
    bm, bk, bn = _sched_blocks(mb, K, nb)
    if not (bm and bk and bn):
        return None
    uplo = a_uplo if a_uplo is not None else b_uplo
    a_side = a_uplo is not None
    bt = bm if a_side else bn
    nt, nk = (mb if a_side else nb) // bt, K // bk
    per_dev = []
    for xi in range(d):
        pairs = []
        for t in range(nt):
            r0 = xi * (mb if a_side else nb) + t * bt
            for k in range(nk):
                c0 = k * bk
                if a_side:
                    # A (M, K) triangular: row-tile origin r0, K origin c0
                    live = (c0 < r0 + bt) if uplo == "L" else (c0 + bk > r0)
                else:
                    # B (K, N) triangular: K origin c0 (rows), col origin r0
                    live = (c0 + bk > r0) if uplo == "L" else (c0 < r0 + bt)
                if live:
                    pairs.append((t, k))
        if not pairs:
            return None
        per_dev.append(pairs)
    L = max(len(p) for p in per_dev)
    TO = _np.zeros((d, L), _np.int32)
    KO = _np.zeros((d, L), _np.int32)
    FI = _np.zeros((d, L), _np.int32)
    LA = _np.zeros((d, L), _np.int32)
    for xi, pairs in enumerate(per_dev):
        for idx, (t, k) in enumerate(pairs):
            TO[xi, idx], KO[xi, idx] = t, k
            FI[xi, idx] = 1 if idx == 0 or pairs[idx - 1][0] != t else 0
            LA[xi, idx] = (
                1 if idx == len(pairs) - 1 or pairs[idx + 1][0] != t else 0
            )
        TO[xi, len(pairs):], KO[xi, len(pairs):] = pairs[-1]
    frac = L / float(nt * nk)
    if frac >= 1.0:
        # nothing skippable at this tiling (e.g. a single whole-extent
        # tile): the kernel adds bookkeeping over the segment loop for no
        # executed-flop win — stay on the segment path
        return None
    return (
        (jnp.asarray(TO), jnp.asarray(KO), jnp.asarray(FI), jnp.asarray(LA)),
        frac,
        (bm, bn, bk),
    )


def _sched_pairs_cyclic(grid, M, K, N, a_uplo, b_uplo, t):
    """_sched_pairs for the PERSISTENT tile-cyclic layout
    (balance='tile_cyclic_persistent'): the triangular operand's cyclic axis
    (rows for side L / cols for side R) AND the contraction axis are both
    stored in tile_cyclic_perm order, so liveness is evaluated at ORIGINAL
    tile indices — local storage tile j on device i is original tile j*d+i,
    and gathered storage K-tile kt (contributed by device kt // (K/(d*t)),
    slot kt mod that) is original K-tile (kt % nkc)*d + kt // nkc.  The
    tile size is pinned to the layout's t on the cyclic axes; the dense
    free axis picks the usual 512/256/128.  Under a cyclic K the interval
    segment predicates of the block schedule are simply WRONG (dead
    K-ranges are no longer contiguous), so there is no segment-skipping
    middle ground: callers fall back to a dense contraction on None."""
    import numpy as _np

    d = grid.dx
    a_side = a_uplo is not None
    uplo = a_uplo if a_side else b_uplo
    loc = M // d if a_side else N // d  # triangular/cyclic axis, local
    dense = N // d if a_side else M // d  # dense free axis, local
    if loc % t or K % (d * t):
        return None
    bfree = next((b for b in (512, 256, 128) if dense % b == 0), dense)
    ntl, nkc = loc // t, K // (d * t)
    nkt = d * nkc
    per_dev = []
    for xi in range(d):
        pairs = []
        for j in range(ntl):
            g = j * d + xi  # original tile on the cyclic output axis
            for kt in range(nkt):
                gk = (kt % nkc) * d + kt // nkc  # original K tile
                if a_side:
                    # A (M, K) triangular: U keeps cols >= rows
                    live = gk >= g if uplo == "U" else gk <= g
                else:
                    # B (K, N) triangular: U keeps rows <= cols
                    live = gk <= g if uplo == "U" else gk >= g
                if live:
                    pairs.append((j, kt))
        if not pairs:
            return None
        per_dev.append(pairs)
    L = max(len(p) for p in per_dev)
    TO = _np.zeros((d, L), _np.int32)
    KO = _np.zeros((d, L), _np.int32)
    FI = _np.zeros((d, L), _np.int32)
    LA = _np.zeros((d, L), _np.int32)
    for xi, pairs in enumerate(per_dev):
        for idx, (j, k) in enumerate(pairs):
            TO[xi, idx], KO[xi, idx] = j, k
            FI[xi, idx] = 1 if idx == 0 or pairs[idx - 1][0] != j else 0
            LA[xi, idx] = (
                1 if idx == len(pairs) - 1 or pairs[idx + 1][0] != j else 0
            )
        TO[xi, len(pairs):], KO[xi, len(pairs):] = pairs[-1]
    # padded lockstep like _sched_pairs; the cyclic layout makes per-device
    # live counts near-equal, so L ~ the volumetric mean — max == mean is
    # the whole point of the persistent layout
    frac = L / float(ntl * nkt)
    blocks = (t, bfree, t) if a_side else (bfree, t, t)
    return (
        (jnp.asarray(TO), jnp.asarray(KO), jnp.asarray(FI), jnp.asarray(LA)),
        frac,
        blocks,
    )


def _shard_sched_gate(grid, M, K, N, a_uplo, b_uplo, out_uplo,
                      cyclic_rows=0, cyclic_out=0):
    """Does the d > 1 explicit schedule route through the runtime-scheduled
    per-shard kernels?  trmm shapes only (exactly one triangular operand);
    c == 1, unchunked, tileable.  Shared by the router and the cost model
    like _shard_kernels_gate."""
    d, c = grid.dx, grid.c
    q = max(1, grid.num_chunks)
    if not (d > 1 and grid.dy == d and c == 1 and q == 1):
        return None
    if (a_uplo is None) == (b_uplo is None) or out_uplo is not None:
        return None
    if cyclic_rows or cyclic_out:
        return None
    if M % d or K % d or N % d:
        return None
    return _sched_pairs(grid, M, K, N, a_uplo, b_uplo)


def _explicit_matmul(
    grid: Grid,
    A: jnp.ndarray,
    B: jnp.ndarray,
    precision: str | None = None,
    a_uplo: str | None = None,
    b_uplo: str | None = None,
    out_uplo: str | None = None,
    cyclic_rows: int = 0,
    cyclic_out: int = 0,
    sched=None,
) -> jnp.ndarray:
    """C = A @ B with the explicit SUMMA schedule on the d x d x c grid.
    `sched` forwards _matmul's already-built device schedule (the cost
    model evaluates the same gate; building the O(d·nt·nk) arrays twice
    per trace would be pure waste) — direct callers may omit it.

    Schedule (the reference's distribute/compute/collect, summa.hpp:177-249,
    re-expressed with the collectives TPU SPMD actually has):

      c == 1:  a_row = all_gather(A block, 'y')   # the d per-step row-comm
               b_col = all_gather(B block, 'x')   # Bcasts of summa.hpp:185-193
               acc  += a_row @ b_col               # amortized into one ring
                                                   # gather per operand: same
                                                   # (d-1)/d * bytes as d ring
                                                   # bcasts, 1 collective vs d
      c  > 1:  for each of this layer's d/c K-steps:
                 a_panel = psum(mask(y == k, A chunk), 'y')  # root bcast as
                 b_panel = psum(mask(x == k, B chunk), 'x')  # masked psum
                 acc += a_panel @ b_panel
               # per-step bcasts move only the layer's 1/c of the panels —
               # the 2.5D comm saving (topology.h:76-78); an amortized
               # full-row gather here would pay c/2 x the bytes (masked psum
               # costs 2x a ring bcast per panel, but c x fewer panels move).
      C = psum(acc, 'z')                  # depth collect (summa.hpp:236)

    (A true per-step one-to-many broadcast has no native SPMD primitive, so
    the two encodings above trade bytes against synchronization: the
    amortized gather is ring-bcast-byte-optimal and wins whenever a layer
    needs every panel (c == 1, and ties at c == 2); the masked psum pays 2x
    per moved panel but scales with the depth split.  tracing.gemm_cost
    prices whichever this function emits.)

    K-segments are assigned to depth layers contiguously — layer z owns
    segments [z*d/c, (z+1)*d/c).

    With grid.num_chunks = q > 1 both gathers and the depth collect are
    split into q independent slices — the reference's Ibcast/Iallreduce
    pipeline (summa.hpp:196-215, 239-248): each slice is a separate
    collective the latency-hiding scheduler can overlap with the previous
    slice's local matmul, and peak memory for the gathered row/col drops by
    q.  The chunk loop is unrolled at trace time (static shapes).

    Triangular structure (the distributed dead-block saving, reference
    summa.hpp:47-161 via local BLAS trmm/syrk):
      a_uplo/b_uplo — the operand *as passed* is upper/lower triangular
          (already masked by the caller); K-segments entirely inside its
          dead triangle for this device's block row/column are skipped with
          lax.cond, so the dead half of a distributed trmm never reaches
          the MXU.  Volumetric flops drop ~2x; note the *critical path* is
          still the fullest block row (block distribution does not load-
          balance a triangle the way the reference's element-cyclic layout
          does — that rebalancing is a layout choice, not a schedule one).
      out_uplo — only that triangle of C is needed: devices whose C block
          is entirely dead skip all local compute (syrk's saving; the
          caller symmetrizes or reads the live triangle only).

    Local accumulation is f32 for sub-f32 inputs (the pallas kernels'
    accumulator discipline); each layer's partial is cast back to the wire
    dtype before the depth psum, so collect bytes match the operand dtype.
    """
    d, c = grid.dx, grid.c
    if grid.dy != d:
        raise ValueError("explicit SUMMA requires a square grid face")
    if d % c != 0:
        raise ValueError(f"depth c={c} must divide face d={d}")
    (M, K), (K2, N) = A.shape, B.shape
    if K != K2:
        raise ValueError(f"inner dims mismatch: {A.shape} @ {B.shape}")
    if M % d or K % d or N % d:
        raise ValueError(f"global dims {(M, K, N)} must be divisible by d={d}")

    if cyclic_rows:
        # tile-cyclic row balance: A's rows (and the output's) are in
        # tile_cyclic_perm order — local row-tile t on device xi is
        # ORIGINAL tile t*d + xi, and per-(tile, segment) liveness is
        # tested against the original index, so every device carries an
        # equal share of the triangle's live work (max-per-process ==
        # volumetric, vs 1.0 under contiguous blocks — see tri_fractions)
        if c != 1 or a_uplo is None or b_uplo is not None or out_uplo is not None:
            raise ValueError(
                "cyclic_rows supports the c==1 triangular-A (side-L trmm) "
                "schedule only"
            )
        if (M // d) % cyclic_rows:
            raise ValueError(
                f"cyclic tile {cyclic_rows} must divide the local rows {M // d}"
            )
    if cyclic_out:
        # tile-cyclic SYMMETRIC-output balance (syrk): BOTH output axes are
        # in tile_cyclic_perm order (C_p = A_pᵀA_p with A's columns
        # permuted), so local output tile (ti, tj) on device (xi, yi) is
        # ORIGINAL tile pair (ti*d + xi, tj*d + yi) and the dead-triangle
        # skip tests original indices — every device carries ~half the
        # tile pairs regardless of position
        if c != 1 or out_uplo is None or a_uplo is not None or b_uplo is not None:
            raise ValueError(
                "cyclic_out supports the c==1 tri-output (syrk) schedule only"
            )
        if (M // d) % cyclic_out or (N // d) % cyclic_out or M != N:
            raise ValueError(
                f"cyclic_out tile {cyclic_out} must tile the square local "
                f"block {(M // d, N // d)}"
            )

    spl = d // c  # K-segments owned by each depth layer
    q = max(1, grid.num_chunks)
    lk = K // d  # local K extent (A cols = B rows per device)
    if lk % q:
        raise ValueError(f"num_chunks={q} must divide the local K extent {lk}")
    w = lk // q  # K-slice width per chunk, per segment
    mb, nb = M // d, N // d
    wire_dtype = jnp.result_type(A, B)
    acc_dtype = jnp.promote_types(wire_dtype, jnp.float32)

    def _seg_live_a(xi, s, ch):
        return _seg_live_a_global(xi, s, ch, mb, lk, w, a_uplo)

    def _seg_live_b(yi, s, ch):
        return _seg_live_b_global(yi, s, ch, nb, lk, w, b_uplo)

    solo = getattr(grid, "collective_concurrency", "free") == "solo"

    # round 5 (VERDICT r4 #2, second half): route the LOCAL compute of the
    # explicit schedule through the live-tile Mosaic kernels per shard —
    # the reference's per-rank BLAS trmm/syrk saving at tile granularity
    # (blas/interface.hpp:74-97) instead of K-segment granularity.  Inside
    # shard_map the partitioning is manual, so the single-device kernels
    # compile unchanged (the fused-CQR2 finding).  First increment: d == 1
    # grids, where liveness is static — this is exactly the configuration
    # that prices the mesh machinery's overhead (the DISTRIBUTED.md
    # single-chip constant), and tile skipping removes its 2x flop
    # penalty.  d > 1 needs runtime (device-indexed) schedules and stays
    # on the K-segment path.  check_vma is disabled on this route: the
    # kernels' out_shapes carry no varying-axes annotation, and the
    # guarded-zeros vma logic is never reached.
    shard_kernels = _shard_kernels_gate(
        grid, M, K, N, a_uplo, b_uplo, out_uplo, cyclic_rows, cyclic_out
    )
    if shard_kernels:
        tracing.note("explicit::shard_kernels")
        sched = None
    elif sched is None:  # direct callers: build what _matmul forwards
        sched = _shard_sched_gate(
            grid, M, K, N, a_uplo, b_uplo, out_uplo, cyclic_rows, cyclic_out
        )
    if sched is not None:
        tracing.note("explicit::shard_sched")

    def kernel(a, b):
        # a: (M/d, K/d) block at (x, y);  b: (K/d, N/d) block at (x, y)
        xi = lax.axis_index("x")
        yi = lax.axis_index("y")
        zi = lax.axis_index("z")

        # collective_concurrency='solo' (Grid knob — the reference's
        # COLLECTIVE_CONCURRENCY_SOLO congestion experiment,
        # summa.hpp:179-192): chain every collective behind the previous
        # one with an optimization_barrier data dependency, so at most one
        # is in flight.  `chain` threads a token value through each
        # collective's INPUT; 'free' mode is the identity.
        token = [None]

        def chain(x):
            if not solo:
                return x
            if token[0] is not None:
                x, _ = lax.optimization_barrier((x, token[0]))
            return x

        def stamp(res):
            if solo:
                # tie the token to one element (cheap; keeps the barrier
                # operand small and the dependency real)
                token[0] = lax.slice(res.reshape(-1), (0,), (1,))
            return res

        if shard_kernels:
            a_ch = stamp(lax.all_gather(chain(a), "y", axis=1, tiled=True))
            b_ch = stamp(lax.all_gather(chain(b), "x", axis=0, tiled=True))
            if out_uplo is not None:
                part = pallas_tpu.tri_matmul(
                    a_ch, b_ch, out_uplo=out_uplo, precision=precision
                )
            else:
                part = pallas_tpu.tri_matmul(
                    a_ch, b_ch, a_uplo=a_uplo, b_uplo=b_uplo,
                    precision=precision,
                )
            return part.astype(wire_dtype)
        if sched is not None:
            # d > 1: each device selects ITS OWN tile schedule by mesh
            # position and runs the scheduled kernel on the gathered slabs
            (TO, KO, FI, LA), _, blocks = sched
            a_ch = stamp(lax.all_gather(chain(a), "y", axis=1, tiled=True))
            b_ch = stamp(lax.all_gather(chain(b), "x", axis=0, tiled=True))
            sel = xi if a_uplo is not None else yi
            part = pallas_tpu.sched_matmul(
                a_ch, b_ch,
                jnp.take(TO, sel, axis=0), jnp.take(KO, sel, axis=0),
                jnp.take(FI, sel, axis=0), jnp.take(LA, sel, axis=0),
                tri_side="a" if a_uplo is not None else "b",
                blocks=blocks, precision=precision,
            )
            return part.astype(wire_dtype)

        # every liveness test guards ONLY local matmuls, never a collective:
        # the gathers run unconditionally on all devices (a collective under
        # a device-varying cond would desynchronize the mesh)
        out_live = None
        if out_uplo is not None:
            out_live = (
                xi * mb < (yi + 1) * nb
                if out_uplo == "U"
                else (xi + 1) * mb - 1 >= yi * nb
            )

        def guarded(live, mm, *operands, shape=None):
            if live is None:
                return mm()
            # the zero branch must carry the same varying-manual-axes type as
            # the matmul branch (cond requires equal output types under
            # shard_map's replication checking): mark it varying over the
            # union of the operands' axes
            vma: set = set()
            for r in operands:
                vma |= jax.typeof(r).vma
            zeros = jnp.zeros(shape or (mb, nb), dtype=acc_dtype)
            if vma:
                zeros = lax.pcast(zeros, tuple(sorted(vma)), to="varying")
            return lax.cond(live, mm, lambda: zeros)

        def matmul_term(live, a_op, b_op):
            return guarded(
                live,
                lambda: jnp.matmul(
                    a_op, b_op, precision=precision,
                    preferred_element_type=acc_dtype,
                ),
                a_op, b_op,
            )

        acc = jnp.zeros((mb, nb), dtype=acc_dtype)
        if c == 1:
            for ch in range(q):
                # gathered chunk: segment-major — segment s holds global
                # K-range [s*lk + ch*w, +w), contributed by device s of the
                # gather axis; A's and B's segment decompositions of K match
                # because the face is square
                a_ch = stamp(lax.all_gather(
                    chain(a[:, ch * w : (ch + 1) * w]), "y", axis=1, tiled=True
                ))
                b_ch = stamp(lax.all_gather(
                    chain(b[ch * w : (ch + 1) * w, :]), "x", axis=0, tiled=True
                ))
                if cyclic_out:
                    # balanced tri-output skipping: per LOCAL OUTPUT TILE
                    # PAIR — original tile pair (gi, gj) is live iff it
                    # touches the stored triangle of the UN-permuted C
                    T = cyclic_out
                    for ti in range(mb // T):
                        gi = ti * d + xi
                        a_t = lax.slice_in_dim(a_ch, ti * T, (ti + 1) * T, axis=0)
                        for tj in range(nb // T):
                            gj = tj * d + yi
                            live = gi <= gj if out_uplo == "U" else gi >= gj
                            tile_mm = guarded(
                                live,
                                lambda a_=a_t, tj_=tj: jnp.matmul(
                                    a_,
                                    lax.slice_in_dim(
                                        b_ch, tj_ * T, (tj_ + 1) * T, axis=1
                                    ),
                                    precision=precision,
                                    preferred_element_type=acc_dtype,
                                ),
                                a_t, b_ch,
                                shape=(T, T),
                            )
                            acc = acc.at[
                                ti * T : (ti + 1) * T, tj * T : (tj + 1) * T
                            ].add(tile_mm)
                elif a_uplo is None and b_uplo is None:
                    acc = acc + matmul_term(out_live, a_ch, b_ch)
                elif cyclic_rows:
                    # balanced skipping: per LOCAL ROW-TILE x segment —
                    # each tile row-band contracts only the K-segments
                    # intersecting its ORIGINAL tile's live range (the
                    # SAME predicate as block mode, applied at tile
                    # granularity with the original tile index g)
                    tile = cyclic_rows
                    for t in range(mb // tile):
                        g = t * d + xi  # traced original row-tile index
                        a_t = lax.slice_in_dim(
                            a_ch, t * tile, (t + 1) * tile, axis=0
                        )
                        for s in range(d):
                            live = _seg_live_a_global(
                                g, s, ch, tile, lk, w, a_uplo
                            )
                            a_ts = lax.slice_in_dim(
                                a_t, s * w, (s + 1) * w, axis=1
                            )
                            b_s = lax.slice_in_dim(
                                b_ch, s * w, (s + 1) * w, axis=0
                            )
                            band = guarded(
                                live,
                                lambda a_=a_ts, b_=b_s: jnp.matmul(
                                    a_, b_, precision=precision,
                                    preferred_element_type=acc_dtype,
                                ),
                                a_ts, b_s,
                                shape=(tile, nb),
                            )
                            acc = acc.at[t * tile : (t + 1) * tile].add(band)
                else:
                    # triangular operand: per-segment liveness — dead
                    # segments never reach the MXU (summa.hpp:47-161's
                    # saving, at K-segment granularity)
                    for s in range(d):
                        a_s = lax.slice_in_dim(
                            a_ch, s * w, (s + 1) * w, axis=1
                        )
                        b_s = lax.slice_in_dim(
                            b_ch, s * w, (s + 1) * w, axis=0
                        )
                        live = None
                        if a_uplo is not None:
                            live = _seg_live_a(xi, s, ch)
                        if b_uplo is not None:
                            lb = _seg_live_b(yi, s, ch)
                            live = lb if live is None else jnp.logical_and(live, lb)
                        if out_live is not None:
                            live = (
                                out_live
                                if live is None
                                else jnp.logical_and(live, out_live)
                            )
                        acc = acc + matmul_term(live, a_s, b_s)
        else:
            # per-step masked-psum broadcast of this layer's own d/c panels
            # (the 2.5D comm saving); the liveness conds still skip the
            # matmul of dead panels, but the bcast itself is unconditional
            for i in range(spl):
                k = zi * spl + i  # traced: the layer's i-th global K-step
                for ch in range(q):
                    a_sl = a[:, ch * w : (ch + 1) * w]
                    b_sl = b[ch * w : (ch + 1) * w, :]
                    a_panel = stamp(lax.psum(
                        chain(jnp.where(yi == k, a_sl, jnp.zeros_like(a_sl))), "y"
                    ))
                    b_panel = stamp(lax.psum(
                        chain(jnp.where(xi == k, b_sl, jnp.zeros_like(b_sl))), "x"
                    ))
                    live = None
                    if a_uplo is not None:
                        live = _seg_live_a(xi, k, ch)
                    if b_uplo is not None:
                        lb = _seg_live_b(yi, k, ch)
                        live = lb if live is None else jnp.logical_and(live, lb)
                    if out_live is not None:
                        live = (
                            out_live
                            if live is None
                            else jnp.logical_and(live, out_live)
                        )
                    acc = acc + matmul_term(live, a_panel, b_panel)

        part = acc.astype(wire_dtype)  # collect in the wire dtype
        if c == 1:
            return part
        # chunked depth collect (the reference's Iallreduce slices,
        # summa.hpp:239-248): q independent psums over column slices —
        # uneven widths when q does not divide the block; zero-width tails
        # (q > nb) are skipped, so min(q, nb) psums are emitted, which is
        # what tracing.gemm_cost counts
        widths = [nb // q + (1 if j < nb % q else 0) for j in range(q)]
        pieces, off = [], 0
        for wd in widths:
            if wd:
                pieces.append(stamp(lax.psum(chain(part[:, off : off + wd]), "z")))
                off += wd
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)

    return jax.shard_map(
        kernel,
        mesh=grid.mesh,
        in_specs=(P("x", "y"), P("x", "y")),
        out_specs=P("x", "y"),
        check_vma=not (shard_kernels or sched is not None),
    )(grid.pin(A), grid.pin(B))


# --------------------------------------------------------------------------
# public ops
# --------------------------------------------------------------------------


def _matmul(
    grid: Grid,
    A: jnp.ndarray,
    B: jnp.ndarray,
    mode: str,
    precision: str | None = None,
    a_uplo: str | None = None,
    b_uplo: str | None = None,
    out_uplo: str | None = None,
    cyclic_rows: int = 0,
    cyclic_out: int = 0,
    sched_override=None,
) -> jnp.ndarray:
    """The uplo flags describe triangular structure of the (already masked)
    operands/result; only mode='explicit' exploits them (dead K-segments /
    dead output blocks skipped per device).  The homogeneous model count
    (`flops`) stays dense; the executed views carry the skipping:
    flops_vol (mean over devices) and flops_max (the critical-path device,
    which with block distribution still runs up to the full contraction —
    see tri_fractions).  sched_override hands in an externally built
    per-device tile schedule (_sched_pairs_cyclic — the persistent layout,
    whose liveness the gates here cannot derive from shapes alone)."""
    # cost-model attribution (no-op without an active tracing.Recorder)
    M, K, N = A.shape[0], A.shape[1], B.shape[1]
    flops, comm, ncoll = tracing.gemm_cost(
        grid, M, N, K, jnp.result_type(A, B)
    )
    if mode == "explicit":
        sched = None
        if sched_override is not None:
            sched = sched_override
            mean_f = max_f = sched[1]
        elif _shard_kernels_gate(
            grid, M, K, N, a_uplo, b_uplo, out_uplo, cyclic_rows, cyclic_out
        ):
            # per-shard live-tile kernels: same /2 executed convention as
            # the single-device pallas branches (tile skipping)
            mean_f = max_f = 0.5
        elif (
            sched := _shard_sched_gate(
                grid, M, K, N, a_uplo, b_uplo, out_uplo, cyclic_rows,
                cyclic_out,
            )
        ) is not None:
            # runtime-scheduled per-shard kernels: every device runs the
            # padded maximum schedule, so mean == max == L/(nt*nk)
            mean_f = max_f = sched[1]
        else:
            mean_f, max_f = tri_fractions(
                grid, M, K, N, a_uplo, b_uplo, out_uplo,
                cyclic_rows=cyclic_rows, cyclic_out=cyclic_out,
            )
    else:
        mean_f = max_f = 1.0  # dense+mask executes the full contraction
    tracing.emit(
        flops=flops, comm_bytes=comm, collectives=ncoll,
        flops_vol=flops * mean_f, flops_max=flops * max_f,
    )
    if mode in ("xla", "pallas"):  # gemm has no dead blocks: XLA is optimal
        return grid.pin(jnp.matmul(grid.pin(A), grid.pin(B), precision=precision))
    if mode == "explicit":
        return _explicit_matmul(
            grid, A, B, precision, a_uplo, b_uplo, out_uplo, cyclic_rows,
            cyclic_out, sched=sched,
        )
    raise ValueError(f"unknown summa mode {mode!r}")


@pallas_tpu.scoped_by_grid
def gemm(
    grid: Grid,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray | None = None,
    args: GemmArgs = GemmArgs(),
    mode: str = "xla",
) -> jnp.ndarray:
    """C = alpha * op(A) @ op(B) + beta * C  (reference summa.hpp:7-44)."""
    Aop = A.T if args.trans_a else A
    Bop = B.T if args.trans_b else B
    if args.beta != 0.0 and C is None:
        raise ValueError("beta != 0 requires the accumulate operand C")
    out = _matmul(grid, Aop, Bop, mode, args.precision)
    if args.alpha != 1.0:
        out = args.alpha * out
    if args.beta != 0.0:
        out = out + args.beta * grid.pin(C)
    return grid.pin(out)


def _take_view(X, view):
    if X is None or view is None:
        return X
    return pallas_tpu._window(X, view)


def _i32_off(off):
    # i32 start indices for dynamic_update_slice on sharded buffers: under
    # x64 a Python-int index lowers as s64 and the 0.4.x SPMD partitioner
    # compares it against its own s32 shard offsets (hlo-verifier rejection)
    return tuple(jnp.int32(o) for o in off)


def _persistent_params(grid: Grid, mode: str, cyclic_tile: int, who: str):
    """Validate a balance='tile_cyclic_persistent' call.  Unlike
    'tile_cyclic' (a schedule preference with a benign block fallback),
    'persistent' is a STORAGE contract: the caller asserts the passed
    buffers are in the symmetric tile-cyclic layout, so any silent fallback
    would read them as block-ordered and compute garbage — violations
    raise."""
    d = grid.dx
    q = max(1, grid.num_chunks)
    if (
        mode != "explicit" or grid.c != 1 or grid.dy != d or d < 2
        or q != 1 or cyclic_tile < 1
    ):
        raise ValueError(
            f"{who}: balance='tile_cyclic_persistent' requires "
            "mode='explicit' on an unchunked c==1 square face with d>1 and "
            f"an explicit cyclic_tile >= 1 (the layout's tile); got "
            f"mode={mode!r}, grid {grid.dx}x{grid.dy}x{grid.c}, chunks={q}, "
            f"cyclic_tile={cyclic_tile}"
        )
    return d, cyclic_tile


def _copy_bytes_of(*terms) -> float:
    """Sum of (factor, array) HBM-copy prices: factor counts reads+writes
    of the moved array (2.0 = one read + one write)."""
    return float(
        sum(f * a.size * jnp.dtype(a.dtype).itemsize for f, a in terms)
    )


def _trmm_persistent(
    grid, A, B, args, mode, a_view, b_view, out, out_off, cyclic_tile
):
    """trmm where EVERY passed buffer is stored in the persistent symmetric
    tile-cyclic layout V = X[perm][:, perm] (models/cholesky.py's
    balance='tile_cyclic_persistent'): window reads are chunk-local
    reshapes (cyclic_window), the triangle mask tests original indices
    (masking.take_triangle_cyclic), liveness is scheduled per original
    tile (_sched_pairs_cyclic -> pallas_tpu.sched_matmul with the layout's
    tile), and the product emerges ALREADY in layout — zero per-call row
    shuffles, where balance='tile_cyclic' pays two per call."""
    d, t = _persistent_params(grid, mode, cyclic_tile, "trmm")
    if args.diag == "U":
        raise ValueError(
            "tile_cyclic_persistent trmm does not support diag='U'"
        )
    Aw = cyclic_window(A, a_view, d, t) if a_view is not None else A
    Bw = cyclic_window(B, b_view, d, t) if b_view is not None else B
    T = masking.take_triangle_cyclic(Aw, args.uplo, d, t)
    Top = T.T if args.trans_a else T
    eff_uplo = (
        args.uplo if not args.trans_a else ("L" if args.uplo == "U" else "U")
    )
    # residual data motion: the windows/mask/transpose still materialize,
    # but WINDOW-sized and shuffle-free — price it so the ledger separates
    # this residue from the full-buffer copies the layout removed
    cb = _copy_bytes_of((2.0, Aw))  # triangle mask
    if a_view is not None:
        cb += _copy_bytes_of((2.0, Aw))
    if args.trans_a:
        cb += _copy_bytes_of((2.0, Aw))
    if b_view is not None:
        cb += _copy_bytes_of((2.0, Bw))
    if args.side == "L":
        sched = _sched_pairs_cyclic(
            grid, Top.shape[0], Top.shape[1], Bw.shape[1], eff_uplo, None, t
        )
        if sched is None:
            tracing.note("trmm::persistent_dense")
            res = _matmul(grid, Top, Bw, mode, args.precision)
        else:
            tracing.note("trmm::persistent_cyclic")
            res = _matmul(
                grid, Top, Bw, mode, args.precision, a_uplo=eff_uplo,
                sched_override=sched,
            )
    elif args.side == "R":
        sched = _sched_pairs_cyclic(
            grid, Bw.shape[0], Bw.shape[1], Top.shape[1], None, eff_uplo, t
        )
        if sched is None:
            tracing.note("trmm::persistent_dense")
            res = _matmul(grid, Bw, Top, mode, args.precision)
        else:
            tracing.note("trmm::persistent_cyclic")
            res = _matmul(
                grid, Bw, Top, mode, args.precision, b_uplo=eff_uplo,
                sched_override=sched,
            )
    else:
        raise ValueError(f"side must be 'L' or 'R', got {args.side!r}")
    if args.alpha != 1.0:
        res = args.alpha * res
    if out is not None:
        # band-sized read-modify-write, not the whole-buffer dus round-trip
        cb += _copy_bytes_of((4.0, res))
        tracing.emit(copy_bytes=cb / grid.num_devices)
        view = (out_off[0], out_off[1], res.shape[0], res.shape[1])
        return grid.pin(cyclic_window_update(out, res, view, d, t))
    tracing.emit(copy_bytes=cb / grid.num_devices)
    return grid.pin(res)


def _syrk_persistent(grid, A, C, args, mode, a_view, c_view, in_place,
                     cyclic_tile):
    """syrk under the persistent layout: the cyclic_out schedule of
    _explicit_matmul IS window-local cyclic liveness (original tile pair
    (ti*d+xi, tj*d+yi)), so the balanced contraction runs unchanged — what
    disappears are the three per-call shuffles balance='tile_cyclic' pays
    (A's free axis in, both output axes out): operands arrive and the
    update leaves in layout.  Symmetrization is cyclic-aware — the live
    triangle sits at ORIGINAL indices (masking.take_triangle_cyclic), and
    transposing a both-axes-same-perm matrix stays in layout."""
    d, t = _persistent_params(grid, mode, cyclic_tile, "syrk")
    Aw = cyclic_window(A, a_view, d, t) if a_view is not None else A
    cb = _copy_bytes_of((2.0, Aw))  # the .T below
    if a_view is not None:
        cb += _copy_bytes_of((2.0, Aw))
    Aop = (Aw.T, Aw) if args.trans else (Aw, Aw.T)
    D = _matmul(
        grid, Aop[0], Aop[1], mode, args.precision, out_uplo=args.uplo,
        cyclic_out=t,
    )
    tracing.note("syrk::persistent_cyclic")
    live = masking.take_triangle_cyclic(D, args.uplo, d, t)
    strict = masking.take_triangle_cyclic(D, args.uplo, d, t, strict=True)
    out = live + transpose(grid, strict)
    cb += _copy_bytes_of((4.0, D))  # the two mask materializations
    if args.alpha != 1.0:
        out = args.alpha * out
    if args.beta != 0.0:
        Cw = cyclic_window(C, c_view, d, t) if c_view is not None else C
        out = out + args.beta * grid.pin(Cw)
        if c_view is not None:
            cb += _copy_bytes_of((2.0, Cw))
    if in_place:
        r0, c0 = (c_view[0], c_view[1]) if c_view is not None else (0, 0)
        cb += _copy_bytes_of((4.0, out))
        tracing.emit(copy_bytes=cb / grid.num_devices)
        view = (r0, c0, out.shape[0], out.shape[1])
        return grid.pin(cyclic_window_update(C, out, view, d, t))
    tracing.emit(copy_bytes=cb / grid.num_devices)
    return grid.pin(out)


@pallas_tpu.scoped_by_grid
def trmm(
    grid: Grid,
    A: jnp.ndarray,
    B: jnp.ndarray,
    args: TrmmArgs = TrmmArgs(),
    mode: str = "xla",
    *,
    a_view: tuple[int, int, int, int] | None = None,
    b_view: tuple[int, int, int, int] | None = None,
    out: jnp.ndarray | None = None,
    out_off: tuple[int, int] = (0, 0),
    balance: str = "block",
    cyclic_tile: int = 0,
) -> jnp.ndarray:
    """B <- alpha * op(tri(A)) @ B   (side L)   or   alpha * B @ op(tri(A))
    (side R) — reference summa.hpp:47-83.

    balance='tile_cyclic' (explicit mode, side L, c==1 square faces):
    rows are redistributed block-cyclically over MXU-sized tiles
    (tile_cyclic_perm) so every device executes an equal share of the
    triangle — the reference's element-cyclic load balancing
    (structure.hpp:80-85) at tile granularity, which keeps dead tiles
    whole and skippable.  The critical-path device drops from the full
    dense contraction to the volumetric mean (tri_fractions; max = mean).
    The standalone call pays two row-shuffles (permute the triangular
    operand in, un-permute the product out — priced into the cost model);
    an algorithm adopting the cyclic layout persistently pays them once.
    cyclic_tile overrides the auto-picked tile (local rows / 4).
    Unsupported combinations fall back to the block schedule with a
    tracing note.

    balance='tile_cyclic_persistent' (explicit mode, both sides): the
    caller asserts EVERY passed buffer — operands, `out`, and the views
    into them — is already stored in the symmetric tile-cyclic layout
    V = X[perm][:, perm] with tile `cyclic_tile` (models/cholesky.py
    permutes once per matrix lifetime).  Window reads become chunk-local
    reshapes (cyclic_window), liveness is scheduled per original tile, and
    the product emerges in layout: the two per-call shuffles of
    'tile_cyclic' and the whole-buffer dus round-trip disappear.  This is
    a storage contract, not a preference — unsupported topologies raise
    instead of falling back (a block-ordered read of a cyclic buffer would
    be garbage).

    The triangular operand is dense + masked; the mask fuses into the matmul
    (no packed storage — SURVEY §7.1).  mode='pallas' on a single-device
    grid skips the dead blocks on the MXU instead (ops/pallas_tpu.py).

    a_view/b_view select static windows of the passed buffers as the
    operands, and out/out_off writes the result into a window of `out`
    (returning the whole updated buffer).  On the single-device pallas path
    these compile to offset index maps / an in-place aliased write (no slice
    or scatter materialization, ops/pallas_tpu.py); every other path
    materializes the windows and a dynamic_update_slice — identical
    semantics, so callers can be written once against views (the recursion
    in models/cholesky.py is)."""
    a_dims = (a_view[2], a_view[3]) if a_view is not None else A.shape
    b_dims = (b_view[2], b_view[3]) if b_view is not None else B.shape
    if (
        mode in ("pallas", "explicit")
        and grid.num_devices == 1
        and args.diag != "U"
        and balance != "tile_cyclic_persistent"
    ):
        if balance == "tile_cyclic":
            # single-device kernels skip dead tiles directly; the balanced
            # schedule does not apply — honor the fallback-with-a-note
            # contract instead of silently dropping the request
            tracing.note("trmm::tile_cyclic_fallback")
        flops, comm, ncoll = tracing.gemm_cost(
            grid, b_dims[0], b_dims[1], a_dims[0], jnp.result_type(A, B)
        )
        if mode == "explicit":
            # copy-free d==1 route (the single-chip constant of the explicit
            # path, DISTRIBUTED.md): at one device every liveness predicate
            # is static, so the schedule the K-segment path would run is
            # exactly what the aliasing pallas kernels already execute —
            # minus the take_triangle copy, the window materializations and
            # the whole-buffer dus round-trip below.  Ride the kernels.
            # Cost convention follows explicit::shard_kernels: homogeneous
            # model count stays dense, executed views carry the /2.
            tracing.note("explicit::copy_free")
            tracing.emit(
                flops=flops, comm_bytes=comm, collectives=ncoll,
                flops_vol=flops / 2, flops_max=flops / 2,
            )
        else:
            tracing.emit(flops=flops / 2, comm_bytes=comm, collectives=ncoll)
        if args.side == "L":
            return pallas_tpu.tri_matmul(
                A, B, a_uplo=args.uplo, a_trans=args.trans_a,
                alpha=args.alpha, precision=args.precision,
                a_view=a_view, b_view=b_view, out=out, out_off=out_off,
            )
        elif args.side == "R":
            return pallas_tpu.tri_matmul(
                B, A, b_uplo=args.uplo, b_trans=args.trans_a,
                alpha=args.alpha, precision=args.precision,
                a_view=b_view, b_view=a_view, out=out, out_off=out_off,
            )
        raise ValueError(f"side must be 'L' or 'R', got {args.side!r}")
    if balance == "tile_cyclic_persistent":
        return _trmm_persistent(
            grid, A, B, args, mode, a_view, b_view, out, out_off, cyclic_tile
        )
    Aw = _take_view(A, a_view)
    Bw = _take_view(B, b_view)
    T = masking.take_triangle(Aw, args.uplo)
    if args.diag == "U":
        T = masking.with_unit_diagonal(T)
    Top = T.T if args.trans_a else T
    # structure of the operand *as passed* to the schedule: transposing a
    # triangular matrix flips its triangle — explicit mode uses this to skip
    # dead K-segments per device (summa.hpp:47-161's trmm saving)
    eff_uplo = (
        args.uplo if not args.trans_a else ("L" if args.uplo == "U" else "U")
    )
    res = None
    if balance == "tile_cyclic":
        M = Top.shape[0] if args.side == "L" else 0
        tile = (
            _pick_cyclic_tile(grid, M, cyclic_tile)
            if (mode == "explicit" and args.side == "L")
            else 0
        )
        if tile:
            perm, inv = tile_cyclic_perm(M, grid.dx, tile)
            # two row-shuffles priced like grid transposes (block
            # exchanges across the face): the M x M triangular operand in,
            # the M x N product out
            comm_a, nc_a = tracing.transpose_cost(grid, M, M, Top.dtype)
            comm_o, nc_o = tracing.transpose_cost(grid, M, Bw.shape[1], Top.dtype)
            tracing.emit(comm_bytes=comm_a + comm_o, collectives=nc_a + nc_o)
            res = _matmul(
                grid, grid.pin(Top[jnp.asarray(perm)]), Bw, mode,
                args.precision, a_uplo=eff_uplo, cyclic_rows=tile,
            )
            res = grid.pin(res[jnp.asarray(inv)])
        else:
            tracing.note("trmm::tile_cyclic_fallback")
    if res is None:
        if args.side == "L":
            res = _matmul(grid, Top, Bw, mode, args.precision, a_uplo=eff_uplo)
        elif args.side == "R":
            res = _matmul(grid, Bw, Top, mode, args.precision, b_uplo=eff_uplo)
        else:
            raise ValueError(f"side must be 'L' or 'R', got {args.side!r}")
    if args.alpha != 1.0:
        res = args.alpha * res
    # copy-bytes attribution of this materializing path (the term the
    # copy-free d==1 route and the persistent layout shrink): triangle mask,
    # window slices, transpose, and the write-back round-trip — each priced
    # as read + write of the moved array, per device
    cb = _copy_bytes_of((2.0, T))  # take_triangle
    if a_view is not None:
        cb += _copy_bytes_of((2.0, T))
    if args.diag == "U":
        cb += _copy_bytes_of((2.0, T))
    if args.trans_a:
        cb += _copy_bytes_of((2.0, T))
    if b_view is not None:
        cb += _copy_bytes_of((2.0, Bw))
    if out is not None:
        cb += _copy_bytes_of((2.0, out))  # whole-buffer dus round-trip
        tracing.emit(copy_bytes=cb / grid.num_devices)
        return grid.pin(
            lax.dynamic_update_slice(out, res.astype(out.dtype), _i32_off(out_off))
        )
    tracing.emit(copy_bytes=cb / grid.num_devices)
    return grid.pin(res)


@pallas_tpu.scoped_by_grid
def syrk(
    grid: Grid,
    A: jnp.ndarray,
    C: jnp.ndarray | None = None,
    args: SyrkArgs = SyrkArgs(),
    mode: str = "xla",
    *,
    a_view: tuple[int, int, int, int] | None = None,
    c_view: tuple[int, int, int, int] | None = None,
    in_place: bool = False,
    balance: str = "block",
    cyclic_tile: int = 0,
) -> jnp.ndarray:
    """Symmetric rank-k update (reference summa.hpp:86-161, which lowers syrk
    to an explicit grid transpose + gemm; here the transpose is a logical
    .T — XLA emits the collective-permute when resharding is needed).

    trans=False: C = alpha*A@Aᵀ + beta*C;  trans=True: C = alpha*Aᵀ@A + beta*C.
    In 'xla' mode (and 'explicit' on a mesh) the full dense symmetric
    result is computed (MXU-friendly); callers that need only a triangle
    mask the output.  mode='pallas' — and 'explicit' on a SINGLE-device
    grid, which rides the same copy-free kernels — instead honors
    args.uplo: only that triangle of the result is valid — with beta=0 the
    dead half is zeroed, with beta!=0 it is UNDEFINED (the fused in-kernel
    beta*C accumulate never visits dead tiles) — so callers must read only
    the args.uplo triangle (models/cholesky.py symmetrizes its base-case
    panel from 'U').

    balance='tile_cyclic_persistent': storage contract as in trmm — all
    buffers are in the symmetric tile-cyclic layout; the balanced
    cyclic_out contraction runs without the three per-call shuffles of
    'tile_cyclic', the symmetrize is cyclic-aware, and in_place writes
    back through cyclic_window_update (band-sized, not buffer-sized).

    in_place (requires beta != 0 and a c_view): the update is written back
    INTO the C buffer at the c_view window and the whole updated buffer is
    returned — the caller must treat the passed-in C value as consumed.
    On the pallas path this is a tile-local read-modify-write through
    ``input_output_aliases`` (no fresh result allocation: cholinv's Schur
    chain of Σ(n/2ᵏ)² intermediate buffers disappears, which is what lets
    the n=49152 flagship fit one v5e HBM — see docs/PERF.md); other modes
    materialize the window result and dynamic_update_slice it back, same
    semantics.  The dead (non-args.uplo) half of the window keeps the
    buffer's previous contents on the aligned pallas path.
    """
    if args.beta != 0.0 and C is None:
        raise ValueError("beta != 0 requires the accumulate operand C")
    if in_place and (args.beta == 0.0 or C is None):
        raise ValueError("in_place syrk requires the accumulate operand C")
    if (
        mode in ("pallas", "explicit")
        and grid.num_devices == 1
        and balance != "tile_cyclic_persistent"
    ):
        if balance == "tile_cyclic":
            # same contract as trmm's pallas branch: the kernel skips dead
            # tiles itself, so the cyclic schedule is a no-op here — note it
            tracing.note("syrk::tile_cyclic_fallback")
        # mode='pallas' honors args.uplo: only that triangle of the product
        # is computed; skipping the symmetric redundancy is where the ~1.65x
        # comes from.  beta*C accumulates INSIDE the kernel at flush time
        # (one C-tile read per live output tile instead of a full-matrix
        # slice + add downstream), which leaves the dead half UNDEFINED when
        # beta != 0 — callers must read only the args.uplo triangle
        # (models/cholesky.py symmetrizes its base-case panel from 'U').
        a_dims = (a_view[2], a_view[3]) if a_view is not None else A.shape
        n_out = a_dims[1] if args.trans else a_dims[0]
        k_in = a_dims[0] if args.trans else a_dims[1]
        flops, comm, ncoll = tracing.gemm_cost(
            grid, n_out, n_out, k_in, jnp.result_type(A)
        )
        if mode == "explicit":
            # copy-free d==1 route, same reasoning as trmm's: at one device
            # the explicit schedule's liveness is static and the aliasing
            # kernels execute it without the materialization chain below.
            # NOTE the contract narrows to the pallas one — only the
            # args.uplo triangle of the result is valid (beta=0 zeroes the
            # dead half, beta!=0 leaves it undefined); the in-repo explicit
            # consumers (models/cholesky.py, the CQR gram) already read
            # only that triangle, exactly as they do under mode='pallas'.
            tracing.note("explicit::copy_free")
            tracing.emit(
                flops=flops, comm_bytes=comm, collectives=ncoll,
                flops_vol=flops / 2, flops_max=flops / 2,
            )
        else:
            tracing.emit(flops=flops / 2, comm_bytes=comm, collectives=ncoll)
        out_kw = {}
        if in_place:
            out_kw = dict(
                out=C,
                out_off=(c_view[0], c_view[1]) if c_view is not None else (0, 0),
            )
        return pallas_tpu.tri_matmul(
            A, A,
            a_trans=args.trans, b_trans=not args.trans,
            out_uplo=args.uplo, alpha=args.alpha, precision=args.precision,
            a_view=a_view, b_view=a_view,
            c=C, c_view=c_view, beta=args.beta,
            **out_kw,
        )
    if balance == "tile_cyclic_persistent":
        return _syrk_persistent(
            grid, A, C, args, mode, a_view, c_view, in_place, cyclic_tile
        )
    Aw = _take_view(A, a_view)
    if balance == "tile_cyclic" and mode != "explicit":
        # xla/pallas modes have no balanced schedule to route to — say so
        # in the recorder instead of silently dropping the request (same
        # contract as trmm's fallback note)
        tracing.note("syrk::tile_cyclic_fallback")
    if mode == "explicit":
        # compute only the args.uplo triangle's blocks (devices with a fully
        # dead C block skip all local flops), then symmetrize — one grid
        # transpose, the same data motion the reference's syrk-via-transpose
        # already pays (summa.hpp:86-161); the dense-result contract of this
        # mode is preserved.
        # balance='tile_cyclic': C's OUTPUT tile indices are block-cyclic
        # over devices (permute A's free axis in, un-permute C's rows+cols
        # out), so every device carries ~half the live tile pairs instead
        # of whole blocks being dead — the syrk analog of trmm's balanced
        # schedule (see trmm's docstring; same decision calculus).
        cyc = 0
        perm = inv = None
        if balance == "tile_cyclic":
            n_out = Aw.shape[1] if args.trans else Aw.shape[0]
            T = _pick_cyclic_tile(grid, n_out, cyclic_tile)
            if T:
                perm, inv = tile_cyclic_perm(n_out, grid.dx, T)
                pj = jnp.asarray(perm)
                Aw = Aw[:, pj] if args.trans else Aw[pj, :]
                cyc = T
                # three shuffles, each priced at its true shape: the whole
                # A operand in, then C's rows AND cols out (two n_out²
                # motions — D[inv][:, inv])
                ca, na = tracing.transpose_cost(grid, *Aw.shape, Aw.dtype)
                cc, nc = tracing.transpose_cost(grid, n_out, n_out, Aw.dtype)
                tracing.emit(comm_bytes=ca + 2 * cc, collectives=na + 2 * nc)
            else:
                tracing.note("syrk::tile_cyclic_fallback")
        Aop = (Aw.T, Aw) if args.trans else (Aw, Aw.T)
        D = _matmul(
            grid, Aop[0], Aop[1], mode, args.precision, out_uplo=args.uplo,
            cyclic_out=cyc,
        )
        if cyc:
            ij = jnp.asarray(inv)
            D = grid.pin(D[ij][:, ij])
        if args.uplo == "U":
            out = jnp.triu(D) + transpose(grid, jnp.triu(D, 1))
        else:
            out = jnp.tril(D) + transpose(grid, jnp.tril(D, -1))
    else:
        Aop = (Aw.T, Aw) if args.trans else (Aw, Aw.T)
        out = _matmul(grid, Aop[0], Aop[1], mode, args.precision)
    if args.alpha != 1.0:
        out = args.alpha * out
    # copy-bytes attribution (see trmm): the .T operand, window slices, the
    # symmetrize's two triangle masks, and the write-back round-trip
    cb = _copy_bytes_of((2.0, Aw))
    if a_view is not None:
        cb += _copy_bytes_of((2.0, Aw))
    if mode == "explicit":
        cb += _copy_bytes_of((4.0, out))
    if args.beta != 0.0:
        Cw = _take_view(C, c_view)
        out = out + args.beta * grid.pin(Cw)
        if c_view is not None:
            cb += _copy_bytes_of((2.0, Cw))
    if in_place:
        off = (c_view[0], c_view[1]) if c_view is not None else (0, 0)
        cb += _copy_bytes_of((2.0, C))  # whole-buffer dus round-trip
        tracing.emit(copy_bytes=cb / grid.num_devices)
        return grid.pin(
            lax.dynamic_update_slice(C, out.astype(C.dtype), _i32_off(off))
        )
    tracing.emit(copy_bytes=cb / grid.num_devices)
    return grid.pin(out)


def transpose(grid: Grid, A: jnp.ndarray) -> jnp.ndarray:
    """Grid transpose: Aᵀ re-pinned to the face layout.

    Reference util::transpose swaps blocks with the mirrored grid rank via
    MPI_Sendrecv_replace (util.hpp:232-247); on TPU the same data motion is
    XLA's collective-permute, emitted from the layout constraint."""
    comm, ncoll = tracing.transpose_cost(grid, A.shape[0], A.shape[1], A.dtype)
    tracing.emit(comm_bytes=comm, collectives=ncoll)
    return grid.pin(A.T)
