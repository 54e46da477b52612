"""cacqr: communication-avoiding CholeskyQR2 for tall-skinny QR.

TPU-native re-design of qr::cacqr (reference src/alg/qr/cacqr/), the
CA-CQR2 algorithm (IPDPS'19, arXiv:1710.08471): for tall-skinny A (M x N,
M >> N), one *sweep* is

    G = AᵀA          (gram — the only global reduction)
    R = chol(G)      (small N x N factorization)
    Q = A · R⁻¹      (tall-skinny scaling)

CQR2 runs two sweeps and merges R = R2·R1, recovering orthogonality to
machine precision (cacqr.hpp:181-210).

The reference dispatches on grid shape (cacqr.hpp:229-245):
  c == 1  'invoke_1d'  : local syrk + MPI_Allreduce(world) + local LAPACK
  c == d  'invoke_3d'  : gram via bcast/reduce pipeline + cholinv on the gram
                          on the cube's square sub-grid + SUMMA trmm
  1<c<d   'sweep_tune' : same with the column reduction split over
                          column_contig/column_alt sub-communicators

On a TPU mesh the three regimes collapse to one question — *where does the
N x N gram live?* — so this module exposes two paths and an auto rule:

  regime='1d'   : A is sharded along its long axis over every device
                  (Grid.rows_sharding); the gram psum is the single
                  collective; chol+inverse run replicated on every chip.
                  This is the reference's 1D path and the right choice
                  whenever N is small enough that the N x N gram fits
                  replicated (the common tall-skinny case).
  regime='dist' : A is face-sharded; the gram forms via distributed syrk and
                  **cholinv.factor runs on the gram** exactly like the
                  reference wires its 3D path into cholinv (cacqr.hpp:103);
                  Q = A·R⁻¹ via SUMMA trmm, or the blocked triangular solve
                  when complete_inv=False (cacqr.hpp:46-73).
  regime='auto' : '1d' when the grid is flat or N <= dist_threshold,
                  else 'dist'.

The reference's tunable grid shape (topo::rect c,d sweep) maps to how the
caller constructs the Grid (Grid.rect(dx, dy, c)) — mesh shape is the
runtime knob that replaces communicator re-splitting (SURVEY §2.5).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from capital_tpu.models import cholesky
from capital_tpu.models.cholesky import CholinvConfig
from capital_tpu.obs import spans
from capital_tpu.ops import lapack, pallas_tpu
from capital_tpu.parallel import summa
from capital_tpu.parallel.summa import GemmArgs, SyrkArgs, TrmmArgs
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust import faultinject, recovery
from capital_tpu.robust import config as config_mod
from capital_tpu.robust.config import RobustConfig, RobustInfo
from capital_tpu.utils import tracing


@dataclasses.dataclass(frozen=True)
class CacqrConfig:
    """Mirror of qr::cacqr::info (reference cacqr.h:17-45).

    num_iter: 1 = CholeskyQR, 2 = CholeskyQR2 (the reference's `variant`
        driver knob, bench/qr/cacqr.cpp:14).
    regime: '1d' | 'dist' | 'auto' (see module docstring).
    dist_threshold: in 'auto', gram sizes above this go distributed.
    cholinv: configuration for the nested Cholesky when regime='dist'
        (the reference nests its cholinv pack the same way, cacqr.cpp:38-40).
        cholinv.complete_inv=False switches Q formation to the blocked
        triangular solve (reference cacqr.hpp:46-73).
    """

    num_iter: int = 2
    regime: str = "auto"
    dist_threshold: int = 4096
    cholinv: CholinvConfig = CholinvConfig()
    mode: str = "xla"
    precision: str | None = "highest"  # gram/scaling matmul precision: the
    # gram AᵀA is the numerically critical contraction of CholeskyQR — at
    # the TPU default (bf16 passes) orthogonality degrades ~200x for f32
    # inputs; 'highest' keeps it f32-grade
    fused_g: int = 0  # in-kernel column split of the fused tall-pass
    # kernels: executed flops are (g+1)/2g of dense at zero extra HBM
    # traffic (all sub-products VMEM-resident).  0 = auto
    # (qr_fused.pick_g: largest eligible in {8,4,2})
    robust: RobustConfig | None = None  # breakdown detection + shifted-
    # CholeskyQR recovery (docs/ROBUSTNESS.md): factor() returns
    # (Q, R, RobustInfo) instead of (Q, R), every Cholesky site is guarded,
    # and a detected breakdown re-factors the shifted gram + escalates to a
    # third sweep (sCQR3) when the orthogonality gate still fails.  On a
    # multi-device grid the guarded sweeps run unfused (traced status
    # values cannot escape the fused pipeline's shard_map body).


# --------------------------------------------------------------------------
# robust session: collects per-site CholEvents while factor() traces
# --------------------------------------------------------------------------


class _Session:
    """One robust factor() invocation: the active RobustConfig plus the
    CholEvents its guarded sites record (trace-order, so the aggregate in
    _finish_robust is deterministic)."""

    def __init__(self, rcfg: RobustConfig):
        self.rcfg = rcfg
        self.events: list = []


_ROBUST: list[_Session] = []


def _chol_site(G: jnp.ndarray, m_rows: int, chol_fn):
    """Factor a gram at one Cholesky site.  Outside a robust session this
    is chol_fn(G) verbatim — zero overhead on the default path.  Inside
    one, the site is wrapped in recovery.guarded_chol (detection + shifted
    retry) and its CholEvent lands on the session."""
    if not _ROBUST:
        return chol_fn(G)
    ses = _ROBUST[-1]
    R, Rinv, ev = recovery.guarded_chol(G, m_rows, ses.rcfg, chol_fn)
    ses.events.append(ev)
    return R, Rinv


# --------------------------------------------------------------------------
# sweeps
# --------------------------------------------------------------------------


def _col_blocks(n: int) -> int:
    """Column-block count for the triangular-blocked gram/scaling.  Fixed at
    2 (or 1 = unblocked for small/unaligned n): these tall-skinny products
    sit near the HBM roofline, and each extra split re-reads more of A —
    measured on v5e at 1M x 1024 bf16, g=4 with per-block products cost 5x
    the A traffic plus XLA relayout copies and ran 1.5x SLOWER than dense
    (86 vs 57 ms/iter device time); g=2 over contiguous slabs is the only
    split whose flop saving (25%) exceeds its traffic increase."""
    if n % 2 == 0 and (n // 2) % 128 == 0 and n // 2 >= 256:
        return 2
    return 1


def _sweep_1d(
    grid: Grid, A: jnp.ndarray, cfg: CacqrConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One CQR sweep, 1D regime (reference sweep_1d, cacqr.hpp:7-29).

    A arrives sharded along rows over the whole mesh; gram contractions are
    written globally and pinned replicated — XLA emits the local partial
    product and the all-axis psum, the exact analog of the reference's
    local syrk + MPI_Allreduce over world (cacqr.hpp:14-25).

    The triangular flop savings of the reference's local cblas_dsyrk /
    cblas_dtrmm (cacqr.hpp:14,25), measured into this shape on v5e at
    1M x 1024 bf16 (the BASELINE-adjacent row):

      * gram — **XLA-level row blocking**: G[i, i*nb:] = A_iᵀ · A[:, i*nb:]
        computes only the upper block-rows off one contiguous trailing slab
        per row (lower blocks are transposes, n x n elementwise);
        (g+1)/2g of dense flops at minimum extra A-traffic.
      * scaling — Q = A·R⁻¹ through the live-tile trmm kernel with column
        blocks sized to the triangle (bn = bk = n/g): 3/4 executed flops at
        g=2, output written once, row-major, no assembly.

    Rejected alternatives, with v5e measurements: per-256-block XLA
    products (5x A traffic + whole-Q relayout copies: 86 ms/iter vs dense
    57), column-slab threading between sweeps (XLA assigns the slabs mixed
    layouts and re-layouts the assembled Q: ~13 ms/iter of copies), and
    default-block pallas routing (an n=1024 triangle is a single tile at
    deep-K defaults — no skipping happens, 76 ≈ 78 TF/s dense).
    """
    m, n = A.shape
    precision = cfg.precision
    g = _col_blocks(n)
    nb = n // g
    A = lax.with_sharding_constraint(A, grid.rows_sharding())
    live_frac = (g + 1) / (2.0 * g) if g > 1 else 1.0
    # phase tags follow the reference symbols CQR::gram / CQR::formR
    # (cacqr.hpp:82-116)
    with tracing.scope("CQR::gram"):
        comm, ncoll = tracing.allreduce_cost(grid, n, n, A.dtype, axes="all")
        tracing.emit(
            flops=2.0 * m * n * n / grid.num_devices * live_frac,
            # blocked gram: one psum per block-row product of live_frac of
            # the n x n bytes in total (g collectives, not one)
            comm_bytes=comm * live_frac,
            collectives=ncoll * (g if g > 1 else 1),
        )
        if g > 1:
            # each block-row partial is pinned replicated BEFORE the
            # transpose/concat assembly: the cost model above prices g
            # reductions of live_frac·n² bytes total, and without the
            # constraint GSPMD is free to sink the psum past the assembly
            # and move the dense n² in one collective (ADVICE r2) — the
            # constraint makes the modeled schedule the emitted one
            # (pinned by TestGramEmission1d)
            grows = [
                lax.with_sharding_constraint(
                    jnp.matmul(
                        A[:, i * nb : (i + 1) * nb].T,
                        A[:, i * nb :],
                        precision=precision,
                    ),
                    grid.replicated_sharding(),
                )
                for i in range(g)
            ]
            G = jnp.concatenate(
                [
                    jnp.concatenate(
                        [
                            grows[j][:, (i - j) * nb : (i - j + 1) * nb].T
                            for j in range(i)
                        ]
                        + [grows[i]],
                        axis=1,
                    )
                    for i in range(g)
                ],
                axis=0,
            )
        else:
            G = jnp.matmul(A.T, A, precision=precision)
        G = lax.with_sharding_constraint(G, grid.replicated_sharding())
        G = faultinject.tap(G)
    with tracing.scope("CQR::chol"):
        tracing.emit(flops=tracing.potrf_trtri_flops(n))
        R, Rinv = _chol_site(G, m, lambda g_: lapack.potrf_trtri(g_, uplo="U"))
    with tracing.scope("CQR::formR"):
        # the live-tile kernel is an explicit mode choice (the bench driver's
        # 'auto' resolves to pallas on one TPU); other modes take the dense
        # matmul — on CPU the interpreter would be orders of magnitude slower
        # nb <= 2048 is the live-tile kernel's VMEM envelope at these
        # blocks ((bm, nb, nb) + f32 acc): nb=4096 blows Mosaic's scoped
        # limit ("112.00M of 100.00M", n=8192) — wider shapes take the
        # dense matmul (the CQR2 path covers them with the panel tier)
        tri_kernel = (
            g > 1
            and grid.num_devices == 1
            and cfg.mode == "pallas"
            and n // g <= 2048
        )
        # live_frac applies only where the tri kernel actually skips dead
        # blocks; the multi-device path executes the dense matmul
        tracing.emit(
            flops=2.0 * m * n * n / grid.num_devices
            * (live_frac if tri_kernel else 1.0)
        )
        if tri_kernel:
            # live-tile trmm with triangle-sized column blocks (bn = bk =
            # n/g); bm capped at the kernel's large-tile budget.  Measured
            # at 1M x 1024 bf16 on v5e (device-trace kernel totals/sweep):
            # 512 blocks 10.7 ms (3/4 executed at 154 TF/s), 256 blocks
            # 13.9 ms (5/8 executed but per-tile efficiency collapses) —
            # finer blocks lose more to tile overhead than they save in
            # dead flops
            bm = min(1024, pallas_tpu._round_up(m, 128))
            Q = pallas_tpu.tri_matmul(
                A, Rinv, b_uplo="U", blocks=(bm, nb, nb), precision=precision
            )
        else:
            Q = jnp.matmul(A, jnp.triu(Rinv), precision=precision)
        Q = lax.with_sharding_constraint(Q, grid.rows_sharding())
    return Q, R


def _gram_chol(grid: Grid, G: jnp.ndarray, cfg: CacqrConfig, m_rows: int):
    """(R, R⁻¹) of the UPPER-VALID gram, shared by every fused/panel tier.

    Wide grams route through the recursive cholinv: the whole-matrix lax
    chol+solve serializes its panel sweep (measured 10.7 ms at n=4096 ≈
    17 TF/s); the framework's own factor does the same job in ~3.9 ms.
    cholinv reads ONLY the upper triangle (its potrf_trtri_upper
    base-case contract, verified bit-identical under a garbage lower
    half), so the gram kernels' upper-block-row output feeds it with NO
    symmetric-assembly pass; below the crossover the upper-valid factor
    pair does the same.  The caller's nested cholinv config carries the
    --bc knob; complete_inv is FORCED True — these tiers multiply by the
    full triangular inverse (the partial-inverse contract is the dist
    regime's blocked solve, solve_blocked)."""
    n = G.shape[0]
    if n >= 2048 and grid.num_devices == 1:
        # robust=None on the NESTED config: the session's guarded_chol
        # owns detection here — a 3-tuple from cholinv would break the
        # (R, Rinv) contract every tier builds on
        ccfg = dataclasses.replace(
            cfg.cholinv, mode=cfg.mode, precision=cfg.precision,
            complete_inv=True, robust=None,
        )
        return _chol_site(G, m_rows, lambda g_: cholesky.factor(grid, g_, ccfg))
    return _chol_site(G, m_rows, lapack.potrf_trtri_upper)


def _cqr2_fused(
    grid: Grid, A: jnp.ndarray, cfg: CacqrConfig, g: int, plan: str = "full"
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CQR2 through the fused tall-pass kernels (ops/qr_fused.py): sweep 1's
    gram in one A read, sweep 1's scale and sweep 2's gram in one shared
    pass (Q1 is written once and its gram taken from registers — the
    re-read the unfused pipeline pays is gone), then the standard blocked
    scale and triangular merge.  `g` is the in-kernel column split
    (executed flops (g+1)/2g of dense at zero extra HBM — VERDICT r3 #1).
    Numerically the same pipeline as two _sweep_1d calls (grams from the
    rounded Q, f32 accumulation) up to reduction association order.
    `plan` picks the tier (qr_fused.fused_plan): 'full' shares sweep 1's
    scale and sweep 2's gram in one scale_gram pass; 'split' (wide n) runs
    them as two kernels to stay inside the per-kernel VMEM envelopes."""
    from capital_tpu.ops import qr_fused

    m, n = A.shape
    precision = cfg.precision
    live = qr_fused.live_fraction(g)

    def _chol(G):
        return _gram_chol(grid, G, cfg, m)

    def _gram_out(Gu):
        # both chol routes read only the valid upper triangle — the
        # symmetric assembly pass (n² of block transposes + re-layout,
        # ~3 ms/iter inside the gram scopes at n=4096) is never needed
        return faultinject.tap(Gu.astype(A.dtype))

    with tracing.scope("CQR::gram"):
        tracing.emit(flops=2.0 * m * n * n * live)
        G1 = _gram_out(qr_fused.gram_blocked(A, g=g, precision=precision))
    with tracing.scope("CQR::chol"):
        tracing.emit(flops=tracing.potrf_trtri_flops(n))
        R1, R1inv = _chol(G1)
    with tracing.scope("CQR::fused"):
        # scale1 (live) + gram2 (live): one shared read of A on the 'full'
        # tier; the wide-n 'split' tier runs them as two kernels (sweep 2's
        # gram re-reads the written Q1 — one extra HBM pass, every in-kernel
        # flop saving kept; see qr_fused.fused_plan)
        tracing.emit(flops=2.0 * m * n * n * (live + live))
        if plan == "split":
            Q1 = qr_fused.scale_blocked(
                A, jnp.triu(R1inv), g=g, precision=precision
            )
            G2 = qr_fused.gram_blocked(Q1, g=g, precision=precision)
        else:
            Q1, G2 = qr_fused.scale_gram(
                A, jnp.triu(R1inv), g=g, precision=precision
            )
        G2 = _gram_out(G2)
    with tracing.scope("CQR::chol"):
        tracing.emit(flops=tracing.potrf_trtri_flops(n))
        R2, R2inv = _chol(G2)
    with tracing.scope("CQR::formR"):
        tracing.emit(flops=2.0 * m * n * n * live)
        Q = qr_fused.scale_blocked(Q1, jnp.triu(R2inv), g=g, precision=precision)
    with tracing.scope("CQR::merge"):
        tracing.emit(flops=2.0 * n**3)
        R = jnp.matmul(jnp.triu(R2), jnp.triu(R1), precision=precision)
    return Q, R


def _cqr2_panels(
    grid: Grid, A: jnp.ndarray, cfg: CacqrConfig, c: int = 512
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CQR2 for very wide n — past EVERY fused kernel's VMEM envelope
    (qr_fused.fused_plan tier 'panels').  Pure-XLA panel pipeline with the
    same triangular flop structure the kernels exploit:

      gram:  column-panel j needs only rows [0, (j+1)c) — the product
             X[:, :(j+1)c]ᵀ · X[:, jc:(j+1)c] IS the valid upper part,
             zero-padded below (cholinv's upper-only read contract).
      scale: Q[:, jc:(j+1)c] = X[:, :(j+1)c] · R⁻¹[: (j+1)c, panel]
             (upper-triangular R⁻¹: the zero lower blocks never load).

    Executed flops are (g+1)/2g of dense, like the kernels.  The extra
    operand reads (panel j re-reads X's leading columns) that made the
    XLA-level split a measured LOSER at n=1024 (docs/PERF.md round-2) are
    noise here: arithmetic intensity ~n/(g+1) ≈ 512 flops/byte at n=8192,
    far above the v5e compute/bandwidth ratio (~240) — the pipeline is
    MXU-bound, XLA pipelines the HBM traffic under it.  The n×n gram
    factor rides the recursive cholinv (n ≥ 2048 always holds here)."""
    m, n = A.shape
    g = n // c
    precision = cfg.precision
    live = (g + 1) / (2.0 * g)

    def _chol(G):
        return _gram_chol(grid, G, cfg, m)

    def gram(X):
        cols = []
        for j in range(g):
            P = jnp.matmul(
                X[:, : (j + 1) * c].T, X[:, j * c : (j + 1) * c],
                precision=precision,
            )
            cols.append(jnp.pad(P, ((0, n - (j + 1) * c), (0, 0))))
        return faultinject.tap(jnp.concatenate(cols, axis=1).astype(A.dtype))

    def scale(X, Rinv):
        Rt = jnp.triu(Rinv)
        return jnp.concatenate(
            [
                jnp.matmul(
                    X[:, : (j + 1) * c],
                    Rt[: (j + 1) * c, j * c : (j + 1) * c],
                    precision=precision,
                )
                for j in range(g)
            ],
            axis=1,
        ).astype(A.dtype)

    with tracing.scope("CQR::gram"):
        tracing.emit(flops=2.0 * m * n * n * live)
        G1 = gram(A)
    with tracing.scope("CQR::chol"):
        tracing.emit(flops=tracing.potrf_trtri_flops(n))
        R1, R1inv = _chol(G1)
    with tracing.scope("CQR::fused"):
        tracing.emit(flops=2.0 * m * n * n * (live + live))
        Q1 = scale(A, R1inv)
        G2 = gram(Q1)
    with tracing.scope("CQR::chol"):
        tracing.emit(flops=tracing.potrf_trtri_flops(n))
        R2, R2inv = _chol(G2)
    with tracing.scope("CQR::formR"):
        tracing.emit(flops=2.0 * m * n * n * live)
        Q = scale(Q1, R2inv)
    with tracing.scope("CQR::merge"):
        tracing.emit(flops=2.0 * n**3)
        R = jnp.matmul(jnp.triu(R2), jnp.triu(R1), precision=precision)
    return Q, R


def _cqr2_fused_sharded(
    grid: Grid, A: jnp.ndarray, cfg: CacqrConfig, g: int, plan: str = "full"
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The fused CQR2 pipeline on a mesh: the SAME Mosaic kernels, run PER
    SHARD inside one shard_map over the row-sharded operand (VERDICT r4 #2
    — the reference gets its local-BLAS flop saving on every rank,
    blas/interface.hpp:74-97; here every chip runs the fused tall-pass
    kernels on its own m/p rows).  Mosaic custom calls cannot be GSPMD-
    partitioned (the round-4 AOT finding), but inside shard_map the
    partitioning is manual — each shard's kernel call is a single-device
    program, so the same `vma`-annotated kernels compile for the 8-chip
    topology (witnessed by bench.aot65536 --alg cacqr).

    Per shard:  G1 += psum(gram(A_loc));  chol+inv replicated;
    (Q1_loc, G2_part) = scale_gram(A_loc, R1inv);  G2 = psum;  chol+inv;
    Q_loc = scale_blocked(Q1_loc, R2inv);  R = R2·R1.  The two psums are
    the pipeline's ONLY collectives — identical to the unfused 1d tree
    (reference MPI_Allreduce over world, cacqr.hpp:14-25)."""
    from capital_tpu.ops import qr_fused

    n = A.shape[1]
    precision = cfg.precision
    live = qr_fused.live_fraction(g)
    axes = ("x", "y", "z")

    def body(a_loc):
        # trace-time emissions run once, inside the body: all quantities
        # are already per-device (the Recorder's convention — _sweep_1d
        # divides global flops by num_devices to land at the same figures)
        m_loc = a_loc.shape[0]
        comm, ncoll = tracing.allreduce_cost(grid, n, n, jnp.float32, axes="all")
        with tracing.scope("CQR::gram"):
            tracing.emit(
                flops=2.0 * m_loc * n * n * live, comm_bytes=comm,
                collectives=ncoll,
            )
            G1u = lax.psum(
                qr_fused.gram_blocked(a_loc, g=g, precision=precision), axes
            )
            # the psum'd gram keeps the kernel's upper-block-row validity;
            # the upper-valid factor pair reads only that triangle, so no
            # per-shard symmetric assembly pass (same rule as _cqr2_fused)
            G1 = G1u.astype(A.dtype)
        with tracing.scope("CQR::chol"):
            tracing.emit(flops=tracing.potrf_trtri_flops(n))
            R1, R1inv = lapack.potrf_trtri_upper(G1)
        with tracing.scope("CQR::fused"):
            tracing.emit(
                flops=2.0 * m_loc * n * n * (live + live), comm_bytes=comm,
                collectives=ncoll,
            )
            if plan == "split":
                Q1 = qr_fused.scale_blocked(
                    a_loc, jnp.triu(R1inv), g=g, precision=precision
                )
                G2u = qr_fused.gram_blocked(Q1, g=g, precision=precision)
            else:
                Q1, G2u = qr_fused.scale_gram(
                    a_loc, jnp.triu(R1inv), g=g, precision=precision
                )
            G2 = lax.psum(G2u, axes).astype(A.dtype)
        with tracing.scope("CQR::chol"):
            tracing.emit(flops=tracing.potrf_trtri_flops(n))
            R2, R2inv = lapack.potrf_trtri_upper(G2)
        with tracing.scope("CQR::formR"):
            tracing.emit(flops=2.0 * m_loc * n * n * live)
            Q = qr_fused.scale_blocked(
                Q1, jnp.triu(R2inv), g=g, precision=precision
            )
        with tracing.scope("CQR::merge"):
            tracing.emit(flops=2.0 * n**3)
            R = jnp.matmul(jnp.triu(R2), jnp.triu(R1), precision=precision)
        return Q, R

    # check_vma=False: pallas's interpret-mode evaluator (the CPU test rig)
    # builds its grid-carry init with empty varying-axes and trips the vma
    # matcher against the per-shard operands — an interpreter limitation,
    # not a replication hazard: R is computed identically on every shard
    # from psum'd grams (gated by the mesh tests' residual checks), and the
    # Mosaic path also compiles under check_vma=True (the vma-annotated
    # out_shapes stay for that).
    Q, R = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=P(axes, None),
        out_specs=(P(axes, None), P()),
        check_vma=False,
    )(lax.with_sharding_constraint(A, grid.rows_sharding()))
    return Q, R


def _sweep_dist(
    grid: Grid, A: jnp.ndarray, cfg: CacqrConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One CQR sweep, distributed regime (reference sweep_3d, cacqr.hpp:82-116).

    Gram via distributed syrk, then **cholinv on the gram** (the wiring at
    cacqr.hpp:103), then Q = A·R⁻¹ by SUMMA trmm — or, when cholinv is run
    without the completed inverse, the 2x2 blocked solve (cacqr.hpp:46-73).
    """
    A = grid.pin(A)
    with tracing.scope("CQR::gram"):
        G = summa.syrk(
            grid, A, args=SyrkArgs(trans=True, precision=cfg.precision), mode=cfg.mode
        )
        G = faultinject.tap(G)
    with tracing.scope("CQR::chol"):
        ccfg = dataclasses.replace(cfg.cholinv, robust=None)
        R, Rinv = _chol_site(
            G, A.shape[0], lambda g_: cholesky.factor(grid, g_, ccfg)
        )
    with tracing.scope("CQR::formR"):
        if cfg.cholinv.complete_inv:
            Q = summa.trmm(
                grid, Rinv, A,
                TrmmArgs(side="R", uplo="U", precision=cfg.precision), mode=cfg.mode,
            )
        else:
            Q = solve_blocked(grid, A, R, Rinv, cfg)
    return Q, R


def solve_blocked(
    grid: Grid,
    A: jnp.ndarray,
    R: jnp.ndarray,
    Rinv: jnp.ndarray,
    cfg: CacqrConfig,
) -> jnp.ndarray:
    """X = A·R⁻¹ from the *partial* inverse: the 2x2 blocked triangular solve
    that is the reference's de-facto distributed TRSM (cacqr.hpp:46-73).

    With R = [[R11, R12], [0, R22]] and only R11⁻¹, R22⁻¹ available (the
    complete_inv=False contract of cholinv):

        X1 = A1 · R11⁻¹
        X2 = (A2 − X1·R12) · R22⁻¹
    """
    n = R.shape[0]
    n1 = cholesky.top_split(n, cfg.cholinv)
    if n1 == n:
        # single base-case window: Rinv is already the full inverse
        return summa.trmm(
            grid, Rinv, A,
            TrmmArgs(side="R", uplo="U", precision=cfg.precision), mode=cfg.mode,
        )
    A1, A2 = A[:, :n1], A[:, n1:]
    R11inv, R22inv = Rinv[:n1, :n1], Rinv[n1:, n1:]
    R12 = R[:n1, n1:]
    X1 = summa.trmm(
        grid, R11inv, A1,
        TrmmArgs(side="R", uplo="U", precision=cfg.precision), mode=cfg.mode,
    )
    A2p = summa.gemm(
        grid, X1, R12, A2,
        GemmArgs(alpha=-1.0, beta=1.0, precision=cfg.precision), mode=cfg.mode,
    )
    X2 = summa.trmm(
        grid, R22inv, A2p,
        TrmmArgs(side="R", uplo="U", precision=cfg.precision), mode=cfg.mode,
    )
    return grid.pin(jnp.concatenate([X1, X2], axis=1))


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------


def _pick_regime(grid: Grid, n: int, cfg: CacqrConfig) -> str:
    # validate up front: an unknown string used to fall through to the dist
    # path silently, turning a typo ('1D', 'fused', ...) into a whole
    # different algorithm with no signal
    if cfg.regime not in ("1d", "dist", "auto"):
        raise ValueError(
            f"unknown regime {cfg.regime!r}; expected '1d', 'dist' or 'auto'"
        )
    if cfg.regime != "auto":
        return cfg.regime
    if grid.dy == 1 and grid.c == 1:
        return "1d"
    return "1d" if n <= cfg.dist_threshold else "dist"


def route(
    grid: Grid, m: int, n: int, dtype, cfg: CacqrConfig, regime: str
) -> tuple[str, dict]:
    """Which pipeline a build of factor() traces, and what it traces it
    at: ``fused_sharded/<plan>`` (the fused kernels per shard on a mesh),
    ``fused/<plan>`` (one device), ``panels``, ``sweeps_1d`` (the unfused
    1d sweeps) or ``dist``, with the per-shard ``rows``, the column split
    ``g`` and the row block each fused kernel is built with
    (qr_fused.tall_bm): ``bm`` the Gram's (None off the kernels), and
    ``bm_scale_gram`` and ``bm_scale`` the scales' where they differ from
    it (0 where the kernel does not fit)."""
    from capital_tpu.ops import qr_fused

    rows = m // grid.num_devices
    if regime != "1d":
        return "dist", {"rows": rows, "g": 0, "bm": None}
    g = qr_fused.pick_g(n, cfg.fused_g)
    plan = (
        qr_fused.fused_plan(grid, m, n, cfg.mode, g=g, dtype=dtype)
        if cfg.num_iter == 2 and g
        else None
    )
    if plan in ("full", "split"):
        bms = qr_fused.row_blocks(grid, rows, n, dtype)
        fused = {"rows": rows, "g": g, "bm": bms.pop("gram")}
        fused.update({f"bm_{k}": v for k, v in bms.items() if v != fused["bm"]})
    if grid.num_devices == 1:
        if plan == "panels":
            return "panels", {"rows": rows, "g": n // 512, "bm": None}
        if plan:
            return f"fused/{plan}", fused
    elif plan in ("full", "split") and not _ROBUST:
        return f"fused_sharded/{plan}", fused
    # a mesh never runs the panel pipeline, and a robust mesh build runs
    # the guarded sweeps unfused: the session's traced event values cannot
    # escape the shard_map body
    return "sweeps_1d", {"rows": rows, "g": _col_blocks(n), "bm": None}


def _sweeps_1d(
    grid: Grid, A: jnp.ndarray, cfg: CacqrConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cfg.num_iter unfused 1d sweeps, their R factors merged."""
    Q, R = _sweep_1d(grid, A, cfg)
    if cfg.num_iter == 2:
        Q, R2 = _sweep_1d(grid, Q, cfg)
        with tracing.scope("CQR::merge"):
            tracing.emit(flops=2.0 * R.shape[0] ** 3)
            R = jnp.matmul(jnp.triu(R2), jnp.triu(R), precision=cfg.precision)
    return Q, R


def _sweeps_dist(
    grid: Grid, A: jnp.ndarray, cfg: CacqrConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cfg.num_iter distributed sweeps, their R factors merged."""
    Q, R = _sweep_dist(grid, A, cfg)
    if cfg.num_iter == 2:
        Q, R2 = _sweep_dist(grid, Q, cfg)
        # merge R = R2 · R1: both upper triangular; small distributed trmm
        # (reference cacqr.hpp:181-189, 204-210)
        with tracing.scope("CQR::merge"):
            R = summa.trmm(
                grid, R2, R,
                TrmmArgs(side="L", uplo="U", precision=cfg.precision), mode=cfg.mode,
            )
    return Q, R


def _factor_core(
    grid: Grid, A: jnp.ndarray, cfg: CacqrConfig, regime: str
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The route dispatch shared by the plain and robust entries (factor).
    Each build counts its route in `spans.ROUTES` and traces it under a
    ``qr.route`` program span carrying the same tags."""
    m, n = A.shape
    name, tags = route(grid, m, n, A.dtype, cfg, regime)
    spans.ROUTES.take(name, **tags)
    with spans.span("qr.route", route=name, **tags):
        kind, _, plan = name.partition("/")
        if kind == "fused_sharded":
            return _cqr2_fused_sharded(grid, A, cfg, tags["g"], plan)
        if kind == "fused":
            return _cqr2_fused(grid, A, cfg, tags["g"], plan)
        if kind == "panels":
            return _cqr2_panels(grid, A, cfg)
        if kind == "sweeps_1d":
            return _sweeps_1d(grid, A, cfg)
        return _sweeps_dist(grid, A, cfg)


def _finish_robust(grid: Grid, A, Q, R, cfg: CacqrConfig, ses: _Session):
    """Aggregate the session's CholEvents into a RobustInfo and, on
    breakdown, run the escalation ladder: the sCQR3 third sweep (one more
    muted gram + guarded chol + scale) when the orthogonality gate of the
    recovered Q still exceeds tolerance, then — under rcfg.tsqr — the
    blocked Householder TSQR rung (ops/tsqr at the always-f64 escalation
    dtype) when even sCQR3 leaves the gate failing.  Everything is
    lax.cond-gated, so the healthy path executes only the O(n²) status
    reductions.  RobustInfo.gate records WHICH gate a surviving nonzero
    info came from (GATE_ORTHO vs GATE_RESIDUAL, robust/config.py)."""
    rcfg = ses.rcfg
    m, n = Q.shape[0], R.shape[0]
    if ses.events:
        infos = jnp.stack([jnp.asarray(ev.info, jnp.int32) for ev in ses.events])
        sigmas = jnp.stack(
            [jnp.asarray(ev.sigma, jnp.float32) for ev in ses.events]
        )
        infos_after = jnp.stack(
            [jnp.asarray(ev.info_after, jnp.int32) for ev in ses.events]
        )
        breakdown = jnp.sum((infos != 0).astype(jnp.int32))
        shifted = jnp.sum((sigmas > 0).astype(jnp.int32))
        sigma = jnp.max(sigmas)
        info = jnp.max(infos_after)
    else:
        breakdown = jnp.int32(0)
        shifted = jnp.int32(0)
        sigma = jnp.float32(0.0)
        info = jnp.int32(0)
    escalated = jnp.int32(0)
    ortho = jnp.float32(-1.0)
    ortho_failed = jnp.bool_(False)
    info3 = jnp.int32(0)
    if rcfg.escalate and ses.events:
        tol = rcfg.ortho_tol
        if tol is None:
            tol = 100.0 * n * recovery.unit_roundoff(Q.dtype)

        def _broke(args):
            Q0, R0 = args
            # CQR::recover scope: named HLO attribution for the audit layer;
            # muted so the cost model keeps describing the healthy path
            # (both cond branches trace — an emit here would double-count)
            with tracing.scope("CQR::recover"), tracing.muted():
                G3 = lax.with_sharding_constraint(
                    jnp.matmul(Q0.T, Q0, precision=cfg.precision),
                    grid.replicated_sharding(),
                )
                gate = (
                    jnp.linalg.norm(G3 - jnp.eye(n, dtype=G3.dtype))
                    / jnp.sqrt(jnp.asarray(n, G3.dtype))
                ).astype(jnp.float32)

                def _polish(args2):
                    Q1, R1 = args2
                    R3, R3inv, ev3 = recovery.guarded_chol(
                        G3, m, rcfg,
                        lambda g_: lapack.potrf_trtri(g_, uplo="U"),
                    )
                    Qp = lax.with_sharding_constraint(
                        jnp.matmul(
                            Q1, jnp.triu(R3inv), precision=cfg.precision
                        ),
                        grid.rows_sharding(),
                    )
                    Rp = jnp.matmul(
                        jnp.triu(R3), jnp.triu(R1), precision=cfg.precision
                    )
                    # re-measure AFTER the third sweep: ortho must report
                    # the returned Q, not the one the escalation replaced
                    G4 = lax.with_sharding_constraint(
                        jnp.matmul(Qp.T, Qp, precision=cfg.precision),
                        grid.replicated_sharding(),
                    )
                    gate2 = (
                        jnp.linalg.norm(G4 - jnp.eye(n, dtype=G4.dtype))
                        / jnp.sqrt(jnp.asarray(n, G4.dtype))
                    ).astype(jnp.float32)
                    return Qp, Rp, jnp.int32(1), ev3.info_after, gate2

                def _skip(args2):
                    Q1, R1 = args2
                    return Q1, R1, jnp.int32(0), jnp.int32(0), gate

                Qn, Rn, esc, info3, gate_f = lax.cond(
                    gate > tol, _polish, _skip, (Q0, R0)
                )
            return Qn, Rn, esc, gate_f, info3

        def _fine(args):
            Q0, R0 = args
            return Q0, R0, jnp.int32(0), jnp.float32(-1.0), jnp.int32(0)

        Q, R, escalated, ortho, info3 = lax.cond(
            breakdown > 0, _broke, _fine, (Q, R)
        )
        # the sentinel condition: every chol after recovery was clean, yet
        # the final orthogonality gate still fails — cond(A) is beyond what
        # sCQR3 can repair at this precision (per shifted sweep cond drops
        # only by ~sqrt(shift_c*u*(m*n+n(n+1))); in f32 that's a factor of
        # a few — see docs/ROBUSTNESS.md).
        unrecovered = (escalated > 0) & (ortho > tol)
        if rcfg.tsqr:
            # the rung above sCQR3: re-factor A itself with the blocked
            # Householder TSQR at the escalation dtype (always-f64 rule,
            # recovery.escalation_dtype) — no gram, so cond(A) up to ~u⁻¹
            # recovers where every CQR-family sweep stalls.  Gated on the
            # same traced predicate; muted like the other recovery work.
            ct = recovery.escalation_dtype(Q.dtype)
            tol_e = 100.0 * n * recovery.unit_roundoff(ct)

            def _tsqr_rung(args):
                Q1, R1 = args
                with tracing.scope("CQR::recover"), tracing.muted():
                    from capital_tpu.ops import tsqr as tsqr_mod

                    Qt, Rt = tsqr_mod.tsqr(
                        A.astype(ct), precision=cfg.precision
                    )
                    gate_t = tsqr_mod.ortho_gate(Qt, cfg.precision)
                    return Qt.astype(Q1.dtype), Rt.astype(R1.dtype), gate_t

            def _keep_qr(args):
                Q1, R1 = args
                return Q1, R1, ortho

            Q, R, ortho = lax.cond(unrecovered, _tsqr_rung, _keep_qr, (Q, R))
            escalated = jnp.where(unrecovered, jnp.int32(2), escalated)
            # recovered iff the f64-measured gate now passes the f64 tol —
            # the sentinel (and gate code) below read the updated verdict
            unrecovered = unrecovered & (ortho > tol_e)
        ortho_failed = unrecovered
        info = jnp.maximum(
            jnp.maximum(info, info3),
            jnp.where(unrecovered, jnp.int32(n + 2), jnp.int32(0)),
        )
    # which gate does a nonzero info describe?  The ortho-gate sentinel
    # outranks residual statuses (it is the TSQR-escalatable case the
    # routing exists to distinguish — robust/config.GATE_* vocabulary).
    gate_code = jnp.where(
        ortho_failed,
        jnp.int32(config_mod.GATE_ORTHO),
        jnp.where(
            jnp.maximum(info, info3) > 0,
            jnp.int32(config_mod.GATE_RESIDUAL),
            jnp.int32(config_mod.GATE_NONE),
        ),
    )
    return Q, R, RobustInfo(
        info=info, breakdown=breakdown, shifted=shifted, sigma=sigma,
        escalated=escalated, ortho=ortho, gate=gate_code,
    )


@pallas_tpu.scoped_by_grid
def factor(grid: Grid, A: jnp.ndarray, cfg: CacqrConfig = CacqrConfig()):
    """QR of tall-skinny A: returns (Q, R) with A = QR, R upper triangular.

    Equivalent of qr::cacqr::factor (cacqr.hpp:216-245); jit-friendly.
    num_iter=2 (CQR2) merges the two sweeps' triangular factors with a
    trmm, R = R2·R1 (cacqr.hpp:181-189, 204-210).

    With cfg.robust set the return is (Q, R, RobustInfo): every Cholesky
    site is breakdown-guarded, broken grams re-factor with the sCQR shift,
    and the sCQR3 third sweep runs when the recovered Q's orthogonality
    gate still exceeds tolerance (docs/ROBUSTNESS.md).  RobustInfo.info is
    the residual status AFTER recovery — nonzero means the result is still
    bad (e.g. a non-finite input) and must not be trusted.
    """
    m, n = A.shape
    if m < n:
        raise ValueError(f"cacqr expects tall-skinny input, got {A.shape}")
    if cfg.num_iter not in (1, 2):
        raise ValueError(f"num_iter must be 1 (CQR) or 2 (CQR2), got {cfg.num_iter}")
    regime = _pick_regime(grid, n, cfg)
    if cfg.robust is None:
        return _factor_core(grid, A, cfg, regime)
    ses = _Session(cfg.robust)
    _ROBUST.append(ses)
    try:
        Q, R = _factor_core(grid, A, cfg, regime)
    finally:
        _ROBUST.pop()
    return _finish_robust(grid, A, Q, R, cfg, ses)


def apply_Q(
    grid: Grid,
    Q: jnp.ndarray,
    X: jnp.ndarray,
    mode: str = "xla",
    precision: str | None = "highest",
) -> jnp.ndarray:
    """Q @ X (reference apply_Q = SUMMA gemm, cacqr.hpp:272-280)."""
    return summa.gemm(grid, Q, X, args=GemmArgs(precision=precision), mode=mode)


def apply_QT(
    grid: Grid,
    Q: jnp.ndarray,
    X: jnp.ndarray,
    mode: str = "xla",
    precision: str | None = "highest",
) -> jnp.ndarray:
    """Qᵀ @ X.  The reference left this as static_assert(0) (cacqr.hpp:284);
    implemented here — it is just the transposed gemm."""
    return summa.gemm(
        grid, Q, X, args=GemmArgs(trans_a=True, precision=precision), mode=mode
    )
