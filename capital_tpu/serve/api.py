"""The served solve kernels: posv / lstsq / inv, batched and single-problem.

Two routes per op, chosen by the engine:

* **batched** — the whole bucket batch in one program, with TWO
  interchangeable implementations behind the `impl` switch:

  - ``vmap`` — a vmap over per-problem kernels built directly on the
    LAPACK seam (ops/lapack) and lax.linalg, which batch natively.  The
    models/ schedules are NOT vmapped: they carry sharding constraints and
    trace-time cost-model emits sized for one distributed problem, neither
    of which means anything replicated over a batch axis:

        posv   potrf(A) + the two-trsm potrs sweeps        (lapack.potrs)
        lstsq  CholeskyQR2 on the gram + triangular solve  (the CQR2
               pipeline of models/qr.py collapsed to single-problem form)
        inv    potrf_trtri + R⁻¹·R⁻ᵀ                       (spd_inverse's
               core)

  - ``pallas`` — the batched-grid kernels of ops/batched_small: ONE
    pallas_call with the batch axis on the grid, factor kept VMEM-resident
    between factor and solve (fused posv / fused CQR2 lstsq).  This is the
    small-N latency path; ``pallas_split`` is its unfused two-call variant
    (separate factor and solve launches — the A/B reference the latency
    autotune measures the fusion win against; lstsq has no split form and
    routes to the fused kernel).  ``auto`` resolves per bucket at trace
    time from the STATIC batch shapes (batched_small.default_impl: pallas
    iff posv/lstsq, n <= SMALL_N_MAX and VMEM-eligible, else vmap) — no
    runtime value feeds the choice, so the engine's zero-recompile
    invariant is untouched.

    inv rides the posv kernel: the serve contract guarantees an SPD
    operand (`submit` rejects anything else), so A⁻¹ = posv(A, Iₙ) — the
    auto resolution treats an inv bucket as a posv with an n-column RHS
    (batched_small itself keeps its "inv goes vmap" contract; the identity
    trick is serve policy, decided here).  Beyond the latency win on small
    buckets, this keeps the program pure HLO, which the persistent
    executable cache needs on CPU: LAPACK custom calls do not survive
    serialization across processes (serve/cache.py).

  Every batched kernel returns (X, info) with info the per-problem int32
  breakdown status — LAPACK with_info on the vmap path, the in-kernel
  O(n²) pivot/off-diagonal checks on the pallas paths (same 0/k/n+1
  convention, robust/detect.factor_info) — detection is O(n²) against the
  O(n³) solve, so it is always on; the engine decides whether to surface
  it (ServeConfig.robust) or let NaNs pass like the raw lax paths would.

* **single** — oversize requests (beyond every bucket ladder) run unbatched
  through the REAL models/ paths (cholesky.solve, qr.factor + triangular
  solve, cholinv factor + SUMMA gemm), so a giant request still gets the
  distributed schedules and, under robust, the full shifted-CholeskyQR
  recovery rather than detect-only flagging.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from capital_tpu.models import arrowhead, blocktri, cholesky, qr
from capital_tpu.ops import batched_small, blocktri_small, lapack, update_small
from capital_tpu.parallel import summa
from capital_tpu.utils import tracing


def _tri_solve_upper(R, B, precision):
    """R·X = B for upper-triangular R at the >= f32 compute dtype."""
    del precision  # triangular_solve has no precision knob; upcast covers it
    ct = lapack._compute_dtype(R.dtype)
    X = lax.linalg.triangular_solve(
        R.astype(ct), B.astype(ct), left_side=True, lower=False
    )
    return X.astype(B.dtype)


def _one_posv(precision):
    def f(a, b):
        with tracing.scope("serve::solve"):
            R, info = lapack.potrf(a, uplo="U", with_info=True)
            return lapack.potrs(R, b, uplo="U"), info

    return f


def _one_lstsq(precision):
    def f(a, b):
        with tracing.scope("serve::solve"):
            # CQR2 (models/qr.py single-problem form): two gram-Cholesky
            # sweeps; Q = A·R1⁻¹·R2⁻¹, R = R2·R1; then solve R·X = QᵀB.
            g = jnp.matmul(a.T, a, precision=precision)
            r1, r1i, i1 = lapack.potrf_trtri(g, uplo="U", with_info=True)
            q1 = jnp.matmul(a, jnp.triu(r1i), precision=precision)
            g2 = jnp.matmul(q1.T, q1, precision=precision)
            r2, r2i, i2 = lapack.potrf_trtri(g2, uplo="U", with_info=True)
            R = jnp.matmul(jnp.triu(r2), jnp.triu(r1), precision=precision)
            qtb = jnp.matmul(
                jnp.triu(r2i).T,
                jnp.matmul(q1.T, b, precision=precision),
                precision=precision,
            )
            return _tri_solve_upper(R, qtb, precision), jnp.maximum(i1, i2)

    return f


def _one_inv(precision):
    def f(a):
        with tracing.scope("serve::solve"):
            _, rinv, info = lapack.potrf_trtri(a, uplo="U", with_info=True)
            tri = jnp.triu(rinv)
            return jnp.matmul(tri, tri.T, precision=precision), info

    return f


def _batched_vmap(op: str, precision):
    """The vmap-over-LAPACK batch program: correctness reference and
    pure-XLA fallback for the pallas paths."""
    if op == "inv":
        return jax.vmap(_one_inv(precision))
    one = {"posv": _one_posv, "lstsq": _one_lstsq}[op](precision)
    return jax.vmap(one)


def _batched_pallas(op: str, precision, split: bool):
    """The batched-grid route: whole bucket batch in one (fused) or two
    (split) pallas_calls.  Resolution happened at trace time on static
    shapes, so the returned callable is shape-monomorphic like the vmap
    one — the engine AOT-compiles it per bucket exactly the same way.

    f64 buckets ALWAYS fall back to the vmap program, even when the impl
    was forced: the kernels compute in f32, so honoring impl='pallas' on
    an f64 bucket would silently downgrade precision behind f64-labeled
    outputs (batched_small.dtype_capable — the 'f64 always vmap'
    contract).  The check reads only the static dtype, so the fallback
    resolves at trace time and the zero-recompile invariant holds."""
    if op == "inv":
        # SPD inverse as posv against the identity (module docstring);
        # split runs the factor and the n-column solve as two launches.
        def kernel(a):
            eye = jnp.broadcast_to(
                jnp.eye(a.shape[-1], dtype=a.dtype), a.shape
            )
            if split:
                R, info = batched_small.potrf(
                    a, uplo="U", precision=precision
                )
                return batched_small.potrs(
                    R, eye, uplo="U", precision=precision
                ), info
            return batched_small.posv(a, eye, uplo="U", precision=precision)

        def f_inv(a):
            if not batched_small.dtype_capable(a.dtype):
                return _batched_vmap(op, precision)(a)
            return kernel(a)

        return f_inv
    if op == "lstsq":
        def kernel(a, b):
            return batched_small.lstsq(a, b, precision=precision)
    elif split:
        def kernel(a, b):
            R, info = batched_small.potrf(a, uplo="U", precision=precision)
            return batched_small.potrs(R, b, uplo="U",
                                       precision=precision), info
    else:
        def kernel(a, b):
            return batched_small.posv(a, b, uplo="U", precision=precision)

    def f(a, b):
        if not batched_small.dtype_capable(a.dtype):
            return _batched_vmap(op, precision)(a, b)
        return kernel(a, b)

    return f


def _batched_blocktri(precision, impl: str, blocktri_impl: str = "auto",
                      partitions: int = 0):
    """The block-tridiagonal bucket program: unpack the (batch, 2,
    nblocks, b, b) chain packing (A[:, 0] = diagonal blocks, A[:, 1] =
    sub-diagonal blocks) and run the fused scan-of-Pallas-blocks posv
    (models/blocktri).  The serve-wide impl vocabulary (batched_small.
    IMPLS, what ServeConfig.small_n_impl speaks) maps onto blocktri's
    own: 'vmap' means the pure lax.linalg scan ('xla' — there is no
    per-problem LAPACK route for the chain), 'pallas_split' means
    'pallas' (the chain has no split form; the scan IS the split).

    `blocktri_impl` is the ALGORITHM knob (ServeConfig.blocktri_impl,
    config-hashed): 'partitioned' forces the Spike driver with the
    serve-wide impl picking its inner scan flavor, 'scan' pins the
    sequential scan even where posv's auto would split, 'auto' leaves
    the choice to models/blocktri (auto kernel flavor only — a forced
    'pallas'/'vmap' engine keeps today's sequential program).  All
    resolution reads only static shapes/dtypes (models/blocktri
    ._resolve_impl incl. the f64-always-xla gate, resolve_partitions),
    so the engine's zero-recompile invariant holds."""
    mapped = {"auto": "auto", "pallas": "pallas",
              "pallas_split": "pallas", "vmap": "xla"}[impl]
    if blocktri_impl not in blocktri.ALGORITHMS:
        raise ValueError(
            f"unknown blocktri_impl {blocktri_impl!r}: expected one of "
            f"{blocktri.ALGORITHMS}")

    def f(a, b):
        if blocktri_impl == "partitioned":
            return blocktri.posv(a[:, 0], a[:, 1], b, precision=precision,
                                 impl="partitioned", partitions=partitions,
                                 partition_inner=mapped)
        if blocktri_impl == "scan" and mapped == "auto":
            # pin the sequential algorithm but keep per-bucket kernel
            # resolution: static-shape trace-time pick, like auto()
            nblocks, bs = a.shape[2], a.shape[3]
            pick = blocktri_small.default_impl(
                bs, b.shape[-1], blocktri.resolve_seg(nblocks), a.dtype)
            return blocktri.posv(a[:, 0], a[:, 1], b,
                                 precision=precision, impl=pick)
        return blocktri.posv(a[:, 0], a[:, 1], b, precision=precision,
                             impl=mapped, partitions=partitions)

    return f


def _batched_arrowhead(precision, impl: str, blocktri_impl: str = "auto",
                       partitions: int = 0):
    """The block-arrowhead bucket program: chain pack A = (batch, 2,
    nblocks, b, b) like posv_blocktri, plus the packed tail operand
    B = (batch, nblocks·b + s, s + k) (models/arrowhead.pack — border
    transpose, corner, and both RHS halves in one array; every geometry
    re-derives from the STATIC shapes, so bucket resolution and the
    zero-recompile invariant are untouched).

    THREE outputs (X_chain, X_corner, info): the chain half stays BLOCKED
    (batch, nblocks, b, k) so `batching.crop` unpads it by plain slicing
    like posv_blocktri's; the (batch, s, k) corner half rides the
    executor's extras slot to the engine's arrowhead landing sink, which
    crops and concatenates the flat (nblocks·b + s, k) response.

    The impl vocabulary and the `blocktri_impl` algorithm knob map
    exactly like `_batched_blocktri` — they reach the ONE widened chain
    solve inside arrowhead.posv (k + s columns), so 'partitioned' runs
    the Spike driver under the border solve."""
    mapped = {"auto": "auto", "pallas": "pallas",
              "pallas_split": "pallas", "vmap": "xla"}[impl]
    if blocktri_impl not in blocktri.ALGORITHMS:
        raise ValueError(
            f"unknown blocktri_impl {blocktri_impl!r}: expected one of "
            f"{blocktri.ALGORITHMS}")

    def f(a, b):
        nblocks, bs = a.shape[2], a.shape[3]
        F, S, B, Bs = arrowhead.unpack(b, nblocks, bs)
        if blocktri_impl == "partitioned":
            return arrowhead.posv(a[:, 0], a[:, 1], F, S, B, Bs,
                                  precision=precision, impl="partitioned",
                                  partitions=partitions,
                                  partition_inner=mapped)
        if blocktri_impl == "scan" and mapped == "auto":
            # pin the sequential algorithm, keep per-bucket kernel
            # resolution — at the WIDENED k + s column count the chain
            # sweeps actually run at (_batched_blocktri's idiom)
            pick = blocktri_small.default_impl(
                bs, B.shape[-1] + F.shape[2],
                blocktri.resolve_seg(nblocks), a.dtype)
            return arrowhead.posv(a[:, 0], a[:, 1], F, S, B, Bs,
                                  precision=precision, impl=pick)
        return arrowhead.posv(a[:, 0], a[:, 1], F, S, B, Bs,
                              precision=precision, impl=mapped,
                              partitions=partitions)

    return f


#: serve-wide impl vocabulary -> the two-impl modules' own ('vmap' means
#: the pure-XLA route; 'pallas_split' collapses to 'pallas' — neither the
#: update sweep nor the chain scan has a split form).
_TWO_IMPL_MAP = {"auto": "auto", "pallas": "pallas",
                 "pallas_split": "pallas", "vmap": "xla"}


def _batched_update(op: str, precision, impl: str):
    """chol_update / chol_downdate bucket program: (resident factor batch,
    rank-k panel batch) -> (R', info).  Impl resolution (incl. the
    f64-always-xla gate) lives in ops/update_small._resolve_impl and
    reads only static shapes/dtypes — zero-recompile safe."""
    mapped = _TWO_IMPL_MAP[impl]
    fn = (update_small.chol_update if op == "chol_update"
          else update_small.chol_downdate)

    def f(r, v):
        return fn(r, v, precision=precision, impl=mapped)

    return f


def _batched_posv_cached(precision, impl: str):
    """Solve against a RESIDENT factor: (R, B) -> (X, info≡0).  No
    factorization happens, so info is identically zero (a resident factor
    was healthy when installed — landing refuses to install flagged
    ones); the program is potrs alone, the whole point of residency."""
    def pallas_f(r, b):
        X = batched_small.potrs(r, b, uplo="U", precision=precision)
        return X, jnp.zeros(r.shape[0], jnp.int32)

    def vmap_f(r, b):
        with tracing.scope("serve::solve"):
            X = jax.vmap(lambda rr, bb: lapack.potrs(rr, bb, uplo="U"))(r, b)
        return X, jnp.zeros(r.shape[0], jnp.int32)

    if impl == "vmap":
        return vmap_f
    if impl in ("pallas", "pallas_split"):
        return lambda r, b: (
            pallas_f(r, b) if batched_small.dtype_capable(r.dtype)
            else vmap_f(r, b))

    def auto(r, b):
        pick = batched_small.default_impl("posv", r.shape, b.shape, r.dtype)
        return vmap_f(r, b) if pick == "vmap" else pallas_f(r, b)

    return auto


def _batched_posv_cached_miss(precision, impl: str):
    """The residency-miss (seeding) program: full (A, B) operands, THREE
    outputs (X, R, info) so landing can install the fresh factor under
    the request's token — a posv that also hands back its factor.  Priced
    as a full refactor (the cost-model point of the residency hit-rate
    gate)."""
    def pallas_f(a, b):
        R, info = batched_small.potrf(a, uplo="U", precision=precision)
        X = batched_small.potrs(R, b, uplo="U", precision=precision)
        return X, R, info

    def one_vmap(a, b):
        with tracing.scope("serve::solve"):
            R, info = lapack.potrf(a, uplo="U", with_info=True)
            return lapack.potrs(R, b, uplo="U"), R, info

    vmap_f = jax.vmap(one_vmap)
    if impl == "vmap":
        return vmap_f
    if impl in ("pallas", "pallas_split"):
        return lambda a, b: (
            pallas_f(a, b) if batched_small.dtype_capable(a.dtype)
            else vmap_f(a, b))

    def auto(a, b):
        pick = batched_small.default_impl("posv", a.shape, b.shape, a.dtype)
        return vmap_f(a, b) if pick == "vmap" else pallas_f(a, b)

    return auto


def _batched_extend(precision, impl: str):
    """The chain-extension bucket program: (appended chain packing
    (batch, 2, nblocks, b, b), resident carry (batch, b, b)) -> (stacked
    [L; Wt] (batch, 2, nblocks, b, b), info).  C[:, 0] arrives LIVE (the
    coupling into the prefix tail; the engine zeroes it host-side for
    fresh-token seeds, so ONE compiled program serves both cases)."""
    mapped = _TWO_IMPL_MAP[impl]

    def f(a, carry):
        L, Wt, info = blocktri.extend(a[:, 0], a[:, 1], carry,
                                      precision=precision, impl=mapped)
        return jnp.stack([L, Wt], axis=1), info

    return f


def _batched_session_extend(precision, impl: str):
    """The session open/append bucket program (docs/SERVING.md "Streaming
    sessions"): same operands and outputs as `_batched_extend` — ONE
    compiled program serves both session_open (engine zeroes C[:, 0] and
    seeds an identity carry host-side) and session_append (resident
    carry, live coupling).  The interior extend traces muted() under the
    SS::extend scope so the chain work is priced exactly once, under the
    session tag the session stats attribute by."""
    mapped = _TWO_IMPL_MAP[impl]

    def f(a, carry):
        nblocks, bs = a.shape[2], a.shape[3]
        with tracing.scope("SS::extend"):
            tracing.emit(flops=a.shape[0]
                         * tracing.blocktri_chol_flops(nblocks, bs))
            with tracing.muted():
                L, Wt, info = blocktri.extend(a[:, 0], a[:, 1], carry,
                                              precision=precision,
                                              impl=mapped)
        return jnp.stack([L, Wt], axis=1), info

    return f


def _batched_session_solve(precision, impl: str):
    """The resident-factor session solve: the 4-stack operand packing
    A = (batch, 4, nblocks, b, b) = [D; C; L; Wt] carries the session's
    explicit window (for the guaranteed tier's residual operator) AND its
    resident factor in one bucket-shaped array; the balanced program
    reads only the factor half — two block-bidiagonal sweeps, no
    factorization, info identically zero (residency installs only
    healthy factors, the posv_cached contract)."""
    mapped = _TWO_IMPL_MAP[impl]

    def f(a, b):
        nblocks, bs = a.shape[2], a.shape[3]
        with tracing.scope("SS::solve"):
            tracing.emit(flops=a.shape[0] * 2 * tracing.blocktri_solve_flops(
                nblocks, bs, b.shape[-1]))
            with tracing.muted():
                X = blocktri.solve(a[:, 2], a[:, 3], b,
                                   precision=precision, impl=mapped)
        return X, jnp.zeros(a.shape[0], jnp.int32)

    return f


def _batched_refine(op: str, precision, impl: str, tier: str):
    """The guaranteed-tier bucket program: mixed-precision iterative
    refinement (robust/refine) over the flagship solve.  FIVE outputs —
    (X, iters, converged, resid, info) — so the executor's extras slot
    carries each request's refinement facts to the engine's refine sink
    (stats + the loud non-convergence contract).  All dtype resolution
    (refine.plan) reads only the static operand dtype, so one compile per
    (bucket, tier) and the zero-recompile invariant holds."""
    from capital_tpu.robust import refine

    def f(a, b):
        p = refine.plan(tier, a.dtype)
        kw = dict(factor_dtype=p.factor_dtype,
                  correction_dtype=p.correction_dtype,
                  max_iters=p.max_iters, impl=impl, precision=precision)
        if op == "posv":
            X, info, ri = refine.posv(a, b, **kw)
        elif op == "lstsq":
            X, info, ri = refine.lstsq(a, b, **kw)
        elif op == "session_solve":
            # resident-factor refinement (PR 14's factor= seam): the
            # session's (L, Wt) ride the 4-stack packing (a[:, 2:4]) at
            # the plan's factor dtype — correct() sweeps against them,
            # the explicit (D, C) window half drives the high-precision
            # residual operator, and no refactor happens at all
            X, info, ri = refine.posv_blocktri(
                a[:, 0], a[:, 1], b,
                factor=(a[:, 2].astype(p.factor_dtype),
                        a[:, 3].astype(p.factor_dtype)), **kw)
        else:  # posv_blocktri (bucket packing: a[:, 0]=D, a[:, 1]=C)
            X, info, ri = refine.posv_blocktri(a[:, 0], a[:, 1], b, **kw)
        return X, ri.iters, ri.converged, ri.resid, info

    return f


#: the ops the accuracy-tier vocabulary applies to — the three flagship
#: solves refine.py wraps, plus the session resident-factor solve (its
#: guaranteed tier rides refine's factor= seam).  Everything else (inv,
#: the factor-residency ops) rejects a non-balanced tier loudly rather
#: than silently serving the balanced program under a tier label.
TIER_OPS = ("posv", "lstsq", "posv_blocktri", "session_solve")


def batched(op: str, precision: str | None = "highest",
            impl: str = "auto", *, blocktri_impl: str = "auto",
            blocktri_partitions: int = 0, tier: str = "balanced"):
    """The function the engine AOT-compiles for one bucket: maps the fixed
    (capacity, *problem) batch through the per-problem kernel, returning
    (X, info) stacks.

    `impl` picks the batch program: 'vmap' (LAPACK-seam reference),
    'pallas' (fused batched-grid kernels), 'pallas_split' (unfused
    batched-grid factor + solve, two launches), or 'auto' (resolve per
    bucket from the static batch shapes at trace time — small VMEM-
    eligible posv/lstsq buckets go pallas, everything else vmap).
    `blocktri_impl` / `blocktri_partitions` reach only the posv_blocktri
    program (`_batched_blocktri` — the partitioned-vs-scan algorithm
    knob; config-hashed by the engine).

    `tier` is the request's accuracy tier (robust/refine.TIERS, part of
    the bucket key): 'balanced' compiles today's program byte-identical;
    'fast' runs it with the factor dtype one notch down (refine._down1 —
    bf16/f32 factor throughput, answers cast back to the request dtype,
    NO refinement: the overload-shedding tier); 'guaranteed' compiles the
    iterative-refinement program (`_batched_refine` — low-precision
    factor, high-precision correction sweeps, five outputs).  Only the
    flagship TIER_OPS accept a non-balanced tier.
    """
    if impl not in batched_small.IMPLS:
        raise ValueError(
            f"unknown batched impl {impl!r}: expected one of "
            f"{batched_small.IMPLS}"
        )
    if tier != "balanced":
        from capital_tpu.robust import refine

        if tier not in refine.TIERS:
            raise ValueError(
                f"accuracy_tier must be one of {refine.TIERS}, got {tier!r}"
            )
        if op not in TIER_OPS:
            raise ValueError(
                f"accuracy_tier={tier!r} applies only to {TIER_OPS}; "
                f"op {op!r} serves the balanced program only"
            )
        if tier == "guaranteed":
            return _batched_refine(op, precision, impl, tier)
        inner = batched(op, precision, impl, blocktri_impl=blocktri_impl,
                        blocktri_partitions=blocktri_partitions)

        def fast(a, b):
            fd = refine.plan("fast", a.dtype).factor_dtype
            X, info = inner(a.astype(fd), b.astype(fd))
            return X.astype(a.dtype), info

        return fast
    if op == "posv_blocktri":
        return _batched_blocktri(precision, impl, blocktri_impl,
                                 blocktri_partitions)
    if op == "posv_arrowhead":
        return _batched_arrowhead(precision, impl, blocktri_impl,
                                  blocktri_partitions)
    if op in ("chol_update", "chol_downdate"):
        return _batched_update(op, precision, impl)
    if op == "posv_cached":
        return _batched_posv_cached(precision, impl)
    if op == "posv_cached_miss":
        return _batched_posv_cached_miss(precision, impl)
    if op == "blocktri_extend":
        return _batched_extend(precision, impl)
    if op == "session_extend":
        return _batched_session_extend(precision, impl)
    if op == "session_solve":
        return _batched_session_solve(precision, impl)
    if impl == "vmap":
        return _batched_vmap(op, precision)
    if impl in ("pallas", "pallas_split"):
        return _batched_pallas(op, precision, split=(impl == "pallas_split"))
    if op == "inv":
        # auto for inv: eligibility of the identity-RHS posv (the RHS is
        # the n-column identity, so the VMEM question is posv's with
        # b_shape == a_shape) — batched_small's own default_impl keeps
        # routing op='inv' to vmap; this resolution is serve policy.
        def auto_inv(a):
            pick = batched_small.default_impl(
                "posv", a.shape, a.shape, a.dtype
            )
            if pick == "vmap":
                return _batched_vmap(op, precision)(a)
            return _batched_pallas(op, precision, split=False)(a)

        return auto_inv

    def auto(a, b):
        b_shape = getattr(b, "shape", None)
        pick = batched_small.default_impl(op, a.shape, b_shape, a.dtype)
        if pick == "vmap":
            return _batched_vmap(op, precision)(a, b)
        return _batched_pallas(op, precision, split=False)(a, b)

    return auto


#: the tiered requests the oversize route serves: (op, accuracy_tier)
SINGLE_TIERS = (("posv", "guaranteed"),)


def single(op: str, grid, precision: str | None = "highest", robust=None,
           tail_fuse_depth: int = 0, tier: str = "balanced"):
    """The oversize route: one exact-shape problem through the models/
    schedules on the engine's grid.  Uniform return contract (X, info):
    info is a scalar int32 (posv/inv) or a RobustInfo pytree (lstsq under
    robust); jnp.int32(0) when robust is None (the engine ignores it then).
    `tail_fuse_depth` threads ServeConfig's fused-recursion-tail knob into
    every CholinvConfig built here — it changes the compiled program, so
    the engine keys it into the cache config-hash.

    `tier` 'guaranteed' (posv only, `SINGLE_TIERS`) is the dense refined
    solve, robust/refine.posv_dense: FIVE outputs (X, iters, converged,
    resid, info) like the batched guaranteed programs, X the refined
    float-float answer rounded to the request dtype, info the factor's
    diagonal status.  posv_dense picks its own factor settings from the
    grid and the operand, so `precision` and `tail_fuse_depth` do not
    apply.
    """
    if tier != "balanced":
        if (op, tier) not in SINGLE_TIERS:
            raise ValueError(f"no oversize route for {op} at accuracy_tier="
                             f"{tier!r}")
        from capital_tpu.robust import refine

        def f(a, b):
            (hi, lo), info, ri = refine.posv_dense(grid, a, b)
            x = hi.astype(b.dtype) + lo.astype(b.dtype)
            return x, ri.iters[0], ri.converged[0], ri.resid[0], info

        return f
    if op == "posv":
        ccfg = cholesky.CholinvConfig(precision=precision, robust=robust,
                                      tail_fuse_depth=tail_fuse_depth)

        def f(a, b):
            out = cholesky.solve(grid, a, b, ccfg)
            return out if robust is not None else (out, jnp.int32(0))

        return f
    if op == "lstsq":
        qcfg = qr.CacqrConfig(
            precision=precision, robust=robust,
            cholinv=cholesky.CholinvConfig(precision=precision,
                                           tail_fuse_depth=tail_fuse_depth),
        )

        def f(a, b):
            out = qr.factor(grid, a, qcfg)
            if robust is not None:
                Q, R, rinfo = out
            else:
                (Q, R), rinfo = out, jnp.int32(0)
            qtb = qr.apply_QT(grid, Q, b, precision=precision)
            return _tri_solve_upper(R, qtb, precision), rinfo

        return f
    if op == "inv":
        ccfg = cholesky.CholinvConfig(precision=precision, robust=robust,
                                      tail_fuse_depth=tail_fuse_depth)

        def f(a):
            if robust is not None:
                _, rinv, info = cholesky.factor(grid, a, ccfg)
            else:
                _, rinv = cholesky.factor(grid, a, ccfg)
                info = jnp.int32(0)
            ainv = summa.gemm(
                grid, rinv, rinv,
                args=summa.GemmArgs(trans_b=True, precision=precision),
                mode=ccfg.mode,
            )
            return ainv, info

        return f
    if op == "posv_blocktri":
        # oversize chains run as a batch of one through the models
        # dispatch — impl='auto' picks the partitioned (Spike) driver
        # above PARTITION_MIN_NBLOCKS, exactly where oversize chains
        # live, cutting the critical path the batch of one cannot hide
        # (`grid` is accepted for signature uniformity).
        def f(a, b):
            X, info = blocktri.posv(a[None, 0], a[None, 1], b[None],
                                    precision=precision)
            return X[0], (info[0] if robust is not None else jnp.int32(0))

        return f
    if op == "posv_arrowhead":
        # oversize arrowheads run as a batch of one, like posv_blocktri
        # (impl='auto' picks the partitioned driver above
        # PARTITION_MIN_NBLOCKS).  The single route has no extras slot,
        # so the flat (nblocks·b + s, k) solution is assembled HERE —
        # the same response layout the engine's arrowhead sink produces
        # for batched requests.
        def f(a, b):
            nblocks, bs = a.shape[1], a.shape[2]
            F, S, B, Bs = arrowhead.unpack(b[None], nblocks, bs)
            X, Xs, info = arrowhead.posv(a[None, 0], a[None, 1], F, S, B,
                                         Bs, precision=precision)
            flat = jnp.concatenate(
                [X[0].reshape(nblocks * bs, X.shape[-1]), Xs[0]], axis=0)
            return flat, (info[0] if robust is not None else jnp.int32(0))

        return f
    raise ValueError(f"unknown serve op {op!r}")
