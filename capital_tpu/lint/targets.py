"""Flagship program targets for the lint gate (`make lint`).

The sanitizer is only as good as the programs it runs over; these builders
construct the repo's flagship entry points the same way the drivers and
the serve engine do — cholinv, cacqr, and one serve bucket ladder per
op — sized for a compile-only CPU CI pass (the invariants are properties of
the *program*, not of the wall clock; `make audit` already owns the big-N
drift runs).

Serve-bucket targets declare the same donation the engine would
(ServeConfig.donate semantics): the RHS batch for posv, the operand batch
for inv — and nothing for lstsq, whose (m, nrhs) RHS can never alias its
(n, nrhs) solution, which is exactly the donation-honored rule's point.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from capital_tpu.lint.program import ProgramTarget

TARGET_NAMES = ("cholinv", "cacqr", "serve", "batched_small", "serve_sched",
                "serve_traced", "cholinv_fused", "blocktri",
                "blocktri_partitioned", "arrowhead", "update_small",
                "refine", "session")


def _grid():
    from capital_tpu.parallel.topology import Grid

    return Grid.square(c=1, devices=jax.devices()[:1])


def cholinv_target(n: int = 512, dtype=jnp.float32) -> ProgramTarget:
    from capital_tpu.models import cholesky
    from capital_tpu.utils import residual

    grid = _grid()
    cfg = cholesky.CholinvConfig(base_case_dim=cholesky.pick_base_case(n))
    A = residual.spd_operand(n, dtype)

    def step(a):
        R, Rinv = cholesky.factor(grid, a, cfg)
        return R + Rinv

    return ProgramTarget(name=f"cholinv-n{n}", fn=step, args=(A,))


def cacqr_target(m: int = 4096, n: int = 256,
                 dtype=jnp.float32) -> ProgramTarget:
    from capital_tpu.models import cholesky, qr

    grid = _grid()
    bc = cholesky.pick_base_case(n)
    cfg = qr.CacqrConfig(
        cholinv=cholesky.CholinvConfig(base_case_dim=bc),
    )
    A = jax.block_until_ready(
        jax.random.normal(jax.random.key(0), (m, n), dtype=dtype)
    )

    def step(a):
        Q, R = qr.factor(grid, a, cfg)
        return Q.at[: R.shape[0], : R.shape[1]].add(R.astype(Q.dtype))

    return ProgramTarget(name=f"cacqr-m{m}-n{n}", fn=step, args=(A,))


def serve_bucket_targets(
    n: int = 256, rows: int = 1024, nrhs: int = 8, capacity: int = 4,
    dtype=jnp.float32,
) -> list[ProgramTarget]:
    """One target per served op at one bucket shape, mirroring
    serve/engine._get_batched's executables and donation declarations."""
    from capital_tpu.serve import api

    dt = jnp.dtype(dtype)
    a_sq = jax.ShapeDtypeStruct((capacity, n, n), dt)
    b_sq = jax.ShapeDtypeStruct((capacity, n, nrhs), dt)
    a_tall = jax.ShapeDtypeStruct((capacity, rows, n), dt)
    b_tall = jax.ShapeDtypeStruct((capacity, rows, nrhs), dt)
    mk = f"b{capacity}-n{n}"
    return [
        ProgramTarget(
            name=f"serve-posv-{mk}", fn=api.batched("posv"),
            args=(a_sq, b_sq), donate_argnums=(1,),
        ),
        ProgramTarget(
            name=f"serve-lstsq-{mk}-m{rows}", fn=api.batched("lstsq"),
            args=(a_tall, b_tall),  # no donation: (m,nrhs) RHS can't alias
        ),
        ProgramTarget(
            name=f"serve-inv-{mk}", fn=api.batched("inv"),
            args=(a_sq,), donate_argnums=(0,),
        ),
    ]


def batched_small_targets(
    n: int = 64, rows: int = 256, nrhs: int = 4, capacity: int = 8,
    dtype=jnp.float32,
) -> list[ProgramTarget]:
    """Batched-grid small-N bucket programs (ops/batched_small), built the
    way serve/engine._get_batched builds them when ServeConfig.small_n_impl
    routes pallas: the fused posv and lstsq buckets plus the split
    potrf+potrs variant the autotune sweeps against them.

    No donation is declared: the kernels' RHS aliasing lives inside the
    ``pallas_call`` (``input_output_aliases``), which the CPU lint rig's
    interpret mode drops entirely — declaring a jit-level donation here
    would make the donation-honored rule fail for a platform reason, not a
    program one.  ``flops_audited=False`` for the same reason: the kernel
    flops execute inside the interpreted ``pallas_call``, invisible to
    XLA ``cost_analysis``, so the whole-program flops envelope would flag
    the rig rather than the program (ProgramTarget docstring)."""
    from capital_tpu.serve import api

    dt = jnp.dtype(dtype)
    a_sq = jax.ShapeDtypeStruct((capacity, n, n), dt)
    b_sq = jax.ShapeDtypeStruct((capacity, n, nrhs), dt)
    a_tall = jax.ShapeDtypeStruct((capacity, rows, n), dt)
    b_tall = jax.ShapeDtypeStruct((capacity, rows, nrhs), dt)
    mk = f"b{capacity}-n{n}"
    return [
        ProgramTarget(
            name=f"small-posv-{mk}", fn=api.batched("posv", impl="pallas"),
            args=(a_sq, b_sq), flops_audited=False,
        ),
        ProgramTarget(
            name=f"small-posv-split-{mk}",
            fn=api.batched("posv", impl="pallas_split"),
            args=(a_sq, b_sq), flops_audited=False,
        ),
        ProgramTarget(
            name=f"small-lstsq-{mk}-m{rows}",
            fn=api.batched("lstsq", impl="pallas"),
            args=(a_tall, b_tall), flops_audited=False,
        ),
    ]


def blocktri_target(
    nblocks: int = 4, b: int = 16, nrhs: int = 2, capacity: int = 4,
    dtype=jnp.float32,
) -> ProgramTarget:
    """The serve posv_blocktri bucket program (models/blocktri through
    api.batched, the executable engine._get_batched compiles): one fused
    factor+forward scan under ``BT::factor`` feeding the backward sweep
    under ``BT::solve`` — both phase tags under the phase-coverage rule,
    and the scan-carried pallas steps under cache-key hygiene.  Forced
    impl='pallas' so the lint sees the kernel route serve routes on TPU
    regardless of the CPU rig's default_impl answer.  ``flops_audited=
    False``: the chain flops execute inside interpreted ``pallas_call``
    scan bodies on the CPU rig, invisible to XLA ``cost_analysis`` (same
    reasoning as batched_small_targets).  No donation — the engine
    donates nothing for posv_blocktri (the packed (2, nblocks, b, b)
    operand can't alias the (nblocks, b, nrhs) solution shape-wise, and
    the RHS aliasing lives inside the kernels)."""
    from capital_tpu.serve import api

    dt = jnp.dtype(dtype)
    a_sds = jax.ShapeDtypeStruct((capacity, 2, nblocks, b, b), dt)
    b_sds = jax.ShapeDtypeStruct((capacity, nblocks, b, nrhs), dt)
    return ProgramTarget(
        name=f"serve-blocktri-b{capacity}-nb{nblocks}-bs{b}",
        fn=api.batched("posv_blocktri", impl="pallas"),
        args=(a_sds, b_sds), flops_audited=False,
    )


def blocktri_partitioned_target(
    nblocks: int = 8, b: int = 8, nrhs: int = 2, capacity: int = 2,
    partitions: int = 2, dtype=jnp.float32,
) -> ProgramTarget:
    """The partitioned-bucket serve posv_blocktri program (ServeConfig.
    blocktri_impl='partitioned' through api.batched — the executable an
    engine configured for the Spike driver compiles): the concurrent
    interior factor+widened solve and the parallel back-substitution
    under ``BT::partition``, the interface Schur assembly + reduced
    P-block chain under ``BT::reduce`` — both new phase tags under the
    phase-coverage rule, alongside the sequential target's ``BT::factor``
    / ``BT::solve`` which the reduced chain still emits.  Forced
    impl='pallas' so the widened interior scans ride the kernel route
    serve routes on TPU (partition_inner maps from the kernel flavor);
    ``flops_audited=False`` for the same interpret-rig reason as
    blocktri_target.  No donation (same shape argument)."""
    from capital_tpu.serve import api

    dt = jnp.dtype(dtype)
    a_sds = jax.ShapeDtypeStruct((capacity, 2, nblocks, b, b), dt)
    b_sds = jax.ShapeDtypeStruct((capacity, nblocks, b, nrhs), dt)
    return ProgramTarget(
        name=(f"serve-blocktri-par-b{capacity}-nb{nblocks}-bs{b}"
              f"-p{partitions}"),
        fn=api.batched("posv_blocktri", impl="pallas",
                       blocktri_impl="partitioned",
                       blocktri_partitions=partitions),
        args=(a_sds, b_sds), flops_audited=False,
    )


def arrowhead_target(
    nblocks: int = 4, b: int = 16, s: int = 4, nrhs: int = 2,
    capacity: int = 4, dtype=jnp.float32,
) -> ProgramTarget:
    """The serve posv_arrowhead bucket program (models/arrowhead through
    api.batched, the executable engine._get_batched compiles): the
    widened chain solve rides blocktri's ``BT::factor`` / ``BT::solve``
    scans unchanged, the Schur completion + corner factor lands under
    ``AH::schur`` and the border back-substitution under ``AH::border``
    — all four phase tags under the phase-coverage rule, and the packed
    operand unpack under cache-key hygiene (geometry comes from static
    shapes, never from traced values).  Forced impl='pallas' so the
    chain scans ride the kernel route serve routes on TPU regardless of
    the CPU rig's default_impl answer.  ``flops_audited=False``: the
    chain half executes inside interpreted ``pallas_call`` scan bodies
    on the CPU rig, invisible to XLA ``cost_analysis`` — the AH::*
    einsums alone would always undershoot the whole-program envelope
    (same reasoning as blocktri_target).  No donation — the engine
    donates nothing for posv_arrowhead: the packed (n_T + s, s + nrhs)
    tail feeds BOTH solve outputs (chain X and corner Xs), so neither
    output can safely alias it."""
    from capital_tpu.serve import api

    dt = jnp.dtype(dtype)
    a_sds = jax.ShapeDtypeStruct((capacity, 2, nblocks, b, b), dt)
    b_sds = jax.ShapeDtypeStruct((capacity, nblocks * b + s, s + nrhs), dt)
    return ProgramTarget(
        name=f"serve-arrowhead-b{capacity}-nb{nblocks}-bs{b}-s{s}",
        fn=api.batched("posv_arrowhead", impl="pallas"),
        args=(a_sds, b_sds), flops_audited=False,
    )


def update_small_target(
    n: int = 64, k: int = 4, capacity: int = 8, dtype=jnp.float32,
) -> ProgramTarget:
    """The online factor-maintenance bucket program (ops/update_small
    through api.batched, the executables serve/engine compiles for
    chol_update / chol_downdate traffic): one rank-k update under
    ``UP::update`` chained into the downdate back under ``UP::downdate``
    — both phase tags under the phase-coverage rule, and the masked
    hyperbolic-rotation sweep's pallas_call under cache-key hygiene.
    Forced impl='pallas' (n=64 is inside the small-N envelope) so the
    lint sees the kernel route serve routes on TPU regardless of the CPU
    rig's resolution.  ``flops_audited=False``: the sweep flops execute
    inside the interpreted ``pallas_call`` on the CPU rig, invisible to
    XLA ``cost_analysis`` (same reasoning as batched_small_targets).  No
    jit-level donation for the same interpret-rig reason — the engine's
    donate_argnums=(0,) on the R operand is honored only by the compiled
    TPU route."""
    from capital_tpu.serve import api

    dt = jnp.dtype(dtype)
    r_sds = jax.ShapeDtypeStruct((capacity, n, n), dt)
    v_sds = jax.ShapeDtypeStruct((capacity, n, k), dt)
    up = api.batched("chol_update", impl="pallas")
    dn = api.batched("chol_downdate", impl="pallas")

    def step(r, v):
        R1, i1 = up(r, v)
        R2, i2 = dn(R1, v)
        return R2, jnp.maximum(i1, i2)

    return ProgramTarget(
        name=f"update-small-b{capacity}-n{n}-k{k}", fn=step,
        args=(r_sds, v_sds), flops_audited=False,
    )


def refine_target(
    n: int = 64, nrhs: int = 4, capacity: int = 4, dtype=jnp.bfloat16,
) -> ProgramTarget:
    """The accuracy_tier='guaranteed' bucket program (robust/refine through
    api.batched — the 5-output executable serve/engine compiles for tiered
    posv traffic): low-dtype factor + upgraded-dtype correction sweeps
    under ``IR::residual`` / ``IR::correct`` — both phase tags under the
    phase-coverage rule.

    bf16 inputs on purpose: the guaranteed plan for bf16 factors in bf16
    and corrects in f32, so the WHOLE mixed-precision ladder stays below
    f64 — a program whose jaxpr emits zero float64 equations, which is
    exactly what rule_dtype_drift then proves (the rule exempts programs
    with wide INPUTS, so a narrow-input tier program is the only shape
    that makes the no-f64-leak claim checkable).  ``flops_audited=False``:
    the refinement loop's sweep count is data-dependent (lax.while_loop),
    while the phase registry prices exactly one sweep — the whole-program
    flops envelope would flag the design, not a bug (measured sweep counts
    live in serve stats' refine block instead).  No donation — the tiered
    program keeps both operands live across every sweep's residual."""
    from capital_tpu.serve import api

    dt = jnp.dtype(dtype)
    a_sds = jax.ShapeDtypeStruct((capacity, n, n), dt)
    b_sds = jax.ShapeDtypeStruct((capacity, n, nrhs), dt)

    solve = api.batched("posv", tier="guaranteed")

    def step(a, b):
        X, iters, converged, resid, info = solve(a, b)
        return X, iters, converged, resid, info

    return ProgramTarget(
        name=f"refine-posv-b{capacity}-n{n}", fn=step,
        args=(a_sds, b_sds), flops_audited=False,
    )


def session_targets(
    nblocks: int = 4, b: int = 16, nrhs: int = 2, capacity: int = 4,
    dtype=jnp.float32,
) -> list[ProgramTarget]:
    """The streaming-session bucket programs (serve/sessions protocol
    through api.batched — the executables engine._submit_session routes
    to; docs/SERVING.md 'Streaming sessions'): the shared open/append
    chain-extension program under ``SS::extend`` and the resident-factor
    sweep program under ``SS::solve`` — both phase tags under the
    phase-coverage rule.  Cache-key hygiene is the protocol's load-
    bearing claim: session ids resolve to resident factors HOST-side, so
    the programs see only bucket-shaped arrays — the 4-stack
    (capacity, 4, nblocks, b, b) = [D; C; L; Wt] solve packing and the
    (capacity, 2, nblocks, b, b) extend packing — and session churn can
    never recompile anything.  Forced impl='pallas' so the interior
    chain scans ride the kernel route serve routes on TPU;
    ``flops_audited=False`` for the same interpret-rig reason as
    blocktri_target.  No donation — the engine's no-donate rule for
    session ops: the landed (L, Wt) stack is concatenated onto the
    RESIDENT chain at the sink, so the operand must survive dispatch."""
    from capital_tpu.serve import api

    dt = jnp.dtype(dtype)
    a2_sds = jax.ShapeDtypeStruct((capacity, 2, nblocks, b, b), dt)
    carry_sds = jax.ShapeDtypeStruct((capacity, b, b), dt)
    a4_sds = jax.ShapeDtypeStruct((capacity, 4, nblocks, b, b), dt)
    b_sds = jax.ShapeDtypeStruct((capacity, nblocks, b, nrhs), dt)
    mk = f"b{capacity}-nb{nblocks}-bs{b}"
    return [
        ProgramTarget(
            name=f"serve-session-extend-{mk}",
            fn=api.batched("session_extend", impl="pallas"),
            args=(a2_sds, carry_sds), flops_audited=False,
        ),
        ProgramTarget(
            name=f"serve-session-solve-{mk}",
            fn=api.batched("session_solve", impl="pallas"),
            args=(a4_sds, b_sds), flops_audited=False,
        ),
    ]


def cholinv_fused_target(n: int = 512, dtype=jnp.float32) -> ProgramTarget:
    """The fused-recursion-tail cholinv program (CholinvConfig.
    tail_fuse_depth > 0): n=512 with bc=128 and depth 2 fuses the whole
    tree into ops/pallas_tpu.fused_tail, putting the ``CI::tail_fused``
    phase tag under the phase-coverage rule and the fused pallas_call's
    windowed-output aliasing under cache-key hygiene.  ``flops_audited=
    False`` because the fused factor+solve sweeps execute inside the
    interpreted ``pallas_call`` on the CPU lint rig, invisible to XLA
    ``cost_analysis`` (same reasoning as batched_small_targets)."""
    from capital_tpu.models import cholesky
    from capital_tpu.utils import residual

    grid = _grid()
    cfg = cholesky.CholinvConfig(
        base_case_dim=128, mode="pallas", tail_fuse_depth=2,
    )
    A = residual.spd_operand(n, dtype)

    def step(a):
        R, Rinv = cholesky.factor(grid, a, cfg)
        return R + Rinv

    return ProgramTarget(
        name=f"cholinv-fused-n{n}", fn=step, args=(A,), flops_audited=False,
    )


def serve_sched_target(
    n: int = 64, nrhs: int = 4, capacity: int = 4, dtype=jnp.bfloat16,
) -> ProgramTarget:
    """The continuous scheduler's staged-dispatch program (serve/scheduler
    + executor; docs/SERVING.md): operand normalization under ``SV::stage``
    — the in-program half of the host->device staging the engine performs
    at submit — feeding one batched bucket dispatch under ``SV::dispatch``,
    the boundary the queue-wait/device latency split is measured across.

    bf16 inputs upcast to f32 at the stage boundary (a real convert
    equation, so the SV::stage tag survives into the jaxpr/HLO name
    stacks the sanitizer and xla_audit attribute by); n=64 keeps the
    dispatch on the batched-grid pallas route, so ``flops_audited=False``
    and no jit-level donation for the same interpret-rig reasons as the
    batched_small targets."""
    from capital_tpu.serve import api
    from capital_tpu.utils import tracing

    dt = jnp.dtype(dtype)
    a_sds = jax.ShapeDtypeStruct((capacity, n, n), dt)
    b_sds = jax.ShapeDtypeStruct((capacity, n, nrhs), dt)
    solve = api.batched("posv")

    def step(a, b):
        with tracing.scope("SV::stage"):
            a32 = a.astype(jnp.float32)
            b32 = b.astype(jnp.float32)
            # the identity-tail symmetrization pad_operands applies on the
            # host, in-program form: keeps the staged operand SPD under
            # the bf16 round-trip
            a32 = 0.5 * (a32 + jnp.swapaxes(a32, -1, -2))
        with tracing.scope("SV::dispatch"):
            X, info = solve(a32, b32)
        return X.astype(dt), info

    return ProgramTarget(
        name=f"serve-sched-posv-b{capacity}-n{n}", fn=step,
        args=(a_sds, b_sds), flops_audited=False,
    )


def serve_traced_target(
    n: int = 64, nrhs: int = 4, capacity: int = 4, dtype=jnp.float32,
) -> ProgramTarget:
    """The traced serve dispatch program: the serve_sched stage/dispatch
    pair with the per-request span stamping the engine performs around it
    (obs/spans.RequestTrace.extend) executed inline, exactly where the
    serve path stamps — before staging, at executable resolution, at
    dispatch issue.

    The property this target pins is the tracing tentpole's core claim:
    span stamps are a pure HOST-side observer.  They run at trace time,
    never become program equations, and above all never become host
    callbacks — ``rule_no_host_sync`` proves the traced program carries
    zero ``pure_callback``/``io_callback``/infeed primitives, because a
    span stamp that leaked into the program as a callback would serialize
    the very device stream it claims to observe.  The stamps must also
    not break phase coverage: ``SV::stage`` / ``SV::dispatch`` still name
    every flop.  ``flops_audited=False`` and no donation for the same
    interpret-rig reasons as serve_sched_target."""
    from capital_tpu.obs import spans
    from capital_tpu.serve import api
    from capital_tpu.utils import tracing

    dt = jnp.dtype(dtype)
    a_sds = jax.ShapeDtypeStruct((capacity, n, n), dt)
    b_sds = jax.ShapeDtypeStruct((capacity, n, nrhs), dt)
    solve = api.batched("posv")
    log = spans.TraceLog()

    def step(a, b):
        tr = log.start(0, "posv", spans.now())
        with tracing.scope("SV::stage"):
            # pad_operands' identity-tail symmetrization, in-program form
            a_sym = 0.5 * (a + jnp.swapaxes(a, -1, -2))
        tr.extend("admit")
        tr.extend("cache_lookup")
        with tracing.scope("SV::dispatch"):
            X, info = solve(a_sym, b)
        tr.extend("batch_form")
        return X, info

    return ProgramTarget(
        name=f"serve-traced-posv-b{capacity}-n{n}", fn=step,
        args=(a_sds, b_sds), flops_audited=False,
    )


def flagship_targets(names=None) -> list[ProgramTarget]:
    """The `make lint` program-pass set.  `names` filters to a subset of
    TARGET_NAMES (all three families by default)."""
    names = tuple(names) if names else TARGET_NAMES
    out: list[ProgramTarget] = []
    for name in names:
        if name == "cholinv":
            out.append(cholinv_target())
        elif name == "cacqr":
            out.append(cacqr_target())
        elif name == "serve":
            out.extend(serve_bucket_targets())
        elif name == "batched_small":
            out.extend(batched_small_targets())
        elif name == "serve_sched":
            out.append(serve_sched_target())
        elif name == "serve_traced":
            out.append(serve_traced_target())
        elif name == "cholinv_fused":
            out.append(cholinv_fused_target())
        elif name == "blocktri":
            out.append(blocktri_target())
        elif name == "blocktri_partitioned":
            out.append(blocktri_partitioned_target())
        elif name == "arrowhead":
            out.append(arrowhead_target())
        elif name == "update_small":
            out.append(update_small_target())
        elif name == "refine":
            out.append(refine_target())
        elif name == "session":
            out.extend(session_targets())
        else:
            raise ValueError(
                f"unknown lint target {name!r}; expected one of {TARGET_NAMES}"
            )
    return out
