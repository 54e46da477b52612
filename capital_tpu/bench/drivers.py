"""Per-algorithm benchmark drivers — the reference's bench/ executables.

One driver per reference binary, same knob surface expressed as named flags
instead of positional argv (SURVEY §5.6: the reference's argv + template
policies collapse to runtime config here):

  cholinv     <- bench/cholesky/cholinv.cpp  (num_rows, rep_div, complete_inv,
                 split, bcMultiplier, layout, num_chunks, num_iter)
  cacqr       <- bench/qr/cacqr.cpp          (variant, m, n, rep factors, ...)
  summa_gemm  <- bench/matmult/summa_gemm.cpp (M, N, K, c, ...)
  rectri      <- bench/inverse/rectri.cpp
  newton      <- bench/inverse/newton.cpp    (bit-rotted upstream; functional here)
  spd_inverse <- the BASELINE.md "SPD inverse via Cholesky" config

Each run prints one JSON line (harness.report) and, with --validate, appends
the residual gates the reference keeps commented out in its drivers
(bench/cholesky/cholinv.cpp:61-66, bench/qr/cacqr.cpp:64-71) — enabled ones
fail the process on a blown tolerance, making every bench double as an
integration test.

Usage: python -m capital_tpu.bench <driver> [--n 4096 ...]
"""

from __future__ import annotations

import argparse
import logging
import sys

import jax
import jax.numpy as jnp

from capital_tpu.bench import harness
from capital_tpu.models import cholesky, inverse, qr
from capital_tpu.parallel import summa
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust.config import RobustConfig
from capital_tpu.serve.stats import percentiles
from capital_tpu.utils import residual, tracing
from capital_tpu.utils.config import PLATFORM_HELP

_log = logging.getLogger(__name__)


#: Residual gate values of the current driver invocation, keyed by gate
#: name — snapshotted and cleared by _ledger_append at the end of each
#: driver, so --validate runs carry their numerics in the same ledger
#: record as the timing and the audit (suite runs several drivers in one
#: process; values must not bleed across rows).
_RESIDUALS: dict[str, float] = {}


def _gate(name: str, value: float, tol: float) -> None:
    _RESIDUALS[name] = value
    ok = value < tol
    print(f"# validate {name} = {value:.3e} (tol {tol:.0e}) {'OK' if ok else 'FAIL'}")
    if not ok:
        sys.exit(f"validation failed: {name} = {value:.3e} >= {tol:.0e}")


def _ledger_append(
    args, rec: dict, *, name: str, grid: Grid, cfg=None, step=None,
    operand=None, dtype=None, extra_record: dict | None = None,
) -> None:
    """Append one unified ledger record for a finished driver run (opt-in
    via --ledger PATH; no-op otherwise).  `name` is the driver's own name —
    args.driver says "suite" for suite rows.

    The record carries the measured JSON line plus, when the driver can
    hand over its (step, operand), the Recorder model decomposition and the
    compiled-program audit + drift report — the same facts
    ``python -m capital_tpu.obs audit`` derives, attached to a real
    measurement.  Model/audit capture is best-effort: a config whose
    re-lowering fails (e.g. a mode unsupported on this backend) still gets
    its manifest + measurement + residuals recorded, with the error noted,
    rather than losing the run."""
    residuals = dict(_RESIDUALS)
    _RESIDUALS.clear()
    path = getattr(args, "ledger", None)
    if not path:
        return
    from capital_tpu.obs import ledger, xla_audit

    model = audit_d = drift_d = None
    err = None
    if step is not None and operand is not None:
        op_args = operand if isinstance(operand, tuple) else (operand,)
        try:
            recd = xla_audit.trace_model(step, *op_args)
            audit = xla_audit.audit(step, *op_args)
            rep = xla_audit.drift(audit, recd)
            model = ledger.model_costs(recd, dtype=dtype)
            audit_d = audit.asdict()
            drift_d = rep.asdict()
        except Exception as e:  # broad on purpose: ledger must not fail the run
            err = f"{type(e).__name__}: {e}"
            _log.warning("ledger audit capture failed: %s", err)
    row = ledger.record(
        f"bench:{name}",
        ledger.manifest(grid=grid, dtype=dtype, config=cfg),
        model=model,
        audit=audit_d,
        drift=drift_d,
        measured=rec,
        residuals=residuals or None,
        **({"audit_error": err} if err else {}),
        **(extra_record or {}),
    )
    ledger.append(path, row)


def _hbm_bytes() -> float:
    """Per-chip HBM capacity: the runtime's own figure when it reports one
    (memory_stats()['bytes_limit']), else the device kind's published
    capacity (tracing.SPECS — an unknown kind is an error there)."""
    dev = jax.devices()[0]
    limit = float((dev.memory_stats() or {}).get("bytes_limit", 0))
    return limit if limit > 0 else tracing.device_spec(dev).hbm_bytes


def _tall_hash(m: int, n: int, dtype, salt) -> jnp.ndarray:
    """Deterministic full-rank tall operand as ONE fused elementwise
    program (the cacqr analog of the benchmark's spd_hash): splitmix32 of
    (i, j, salt) mapped to U[-1, 1].  A tall matrix of i.i.d.-ish uniform
    entries has gram ≈ (m/3)(I + O(sqrt(n/m))) — comfortably full-rank for
    CholeskyQR2 at the bench's m >> n shapes."""
    from jax import lax

    r = lax.broadcasted_iota(jnp.uint32, (m, n), 0)
    c = lax.broadcasted_iota(jnp.uint32, (m, n), 1)
    h = r * jnp.uint32(0x9E3779B1) ^ c * jnp.uint32(0x85EBCA77)
    h = h + jnp.asarray(salt).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    u = h.astype(jnp.float32) * jnp.float32(2.0**-32)
    return (2.0 * u - 1.0).astype(dtype)


def _knobs(args) -> dict:
    """Topology knobs echoed into every JSON record so sweep rows over
    --layout/--chunks stay attributable to the config that produced them."""
    return dict(layout=getattr(args, "layout", 0), chunks=getattr(args, "chunks", 0))


def _timed(args, step, operand, coupling: str = "full", loop=None) -> tuple[float, dict]:
    """timed_loop plus the suite's drift guard (VERDICT r2 weak #4): with
    args.device_check, the device-counter op total of the same in-jit loop
    is measured (drift-immune), a wall that lands BELOW it is re-measured
    (favorable-drift artifact — seen: a 19.0 ms suite row against a 24.7 ms
    device total), and if it still undercuts after retries the row reports
    the device floor as its time with the raw wall kept alongside.  The
    returned extras (device_ms, ...) ride the JSON record."""
    # ONE jitted loop shared by the wall measurement, the device floor, and
    # any retries — each _make_loop product is a fresh jit cache entry, and
    # these fori_loop programs take seconds-to-minutes to trace+compile.
    # Callers with operands _make_loop cannot carry (the trsm driver's
    # (L, B) tuple) pass their own loop of the same shape.
    loop = loop or harness._make_loop(step, coupling)
    samples: list[float] = []
    t = harness.timed_loop(
        step, operand, iters=args.iters, coupling=coupling, loop=loop,
        samples_out=samples,
    )
    extra: dict = {}
    if len(samples) >= 2:
        # per-iteration wall spread (paired-delta samples at the resolved
        # trip count) through the shared quantile helper — the same
        # p50/p95/p99 shape serve/stats.py reports, so bench rows and
        # request_stats records read on one scale
        extra["wall_ms"] = {
            k: round(v * 1e3, 3)
            for k, v in percentiles(samples).items()
        }
    if getattr(args, "device_check", False):
        dms = harness.device_ms_per_iter(
            step, operand, iters=max(3, args.iters), coupling=coupling, loop=loop
        )
        if dms > 0.0:
            extra["device_ms"] = round(dms, 3)
            tries = 0
            while t * 1e3 < dms and tries < 2:
                t = harness.timed_loop(
                    step, operand, iters=args.iters, coupling=coupling, loop=loop
                )
                tries += 1
            if t * 1e3 < dms:
                extra["wall_ms_below_floor"] = round(t * 1e3, 3)
                t = dms / 1e3
    return t, extra


def _precision(args, dtype) -> str | None:
    """The --precision override ('default' = the context default), else
    summa.default_precision of the dtype — the override bounds the f32
    'high' (3-pass) XLA-path gap the default 6-pass 'highest' leaves
    unmeasured (VERDICT r2 weak #7)."""
    if getattr(args, "precision", None):
        return None if args.precision == "default" else args.precision
    return summa.default_precision(dtype)


def _grid(args) -> Grid:
    """The largest d x d x c grid the (--devices-limited) device set
    supports, preferring --c."""
    dev = jax.devices()
    if args.devices:
        dev = dev[: args.devices]
    return Grid.largest_square(
        dev, c=args.c, layout=getattr(args, "layout", 0),
        num_chunks=getattr(args, "chunks", 0),
    )


# --------------------------------------------------------------------------


def cholinv(args) -> dict:
    grid = _grid(args)
    mode = summa.resolve_mode(args.mode, grid)
    dtype = jnp.dtype(args.dtype)
    bc = cholesky.pick_base_case(args.n, args.bc)
    cfg = cholesky.CholinvConfig(
        complete_inv=not args.no_complete_inv,
        split=args.split,
        base_case_dim=bc,
        mode=mode,
        balance=getattr(args, "balance", "block"),
        precision=_precision(args, dtype),
    )
    A = residual.spd_operand(args.n, dtype)

    def step(a):
        R, Rinv = cholesky.factor(grid, a, cfg)
        return R + Rinv

    t, extra = _timed(args, step, A)
    if getattr(args, "phase_attr", False):
        # opt-in wall attribution (bench.trace.phase_attribution): the
        # bubble_frac rides the report line next to the TFLOP/s number and
        # the phase split rides the ledger record for obs trace-report
        from capital_tpu.bench import trace as trace_mod

        arun = trace_mod._cholinv_run(
            args.n, dtype, bc, args.iters, cfg.precision, mode=mode
        )
        ps, bubble, _wall = trace_mod.phase_attribution(arun, args.iters)
        extra = {
            **extra,
            "bubble_frac": round(bubble, 4),
            "phase_seconds": {k: round(v, 9) for k, v in ps.items()},
        }
    flops = 2.0 * args.n**3 / 3.0  # factor n³/3 + triangular inverse n³/3
    rec = harness.report(
        "cholinv_tflops", t, flops, dtype, n=args.n, grid=repr(grid), bc=bc,
        mode=mode, balance=cfg.balance, **_knobs(args), **extra,
    )
    if args.validate:
        R, Rinv = jax.jit(lambda a: cholesky.factor(grid, a, cfg))(A)
        tol = residual.tolerance(dtype)
        _gate("cholesky_residual", float(residual.cholesky_residual(A, R)), tol)
        if cfg.complete_inv:
            _gate(
                "inverse_residual",
                float(residual.cholesky_inverse_residual(R, Rinv)),
                tol,
            )
    _ledger_append(
        args, rec, name="cholinv", grid=grid, cfg=cfg, step=step, operand=A,
        dtype=dtype,
    )
    return rec


def cacqr(args) -> dict:
    # the nested config factors the n x n GRAM — a cholinv-family workload,
    # so the auto-pick follows the cholinv crossovers at the gram size
    bc = cholesky.pick_base_case(args.n, args.bc)
    # tall-skinny topology: the reference uses a tunable rect grid
    # (topology.h:16-65); the 1d/auto regimes want the whole mesh on the
    # long axis (Grid.flat), 'dist' wants a square face
    dev = jax.devices()
    if args.devices:
        dev = dev[: args.devices]
    if args.regime == "dist" or len(dev) == 1:
        grid = _grid(args)
        applied_knobs = _knobs(args)
    else:
        grid = Grid.flat(devices=dev)  # natural order, unchunked
        applied_knobs = dict(layout=0, chunks=0)
    dtype = jnp.dtype(args.dtype)
    mode = summa.resolve_mode(args.mode, grid)
    precision = _precision(args, dtype)
    robust = getattr(args, "robust", False)
    cfg = qr.CacqrConfig(
        num_iter=args.variant,
        regime=args.regime,
        mode=mode,
        cholinv=cholesky.CholinvConfig(
            base_case_dim=bc, mode=mode, precision=precision
        ),
        precision=precision,
        fused_g=getattr(args, "fused_g", 0),
        robust=RobustConfig() if robust else None,
    )
    # One-shot regen protocol when the A-carry would not fit: the standard
    # loop keeps FOUR Q-sized buffers at peak (A carry, Q1, Q, and the
    # carry's while-loop double buffer — measured "Used 16.01G of 15.75G"
    # at the true 2M x 1024 BASELINE shape); regenerating A per iteration
    # from a fused hash (scalar loop carry) drops the peak to ~2 Q-sized
    # buffers, putting the 8-rank BASELINE shape on ONE chip.  Requires
    # the element-coupling eligibility (harness.pallas_coupled) — the one-shot
    # consume is a one-element read.
    elem_ok = harness.pallas_coupled(grid, args.m, args.n, mode, dtype)
    # --robust measures the guarded path (status scalars in the carry), which
    # the scalar one-shot consume would dead-code-eliminate
    oneshot = (
        elem_ok
        and not robust
        and grid.num_devices == 1
        and 4.1 * args.m * args.n * dtype.itemsize > _hbm_bytes()
    )
    if oneshot:
        def gen(i):
            return _tall_hash(args.m, args.n, dtype, i)

        def scalar_step(a):
            Q, R = qr.factor(grid, a, cfg)
            return (Q[0, 0] + R[0, 0]).astype(jnp.float32)

        t, t_regen, extra = harness.timed_oneshot(
            gen, scalar_step, iters=args.iters,
            device_check=getattr(args, "device_check", False),
        )
        extra = {"oneshot": True, "regen_seconds": round(t_regen, 5), **extra}
        A = None
        # the ledger audit lowers against an abstract operand — a concrete
        # A is exactly what the one-shot protocol exists to avoid holding
        step = scalar_step
        audit_operand = jax.ShapeDtypeStruct((args.m, args.n), dtype)
    else:
        # generate on device directly at the target dtype (an f32 staging
        # buffer alone is 8GB at the 2M x 1024 BASELINE shape)
        A = jax.block_until_ready(
            jax.random.normal(jax.random.key(0), (args.m, args.n), dtype=dtype)
        )

        def step(a):
            res = qr.factor(grid, a, cfg)
            Q, R = res[0], res[1]
            # fold R into the tall carry via a slice-add so the carry keeps
            # A's shape while both outputs stay live (the carry is
            # Q-shaped, so the loop factors its own running output)
            out = Q.at[: R.shape[0], : R.shape[1]].add(R.astype(Q.dtype))
            if cfg.robust is not None:
                # keep the guard live in the measured program: the shift is
                # data-dependent and exactly 0 on a healthy factorization
                ri = res[2]
                out = out.at[0, 0].add(
                    (ri.sigma * ri.breakdown.astype(ri.sigma.dtype)).astype(
                        out.dtype
                    )
                )
            return out

        # element carry only when the factor's outputs ride un-narrowable
        # ops (saves a Q-sized full-add, ~5 ms/iter at 1M x 1024); the
        # predicate lives in qr next to the kernel gating it must track
        coupling = "elem" if elem_ok else "full"
        t, extra = _timed(args, step, A, coupling=coupling)
        audit_operand = A
    # useful flops per sweep: gram mn² + Q·R⁻¹ mn²; CQR2 doubles the sweeps
    flops = 2.0 * args.m * args.n**2 * cfg.num_iter
    robust_d = None
    if cfg.robust is not None:
        # one extra factorization of the bench operand to read the status
        # scalars out (the timed loop only keeps them live, not inspectable)
        ri = jax.jit(lambda a: qr.factor(grid, a, cfg)[2])(A)
        robust_d = {
            "info": int(ri.info),
            "breakdown": int(ri.breakdown),
            "shifted": int(ri.shifted),
            "sigma": float(ri.sigma),
            "escalated": int(ri.escalated),
            "ortho": float(ri.ortho),
        }
    rec = harness.report(
        "cacqr_tflops", t, flops, dtype, m=args.m, n=args.n,
        variant=args.variant, grid=repr(grid), mode=mode, **applied_knobs,
        **extra, **({"robust": robust_d} if robust_d else {}),
    )
    if args.validate:
        if A is None:  # one-shot runs: validate one regenerated instance
            A = jax.block_until_ready(
                jax.jit(lambda: _tall_hash(args.m, args.n, dtype, 0))()
            )
        res = jax.jit(lambda a: qr.factor(grid, a, cfg))(A)
        Q, R = res[0], res[1]
        tol = residual.tolerance(dtype)
        _gate("qr_orthogonality", float(residual.qr_orthogonality(Q)), tol)
        # row-blocked accumulation: the dense residual's m x n f32
        # temporaries OOM the 2M x 1024 shape whose factorization fits
        _gate(
            "qr_residual",
            float(jax.jit(residual.qr_residual_blocked)(A, Q, R)),
            tol,
        )
    extra_record = None
    if robust_d is not None:
        extra_record = {"robust": robust_d}
        if robust_d["breakdown"]:
            status = "recovered" if robust_d["info"] == 0 else "breakdown"
            extra_record["event"] = {"status": status}
    _ledger_append(
        args, rec, name="cacqr", grid=grid, cfg=cfg, step=step,
        operand=audit_operand, dtype=dtype, extra_record=extra_record,
    )
    return rec


def summa_gemm(args) -> dict:
    grid = _grid(args)
    mode = summa.resolve_mode(args.mode, grid)
    dtype = jnp.dtype(args.dtype)
    A = jax.random.normal(jax.random.key(0), (args.m, args.k), dtype)
    B = jax.random.normal(jax.random.key(1), (args.k, args.n), dtype)
    gargs = summa.GemmArgs(precision=_precision(args, dtype))

    def step(a):
        return summa.gemm(grid, a, B, args=gargs, mode=mode)

    # carry must match operand shape: square M=N=K benches only need A
    if not (args.m == args.n == args.k):
        raise SystemExit("summa_gemm bench uses square M=N=K")
    t, extra = _timed(args, step, A)
    rec = harness.report(
        "summa_gemm_tflops", t, 2.0 * args.m * args.n * args.k, dtype,
        m=args.m, n=args.n, k=args.k, grid=repr(grid), mode=mode,
        **_knobs(args), **extra,
    )
    if args.validate:
        C = jax.jit(lambda a: summa.gemm(grid, a, B, args=gargs, mode=mode))(A)
        ref = jnp.matmul(A.astype(jnp.float32), B.astype(jnp.float32))
        err = float(residual.rel_fro(C.astype(jnp.float32) - ref, ref))
        _gate("gemm_residual", err, residual.tolerance(dtype))
    _ledger_append(
        args, rec, name="summa_gemm", grid=grid, cfg=gargs, step=step,
        operand=A, dtype=dtype,
    )
    return rec


def rectri(args) -> dict:
    bc = cholesky.pick_base_case(args.n, args.bc, cholinv_family=False)
    grid = _grid(args)
    mode = summa.resolve_mode(args.mode, grid)
    dtype = jnp.dtype(args.dtype)
    L = residual.tri_operand(args.n, dtype)
    extra_cfg = {} if args.batch_below < 0 else {"batch_below": args.batch_below}
    cfg = inverse.RectriConfig(
        base_case_dim=bc, mode=mode,
        precision=_precision(args, dtype), **extra_cfg,
    )

    def step(a):
        return inverse.rectri(grid, a, "L", cfg)

    t, extra = _timed(args, step, L)
    rec = harness.report(
        "rectri_tflops", t, args.n**3 / 3.0, dtype, n=args.n, grid=repr(grid),
        mode=mode, **_knobs(args), **extra,
    )
    if args.validate:
        Linv = jax.jit(lambda a: inverse.rectri(grid, a, "L", cfg))(L)
        # row-blocked gate: the dense I − L·L⁻¹ is an n² f32 buffer that
        # OOMs one v5e at n=49152 (falls back to dense for small n)
        _gate(
            "trtri_residual",
            float(jax.jit(residual.inverse_residual_blocked)(L, Linv)),
            residual.tolerance(dtype),
        )
    _ledger_append(
        args, rec, name="rectri", grid=grid, cfg=cfg, step=step, operand=L,
        dtype=dtype,
    )
    return rec


def newton(args) -> dict:
    grid = _grid(args)
    # xla mode regardless of 'auto': Newton is two dense gemms per step,
    # where the pallas path adds nothing (gemm falls through to xla anyway)
    mode = args.mode if args.mode != "auto" else "xla"
    dtype = jnp.dtype(args.dtype)
    A = residual.spd_operand(args.n, dtype)
    cfg = inverse.NewtonConfig(
        max_iter=args.newton_iters, mode=mode,
        precision=_precision(args, dtype),
    )

    def step(a):
        X, _ = inverse.newton(grid, a, cfg)
        return X

    t, extra = _timed(args, step, A)
    # Executed flops, not the budget: the while_loop exits early on
    # convergence (often ~12 of 30 budgeted steps), so scaling by max_iter
    # would inflate TF/s ~2.5x.  Count the actual data-dependent iteration
    # count — one init gemm (A@X0) plus 2 gemms per executed step, 2n³ each.
    # One extra inversion serves both the count and the --validate gate.
    Ainv, it = jax.jit(lambda a: inverse.newton(grid, a, cfg))(A)
    newton_iters = int(it)
    flops = 2.0 * args.n**3 * (2.0 * newton_iters + 1.0)
    rec = harness.report(
        "newton_tflops", t, flops, dtype, n=args.n, grid=repr(grid),
        iters_executed=newton_iters, max_iters=args.newton_iters, mode=mode,
        **_knobs(args), **extra,
    )
    if args.validate:
        _gate(
            "newton_residual",
            float(residual.inverse_residual(A, Ainv)),
            10 * residual.tolerance(dtype),
        )
    _ledger_append(
        args, rec, name="newton", grid=grid, cfg=cfg, step=step, operand=A,
        dtype=dtype,
    )
    return rec


def spd_inverse(args) -> dict:
    bc = cholesky.pick_base_case(args.n, args.bc)
    grid = _grid(args)
    mode = summa.resolve_mode(args.mode, grid)
    dtype = jnp.dtype(args.dtype)
    cfg = cholesky.CholinvConfig(
        base_case_dim=bc, mode=mode,
        precision=_precision(args, dtype),
    )
    A = residual.spd_operand(args.n, dtype)

    def step(a):
        return cholesky.spd_inverse(grid, a, cfg)

    t, extra = _timed(args, step, A)
    flops = 2.0 * args.n**3 / 3.0 + args.n**3 / 3.0
    rec = harness.report(
        "spd_inverse_tflops", t, flops, dtype, n=args.n, grid=repr(grid),
        mode=mode, **_knobs(args), **extra,
    )
    if args.validate:
        Ainv = jax.jit(lambda a: cholesky.spd_inverse(grid, a, cfg))(A)
        _gate(
            "spd_inverse_residual",
            float(residual.inverse_residual(A, Ainv)),
            10 * residual.tolerance(dtype),
        )
    _ledger_append(
        args, rec, name="spd_inverse", grid=grid, cfg=cfg, step=step,
        operand=A, dtype=dtype,
    )
    return rec


def trsm(args) -> dict:
    """Bench the finished distributed TRSM (models/trsm.py — the capability
    the reference stubs at diaginvert.hpp:9).  Times side='L', uplo='L'
    (the back-substitution shape cholinv/cacqr lean on); --validate smoke-
    tests all four side/uplo combos plus the unit_diag (Diag::AblasUnit)
    surface at the bench size."""
    from capital_tpu.models import trsm as trsm_mod

    bc = cholesky.pick_base_case(args.n, args.bc, cholinv_family=False)
    grid = _grid(args)
    # 'auto' resolves to xla for the invert leaf, not the usual single-TPU
    # pallas pick: with diaginvert leaves every TRSM gemm is DENSE
    # (off-diagonal updates + leaf multiplies), so the live-tile kernels'
    # triangular bookkeeping is pure overhead (measured 163.9 vs 165.2
    # TF/s at n=32768).  The solve leaf keeps the standard resolution.
    if args.mode == "auto":
        mode = "xla" if args.leaf == "invert" else summa.resolve_mode(args.mode, grid)
    else:
        mode = args.mode
    dtype = jnp.dtype(args.dtype)
    L = residual.tri_operand(args.n, dtype)
    nrhs = args.m if args.m != 65536 or args.n >= 65536 else args.n
    B = jax.block_until_ready(
        jax.random.normal(jax.random.key(1), (args.n, nrhs), dtype=dtype)
    )
    cfg = trsm_mod.TrsmConfig(
        base_case_dim=bc, mode=mode, precision=_precision(args, dtype),
        leaf=args.leaf,
    )

    # L must be a REAL jit argument, not a step() closure: a closed-over
    # n x n array becomes an HLO constant (a multi-GB serialized program
    # at n >= 16384).  A custom loop with a (L, B) tuple operand mirrors
    # _make_loop's 'full' coupling body and shares wall + device floor
    # like every driver.
    @jax.jit
    def loop(op, eps, k):
        Lo, B0 = op

        def body(_, carry):
            X = trsm_mod.solve(grid, Lo, carry, side="L", uplo="L", cfg=cfg)
            return carry + eps.astype(carry.dtype) * X

        return jnp.sum(jax.lax.fori_loop(0, k, body, B0), dtype=jnp.float32)

    t, extra = _timed(args, None, (L, B), loop=loop)
    # standard TRSM flop count: n² flops per right-hand side
    flops = 1.0 * args.n**2 * nrhs
    rec = harness.report(
        "trsm_tflops", t, flops, dtype, n=args.n, nrhs=nrhs, grid=repr(grid),
        bc=bc, mode=mode, **_knobs(args), **extra,
    )
    if args.validate:
        # each combo solves + checks inside ONE jit over (L, B) arguments
        # (an f32 copy of the n x n operand is 4.3 GB at n=32768 — holding
        # several eagerly OOM'd the chip), against a reduced RHS
        tol = residual.tolerance(dtype)
        Bv = B[:, : min(nrhs, 4096)]

        def combo_err(t, b, side, uplo, unit):
            tf = t.astype(jnp.float32)
            if unit:
                # solve against the RAW operand (stored diagonal 3.0) with
                # unit_diag: the reference product uses diag == 1, so the
                # gate only passes if the solver truly ignores the stored
                # diagonal (Diag::AblasUnit semantics)
                Tf = jnp.tril(tf, -1) + jnp.eye(t.shape[0], dtype=jnp.float32)
                solve_op = t
            else:
                Tf = jnp.tril(tf) if uplo == "L" else jnp.triu(tf.T)
                solve_op = Tf.astype(dtype)
            X = trsm_mod.solve(
                grid, solve_op, b, side=side, uplo=uplo, cfg=cfg,
                unit_diag=unit,
            )
            # gate matmul at 'highest' like every residual.* helper
            # (residual.py _PREC note): the default f32 product floors the
            # measurable residual near 1e-3 and fails a CORRECT f32 solve
            got = (
                jnp.matmul(Tf, X.astype(jnp.float32), precision="highest")
                if side == "L"
                else jnp.matmul(X.astype(jnp.float32), Tf, precision="highest")
            )
            return residual.rel_fro(got - b.astype(jnp.float32), b)

        for side in ("L", "R"):
            for uplo in ("L", "U"):
                Bs = Bv if side == "L" else Bv.T
                err = float(
                    jax.jit(
                        lambda t, b, s=side, u=uplo: combo_err(t, b, s, u, False)
                    )(L, Bs)
                )
                _gate(f"trsm_residual_{side}{uplo}", err, tol)
        # Diag::AblasUnit parity: the solve must ignore the stored diagonal
        err = float(
            jax.jit(lambda t, b: combo_err(t, b, "L", "L", True))(L, Bv)
        )
        _gate("trsm_residual_unit_diag", err, tol)

    # audit step takes (L, B) as REAL arguments (same HLO-constant rule as
    # the timing loop above); skipped past n=8192 where re-lowering the
    # whole solve just for the inventory costs more than the bench itself
    def audit_step(lo, b):
        return trsm_mod.solve(grid, lo, b, side="L", uplo="L", cfg=cfg)

    _ledger_append(
        args, rec, name="trsm", grid=grid, cfg=cfg,
        step=audit_step if args.n <= 8192 else None, operand=(L, B),
        dtype=dtype,
    )
    return rec


def _small_batch(op: str, n: int, batch: int, nrhs: int, dtype,
                 seed: int = 3):
    """One bucket-shaped problem batch for the small-N drivers: SPD
    problems for posv, tall (4n, n) problems for lstsq — the serve
    bucket geometry, full occupancy."""
    import numpy as np

    rng = np.random.default_rng(seed)
    if op == "posv":
        m = n
        X = rng.standard_normal((batch, n, n))
        A = X @ X.transpose(0, 2, 1) / n + 3.0 * np.eye(n)
    else:
        m = 4 * n
        A = rng.standard_normal((batch, m, n))
    B = rng.standard_normal((batch, m, nrhs))
    return (
        jax.block_until_ready(jnp.asarray(A, dtype)),
        jax.block_until_ready(jnp.asarray(B, dtype)),
    )


def _small_residual(op: str, A, B, X) -> float:
    """Worst per-problem f64 residual of a batch solve (numpy reference)."""
    import numpy as np

    A = np.asarray(A, np.float64)
    B = np.asarray(B, np.float64)
    X = np.asarray(X, np.float64)
    worst = 0.0
    for i in range(A.shape[0]):
        if op == "posv":
            r = np.linalg.norm(A[i] @ X[i] - B[i]) / np.linalg.norm(B[i])
        else:
            num = np.linalg.norm(A[i].T @ (A[i] @ X[i] - B[i]))
            r = num / np.linalg.norm(A[i].T @ B[i])
        worst = max(worst, r)
    return worst


def _small_solve(args, op: str):
    """Shared body of the posv/lstsq small-N drivers: one bucket batch
    through api.batched under --small-impl, measured either amortized
    (TFLOP/s row, the default) or per-call (--latency: p50/p95/p99
    wall_ms via harness.latency_samples + percentiles, sorted facts for
    the latency regime ROADMAP item 5 names — each sample pays the
    dispatch a served request pays)."""
    from capital_tpu.serve import api

    dtype = jnp.dtype(args.dtype)
    n, batch, nrhs = args.n, args.batch, args.nrhs
    grid = Grid.square(c=1, devices=jax.devices()[:1])
    prec = _precision(args, dtype)
    A, B = _small_batch(op, n, batch, nrhs, dtype)
    fn = jax.jit(api.batched(op, prec, args.small_impl))

    if args.validate:
        X, info = jax.block_until_ready(fn(A, B))
        bad = int(jnp.sum(info != 0))
        if bad:
            sys.exit(f"validation failed: {bad} problem(s) report info != 0")
        tol = residual.tolerance(dtype)
        gate = 10 * tol if op == "lstsq" else tol
        _gate(f"{op}_batch_residual", _small_residual(op, A, B, X), gate)

    # useful flops (not the kernels' executed sweep counts): the
    # cross-impl comparable figure
    m = A.shape[1]
    if op == "posv":
        flops = batch * (n**3 / 3.0 + 2.0 * n * n * nrhs)
    else:
        flops = batch * (2.0 * m * n * n + 2.0 * m * n * nrhs)

    if args.latency:
        samples = harness.latency_samples(
            lambda: fn(A, B), calls=args.calls, warmup=3
        )
        pcts = percentiles(samples)
        wall_ms = {k: round(v * 1e3, 4) for k, v in pcts.items()}
        from capital_tpu.obs.ledger import SCHEMA_VERSION

        rec = {
            "metric": f"small_{op}_latency",
            "schema_version": SCHEMA_VERSION,
            # value is rate so an obs diff value-drop reads as "slower p99"
            "value": round(1.0 / pcts["p99"], 3),
            "unit": "batch/s",
            "seconds": pcts["p99"],
            "wall_ms": wall_ms,
            "dtype": str(dtype),
            "device": jax.devices()[0].device_kind,
            "platform": jax.default_backend(),
            "n": n, "batch": batch, "nrhs": nrhs,
            "impl": args.small_impl, "calls": args.calls,
        }
        import json as _json

        print(_json.dumps(rec))
        _ledger_append(args, rec, name="latency", grid=grid, dtype=dtype,
                       cfg={"op": op, "impl": args.small_impl})
        return rec

    samples = harness.latency_samples(
        lambda: fn(A, B), calls=max(args.iters, 3), warmup=3
    )
    t = sum(samples) / len(samples)
    rec = harness.report(
        f"small_{op}_tflops", t, flops, dtype, n=n, batch=batch, nrhs=nrhs,
        impl=args.small_impl, grid=repr(grid),
        wall_ms={k: round(v * 1e3, 4)
                 for k, v in percentiles(samples).items()},
    )
    _ledger_append(args, rec, name=op, grid=grid, dtype=dtype,
                   cfg={"op": op, "impl": args.small_impl})
    return rec


def _blocktri_batch(nblocks: int, b: int, batch: int, nrhs: int, dtype,
                    seed: int = 5):
    """One batch of SPD block-tridiagonal chains (the serve posv_blocktri
    geometry): D_i = G·Gᵀ/b + 3I per block (the spd_operand spectrum family),
    couplings at 0.3/√b — strong enough that a sweep bug blows the
    residual gate, weak enough that the chain stays well-conditioned
    (block diagonal dominance).  Returns device arrays plus the f64 numpy
    masters for --validate."""
    import numpy as np

    rng = np.random.default_rng(seed)
    G = rng.standard_normal((batch, nblocks, b, b))
    D = G @ G.transpose(0, 1, 3, 2) / b + 3.0 * np.eye(b)
    C = 0.3 / np.sqrt(b) * rng.standard_normal((batch, nblocks, b, b))
    C[:, 0] = 0.0
    B = rng.standard_normal((batch, nblocks, b, nrhs))
    dev = tuple(
        jax.block_until_ready(jnp.asarray(x, dtype)) for x in (D, C, B)
    )
    return dev, (D, C, B)


def _blocktri_dense(D, C) -> "jnp.ndarray":
    """Assemble the f64 numpy chain masters to dense (batch, n, n) —
    NumPy-side so the reference the residual gates compare against never
    touches the code under test (models/blocktri.assemble is itself new
    in this round)."""
    import numpy as np

    batch, nblocks, b, _ = D.shape
    n = nblocks * b
    A = np.zeros((batch, n, n))
    for i in range(nblocks):
        sl = slice(i * b, (i + 1) * b)
        A[:, sl, sl] = D[:, i]
        if i:
            up = slice((i - 1) * b, i * b)
            A[:, sl, up] = C[:, i]
            A[:, up, sl] = C[:, i].transpose(0, 2, 1)
    return A


def blocktri(args) -> dict:
    """Bench the block-tridiagonal fast path (models/blocktri.posv) and
    measure its wall-clock speedup against the equal-n dense batched posv
    on the SAME problems assembled dense — the structural O(n·b³) vs
    O(n³) win the round-11 flagship gate pins (docs/PERF.md).  Reports
    useful-flop TF/s (the chain's O(n·b³) count, not dense n³ — so the
    TF/s figure is comparable across impls, and the speedup column
    carries the structural win)."""
    from capital_tpu.models import blocktri as bt_mod
    from capital_tpu.serve import api

    dtype = jnp.dtype(args.dtype)
    grid = Grid.square(c=1, devices=jax.devices()[:1])
    prec = _precision(args, dtype)
    nblocks, b, batch, nrhs = args.nblocks, args.block, args.batch, args.nrhs
    n = nblocks * b
    impl = args.impl
    if impl == "auto" and jax.default_backend() != "tpu":
        # off-TPU 'auto' pins the xla scan — pallas means the interpreter
        # there (the summa.resolve_mode rationale), and a bench must measure an
        # honest wall time.  serve keeps interpret-pallas off-TPU for its
        # own reason (pure-HLO executables persist in the AOT disk cache);
        # the bench and serve resolve 'auto' differently ON PURPOSE.
        impl = "xla"
    (Dj, Cj, Bj), (Dn, Cn, Bn) = _blocktri_batch(nblocks, b, batch, nrhs,
                                                 dtype)
    partitions = 0
    seq_impl = impl
    if impl == "partitioned":
        # the A/B satellite: bench the partitioned driver against the
        # sequential scan on the SAME problems, and measure the thing the
        # algorithm actually buys — jaxpr sequential scan depth (the
        # critical path a 1-core rig can still count honestly even when
        # wall time can't show the parallel win).  Inner scans obey the
        # same off-TPU honest-wall pin as 'auto' above.
        partitions = bt_mod.resolve_partitions(nblocks, args.partitions)
        inner = "xla" if jax.default_backend() != "tpu" else "auto"
        seq_impl = "xla" if jax.default_backend() != "tpu" else "pallas"
        fn = jax.jit(
            lambda d, c, rhs: bt_mod.posv(
                d, c, rhs, precision=prec, impl="partitioned",
                partitions=partitions, partition_inner=inner,
            )
        )
    else:
        fn = jax.jit(
            lambda d, c, rhs: bt_mod.posv(d, c, rhs, precision=prec,
                                          impl=impl)
        )
    seq_fn = fn if impl != "partitioned" else jax.jit(
        lambda d, c, rhs: bt_mod.posv(d, c, rhs, precision=prec,
                                      impl=seq_impl)
    )

    if args.validate:
        X, info = jax.block_until_ready(fn(Dj, Cj, Bj))
        bad = int(jnp.sum(info != 0))
        if bad:
            sys.exit(f"validation failed: {bad} problem(s) report info != 0")
        import numpy as np

        Ad = _blocktri_dense(Dn, Cn)
        Xn = np.asarray(X, np.float64).reshape(batch, n, nrhs)
        Bd = Bn.reshape(batch, n, nrhs)
        tol = residual.tolerance(dtype)
        worst = max(
            float(np.linalg.norm(Ad[i] @ Xn[i] - Bd[i])
                  / np.linalg.norm(Bd[i]))
            for i in range(batch)
        )
        _gate("blocktri_solve_residual", worst, tol)
        # factor residual: reconstruct A from (L, Wt) blockwise in f64 —
        # ‖A − L̃·L̃ᵀ‖_F/‖A‖_F over the whole batch.  factor() is the
        # sequential representation (it rejects 'partitioned'), so the
        # reconstruction rides seq_impl; the partitioned X was already
        # residual-gated above, which is the contract that matters.
        L, Wt, _ = jax.jit(
            lambda d, c: bt_mod.factor(d, c, precision=prec, impl=seq_impl)
        )(Dj, Cj)
        Ln = np.asarray(L, np.float64)
        Wn = np.asarray(Wt, np.float64).transpose(0, 1, 3, 2)  # W_i
        R = np.zeros_like(Ad)
        for i in range(nblocks):
            sl = slice(i * b, (i + 1) * b)
            # A_ii = L_i·L_iᵀ + W_i·W_iᵀ  (W_1 = 0); A_{i,i−1} = W_i·L_{i−1}ᵀ
            R[:, sl, sl] = Ln[:, i] @ Ln[:, i].transpose(0, 2, 1)
            if i:
                up = slice((i - 1) * b, i * b)
                R[:, sl, sl] += Wn[:, i] @ Wn[:, i].transpose(0, 2, 1)
                blk = Wn[:, i] @ Ln[:, i - 1].transpose(0, 2, 1)
                R[:, sl, up] = blk
                R[:, up, sl] = blk.transpose(0, 2, 1)
        _gate(
            "blocktri_factor_residual",
            float(np.linalg.norm(R - Ad) / np.linalg.norm(Ad)),
            tol,
        )

    # useful flops per chain: factor nblocks·(b³/3 chol + b³ trsm + 2b³
    # Schur) + solve nblocks·2 sweeps·(b²k trsm + 2b²k coupling gemm)
    flops = batch * nblocks * (b**3 / 3.0 + 3.0 * b**3
                               + 6.0 * b * b * nrhs)

    if args.latency:
        samples = harness.latency_samples(
            lambda: fn(Dj, Cj, Bj), calls=args.calls, warmup=3
        )
        pcts = percentiles(samples)
        from capital_tpu.obs.ledger import SCHEMA_VERSION

        rec = {
            "metric": "blocktri_latency",
            "schema_version": SCHEMA_VERSION,
            "value": round(1.0 / pcts["p99"], 3),
            "unit": "batch/s",
            "seconds": pcts["p99"],
            "wall_ms": {k: round(v * 1e3, 4) for k, v in pcts.items()},
            "dtype": str(dtype),
            "device": jax.devices()[0].device_kind,
            "platform": jax.default_backend(),
            "nblocks": nblocks, "block": b, "n": n, "batch": batch,
            "nrhs": nrhs, "impl": impl, "calls": args.calls,
        }
        if impl == "partitioned":
            from capital_tpu.obs import xla_audit

            rec["partitions"] = partitions
            rec["depth"] = xla_audit.sequential_depth(fn, Dj, Cj, Bj)
        import json as _json

        print(_json.dumps(rec))
        _ledger_append(args, rec, name="blocktri_latency", grid=grid,
                       dtype=dtype,
                       cfg={"op": "posv_blocktri", "impl": impl})
        return rec

    samples = harness.latency_samples(
        lambda: fn(Dj, Cj, Bj), calls=max(args.iters, 3), warmup=3
    )
    t = sum(samples) / len(samples)

    par_extra: dict = {}
    if impl == "partitioned":
        # A/B rows vs the sequential scan: latency AND jaxpr sequential
        # scan depth — the depth column is the honest metric on a 1-core
        # rig (wall time can't show the parallel win when the P interior
        # factorizations time-slice one core; the shortened critical path
        # is a property of the program, not the host).
        from capital_tpu.obs import xla_audit

        depth = xla_audit.sequential_depth(fn, Dj, Cj, Bj)
        depth_seq = xla_audit.sequential_depth(seq_fn, Dj, Cj, Bj)
        depth_reduction = depth_seq / depth if depth else 0.0
        Xp, _ = jax.block_until_ready(fn(Dj, Cj, Bj))
        Xs, _ = jax.block_until_ready(seq_fn(Dj, Cj, Bj))
        scale = max(float(jnp.max(jnp.abs(Xs))), 1e-30)
        parity = float(jnp.max(jnp.abs(Xp - Xs))) / scale
        sseq = harness.latency_samples(
            lambda: seq_fn(Dj, Cj, Bj), calls=max(args.iters, 3), warmup=3
        )
        t_seq = sum(sseq) / len(sseq)
        print(f"# impl={seq_impl:<12s} {t_seq / batch * 1e3:9.3f} "
              f"ms/problem  depth={depth_seq}")
        print(f"# impl=partitioned  {t / batch * 1e3:9.3f} ms/problem  "
              f"depth={depth}  (P={partitions}, "
              f"{depth_reduction:.2f}x shallower, parity {parity:.2e})")
        par_extra = {
            "partitions": partitions, "depth": depth,
            "depth_seq": depth_seq,
            "depth_reduction": round(depth_reduction, 3),
            "parity": parity,
            "seq_ms": round(t_seq / batch * 1e3, 4),
        }

    # dense comparison on the same problems, per-problem amortized both
    # sides; the dense batch shrinks when batch·n² won't reasonably fit
    # (the structural point survives — per-problem time is the comparand)
    dense_batch = batch
    dense_bytes = batch * n * n * dtype.itemsize
    if dense_bytes > 2e9:
        dense_batch = max(1, int(2e9 // (n * n * dtype.itemsize)))
    Adj = jax.block_until_ready(
        jnp.asarray(_blocktri_dense(Dn[:dense_batch], Cn[:dense_batch]),
                    dtype))
    Bdj = Bj[:dense_batch].reshape(dense_batch, n, nrhs)
    dense_fn = jax.jit(api.batched("posv", prec, args.small_impl))
    dsamples = harness.latency_samples(
        lambda: dense_fn(Adj, Bdj), calls=max(args.iters, 3), warmup=1
    )
    t_dense = sum(dsamples) / len(dsamples)
    speedup = (t_dense / dense_batch) / (t / batch)
    print(f"# speedup {speedup:.1f}x vs dense posv n={n} "
          f"(dense {t_dense / dense_batch * 1e3:.1f} ms/problem, "
          f"blocktri {t / batch * 1e3:.3f} ms/problem)")

    rec = harness.report(
        "blocktri_tflops", t, flops, dtype, nblocks=nblocks, block=b, n=n,
        batch=batch, nrhs=nrhs, impl=impl, grid=repr(grid),
        speedup=round(speedup, 2),
        dense_ms=round(t_dense / dense_batch * 1e3, 3),
        wall_ms={k: round(v * 1e3, 4)
                 for k, v in percentiles(samples).items()},
        **par_extra,
    )
    if args.min_depth_reduction:
        if impl != "partitioned":
            sys.exit("--min-depth-reduction requires --impl partitioned")
        ptol = residual.tolerance(dtype)
        if parity > ptol or depth_reduction < args.min_depth_reduction:
            _ledger_append(args, rec, name="blocktri", grid=grid,
                           dtype=dtype,
                           cfg={"op": "posv_blocktri", "impl": impl,
                                "nblocks": nblocks, "block": b})
            if parity > ptol:
                sys.exit(
                    f"partitioned parity gate failed: max|X_par - X_seq| "
                    f"= {parity:.2e} > {ptol:g} vs impl={seq_impl}"
                )
            sys.exit(
                f"depth gate failed: {depth_reduction:.2f}x < "
                f"{args.min_depth_reduction}x "
                f"(seq {depth_seq} trips -> partitioned {depth})"
            )
    if args.min_speedup and speedup < args.min_speedup:
        _ledger_append(args, rec, name="blocktri", grid=grid, dtype=dtype,
                       cfg={"op": "posv_blocktri", "impl": impl,
                            "nblocks": nblocks, "block": b})
        sys.exit(
            f"speedup gate failed: {speedup:.1f}x < {args.min_speedup}x "
            f"vs dense posv at n={n}"
        )
    _ledger_append(args, rec, name="blocktri", grid=grid, dtype=dtype,
                   cfg={"op": "posv_blocktri", "impl": impl,
                        "nblocks": nblocks, "block": b})
    return rec


def _arrowhead_batch(nblocks: int, b: int, s: int, batch: int, nrhs: int,
                     dtype, seed: int = 5):
    """One batch of SPD block-arrowhead systems (the serve posv_arrowhead
    geometry): the _blocktri_batch chain family plus a thin border at
    0.3/√(nblocks·b)·randn — the border couples EVERY chain block, so the
    Schur correction F·T⁻¹·Fᵀ grows with chain length and the coupling
    must shrink with it or the corner S = S₀·S₀ᵀ/s + 5I goes indefinite
    at flagship n (the whole matrix stops being SPD, not a solver bug).
    Returns device arrays plus the f64 numpy masters."""
    import numpy as np

    rng = np.random.default_rng(seed)
    G = rng.standard_normal((batch, nblocks, b, b))
    D = G @ G.transpose(0, 1, 3, 2) / b + 3.0 * np.eye(b)
    C = 0.3 / np.sqrt(b) * rng.standard_normal((batch, nblocks, b, b))
    C[:, 0] = 0.0
    F = 0.3 / np.sqrt(nblocks * b) * rng.standard_normal(
        (batch, nblocks, s, b))
    S0 = rng.standard_normal((batch, s, s))
    S = S0 @ S0.transpose(0, 2, 1) / s + 5.0 * np.eye(s)
    B = rng.standard_normal((batch, nblocks, b, nrhs))
    Bs = rng.standard_normal((batch, s, nrhs))
    dev = tuple(
        jax.block_until_ready(jnp.asarray(x, dtype))
        for x in (D, C, F, S, B, Bs)
    )
    return dev, (D, C, F, S, B, Bs)


def _arrowhead_chain_solve_np(D, C, R):
    """f64 NumPy block-Cholesky chain solve (batch, nblocks, b, r) — an
    independent reference implementation (LAPACK via numpy/scipy, never
    models/blocktri) so the residual gates compare the code under test
    against something it cannot share a bug with."""
    import numpy as np
    from scipy.linalg import solve_triangular

    batch, nblocks, b, _ = D.shape
    L = np.zeros_like(D)
    W = np.zeros_like(C)
    Y = np.zeros_like(R)
    for z in range(batch):
        for i in range(nblocks):
            Di = D[z, i].copy()
            if i:
                # W_i = C_i · L_{i−1}⁻ᵀ  (solve L·Xᵀ = C_iᵀ, transpose)
                W[z, i] = solve_triangular(
                    L[z, i - 1], C[z, i].T, lower=True).T
                Di -= W[z, i] @ W[z, i].T
            L[z, i] = np.linalg.cholesky(Di)
            rhs = R[z, i] - (W[z, i] @ Y[z, i - 1] if i else 0.0)
            Y[z, i] = solve_triangular(L[z, i], rhs, lower=True)
        for i in range(nblocks - 1, -1, -1):
            rhs = Y[z, i].copy()
            if i + 1 < nblocks:
                rhs -= W[z, i + 1].T @ Y[z, i + 1]
            Y[z, i] = solve_triangular(L[z, i], rhs, lower=True, trans="T")
    return Y


def _arrowhead_dense(D, C, F, S):
    """Assemble the f64 numpy arrowhead masters to dense (batch, n, n) —
    NumPy-side for the same reason as _blocktri_dense (the reference must
    never touch models/arrowhead.assemble, itself new this round)."""
    import numpy as np

    A = _blocktri_dense(D, C)
    batch, nblocks, s, b = F.shape
    n_t = nblocks * b
    Bd = F.transpose(0, 2, 1, 3).reshape(batch, s, n_t)
    top = np.concatenate([A, Bd.transpose(0, 2, 1)], axis=2)
    bottom = np.concatenate([Bd, S], axis=2)
    return np.concatenate([top, bottom], axis=1)


def arrowhead(args) -> dict:
    """Bench the block-arrowhead fast path (models/arrowhead.posv) and
    measure its wall-clock speedup against the equal-n dense batched posv
    on the SAME problems assembled dense — the structural
    O(nblocks·b³ + nblocks·b²·s + s³) vs O((nblocks·b + s)³) win the
    round-15 flagship gate pins (docs/PERF.md).  Unlike the blocktri
    driver this one ALWAYS runs its f64 residual gates — both halves of
    the factorization are new (the widened chain solve and the Schur
    completion), so a speedup row must prove its answers every run:
    the solve gate is the whole-matrix backward error computed blockwise
    in f64 (no densification needed), the factor gate reconstructs
    L_S·L_Sᵀ against a Schur complement built from an independent NumPy
    block-Cholesky chain solve."""
    from capital_tpu.models import arrowhead as ah_mod
    from capital_tpu.models import blocktri as bt_mod
    from capital_tpu.serve import api

    import numpy as np

    dtype = jnp.dtype(args.dtype)
    grid = Grid.square(c=1, devices=jax.devices()[:1])
    prec = _precision(args, dtype)
    nblocks, b, s = args.nblocks, args.block, args.border
    batch, nrhs = args.batch, args.nrhs
    n_t = nblocks * b
    n = n_t + s
    impl = args.impl
    if impl == "auto" and jax.default_backend() != "tpu":
        # the blocktri driver's off-TPU honest-wall pin, same rationale
        impl = "xla"
    (Dj, Cj, Fj, Sj, Bj, Bsj), (Dn, Cn, Fn, Sn, Bn, Bsn) = _arrowhead_batch(
        nblocks, b, s, batch, nrhs, dtype)
    partitions = 0
    if impl == "partitioned":
        partitions = bt_mod.resolve_partitions(nblocks, args.partitions)
        inner = "xla" if jax.default_backend() != "tpu" else "auto"
        fn = jax.jit(
            lambda d, c, f, sc, rhs, bs: ah_mod.posv(
                d, c, f, sc, rhs, bs, precision=prec, impl="partitioned",
                partitions=partitions, partition_inner=inner,
            )
        )
    else:
        fn = jax.jit(
            lambda d, c, f, sc, rhs, bs: ah_mod.posv(
                d, c, f, sc, rhs, bs, precision=prec, impl=impl)
        )

    # --- residual gates (always on; see the docstring) ---
    X, Xs, info = jax.block_until_ready(fn(Dj, Cj, Fj, Sj, Bj, Bsj))
    bad = int(jnp.sum(info != 0))
    if bad:
        sys.exit(f"validation failed: {bad} problem(s) report info != 0")
    tol = residual.tolerance(dtype)
    Xn = np.asarray(X, np.float64)
    Xsn = np.asarray(Xs, np.float64)
    # blockwise residual: chain rows D_i·x_i + C_i·x_{i−1} + C_{i+1}ᵀ·x_{i+1}
    # + F_iᵀ·x_s − b_i, corner rows Σ F_i·x_i + S·x_s − b_s
    Rc = np.einsum("znab,znbk->znak", Dn, Xn) - Bn
    Rc[:, 1:] += np.einsum("znab,znbk->znak", Cn[:, 1:], Xn[:, :-1])
    Rc[:, :-1] += np.einsum("znba,znbk->znak", Cn[:, 1:], Xn[:, 1:])
    Rc += np.einsum("znsb,zsk->znbk", Fn, Xsn)
    Rs = np.einsum("znsb,znbk->zsk", Fn, Xn) + Sn @ Xsn - Bsn
    rhs_n = np.concatenate([Bn.reshape(batch, n_t, nrhs), Bsn], axis=1)
    res = np.concatenate([Rc.reshape(batch, n_t, nrhs), Rs], axis=1)
    solve_resid = max(
        float(np.linalg.norm(res[i]) / np.linalg.norm(rhs_n[i]))
        for i in range(batch)
    )
    _gate("arrowhead_solve_residual", solve_resid, tol)
    # factor gate: L_S·L_Sᵀ vs the f64 reference Schur complement
    # S̃ = S − F·(T⁻¹·Fᵀ) built from the independent NumPy chain solve
    Zb_ref = _arrowhead_chain_solve_np(Dn, Cn, Fn.transpose(0, 1, 3, 2))
    St_ref = Sn - np.einsum("znsb,znbt->zst", Fn, Zb_ref)
    _, _, Ls, _ = jax.block_until_ready(
        jax.jit(lambda d, c, f, sc: ah_mod.schur(
            d, c, f, sc, precision=prec,
            impl="xla" if impl == "partitioned" else impl,
        ))(Dj, Cj, Fj, Sj)
    )
    Lsn = np.asarray(Ls, np.float64)
    factor_resid = max(
        float(np.linalg.norm(Lsn[i] @ Lsn[i].T - St_ref[i])
              / np.linalg.norm(St_ref[i]))
        for i in range(batch)
    )
    _gate("arrowhead_factor_residual", factor_resid, tol)

    # useful flops per system: the widened chain solve (s + nrhs columns
    # through the blocktri count) + the AH::schur / AH::border phases
    flops = batch * (
        nblocks * (b**3 / 3.0 + 3.0 * b**3 + 6.0 * b * b * (s + nrhs))
        + 2.0 * n_t * s * s + s**3 / 3.0
        + 4.0 * n_t * s * nrhs + 2.0 * s * s * nrhs
    )

    if args.latency:
        samples = harness.latency_samples(
            lambda: fn(Dj, Cj, Fj, Sj, Bj, Bsj), calls=args.calls, warmup=3
        )
        pcts = percentiles(samples)
        from capital_tpu.obs.ledger import SCHEMA_VERSION

        rec = {
            "metric": "arrowhead_latency",
            "schema_version": SCHEMA_VERSION,
            "value": round(1.0 / pcts["p99"], 3),
            "unit": "batch/s",
            "seconds": pcts["p99"],
            "wall_ms": {k: round(v * 1e3, 4) for k, v in pcts.items()},
            "dtype": str(dtype),
            "device": jax.devices()[0].device_kind,
            "platform": jax.default_backend(),
            "nblocks": nblocks, "block": b, "border": s, "n": n,
            "batch": batch, "nrhs": nrhs, "impl": impl, "calls": args.calls,
        }
        import json as _json

        print(_json.dumps(rec))
        _ledger_append(args, rec, name="arrowhead_latency", grid=grid,
                       dtype=dtype,
                       cfg={"op": "posv_arrowhead", "impl": impl})
        return rec

    samples = harness.latency_samples(
        lambda: fn(Dj, Cj, Fj, Sj, Bj, Bsj), calls=max(args.iters, 3),
        warmup=3
    )
    t = sum(samples) / len(samples)

    # dense comparison on the same problems, per-problem amortized both
    # sides, batch shrunk when batch·n² won't fit (the blocktri policy)
    dense_batch = batch
    dense_bytes = batch * n * n * dtype.itemsize
    if dense_bytes > 2e9:
        dense_batch = max(1, int(2e9 // (n * n * dtype.itemsize)))
    Adj = jax.block_until_ready(jnp.asarray(
        _arrowhead_dense(Dn[:dense_batch], Cn[:dense_batch],
                         Fn[:dense_batch], Sn[:dense_batch]), dtype))
    Bdj = jax.block_until_ready(
        jnp.asarray(rhs_n[:dense_batch], dtype))
    dense_fn = jax.jit(api.batched("posv", prec, args.small_impl))
    dsamples = harness.latency_samples(
        lambda: dense_fn(Adj, Bdj), calls=max(args.iters, 3), warmup=1
    )
    t_dense = sum(dsamples) / len(dsamples)
    speedup = (t_dense / dense_batch) / (t / batch)
    print(f"# speedup {speedup:.1f}x vs dense posv n={n} "
          f"(dense {t_dense / dense_batch * 1e3:.1f} ms/problem, "
          f"arrowhead {t / batch * 1e3:.3f} ms/problem)")

    rec = harness.report(
        "arrowhead_tflops", t, flops, dtype, nblocks=nblocks, block=b,
        border=s, n=n, batch=batch, nrhs=nrhs, impl=impl, grid=repr(grid),
        speedup=round(speedup, 2),
        arrow_ms=round(t / batch * 1e3, 4),
        dense_ms=round(t_dense / dense_batch * 1e3, 3),
        factor_resid=factor_resid, solve_resid=solve_resid,
        wall_ms={k: round(v * 1e3, 4)
                 for k, v in percentiles(samples).items()},
        **({"partitions": partitions} if impl == "partitioned" else {}),
    )
    if args.min_speedup and speedup < args.min_speedup:
        _ledger_append(args, rec, name="arrowhead", grid=grid, dtype=dtype,
                       cfg={"op": "posv_arrowhead", "impl": impl,
                            "nblocks": nblocks, "block": b, "border": s})
        sys.exit(
            f"speedup gate failed: {speedup:.1f}x < {args.min_speedup}x "
            f"vs dense posv at n={n}"
        )
    _ledger_append(args, rec, name="arrowhead", grid=grid, dtype=dtype,
                   cfg={"op": "posv_arrowhead", "impl": impl,
                        "nblocks": nblocks, "block": b, "border": s})
    return rec


def update(args) -> dict:
    """Bench online factor maintenance (ops/update_small): measured rank-k
    chol_update against the REFACTOR-FROM-RESIDENT-STATE baseline — the
    cache-less server's only alternative on the factor-residency wire
    protocol (docs/SERVING.md: clients ship the rank-k panel V, never A,
    so serving the same request without a resident factor means
    reassembling S = RᵀR + VVᵀ and running a fresh potrf).  That framing
    is load-bearing for the speedup gate and stated with the number
    everywhere it lands (docs/PERF.md round 12): against a
    client-shipped-A refactor (one potrf, no reassembly) the rank-k
    update's algorithmic edge is k/n-bounded and this 1-core CPU rig
    measures ~3x at n=1024, k=16 — the protocol baseline is the honest
    serving comparison, not the flattering one.

    --validate adds f64-NumPy-side residual gates (the bench-blocktri
    discipline): ‖R₊ᵀR₊ − (A + VVᵀ)‖_F/‖·‖_F for the update, the same
    for a downdate back to A, and zero info flags on both sweeps.

    --min-hit-rate additionally runs the 50-request serve smoke: mixed
    chol_update / posv_cached traffic over a handful of tokens through a
    real SolveEngine, gating residency hit-rate >= the floor AND zero
    steady-state executable recompiles (residency is host-side state, so
    factor traffic must never recompile)."""
    from capital_tpu.ops import update_small

    dtype = jnp.dtype(args.dtype)
    grid = Grid.square(c=1, devices=jax.devices()[:1])
    prec = _precision(args, dtype)
    n, k, batch = args.n, args.k, args.batch
    if k > n:
        sys.exit(f"update: rank --k {k} exceeds --n {n}")
    impl = args.impl  # auto/pallas/xla, the blocktri flag; update_small
    # resolves 'auto' per shape (pallas only inside the small-N envelope)

    import numpy as np

    rng = np.random.default_rng(11)
    X = rng.standard_normal((batch, n, n))
    A = (X @ X.transpose(0, 2, 1) / n + 3.0 * np.eye(n)).astype(np.float64)
    R0 = np.linalg.cholesky(A).transpose(0, 2, 1)
    V0 = (0.1 / np.sqrt(n)) * rng.standard_normal((batch, n, k))
    Rj = jax.block_until_ready(jnp.asarray(R0, dtype))
    Vj = jax.block_until_ready(jnp.asarray(V0, dtype))

    fn = jax.jit(lambda r, v: update_small.chol_update(
        r, v, precision=prec, impl=impl))
    dn = jax.jit(lambda r, v: update_small.chol_downdate(
        r, v, precision=prec, impl=impl))

    if args.validate:
        R1, info1 = jax.block_until_ready(fn(Rj, Vj))
        bad = int(jnp.sum(info1 != 0))
        if bad:
            sys.exit(f"validation failed: {bad} update(s) report info != 0")
        R1n = np.asarray(R1, np.float64)
        Ap = A + V0 @ V0.transpose(0, 2, 1)
        tol = residual.tolerance(dtype)
        worst = max(
            float(np.linalg.norm(R1n[i].T @ R1n[i] - Ap[i])
                  / np.linalg.norm(Ap[i]))
            for i in range(batch)
        )
        _gate("update_residual", worst, tol)
        R2, info2 = jax.block_until_ready(dn(R1, Vj))
        if int(jnp.sum(info2 != 0)):
            sys.exit("validation failed: downdate of a just-updated factor "
                     "reports info != 0")
        R2n = np.asarray(R2, np.float64)
        worst = max(
            float(np.linalg.norm(R2n[i].T @ R2n[i] - A[i])
                  / np.linalg.norm(A[i]))
            for i in range(batch)
        )
        _gate("downdate_residual", worst, tol)

    # the baseline the wire protocol forces on a cache-less server:
    # reassemble S = RᵀR + VVᵀ (the operand only the factor encodes) and
    # refactor from scratch — measured with the same per-call protocol
    from capital_tpu.ops import lapack as lapack_mod

    def refactor(r, v):
        s = (jnp.einsum("bji,bjk->bik", r, r, precision=prec)
             + jnp.einsum("bik,bjk->bij", v, v, precision=prec))
        return jax.vmap(
            lambda m: lapack_mod.potrf(m, uplo="U", with_info=True))(s)

    base_fn = jax.jit(refactor)
    calls = max(args.iters, 3)
    samples = harness.latency_samples(
        lambda: fn(Rj, Vj), calls=calls, warmup=3)
    bsamples = harness.latency_samples(
        lambda: base_fn(Rj, Vj), calls=calls, warmup=1)
    # min-of-samples on BOTH sides: the speedup gate compares algorithms,
    # not scheduler noise, and best-observed latency is the stable
    # estimator of that on a shared CPU rig (mean would let one preempted
    # baseline call flip the gate either way).
    t = min(samples)
    t_base = min(bsamples)
    speedup = t_base / t
    print(f"# speedup {speedup:.1f}x vs refactor-from-resident-state at "
          f"n={n} k={k} (refactor {t_base / batch * 1e3:.2f} ms/problem, "
          f"update {t / batch * 1e3:.3f} ms/problem)")

    smoke = None
    if args.min_hit_rate:
        smoke = _update_serve_smoke(min(n, 256), min(k, 16), dtype,
                                    ledger=args.ledger)
        print(f"# serve smoke: {smoke['requests']} requests, residency "
              f"hit_rate {smoke['hit_rate']:.3f}, "
              f"{smoke['recompiles']} steady-state recompiles")

    # useful flops (textbook ~2kn² per problem), not the masked-sweep
    # executed count — comparable against the baseline's ~2n³ reassembly
    flops = batch * 2.0 * k * n * n
    rec = harness.report(
        "update_speedup", t, flops, dtype, n=n, k=k, batch=batch,
        impl=impl, grid=repr(grid), speedup=round(speedup, 2),
        refactor_ms=round(t_base / batch * 1e3, 3),
        update_ms=round(t / batch * 1e3, 4),
        wall_ms={kk: round(v * 1e3, 4)
                 for kk, v in percentiles(samples).items()},
        **({"serve_smoke": smoke} if smoke else {}),
    )
    cfg = {"op": "chol_update", "impl": impl, "n": n, "k": k}
    gates = []
    if args.min_speedup and speedup < args.min_speedup:
        gates.append(
            f"speedup gate failed: {speedup:.1f}x < {args.min_speedup}x vs "
            f"refactor-from-resident-state at n={n} k={k}"
        )
    if smoke and smoke["hit_rate"] < args.min_hit_rate:
        gates.append(
            f"residency gate failed: hit_rate {smoke['hit_rate']:.3f} < "
            f"{args.min_hit_rate}"
        )
    if smoke and smoke["recompiles"]:
        gates.append(
            f"zero-recompile gate failed: {smoke['recompiles']} executable "
            "compiles during steady-state factor traffic"
        )
    _ledger_append(args, rec, name="update", grid=grid, dtype=dtype, cfg=cfg)
    if gates:
        sys.exit("; ".join(gates))
    return rec


def _update_serve_smoke(n: int, k: int, dtype, ledger=None) -> dict:
    """The 50-request mixed-traffic residency smoke (bench-update gate):
    seed a few tokens through posv_cached misses, then drive
    chol_update / posv_cached hits against them through a real
    SolveEngine.  Returns the delta counters the caller gates on —
    hit_rate over THIS traffic (not engine lifetime) and executable
    compiles after the one-time per-bucket warmup.  When `ledger` is
    given, also appends the engine's serve:request_stats record (carrying
    the LIFETIME factor_cache counter block, warmup lookups included) so
    ``obs serve-report --min-residency-hit-rate`` has a record to gate."""
    import numpy as np

    from capital_tpu.serve.engine import ServeConfig, SolveEngine

    rng = np.random.default_rng(13)
    cfg = ServeConfig(buckets=(n,), rows_buckets=(4 * n,),
                      nrhs_buckets=(min(4, k), k), max_batch=2,
                      max_delay_s=0.0, oversize="reject")
    eng = SolveEngine(cfg=cfg)
    X = rng.standard_normal((n, n))
    A = (X @ X.T / n + 3.0 * np.eye(n)).astype(dtype)
    B = rng.standard_normal((n, min(4, k))).astype(dtype)
    V = ((0.05 / np.sqrt(n))
         * rng.standard_normal((n, k))).astype(dtype)
    # warm every program the mix touches (the per-bucket one-time cost);
    # everything after this line must hit the executable cache
    for i in range(2):
        assert eng.solve("posv_cached", A, B,
                         factor_token=f"warm{i}").ok
    assert eng.solve("chol_update", V, factor_token="warm0").ok
    assert eng.solve("posv_cached", A, B, factor_token="warm1").ok
    c0 = eng.cache_stats()["compiles"]
    f0 = eng.factor_stats()
    tokens = [f"tok{i}" for i in range(4)]
    requests = 0
    for tok in tokens:  # 4 seeding misses
        assert eng.solve("posv_cached", A, B, factor_token=tok).ok
        requests += 1
    while requests < 50:  # 46 resident hits, mixed ops
        tok = tokens[requests % len(tokens)]
        if requests % 3 == 0:
            r = eng.solve("chol_update", V, factor_token=tok)
        else:
            r = eng.solve("posv_cached", A, B, factor_token=tok)
        assert r.ok, r.error
        requests += 1
    f1 = eng.factor_stats()
    hits = f1["hits"] - f0["hits"]
    lookups = hits + f1["misses"] - f0["misses"]
    if ledger:
        eng.emit_stats(ledger)
    return {
        "requests": requests,
        "hit_rate": round(hits / lookups, 4),
        "recompiles": eng.cache_stats()["compiles"] - c0,
    }


def session(args) -> dict:
    """Bench streaming state-space sessions (serve/sessions.py +
    models/blocktri.extend/contract): the steady-state sliding-window
    cycle — append --slide new blocks onto the resident chain factor,
    contract the --slide oldest away — measured incrementally against
    REFACTOR-FROM-SCRATCH of the slid window, the only alternative a
    cache-less server has (docs/SERVING.md 'Streaming sessions': the
    wire carries only the new blocks, so serving without a resident
    factor means re-factoring all nblocks).  contract() is a pure slice
    (zero flops), so the incremental cycle costs one extend(slide) and
    the structural win is ~nblocks/slide — the round-19 flagship gate:
    >= 5x at nblocks=64, block=128, slide=8.

    The f64-NumPy residual gates are always-on (the bench-arrowhead
    discipline): the slid-window factor — resident chain extended then
    contracted, exactly the serve composition — must solve the
    MARGINALIZED window matrix (head D ← L_k·L_kᵀ, head coupling zero;
    models/blocktri.contract docstring) to working-precision tolerance,
    both solve and factor-reconstruction residuals.  The replay pin
    holds the docstring's bitwise claim: re-extending the truncated
    chain from the retained carry reproduces the contracted factor's
    trailing blocks exactly (max |Δ| == 0).

    --min-hit-rate additionally runs the 50-request mixed session serve
    workload (bursty arrivals, long-tail lifetimes, sliding append/
    contract/solve cycles over all three accuracy tiers) through a real
    SolveEngine + SessionManager, gating post-warmup session hit-rate
    >= the floor AND zero steady-state executable recompiles (session
    residency is host-side state keyed by session id — session churn
    must never trigger a compile), and emitting the serve:session_stats
    ledger record ``obs serve-report --min-session-hit-rate /
    --max-reseeds`` re-gates."""
    from capital_tpu.models import blocktri as bt_mod

    dtype = jnp.dtype(args.dtype)
    grid = Grid.square(c=1, devices=jax.devices()[:1])
    prec = _precision(args, dtype)
    nblocks, b, batch, nrhs = args.nblocks, args.block, args.batch, args.nrhs
    slide = args.slide
    if not 0 < slide < nblocks:
        sys.exit(f"session: --slide {slide} must be in (0, --nblocks "
                 f"{nblocks})")
    impl = args.impl
    if impl == "auto" and jax.default_backend() != "tpu":
        # the bench-blocktri honest-wall pin: off-TPU 'auto' is the xla
        # scan, never the pallas interpreter
        impl = "xla"

    import numpy as np

    # nblocks + slide chain blocks: the first nblocks seed the resident
    # window, the last slide are the streamed-in extension (its leading
    # coupling C[:, nblocks] is LIVE — it ties the new blocks to the old
    # window tail, the session_append contract)
    (Dj, Cj, _), (Dn, Cn, _) = _blocktri_batch(nblocks + slide, b, batch,
                                               nrhs, dtype, seed=7)
    ext_fn = jax.jit(lambda d, c, carry: bt_mod.extend(
        d, c, carry, precision=prec, impl=impl))
    fac_fn = jax.jit(lambda d, c: bt_mod.factor(
        d, c, precision=prec, impl=impl))

    L0, Wt0, info0 = jax.block_until_ready(
        fac_fn(Dj[:, :nblocks], Cj[:, :nblocks]))
    if int(jnp.sum(info0 != 0)):
        sys.exit("session: seed window factorization reports info != 0")
    carry = L0[:, -1]
    Dext = jax.block_until_ready(Dj[:, nblocks:])
    Cext = jax.block_until_ready(Cj[:, nblocks:])

    calls = max(args.iters, 3)
    # incremental side: ONE extend(slide) per cycle — contract is a pure
    # slice with no device work, so it contributes nothing to time
    samples = harness.latency_samples(
        lambda: ext_fn(Dext, Cext, carry), calls=calls, warmup=3)
    # baseline: refactor the slid nblocks-window from scratch (factor()
    # zeroes the head coupling itself, so the operand slice is exact)
    bsamples = harness.latency_samples(
        lambda: fac_fn(Dj[:, slide:], Cj[:, slide:]), calls=calls,
        warmup=1)
    # min-of-samples both sides: algorithms, not scheduler noise
    # (the bench-update estimator rationale)
    t = min(samples)
    t_base = min(bsamples)
    speedup = t_base / t
    print(f"# speedup {speedup:.1f}x vs refactor-from-scratch at "
          f"nblocks={nblocks} b={b} slide={slide} "
          f"(refactor {t_base / batch * 1e3:.2f} ms/problem, "
          f"append {t / batch * 1e3:.3f} ms/problem)")

    # ---- always-on correctness gates (f64 NumPy side) ----------------------
    Lx, Wtx, infox = jax.block_until_ready(ext_fn(Dext, Cext, carry))
    if int(jnp.sum(infox != 0)):
        sys.exit("session: extend of the streamed blocks reports info != 0")
    Lfull = jnp.concatenate([L0, Lx], axis=1)
    Wtfull = jnp.concatenate([Wt0, Wtx], axis=1)
    Lc, Wtc = bt_mod.contract(Lfull, Wtfull, slide)
    # replay pin (the contract docstring's bitwise claim): re-extending
    # the truncated chain — head coupling LIVE, carried from the retained
    # L_{slide-1} — reproduces every factor block the contract kept, bit
    # for bit
    Lr, Wtr, infor = jax.block_until_ready(
        ext_fn(Dj[:, slide:], Cj[:, slide:], Lfull[:, slide - 1]))
    if int(jnp.sum(infor != 0)):
        sys.exit("session: replay refactor reports info != 0")
    replay_delta = max(
        float(jnp.max(jnp.abs(Lr - Lc))),
        float(jnp.max(jnp.abs(Wtr - Wtc))),
    )
    print(f"# contract replay pin: max |Δ| = {replay_delta:g} "
          f"(extend-replay of the truncated chain vs contracted factor)")
    if replay_delta != 0.0:
        sys.exit(
            f"contract replay pin failed: trailing factor blocks differ "
            f"from the truncated-chain refactor by {replay_delta:g} "
            "(contract must be a pure slice)"
        )
    # the MARGINALIZED window matrix the contracted factor answers for
    # (f64 masters; head diagonal from the f64 cast of the factor block)
    Lcn = np.asarray(Lc, np.float64)
    Wcn = np.asarray(Wtc, np.float64).transpose(0, 1, 3, 2)  # W_i
    Dw = Dn[:, slide:].copy()
    Dw[:, 0] = Lcn[:, 0] @ Lcn[:, 0].transpose(0, 2, 1)
    Cw = Cn[:, slide:].copy()
    Cw[:, 0] = 0.0
    Ad = _blocktri_dense(Dw, Cw)
    rng = np.random.default_rng(19)
    Bn = rng.standard_normal((batch, nblocks, b, nrhs))
    Bj = jax.block_until_ready(jnp.asarray(Bn, dtype))
    X = jax.block_until_ready(jax.jit(
        lambda l, w, rhs: bt_mod.solve(l, w, rhs, precision=prec,
                                       impl=impl))(Lc, Wtc, Bj))
    n = nblocks * b
    Xn = np.asarray(X, np.float64).reshape(batch, n, nrhs)
    Bd = Bn.reshape(batch, n, nrhs)
    tol = residual.tolerance(dtype)
    worst = max(
        float(np.linalg.norm(Ad[i] @ Xn[i] - Bd[i])
              / np.linalg.norm(Bd[i]))
        for i in range(batch)
    )
    _gate("session_solve_residual", worst, tol)
    # factor reconstruction residual of the contracted chain vs the
    # marginalized window (blockwise, the bench-blocktri reconstruction)
    R = np.zeros_like(Ad)
    for i in range(nblocks):
        sl = slice(i * b, (i + 1) * b)
        R[:, sl, sl] = Lcn[:, i] @ Lcn[:, i].transpose(0, 2, 1)
        if i:
            up = slice((i - 1) * b, i * b)
            R[:, sl, sl] += Wcn[:, i] @ Wcn[:, i].transpose(0, 2, 1)
            blk = Wcn[:, i] @ Lcn[:, i - 1].transpose(0, 2, 1)
            R[:, sl, up] = blk
            R[:, up, sl] = blk.transpose(0, 2, 1)
    _gate(
        "session_factor_residual",
        float(np.linalg.norm(R - Ad) / np.linalg.norm(Ad)),
        tol,
    )

    smoke = None
    if args.min_hit_rate:
        smoke = _session_serve_workload(min(b, 16), dtype,
                                        ledger=args.ledger)
        print(f"# serve workload: {smoke['requests']} requests over "
              f"{smoke['sessions']} sessions, session hit_rate "
              f"{smoke['hit_rate']:.3f}, {smoke['reseeds']} reseeds, "
              f"{smoke['recompiles']} steady-state recompiles")

    # useful flops of the incremental side: extend(slide) chain work
    flops = batch * slide * (b**3 / 3.0 + 3.0 * b**3)
    rec = harness.report(
        "session_speedup", t, flops, dtype, nblocks=nblocks, block=b,
        slide=slide, batch=batch, nrhs=nrhs, impl=impl, grid=repr(grid),
        speedup=round(speedup, 2),
        refactor_ms=round(t_base / batch * 1e3, 3),
        append_ms=round(t / batch * 1e3, 4),
        wall_ms={k: round(v * 1e3, 4)
                 for k, v in percentiles(samples).items()},
        **({"serve_workload": smoke} if smoke else {}),
    )
    cfg = {"op": "session_append", "impl": impl, "nblocks": nblocks,
           "block": b, "slide": slide}
    gates = []
    if args.min_speedup and speedup < args.min_speedup:
        gates.append(
            f"speedup gate failed: {speedup:.1f}x < {args.min_speedup}x "
            f"vs refactor-from-scratch at nblocks={nblocks} b={b} "
            f"slide={slide}"
        )
    if smoke and smoke["hit_rate"] < args.min_hit_rate:
        gates.append(
            f"session residency gate failed: hit_rate "
            f"{smoke['hit_rate']:.3f} < {args.min_hit_rate}"
        )
    if smoke and smoke["recompiles"]:
        gates.append(
            f"zero-recompile gate failed: {smoke['recompiles']} executable "
            "compiles during steady-state session traffic"
        )
    _ledger_append(args, rec, name="session", grid=grid, dtype=dtype,
                   cfg=cfg)
    if gates:
        sys.exit("; ".join(gates))
    return rec


def _session_serve_workload(b: int, dtype, ledger=None) -> dict:
    """The 50-request mixed session workload (bench-session gate): bursty
    session arrivals (seeded RNG, 1-3 sessions per burst), long-tail
    lifetimes (geometric cycle counts — most sessions die young, a few
    live many sliding-window cycles), each cycle one append(slide) +
    contract(slide) + solve at a mixed accuracy tier.  Returns the delta
    counters the caller gates on — session hit-rate over THIS traffic and
    executable compiles after the one-time per-bucket warmup — and, when
    `ledger` is given, appends the manager's serve:session_stats record
    plus the engine's serve:request_stats record so ``obs serve-report
    --min-session-hit-rate / --max-reseeds`` has records to gate."""
    import numpy as np

    from capital_tpu.serve import sessions as sessions_mod
    from capital_tpu.serve.engine import ServeConfig, SolveEngine

    rng = np.random.default_rng(17)
    nb_w, nb_s, nrhs = 8, 4, 2  # window blocks, slide blocks, RHS cols
    cfg = ServeConfig(nblocks_buckets=(nb_s, nb_w), block_buckets=(b,),
                      nrhs_buckets=(nrhs,), max_batch=2, max_delay_s=0.0,
                      oversize="reject")
    eng = SolveEngine(cfg=cfg)
    mgr = sessions_mod.SessionManager(eng)

    def chain(k):
        G = rng.standard_normal((k, b, b))
        D = (G @ G.transpose(0, 2, 1) / b + 3.0 * np.eye(b)).astype(dtype)
        C = (0.3 / np.sqrt(b)
             * rng.standard_normal((k, b, b))).astype(dtype)
        return D, C

    def rhs():
        return rng.standard_normal((nb_w, b, nrhs)).astype(dtype)

    # warm every program the mix touches (open@nb_w, append@nb_s, solve
    # at all three tiers); everything after this must hit the executable
    # cache — session residency is host-side state, so session churn must
    # never compile
    D, C = chain(nb_w)
    assert mgr.open("warm", D, C).ok
    Da, Ca = chain(nb_s)
    assert mgr.append("warm", Da, Ca).ok
    assert mgr.contract("warm", nb_s).ok
    for tier in ("balanced", "fast", "guaranteed"):
        r = mgr.solve("warm", rhs(), accuracy_tier=tier)
        assert r.ok, r.error
    assert mgr.close("warm").ok
    c0 = eng.cache_stats()["compiles"]
    h0, m0 = mgr.hits, mgr.misses

    tiers = ("balanced", "balanced", "balanced", "fast", "guaranteed")
    active: list[list] = []
    sid_n = 0
    requests = 0
    while requests < 50:
        if not active or (len(active) < 6 and rng.random() < 0.3):
            # burst arrival: 1-3 sessions open back to back
            for _ in range(int(rng.integers(1, 4))):
                sid = f"s{sid_n}"
                sid_n += 1
                D, C = chain(nb_w)
                assert mgr.open(sid, D, C).ok
                requests += 1
                # long-tail lifetime in sliding-window cycles
                active.append([sid, 1 + int(rng.geometric(0.35))])
        i = int(rng.integers(len(active)))
        sid = active[i][0]
        Da, Ca = chain(nb_s)
        assert mgr.append(sid, Da, Ca).ok
        assert mgr.contract(sid, nb_s).ok
        r = mgr.solve(sid, rhs(),
                      accuracy_tier=tiers[int(rng.integers(len(tiers)))])
        assert r.ok, r.error
        requests += 3
        active[i][1] -= 1
        if active[i][1] <= 0:
            assert mgr.close(active.pop(i)[0]).ok
            requests += 1
    for sid, _ in active:
        assert mgr.close(sid).ok
    recompiles = eng.cache_stats()["compiles"] - c0
    if ledger:
        mgr.emit_session_stats(ledger)
        eng.emit_stats(ledger)
    hits = mgr.hits - h0
    lookups = hits + mgr.misses - m0
    st = mgr.stats()
    return {
        "requests": requests,
        "sessions": sid_n,
        "hit_rate": round(hits / lookups, 4) if lookups else 1.0,
        "reseeds": st["reseeds"],
        "recompiles": recompiles,
    }


def refine(args) -> dict:
    """Bench mixed-precision iterative refinement (robust/refine + the
    serve accuracy tiers): the guaranteed-tier posv program — factor one
    precision down, Wilkinson residual/correction sweeps at the request
    precision — against the straight request-dtype factor, at matched
    residual on cond ~1e5 masters.

    The --min-speedup gate is on the FACTOR PHASE (potrf at the tier's
    factor dtype vs potrf at the request dtype): that ratio is where the
    mixed-precision advantage lives and what scales with the rig's
    narrow:wide throughput gap — this 1-core CPU's f32:f64 LAPACK gap
    measures ~1.9x at n=1024, a TPU MXU's bf16:f32 gap is ~4-8x and its
    f32-vs-emulated-f64 gap far larger.  End-to-end guaranteed-vs-
    balanced latency is measured and REPORTED UNGATED in the same record
    (`end_to_end_speedup`): on this rig it lands below 1.0 — the fused
    LAPACK f64 posv baseline sits within that same ~1.9x of the f32
    factor, while every sweep pays a skinny-RHS triangular solve that
    XLA's CPU backend runs at ~2.4 GFLOP/s — and a bench that hid that
    behind the phase number would be lying about the serving economics.
    The accuracy half is gated both ways: --max-resid-ratio bounds the
    refined normwise backward error as a multiple of the straight wide
    factor's (round-14 gate: 10; measured ~0.9-1.8x, i.e. genuinely
    f64-grade answers), and --validate adds the absolute residual gate
    plus all-converged / zero-info checks.

    Also rides: the TSQR escalation probe — a cond 1e12 tall-skinny
    factor through recovery.tsqr_escalate, --validate gating ortho
    <= 1e-13, the regime where the gram-forming CQR family cannot
    recover (docs/ROBUSTNESS.md escalation ladder) — and the three-tier
    serve smoke: mixed balanced/fast/guaranteed traffic through a real
    SolveEngine with any steady-state recompile failing the run
    (precision is a bucket dimension, never a recompile), emitting the
    serve:request_stats record whose refine block
    ``obs serve-report --max-refine-iters/--min-converged-frac``
    re-gates."""
    from capital_tpu.ops import lapack as lapack_mod
    from capital_tpu.robust import recovery
    from capital_tpu.robust import refine as refine_mod
    from capital_tpu.serve import api

    # the guaranteed tier's correction dtype and the TSQR escalation
    # dtype are both f64 for the flagship request dtypes; without x64 the
    # whole bench would silently measure f32-vs-f32
    jax.config.update("jax_enable_x64", True)
    dtype = jnp.dtype(args.dtype)
    grid = Grid.square(c=1, devices=jax.devices()[:1])
    n, nrhs, batch = args.n, args.nrhs, args.batch
    tp = refine_mod.plan("guaranteed", dtype)
    fd, cd = jnp.dtype(tp.factor_dtype), jnp.dtype(tp.correction_dtype)

    import numpy as np

    # cond ~1e5 SPD masters (f64 NumPy side): enough to make the narrow
    # factor's raw answer visibly wrong (f32 backward error ~cond·u32)
    # so convergence is a measured property, not a well-conditioned gift
    rng = np.random.default_rng(17)
    eigs = np.logspace(0.0, -5.0, n)
    A = np.empty((batch, n, n))
    for i in range(batch):
        Q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        A[i] = (Q * eigs) @ Q.T
    A = 0.5 * (A + A.transpose(0, 2, 1))
    Bm = rng.standard_normal((batch, n, nrhs))
    Aj = jax.block_until_ready(jnp.asarray(A, dtype))
    Bj = jax.block_until_ready(jnp.asarray(Bm, dtype))

    prec = _precision(args, dtype)
    base_fn = jax.jit(api.batched("posv", precision=prec, impl="vmap"))
    ref_fn = jax.jit(api.batched("posv", precision=prec, impl="vmap",
                                 tier="guaranteed"))
    calls = max(args.iters, 3)

    # --- factor phase: the gated number -------------------------------
    potrf_fn = jax.jit(jax.vmap(
        lambda m: lapack_mod.potrf(m, uplo="U", with_info=True)))
    An = jax.block_until_ready(Aj.astype(fd))
    ws = harness.latency_samples(lambda: potrf_fn(Aj), calls=calls, warmup=1)
    ns = harness.latency_samples(lambda: potrf_fn(An), calls=calls, warmup=1)
    t_wide, t_narrow = min(ws), min(ns)
    factor_speedup = t_wide / t_narrow
    print(f"# factor-phase speedup {factor_speedup:.2f}x "
          f"({fd} potrf {t_narrow * 1e3:.1f} ms vs {dtype} potrf "
          f"{t_wide * 1e3:.1f} ms, n={n} batch={batch})")

    # --- end to end: measured, reported, ungated ----------------------
    bs = harness.latency_samples(lambda: base_fn(Aj, Bj),
                                 calls=calls, warmup=2)
    rs = harness.latency_samples(lambda: ref_fn(Aj, Bj),
                                 calls=calls, warmup=2)
    t_base, t_ref = min(bs), min(rs)
    end_to_end = t_base / t_ref
    print(f"# end-to-end guaranteed {t_ref * 1e3:.1f} ms vs balanced "
          f"{t_base * 1e3:.1f} ms ({end_to_end:.2f}x, ungated — the "
          f"sweeps price in at this backend's potrs throughput)")

    # --- matched residual (f64 NumPy side, the bench-blocktri posture) -
    Xb, info_b = jax.block_until_ready(base_fn(Aj, Bj))
    Xr, it_r, conv_r, _resid, info_r = jax.block_until_ready(
        ref_fn(Aj, Bj))
    iters = int(jnp.max(it_r))

    def _bwerr(Xn):
        worst = 0.0
        for i in range(batch):
            r = A[i] @ Xn[i] - Bm[i]
            denom = (np.linalg.norm(A[i]) * np.linalg.norm(Xn[i])
                     + np.linalg.norm(Bm[i]) + np.finfo(np.float64).tiny)
            worst = max(worst, float(np.linalg.norm(r) / denom))
        return worst

    err_base = _bwerr(np.asarray(Xb, np.float64))
    err_ref = _bwerr(np.asarray(Xr, np.float64))
    resid_ratio = err_ref / max(err_base, np.finfo(np.float64).tiny)
    print(f"# matched residual: refined {err_ref:.3e} vs wide-factor "
          f"{err_base:.3e} (ratio {resid_ratio:.2f}) after {iters} "
          f"sweep(s)")

    # --- TSQR escalation probe: cond 1e12, past the CQR-family envelope
    mt, kt = 2048, 64
    Ut, _ = np.linalg.qr(rng.standard_normal((mt, kt)))
    Vt, _ = np.linalg.qr(rng.standard_normal((kt, kt)))
    At = (Ut * np.logspace(0.0, -12.0, kt)) @ Vt.T
    _Qt, _Rt, ortho = recovery.tsqr_escalate(
        jnp.asarray(At, jnp.float32), precision=prec)
    tsqr_ortho = float(ortho)
    print(f"# tsqr escalation: ortho {tsqr_ortho:.3e} at cond 1e12 "
          f"(m={mt} k={kt}, escalation dtype "
          f"{recovery.escalation_dtype(jnp.float32)})")

    if args.validate:
        # conv_r ships as the executor's stacked extras (integer 0/1)
        nonconv = int(conv_r.size) - int(jnp.count_nonzero(conv_r))
        if nonconv:
            sys.exit(f"validation failed: {nonconv} guaranteed-tier "
                     "problem(s) did not converge")
        if int(jnp.sum(info_b != 0)) or int(jnp.sum(info_r != 0)):
            sys.exit("validation failed: nonzero factorization info flag")
        _gate("refine_residual", err_ref, residual.tolerance(dtype))
        _gate("tsqr_ortho", tsqr_ortho, 1e-13)

    smoke = _refine_serve_smoke(min(n, 256), min(nrhs, 4), dtype,
                                ledger=args.ledger)
    print(f"# serve smoke: {smoke['requests']} mixed-tier requests, "
          f"{smoke['recompiles']} steady-state recompiles")

    # useful flops of one guaranteed batch: the narrow factor plus
    # (X0 + iters) solve/residual passes — comparable to the baseline's
    # straight n³/3 factor
    flops = batch * (n ** 3 / 3.0 + (iters + 1) * 4.0 * n * n * nrhs)
    rec = harness.report(
        "refine_speedup", t_ref, flops, dtype, n=n, nrhs=nrhs,
        batch=batch, grid=repr(grid),
        factor_dtype=str(fd), correction_dtype=str(cd),
        speedup=round(factor_speedup, 2),
        factor_wide_ms=round(t_wide * 1e3, 2),
        factor_narrow_ms=round(t_narrow * 1e3, 2),
        end_to_end_speedup=round(end_to_end, 3),
        baseline_ms=round(t_base * 1e3, 2),
        refined_ms=round(t_ref * 1e3, 2),
        resid_ratio=round(resid_ratio, 3),
        iters=iters,
        tsqr_ortho=tsqr_ortho,
        wall_ms={kk: round(v * 1e3, 3)
                 for kk, v in percentiles(rs).items()},
        serve_smoke=smoke,
    )
    cfg = {"op": "posv", "tier": "guaranteed", "n": n, "nrhs": nrhs,
           "factor_dtype": str(fd), "correction_dtype": str(cd)}
    gates = []
    if args.min_speedup and factor_speedup < args.min_speedup:
        gates.append(
            f"factor-phase speedup gate failed: {factor_speedup:.2f}x < "
            f"{args.min_speedup}x ({fd} vs {dtype} potrf at n={n})"
        )
    if args.max_resid_ratio and resid_ratio > args.max_resid_ratio:
        gates.append(
            f"matched-residual gate failed: refined backward error is "
            f"{resid_ratio:.2f}x the wide factor's > "
            f"{args.max_resid_ratio}x"
        )
    if smoke["recompiles"]:
        gates.append(
            f"zero-recompile gate failed: {smoke['recompiles']} "
            "executable compiles during steady-state mixed-tier traffic"
        )
    _ledger_append(args, rec, name="refine", grid=grid, dtype=dtype,
                   cfg=cfg)
    if gates:
        sys.exit("; ".join(gates))
    return rec


def _refine_serve_smoke(n: int, nrhs: int, dtype, ledger=None) -> dict:
    """The mixed-tier serve smoke (bench-refine gate): warm one posv
    bucket per accuracy tier through a real SolveEngine, then drive 24
    requests cycling balanced/fast/guaranteed and count executable
    compiles after warmup — the zero-recompile invariant with precision
    as a bucket dimension.  When `ledger` is given, also appends the
    engine's serve:request_stats record (carrying the refine block the
    guaranteed requests populate) so ``obs serve-report
    --max-refine-iters/--min-converged-frac`` has a record to gate."""
    import numpy as np

    from capital_tpu.serve.engine import ServeConfig, SolveEngine

    rng = np.random.default_rng(23)
    cfg = ServeConfig(buckets=(n,), nrhs_buckets=(nrhs,), max_batch=2,
                      max_delay_s=0.0, oversize="reject")
    eng = SolveEngine(cfg=cfg)
    X = rng.standard_normal((n, n))
    A = np.asarray((X @ X.T / n + 3.0 * np.eye(n)), dtype)
    B = np.asarray(rng.standard_normal((n, nrhs)), dtype)
    tiers = ("balanced", "fast", "guaranteed")
    for t in tiers:  # the one-time per-(bucket, tier) warmup compiles
        assert eng.solve("posv", A, B, accuracy_tier=t).ok
    c0 = eng.cache_stats()["compiles"]
    requests = 0
    while requests < 24:
        r = eng.solve("posv", A, B,
                      accuracy_tier=tiers[requests % len(tiers)])
        assert r.ok, r.error
        requests += 1
    if ledger:
        eng.emit_stats(ledger)
    return {
        "requests": requests,
        "recompiles": eng.cache_stats()["compiles"] - c0,
    }


def posv(args):
    return _small_solve(args, "posv")


def lstsq(args):
    return _small_solve(args, "lstsq")


DRIVERS = {
    "cholinv": cholinv,
    "cacqr": cacqr,
    "summa_gemm": summa_gemm,
    "rectri": rectri,
    "newton": newton,
    "spd_inverse": spd_inverse,
    "trsm": trsm,
    "posv": posv,
    "lstsq": lstsq,
    "blocktri": blocktri,
    "arrowhead": arrowhead,
    "update": update,
    "refine": refine,
    "session": session,
}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="capital_tpu.bench")
    p.add_argument("driver", choices=[*DRIVERS, "suite"])
    p.add_argument("--n", type=int, default=4096)
    p.add_argument("--m", type=int, default=65536)
    p.add_argument("--k", type=int, default=4096)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--iters", type=int, default=3)
    p.add_argument(
        "--bc", type=int, default=0,
        help="base-case dim (0 = auto: cholinv/spd pick 256 below the "
        "n<=8192 crossover, 512 above; every other driver keeps 512)",
    )
    p.add_argument("--split", type=int, default=1)
    p.add_argument(
        "--mode", default="auto", choices=["auto", "xla", "explicit", "pallas"],
        help="SUMMA mode; auto = pallas on one device, xla on a mesh",
    )
    p.add_argument(
        "--balance", default="block",
        choices=["block", "tile_cyclic", "tile_cyclic_persistent"],
        help="cholinv: explicit-mode triangular work balance; "
        "tile_cyclic_persistent permutes once per factor lifetime instead "
        "of per trmm/syrk call (docs/DISTRIBUTED.md)",
    )
    p.add_argument("--variant", type=int, default=2, help="1=CQR, 2=CQR2")
    p.add_argument("--regime", default="auto", choices=["auto", "1d", "dist"])
    p.add_argument("--c", type=int, default=1, help="replication depth")
    p.add_argument(
        "--layout", type=int, default=0, choices=[0, 1, 2],
        help="device->grid-coordinate layout (reference topology.h:77-123)",
    )
    p.add_argument(
        "--chunks", type=int, default=0,
        help="explicit-SUMMA bcast pipelining chunks (reference num_chunks)",
    )
    p.add_argument("--devices", type=int, default=0, help="limit device count")
    p.add_argument(
        "--precision", default=None, choices=["default", "high", "highest"],
        help="matmul precision override for f32 operands: 'high' (3-pass "
        "bf16) exists only on the XLA paths — Mosaic kernels round it up "
        "to 'highest' (6-pass); default: 'highest' for f32, None for bf16",
    )
    p.add_argument(
        "--device-check", action="store_true",
        help="measure the device-counter op total of the timed loop and "
        "re-measure (then floor) walls that land below it — the suite's "
        "drift guard; on by default under the suite driver on TPU",
    )
    p.add_argument("--newton-iters", type=int, default=30)
    p.add_argument(
        "--fused-g", type=int, default=0,
        help="cacqr: in-kernel column split of the fused tall-pass kernels "
        "(0 = auto, qr_fused.pick_g)",
    )
    p.add_argument(
        "--leaf", default="invert", choices=["invert", "solve"],
        help="trsm leaf policy (TrsmConfig.leaf)",
    )
    p.add_argument(
        "--batch-below", type=int, default=-1,
        help="rectri batched-level-sweep threshold (-1 = config default)",
    )
    p.add_argument("--no-complete-inv", action="store_true")
    p.add_argument(
        "--robust", action="store_true",
        help="cacqr: factor under RobustConfig (breakdown detection + "
        "shifted-CholeskyQR recovery, docs/ROBUSTNESS.md); the status "
        "scalars ride the report and the ledger record",
    )
    p.add_argument("--validate", action="store_true")
    p.add_argument(
        "--batch", type=int, default=8,
        help="posv/lstsq: problems per bucket batch (serve max_batch)",
    )
    p.add_argument(
        "--nrhs", type=int, default=1,
        help="posv/lstsq: RHS columns per problem",
    )
    p.add_argument(
        "--latency", action="store_true",
        help="posv/lstsq: per-call latency mode — p50/p95/p99 wall_ms via "
        "harness.latency_samples/percentiles (one dispatch per sample, the "
        "serving protocol) and a bench:latency ledger record, instead of "
        "the amortized TFLOP/s row",
    )
    p.add_argument(
        "--calls", type=int, default=32,
        help="posv/lstsq --latency: number of per-call samples",
    )
    p.add_argument(
        "--small-impl", default="auto",
        choices=["auto", "vmap", "pallas", "pallas_split"],
        help="posv/lstsq: batched implementation (api.batched impl switch; "
        "auto resolves from the bucket shape like serve does)",
    )
    p.add_argument(
        "--nblocks", type=int, default=8,
        help="blocktri: chain length (diagonal blocks per problem)",
    )
    p.add_argument(
        "--block", type=int, default=32,
        help="blocktri: block size b (each diagonal block is b x b; "
        "n = nblocks * block)",
    )
    p.add_argument(
        "--border", type=int, default=32,
        help="arrowhead: border rank s (rows of the coupling block-row "
        "and the dense corner; n = nblocks * block + border)",
    )
    p.add_argument(
        "--slide", type=int, default=8,
        help="session: sliding-window stride in blocks — each steady-state "
        "cycle appends this many new blocks and contracts this many old "
        "ones away (must be in (0, --nblocks))",
    )
    p.add_argument(
        "--impl", default="auto",
        choices=["auto", "pallas", "xla", "partitioned"],
        help="blocktri: chain implementation; auto = pallas scan on TPU, "
        "xla scan elsewhere (off-TPU pallas is the interpreter — serve "
        "keeps it there for AOT-cache persistability, a bench must not); "
        "partitioned = the Spike chain driver, benched A/B against the "
        "sequential scan with latency + jaxpr-depth columns",
    )
    p.add_argument(
        "--partitions", type=int, default=0,
        help="blocktri --impl partitioned: requested partition count "
        "(0 = resolve_partitions default, the largest divisor of nblocks "
        "<= sqrt(nblocks); requests decrement to a valid divisor)",
    )
    p.add_argument(
        "--min-depth-reduction", type=float, default=0.0,
        help="blocktri --impl partitioned: fail the run when the measured "
        "jaxpr sequential scan-depth reduction vs the sequential impl "
        "lands below this factor (the round-13 gate: 4 at nblocks=64) or "
        "when partitioned results drift past the pinned parity tolerance",
    )
    p.add_argument(
        "--min-speedup", type=float, default=0.0,
        help="blocktri/arrowhead: fail the run when the measured "
        "per-problem speedup vs equal-n dense posv lands below this "
        "factor (the round-11 flagship gate: 25 at nblocks=64, block=128, "
        "f32; the round-15 arrowhead gate: 10 at nblocks=64, block=128, "
        "border=32, f32); "
        "refine: the same flag gates the FACTOR-PHASE narrow-vs-wide "
        "potrf speedup (the round-14 gate: 1.5 at n=1024 f64 on the CPU "
        "rig — end-to-end latency is reported ungated, see the driver "
        "docstring); "
        "session: gates the incremental append(slide) vs "
        "refactor-from-scratch speedup (the round-19 gate: 5 at "
        "nblocks=64, block=128, slide=8)",
    )
    p.add_argument(
        "--max-resid-ratio", type=float, default=0.0,
        help="refine: fail when the guaranteed-tier normwise backward "
        "error exceeds this multiple of the straight request-dtype "
        "factor's (the matched-residual half of the round-14 gate: 10; "
        "0 = report only)",
    )
    p.add_argument(
        "--min-hit-rate", type=float, default=0.0,
        help="update: run the 50-request mixed chol_update/posv_cached "
        "serve smoke and fail below this residency hit-rate (the round-12 "
        "gate: 0.9) or on any steady-state executable recompile; "
        "session: the same flag gates the 50-request mixed session "
        "workload (the round-19 gate: 0.85, zero recompiles)",
    )
    p.add_argument(
        "--phase-attr", action="store_true",
        help="cholinv: decompose the measured wall into per-phase seconds "
        "(bench.trace.phase_attribution) — bubble_frac joins the report "
        "line and the phase_seconds split rides the ledger record, "
        "re-readable via obs trace-report",
    )
    p.add_argument(
        "--ledger", default=None,
        help="append one unified obs ledger record per run (manifest + "
        "model costs + compiled-program audit + measured + residuals) to "
        "this JSONL file; query with python -m capital_tpu.obs diff",
    )
    p.add_argument("--scale", type=int, default=1, help="suite: divide problem sizes")
    p.add_argument(
        "--platform", default=None, help=PLATFORM_HELP,
    )
    p.add_argument(
        "--host-devices", type=int, default=0,
        help="virtual CPU device count (--xla_force_host_platform_device_count)",
    )
    return p


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    if args.host_devices:
        import os

        flags = [
            f
            for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={args.host_devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    if args.driver == "suite":
        from capital_tpu.bench import suite

        suite.run(args)
    else:
        DRIVERS[args.driver](args)


if __name__ == "__main__":
    main()
