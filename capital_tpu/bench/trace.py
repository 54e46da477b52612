"""Per-phase device-time budgets from real hardware traces.

The reference ships critter's symbol decomposition (autotune/util.h:63-127:
per-phase cp-comp/cp-comm columns); the runtime counterpart here is a
`jax.profiler` device trace of the actual benchmark loop, bucketed by the
``CI::*`` / ``CQR::*`` phase scopes that `tracing.scope` stamps into every
HLO op's metadata.  Host wall clocks include dispatch and host jitter;
per-kernel device *own time* from the trace is immune to both, so this is
the tool that settles where a flagship millisecond actually goes.

CLI::

    python -m capital_tpu.bench.trace cholinv --n 16384 [--bc 512] [--iters 3]
    python -m capital_tpu.bench.trace cacqr --m 1048576 --n 1024

prints one line per phase bucket (device ms per iteration, % of total) plus
a JSON record, from a trace of `iters` in-jit iterations of the same loop
the flagship bench runs.

Parsing: the xplane protobuf's "XLA Ops" line carries one event per HLO op
execution with its self (own) duration; each op's metadata carries the
named_scope chain (``CI.trsm`` etc.), searched longest-scope-first so
nested scopes attribute to the innermost phase, matching critter's
innermost-symbol attribution.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import tempfile
import time

import jax
import jax.numpy as jnp

from capital_tpu.parallel import summa
from capital_tpu.utils import residual, tracing


def _phase_tags() -> tuple[str, ...]:
    """Named-scope (dot) forms of the registered phase tags.  Derived from
    tracing.PHASE_REGISTRY — the single source of truth — so a phase added
    to scope() can never be silently bucketed into 'other' here (the fate
    of RT::batch_write under the old hardcoded copy of this tuple).
    Re-evaluated lazily so in-process register_phase() calls are seen."""
    return tuple(t.replace("::", ".") for t in tracing.PHASE_REGISTRY)


#: flagship phase buckets (dot form).  An op whose metadata mentions none
#: of these lands in 'copy' / 'fusion' / 'other' by HLO kind — the
#: catch-alls that caught the round-2 relayout-copy regressions.
PHASE_TAGS = _phase_tags()


def _own_times(line):
    """(metadata_id, own_duration_ps) per event: the 'XLA Ops' line is
    hierarchical (a `while` event spans its whole body), so an op's own time
    is its duration minus the durations of the events it directly contains —
    a stack sweep over (offset, duration)-sorted events.  Accepts either an
    XLine or a pre-filtered event list (the host-plane fallback filters
    thread-pool bookkeeping events out BEFORE the sweep, so a listener
    region can't absorb a real op's duration as its child)."""
    evs = sorted(
        getattr(line, "events", line),
        key=lambda e: (e.offset_ps, -e.duration_ps),
    )
    out = []
    stack = []  # [end_ps, metadata_id, duration_ps, child_sum]
    for e in evs:
        start, dur = e.offset_ps, e.duration_ps
        while stack and stack[-1][0] <= start:
            fin = stack.pop()
            own = fin[2] - fin[3]
            if stack:
                stack[-1][3] += fin[2]
            out.append((fin[1], own))
        while stack and start + dur > stack[-1][0]:
            # overlapping, not nested (async tails) — close EVERY stacked
            # ancestor the new event outlasts, not just the top, so a tail
            # spanning several ancestors doesn't leave the deeper ones open
            # to absorb the overlap into the wrong phase bucket
            fin = stack.pop()
            own = fin[2] - fin[3]
            if stack:
                stack[-1][3] += fin[2]
            out.append((fin[1], own))
        stack.append([start + dur, e.metadata_id, dur, 0])
    while stack:
        fin = stack.pop()
        own = fin[2] - fin[3]
        if stack:
            stack[-1][3] += fin[2]
        out.append((fin[1], own))
    return out


def _iter_xla_op_events(space):
    """Yield (plane_name, metadata, own_duration_ps, stat_metadata, is_async)
    for every device XLA-op event.  The 'Async XLA Ops' line reports
    in-flight occupancy of DMAs that overlap compute — kept separate
    (occupancy is not additive with op own time)."""
    for plane in space.planes:
        if "TPU" not in plane.name:
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for mid, own_ps in _own_times(line):
                    yield (plane.name, plane.event_metadata.get(mid), own_ps,
                           plane.stat_metadata, False)
            elif line.name == "Async XLA Ops":
                for ev in line.events:
                    md = plane.event_metadata.get(ev.metadata_id)
                    yield (plane.name, md, ev.duration_ps,
                           plane.stat_metadata, True)


def _bucket(md, stat_metadata) -> str:
    """Phase bucket for one op.  The HLO op NAME (XLA names each op after
    the named_scope that produced it: %CI.tmu.90) is authoritative; the
    metadata stats (tf_op paths etc.) often mention *several* scopes for
    fused/derived ops and are only consulted when the name says nothing —
    matching against them first mis-filed tmu kernels under trsm."""

    def match(hay: str) -> str | None:
        best = None
        for tag in _phase_tags():
            if tag in hay and (best is None or len(tag) > len(best)):
                best = tag
        return best

    name = md.name or md.display_name
    best = match(name.split(" = ")[0])  # the op's own %name only
    if best is None:
        hay = name + " " + md.display_name
        for s in md.stats:
            sm = stat_metadata.get(s.metadata_id)
            if sm is not None and sm.name in ("tf_op", "hlo_op", "name_scope"):
                hay += " " + s.str_value
        best = match(hay)
    if best is not None:
        return best.replace(".", "::")
    if "copy" in name:
        return "copy"
    if "fusion" in name:
        return "fusion"
    if "custom-call" in name or "cholesky" in name or "triangular" in name:
        return "custom-call"
    return "other"


def device_budget(run, trace_dir: str | None = None) -> dict[str, float]:
    """Trace `run()` (which must block on completion) and return
    {bucket: device milliseconds} of XLA-op own time for the
    **critical-path device plane** (the plane with the largest total own
    time), plus an 'async (overlapped)' entry for that plane's DMA
    in-flight occupancy (informational — overlaps compute, not additive).

    Per-plane selection matters: on an n-device run every device's own time
    ~equals the wall, so summing planes would report ~n x the true
    per-iteration floor and poison harness.device_ms_per_iter's
    below-floor check (round-3 advisor finding).  Taking the max plane is
    the device-side critical path — the same max-over-ranks convention the
    reference's bench timing uses (bench/cholesky/cholinv.cpp:51-59)."""
    return _critical_plane_budget(_trace_spaces(run, trace_dir))


def _trace_spaces(run, trace_dir: str | None = None):
    """Trace `run()` once and return the parsed [(path, XSpace)] protos —
    the raw material shared by device_budget and phase_attribution so a
    gated CLI invocation profiles exactly once."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    with tempfile.TemporaryDirectory() as tmp:
        d = trace_dir or tmp
        with jax.profiler.trace(d):
            run()
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        if not paths:
            raise RuntimeError(f"no xplane.pb under {d}")
        spaces = []
        for p in paths:
            space = xplane_pb2.XSpace()
            with open(p, "rb") as f:
                space.ParseFromString(f.read())
            spaces.append((p, space))
    return spaces


def _critical_plane_budget(spaces) -> dict[str, float]:
    """{bucket: ms} of the max-total device plane over [(tag, XSpace)]."""
    per_plane: dict[str, dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float)
    )
    for tag, space in spaces:
        for plane, md, dur_ps, stat_md, is_async in _iter_xla_op_events(space):
            if md is None:
                continue
            key = "async (overlapped)" if is_async else _bucket(md, stat_md)
            # key planes by (source, plane-name): one xplane.pb per host,
            # one plane per local device
            per_plane[f"{tag}::{plane}"][key] += dur_ps * 1e-9  # ps -> ms
    if not per_plane:
        return {}

    def compute_total(buckets):
        return sum(v for k, v in buckets.items() if k != "async (overlapped)")

    crit = max(per_plane.values(), key=compute_total)
    return dict(crit)


def check_copy_fraction(
    budget: dict[str, float], max_frac: float, label: str = ""
) -> float:
    """Gate the 'copy' bucket — schedule-inserted relayout/materialization
    copies, the device-time cost the copy-free explicit routes and the
    persistent tile-cyclic layout exist to remove — at <= ``max_frac`` of
    the plane's compute own-time.  Returns the measured fraction; raises
    RuntimeError on violation so copy regressions fail as loudly as
    collective-inventory regressions (tests/test_collective_audit.py)
    already do.  The async-occupancy row is excluded from both sides
    (it overlaps compute; it is not additive own-time).  The cost-model
    counterpart is the copy_bytes column of tracing.Recorder
    (docs/OBSERVABILITY.md)."""
    compute = {
        k: v for k, v in budget.items() if k != "async (overlapped)"
    }
    total = sum(compute.values())
    frac = (compute.get("copy", 0.0) / total) if total > 0 else 0.0
    if frac > max_frac:
        raise RuntimeError(
            f"copy-budget regression{f' ({label})' if label else ''}: "
            f"copy bucket is {100 * frac:.1f}% of device own-time, "
            f"budget {100 * max_frac:.1f}% — a schedule copy "
            "(take_triangle materialization / whole-buffer "
            "dynamic_update_slice) crept back in"
        )
    return frac


# --------------------------------------------------------------------------
# phase-level wall-time attribution
# --------------------------------------------------------------------------

#: one optimized-HLO instruction definition with its op_name metadata —
#: the scope chain tracing.scope stamped through jax.named_scope
#: survives XLA optimization in exactly this field (fusions inherit a
#: constituent op's chain).
_HLO_OP_RE = re.compile(
    r"%?([A-Za-z0-9_.\-]+)\s*=\s*[^\n]*metadata=\{[^}\n]*op_name=\"([^\"]*)\""
)


def hlo_phase_map(compiled_text: str) -> dict[str, str]:
    """{instruction name: phase tag ('CI::tmu' form)} from an optimized-HLO
    dump (``compiled.as_text()``).  Longest registered tag mentioned in the
    op_name wins (innermost scope, same convention as _bucket); instructions
    whose op_name names no registered phase are simply absent.  Nested
    computations are parsed too — dict insertion order means the ENTRY
    computation (printed last) wins a name collision, which is the
    computation whose instruction names the runtime's thunk events carry."""
    out: dict[str, str] = {}
    tags = sorted(_phase_tags(), key=len)  # ascending: longest match wins
    for m in _HLO_OP_RE.finditer(compiled_text):
        name, op_name = m.groups()
        best = None
        for tag in tags:
            if tag in op_name:
                best = tag
        if best is not None:
            out[name] = best.replace(".", "::")
    return out


def _host_plane_budget(spaces, phase_map: dict[str, str]) -> dict[str, float]:
    """{bucket: ms} fallback for rigs with no device plane (the CPU CI rig):
    the host trace's XLA-client lines carry one event per executed thunk,
    named after the entry-computation HLO instruction and stamped with an
    ``hlo_op`` stat.  Those events are bucketed through `phase_map` (from
    hlo_phase_map of the SAME compiled program that ran).  Events without
    the hlo_op stat (ThreadpoolListener / ThunkExecutor bookkeeping) are
    dropped BEFORE the own-time sweep so they can't swallow op durations.
    Busiest host plane wins, mirroring _critical_plane_budget."""
    per_plane: dict[str, dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float)
    )
    for tag, space in spaces:
        for plane in space.planes:
            if "TPU" in plane.name:
                continue
            stat_names = {
                sid: sm.name for sid, sm in plane.stat_metadata.items()
            }
            for line in plane.lines:
                evs = [
                    e for e in line.events
                    if any(
                        stat_names.get(s.metadata_id) == "hlo_op"
                        for s in e.stats
                    )
                ]
                if not evs:
                    continue
                buckets = per_plane[f"{tag}::{plane.name}"]
                for mid, own_ps in _own_times(evs):
                    md = plane.event_metadata.get(mid)
                    if md is None:
                        continue
                    name = (md.name or md.display_name).lstrip("%")
                    key = phase_map.get(name)
                    if key is None:
                        if "copy" in name:
                            key = "copy"
                        elif "fusion" in name:
                            key = "fusion"
                        else:
                            key = "other"
                    buckets[key] += own_ps * 1e-9  # ps -> ms
    if not per_plane:
        return {}
    return dict(max(per_plane.values(), key=lambda b: sum(b.values())))


def wall_seconds(run, repeats: int = 3) -> float:
    """min-of-repeats wall clock of one (compiled, warm) run() — the min is
    the drift-resistant estimator docs/PERF.md's measurement discipline
    prescribes for walls that only err upward."""
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def phase_attribution(run, iters: int, spaces=None, trace_dir=None):
    """Decompose measured wall-clock into per-phase seconds.

    Returns ``(phase_seconds, bubble_frac, wall_s_per_iter)`` where
    phase_seconds maps each PHASE_REGISTRY tag (plus the copy/fusion/other
    catch-alls) to seconds per iteration and
    ``bubble_frac = max(0, (wall − Σ attributed) / wall)`` — the fraction
    of the wall no op execution accounts for (launch gaps, host stalls,
    inter-phase bubbles).  Clamped at 0 because concurrent thunk execution
    on the CPU rig can legitimately attribute MORE op-seconds than wall.

    Device planes ('XLA Ops' own time) are authoritative when present; on
    rigs without one, host-side thunk events are bucketed through the
    compiled module's op_name metadata (``run.compiled`` — the AOT
    executable every _*_run builder attaches).  Pass `spaces` to reuse an
    existing _trace_spaces parse; the wall always comes from separate
    UNtraced runs (profiling overhead must not count as bubble)."""
    wall = wall_seconds(run) / iters
    if spaces is None:
        spaces = _trace_spaces(run, trace_dir)
    budget = _critical_plane_budget(spaces)
    budget.pop("async (overlapped)", None)
    if not budget:
        compiled = getattr(run, "compiled", None)
        if compiled is not None:
            budget = _host_plane_budget(
                spaces, hlo_phase_map(compiled.as_text())
            )
    phase_seconds = {
        k: v * 1e-3 / iters for k, v in budget.items() if v > 0.0
    }
    attributed = sum(phase_seconds.values())
    bubble = max(0.0, (wall - attributed) / wall) if wall > 0 else 0.0
    return phase_seconds, bubble, wall


def check_bubble_fraction(
    phase_seconds: dict[str, float],
    bubble_frac: float,
    max_frac: float,
    label: str = "",
) -> float:
    """Gate the un-attributed fraction of the wall — the --max-bubble-frac
    CI mirror of check_copy_fraction.  An EMPTY attribution fails too: a
    gate that passes because nothing was attributed is a dead gate, and
    dead gates are how the round-2 copy regressions shipped."""
    tag = f" ({label})" if label else ""
    if not phase_seconds:
        raise RuntimeError(
            f"bubble gate is dead{tag}: no phase seconds were attributed — "
            "no device plane in the trace and no compiled module to map "
            "host events through; fix the attribution before trusting the "
            "gate"
        )
    if bubble_frac > max_frac:
        raise RuntimeError(
            f"bubble-budget regression{tag}: {100 * bubble_frac:.1f}% of "
            f"wall is unattributed (budget {100 * max_frac:.1f}%) — "
            "inter-phase bubbles / launch gaps grew; see the phase "
            "breakdown above"
        )
    return bubble_frac


def print_budget(budget: dict[str, float], iters: int, label: str) -> dict:
    budget = dict(budget)
    async_ms = budget.pop("async (overlapped)", 0.0)
    total = sum(budget.values())
    rows = sorted(budget.items(), key=lambda kv: -kv[1])
    print(f"# device-op budget: {label} ({iters} traced iterations)")
    for k, ms in rows:
        print(f"#   {k:16s} {ms / iters:9.3f} ms/iter  {100 * ms / total:5.1f}%")
    print(f"#   {'TOTAL':16s} {total / iters:9.3f} ms/iter")
    if async_ms:
        print(
            f"#   {'async-overlap':16s} {async_ms / iters:9.3f} ms/iter  "
            "(DMA occupancy, overlaps the rows above)"
        )
        rows = rows + [("async (overlapped)", async_ms)]
    rec = {
        "metric": "device_budget",
        "label": label,
        "iters": iters,
        "total_ms_per_iter": round(total / iters, 3),
        "phases_ms_per_iter": {k: round(v / iters, 3) for k, v in rows},
    }
    print(json.dumps(rec))
    return rec


def _aot_run(jitted, *args):
    """AOT-compile ``jitted(*args)`` and return a zero-arg runner that
    blocks on the scalar result.  The runner carries the executable as
    ``run.compiled`` so phase_attribution can read the optimized HLO of
    EXACTLY the program the trace ran (hlo_phase_map) — a re-jit could
    legally schedule differently."""
    compiled = jitted.lower(*args).compile()

    def run():
        float(compiled(*args))

    run.compiled = compiled
    return run


def _cholinv_run(n: int, dtype, bc: int, iters: int, prec=None,
                 mode: str = "pallas"):
    """The carry loop (fori_loop + element coupling), compiled once and
    traced for `iters` iterations."""
    from capital_tpu.models import cholesky
    from capital_tpu.parallel.topology import Grid

    grid = Grid.square(c=1, devices=[jax.devices()[0]])
    cfg = cholesky.CholinvConfig(base_case_dim=bc, mode=mode, precision=prec)
    A = residual.spd_operand(n, dtype)
    eps = jnp.asarray(0.0, jnp.float32)

    @jax.jit
    def loop(a, eps, k):
        def body(_, carry):
            R, Rinv = cholesky.factor(grid, carry, cfg)
            d = R[0, 0] + Rinv[0, 0]
            return carry.at[0, 0].add(eps.astype(carry.dtype) * d)

        return jnp.sum(jax.lax.fori_loop(0, k, body, a), dtype=jnp.float32)

    run = _aot_run(loop, A, eps, jnp.int32(iters))
    run()  # warm (already AOT-compiled)
    return run


def _rectri_run(n: int, dtype, bc: int, iters: int, prec=None):
    from capital_tpu.models import inverse
    from capital_tpu.parallel.topology import Grid

    grid = Grid.square(c=1, devices=[jax.devices()[0]])
    cfg = inverse.RectriConfig(
        base_case_dim=bc, mode="pallas",
        precision=prec,
    )
    T = residual.tri_operand(n, dtype)
    eps = jnp.asarray(0.0, jnp.float32)

    @jax.jit
    def loop(a, eps, k):
        def body(_, carry):
            inv = inverse.rectri(grid, carry, "L", cfg)
            return carry.at[0, 0].add(eps.astype(carry.dtype) * inv[0, 0])

        return jnp.sum(jax.lax.fori_loop(0, k, body, a), dtype=jnp.float32)

    run = _aot_run(loop, T, eps, jnp.int32(iters))
    run()
    return run


def _cacqr_run(m: int, n: int, dtype, bc: int, iters: int, prec=None):
    from capital_tpu.models import cholesky, qr
    from capital_tpu.parallel.topology import Grid

    grid = Grid.square(c=1, devices=[jax.devices()[0]])
    precision = prec
    cfg = qr.CacqrConfig(
        num_iter=2, mode="pallas",
        cholinv=cholesky.CholinvConfig(
            base_case_dim=bc, mode="pallas", precision=precision
        ),
        precision=precision,
    )
    A = jax.block_until_ready(
        jax.random.normal(jax.random.key(0), (m, n), dtype=dtype)
    )
    eps = jnp.asarray(0.0, jnp.float32)

    @jax.jit
    def loop(a, eps, k):
        def body(_, carry):
            Q, R = qr.factor(grid, carry, cfg)
            return Q.at[: R.shape[0], : R.shape[1]].add(R.astype(Q.dtype))

        return jnp.sum(jax.lax.fori_loop(0, k, body, a), dtype=jnp.float32)

    run = _aot_run(loop, A, eps, jnp.int32(iters))
    run()
    return run


def _trsm_run(n: int, nrhs: int, dtype, bc: int, iters: int, prec=None):
    from capital_tpu.models import trsm as trsm_mod
    from capital_tpu.parallel.topology import Grid

    grid = Grid.square(c=1, devices=[jax.devices()[0]])
    cfg = trsm_mod.TrsmConfig(
        base_case_dim=bc, mode="xla",
        precision=prec,
    )
    L = residual.tri_operand(n, dtype)
    B = jax.block_until_ready(
        jax.random.normal(jax.random.key(1), (n, nrhs), dtype=dtype)
    )
    eps = jnp.asarray(0.0, jnp.float32)

    @jax.jit
    def loop(op, eps, k):
        Lo, B0 = op

        def body(_, carry):
            X = trsm_mod.solve(grid, Lo, carry, side="L", uplo="L", cfg=cfg)
            return carry + eps.astype(carry.dtype) * X

        return jnp.sum(jax.lax.fori_loop(0, k, body, B0), dtype=jnp.float32)

    run = _aot_run(loop, (L, B), eps, jnp.int32(iters))
    run()
    return run


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="capital_tpu.bench.trace")
    p.add_argument("algo", choices=["cholinv", "cacqr", "rectri", "trsm"])
    p.add_argument("--n", type=int, default=16384)
    p.add_argument("--m", type=int, default=1 << 20)
    p.add_argument("--bc", type=int, default=512)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--trace-dir", default=None,
                   help="keep the raw trace here instead of a temp dir")
    p.add_argument("--max-copy-frac", type=float, default=None,
                   help="fail (non-zero exit) if the 'copy' bucket exceeds "
                        "this fraction of device own-time — the CI gate for "
                        "schedule-copy regressions (see "
                        "trace.check_copy_fraction)")
    p.add_argument("--max-bubble-frac", type=float, default=None,
                   help="fail (non-zero exit) if more than this fraction of "
                        "measured wall-clock is attributed to NO phase "
                        "(launch gaps / host stalls / inter-phase bubbles); "
                        "also fails when nothing could be attributed at all "
                        "— no silently-dead gates (trace."
                        "check_bubble_fraction)")
    p.add_argument("--ledger", default=None,
                   help="append one bench:trace:<algo> ledger record "
                        "carrying the phase_seconds / bubble_frac block "
                        "(obs diff watches measured.value = attributed "
                        "fraction for drift)")
    p.add_argument("--precision", default=None,
                   choices=["default", "high", "highest"],
                   help="override the matmul precision ('high' traces the "
                        "f32 3-pass family, 'default' the TPU-default "
                        "1-pass) — same semantics as the drivers CLI")
    p.add_argument("--platform", default=None,
                   help="jax platform override (e.g. 'cpu') — config API, "
                        "same reason as the drivers CLI")
    args = p.parse_args(argv)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    dtype = jnp.dtype(args.dtype)
    # 'default' -> None (the context default), unset -> the dtype rule
    if args.precision:
        prec = None if args.precision == "default" else args.precision
    else:
        prec = summa.default_precision(dtype)
    ptag = f" prec={args.precision}" if args.precision else ""

    if args.algo == "cholinv":
        run = _cholinv_run(args.n, dtype, args.bc, args.iters, prec)
        label = f"cholinv n={args.n} bc={args.bc} {dtype}" + ptag
    elif args.algo == "rectri":
        run = _rectri_run(args.n, dtype, args.bc, args.iters, prec)
        label = f"rectri n={args.n} bc={args.bc} {dtype}" + ptag
    elif args.algo == "trsm":
        nrhs = min(args.m, args.n)
        run = _trsm_run(args.n, nrhs, dtype, args.bc, args.iters, prec)
        label = f"trsm n={args.n} nrhs={nrhs} bc={args.bc} {dtype}" + ptag
    else:
        run = _cacqr_run(args.m, args.n, dtype, args.bc, args.iters, prec)
        label = f"cacqr {args.m}x{args.n} {dtype}" + ptag

    spaces = _trace_spaces(run, args.trace_dir)
    budget = _critical_plane_budget(spaces)
    print_budget(budget, args.iters, label)
    if args.max_copy_frac is not None:
        frac = check_copy_fraction(budget, args.max_copy_frac, label)
        print(
            f"# copy budget OK: {100 * frac:.1f}% <= "
            f"{100 * args.max_copy_frac:.1f}%"
        )
    if args.max_bubble_frac is not None or args.ledger is not None:
        phase_s, bubble, wall = phase_attribution(
            run, args.iters, spaces=spaces
        )
        attributed = sum(phase_s.values())
        print(
            f"# phase attribution: wall {wall * 1e3:.3f} ms/iter, "
            f"attributed {attributed * 1e3:.3f} ms/iter, "
            f"bubble_frac {bubble:.4f}"
        )
        for k, v in sorted(phase_s.items(), key=lambda kv: -kv[1]):
            print(f"#   {k:16s} {v * 1e3:9.3f} ms/iter")
        if args.ledger:
            from capital_tpu.obs import ledger

            meas = {
                "metric": f"trace_{args.algo}_attributed",
                # value is the attributed fraction so an obs diff
                # value-drop reads as "bubbles grew"
                "value": round(1.0 - bubble, 4),
                "unit": "frac",
                "seconds": wall,
                "n": args.n,
                "bc": args.bc,
                "phase_seconds": {k: round(v, 9) for k, v in phase_s.items()},
                "bubble_frac": round(bubble, 4),
            }
            row = ledger.record(
                f"bench:trace:{args.algo}",
                ledger.manifest(
                    dtype=dtype,
                    config={
                        "algo": args.algo, "n": args.n, "bc": args.bc,
                        "iters": args.iters,
                    },
                ),
                measured=meas,
            )
            ledger.append(args.ledger, row)
            print(f"# ledger: bench:trace:{args.algo} -> {args.ledger}")
        if args.max_bubble_frac is not None:
            check_bubble_fraction(phase_s, bubble, args.max_bubble_frac, label)
            print(
                f"# bubble budget OK: {100 * bubble:.1f}% <= "
                f"{100 * args.max_bubble_frac:.1f}%"
            )


if __name__ == "__main__":
    main()
