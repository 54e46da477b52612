"""Autotune: config sweeps over algorithm knobs, with cost tables.

The reference's autotune layer (autotune/{cholesky,qr}/*/tune.cpp +
autotune/util.h) sweeps base-case policy x bcMultiplier (x grid shape for
QR) under the critter measurement tool and writes critical-path cost tables
(tune.cpp:175-253, autotune/util.h:4-127).  The TPU equivalent here:

* the **measured** axis is wall time per factor call, taken with the in-jit
  loop + delta discipline (bench/harness.py) — the reference's
  barrier+MPI_Wtime with critter's timers;
* the **modeled** axis is the alpha-beta cost decomposition captured by
  tracing.Recorder at trace time (per-phase flops / comm bytes /
  collective counts — critter's comp/comm/synch columns);
* outputs: `<alg>_cp_times.txt` (measured + per-phase estimates) and
  `<alg>_cp_costs.txt` (model decomposition), the *_cp_times/*_cp_costs
  table family of autotune/util.h, plus `<alg>_best.json` with the winning
  config — the piece the reference leaves to the user's eyeballs.

Config spaces mirror tune.cpp: cholinv sweeps policy x base_case_dim
(x split); cacqr sweeps variant x base_case_dim x regime.  Grid-shape
sweeping (the reference's rep-factor loop, qr tune.cpp) plugs in via the
`grids` argument.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from capital_tpu.bench import harness
from capital_tpu.models import cholesky, qr
from capital_tpu.parallel import summa
from capital_tpu.parallel.topology import Grid
from capital_tpu.serve.stats import percentiles
from capital_tpu.utils import residual, tracing
from capital_tpu.utils.config import BaseCasePolicy


@dataclasses.dataclass
class SweepResult:
    config_id: str
    config: dict
    seconds: float
    recorder: tracing.Recorder
    #: measurement-protocol sidecar (e.g. latency_measure's wall_ms
    #: percentile block); merged into the ledger's measured dict.  None
    #: under the default amortized protocol.
    extra: dict | None = None


# --------------------------------------------------------------------------
# sweep checkpointing: hardware sweeps take tens of minutes per pass and the
# environment can preempt them; a resumed sweep (CLI --resume) skips configs
# already measured for the same (shape, dtype, device) problem.  The
# reference has no such capability (its tune.cpp restarts from scratch).
# --------------------------------------------------------------------------


def _grid_key(grid: Grid) -> dict:
    """Topology identity for the resume key: shape AND the concrete device
    ordering (device ids in mesh order), which captures the layout knob —
    two grids differing only in layout place devices differently, time
    collectives differently, and must not share resumed timings."""
    return {
        "grid": repr(grid),
        "devices": [int(d.id) for d in grid.mesh.devices.ravel()],
    }


# Bump on any kernel or measurement-protocol change that invalidates stored
# timings (e.g. the paired-median drift protocol, tri-operand bk halving):
# resumed sweeps must not mix pre-change checkpointed numbers with fresh ones
# and crown a stale config.
MEASUREMENT_PROTOCOL_VERSION = 2


def _ckpt_key(name: str, operand, extra: dict | None = None) -> dict:
    """Problem identity for resume: name, operand, device kind, protocol
    version, and whatever the caller adds (the grid topology — a 2x2x1
    sweep's timings must never be resumed into a 1-device sweep of the same
    matrix)."""
    return {
        "name": name,
        "shape": list(operand.shape),
        "dtype": str(operand.dtype),
        "device": jax.devices()[0].device_kind,
        "protocol": MEASUREMENT_PROTOCOL_VERSION,
        **(extra or {}),
    }


def _ckpt_path(out_dir: str, name: str, key: dict) -> str:
    """Checkpoint file keyed by the problem hash, so sweeps of different
    problems sharing an out_dir cannot clobber each other's partial state."""
    import hashlib

    h = hashlib.sha256(json.dumps(key, sort_keys=True).encode()).hexdigest()[:10]
    return os.path.join(out_dir, f"{name}_sweep_{h}.json")


def _ckpt_load(path: str, key: dict) -> dict:
    """Load the resume state, tolerating entries written by older schemas.

    A checkpoint is a cache, not a contract: an entry missing fields this
    version reads (older writer, hand-edited file) is DROPPED with a note —
    the config simply re-measures — instead of KeyError-aborting the whole
    resume.  Kept entries:
      * failure records ({"failed": true, ...}) — persisted so a config
        that OOMed is not retried forever across resumes;
      * measurement records with a numeric "seconds"; missing "config" /
        "stats" default to {} (the table row degrades, the timing
        survives)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    if data.get("key") != key:
        return {}
    done = data.get("done", {})
    if not isinstance(done, dict):
        return {}
    out: dict = {}
    for cid, entry in done.items():
        if not isinstance(entry, dict):
            print(f"# autotune resume: dropping malformed entry {cid!r}")
            continue
        if entry.get("failed"):
            out[cid] = entry
            continue
        if isinstance(entry.get("seconds"), (int, float)):
            out[cid] = {
                "config": entry.get("config", {}),
                "seconds": float(entry["seconds"]),
                "stats": entry.get("stats", {}),
                # protocol sidecar (latency percentiles): optional, rides
                # the resume so a resumed latency sweep keeps its wall_ms
                "extra": entry.get("extra"),
            }
        else:
            print(
                f"# autotune resume: dropping {cid!r} (no usable 'seconds' "
                "— older schema?); it will be re-measured"
            )
    return out


def _ckpt_save(path: str, key: dict, done: dict) -> None:
    # same atomic-rename discipline as utils/checkpoint.save; kept separate
    # because sweep state is pure JSON (no arrays — npz would bury the
    # human-inspectable per-config record the sweep wants to expose)
    tmp = f"{path}.tmp{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump({"key": key, "done": done}, f)
        os.replace(tmp, path)  # atomic: a preemption mid-write tears nothing
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _recorder_from(stats: dict) -> tracing.Recorder:
    rec = tracing.Recorder()
    for tag, s in stats.items():
        # dataclass round trip: a future PhaseStats field restores too
        rec.stats[tag].merge(tracing.PhaseStats(**s))
    return rec


def _recorder_dump(rec: tracing.Recorder) -> dict:
    return {tag: dataclasses.asdict(s) for tag, s in rec.stats.items()}


def _model_costs(step: Callable, operand) -> tracing.Recorder:
    """Capture the alpha-beta model decomposition for one config by tracing
    (no execution): phase emits fire at trace time."""
    rec = tracing.Recorder()
    with rec:
        jax.eval_shape(step, operand)
    return rec


def run_sweep(
    name: str,
    configs: Iterable[tuple[str, dict, Callable]],
    operand,
    out_dir: str = ".",
    iters: int = 2,
    dtype=None,
    checkpoint: bool = False,
    key_extra: dict | None = None,
    ledger: str | None = None,
    retry: harness.RetryPolicy = harness.RetryPolicy(),
    measure: Callable | None = None,
) -> list[SweepResult]:
    """Measure + model every (config_id, config_dict, step_fn) and write the
    cost tables.  Returns results sorted best-first by measured time.

    `measure` swaps the measurement protocol: a callable
    ``measure(step, operand) -> (seconds, extra_dict | None)`` replacing
    the default amortized ``harness.timed_loop`` (which `iters` feeds).
    The returned seconds is what the sweep SORTS on — a latency protocol
    returning p99 wall seconds (latency_measure) makes the sweep optimize
    p99, not mean throughput — and extra_dict rides the SweepResult, the
    checkpoint, and the ledger's measured block (e.g. the full wall_ms
    percentile split).  Containment is identical either way: the call runs
    under run_guarded with the same retry policy.

    checkpoint=True persists per-config results to a problem-keyed
    ``<out_dir>/<name>_sweep_<hash>.json`` after each measurement; a re-run
    of the same problem (shape/dtype/device/topology) resumes, skipping
    measured configs.  Unresolved (noise-floor) configs are NOT persisted —
    the condition can be a transient drift window, so every resume retries
    them.

    Runtime failures (OOM / compile abort — XlaRuntimeError) of one config
    are CONTAINED: retried per `retry` (harness.run_guarded), then recorded
    as a failure — in the checkpoint (so resumes don't retry a known-bad
    config forever) and as a status='failed' event in the ledger — while
    the remaining configs keep sweeping.

    ledger=PATH additionally appends one obs ledger record per swept config
    (manifest keyed by config_id, the Recorder model decomposition, and the
    measured seconds) so sweeps land in the same queryable JSONL stream as
    bench runs and audits.  Configs that needed retries land with a
    status='recovered' event."""
    dtype = dtype or operand.dtype
    configs = list(configs)
    if not configs:
        raise ValueError(f"autotune sweep {name!r}: no configs to sweep")
    key = _ckpt_key(name, operand, key_extra)
    ckpt_path = _ckpt_path(out_dir, name, key)
    done: dict = {}
    if checkpoint:
        os.makedirs(out_dir, exist_ok=True)
        done = _ckpt_load(ckpt_path, key)
    results: list[SweepResult] = []
    attempts_by: dict[str, int] = {}
    failures: list[tuple[str, dict, dict]] = []  # (cid, cdict, failure entry)
    for cid, cdict, step in configs:
        if cid in done:
            entry = done[cid]
            if entry.get("failed"):
                print(
                    f"# autotune {name}: {cid}  FAILED previously "
                    f"({entry.get('error', '?')}) — skipped on resume"
                )
                continue
            results.append(
                SweepResult(
                    cid, entry["config"], entry["seconds"],
                    _recorder_from(entry["stats"]), entry.get("extra"),
                )
            )
            print(f"# autotune {name}: {cid}  {entry['seconds'] * 1e3:.3f} ms (resumed)")
            continue
        rec = _model_costs(step, operand)
        extra_m: dict | None = None
        try:
            if measure is None:
                secs, attempts = harness.run_guarded(
                    lambda: harness.timed_loop(step, operand, iters=iters),
                    policy=retry,
                    label=f"{name}:{cid}",
                )
            else:
                out, attempts = harness.run_guarded(
                    lambda: measure(step, operand),
                    policy=retry,
                    label=f"{name}:{cid}",
                )
                secs, extra_m = out
        except harness.MeasurementUnresolved as e:
            # below the measurement noise floor: record nothing for this
            # config rather than aborting the sweep and losing the rest
            print(f"# autotune {name}: {cid}  UNRESOLVED ({e})")
            continue  # deliberately not checkpointed: retried on resume
        except harness.ConfigFailed as e:
            # runtime failure contained to this config: the sweep goes on
            print(f"# autotune {name}: {cid}  FAILED ({e})")
            entry = {
                "failed": True,
                "error": f"{type(e.cause).__name__}: {e.cause}",
                "attempts": e.attempts,
                "config": cdict,
            }
            failures.append((cid, cdict, entry))
            if checkpoint:
                done[cid] = entry
                _ckpt_save(ckpt_path, key, done)
            continue
        if attempts > 1:
            attempts_by[cid] = attempts
        results.append(SweepResult(cid, cdict, secs, rec, extra_m))
        print(f"# autotune {name}: {cid}  {secs * 1e3:.3f} ms")
        if checkpoint:
            done[cid] = {
                "config": cdict, "seconds": secs, "stats": _recorder_dump(rec),
            }
            if extra_m is not None:
                done[cid]["extra"] = extra_m
            _ckpt_save(ckpt_path, key, done)

    os.makedirs(out_dir, exist_ok=True)
    spec = tracing.device_spec()
    tracing.write_times_table(
        os.path.join(out_dir, f"{name}_cp_times.txt"),
        [
            (r.config_id, r.seconds, r.recorder.estimate_seconds(spec, dtype))
            for r in results
        ],
    )
    tracing.write_costs_table(
        os.path.join(out_dir, f"{name}_cp_costs.txt"),
        [(r.config_id, r.recorder) for r in results],
    )
    if ledger:
        from capital_tpu.obs import ledger as obs_ledger

        extra = dict(key_extra or {})
        # key_extra's "grid" is already a repr string — it must not bind
        # manifest()'s grid parameter (which expects a Grid object)
        grid_repr = extra.pop("grid", None)

        def _man(cdict, cid):
            man = obs_ledger.manifest(
                dtype=dtype, config=cdict, config_id=cid,
                shape=list(operand.shape), **extra,
            )
            if grid_repr is not None:
                man["grid"] = grid_repr
            return man

        # failure events FIRST: even a sweep where nothing resolved leaves
        # its failures queryable (the raise below fires after this block)
        for cid, cdict, entry in failures:
            obs_ledger.append(
                ledger,
                obs_ledger.record(
                    f"autotune:{name}",
                    _man(cdict, cid),
                    event={
                        "status": "failed",
                        "error": entry["error"],
                        "attempts": entry["attempts"],
                    },
                ),
            )
        for r in results:
            ev = (
                {"status": "recovered", "attempts": attempts_by[r.config_id]}
                if r.config_id in attempts_by
                else None
            )
            obs_ledger.append(
                ledger,
                obs_ledger.record(
                    f"autotune:{name}",
                    _man(r.config, r.config_id),
                    model=obs_ledger.model_costs(r.recorder, dtype=dtype),
                    # value is rate (1/s), not seconds: diff() flags VALUE
                    # drops, and "slower" must read as a drop
                    measured={
                        "metric": f"{name}_sweep",
                        "value": round(1.0 / r.seconds, 4),
                        "unit": "iter/s",
                        "seconds": r.seconds,
                        # protocol sidecar: a latency sweep lands its
                        # wall_ms percentile block here, so per-bucket
                        # p99 is queryable straight off the ledger
                        **(r.extra or {}),
                    },
                    **({"event": ev} if ev else {}),
                ),
            )
    if not results:
        raise RuntimeError(
            f"autotune sweep {name!r}: no config produced a resolvable time"
        )
    results.sort(key=lambda r: r.seconds)
    best = results[0]
    with open(os.path.join(out_dir, f"{name}_best.json"), "w") as f:
        json.dump(
            {
                "config": best.config,
                "seconds": best.seconds,
                "configs_swept": len(results),
                "device": jax.devices()[0].device_kind,
            },
            f,
            indent=1,
        )
    return results


# --------------------------------------------------------------------------
# per-algorithm config spaces (reference tune.cpp sweeps)
# --------------------------------------------------------------------------


def grid_space(
    devices=None,
    c_values: Iterable[int] = (1, 2, 4),
    include_flat: bool = False,
) -> list[Grid]:
    """Feasible grid shapes over the available devices — the reference's
    rep-factor loop (bench/qr/cacqr.cpp:8-25, qr tune.cpp sweeps grid shape
    alongside bc).  For each replication depth c, the largest d x d x c
    square grid the device count supports; plus the flat 1D topology when
    requested (the tall-skinny regime).  Degenerates to [1x1x1] on one
    device."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    grids: list[Grid] = []
    seen: set[tuple[int, int, int]] = set()
    for c in c_values:
        d = 1
        while (d + 1) * (d + 1) * c <= n:
            d += 1
        # feasibility: the explicit schedule needs c | d (summa.py K-segment
        # split), so a 2x2x4 "fits 16 devices" shape would abort a sweep
        # mid-run — step DOWN to the largest multiple of c that fits rather
        # than dropping the whole c-axis (128 devices, c=4: d=5 fits but
        # 4x4x4 is the feasible shape); and 1x1xC is pure redundancy
        d -= d % c
        if (
            d >= 1
            and d * d * c <= n
            and (d, d, c) not in seen
            and (d > 1 or c == 1)
        ):
            seen.add((d, d, c))
            grids.append(Grid.square(c=c, devices=devices[: d * d * c]))
    if include_flat and n > 1:
        grids.append(Grid.flat(devices=devices))
    return grids


def _with_grids(grids, base_grid):
    """The grid axis of a config space: explicit list, or just the fixed
    sweep grid."""
    return list(grids) if grids else [base_grid]


def _gid(grid: Grid) -> str:
    tag = f"g{grid.dx}x{grid.dy}x{grid.c}"
    if getattr(grid, "layout", 0):
        tag += f"l{grid.layout}"
    if getattr(grid, "num_chunks", 0):
        tag += f"q{grid.num_chunks}"
    return tag


def cholinv_space(
    grid: Grid,
    dtype,
    bc_dims: Iterable[int] = (128, 256, 512, 1024),
    policies: Iterable[BaseCasePolicy] = (
        BaseCasePolicy.REPLICATE_COMM_COMP,
        BaseCasePolicy.NO_REPLICATION,
    ),
    splits: Iterable[int] = (1,),
    modes: Iterable[str] = ("xla",),
    grids: Iterable[Grid] | None = None,
    balances: Iterable[str] = ("block",),
    tail_depths: Iterable[int] = (0,),
):
    """policy x bc x split x mode (x grid shape) (x balance)
    (x tail_fuse_depth) — the reference's decomposition sweep (cholesky
    tune.cpp:175-253: 3 policies x bcMultiplier range) plus the
    rep-factor/grid-shape axis (`grids`, e.g. from grid_space()).  The
    operand reshards to each grid's face on the first in-loop iteration;
    subsequent iterations carry the face layout, so the measured
    steady-state time is that grid's.  `balances` adds the schedule axis
    ('block' / 'tile_cyclic' / 'tile_cyclic_persistent', explicit mode
    only) — the planner prices the copy-bytes difference, so the
    persistent spelling ranks on the model, not only in the measured
    sweep.  `tail_depths` adds the fused-recursion-tail axis
    (CholinvConfig.tail_fuse_depth; depth 0 = unfused, the default, so
    existing config ids stay stable)."""
    prec = summa.default_precision(dtype)
    glist = _with_grids(grids, grid)
    for g, pol, bc, split, mode, bal, td in itertools.product(
        glist, policies, bc_dims, splits, modes, balances, tail_depths
    ):
        if bal != "block" and mode != "explicit":
            continue  # balanced schedules are explicit-only (cholesky.factor raises)
        cfg = cholesky.CholinvConfig(
            base_case_dim=bc, split=split, policy=pol, mode=mode,
            precision=prec, balance=bal, tail_fuse_depth=td,
        )

        def step(a, cfg=cfg, g=g):
            R, Rinv = cholesky.factor(g, a, cfg)
            return R + Rinv

        cid = f"pol{pol.value}_bc{bc}_s{split}_{mode}"
        if bal != "block":
            cid += f"_{bal}"
        if td:
            cid += f"_tf{td}"
        cdict = {
            "policy": pol.name, "base_case_dim": bc, "split": split, "mode": mode,
        }
        if bal != "block":
            cdict["balance"] = bal
        if td:
            cdict["tail_fuse_depth"] = td
        if grids is not None:
            # topology parameters ride the config dict whenever a grids
            # axis was passed — even a single-element axis may differ from
            # the base grid, and the prefilter must model the topology the
            # step actually measures on
            cdict["grid"] = repr(g)
            cdict["grid_shape"] = [g.dx, g.dy, g.c]
            cdict["num_chunks"] = g.num_chunks
            cdict["layout"] = getattr(g, "layout", 0)
        if len(glist) > 1:
            cid = f"{_gid(g)}_{cid}"
        yield cid, cdict, step


def cacqr_space(
    grid: Grid,
    dtype,
    bc_dims: Iterable[int] = (128, 256, 512),
    variants: Iterable[int] = (1, 2),
    regimes: Iterable[str] = ("auto",),
    grids: Iterable[Grid] | None = None,
):
    """variant x bc x regime (x grid shape) — qr tune.cpp sweeps
    bcMultiplier x grid shape; pass grids=grid_space(include_flat=True) to
    sweep the topology axis on real hardware."""
    prec = summa.default_precision(dtype)
    glist = _with_grids(grids, grid)
    for g, variant, bc, regime in itertools.product(
        glist, variants, bc_dims, regimes
    ):
        cfg = qr.CacqrConfig(
            num_iter=variant,
            regime=regime,
            cholinv=cholesky.CholinvConfig(base_case_dim=bc, precision=prec),
            precision=prec,
        )

        def step(a, cfg=cfg, g=g):
            Q, R = qr.factor(g, a, cfg)
            return Q.at[: R.shape[0], : R.shape[1]].add(R.astype(Q.dtype))

        cid = f"v{variant}_bc{bc}_{regime}"
        cdict = {"variant": variant, "base_case_dim": bc, "regime": regime}
        if len(glist) > 1:
            cid = f"{_gid(g)}_{cid}"
            cdict["grid"] = repr(g)
        yield cid, cdict, step


def trsm_space(
    grid: Grid,
    dtype,
    L,
    bc_dims: Iterable[int] = (256, 512, 1024),
    leaves: Iterable[str] = ("invert", "solve"),
    modes: Iterable[str] = ("xla",),
):
    """bc x leaf x mode for the finished TRSM (the reference's diaginvert
    policies were forward-declared only, trsm/diaginvert/policy.h:8-9 —
    this is the sweep its tune.cpp never got).  The triangular operand L
    rides as a closure constant, so sweeps are bounded to moderate n
    (<= ~8192): at n >= 16384 a closed-over n x n array serializes into
    the program past the compile server's request limit (HTTP 413 — the
    trsm driver's jit-argument loop is the large-n path)."""
    from capital_tpu.models import trsm as trsm_mod

    prec = summa.default_precision(dtype)
    for bc, leaf, mode in itertools.product(bc_dims, leaves, modes):
        cfg = trsm_mod.TrsmConfig(
            base_case_dim=bc, mode=mode, precision=prec, leaf=leaf
        )

        def step(b, cfg=cfg):
            return trsm_mod.solve(grid, L, b, "L", "L", cfg=cfg)

        yield (
            f"bc{bc}_{leaf}_{mode}",
            {"base_case_dim": bc, "leaf": leaf, "mode": mode},
            step,
        )


def latency_measure(calls: int = 32, warmup: int = 3) -> Callable:
    """Measurement protocol for `run_sweep(measure=...)`: per-call wall
    time (harness.latency_samples — one dispatch + one device round-trip
    per sample, the cost a served request actually pays, NOT timed_loop's
    in-jit amortized body), sorted on **p99**.  Returns
    ``(p99_seconds, {"wall_ms": {"p50": .., "p95": .., "p99": ..}})`` so
    the sweep crowns the config with the best tail latency and the full
    percentile split rides the checkpoint and the ledger."""

    def measure(step, operand):
        fn = jax.jit(step)
        samples = harness.latency_samples(
            lambda: fn(operand), calls=calls, warmup=warmup
        )
        pcts = percentiles(samples)
        return pcts["p99"], {
            "wall_ms": {k: round(v * 1e3, 4) for k, v in pcts.items()}
        }

    return measure


def batched_small_space(
    op: str,
    n: int,
    B_rhs,
    dtype,
    impls: Iterable[str] = ("vmap", "pallas", "pallas_split"),
    blocks: Iterable[int] = (0,),
):
    """impl x block for the batched small-N kernel layer (ops/
    batched_small): the serve dispatch alternatives measured against each
    other — vmap-over-LAPACK (the pure-XLA fallback, no block axis),
    the fused batched-grid kernel, and the unfused two-launch split
    (posv only; the A/B that isolates the fusion win from the
    batched-grid win).  `B_rhs` is the bucket's RHS batch, closed over so
    the swept operand stays the single A array run_sweep's manifest and
    checkpoint key expect."""
    from capital_tpu.ops import batched_small
    from capital_tpu.serve import api

    prec = summa.default_precision(dtype)
    for impl in impls:
        if impl == "vmap":
            fn = api.batched(op, prec, "vmap")

            def step(a, fn=fn):
                return fn(a, B_rhs)

            yield "vmap", {"impl": "vmap"}, step
            continue
        if impl == "pallas_split" and op == "lstsq":
            continue  # lstsq has no split form (api.batched routes it fused)
        for blk in blocks:
            blk_eff = blk or batched_small.pick_block(n)
            if impl == "pallas":
                if op == "posv":
                    def step(a, blk=blk):
                        return batched_small.posv(
                            a, B_rhs, block=blk, precision=prec
                        )
                else:
                    def step(a, blk=blk):
                        return batched_small.lstsq(
                            a, B_rhs, block=blk, precision=prec
                        )
            else:
                def step(a, blk=blk):
                    R, info = batched_small.potrf(
                        a, uplo="U", block=blk, precision=prec
                    )
                    X = batched_small.potrs(
                        R, B_rhs, uplo="U", block=blk, precision=prec
                    )
                    return X, info

            yield (
                f"{impl}_b{blk_eff}",
                {"impl": impl, "block": blk_eff},
                step,
            )


def tune_small(
    grid: Grid,
    op: str,
    n: int,
    batch: int = 8,
    nrhs: int = 1,
    dtype=jnp.float32,
    out_dir: str = "autotune_out",
    occupancy: float = 1.0,
    rows: int | None = None,
    calls: int = 32,
    warmup: int = 3,
    checkpoint: bool = False,
    ledger: str | None = None,
    **space,
) -> list[SweepResult]:
    """Latency-mode sweep for ONE serve bucket: impl x block measured by
    per-call p99 wall time (latency_measure) at a FIXED batch occupancy —
    the serving objective, not peak TFLOP/s.  The operand batch carries
    ``round(occupancy * batch)`` real problems and identity fill for the
    tail, exactly the mixture a `serve` bucket flushes at that occupancy
    (batching.assemble's fill problems), so the crowned config is tuned
    for the batches production actually runs.  Results/checkpoints/ledger
    all ride run_sweep: resumable per-config, per-bucket p99 wall_ms in
    every autotune:small_<op> measured block."""
    import numpy as np

    if op not in ("posv", "lstsq"):
        raise ValueError(
            f"tune_small: op must be 'posv' or 'lstsq', got {op!r}"
        )
    if not 0.0 < occupancy <= 1.0:
        raise ValueError(f"tune_small: occupancy {occupancy} outside (0, 1]")
    real = max(1, round(occupancy * batch))
    rng = np.random.default_rng(2)
    if op == "posv":
        m_rows = n
        X = rng.standard_normal((batch, n, n))
        A = X @ X.transpose(0, 2, 1) / n + 3.0 * np.eye(n)
        A[real:] = np.eye(n)
    else:
        m_rows = rows if rows is not None else 4 * n
        A = rng.standard_normal((batch, m_rows, n))
        A[real:] = np.eye(m_rows, n)
    B = rng.standard_normal((batch, m_rows, nrhs))
    B[real:] = 0.0  # fill problems: zero RHS -> exact-zero solutions
    A = jax.block_until_ready(jnp.asarray(A, dtype))
    B = jax.block_until_ready(jnp.asarray(B, dtype))
    return run_sweep(
        f"small_{op}",
        batched_small_space(op, n, B, dtype, **space),
        A,
        out_dir,
        dtype=dtype,
        checkpoint=checkpoint,
        key_extra={
            **_grid_key(grid), "op": op, "n": n, "batch": batch,
            "nrhs": nrhs, "occupancy": occupancy, "calls": calls,
        },
        ledger=ledger,
        measure=latency_measure(calls=calls, warmup=warmup),
    )


def blocktri_space(
    nblocks: int,
    b: int,
    B_rhs,
    dtype,
    impls: Iterable[str] = ("xla", "pallas"),
    blocks: Iterable[int] = (0,),
    segs: Iterable[int] = (1, 4, 8),
    partitions: Iterable[int] = (0,),
):
    """impl x block-unroll x scan-segment-length for the block-tridiagonal
    chain (models/blocktri): the knobs that shape the scan-of-Pallas-blocks
    executable — in-kernel column unroll (`block`, the batched_small knob)
    and chain blocks per pallas_call (`seg`, launch amortization vs the
    VMEM step envelope).  The xla scan ignores both knobs (it scans one
    block per step through lax.linalg), so that impl contributes ONE
    baseline config rather than a degenerate axis product.  The
    'partitioned' impl (the round-13 Spike driver) sweeps the partitions
    x block-unroll plane instead: `partitions` snaps through
    resolve_partitions (so 0 is the √nblocks default and infeasible
    requests collapse — duplicates are deduped rather than re-measured),
    and `seg` is NOT an axis there (the interior fold already amortizes
    launches across batch·P problems; its inner scans keep the resolved
    default).  `B_rhs` rides as a closure so the swept operand stays the
    single packed A array (batch, 2, nblocks, b, b) — A[:, 0] the
    diagonal blocks, A[:, 1] the couplings, the serve bucket packing."""
    from capital_tpu.models import blocktri
    from capital_tpu.ops import batched_small

    prec = summa.default_precision(dtype)
    for impl in impls:
        if impl not in ("xla", "pallas", "partitioned"):
            raise ValueError(
                "blocktri_space: impl must be 'xla', 'pallas' or "
                f"'partitioned', got {impl!r}"
            )
        if impl == "xla":
            def step(a):
                return blocktri.posv(a[:, 0], a[:, 1], B_rhs,
                                     precision=prec, impl="xla")

            yield "xla", {"impl": "xla"}, step
            continue
        if impl == "partitioned":
            seen_p = set()
            for part in partitions:
                p_eff = blocktri.resolve_partitions(nblocks, part)
                for blk in blocks:
                    blk_eff = blk or batched_small.pick_block(b)
                    if (p_eff, blk_eff) in seen_p:
                        continue
                    seen_p.add((p_eff, blk_eff))

                    def step(a, blk=blk, part=p_eff):
                        return blocktri.posv(
                            a[:, 0], a[:, 1], B_rhs, block=blk,
                            precision=prec, impl="partitioned",
                            partitions=part)

                    yield (
                        f"part_p{p_eff}_b{blk_eff}",
                        {"impl": "partitioned", "partitions": p_eff,
                         "block": blk_eff},
                        step,
                    )
            continue
        for blk in blocks:
            blk_eff = blk or batched_small.pick_block(b)
            for seg in segs:
                seg_eff = blocktri.resolve_seg(nblocks, seg)

                def step(a, blk=blk, seg=seg_eff):
                    return blocktri.posv(
                        a[:, 0], a[:, 1], B_rhs, block=blk, seg=seg,
                        precision=prec, impl="pallas")

                yield (
                    f"pallas_b{blk_eff}_s{seg_eff}",
                    {"impl": "pallas", "block": blk_eff, "seg": seg_eff},
                    step,
                )


def tune_blocktri(
    grid: Grid,
    nblocks: int,
    b: int,
    batch: int = 8,
    nrhs: int = 1,
    dtype=jnp.float32,
    out_dir: str = "autotune_out",
    occupancy: float = 1.0,
    calls: int = 32,
    warmup: int = 3,
    checkpoint: bool = False,
    ledger: str | None = None,
    **space,
) -> list[SweepResult]:
    """Latency-mode sweep for ONE posv_blocktri serve bucket: impl x
    block-unroll x scan-segment-length measured by per-call p99 wall time
    (latency_measure) at fixed batch occupancy — the same serving
    objective as tune_small, on the chain op.  The operand batch carries
    ``round(occupancy * batch)`` real SPD chains and identity-chain fill
    (identity diagonal blocks, zero couplings, zero RHS — exactly
    batching.fill_problem) for the tail."""
    import numpy as np

    if not 0.0 < occupancy <= 1.0:
        raise ValueError(f"tune_blocktri: occupancy {occupancy} outside (0, 1]")
    real = max(1, round(occupancy * batch))
    rng = np.random.default_rng(4)
    G = rng.standard_normal((batch, nblocks, b, b))
    D = G @ G.transpose(0, 1, 3, 2) / b + 3.0 * np.eye(b)
    C = 0.3 / np.sqrt(b) * rng.standard_normal((batch, nblocks, b, b))
    C[:, 0] = 0.0
    D[real:] = np.eye(b)
    C[real:] = 0.0
    B = rng.standard_normal((batch, nblocks, b, nrhs))
    B[real:] = 0.0  # fill chains: zero RHS -> exact-zero solutions
    A = jax.block_until_ready(jnp.asarray(np.stack([D, C], axis=1), dtype))
    B = jax.block_until_ready(jnp.asarray(B, dtype))
    return run_sweep(
        "blocktri",
        blocktri_space(nblocks, b, B, dtype, **space),
        A,
        out_dir,
        dtype=dtype,
        checkpoint=checkpoint,
        key_extra={
            **_grid_key(grid), "op": "posv_blocktri", "nblocks": nblocks,
            "b": b, "batch": batch, "nrhs": nrhs, "occupancy": occupancy,
            "calls": calls,
        },
        ledger=ledger,
        measure=latency_measure(calls=calls, warmup=warmup),
    )


def arrowhead_space(
    nblocks: int,
    b: int,
    tail,
    dtype,
    impls: Iterable[str] = ("xla", "pallas"),
    blocks: Iterable[int] = (0,),
    segs: Iterable[int] = (1, 4, 8),
    partitions: Iterable[int] = (0,),
):
    """impl x border-column blocking x scan-segment-length for the
    block-arrowhead solve (models/arrowhead): the chain knobs of
    blocktri_space applied to the WIDENED chain solve that carries the
    border columns alongside the RHS.  `block` is the border-blocking
    knob — the batched_small in-kernel column unroll tiles the s + nrhs
    solve columns, so it decides how the border block-row is chunked
    through the chain sweep; `seg` amortizes pallas_call launches exactly
    as in blocktri_space; the xla impl contributes one baseline config
    and 'partitioned' sweeps the partitions x block plane (seg is not an
    axis there).  `tail` = (F, S, B_rhs, Bs) rides as a closure so the
    swept operand stays the single packed chain array
    (batch, 2, nblocks, b, b) — the serve bucket packing of the chain
    half, like blocktri_space."""
    from capital_tpu.models import arrowhead, blocktri
    from capital_tpu.ops import batched_small

    F, S, B_rhs, Bs = tail
    prec = summa.default_precision(dtype)
    for impl in impls:
        if impl not in ("xla", "pallas", "partitioned"):
            raise ValueError(
                "arrowhead_space: impl must be 'xla', 'pallas' or "
                f"'partitioned', got {impl!r}"
            )
        if impl == "xla":
            def step(a):
                return arrowhead.posv(a[:, 0], a[:, 1], F, S, B_rhs, Bs,
                                      precision=prec, impl="xla")

            yield "xla", {"impl": "xla"}, step
            continue
        if impl == "partitioned":
            seen_p = set()
            for part in partitions:
                p_eff = blocktri.resolve_partitions(nblocks, part)
                for blk in blocks:
                    blk_eff = blk or batched_small.pick_block(b)
                    if (p_eff, blk_eff) in seen_p:
                        continue
                    seen_p.add((p_eff, blk_eff))

                    def step(a, blk=blk, part=p_eff):
                        return arrowhead.posv(
                            a[:, 0], a[:, 1], F, S, B_rhs, Bs, block=blk,
                            precision=prec, impl="partitioned",
                            partitions=part)

                    yield (
                        f"part_p{p_eff}_b{blk_eff}",
                        {"impl": "partitioned", "partitions": p_eff,
                         "block": blk_eff},
                        step,
                    )
            continue
        for blk in blocks:
            blk_eff = blk or batched_small.pick_block(b)
            for seg in segs:
                seg_eff = blocktri.resolve_seg(nblocks, seg)

                def step(a, blk=blk, seg=seg_eff):
                    return arrowhead.posv(
                        a[:, 0], a[:, 1], F, S, B_rhs, Bs, block=blk,
                        seg=seg, precision=prec, impl="pallas")

                yield (
                    f"pallas_b{blk_eff}_s{seg_eff}",
                    {"impl": "pallas", "block": blk_eff, "seg": seg_eff},
                    step,
                )


def tune_arrowhead(
    grid: Grid,
    nblocks: int,
    b: int,
    border: int = 8,
    batch: int = 8,
    nrhs: int = 1,
    dtype=jnp.float32,
    out_dir: str = "autotune_out",
    occupancy: float = 1.0,
    calls: int = 32,
    warmup: int = 3,
    checkpoint: bool = False,
    ledger: str | None = None,
    **space,
) -> list[SweepResult]:
    """Latency-mode sweep for ONE posv_arrowhead serve bucket: impl x
    border blocking x scan-segment-length measured by per-call p99 wall
    time at fixed batch occupancy — tune_blocktri's objective, on the
    bordered op.  The operand batch carries ``round(occupancy * batch)``
    real arrowheads and identity fill for the tail (identity chain +
    identity corner + zero border/RHS — exactly batching.fill_problem)."""
    import numpy as np

    if not 0.0 < occupancy <= 1.0:
        raise ValueError(
            f"tune_arrowhead: occupancy {occupancy} outside (0, 1]")
    real = max(1, round(occupancy * batch))
    rng = np.random.default_rng(4)
    G = rng.standard_normal((batch, nblocks, b, b))
    D = G @ G.transpose(0, 1, 3, 2) / b + 3.0 * np.eye(b)
    C = 0.3 / np.sqrt(b) * rng.standard_normal((batch, nblocks, b, b))
    C[:, 0] = 0.0
    # border coupling shrinks with chain length: it touches every chain
    # block, so its Schur correction grows with nblocks·b and a fixed
    # scale would push the corner indefinite at long chains
    F = 0.3 / np.sqrt(nblocks * b) * rng.standard_normal(
        (batch, nblocks, border, b))
    S0 = rng.standard_normal((batch, border, border))
    S = S0 @ S0.transpose(0, 2, 1) / border + 5.0 * np.eye(border)
    B = rng.standard_normal((batch, nblocks, b, nrhs))
    Bs = rng.standard_normal((batch, border, nrhs))
    D[real:] = np.eye(b)
    C[real:] = 0.0
    F[real:] = 0.0
    S[real:] = np.eye(border)
    B[real:] = 0.0  # fill problems: zero RHS -> exact-zero solutions
    Bs[real:] = 0.0
    A = jax.block_until_ready(jnp.asarray(np.stack([D, C], axis=1), dtype))
    tail = tuple(
        jax.block_until_ready(jnp.asarray(t, dtype)) for t in (F, S, B, Bs)
    )
    return run_sweep(
        "arrowhead",
        arrowhead_space(nblocks, b, tail, dtype, **space),
        A,
        out_dir,
        dtype=dtype,
        checkpoint=checkpoint,
        key_extra={
            **_grid_key(grid), "op": "posv_arrowhead", "nblocks": nblocks,
            "b": b, "border": border, "batch": batch, "nrhs": nrhs,
            "occupancy": occupancy, "calls": calls,
        },
        ledger=ledger,
        measure=latency_measure(calls=calls, warmup=warmup),
    )


def update_small_space(
    n: int,
    k: int,
    V,
    dtype,
    op: str = "chol_update",
    impls: Iterable[str] = ("xla", "pallas"),
    blocks: Iterable[int] = (0,),
    panels: Iterable[int] = (0,),
):
    """impl x block-unroll (pallas) / panel-width (xla) for the rank-k
    factor-maintenance kernels (ops/update_small): the serve dispatch
    alternatives for the chol_update / chol_downdate buckets — the masked
    hyperbolic-rotation pallas sweep (knob: in-kernel column unroll
    `block`, the batched_small convention) against the blocked
    J-orthogonal XLA panel scan (knob: `panel`, rows factored per
    J-Cholesky step).  Each impl sweeps ITS OWN knob so the product stays
    non-degenerate (the other impl ignores it).  `V` rides as a closure
    so the swept operand stays the single resident-factor batch R the
    run_sweep manifest and checkpoint key expect."""
    from capital_tpu.ops import batched_small, update_small

    if op not in ("chol_update", "chol_downdate"):
        raise ValueError(
            f"update_small_space: op must be 'chol_update' or "
            f"'chol_downdate', got {op!r}"
        )
    fn = getattr(update_small, op)
    prec = summa.default_precision(dtype)
    for impl in impls:
        if impl not in ("xla", "pallas"):
            raise ValueError(
                f"update_small_space: impl must be 'xla' or 'pallas', "
                f"got {impl!r}"
            )
        if impl == "xla":
            for pan in panels:
                pan_eff = update_small.resolve_panel(n, k, pan)

                def step(r, pan=pan):
                    return fn(r, V, panel=pan, precision=prec, impl="xla")

                yield (
                    f"xla_p{pan_eff}",
                    {"impl": "xla", "panel": pan_eff},
                    step,
                )
            continue
        for blk in blocks:
            blk_eff = blk or batched_small.pick_block(n)

            def step(r, blk=blk):
                return fn(r, V, block=blk, precision=prec, impl="pallas")

            yield (
                f"pallas_b{blk_eff}",
                {"impl": "pallas", "block": blk_eff},
                step,
            )


def tune_update(
    grid: Grid,
    n: int,
    k: int,
    batch: int = 8,
    op: str = "chol_update",
    dtype=jnp.float32,
    out_dir: str = "autotune_out",
    occupancy: float = 1.0,
    calls: int = 32,
    warmup: int = 3,
    checkpoint: bool = False,
    ledger: str | None = None,
    **space,
) -> list[SweepResult]:
    """Latency-mode sweep for ONE chol_update / chol_downdate serve
    bucket: impl x block-unroll/panel measured by per-call p99 wall time
    (latency_measure) at fixed batch occupancy — the serving objective
    (a residency update sits on a request's critical path), not peak
    TFLOP/s.  The operand batch carries ``round(occupancy * batch)``
    real resident factors and identity fill for the tail (identity R
    with a zero V panel — exactly batching.pad_operands' fixed-point pad,
    so fill rotations are t = 0 no-ops); a downdate sweep downdates a
    panel the real factors provably contain (V scaled well inside the
    smallest eigenvalue), so no swept config ever measures the breakdown
    path."""
    import numpy as np

    if not 0.0 < occupancy <= 1.0:
        raise ValueError(f"tune_update: occupancy {occupancy} outside (0, 1]")
    real = max(1, round(occupancy * batch))
    rng = np.random.default_rng(7)
    X = rng.standard_normal((batch, n, n))
    A = X @ X.transpose(0, 2, 1) / n + 3.0 * np.eye(n)
    R = np.linalg.cholesky(A).transpose(0, 2, 1)
    R[real:] = np.eye(n)
    # 0.1/sqrt(n) scaling keeps ||VVᵀ|| well under the 3I shift: the
    # downdate stays deep inside SPD territory for every real problem
    V = 0.1 / np.sqrt(n) * rng.standard_normal((batch, n, k))
    V[real:] = 0.0  # fill factors: zero panel -> t = 0 no-op rotations
    R = jax.block_until_ready(jnp.asarray(R, dtype))
    V = jax.block_until_ready(jnp.asarray(V, dtype))
    return run_sweep(
        "update",
        update_small_space(n, k, V, dtype, op=op, **space),
        R,
        out_dir,
        dtype=dtype,
        checkpoint=checkpoint,
        key_extra={
            **_grid_key(grid), "op": op, "n": n, "k": k, "batch": batch,
            "occupancy": occupancy, "calls": calls,
        },
        ledger=ledger,
        measure=latency_measure(calls=calls, warmup=warmup),
    )


def tune_trsm(
    grid: Grid,
    n: int,
    nrhs: int,
    dtype=jnp.bfloat16,
    out_dir: str = "autotune_out",
    checkpoint: bool = False,
    ledger: str | None = None,
    **space,
) -> list[SweepResult]:
    if n > 8192:
        raise ValueError(
            f"tune_trsm: n={n} exceeds the sweep bound (8192): the closed-"
            "over n x n operand serializes into every config's program and "
            "breaks the compile server at n >= 16384 (HTTP 413) — use the "
            "trsm bench driver's jit-argument loop for large-n measurement"
        )
    L = residual.tri_operand(n, dtype)
    B = jax.block_until_ready(
        jax.random.normal(jax.random.key(1), (n, nrhs), dtype=dtype)
    )
    return run_sweep(
        "trsm", trsm_space(grid, dtype, L, **space), B, out_dir, dtype=dtype,
        checkpoint=checkpoint, key_extra={**_grid_key(grid), "n": n},
        ledger=ledger,
    )


def tune_cholinv(
    grid: Grid,
    n: int,
    dtype=jnp.bfloat16,
    out_dir: str = "autotune_out",
    prefilter_top_k: int = 0,
    checkpoint: bool = False,
    ledger: str | None = None,
    **space,
) -> list[SweepResult]:
    """Sweep cholinv configs.  With prefilter_top_k > 0, the alpha-beta
    planner (planner.cholinv_predict) ranks the (policy, bc) space
    first and only the top-k model candidates are measured — the predictive
    upgrade over the reference's measure-everything sweep (tune.cpp:239-253)."""
    A = residual.spd_operand(n, dtype)
    configs = list(cholinv_space(grid, dtype, **space))
    if prefilter_top_k and prefilter_top_k < len(configs):
        from capital_tpu.autotune import planner

        if len({c[1].get("layout", 0) for c in configs}) > 1:
            # the alpha-beta model is layout-insensitive (device ordering
            # is a locality knob): layout variants TIE in the ranking and
            # a top-k cut keeps whichever was generated first — the
            # dropped layouts go unmeasured
            print(
                "# autotune cholinv: --top-k with a layout axis prunes on "
                "modeled cost only (layouts tie in the model)"
            )
        spec = tracing.device_spec()
        peak = spec.peak_tflops(dtype) * 1e12 * 0.6
        preds = []
        for cid, cdict, step in configs:
            # each config is modeled with ITS OWN topology (grid axis rows
            # carry grid_shape/num_chunks in the config dict) — round 3
            # disabled the prefilter under a grid axis; with chunks in the
            # alpha term the model now ranks those rows too.  Layout
            # variants tie (the model is layout-insensitive), so a top-k
            # cut across a layout axis prunes on modeled cost only.
            shape = tuple(cdict.get("grid_shape", (grid.dx, grid.dy, grid.c)))
            q = cdict.get("num_chunks", grid.num_chunks)
            out, _ = planner.cholinv_predict(
                n, shape,
                [cdict["base_case_dim"]],
                [BaseCasePolicy[cdict["policy"]]],
                peak_flops=peak,
                itemsize=jnp.dtype(dtype).itemsize,
                split=cdict["split"],
                num_chunks=q,
                balance=cdict.get("balance", "block"),
            )
            preds.append(float(out[0, 0]))
        order = sorted(range(len(configs)), key=preds.__getitem__)
        kept = [configs[i] for i in order[:prefilter_top_k]]
        print(
            f"# autotune cholinv: planner kept {len(kept)}/{len(configs)} configs"
        )
        configs = kept
    return run_sweep(
        "cholinv", configs, A, out_dir, dtype=dtype, checkpoint=checkpoint,
        key_extra=_grid_key(grid), ledger=ledger,
    )


def tune_cacqr(
    grid: Grid,
    m: int,
    n: int,
    dtype=jnp.bfloat16,
    out_dir: str = "autotune_out",
    checkpoint: bool = False,
    ledger: str | None = None,
    **space,
) -> list[SweepResult]:
    A = jax.block_until_ready(
        jax.random.normal(jax.random.key(0), (m, n), dtype=dtype)
    )
    return run_sweep(
        "cacqr", cacqr_space(grid, dtype, **space), A, out_dir, dtype=dtype,
        checkpoint=checkpoint, key_extra=_grid_key(grid), ledger=ledger,
    )
