"""SolveEngine: the continuously-batched, AOT-cached, shape-bucketed solve
service — the facade over serve's three independently-scalable pieces
(docs/SERVING.md has the full lifecycle):

* **scheduler.py** — admission into in-flight bucket batches, async
  host→device staging (`jax.device_put` at submit, ahead of dispatch),
  overlapping dispatch of consecutive buckets with a bounded in-flight
  window, deadline flushes.  ``ServeConfig.scheduler="sync"`` is the PR 4
  stop-and-go loop, kept as the measured A/B baseline (serve/loadgen.py).

* **cache.py** — the AOT executable cache: every program the engine runs
  is compiled once via ``jax.jit(fn).lower(ShapeDtypeStruct...).compile()``
  under an explicit key (op, dtype, shape-bucket, mesh/topology,
  config-hash), with hit/miss counters that make "steady-state traffic
  hits zero recompiles" assertable.  ``ServeConfig.persist_dir`` adds the
  disk tier: compiled executables are serialized there so replicas and
  restarts skip warmup entirely (``compiles == 0`` on a warm dir — the
  cold-start gate of `make serve-smoke`); corrupt or stale entries fall
  back to compile-and-overwrite, never to the caller.

* **executor.py** — dispatch, donation, fault containment, result
  landing.  Batched dispatch does NOT synchronize; landing stamps each
  request's queue-wait/device latency split into the stats.

The engine itself keeps the public surface (`submit`/`pump`/`drain`/
`solve`/`warmup`/`cache_stats`/`emit_stats`) plus the policies that need
the whole picture: request validation, the host-side ``serve::ingest``
fault tap (a planted fault corrupts exactly one request and never bakes
into a cached executable), bucket resolution, and the config hash.

Donation (PR 4 contract, unchanged): engine-built batch buffers only,
TPU-only by default; posv donates its RHS batch, inv its operand batch,
lstsq nothing — its (m, nrhs) RHS cannot alias the (n, nrhs) solution and
XLA would silently drop the declaration.  ``SolveEngine(validate=True)``
asserts the compiled input_output_alias honors every declared donation at
cache-insert time (fresh compiles only — a disk-loaded executable was
validated by the process that compiled it).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Optional

import jax
import jax.numpy as jnp

from capital_tpu.models import blocktri
from capital_tpu.obs import spans
from capital_tpu.ops import batched_small, blocktri_small, lapack, update_small
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust import faultinject
from capital_tpu.robust.config import RobustConfig, RobustInfo
from capital_tpu.serve import api, batching, stats
from capital_tpu.serve.cache import ExecutableCache
from capital_tpu.serve.factorcache import FactorCache
from capital_tpu.serve.executor import (  # noqa: F401  (re-exported API)
    Executor,
    Response,
    Ticket,
    _Pending,
)
from capital_tpu.serve.scheduler import Scheduler
from capital_tpu.utils import tracing

SCHEDULERS = ("continuous", "sync")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine policy knobs.

    buckets: the n ladder (SPD dimension / lstsq columns).
    rows_buckets: the lstsq m ladder (requests bucket at m + column-pad).
    nrhs_buckets: the RHS-columns ladder.
    nblocks_buckets: the posv_blocktri chain-length ladder (number of
        diagonal blocks; padded chains append identity blocks with zero
        couplings — bitwise-inert, the chain is sequential).
    block_buckets: the posv_blocktri block-size ladder (per-block b;
        padded blocks embed diag(D_i, I)).  Both join the config hash
        with the dense ladders — the blocktri buckets AOT-cache alongside
        dense buckets under the same discipline.
    border_buckets: the posv_arrowhead border-width ladder (s — the
        number of dense corner rows coupling the chain to the corner).
        A structural rank, not an RHS count, so it gets its own ladder
        rather than riding nrhs_buckets; padded borders append zero rows
        and the corner embeds diag(S, I) (batching._pad_arrowhead).
        Joins the config hash with the other ladders.
    blocktri_impl: which chain ALGORITHM the posv_blocktri bucket
        programs compile (models/blocktri.ALGORITHMS): 'auto' lets
        posv's dispatch pick (the partitioned Spike driver above
        PARTITION_MIN_NBLOCKS when the kernel flavor is auto too),
        'partitioned' forces the split, 'scan' pins the sequential scan.
        Joins the config hash — a partitioned and a sequential engine
        compile different programs and must never share cache entries.
    blocktri_partitions: requested partition count for the partitioned
        chain driver (0 = resolve_partitions default, the largest
        divisor of nblocks ≤ √nblocks; requests decrement to a valid
        divisor per bucket).  Joins the config hash for the same reason
        — the partition count is baked into every compiled chain
        program's geometry.
    max_batch: per-bucket batch capacity — one executable per bucket at
        this fixed batch size; also the submit-time flush threshold.
    max_delay_s: oldest-request age that forces a flush at pump() — the
        latency bound a half-full batch is allowed to cost.
    precision: matmul precision inside the kernels ('highest' matches the
        models/ defaults; see CholinvConfig.precision).
    robust: attach per-request breakdown flagging (batched: detect-only;
        oversize lstsq: the full shifted-CholeskyQR recovery).
    donate: donate engine-built batch inputs to their executables; None =
        auto (TPU yes, CPU no — the CPU runtime warns and ignores).
    oversize: 'models' routes beyond-ladder requests through the unbatched
        models/ paths; 'reject' fails them (a hard-real-time posture where
        an unexpected compile is worse than an error).
    small_n_impl: which batched implementation the bucket executables use
        (serve/api.batched): 'auto' resolves per bucket at trace time
        (small VMEM-eligible posv/lstsq buckets take the fused batched-
        grid pallas kernels of ops/batched_small, the rest vmap-over-
        LAPACK); 'vmap' / 'pallas' / 'pallas_split' force one route for
        every bucket.  Joins the config hash — two engines differing here
        compile different programs and must never share cache entries.
    tail_fuse_depth: CholinvConfig.tail_fuse_depth for the oversize single
        route (fused recursion tail, ops/pallas_tpu.fused_tail; 0 =
        unfused).  Joins the config hash: a fused and an unfused engine
        compile different programs and must never share cache entries —
        the zero-recompile smoke stays green precisely because the knob
        is keyed, not hidden.
    scheduler: 'continuous' (default) overlaps staging/dispatch/landing
        across consecutive buckets (serve/scheduler.py); 'sync' is the
        PR 4 stop-and-go flush, kept as the loadgen A/B baseline.  NOT in
        the config hash: both modes run byte-identical programs, so they
        share cache entries (and a persistent dir) on purpose.
    max_inflight: continuous mode's bound on unlanded dispatched batches;
        the oldest is collected before exceeding it.
    persist_dir: disk directory for the persistent AOT cache tier
        (serve/cache.py); None keeps the cache in-memory only.  NOT in
        the config hash — the hash keys WHAT is compiled, the dir is
        WHERE it is remembered.
    factor_cache_bytes: byte budget of the resident-factor pool
        (serve/factorcache.py — the chol_update / chol_downdate /
        posv_cached / blocktri_extend residency state).  NOT in the
        config hash, deliberately: residency is host-side runtime policy
        (which factors are remembered), the compiled bucket programs are
        keyed by shape alone — two engines differing only here share
        cache entries and a persistent dir on purpose, and a resizing
        never recompiles anything.
    """

    buckets: tuple[int, ...] = (256, 512, 1024)
    rows_buckets: tuple[int, ...] = (4096, 16384, 65536)
    nrhs_buckets: tuple[int, ...] = (1, 8, 64)
    nblocks_buckets: tuple[int, ...] = (8, 32, 64)
    block_buckets: tuple[int, ...] = (32, 64, 128)
    border_buckets: tuple[int, ...] = (8, 16, 32)
    blocktri_impl: str = "auto"
    blocktri_partitions: int = 0
    max_batch: int = 8
    max_delay_s: float = 0.005
    precision: Optional[str] = "highest"
    robust: Optional[RobustConfig] = None
    donate: Optional[bool] = None
    oversize: str = "models"
    small_n_impl: str = "auto"
    tail_fuse_depth: int = 0
    scheduler: str = "continuous"
    max_inflight: int = 2
    persist_dir: Optional[str] = None
    factor_cache_bytes: int = 256 << 20


class SolveEngine:
    """See module docstring.  One engine per (grid, ServeConfig); not
    thread-safe (a single dispatch loop owns it, like a jax program)."""

    def __init__(self, grid: Optional[Grid] = None,
                 cfg: ServeConfig = ServeConfig(), *,
                 validate: bool = False):
        if cfg.oversize not in ("models", "reject"):
            raise ValueError(f"unknown oversize policy {cfg.oversize!r}")
        if cfg.small_n_impl not in batched_small.IMPLS:
            raise ValueError(
                f"unknown small_n_impl {cfg.small_n_impl!r}: expected one "
                f"of {batched_small.IMPLS}"
            )
        if cfg.blocktri_impl not in blocktri.ALGORITHMS:
            raise ValueError(
                f"unknown blocktri_impl {cfg.blocktri_impl!r}: expected "
                f"one of {blocktri.ALGORITHMS}"
            )
        if cfg.blocktri_partitions < 0:
            raise ValueError(
                f"blocktri_partitions must be >= 0, got "
                f"{cfg.blocktri_partitions}"
            )
        if cfg.scheduler not in SCHEDULERS:
            raise ValueError(
                f"unknown scheduler {cfg.scheduler!r}: expected one of "
                f"{SCHEDULERS}"
            )
        if cfg.max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got "
                             f"{cfg.max_inflight}")
        self.grid = grid or Grid.square(c=1, devices=jax.devices()[:1])  # guarded-by: <frozen>
        self.cfg = cfg  # guarded-by: <frozen>
        # validate: run the lint donation-honored rule on every executable at
        # cache-insert time — a declared donate_argnums that XLA silently
        # drops (shape mismatch with every output) raises instead of leaving
        # the batch buffer double-resident for the cache entry's lifetime.
        self.validate = validate  # guarded-by: <frozen>
        self.stats = stats.Collector()  # guarded-by: <owner-thread>
        self.cache = ExecutableCache(  # guarded-by: <owner-thread>
            cfg.persist_dir, devices=list(self.grid.mesh.devices.flat))
        # host-side resident-factor pool (serve/factorcache.py): never part
        # of a traced program, so residency changes never recompile
        self.factors = FactorCache(cfg.factor_cache_bytes)  # guarded-by: <owner-thread>
        self.executor = Executor(cfg, self.grid, self.stats)  # guarded-by: <owner-thread>
        self.scheduler = Scheduler(cfg, self.executor, self._resolve_bucket)  # guarded-by: <owner-thread>
        # per-request span traces (obs/spans.py): every submit() starts a
        # RequestTrace; the serve path stamps it host-side as the request
        # moves.  Bounded (oldest dropped, counted) — emit_trace() exports
        # the run's chains as one serve:trace record.
        self.trace_log = spans.TraceLog()  # guarded-by: <owner-thread>
        # rolling-window live telemetry (serve/telemetry.py): None until
        # enable_telemetry() attaches an aggregator to the stats tap.
        self.telemetry = None  # guarded-by: <owner-thread>
        self._next_id = 0  # guarded-by: <owner-thread>
        # the device batched executables run on — staging target.  The
        # bucket programs are single-device (jit, no sharding); oversize
        # requests run the models/ schedules on the full grid.
        self._stage_device = self.grid.mesh.devices.ravel()[0]  # guarded-by: <frozen>
        # config-hash: everything that changes the compiled programs or the
        # padding geometry — two engines differing here must never share
        # cache entries, and the key makes that structural.  scheduler /
        # max_inflight / persist_dir are deliberately absent: they change
        # when and where programs run, never what was compiled.
        ident = repr((cfg.buckets, cfg.rows_buckets, cfg.nrhs_buckets,
                      cfg.nblocks_buckets, cfg.block_buckets,
                      cfg.border_buckets,
                      cfg.max_batch, cfg.precision, cfg.robust,
                      cfg.small_n_impl, cfg.tail_fuse_depth,
                      cfg.blocktri_impl, cfg.blocktri_partitions))
        self._cfg_hash = hashlib.sha1(ident.encode()).hexdigest()[:12]  # guarded-by: <frozen>
        self._grid_key = (self.grid.dx, self.grid.dy, self.grid.c,  # guarded-by: <frozen>
                          self.grid.platform)

    # ---- cache -------------------------------------------------------------

    def _small_route(self, bucket: batching.Bucket) -> bool:
        """Whether this bucket's executable runs the batched-grid small-N
        kernels — the same static-shape resolution api.batched('auto')
        makes at trace time, re-derived here so the stats collector can
        split small-bucket latency (latency_ms_small) from the rest."""
        impl = self.cfg.small_n_impl
        if impl == "vmap":
            return False
        # tiered buckets factor at the PLAN's dtype, not the request's —
        # a guaranteed f64 bucket factors in f32 and CAN take the
        # batched-grid kernels (the whole point of the tier); resolve
        # capability against what the compiled program actually factors in
        dtype = bucket.dtype
        if bucket.tier != "balanced":
            from capital_tpu.robust import refine

            dtype = str(refine.plan(bucket.tier, bucket.dtype).factor_dtype)
        if not batched_small.dtype_capable(dtype):
            # forced pallas included: api._batched_pallas falls back to the
            # vmap program for f64, so the executable is NOT small-route
            return False
        if bucket.op in ("posv_blocktri", "blocktri_extend",
                         "posv_arrowhead"):
            # the chain resolves through blocktri_small's own gate (per
            # scan step, not per bucket problem); impl mapping mirrors
            # api._batched_blocktri ('vmap'->xla handled above, forced
            # pallas variants below).  extend's scan step is the factor
            # step at k = b (no RHS rides the chain); the arrowhead's
            # widened chain solve runs at s + nrhs columns, which is
            # exactly the packed tail's column count.
            if impl in ("pallas", "pallas_split"):
                return True
            _, nblocks, b, _ = bucket.a_shape
            seg = blocktri.resolve_seg(nblocks)
            if bucket.op == "posv_blocktri":
                k = bucket.b_shape[2]
            elif bucket.op == "posv_arrowhead":
                k = bucket.b_shape[1]
            else:
                k = b
            return blocktri_small.default_impl(
                b, k, seg, dtype
            ) == "pallas"
        if bucket.op in ("chol_update", "chol_downdate"):
            if impl in ("pallas", "pallas_split"):
                return True
            return update_small.default_impl(
                bucket.a_shape[0], bucket.b_shape[1], bucket.dtype
            ) == "pallas"
        if impl in ("pallas", "pallas_split"):
            return True
        if bucket.op in ("posv_cached", "posv_cached_miss"):
            # potrs / potrf+potrs against posv's exact geometry — posv's
            # resolution is the right proxy (api's auto does the same)
            a_shape = (bucket.capacity,) + bucket.a_shape
            b_shape = (bucket.capacity,) + bucket.b_shape
            return batched_small.default_impl(
                "posv", a_shape, b_shape, dtype
            ) == "pallas"
        a_shape = (bucket.capacity,) + bucket.a_shape
        if bucket.op == "inv":
            # inv rides the posv kernel with an identity RHS (api.batched):
            # eligibility is posv's with b_shape == a_shape
            return batched_small.default_impl(
                "posv", a_shape, a_shape, dtype
            ) == "pallas"
        b_shape = ((bucket.capacity,) + bucket.b_shape
                   if bucket.b_shape is not None else None)
        return batched_small.default_impl(
            bucket.op, a_shape, b_shape, dtype
        ) == "pallas"

    def _blocktri_algorithm(self, nblocks: int, dtype) -> str:
        """Which chain algorithm a posv_blocktri bucket program runs —
        'scan' or 'partitioned' — re-derived from the same static
        resolution api._batched_blocktri makes at trace time, so the
        stats collector's impl split (serve-report's `blocktri` note)
        reflects the compiled reality, not the request."""
        if self.cfg.blocktri_impl == "partitioned":
            return blocktri.posv_algorithm(
                nblocks, dtype, impl="partitioned",
                partitions=self.cfg.blocktri_partitions)
        if self.cfg.blocktri_impl == "scan":
            return "scan"
        if self.cfg.small_n_impl != "auto":
            # a forced kernel flavor pins the sequential program under
            # blocktri_impl='auto' (api._batched_blocktri)
            return "scan"
        return blocktri.posv_algorithm(
            nblocks, dtype, partitions=self.cfg.blocktri_partitions)

    def _resolve_bucket(self, bucket: batching.Bucket) -> tuple:
        """The scheduler's get_exe callback: (executable, small_route)."""
        return self._get_batched(bucket), self._small_route(bucket)

    def _get_batched(self, bucket: batching.Bucket, warmup: bool = False):
        key = ("batch", bucket.key, self._grid_key, self._cfg_hash)
        dn = self.executor.donate_argnums(bucket)

        def build():
            dt = jnp.dtype(bucket.dtype)
            specs = [jax.ShapeDtypeStruct(
                (bucket.capacity,) + bucket.a_shape, dt)]
            if bucket.b_shape is not None:
                specs.append(jax.ShapeDtypeStruct(
                    (bucket.capacity,) + bucket.b_shape, dt))
            fn = api.batched(bucket.op, self.cfg.precision,
                             self.cfg.small_n_impl,
                             blocktri_impl=self.cfg.blocktri_impl,
                             blocktri_partitions=self.cfg.blocktri_partitions,
                             tier=bucket.tier)
            exe = jax.jit(fn, donate_argnums=dn).lower(*specs).compile()
            if self.validate and dn:
                from capital_tpu.lint import program as lint_program

                probs = lint_program.check_donation(
                    exe, dn, target=f"serve:{bucket.key}",
                )
                if probs:
                    raise AssertionError(
                        "donation dropped at cache insert: "
                        + "; ".join(f.message for f in probs)
                    )
            return exe

        return self.cache.get(key, build, warmup=warmup)

    def _get_single(self, op: str, a_sds, b_sds, warmup: bool = False,
                    tier: str = "balanced"):
        key = ("single", op, str(a_sds.dtype), a_sds.shape,
               b_sds.shape if b_sds is not None else None,
               self._grid_key, self._cfg_hash, tier)

        def build():
            fn = api.single(op, self.grid, self.cfg.precision,
                            self.cfg.robust,
                            tail_fuse_depth=self.cfg.tail_fuse_depth,
                            tier=tier)
            specs = (a_sds,) if b_sds is None else (a_sds, b_sds)
            return jax.jit(fn).lower(*specs).compile()

        return self.cache.get(key, build, warmup=warmup)

    def cache_stats(self) -> dict:
        """Hit/miss counters over request-driven executable lookups plus
        compile and persistent-tier counters (serve/cache.py).  warmup()
        compiles count separately — hit_rate measures steady-state
        traffic, and the acceptance gate is hit_rate == 1.0 after warmup;
        ``compiles`` is the cold-start gate (0 on a warm persistent
        dir)."""
        return self.cache.stats()

    def warmup(self, specs) -> int:
        """Pre-compile (or load from the persistent tier) executables for
        example request shapes.  `specs` is an iterable of (op, a_shape,
        b_shape, dtype) or (op, a_shape, b_shape, dtype, accuracy_tier) —
        b_shape None for inv, tier defaulting to 'balanced'.  Shapes
        resolve through the SAME bucket ladder as submit(), so warming one
        representative per bucket covers every shape that maps there;
        oversize shapes warm their exact-shape single route.  Returns the
        number of fresh compiles (0 when every entry loaded from a warm
        persist_dir)."""
        before = self.cache.warmup_compiles
        for op, a_shape, b_shape, dtype, *rest in specs:
            tier = rest[0] if rest else "balanced"
            dt = jnp.dtype(dtype)
            bucket = batching.bucket_for(
                op, tuple(a_shape), tuple(b_shape) if b_shape else None,
                str(dt), self.cfg, tier=tier,
            )
            if bucket is not None:
                self._get_batched(bucket, warmup=True)
            elif self.cfg.oversize == "models" and (
                    tier == "balanced" or (op, tier) in api.SINGLE_TIERS):
                a_sds = jax.ShapeDtypeStruct(tuple(a_shape), dt)
                b_sds = (jax.ShapeDtypeStruct(tuple(b_shape), dt)
                         if b_shape else None)
                self._get_single(op, a_sds, b_sds, warmup=True, tier=tier)
        return self.cache.warmup_compiles - before

    # ---- request path ------------------------------------------------------

    def submit(self, op: str, A, B=None, *,
               factor_token: Optional[str] = None,
               accuracy_tier: str = "balanced",
               deadline_ms: Optional[float] = None) -> Ticket:
        """Enqueue one solve request; returns a Ticket that resolves when
        its batch lands.  A capacity-full bucket DISPATCHES inside this
        call; under the continuous scheduler the dispatch is issued
        without waiting (the ticket is `done`, and `result()`/`pump()`/
        `drain()` land it).

        `accuracy_tier` makes precision a scheduling dimension
        (docs/SERVING.md "Accuracy tiers"): 'balanced' (default) runs the
        request dtype end-to-end; 'fast' factors one dtype DOWN
        (f64→f32, f32→bf16); 'guaranteed' factors in the fast dtype but
        iteratively refines the answer back to the request dtype's
        backward error (robust/refine), failing the request loudly if
        refinement does not converge.  Tiers bucket separately — the tier
        is part of the executable cache key — and are only defined for
        posv / lstsq / posv_blocktri.

        `factor_token` names a resident factor for the factor-residency
        ops (docs/SERVING.md "Factor residency"): chol_update /
        chol_downdate submit only the rank-k panel A = V (n, k) against
        the resident factor (loud failure when not resident — V alone
        cannot determine the answer); posv_cached submits the full
        (A, B) so a miss can seed the factor by refactoring; and
        blocktri_extend submits the appended chain packing
        A = (2, nblocks, b, b) — a never-seen token seeds a fresh chain
        (C[:, 0] zeroed host-side), an EVICTED token fails loudly (a
        silently re-seeded chain would be a wrong answer).

        `deadline_ms` is a per-request latency SLO (relative to submit
        entry).  It never changes scheduling today — it stamps the
        request's trace so the serve:trace record carries
        slack-at-dispatch and, on violation, which span ate the budget
        (docs/SERVING.md 'Deadlines and SLO attribution')."""
        t_enq = spans.now()
        tid = self._next_id
        self._next_id += 1
        ticket = Ticket(tid, t_enq)
        ticket.deadline_ms = (float(deadline_ms)
                              if deadline_ms is not None else None)
        if A is None and op != "session_close":
            raise ValueError(f"{op} requires an A operand")
        with tracing.host_scope("SV::stage"):
            A = jnp.asarray(A) if A is not None else None
            B = jnp.asarray(B) if B is not None else None
        if op not in batching.OPS and op not in batching.SESSION_OPS:
            raise ValueError(
                f"unknown serve op {op!r}; expected one of "
                f"{batching.OPS + batching.SESSION_OPS}"
            )
        if accuracy_tier != "balanced" and op not in api.TIER_OPS:
            raise ValueError(
                f"accuracy_tier={accuracy_tier!r} is only defined for "
                f"{api.TIER_OPS}, got op {op!r}"
            )
        if op in batching.SESSION_OPS:
            if factor_token is None:
                raise ValueError(
                    f"{op} requires factor_token= (the session id — "
                    "docs/SERVING.md 'Streaming sessions')"
                )
            return self._submit_session(ticket, op, A, B,
                                        str(factor_token), accuracy_tier,
                                        t_enq)
        if op in batching.FACTOR_OPS:
            if factor_token is None:
                raise ValueError(
                    f"{op} requires factor_token= (docs/SERVING.md "
                    "'Factor residency')"
                )
            return self._submit_factor(ticket, op, A, B,
                                       str(factor_token), t_enq)
        if factor_token is not None:
            raise ValueError(
                f"factor_token is only valid for {batching.FACTOR_OPS}, "
                f"got op {op!r}"
            )
        if op == "posv_blocktri":
            if (A.ndim != 4 or A.shape[0] != 2
                    or A.shape[2] != A.shape[3]):
                raise ValueError(
                    f"posv_blocktri needs A = (2, nblocks, b, b) — "
                    f"[diagonal blocks, sub-diagonal blocks] — got "
                    f"{A.shape}"
                )
            if B is None or B.ndim != 3 or B.shape[:2] != A.shape[1:3]:
                raise ValueError(
                    f"posv_blocktri needs B = (nblocks, b, nrhs) riding "
                    f"A {A.shape}, got {None if B is None else B.shape}"
                )
        if op == "posv_arrowhead":
            if (A.ndim != 4 or A.shape[0] != 2
                    or A.shape[2] != A.shape[3]):
                raise ValueError(
                    f"posv_arrowhead needs A = (2, nblocks, b, b) — "
                    f"[diagonal blocks, sub-diagonal blocks], the "
                    f"posv_blocktri chain pack — got {A.shape}"
                )
            n_t = A.shape[1] * A.shape[2]
            if (B is None or B.ndim != 2 or B.shape[0] <= n_t
                    or B.shape[1] <= B.shape[0] - n_t):
                raise ValueError(
                    f"posv_arrowhead needs the packed tail B = "
                    f"(nblocks·b + s, s + nrhs) with s >= 1, nrhs >= 1 "
                    f"(models/arrowhead.pack) riding A {A.shape} "
                    f"(nblocks·b = {n_t}), got "
                    f"{None if B is None else B.shape}"
                )
        if op in ("posv", "lstsq") and (B is None or B.ndim != 2
                                        or B.shape[0] != A.shape[0]):
            raise ValueError(
                f"{op} needs a 2D RHS with {A.shape[0]} rows, got "
                f"{None if B is None else B.shape}"
            )
        if op in ("posv", "inv") and A.shape[0] != A.shape[1]:
            raise ValueError(f"{op} needs a square SPD operand, got {A.shape}")
        if op == "lstsq" and A.shape[0] < A.shape[1]:
            raise ValueError(f"lstsq expects tall input, got {A.shape}")
        # trace starts AFTER the raise-validation above: a rejected call
        # never entered the serve path, so no orphan chain may pollute
        # the 100%-complete trace gate
        self._start_trace(ticket, op, accuracy_tier)
        try:
            # HOST-side per-request fault tap on the concrete operand:
            # deterministic per submit() occurrence, and — critically —
            # never part of a traced program, so a fault corrupts exactly
            # one request and leaves the executable cache clean.
            A = faultinject.tap(A, point="serve::ingest")
        except faultinject.FaultInjected as e:
            self.executor.fail(ticket, op, str(e), t_enq)
            return ticket
        bucket = batching.bucket_for(
            op, A.shape, B.shape if B is not None else None,
            str(A.dtype), self.cfg, tier=accuracy_tier,
        )
        if (bucket is None and accuracy_tier != "balanced"
                and (op, accuracy_tier) not in api.SINGLE_TIERS):
            # the oversize route has a tiered program only for a
            # 'guaranteed' posv (robust/refine.posv_dense) — silently
            # serving any other tiered request at balanced precision (or a
            # 'fast' one at full) would betray the contract, so fail loud
            self.executor.fail(
                ticket, op,
                f"no bucket for {op} {A.shape}: accuracy_tier="
                f"{accuracy_tier!r} requests have no oversize route",
                t_enq,
            )
            return ticket
        if op in ("posv_blocktri", "posv_arrowhead"):
            # impl split: the bucketed program follows the engine's
            # algorithm knobs; the oversize single route runs posv's own
            # defaults (api.single), so it is counted that way.  The
            # arrowhead counts too — its widened chain solve runs the
            # same algorithm resolution (api._batched_arrowhead).
            self.stats.note_blocktri_impl(
                self._blocktri_algorithm(bucket.a_shape[1], bucket.dtype)
                if bucket is not None
                else blocktri.posv_algorithm(A.shape[1], A.dtype))
        if bucket is None:
            if self.cfg.oversize == "reject":
                self.executor.fail(
                    ticket, op,
                    f"no bucket for {op} {A.shape} and oversize='reject'",
                    t_enq,
                )
            else:
                self._run_single(ticket, op, A, B, t_enq, accuracy_tier)
            return ticket
        pa, pb = batching.pad_operands(op, A, B, bucket)
        if bucket.tier == "guaranteed":
            sink = self._refine_sink(op)
        elif op == "posv_arrowhead":
            sink = self._arrowhead_sink(tuple(A.shape), tuple(B.shape))
        else:
            sink = None
        self._admit(ticket, bucket, pa, pb, tuple(A.shape),
                    tuple(B.shape) if B is not None else None, t_enq,
                    sink=sink)
        return ticket

    def pump(self, now: Optional[float] = None) -> int:
        """Deadline flush + opportunistic landing: dispatch every bucket
        whose oldest request has aged past max_delay_s, and land every
        in-flight batch whose results are ready.  Call from the dispatch
        loop between submits; returns the number of batches flushed."""
        now = spans.now() if now is None else now
        return self.scheduler.pump(now)

    def drain(self) -> int:
        """Flush every non-empty queue regardless of age and land every
        in-flight batch (shutdown / test barrier).  Returns the number of
        batches flushed."""
        return self.scheduler.drain()

    def solve(self, op: str, A, B=None, *,
              factor_token: Optional[str] = None,
              accuracy_tier: str = "balanced",
              deadline_ms: Optional[float] = None) -> Response:
        """Convenience synchronous path: submit + drain + result."""
        ticket = self.submit(op, A, B, factor_token=factor_token,
                             accuracy_tier=accuracy_tier,
                             deadline_ms=deadline_ms)
        if not ticket.done:
            self.drain()
        return ticket.result()

    def queue_depth(self) -> int:
        return self.scheduler.queue_depth()

    def emit_stats(self, path: Optional[str] = None, **extra) -> dict:
        """Snapshot telemetry + cache counters into one serve:request_stats
        ledger record (appended to `path` when given)."""
        return self.stats.emit(
            path, grid=self.grid, config=self.cfg,
            cache=self.cache_stats(), factor_cache=self.factors.stats(),
            **extra,
        )

    def emit_trace(self, path: Optional[str] = None, *,
                   bubble_tol_ms: float = spans.DEFAULT_BUBBLE_TOL_MS,
                   **extra) -> dict:
        """Export the run's span chains as one serve:trace ledger record
        (appended to `path` when given) — the per-request counterpart of
        emit_stats()."""
        return self.trace_log.emit(
            path, grid=self.grid, config=self.cfg,
            bubble_tol_ms=bubble_tol_ms, **extra,
        )

    def enable_telemetry(self, window_s: float = 1.0, *,
                         sample_cap: Optional[int] = None):
        """Attach a rolling-window aggregator (serve/telemetry.py) to the
        stats tap: every request/batch/queue-depth note also lands in the
        current time window, and `self.telemetry.emit(path)` appends one
        serve:window record per closed window.  Host-side counters only —
        never part of the config hash, never a compiled program's
        concern.  Returns the aggregator."""
        from capital_tpu.serve import telemetry

        kw = {} if sample_cap is None else {"sample_cap": sample_cap}
        self.telemetry = telemetry.WindowAggregator(window_s, **kw)
        self.stats.window = self.telemetry
        return self.telemetry

    def _start_trace(self, ticket: Ticket, op: str,
                     tier: str) -> spans.RequestTrace:
        tr = self.trace_log.start(
            ticket.request_id, op, ticket.t_enq,
            deadline_ms=ticket.deadline_ms,
            tier=tier, cfg_hash=self._cfg_hash,
            replica_id=self.stats.replica_id,
        )
        ticket.trace = tr
        return tr

    # ---- factor residency (docs/SERVING.md "Factor residency") -------------

    def install_factor(self, token: str, R) -> list[str]:
        """Out-of-band seeding: install an upper-triangular R (A = RᵀR,
        the lapack.potrf uplo='U' convention) as the resident dense
        factor for `token`.  The serve-path seeding route is a
        posv_cached miss; this exists for clients that factored locally
        and want updates/solves without one priced miss.  Returns the
        tokens the byte budget evicted to make room."""
        R = jnp.asarray(R)
        if R.ndim != 2 or R.shape[0] != R.shape[1]:
            raise ValueError(
                f"install_factor needs a square (n, n) factor, got {R.shape}"
            )
        return self.factors.put(
            token, "dense", (R,),
            {"n": int(R.shape[0]), "dtype": str(R.dtype)},
        )

    def release_factor(self, token: str) -> bool:
        """Explicit client drop of a resident factor (clears any eviction
        tombstone — the token is free for honest reuse).  Returns whether
        an entry was resident."""
        return self.factors.release(token)

    def factor_stats(self) -> dict:
        """The FactorCache counter block (hits/misses/evictions/installs/
        released/downdate_degrades/bytes/hit_rate) — also emitted inside
        every serve:request_stats record once factor traffic exists."""
        return self.factors.stats()

    # ---- internals ---------------------------------------------------------

    def _admit(self, ticket: Ticket, bucket: batching.Bucket, pa, pb,
               a_shape, b_shape, t_enq: float, client_op=None,
               sink=None) -> None:
        """Stage + enqueue one padded request (the shared tail of submit
        and _submit_factor)."""
        if self.cfg.scheduler == "continuous":
            # async host->device staging AHEAD of dispatch: the transfer
            # overlaps whatever batch is currently executing, so by flush
            # time the operands are already device-resident (on-device
            # no-op when eager padding placed them there)
            with tracing.host_scope("SV::stage"):
                pa = jax.device_put(pa, self._stage_device)
                if pb is not None:
                    pb = jax.device_put(pb, self._stage_device)
        if ticket.trace is not None:
            # admit covers validation + fault tap + pad + stage; stamped
            # BEFORE scheduler.admit because a capacity flush dispatches
            # synchronously inside it (the enqueue span must start here)
            ticket.trace.tag(bucket=batching.bucket_label(bucket),
                             tier=bucket.tier)
            ticket.trace.extend("admit")
        self.scheduler.admit(bucket, _Pending(
            ticket, pa, pb, a_shape, b_shape, t_enq,
            client_op=client_op, sink=sink,
        ))
        self.stats.note_queue_depth(self.queue_depth())

    def _submit_factor(self, ticket: Ticket, op: str, A, B, token: str,
                       t_enq: float) -> Ticket:
        """The factor-residency submit path.  Residency resolves HERE,
        host-side, before padding or staging — the compiled bucket
        programs never see tokens, so residency changes never recompile
        anything.  Every not-servable case lands a LOUD failed Response,
        never a silent wrong answer: update/downdate against a
        non-resident token (V alone cannot determine the answer), any
        kind/shape/dtype mismatch with the resident entry, an extend
        against an EVICTED chain (a silently re-seeded identity chain
        would be a wrong answer), and oversize shapes regardless of
        cfg.oversize (the models/ paths have no residency to serve
        against)."""
        if op in ("chol_update", "chol_downdate"):
            if A.ndim != 2 or B is not None:
                raise ValueError(
                    f"{op} needs A = V (n, k), no B — the resident factor "
                    f"is the other operand; got A {A.shape}"
                    + ("" if B is None else f", B {B.shape}")
                )
        elif op == "posv_cached":
            if A.ndim != 2 or A.shape[0] != A.shape[1]:
                raise ValueError(
                    f"posv_cached needs a square SPD operand, got {A.shape}"
                )
            if B is None or B.ndim != 2 or B.shape[0] != A.shape[0]:
                raise ValueError(
                    f"posv_cached needs a 2D RHS with {A.shape[0]} rows, "
                    f"got {None if B is None else B.shape}"
                )
        else:  # blocktri_extend
            if A.ndim != 4 or A.shape[0] != 2 or A.shape[2] != A.shape[3]:
                raise ValueError(
                    f"blocktri_extend needs A = (2, nblocks, b, b) appended "
                    f"[diagonal, sub-diagonal] blocks, got {A.shape}"
                )
            if B is not None:
                raise ValueError(
                    f"blocktri_extend takes no B (the resident carry is "
                    f"the second operand), got B {B.shape}"
                )
        # same discipline as submit(): trace only once the request is past
        # the raise-validation and actually inside the serve path
        self._start_trace(ticket, op, "balanced")
        try:
            # same host-side per-request tap as submit(): a planted fault
            # corrupts exactly one request's operand and never bakes into
            # a cached executable OR a resident factor (sinks refuse to
            # install flagged results)
            A = faultinject.tap(A, point="serve::ingest")
        except faultinject.FaultInjected as e:
            self.executor.fail(ticket, op, str(e), t_enq)
            return ticket
        dt = str(A.dtype)
        ent = self.factors.lookup(token)

        def lose(msg: str) -> Ticket:
            self.executor.fail(
                ticket, op,
                msg + " (docs/SERVING.md 'Factor residency')", t_enq,
            )
            return ticket

        if op in ("chol_update", "chol_downdate"):
            if ent is None:
                why = ("evicted" if self.factors.evicted(token)
                       else "never seeded")
                return lose(
                    f"factor_token {token!r} not resident ({why}): {op} "
                    "ships only the rank-k panel V, so there is nothing to "
                    "update — seed with posv_cached or install_factor()"
                )
            if ent.kind != "dense":
                return lose(
                    f"factor_token {token!r} holds a {ent.kind} factor; "
                    f"{op} needs a dense one"
                )
            R = ent.arrays[0]
            n = int(R.shape[0])
            if A.shape[0] != n or str(R.dtype) != dt:
                return lose(
                    f"V {A.shape}/{dt} does not ride the resident factor "
                    f"({n}, {n})/{R.dtype} under token {token!r}"
                )
            bucket = batching.bucket_for(op, (n, n), tuple(A.shape), dt,
                                         self.cfg)
            if bucket is None:
                return lose(
                    f"no bucket for {op} n={n} k={A.shape[1]}: factor ops "
                    "have no oversize route"
                )
            pa, pb = batching.pad_operands(op, R, A, bucket)
            self._admit(
                ticket, bucket, pa, pb, (n, n), tuple(A.shape), t_enq,
                client_op=op, sink=self._update_sink(op, token, n, A),
            )
            return ticket

        if op == "posv_cached":
            n = int(A.shape[0])
            if ent is not None:
                if ent.kind != "dense":
                    return lose(
                        f"factor_token {token!r} holds a {ent.kind} "
                        "factor; posv_cached needs a dense one"
                    )
                R = ent.arrays[0]
                if int(R.shape[0]) != n or str(R.dtype) != dt:
                    return lose(
                        f"operand {A.shape}/{dt} does not match the "
                        f"resident factor {tuple(R.shape)}/{R.dtype} "
                        f"under token {token!r}"
                    )
                bucket = batching.bucket_for(
                    "posv_cached", (n, n), tuple(B.shape), dt, self.cfg)
                if bucket is None:
                    return lose(
                        f"no bucket for posv_cached n={n} "
                        f"nrhs={B.shape[1]}: factor ops have no oversize "
                        "route"
                    )
                pa, pb = batching.pad_operands("posv_cached", R, B, bucket)
                self._admit(ticket, bucket, pa, pb, (n, n),
                            tuple(B.shape), t_enq, client_op="posv_cached")
                return ticket
            # miss: seed by refactoring through the 3-output miss program
            # (X, R, info) — the full operand is on the wire, so re-seeding
            # is safe even for an evicted token (unlike extend, no hidden
            # state is lost); priced as a residency miss
            bucket = batching.bucket_for(
                "posv_cached_miss", tuple(A.shape), tuple(B.shape), dt,
                self.cfg)
            if bucket is None:
                return lose(
                    f"no bucket for posv_cached n={n} nrhs={B.shape[1]}: "
                    "factor ops have no oversize route"
                )
            pa, pb = batching.pad_operands("posv_cached_miss", A, B, bucket)
            self._admit(
                ticket, bucket, pa, pb, tuple(A.shape), tuple(B.shape),
                t_enq, client_op="posv_cached",
                sink=self._seed_sink(token, n),
            )
            return ticket

        # blocktri_extend
        nblocks, b = int(A.shape[1]), int(A.shape[2])
        if ent is not None:
            if ent.kind != "blocktri":
                return lose(
                    f"factor_token {token!r} holds a {ent.kind} factor; "
                    "blocktri_extend needs a blocktri chain"
                )
            if int(ent.meta["b"]) != b or ent.meta["dtype"] != dt:
                return lose(
                    f"appended blocks {A.shape}/{dt} do not ride the "
                    f"resident chain b={ent.meta['b']}/"
                    f"{ent.meta['dtype']} under token {token!r}"
                )
            carry = ent.arrays[2]
            prior = int(ent.meta["nblocks"])
        else:
            if self.factors.evicted(token):
                return lose(
                    f"factor_token {token!r} was EVICTED: extending a "
                    "silently re-seeded identity chain would be a wrong "
                    "answer — resubmit the full chain under a fresh token"
                )
            # fresh chain: identity carry + zeroed first coupling run the
            # SAME compiled program as a continuation (zero-recompile —
            # seed/continue is data, not a shape)
            carry = jnp.eye(b, dtype=A.dtype)
            A = A.at[1, 0].set(jnp.zeros((b, b), A.dtype))
            prior = 0
        bucket = batching.bucket_for(
            "blocktri_extend", tuple(A.shape), (b, b), dt, self.cfg)
        if bucket is None:
            return lose(
                f"no bucket for blocktri_extend nblocks={nblocks} b={b}: "
                "factor ops have no oversize route"
            )
        pa, pb = batching.pad_operands("blocktri_extend", A, carry, bucket)
        self._admit(
            ticket, bucket, pa, pb, tuple(A.shape), (b, b), t_enq,
            client_op="blocktri_extend",
            sink=self._extend_sink(token, b, prior),
        )
        return ticket

    # ---- streaming sessions (docs/SERVING.md "Streaming sessions") ---------

    def _submit_session(self, ticket: Ticket, op: str, A, B, token: str,
                        tier: str, t_enq: float) -> Ticket:
        """The session protocol submit path (serve/sessions.py drives it;
        the wire contract is engine-level so sessions are first-class serve
        ops, not a facade trick).  Residency resolves HERE, host-side,
        exactly like `_submit_factor` — the compiled bucket programs never
        see session ids, so session churn never recompiles anything.

        Wire shapes: session_open / session_append take the window blocks
        A = (2, nblocks, b, b) ([D; C] — C[:, 0] live for append, zeroed
        host-side for open) and no B; session_solve takes the CURRENT
        window A = (2, nblocks, b, b) plus B = (nblocks, b, nrhs) and the
        engine composes the 4-stack [D; C; L; Wt] from the resident
        factor; session_contract takes A = k (scalar — the number of
        oldest blocks to drop) and returns the NEW head diagonal factor
        block L_k (b, b) so the client can marginalize its window head
        (D[0] ← L_k·L_kᵀ, C[0] ← 0 — models/blocktri.contract docstring);
        session_close takes no operands and returns a 0/1 released flag.

        Loudness contract: any request against an EVICTED session fails
        with a tombstone-loud ``SessionEvicted`` error — the client must
        re-seed via session_open (which clears the tombstone); a request
        against a never-opened session fails as 'not open'.  Both are
        failed Responses, never silent identity answers."""
        if op in ("session_open", "session_append"):
            if (A.ndim != 4 or A.shape[0] != 2
                    or A.shape[2] != A.shape[3]):
                raise ValueError(
                    f"{op} needs A = (2, nblocks, b, b) window blocks "
                    f"[diagonal, sub-diagonal], got {A.shape}"
                )
            if B is not None:
                raise ValueError(
                    f"{op} takes no B (the carry is resident), got "
                    f"B {B.shape}"
                )
        elif op == "session_solve":
            if (A.ndim != 4 or A.shape[0] != 2
                    or A.shape[2] != A.shape[3]):
                raise ValueError(
                    f"session_solve needs A = (2, nblocks, b, b) — the "
                    f"session's current [D; C] window — got {A.shape}"
                )
            if B is None or B.ndim != 3 or B.shape[:2] != A.shape[1:3]:
                raise ValueError(
                    f"session_solve needs B = (nblocks, b, nrhs) riding "
                    f"A {A.shape}, got {None if B is None else B.shape}"
                )
        elif op == "session_contract":
            if A.ndim != 0:
                raise ValueError(
                    f"session_contract needs a scalar A = k (blocks to "
                    f"drop), got shape {A.shape}"
                )
            if B is not None:
                raise ValueError("session_contract takes no B")
        else:  # session_close
            if A is not None or B is not None:
                raise ValueError("session_close takes no operands")
        self._start_trace(ticket, op, tier)

        def lose(msg: str) -> Ticket:
            self.executor.fail(
                ticket, op,
                msg + " (docs/SERVING.md 'Streaming sessions')", t_enq,
            )
            return ticket

        def lose_missing() -> Ticket:
            if self.factors.evicted(token):
                return lose(
                    f"SessionEvicted: session {token!r} lost its resident "
                    "factor to cache pressure — re-seed the window with "
                    "session_open"
                )
            return lose(f"session {token!r} is not open")

        # host-side administrative ops: no compiled program, no device
        # flops — the span chain collapses to admit -> cache_lookup ->
        # respond under the 'session' trace kind
        if op == "session_close":
            if ticket.trace is not None:
                ticket.trace.kind = "session"
                ticket.trace.extend("admit")
            released = self.factors.release(token)
            if ticket.trace is not None:
                ticket.trace.extend("cache_lookup")
            return self._finish_host(
                ticket, op, jnp.int32(1 if released else 0), t_enq)
        if op == "session_contract":
            if ticket.trace is not None:
                ticket.trace.kind = "session"
                ticket.trace.extend("admit")
            ent = self.factors.lookup(token)
            if ticket.trace is not None:
                ticket.trace.extend("cache_lookup")
            if ent is None:
                return lose_missing()
            if ent.kind != "session":
                return lose(
                    f"factor_token {token!r} holds a {ent.kind} factor; "
                    "session ops need a session chain"
                )
            k = int(A)
            nblocks = int(ent.meta["nblocks"])
            if not 0 < k < nblocks:
                return lose(
                    f"session_contract k={k} must satisfy 0 < k < "
                    f"nblocks={nblocks} (contracting the whole chain is "
                    "session_close)"
                )
            L, Wt = ent.arrays[0], ent.arrays[1]
            Lc, Wtc = blocktri.contract(L[None], Wt[None], k)
            Lc, Wtc = Lc[0], Wtc[0]
            self.factors.put(
                token, "session", (Lc, Wtc, ent.arrays[2]),
                {"b": int(ent.meta["b"]), "nblocks": nblocks - k,
                 "dtype": ent.meta["dtype"],
                 "dropped": int(ent.meta.get("dropped", 0)) + k},
            )
            # the new head diagonal factor block: exactly what the client
            # needs to marginalize its window head (D[0] <- L_k·L_kᵀ)
            return self._finish_host(ticket, op, Lc[0], t_enq)

        try:
            A = faultinject.tap(A, point="serve::ingest")
        except faultinject.FaultInjected as e:
            self.executor.fail(ticket, op, str(e), t_enq)
            return ticket
        dt = str(A.dtype)

        if op == "session_open":
            nblocks, b = int(A.shape[1]), int(A.shape[2])
            # open IS the re-seed path: drop any prior incarnation and
            # clear an eviction tombstone — the one sanctioned way back
            # after a SessionEvicted failure
            self.factors.release(token)
            carry = jnp.eye(b, dtype=A.dtype)
            A = A.at[1, 0].set(jnp.zeros((b, b), A.dtype))
            bucket = batching.bucket_for(
                "session_extend", tuple(A.shape), (b, b), dt, self.cfg)
            if bucket is None:
                return lose(
                    f"no bucket for session window nblocks={nblocks} "
                    f"b={b}: session ops have no oversize route"
                )
            pa, pb = batching.pad_operands("session_extend", A, carry,
                                           bucket)
            self._admit(
                ticket, bucket, pa, pb, tuple(A.shape), (b, b), t_enq,
                client_op="session_open",
                sink=self._session_extend_sink(op, token, b),
            )
            return ticket

        ent = self.factors.lookup(token)
        if ent is None:
            return lose_missing()
        if ent.kind != "session":
            return lose(
                f"factor_token {token!r} holds a {ent.kind} factor; "
                "session ops need a session chain"
            )
        if int(ent.meta["b"]) != int(A.shape[2]) or ent.meta["dtype"] != dt:
            return lose(
                f"operand {A.shape}/{dt} does not ride the resident "
                f"session chain b={ent.meta['b']}/{ent.meta['dtype']} "
                f"under token {token!r}"
            )

        if op == "session_append":
            nblocks, b = int(A.shape[1]), int(A.shape[2])
            carry = ent.arrays[2]
            bucket = batching.bucket_for(
                "session_extend", tuple(A.shape), (b, b), dt, self.cfg)
            if bucket is None:
                return lose(
                    f"no bucket for session append nblocks={nblocks} "
                    f"b={b}: session ops have no oversize route"
                )
            pa, pb = batching.pad_operands("session_extend", A, carry,
                                           bucket)
            self._admit(
                ticket, bucket, pa, pb, tuple(A.shape), (b, b), t_enq,
                client_op="session_append",
                sink=self._session_extend_sink(op, token, b),
            )
            return ticket

        # session_solve
        nblocks, b = int(A.shape[1]), int(A.shape[2])
        if int(ent.meta["nblocks"]) != nblocks:
            return lose(
                f"session_solve window has {nblocks} blocks but the "
                f"resident chain under {token!r} has "
                f"{ent.meta['nblocks']} — the client window is out of "
                "sync (append/contract landed without updating it?)"
            )
        A4 = jnp.stack([A[0], A[1], ent.arrays[0], ent.arrays[1]])
        bucket = batching.bucket_for(
            "session_solve", tuple(A4.shape), tuple(B.shape), dt,
            self.cfg, tier=tier)
        if bucket is None:
            return lose(
                f"no bucket for session_solve nblocks={nblocks} b={b} "
                f"nrhs={B.shape[2]}: session ops have no oversize route"
            )
        pa, pb = batching.pad_operands("session_solve", A4, B, bucket)
        sink = (self._refine_sink("session_solve")
                if bucket.tier == "guaranteed" else None)
        self._admit(
            ticket, bucket, pa, pb, tuple(A4.shape), tuple(B.shape),
            t_enq, client_op="session_solve", sink=sink,
        )
        return ticket

    def _session_extend_sink(self, op: str, token: str, b: int):
        """Landing hook for session_open / session_append: install (open)
        or concatenate (append) the landed (L, Wt) blocks and roll the
        carry — `_extend_sink` with session bookkeeping.  Sessions are
        STATEFUL, so a flagged extend fails the request LOUDLY even under
        robust=None (the blocktri_extend path lets the engine's robust
        knob decide; a silently uninstalled session suffix would desync
        the client window from the resident chain forever)."""

        def sink(x, extras, raw_info):
            i = int(raw_info)
            if i != 0:
                return x, raw_info, (
                    f"{op} flagged breakdown (info={i}, segment-relative "
                    "to the submitted window blocks): the window is not "
                    f"SPD-consistent; resident session chain {token!r} "
                    "left unchanged" + (
                        " (open failed — the session is closed)"
                        if op == "session_open" else "")
                )
            L, Wt = x[0], x[1]
            dropped = 0
            ent = self.factors.peek(token)
            if ent is None and op != "session_open" \
                    and self.factors.evicted(token):
                # the resident chain was evicted between dispatch and
                # landing (the pool honored its byte budget mid-flight).
                # Installing only the new suffix would silently re-seed a
                # TRUNCATED chain — every later solve against it would be
                # wrong.  Fail loudly; "SessionEvicted:" is the tombstone
                # contract SessionManager._lose converts to the typed
                # SessionEvicted (misses == evicted_failures stays exact).
                return x, raw_info, (
                    f"SessionEvicted: resident chain {token!r} was evicted "
                    f"mid-flight (before this {op} landed); the suffix was "
                    "NOT installed — reopen the session and replay"
                )
            if ent is not None and ent.kind == "session":
                L = jnp.concatenate([ent.arrays[0], L], axis=0)
                Wt = jnp.concatenate([ent.arrays[1], Wt], axis=0)
                dropped = int(ent.meta.get("dropped", 0))
            self.factors.put(
                token, "session", (L, Wt, L[-1]),
                {"b": b, "nblocks": int(L.shape[0]),
                 "dtype": str(L.dtype), "dropped": dropped},
            )
            return x, raw_info, None

        return sink

    def _finish_host(self, ticket: Ticket, op: str, x, t_enq: float):
        """Land a host-side administrative session op (contract/close):
        no device dispatch happened, so there is no queue-wait/device
        split — latency is pure host bookkeeping."""
        t_land = spans.now()
        ticket.response = Response(
            request_id=ticket.request_id, op=op, ok=True, x=x, info=None,
            error=None, bucket=None, batched=False,
            latency_s=t_land - t_enq,
        )
        if ticket.trace is not None:
            ticket.trace.extend("respond")
            ticket.response.trace = ticket.trace
        self.stats.record_request(op, t_land - t_enq, ok=True)
        return ticket

    def _update_sink(self, op: str, token: str, n: int, V):
        """Landing hook for chol_update / chol_downdate: install R' on a
        clean info, refuse to install on breakdown.  A flagged DOWNDATE
        degrades to a fresh refactor S = RᵀR − VVᵀ from the still-resident
        OLD factor (put() only runs on success, so it was never
        overwritten) — the docs/ROBUSTNESS.md 'downdate failure'
        contract: degrade, and only if THAT also fails, fail loudly."""

        def sink(x, extras, raw_info):
            i = int(raw_info)
            if i == 0:
                self.factors.put(token, "dense", (x,),
                                 {"n": n, "dtype": str(x.dtype)})
                return x, raw_info, None
            if op == "chol_update":
                # a rank-k UPDATE of an SPD matrix cannot break down in
                # exact arithmetic — a flag here means a poisoned operand
                # (NaN/Inf V, e.g. an injected ingest fault).  No degrade
                # identity exists; refuse the result loudly and leave the
                # resident factor at its pre-update state.
                return x, raw_info, (
                    f"chol_update flagged breakdown (info={i}) — operand "
                    f"is not finite-SPD-consistent; resident factor "
                    f"{token!r} left unchanged"
                )
            ent = self.factors.peek(token)
            if ent is None:
                return x, raw_info, (
                    f"chol_downdate breakdown (info={i}) and token "
                    f"{token!r} was released/evicted mid-flight: no "
                    "resident state to degrade from"
                )
            self.factors.note_downdate_degrade()
            fn = self._get_degrade(n, int(V.shape[1]), str(V.dtype))
            R2, info2 = jax.block_until_ready(fn(ent.arrays[0], V))
            if int(info2) == 0:
                self.factors.put(token, "dense", (R2,),
                                 {"n": n, "dtype": str(R2.dtype)})
                return R2, RobustInfo(info=0, breakdown=1, shifted=0,
                                      sigma=0.0, escalated=1,
                                      ortho=-1.0), None
            return x, raw_info, (
                f"chol_downdate breakdown (info={i}) and the degrade "
                f"refactor ALSO failed (potrf info={int(info2)}): "
                "A − VVᵀ is not positive definite — resident factor "
                f"{token!r} left at its pre-downdate state"
            )

        return sink

    def _seed_sink(self, token: str, n: int):
        """Landing hook for the posv_cached miss program: install the
        freshly-refactored R (cropped from its padded batch slot) — but
        only on a clean info; a flagged refactor (operand not SPD) must
        never become resident truth."""

        def sink(x, extras, raw_info):
            if int(raw_info) == 0:
                R = extras[0][:n, :n]
                self.factors.put(token, "dense", (R,),
                                 {"n": n, "dtype": str(R.dtype)})
            return x, raw_info, None

        return sink

    def _extend_sink(self, token: str, b: int, prior: int):
        """Landing hook for blocktri_extend: append the new (L, Wt)
        blocks to the resident chain and roll the carry to the new last
        diagonal factor block.  A flagged extend installs nothing — the
        resident prefix stays valid (the chain is sequential; a failed
        suffix never corrupts it).  The landed info is SEGMENT-relative
        (offset 0) by design: offsetting inside the program would key a
        recompile per prefix length."""

        def sink(x, extras, raw_info):
            if int(raw_info) != 0:
                return x, raw_info, None
            L, Wt = x[0], x[1]
            ent = self.factors.peek(token)
            if ent is None and prior > 0 and self.factors.evicted(token):
                # the resident prefix was evicted between dispatch and
                # landing; installing only this suffix would re-seed a
                # chain missing its first `prior` blocks — silently wrong
                # for every later blocktri_solve.  Fail the extend loudly
                # (the tombstone stays, so retries fail too until the
                # client re-factors from scratch).
                return x, raw_info, (
                    f"resident blocktri chain {token!r} was evicted "
                    "mid-flight (before this extend landed); the suffix "
                    "was NOT installed — re-factor the full chain"
                )
            if ent is not None and ent.kind == "blocktri":
                L = jnp.concatenate([ent.arrays[0], L], axis=0)
                Wt = jnp.concatenate([ent.arrays[1], Wt], axis=0)
            self.factors.put(
                token, "blocktri", (L, Wt, L[-1]),
                {"b": b, "nblocks": int(L.shape[0]),
                 "dtype": str(L.dtype)},
            )
            return x, raw_info, None

        return sink

    def _arrowhead_sink(self, a_shape, b_shape):
        """Landing hook for posv_arrowhead: the 3-output bucket program
        (api._batched_arrowhead) lands the BLOCKED chain half through
        batching.crop with the padded corner half in the extras slot;
        crop the corner and concatenate the flat (nblocks·b + s, nrhs)
        response — the same layout the oversize single route returns, so
        clients see one contract on both routes."""
        nblocks, b = a_shape[1], a_shape[2]
        s = b_shape[0] - nblocks * b
        k = b_shape[1] - s

        def sink(x, extras, raw_info):
            flat = jnp.concatenate(
                [x.reshape(nblocks * b, k), extras[0][:s, :k]], axis=0)
            return flat, raw_info, None

        return sink

    def _refine_sink(self, op: str):
        """Landing hook for accuracy_tier='guaranteed' buckets: the tiered
        program (api._batched_refine) lands (X, iters, converged, resid)
        per request.  Record the measured refinement cost into the stats
        (sweep counts are data-dependent — they CANNOT be priced at trace
        time, which is why tracing only prices one sweep), and fail the
        request loudly when the refinement loop froze before reaching the
        correction-dtype backward-error tolerance: a 'guaranteed' answer
        that isn't is worse than an error."""

        def sink(x, extras, raw_info):
            it, conv, resid = (int(extras[0]), int(extras[1]),
                               float(extras[2]))
            self.stats.note_refine(it, bool(conv), resid)
            if not conv:
                return x, raw_info, (
                    f"accuracy_tier='guaranteed' {op} did not converge: "
                    f"refinement froze after {it} sweep(s) at backward "
                    f"error {resid:.3e} (stalled or diverging — the "
                    "operand is likely too ill-conditioned for the "
                    "factor dtype; resubmit at tier='balanced' in a "
                    "wider dtype)"
                )
            return x, raw_info, None

        return sink

    def _get_degrade(self, n: int, k: int, dtype: str):
        """The downdate-degrade program: refactor S = RᵀR − VVᵀ from
        scratch (lapack.potrf upper, with info).  Cached under the warmup
        counters on purpose — an exceptional-path compile must not read
        as a steady-state recompile in the zero-recompile gates."""
        key = ("degrade", n, k, dtype, self._grid_key, self._cfg_hash)

        def build():
            prec = self.cfg.precision

            def fn(R, V):
                with tracing.scope("UP::downdate"):
                    S = (jnp.einsum("ji,jk->ik", R, R, precision=prec)
                         - jnp.einsum("ik,jk->ij", V, V, precision=prec))
                    return lapack.potrf(S, uplo="U", with_info=True)

            dt = jnp.dtype(dtype)
            return jax.jit(fn).lower(
                jax.ShapeDtypeStruct((n, n), dt),
                jax.ShapeDtypeStruct((n, k), dt),
            ).compile()

        return self.cache.get(key, build, warmup=True)

    def _run_single(self, ticket: Ticket, op: str, A, B,
                    t_enq: float, tier: str = "balanced") -> None:
        tr = ticket.trace
        if tr is not None:
            # oversize singles never queue or batch: the chain collapses
            # to admit -> cache_lookup -> device -> respond
            tr.kind = "single"
            tr.extend("admit")
        a_sds = jax.ShapeDtypeStruct(A.shape, A.dtype)
        b_sds = (jax.ShapeDtypeStruct(B.shape, B.dtype)
                 if B is not None else None)
        exe = self._get_single(op, a_sds, b_sds, tier=tier)
        if tr is not None:
            tr.extend("cache_lookup")
        sink = self._refine_sink(op) if tier == "guaranteed" else None
        self.executor.run_single(ticket, op, A, B, exe, t_enq, sink=sink)
