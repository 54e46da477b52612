"""Dispatch, donation, fault containment, and result landing.

The executor is the piece of the PR 4 engine that actually touches the
device: it turns an assembled bucket batch into a dispatched executable
call, and a dispatched call into per-request `Response`s.  Splitting it
from admission (scheduler.py) is what makes continuous batching possible —
`dispatch()` returns an `InFlight` handle *without synchronizing* (jax
dispatch is async), so the scheduler can stage and dispatch the next
bucket while this one executes, and `land()` blocks only when someone
needs the results (an aged `pump()`, a `Ticket.result()`, the in-flight
cap, or `drain()`).

Timing contract (the queue-wait/device split serve/stats.py reports):

* ``t_enq`` — request enqueue time (set at `submit()`, carried on the
  Ticket and the pending entry);
* ``t0`` — dispatch time (set here when the executable is invoked; also
  stamped onto each Ticket);
* landing time — when `land()` observed the outputs ready.

``queue_wait_s = t0 - t_enq`` is scheduling policy (flush thresholds,
ladder fit, in-flight backpressure); ``device_s = t_land - t0`` is
compute + transfer + any async slack the scheduler chose not to collect
earlier.  Both populations feed `serve:request_stats` percentiles, so
`obs serve-report` can tell a mis-tuned flush policy (queue-wait grows)
from a slow kernel (device grows) without re-running anything.

Donation stays exactly PR 4's contract: engine-built batch buffers only,
TPU-only by default, posv RHS / inv operand only (lstsq's (m, nrhs) RHS
can never alias its (n, nrhs) solution).  Fault containment likewise:
`fail()` lands host-side ingest faults as failed Responses, and the
per-problem `info` vector flags breakdowns one request at a time.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from capital_tpu.obs import spans
from capital_tpu.robust.config import RobustInfo
from capital_tpu.serve import batching
from capital_tpu.utils import tracing


@dataclasses.dataclass
class Response:
    """One finished request.  `x` is the cropped solution (None only when
    `ok` is False with `error` set — an ingest fault or a rejected
    request).  `info` is a RobustInfo under ServeConfig.robust (breakdown
    != 0 means x is flagged garbage), else None.  `latency_s` is
    enqueue-to-landing; `queue_wait_s`/`device_s` are its two halves
    (None when no dispatch happened, e.g. an ingest fault)."""

    request_id: int
    op: str
    ok: bool
    x: Optional[jnp.ndarray]
    info: Optional[RobustInfo]
    error: Optional[str]
    bucket: Optional[tuple]
    batched: bool
    latency_s: float
    queue_wait_s: Optional[float] = None
    device_s: Optional[float] = None
    trace: Optional[spans.RequestTrace] = None


class Ticket:
    """Handle returned by submit().  Carries the request's clock marks
    (`t_enq` at submit, `t0` at dispatch) and resolves when its batch
    lands.  Under the continuous scheduler a capacity flush DISPATCHES the
    batch without waiting for it: the ticket is `done` (its results are in
    flight and will materialize), and `result()` lands the batch on demand
    if `pump()`/`drain()` hasn't already."""

    __slots__ = ("request_id", "t_enq", "t0", "response", "trace",
                 "deadline_ms", "_entry", "_land")

    def __init__(self, request_id: int, t_enq: float = 0.0):
        self.request_id = request_id
        self.t_enq = t_enq
        self.t0: Optional[float] = None  # stamped at dispatch
        self.response: Optional[Response] = None
        self.trace: Optional[spans.RequestTrace] = None
        self.deadline_ms: Optional[float] = None
        self._entry = None  # InFlight carrying this ticket, once dispatched
        self._land = None  # scheduler callback that lands _entry

    @property
    def done(self) -> bool:
        """True once the request's fate is sealed: a Response landed, or
        its batch is dispatched and in flight (result() will land it)."""
        return self.response is not None or self._entry is not None

    def result(self) -> Response:
        if self.response is None:
            if self._entry is None:
                raise RuntimeError(
                    f"request {self.request_id} not flushed yet — call "
                    "engine.pump() (deadline flush) or engine.drain()"
                )
            self._land(self._entry)  # lands the whole batch, fills response
        return self.response


@dataclasses.dataclass
class _Pending:
    """One queued request: its ticket plus the padded, staged operands.

    The factor-residency fields ride along host-side (serve/factorcache):
    `client_op` is the op the CLIENT submitted when the bucket runs an
    internal program on its behalf (posv_cached_miss buckets land as
    posv_cached responses/stats); `sink` is the engine's landing hook —
    called with (cropped_x, extra_outputs, raw_info), it installs/updates
    the resident factor and may REWRITE the landed result (the downdate
    degrade path) or fail it loudly; returns (x, info, error)."""

    ticket: Ticket
    pa: jnp.ndarray
    pb: Optional[jnp.ndarray]
    a_shape: tuple[int, ...]
    b_shape: Optional[tuple[int, ...]]
    t_enq: float
    client_op: Optional[str] = None
    sink: Optional[object] = None


@dataclasses.dataclass
class InFlight:
    """One dispatched-but-not-landed bucket batch."""

    bucket: batching.Bucket
    pending: list[_Pending]
    outputs: tuple  # (X, info) device arrays, possibly still computing
    t0: float  # dispatch time
    small: bool  # served by the batched-grid small-N kernels (stats split)
    landed: bool = False


class Executor:
    """Dispatch + landing.  Owns no queues and no cache — the scheduler
    decides *when*, the engine decides *what program*; this class only
    runs it and lands the results into Responses/stats."""

    def __init__(self, cfg, grid, stats):
        self.cfg = cfg
        self.grid = grid
        self.stats = stats

    # ---- donation ----------------------------------------------------------

    def donate(self) -> bool:
        d = self.cfg.donate
        return self.grid.platform == "tpu" if d is None else d

    def donate_argnums(self, bucket: batching.Bucket) -> tuple[int, ...]:
        """The jit donation declaration for one bucket program: posv's RHS
        batch, inv's operand batch, nothing for lstsq (its (m, nrhs) RHS
        cannot alias the (n, nrhs) solution — XLA would silently drop the
        declaration; the lint donation-honored rule's point).

        Factor-residency buckets: chol_update/chol_downdate donate the
        assembled FACTOR batch (argnum 0 — shaped exactly like the R'
        output, and an engine-built stack of padded copies, so the
        resident originals in the FactorCache stay intact); posv_cached
        donates its RHS like posv.  The miss and extend programs donate
        nothing (3-output / carry-shaped operands XLA would drop the
        declaration for).

        Tiered buckets donate nothing: the fast program downcasts the
        request-dtype inputs before factoring (different itemsize — XLA
        would drop the alias), and the guaranteed program keeps BOTH
        operands live across every refinement sweep's residual."""
        if not self.donate():
            return ()
        if bucket.tier != "balanced":
            return ()
        if bucket.op in ("chol_update", "chol_downdate"):
            return (0,)
        if bucket.op == "posv_cached":
            return (1,)
        if bucket.op in ("posv_cached_miss", "blocktri_extend",
                         "session_extend", "session_solve"):
            # session_solve's 4-stack operand CONTAINS the FactorCache-
            # resident (L, Wt) — donating it would let XLA scribble over
            # the session's resident factor; the extend programs donate
            # nothing for the blocktri_extend reasons above
            return ()
        if bucket.b_shape is not None:
            return (1,) if bucket.op == "posv" else ()
        return (0,)

    # ---- batched dispatch + landing ---------------------------------------

    def dispatch(self, bucket: batching.Bucket, exe,
                 pending: list[_Pending], small: bool) -> InFlight:
        """Assemble and invoke one bucket batch WITHOUT synchronizing.
        The returned InFlight's outputs are device arrays that may still
        be computing; land() collects them."""
        Ab, Bb, occupancy = batching.assemble(
            [p.pa for p in pending], [p.pb for p in pending], bucket,
        )
        with tracing.scope("SV::dispatch"):
            outputs = exe(Ab) if Bb is None else exe(Ab, Bb)
        t0 = spans.now()
        fl = InFlight(bucket=bucket, pending=list(pending), outputs=outputs,
                      t0=t0, small=small)
        for p in pending:
            p.ticket.t0 = t0
            if p.ticket.trace is not None:
                # assemble + async invoke issue; host-side stamp only
                p.ticket.trace.extend("batch_form", t0)
        self.stats.note_batch(occupancy, bucket=batching.bucket_label(bucket))
        return fl

    def ready(self, fl: InFlight) -> bool:
        """Non-blocking readiness probe (jax.Array.is_ready).  Platforms
        whose arrays lack the probe report ready, degrading the continuous
        scheduler's opportunistic pump-landing to land-on-pump — correct,
        just less overlapped."""
        try:
            return all(
                x.is_ready() for x in jax.tree_util.tree_leaves(fl.outputs)
            )
        except AttributeError:
            return True

    def land(self, fl: InFlight) -> None:
        """Block on one in-flight batch and land every request in it:
        crop, robust-flag, stamp the queue-wait/device split, feed stats.
        Idempotent (the scheduler, a Ticket.result(), and drain() may all
        try)."""
        if fl.landed:
            return
        fl.landed = True
        # programs return (X, info) — the factor-residency miss program
        # returns (X, R, info); everything between the primary output and
        # the trailing info batch is an extra the landing sink consumes
        *xs, info = jax.block_until_ready(fl.outputs)
        t_land = spans.now()
        for i, p in enumerate(fl.pending):
            tr = p.ticket.trace
            if tr is not None:
                tr.extend("device", t_land)
            xi = batching.crop(fl.bucket.op, xs[0][i], p.a_shape, p.b_shape)
            ri = info[i]
            err = None
            if p.sink is not None:
                xi, ri, err = p.sink(xi, tuple(x[i] for x in xs[1:]), ri)
                if tr is not None:
                    tr.extend("refine")  # sink bookkeeping ran host-side
            op = p.client_op or fl.bucket.op
            if err is not None:
                self._refuse(p.ticket, op, ri, err, fl.bucket.key,
                             batched=True, t_enq=p.t_enq, t0=fl.t0,
                             t_land=t_land)
                continue
            self._finish(
                p.ticket, op, xi, ri, fl.bucket.key,
                batched=True, t_enq=p.t_enq, t0=fl.t0, t_land=t_land,
                small=fl.small,
            )
        fl.pending = []
        fl.outputs = ()  # release the batch buffers

    # ---- single-problem (oversize) route ----------------------------------

    def run_single(self, ticket: Ticket, op: str, A, B, exe,
                   t_enq: float, sink=None) -> None:
        """Oversize requests stay synchronous: one exact-shape problem
        through the models/ schedules, landed immediately (no batch to
        overlap against, and the models paths carry their own internal
        pipelining).  The program returns (X, *extras, info); a landing
        `sink` consumes the extras as it does for a batch (the guaranteed
        tier's refine sink)."""
        t0 = spans.now()
        ticket.t0 = t0
        outs = exe(A) if B is None else exe(A, B)
        x, *extras, raw = jax.block_until_ready(outs)
        t_land = spans.now()
        if ticket.trace is not None:
            ticket.trace.extend("device", t_land)
        if sink is not None:
            x, raw, err = sink(x, tuple(extras), raw)
            if ticket.trace is not None:
                ticket.trace.extend("refine")
            if err is not None:
                self._refuse(ticket, op, raw, err, None, batched=False,
                             t_enq=t_enq, t0=t0, t_land=t_land)
                return
        self._finish(ticket, op, x, raw, None, batched=False, t_enq=t_enq,
                     t0=t0, t_land=t_land)

    # ---- landing internals -------------------------------------------------

    def fail(self, ticket: Ticket, op: str, error: str,
             t_enq: float) -> None:
        """Land a request that never reached a device: ingest fault or
        oversize-reject.  No queue-wait/device split exists for it."""
        now = spans.now()
        lat = now - t_enq
        tr = ticket.trace
        if tr is not None:
            # collapse to the failed chain: admit covers submit-to-fault,
            # respond is the Response/stats stamp happening right here
            tr.kind = "failed"
            if not tr.spans:
                tr.extend("admit", now)
            tr.extend("respond")
        ticket.response = Response(
            request_id=ticket.request_id, op=op, ok=False, x=None,
            info=None, error=error, bucket=None, batched=False,
            latency_s=lat, trace=tr,
        )
        self.stats.record_request(op, lat, ok=False, failed=True)

    def _refuse(self, ticket: Ticket, op: str, raw_info, err: str,
                bucket_key: Optional[tuple], batched: bool, t_enq: float,
                t0: float, t_land: float) -> None:
        """Land a result its sink refused (a guaranteed solve that did not
        converge, a double-failed downdate degrade) as a LOUD failure,
        never a silent wrong answer (docs/ROBUSTNESS.md)."""
        lat = t_land - t_enq
        tr = ticket.trace
        ticket.response = Response(
            request_id=ticket.request_id, op=op, ok=False, x=None,
            info=self._norm_info(raw_info), error=err, bucket=bucket_key,
            batched=batched, latency_s=lat,
            queue_wait_s=max(0.0, t0 - t_enq),
            device_s=max(0.0, t_land - t0), trace=tr,
        )
        if tr is not None:
            tr.extend("respond")
        self.stats.record_request(
            op, lat, ok=False, failed=True,
            bucket=(batching.bucket_label(bucket_key)
                    if bucket_key is not None else None))

    def _norm_info(self, raw) -> Optional[RobustInfo]:
        if self.cfg.robust is None:
            return None
        if isinstance(raw, RobustInfo):
            return RobustInfo(
                info=int(raw.info), breakdown=int(raw.breakdown),
                shifted=int(raw.shifted), sigma=float(raw.sigma),
                escalated=int(raw.escalated), ortho=float(raw.ortho),
                gate=int(raw.gate),
            )
        i = int(raw)
        # detect-only sites surface the potrf convention; no recovery ran
        # (and no gate was evaluated — gate stays GATE_NONE)
        return RobustInfo(info=i, breakdown=int(i != 0), shifted=0,
                          sigma=0.0, escalated=0, ortho=-1.0)

    def _finish(self, ticket: Ticket, op: str, x, raw_info,
                bucket_key: Optional[tuple], batched: bool, t_enq: float,
                t0: float, t_land: float, small: bool = False) -> None:
        info = self._norm_info(raw_info)
        ok = info is None or info.info == 0
        queue_wait = max(0.0, t0 - t_enq)
        device = max(0.0, t_land - t0)
        ticket.response = Response(
            request_id=ticket.request_id, op=op, ok=ok, x=x, info=info,
            error=None, bucket=bucket_key, batched=batched,
            latency_s=t_land - t_enq,
            queue_wait_s=queue_wait, device_s=device,
            trace=ticket.trace,
        )
        if ticket.trace is not None:
            ticket.trace.extend("respond")
        self.stats.record_request(
            op, t_land - t_enq, ok=ok,
            flagged=(info is not None and not ok), small=small,
            queue_wait_s=queue_wait, device_s=device,
            bucket=(batching.bucket_label(bucket_key)
                    if bucket_key is not None else None),
        )
