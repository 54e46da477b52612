"""Continuous-batching admission: in-flight bucket batches, overlapped
dispatch, deadline flushes.

PR 4's loop was stop-and-go: a flush called the bucket executable and the
host sat inside that call until the device finished, so the device idled
while the host padded/stacked the next batch and the host idled while the
device solved.  The CAPITAL thesis — the *schedule*, not the local kernel,
decides delivered performance — applies to this axis exactly like it does
to inter-node traffic: overlap the phases instead of alternating them.

The continuous scheduler (``ServeConfig.scheduler="continuous"``):

* **admission into in-flight batches** — `admit()` queues per bucket; a
  capacity-full bucket dispatches immediately, but `flush()` returns as
  soon as the executable call is *issued* (jax dispatch is async) — the
  batch goes onto the in-flight deque instead of blocking the host;
* **overlapping dispatch of consecutive buckets** — while batch k
  executes, the host stages, pads, and dispatches batch k+1; there is no
  `block_until_ready` between flushes;
* **bounded in-flight depth** — at most `max_inflight` unlanded batches;
  beyond that the oldest is landed (collected) first, so device queueing
  and batch-buffer memory stay bounded under a submit storm;
* **opportunistic landing** — `pump()` lands any in-flight batch whose
  outputs report ready (`jax.Array.is_ready`, non-blocking) in addition
  to running deadline flushes, so results materialize as the device
  produces them rather than in one stall at `drain()`.

``scheduler="sync"`` reproduces the PR 4 submit/pump/drain behavior
exactly (dispatch + immediate land, no staging, no in-flight window) —
kept as the A/B baseline `serve/loadgen.py` measures the overlap win
against and as the conservative posture for platforms where async
dispatch is a liability.

This module owns no executables and no padding: the engine resolves the
bucket program via its cache (`get_exe` callback) and pads/stages at
submit; the executor dispatches and lands.  Single-problem (oversize)
requests never enter the scheduler.
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Optional

from capital_tpu.obs import spans
from capital_tpu.serve import batching
from capital_tpu.serve.executor import Executor, InFlight, _Pending


class Scheduler:
    """Per-bucket queues + the in-flight window.  `get_exe(bucket)`
    returns ``(executable, small_route)`` — the engine's cache lookup."""

    def __init__(self, cfg, executor: Executor,
                 get_exe: Callable[[batching.Bucket], tuple]):
        self.cfg = cfg  # guarded-by: <frozen>
        self.executor = executor  # guarded-by: <frozen>
        self._get_exe = get_exe  # guarded-by: <frozen>
        self._queues: dict[batching.Bucket, list[_Pending]] = {}  # guarded-by: <owner-thread>
        self._inflight: deque[InFlight] = deque()  # guarded-by: <owner-thread>

    # ---- admission ---------------------------------------------------------

    def admit(self, bucket: batching.Bucket, p: _Pending) -> None:
        """Queue one padded request; dispatch the bucket when it reaches
        capacity (the capacity-flush path — inside submit())."""
        q = self._queues.setdefault(bucket, [])
        q.append(p)
        if len(q) >= bucket.capacity:
            self.flush(bucket)

    def queue_depth(self) -> int:
        return sum(len(q) for q in self._queues.values())

    @property
    def inflight_depth(self) -> int:
        return sum(1 for fl in self._inflight if not fl.landed)

    # ---- dispatch ----------------------------------------------------------

    def flush(self, bucket: batching.Bucket) -> bool:
        """Dispatch one bucket's queue.  Continuous: issue and return
        (results land later); sync: land before returning (the PR 4
        behavior).  Returns True when a batch was dispatched."""
        q = self._queues.pop(bucket, [])
        if not q:
            return False
        t_form = spans.now()
        exe, small = self._get_exe(bucket)
        t_exe = spans.now()
        for p in q:
            if p.ticket.trace is not None:
                # enqueue = parked in the bucket queue until this flush;
                # cache_lookup = executable resolution (a compile lands
                # its full cost HERE — the attribution the zero-recompile
                # gates key on)
                p.ticket.trace.extend("enqueue", t_form)
                p.ticket.trace.extend("cache_lookup", t_exe)
        fl = self.executor.dispatch(bucket, exe, q, small)
        if self.cfg.scheduler == "sync":
            self.executor.land(fl)
            return True
        for p in q:
            p.ticket._entry = fl
            p.ticket._land = self.land
        self._inflight.append(fl)
        # bound the window: collect the oldest before over-queuing the
        # device (also bounds live batch-buffer memory)
        while self.inflight_depth > self.cfg.max_inflight:
            self.land(self._oldest_unlanded())
        return True

    def _oldest_unlanded(self) -> InFlight:
        for fl in self._inflight:
            if not fl.landed:
                return fl
        raise AssertionError("no unlanded in-flight batch")  # unreachable

    # ---- landing -----------------------------------------------------------

    def land(self, fl: InFlight) -> None:
        """Land one in-flight batch (idempotent; also the Ticket.result()
        callback) and drop collected entries from the window."""
        self.executor.land(fl)
        while self._inflight and self._inflight[0].landed:
            self._inflight.popleft()

    def reap(self) -> int:
        """Land every in-flight batch whose outputs report ready — the
        non-blocking half of pump().  Returns the number landed."""
        n = 0
        for fl in list(self._inflight):
            if not fl.landed and self.executor.ready(fl):
                self.land(fl)
                n += 1
        return n

    # ---- the loop verbs ----------------------------------------------------

    def pump(self, now: float) -> int:
        """Deadline flush + opportunistic landing.  Returns the number of
        batches flushed (deadline-triggered), matching the PR 4 pump()
        contract."""
        flushed = 0
        for bucket in list(self._queues):
            q = self._queues.get(bucket)
            if q and now - q[0].t_enq >= self.cfg.max_delay_s:
                if self.flush(bucket):
                    flushed += 1
        self.reap()
        return flushed

    def drain(self) -> int:
        """Flush every non-empty queue and land every in-flight batch
        (shutdown / test barrier).  Returns the number of batches flushed
        by this call."""
        flushed = 0
        for bucket in list(self._queues):
            if self.flush(bucket):
                flushed += 1
        while self._inflight:
            self.land(self._inflight[0])
        return flushed
