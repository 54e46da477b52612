"""Program spans and per-request span chains, on one clock.

Two span kinds share this module and its clock:

* **Program spans** (`span`, `SpanLog`): ``with span(name, **tags):``
  around any stretch of host work records one `SpanRecord` (name, start,
  end, the id of the span open when it began, tags) in the process's
  bounded `SPAN_LOG`.  While a JAX profiler trace records, the span also
  opens a ``jax.profiler.TraceAnnotation`` of the same name, so it sits in
  the trace beside the device ops it issued.  `watch_builds` turns JAX's
  own build events into spans — ``build.trace``, ``build.lower``,
  ``build.compile`` — and counts each backend build as a persistent-cache
  load or a compile (`BUILDS`); `KERNELS` counts the Pallas call sites
  and the distinct kernels built for them; `ROUTES` counts the routes the
  builds of `qr.factor` took.  The span vocabulary is in
  docs/OBSERVABILITY.md "Program spans".
* **Request chains** (`RequestTrace`): every request the SolveEngine
  admits carries an ordered chain of spans covering its whole life —

    admit -> enqueue -> cache_lookup -> batch_form -> device
          [-> refine] -> respond

  `admit` is validation + fault tap + pad + stage (submit() entry to
  scheduler admission); `enqueue` is time parked in the bucket queue until
  a flush starts; `cache_lookup` is executable resolution (near-zero on a
  cache hit — a compile shows up HERE, which is exactly the attribution
  the zero-recompile gates want); `batch_form` is assemble + async
  dispatch issue; `device` is dispatch to landing
  (`jax.block_until_ready` observed); `refine` is the landing sink when
  one ran (guaranteed-tier refinement bookkeeping, factor installs,
  arrowhead re-pack); `respond` is Response construction + stats
  stamping.  Oversize singles skip the queue/batch spans (kind
  "single"), never-dispatched failures collapse to admit -> respond (kind
  "failed").

**The clock** (`now_ns`, `now`) is the wall clock, ``time.time_ns()``:
the clock JAX's profiler stamps host events with.  A profiler trace keeps
each event as an offset from its session's ``profile_start_time``
(`profile_anchor_ns`), so anchor + offset is the event's time on the span
clock, and program spans, request chains and device ops line up in one
trace.  The wall clock is shared by every process on the host, so replica
chains line up with engine ones too.

Everything here is HOST-side pure Python — stamps around the dispatch
path, never a device sync (the lint no-host-sync rule pins that via the
``serve_traced`` ProgramTarget), and the module imports neither jax nor
numpy at import time, so the host-only router/replica modules can carry
trace dicts freely (jax is touched only once the process has imported
it).

The ledger surface of request chains is the schema-tagged ``serve:trace``
record (one per run, `build_block`/`emit`): per-trace tags (bucket/op/
tier/replica/cfg-hash), per-span start/duration, completeness +
monotonicity verdicts under a pinned bubble tolerance, and — when the
request carried a ``deadline_ms`` — slack-at-dispatch and SLO-violation
*attribution* (the span that ate the budget), the signal ROADMAP item 3's
shed/downgrade policy keys on.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
import warnings
from collections import deque
from typing import Optional

#: The full span vocabulary, in chain order.  Validation rejects names
#: outside it and out-of-order stamping within it.
CHAIN = ("admit", "enqueue", "cache_lookup", "batch_form", "device",
         "refine", "respond")

#: Required sub-chain per trace kind.  "refine" is optional everywhere
#: (present only when a landing sink ran).
REQUIRED = {
    "batched": ("admit", "enqueue", "cache_lookup", "batch_form",
                "device", "respond"),
    "single": ("admit", "cache_lookup", "device", "respond"),
    "failed": ("admit", "respond"),
    # host-side session administrative ops (session_contract/close):
    # residency resolves on the host, nothing is dispatched — the chain
    # collapses to the lookup (docs/SERVING.md 'Streaming sessions')
    "session": ("admit", "cache_lookup", "respond"),
}

#: Pinned bubble tolerance: the largest host-side gap (seconds of
#: un-spanned time between consecutive spans) a chain may carry and still
#: count as complete.  Spans are stamped contiguously (each starts where
#: the previous ended), so real gaps only appear when a stamping site is
#: missed or the host stalls between stamps — 25 ms absorbs GC pauses on
#: a loaded CPU rig while still catching a dropped span site.
DEFAULT_BUBBLE_TOL_MS = 25.0

#: Allowance for the float rounding `asdict` applies (µs-scale), used by
#: the overlap check — NOT a gap budget.
_OVERLAP_EPS_S = 1e-5

#: Default bound on traces a TraceLog retains (oldest dropped first, with
#: a visible `dropped` counter) — bounded memory for long-running
#: replicas, comfortably above any smoke/loadgen run's request count.
DEFAULT_TRACE_CAP = 4096


#: Default bound on program spans a SpanLog retains (oldest dropped first,
#: counted in `dropped`): a cholinv n=49152 set-up records about 13,000
#: build spans, a served request three staging spans.
DEFAULT_SPAN_CAP = 65536


# ---------------------------------------------------------------------------
# the span clock
# ---------------------------------------------------------------------------


def now_ns() -> int:
    """The span clock in integer nanoseconds: the wall clock, which is the
    clock JAX's profiler stamps host events with (see the module
    docstring)."""
    return time.time_ns()


def now() -> float:
    """The span clock in float seconds (request chains stamp with it)."""
    return time.time()


def profile_anchor_ns(profile) -> int:
    """The span clock's reading at the zero of a profiler trace: the
    ``profile_start_time`` of a `jax.profiler.ProfileData`'s "Task
    Environment" plane.  An event's ``start_ns`` plus this is its start on
    the span clock."""
    for plane in profile.planes:
        if plane.name == "Task Environment":
            with warnings.catch_warnings():
                # the binding's stats type warns on construction
                warnings.simplefilter("ignore", DeprecationWarning)
                stats = dict(plane.stats)
            if "profile_start_time" in stats:
                return int(stats["profile_start_time"])
    raise ValueError("the profile has no Task Environment plane with a "
                     "profile_start_time")


# ---------------------------------------------------------------------------
# program spans
# ---------------------------------------------------------------------------


class SpanRecord:
    """One closed program span, on the span clock."""

    __slots__ = ("span_id", "name", "start_ns", "end_ns", "parent", "tags")

    def __init__(self, span_id: int, name: str, start_ns: int, end_ns: int,
                 parent: Optional[int] = None, tags: Optional[dict] = None):
        self.span_id = span_id
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.parent = parent  # id of the span open when this one began
        self.tags = tags or {}

    @property
    def dur_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class SpanLog:
    """Bounded in-memory log of a process's program spans, read at the end
    of a run.  Oldest records drop first past `cap`, counted visibly
    (`dropped`) — the TraceLog discipline.  Any thread may add."""

    def __init__(self, cap: int = DEFAULT_SPAN_CAP):
        if cap < 1:
            raise ValueError(f"span cap must be >= 1, got {cap}")
        self.cap = cap  # guarded-by: <frozen>
        self._lock = threading.Lock()  # guarded-by: <lock>
        self.total = 0  # guarded-by: self._lock
        self._records: deque = deque(maxlen=cap)  # guarded-by: self._lock

    def add(self, rec: SpanRecord) -> None:
        with self._lock:
            self.total += 1
            self._records.append(rec)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self.total - len(self._records)

    def records(self, prefix: str = "") -> list[SpanRecord]:
        """The retained records whose name starts with `prefix`, in the
        order they closed."""
        with self._lock:
            recs = list(self._records)
        return [r for r in recs if r.name.startswith(prefix)]

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)


#: The process's span log: every `span` and build span lands here.
SPAN_LOG = SpanLog()

_SPAN_IDS = itertools.count(1)
_THREAD = threading.local()  # per thread: open span ids, a pending cache load


def _open_spans() -> list:
    stack = getattr(_THREAD, "stack", None)
    if stack is None:
        stack = _THREAD.stack = []
    return stack


def profiling() -> bool:
    """Whether a JAX profiler trace is recording in this process.  Before
    jax is imported there is no profiler, and jax is not imported here."""
    prof = getattr(sys.modules.get("jax"), "profiler", None)
    return prof is not None and prof.TraceAnnotation.is_enabled()


class span:
    """``with span(name, **tags):`` — one program span in `SPAN_LOG`, and
    a ``jax.profiler.TraceAnnotation`` of the same name while a profiler
    trace records.  Its parent is the span open on this thread when it
    began."""

    __slots__ = ("name", "tags", "span_id", "parent", "start_ns", "_ann")

    def __init__(self, name: str, **tags):
        self.name = name
        self.tags = tags

    def __enter__(self) -> "span":
        stack = _open_spans()
        self.parent = stack[-1] if stack else None
        self.span_id = next(_SPAN_IDS)
        stack.append(self.span_id)
        self._ann = None
        if profiling():
            self._ann = sys.modules["jax"].profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        end_ns = time.time_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _open_spans().pop()
        SPAN_LOG.add(SpanRecord(self.span_id, self.name, self.start_ns,
                                end_ns, self.parent, self.tags))


# ---------------------------------------------------------------------------
# build spans and the build counter
# ---------------------------------------------------------------------------

#: JAX's build events (jax.monitoring) and the program spans they become.
#: Nested jits overlap, so a reader takes the union of each kind's
#: intervals, never the sum.
BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "build.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "build.lower",
    "/jax/core/compile/backend_compile_duration": "build.compile",
}
#: fired, on the building thread, only when the persistent cache served
#: the backend build that is under way
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class BuildCounter:
    """The process's backend builds, split into persistent-cache loads and
    compiles."""

    def __init__(self):
        self._lock = threading.Lock()  # guarded-by: <lock>
        self.compiles = 0  # guarded-by: self._lock
        self.cache_loads = 0  # guarded-by: self._lock

    def count(self, loaded: bool) -> None:
        with self._lock:
            if loaded:
                self.cache_loads += 1
            else:
                self.compiles += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles,
                    "cache_loads": self.cache_loads}


BUILDS = BuildCounter()


class KernelCounter:
    """The process's Pallas call sites that went through the kernel cache
    of ops/pallas_tpu.py (`calls`), and the kernels that cache actually
    traced (`built`): one per distinct kernel, however many sites call it."""

    def __init__(self):
        self._lock = threading.Lock()  # guarded-by: <lock>
        self.calls = 0  # guarded-by: self._lock
        self.built = 0  # guarded-by: self._lock

    def call(self) -> None:
        with self._lock:
            self.calls += 1

    def build(self) -> None:
        with self._lock:
            self.built += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"calls": self.calls, "built": self.built}


KERNELS = KernelCounter()


class RouteCounter:
    """The routes the process's builds of an entry point took, counted at
    trace time: ``take(route, **tags)`` once per build, the tags describing
    what that build traced (for `qr.factor`: the per-shard rows, the
    column split ``g`` and the row block ``bm``)."""

    def __init__(self):
        self._lock = threading.Lock()  # guarded-by: <lock>
        self._builds: dict = {}  # guarded-by: self._lock
        self._tags: dict = {}  # guarded-by: self._lock

    def take(self, route: str, **tags) -> None:
        with self._lock:
            self._builds[route] = self._builds.get(route, 0) + 1
            self._tags[route] = tags

    def snapshot(self) -> dict:
        """{route: {"builds": n, **the tags of its latest build}}."""
        with self._lock:
            return {r: {"builds": n, **self._tags[r]}
                    for r, n in self._builds.items()}


ROUTES = RouteCounter()
_WATCH_LOCK = threading.Lock()
_watching = False


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == CACHE_LOAD_EVENT:
        _THREAD.cache_load = True


def _on_time_span(event: str, start_s: float, end_s: float, **kw) -> None:
    name = BUILD_EVENTS.get(event)
    if name is None:
        return
    tags = {}
    if kw.get("fun_name"):
        tags["fun_name"] = str(kw["fun_name"])
    if name == "build.compile":
        loaded = getattr(_THREAD, "cache_load", False)
        _THREAD.cache_load = False
        tags["cache"] = "load" if loaded else "compile"
        BUILDS.count(loaded)
    if profiling():
        tags["profiled"] = True
    stack = _open_spans()
    SPAN_LOG.add(SpanRecord(next(_SPAN_IDS), name, int(start_s * 1e9),
                            int(end_s * 1e9), stack[-1] if stack else None,
                            tags))


def watch_builds() -> None:
    """Register, once per process, the jax.monitoring listeners that turn
    JAX's build events into build spans and counts.  JAX reports each
    build's start and end on ``time.time()``, the span clock, so the spans
    need no conversion.  The program's jax-side modules call this at
    import (capital_tpu/utils/tracing.py)."""
    global _watching
    with _WATCH_LOCK:
        if _watching:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_time_span_listener(_on_time_span)
        _watching = True


# ---------------------------------------------------------------------------
# request chains
# ---------------------------------------------------------------------------


class Span:
    """One contiguous phase of a request's life, on the span clock."""

    __slots__ = ("name", "t_start", "t_end")

    def __init__(self, name: str, t_start: float, t_end: float):
        self.name = name
        self.t_start = t_start
        self.t_end = t_end

    @property
    def dur_s(self) -> float:
        return self.t_end - self.t_start

    def __repr__(self) -> str:  # debugging aid only
        return f"Span({self.name!r}, {self.dur_s * 1e3:.3f}ms)"


class RequestTrace:
    """The span chain + tags for one request.

    Stamping contract: `extend(name)` appends a span running from the
    previous span's end (or `t_enq` for the first) to now — the serve
    path stamps chains contiguously, so chain gaps measure *missed
    stamping sites*, not scheduling (scheduling time lives INSIDE the
    enqueue/device spans).  `span(name, t0, t1)` exists for explicit
    intervals (tests, replay)."""

    __slots__ = ("request_id", "op", "kind", "t_enq", "deadline_ms",
                 "tags", "spans")

    def __init__(self, request_id: int, op: str, t_enq: float, *,
                 deadline_ms: Optional[float] = None, **tags):
        self.request_id = request_id  # guarded-by: <frozen>
        self.op = op  # guarded-by: <frozen>
        self.kind = "batched"  # guarded-by: <owner-thread>  (rewritten by the single/failed routes)
        self.t_enq = t_enq  # guarded-by: <frozen>
        self.deadline_ms = deadline_ms  # guarded-by: <frozen>
        # bucket / tier / replica_id / cfg_hash ride here (str or None)
        self.tags = {k: v for k, v in tags.items() if v is not None}  # guarded-by: <owner-thread>
        self.spans: list[Span] = []  # guarded-by: <owner-thread>

    # ---- stamping ----------------------------------------------------------

    def tag(self, **kv) -> None:
        for k, v in kv.items():
            if v is not None:
                self.tags[k] = v

    @property
    def last_end(self) -> float:
        return self.spans[-1].t_end if self.spans else self.t_enq

    def span(self, name: str, t_start: float, t_end: float) -> None:
        self.spans.append(Span(name, t_start, t_end))

    def extend(self, name: str, t_end: Optional[float] = None) -> None:
        t_end = now() if t_end is None else t_end
        self.spans.append(Span(name, self.last_end, t_end))

    # ---- derived signals ---------------------------------------------------

    @property
    def latency_ms(self) -> float:
        return (self.last_end - self.t_enq) * 1e3

    def _device_start(self) -> Optional[float]:
        for sp in self.spans:
            if sp.name == "device":
                return sp.t_start
        return None

    @property
    def slack_at_dispatch_ms(self) -> Optional[float]:
        """Deadline budget left when the request hit the device — the
        number a deadline-aware scheduler sheds/downgrades on.  None
        without a deadline or before dispatch."""
        d0 = self._device_start()
        if self.deadline_ms is None or d0 is None:
            return None
        return self.deadline_ms - (d0 - self.t_enq) * 1e3

    @property
    def violated(self) -> bool:
        return (self.deadline_ms is not None
                and self.latency_ms > self.deadline_ms)

    @property
    def attribution(self) -> Optional[str]:
        """Which span ate the budget: the longest one, reported only for
        violated requests (attribution of a met deadline is noise)."""
        if not self.violated or not self.spans:
            return None
        return max(self.spans, key=lambda sp: sp.dur_s).name

    # ---- validation --------------------------------------------------------

    def problems(self, bubble_tol_ms: float = DEFAULT_BUBBLE_TOL_MS
                 ) -> list[str]:
        return _chain_problems(
            [(sp.name, sp.t_start, sp.t_end) for sp in self.spans],
            self.kind, self.t_enq, bubble_tol_ms,
        )

    def complete(self, bubble_tol_ms: float = DEFAULT_BUBBLE_TOL_MS
                 ) -> bool:
        return not self.problems(bubble_tol_ms)

    # ---- export ------------------------------------------------------------

    def asdict(self) -> dict:
        """The per-trace dict inside a ``serve:trace`` record (also the
        wire form a replica marshals back to the router).  Times stay on
        the span clock, which every process on the host shares, so replica
        traces line up with engine ones."""
        return {
            "request_id": int(self.request_id),
            "op": self.op,
            "kind": self.kind,
            "bucket": self.tags.get("bucket"),
            "tier": self.tags.get("tier"),
            "replica_id": self.tags.get("replica_id"),
            "cfg_hash": self.tags.get("cfg_hash"),
            "deadline_ms": self.deadline_ms,
            "t_enq_s": round(self.t_enq, 6),
            "latency_ms": round(self.latency_ms, 4),
            "slack_at_dispatch_ms": (
                round(self.slack_at_dispatch_ms, 4)
                if self.slack_at_dispatch_ms is not None else None
            ),
            "violated": bool(self.violated),
            "attribution": self.attribution,
            "spans": [
                {"name": sp.name, "t_start_s": round(sp.t_start, 6),
                 "dur_ms": round(max(0.0, sp.dur_s) * 1e3, 4)}
                for sp in self.spans
            ],
        }


def _chain_problems(spans: list[tuple], kind: str, t_enq: float,
                    bubble_tol_ms: float) -> list[str]:
    """Shared chain validation over (name, t_start, t_end) triples —
    RequestTrace objects and ledger trace dicts both route here, so the
    in-run gate and `ledger.validate_serve_trace` can never disagree."""
    probs: list[str] = []
    if kind not in REQUIRED:
        return [f"unknown trace kind {kind!r}"]
    if not spans:
        return [f"empty span chain (kind {kind!r})"]
    names = [n for n, _, _ in spans]
    for n in names:
        if n not in CHAIN:
            probs.append(f"unknown span name {n!r}")
    order = [CHAIN.index(n) for n in names if n in CHAIN]
    if order != sorted(order):
        probs.append(f"span names out of chain order: {names}")
    it = iter(names)
    if not all(req in it for req in REQUIRED[kind]):
        probs.append(
            f"incomplete chain for kind {kind!r}: have {names}, need "
            f"{list(REQUIRED[kind])}"
        )
    tol_s = bubble_tol_ms / 1e3
    prev_end = t_enq
    for name, t0, t1 in spans:
        if t1 < t0 - _OVERLAP_EPS_S:
            probs.append(f"span {name!r} ends before it starts "
                         f"({t1:.6f} < {t0:.6f})")
        if t0 < prev_end - _OVERLAP_EPS_S:
            probs.append(
                f"span {name!r} starts at {t0:.6f}, before the previous "
                f"span ended ({prev_end:.6f}) — non-monotonic chain"
            )
        gap = t0 - prev_end
        if gap > tol_s:
            probs.append(
                f"{gap * 1e3:.3f} ms un-spanned gap before {name!r} "
                f"exceeds the {bubble_tol_ms} ms bubble tolerance"
            )
        prev_end = max(prev_end, t1)
    return probs


def trace_dict_problems(t: dict,
                        bubble_tol_ms: float = DEFAULT_BUBBLE_TOL_MS
                        ) -> list[str]:
    """Structural + chain validation of one exported trace dict (the
    `traces` entries of a ``serve:trace`` block).  Returns problem
    strings, [] when valid — the obs.ledger validator convention."""
    probs: list[str] = []
    if not isinstance(t, dict):
        return [f"trace entry is {type(t).__name__}, not a dict"]
    if not isinstance(t.get("request_id"), int):
        probs.append(f"request_id {t.get('request_id')!r} is not an int")
    if not isinstance(t.get("op"), str):
        probs.append(f"op {t.get('op')!r} is not a string")
    spans = t.get("spans")
    if not isinstance(spans, list):
        return probs + [f"spans is {type(spans).__name__}, not a list"]
    triples = []
    for i, sp in enumerate(spans):
        if not isinstance(sp, dict):
            probs.append(f"spans[{i}] is not a dict")
            continue
        name, t0, dur = sp.get("name"), sp.get("t_start_s"), sp.get("dur_ms")
        if not isinstance(name, str):
            probs.append(f"spans[{i}].name {name!r} is not a string")
            continue
        if not isinstance(t0, (int, float)) \
                or not isinstance(dur, (int, float)):
            probs.append(f"span {name!r} has non-numeric timing "
                         f"(t_start_s={t0!r}, dur_ms={dur!r})")
            continue
        if dur < 0:
            probs.append(f"span {name!r} has negative duration {dur}")
            continue
        triples.append((name, float(t0), float(t0) + float(dur) / 1e3))
    if not probs:
        t_enq = t.get("t_enq_s")
        t_enq = float(t_enq) if isinstance(t_enq, (int, float)) else (
            triples[0][1] if triples else 0.0)
        probs.extend(_chain_problems(triples, t.get("kind", "batched"),
                                     t_enq, bubble_tol_ms))
    dl = t.get("deadline_ms")
    if dl is not None and not isinstance(dl, (int, float)):
        probs.append(f"deadline_ms {dl!r} is not numeric")
    return probs


class TraceLog:
    """Bounded accumulator of a run's traces.  The engine `start()`s one
    RequestTrace per submitted request; a router `add()`s the already-
    exported dicts its replicas marshal back.  Oldest traces drop first
    past `cap`, counted visibly (`dropped`) so a truncated export can
    never read as a complete run."""

    def __init__(self, cap: int = DEFAULT_TRACE_CAP):
        if cap < 1:
            raise ValueError(f"trace cap must be >= 1, got {cap}")
        # single-owner by default; the Router shares ONE TraceLog between
        # its pump thread and client threads and guards every call with
        # its RLock (see serve/router.py emit_trace)
        self.cap = cap  # guarded-by: <frozen>
        self.total = 0  # guarded-by: <owner-thread>
        self._traces: deque = deque(maxlen=cap)  # guarded-by: <owner-thread>

    def start(self, request_id: int, op: str, t_enq: float, *,
              deadline_ms: Optional[float] = None, **tags) -> RequestTrace:
        tr = RequestTrace(request_id, op, t_enq,
                          deadline_ms=deadline_ms, **tags)
        self.total += 1
        self._traces.append(tr)
        return tr

    def add(self, trace_dict: dict) -> None:
        self.total += 1
        self._traces.append(trace_dict)

    @property
    def dropped(self) -> int:
        return self.total - len(self._traces)

    def trace_dicts(self) -> list[dict]:
        return [t.asdict() if isinstance(t, RequestTrace) else dict(t)
                for t in self._traces]

    def __len__(self) -> int:
        return len(self._traces)

    def block(self, bubble_tol_ms: float = DEFAULT_BUBBLE_TOL_MS) -> dict:
        return build_block(self.trace_dicts(), bubble_tol_ms=bubble_tol_ms,
                           dropped=self.dropped)

    def emit(self, path: Optional[str] = None, *, grid=None, config=None,
             bubble_tol_ms: float = DEFAULT_BUBBLE_TOL_MS,
             **extra) -> dict:
        """One schema-tagged ``serve:trace`` ledger record carrying the
        whole log (appended to `path` when given) — same manifest
        discipline as serve:request_stats."""
        from capital_tpu.obs import ledger

        rec = ledger.record(
            "serve:trace",
            ledger.manifest(grid=grid, config=config),
            serve_trace=self.block(bubble_tol_ms),
            **extra,
        )
        if path:
            ledger.append(path, rec)
        return rec


def build_block(trace_dicts: list[dict], *,
                bubble_tol_ms: float = DEFAULT_BUBBLE_TOL_MS,
                dropped: int = 0) -> dict:
    """The ``serve_trace`` record block: the traces plus the aggregate
    verdicts the gates read (complete count under the pinned bubble
    tolerance, SLO violations)."""
    from capital_tpu.obs.ledger import SCHEMA_VERSION

    complete = sum(
        1 for t in trace_dicts if not trace_dict_problems(t, bubble_tol_ms)
    )
    return {
        "schema_version": SCHEMA_VERSION,
        "bubble_tol_ms": float(bubble_tol_ms),
        "requests": len(trace_dicts),
        "complete": complete,
        "dropped": int(dropped),
        "violations": sum(1 for t in trace_dicts if t.get("violated")),
        "traces": trace_dicts,
    }

