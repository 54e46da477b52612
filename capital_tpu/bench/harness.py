"""Shared benchmark harness: timing discipline + result reporting.

The reference's drivers (bench/*/*.cpp) share a fixed shape: parse argv,
build a topology, distribute a matrix, warm up, run timed iterations under
`MPI_Barrier; MPI_Wtime`, and print the max-over-ranks wall time
(bench/cholesky/cholinv.cpp:44-59).  On TPU the same discipline needs two
changes:

* async dispatch means host-side walls lie — so the iteration loop runs
  INSIDE one jit (`lax.fori_loop` with a data-dependent carry that consumes
  every algorithm output, preventing dead-code elimination of the work), and
  the per-iteration time is the delta between an (iters+1)-iteration run and
  a 1-iteration run, which also cancels the fixed dispatch overhead;
* "max over ranks" is automatic — one XLA program spans the mesh, so the
  wall covers the slowest chip.

Each driver prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline", ...context}.  `vs_baseline` is achieved/target where the
target is 90% of the chip's peak dense-matmul throughput at the bench dtype
(BASELINE.md: the reference publishes no absolute numbers, so the
peak-relative north star *is* the baseline).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Callable

import jax
import jax.numpy as jnp

from capital_tpu.utils import tracing

# The device-trace floor machinery (bench/trace.device_budget) can fail for
# exactly these reasons: the xplane protobuf import is unavailable
# (ImportError), the profiler emitted no xplane.pb / an unreadable one
# (RuntimeError / OSError), or a malformed plane parses to nonsense
# (ValueError).  Anything else — XlaRuntimeError from the measured program,
# KeyboardInterrupt, bugs — must PROPAGATE: the old bare `except Exception`
# here swallowed real failures into a silent "no floor".
TRACE_FLOOR_ERRORS = (ImportError, OSError, RuntimeError, ValueError)


def _warn(msg: str) -> None:
    print(f"# harness: {msg}", file=sys.stderr)


def peak_tflops(device=None, dtype=jnp.bfloat16) -> float:
    """Peak dense-matmul TFLOP/s for one chip at `dtype` (public specs)."""
    return tracing.device_spec(device).peak_tflops(dtype)


class MeasurementUnresolved(RuntimeError):
    """timed_loop could not resolve a positive per-iteration time — the step
    is below the host-wall noise floor even at the escalated trip cap.
    Distinct from generic RuntimeError so sweep drivers can skip noise-floor
    configs without also swallowing real failures (XlaRuntimeError — OOM,
    compile errors — subclasses RuntimeError)."""


# The runtime failure class the containment layer bounds: OOMs, compile
# errors, device aborts.  jax.errors.JaxRuntimeError IS the XlaRuntimeError
# alias in current jax; the tuple exists so a jaxlib rename stays a one-line
# fix here instead of a hunt through every sweep driver.
RUNTIME_FAILURES = (jax.errors.JaxRuntimeError,)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry/backoff for per-config runtime failures in a sweep.

    retries: attempts AFTER the first (0 = fail immediately).  Default 1:
        transient device OOMs (fragmentation after a big predecessor
        config) often clear on a retry; deterministic failures shouldn't
        burn more than one.
    backoff_s / multiplier: sleep before attempt k is
        backoff_s * multiplier**(k-1) — gives the runtime a beat to release
        buffers before the retry."""

    retries: int = 1
    backoff_s: float = 0.25
    multiplier: float = 2.0


class ConfigFailed(RuntimeError):
    """One sweep config exhausted its RetryPolicy on runtime failures.
    Carries the attempt count and the final cause so the sweep can persist
    a useful failure record instead of a bare traceback."""

    def __init__(self, label: str, attempts: int, cause: BaseException):
        super().__init__(
            f"{label} failed after {attempts} attempt(s): "
            f"{type(cause).__name__}: {cause}"
        )
        self.label = label
        self.attempts = attempts
        self.cause = cause


def run_guarded(
    fn: Callable[[], object],
    policy: RetryPolicy = RetryPolicy(),
    label: str = "config",
) -> tuple[object, int]:
    """Run fn() with the bounded retry/backoff of `policy`; returns
    (result, attempts).  Catches ONLY RUNTIME_FAILURES — an OOM/compile
    abort of this config must not kill the whole sweep — and re-raises as
    ConfigFailed once the policy is exhausted.  MeasurementUnresolved and
    every other exception propagate untouched (they already have their own
    handling story in the callers)."""
    delay = policy.backoff_s
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn(), attempt
        except RUNTIME_FAILURES as e:
            if attempt > policy.retries:
                raise ConfigFailed(label, attempt, e) from e
            _warn(
                f"{label} attempt {attempt} failed "
                f"({type(e).__name__}); retrying in {delay:.2f}s"
            )
            time.sleep(delay)
            delay *= policy.multiplier


#: The dispatch-noise band a measured delta must clear to be trusted.  On
#: a TPU v5e, back-to-back 1-trip round trips (dispatch + host sync, 1.12 ms
#: median wall) differ by 0.06 ms median, 0.18 ms p90, 0.24 ms max over 30
#: pairs (my chip run, PR 21); 2 ms is ~8x that max, and the same band
#: serves the CPU rig.
NOISE_BAND_S = 0.002


def latency_samples(fn, calls: int = 32, warmup: int = 3) -> list[float]:
    """Per-call wall seconds of `fn()` — the SERVING-latency protocol, the
    deliberate opposite of timed_loop's in-jit amortized one: each sample
    is one dispatch + one device round-trip (block_until_ready), because a
    served request pays exactly that, and a p99 over amortized loop bodies
    would hide the dispatch tail a latency SLO exists to catch.  Compile
    time stays out via the warmup calls.  Feed the result to
    `serve.stats.percentiles` — the shared quantile rule keeps a bench
    latency row and a serve request_stats record on one scale."""
    import time

    if calls < 1:
        raise ValueError(f"latency_samples needs calls >= 1, got {calls}")
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(time.perf_counter() - t0)
    return samples


def _resolve_delta(
    run, k: int, cap: int, repeats: int, noise: float, samples_out=None
) -> tuple[float, float, int]:
    """The one escalate-until-the-delta-clears-the-noise-band loop shared by
    every protocol (timed_loop, timed_oneshot x2): returns (per-iter
    seconds, raw delta, final trip count).  Callers decide what an
    unresolved result means."""
    t, delta = paired_median_delta(run, k, repeats, samples_out)
    while k < cap and delta < noise:
        k = min(cap, max(k * 2, int(3.0 * noise / max(t, 1e-9))))
        if samples_out is not None:
            samples_out.clear()  # samples from a rejected trip count
        t, delta = paired_median_delta(run, k, repeats, samples_out)
    return t, delta, k


def paired_median_delta(
    run, k: int, nrep: int, samples_out=None
) -> tuple[float, float]:
    """(per-iteration seconds, raw delta): median over INTERLEAVED
    (base, full) wall pairs of `run(1)` vs `run(k+1)`.

    The measurement protocol under timed_loop and timed_oneshot.  Adjacent
    pairs share a drift window, so the delta isolates the in-jit
    iterations; sampling all bases then all fulls lets monotone
    drift between the blocks bias the result (observed: 16.8 ms/iter
    reported for a step whose device-counter op time is 26.6 ms and whose
    200-iteration sustained marginal is 24.9 ms).  The median rejects
    jitter outliers — a single paired delta can even go negative for sub-ms
    steps, which once let an autotune sweep crown a config with a negative
    "time".

    `samples_out` (a list) collects the raw per-iteration seconds of each
    pair (delta / k) for percentile reporting (serve.stats.percentiles);
    individual samples keep the jitter the median rejects — including
    possible negatives — which is exactly what a spread statistic should
    see."""
    import statistics

    deltas = []
    for _ in range(nrep):
        b = run(1)
        f = run(k + 1)
        deltas.append(f - b)
    if samples_out is not None:
        samples_out.extend(d / k for d in deltas)
    d = statistics.median(deltas)
    return d / k, d


def _make_loop(step: Callable, coupling: str):
    """The one in-jit measurement loop shared by timed_loop and
    device_ms_per_iter — both must run the SAME program or the device
    floor would not be the wall's floor."""

    @jax.jit
    def loop(a, eps, k):
        def body(_, carry):
            out = step(carry)
            e = eps.astype(carry.dtype)
            if coupling == "elem":
                return carry.at[0, 0].add(e * out[0, 0])
            return carry + e * out

        out = jax.lax.fori_loop(0, k, body, a)
        return jnp.sum(out, dtype=jnp.float32)

    return loop


def pallas_coupled(grid, m: int, n: int, mode: str, dtype) -> bool:
    """True when a 1d qr.factor of an (m, n) operand returns outputs that
    ride ops XLA cannot slice into (Q through pallas custom calls — the
    blocked/fused kernels engaged — and R through a whole-input potrf
    chain), making the one-element carry (coupling='elem') measurement-safe.
    It asks the model which pipeline it builds (qr.route): the fused routes
    ride Mosaic custom calls (coupled); 'panels' is pure XLA (one-element
    consumption would let the simplifier drop every other panel — NOT
    coupled); the unfused sweeps are coupled on one device in pallas mode
    when the column-blocked scaling runs the live-tile trmm kernel (block
    width <= 2048)."""
    from capital_tpu.models import qr

    route, tags = qr.route(grid, m, n, dtype, qr.CacqrConfig(mode=mode), "1d")
    if route != "sweeps_1d":
        return route != "panels"
    g = tags["g"]
    return grid.num_devices == 1 and mode == "pallas" and g > 1 and n // g <= 2048


def device_ms_per_iter(
    step: Callable[[jnp.ndarray], jnp.ndarray],
    operand: jnp.ndarray,
    iters: int = 3,
    coupling: str = "full",
    loop=None,
) -> float:
    """Device-op own-time per iteration of the SAME in-jit loop timed_loop
    measures, from jax.profiler traces — the drift-immune floor a wall
    reading must not undercut (a wall below it is a favorable-drift
    artifact, docs/PERF.md "Measurement discipline").  Measured as a
    PAIRED DELTA, device_total(iters+1) - device_total(1), exactly like
    the wall protocol: a single run's total would include the per-call
    epilogue (the full-operand DCE sum — ~2.6 ms at the 1M x 1024 proxy)
    that the wall's delta cancels, and the floor would sit above honest
    walls.  Returns 0.0 when no device plane exists (CPU rigs) — callers
    skip the guard then.  Pass `loop` (a _make_loop product) to share the
    compiled program with timed_loop."""
    from capital_tpu.bench import trace as trace_mod

    loop = loop or _make_loop(step, coupling)
    eps = jnp.asarray(0.0, jnp.float32)
    float(loop(operand, eps, 1))  # compile + warm outside the trace

    def total(k: int) -> float:
        budget = trace_mod.device_budget(lambda: float(loop(operand, eps, k)))
        budget.pop("async (overlapped)", None)
        return sum(budget.values())

    try:
        return max(0.0, (total(iters + 1) - total(1)) / iters)
    except TRACE_FLOOR_ERRORS as e:
        if isinstance(e, jax.errors.JaxRuntimeError):
            raise  # a device-side failure of the measured program itself
        _warn(
            f"device trace unavailable ({type(e).__name__}: {e}); "
            "no device floor, wall stands"
        )
        return 0.0


def timed_loop(
    step: Callable[[jnp.ndarray], jnp.ndarray],
    operand: jnp.ndarray,
    iters: int = 3,
    repeats: int = 3,
    coupling: str = "full",
    loop=None,
    samples_out=None,
) -> float:
    """Per-iteration seconds of `step`, run `iters` times inside jit —
    the median over interleaved (1-trip, iters+1-trip) wall pairs
    (paired_median_delta); escalates the trip count when the delta is below
    the dispatch-noise band (NOISE_BAND_S).  Raises if it never resolves.

    `step(operand) -> array of operand's shape/dtype` must consume all the
    outputs it wants timed (see module docstring on DCE).  The perturbation
    scalar `eps` is 0.0 at call time but runtime-valued, so XLA cannot fold
    the iteration chain away.

    The default carry consumes the step output with a FULL-matrix add,
    deliberately: for arbitrary steps (xla-mode SUMMA, plain matmul chains)
    a one-element coupling would let the algebraic simplifier legitimately
    narrow slices into the producing ops and shrink the measured work.
    The cost: up to ~4 extra HBM passes of harness overhead per iteration,
    so suite/autotune numbers are slightly conservative.

    coupling='elem' opts a driver into the one-element carry
    (`carry[0,0] += eps·out[0,0]`): ONLY valid when the step's output
    arrives through ops XLA cannot narrow a slice into — pallas custom
    calls, full-input consumers like cholesky.  The cacqr pallas driver
    qualifies (Q is a pallas kernel output; R rides potrf, whose input
    gram is consumed whole) and its tall Q-sized full-add was ~5 ms/iter
    of pure harness overhead at the 1M x 1024 BASELINE proxy.
    """

    loop = loop or _make_loop(step, coupling)
    eps = jnp.asarray(0.0, jnp.float32)

    def run(k: int) -> float:
        t0 = time.perf_counter()
        float(loop(operand, eps, k))  # host transfer = real sync
        return time.perf_counter() - t0

    run(1)  # compile (dynamic trip count -> one executable reused for both k)
    t, delta = paired_median_delta(run, iters, repeats + 2, samples_out)
    # Escalate the trip count until the DELTA clears the noise band: a
    # positive but small delta is still mostly noise (a ~2ms step was
    # observed reporting 13ms when the total delta sat at ~40ms).
    noise = NOISE_BAND_S
    if delta < noise:
        if samples_out is not None:
            samples_out.clear()  # below-noise samples from the first pass
        t, delta, k = _resolve_delta(run, iters, 4096, repeats, noise,
                                     samples_out)
    else:
        k = iters
    if t <= 0.0 or delta < noise:
        # never resolved: refuse to return a fake number (a silent floor
        # once let a noise artifact win an autotune sweep; a positive delta
        # still inside the noise band at the trip-count cap is the same
        # artifact with extra steps)
        raise MeasurementUnresolved(
            f"timed_loop could not resolve a per-iteration time (delta "
            f"{delta:.3e}s after {k} iterations is inside the "
            f"{noise:.0e}s dispatch-noise band)"
        )
    return t


def timed_oneshot(
    gen: Callable[[jnp.ndarray], jnp.ndarray],
    step: Callable[[jnp.ndarray], jnp.ndarray],
    iters: int = 3,
    repeats: int = 8,
    device_check: bool = False,
) -> tuple[float, float, dict]:
    """The one-shot protocol for operands too large to carry: the operand
    is REGENERATED inside the loop each iteration by `gen(i)` (a fused
    elementwise program of the loop index — no persistent operand carry,
    so peak memory excludes it) and `step(a)` must return a
    scalar coupling value riding ops XLA cannot narrow (pallas chains /
    whole-input consumers — the caller asserts this, e.g.
    pallas_coupled).  A regen-only loop is measured separately and
    subtracted; the subtracted time must clear the noise band on its own.
    Returns (net seconds/iter, regen seconds/iter, extras) — extras carries
    the drift-guard fields (device_ms, wall_ms_below_floor) when
    device_check measures a device floor for the net time."""

    def make_loop(consume):
        @jax.jit
        def loop(eps, k):
            def body(i, c):
                a = jax.lax.optimization_barrier(gen(i))
                return c + eps * consume(a)

            return jax.lax.fori_loop(0, k, body, jnp.float32(0.0))

        return loop

    full = make_loop(lambda a: step(a).astype(jnp.float32))
    regen = make_loop(lambda a: a[0, 0].astype(jnp.float32))
    eps = jnp.asarray(0.0, jnp.float32)

    def run(loop, k):
        t0 = time.perf_counter()
        float(loop(eps, k))
        return time.perf_counter() - t0

    run(full, 1), run(full, 1)  # compile + settle
    noise = NOISE_BAND_S
    t, delta, iters = _resolve_delta(
        lambda k: run(full, k), iters, 512, repeats, noise
    )
    if t <= 0.0 or delta < noise:
        raise MeasurementUnresolved(
            f"one-shot full loop unresolved (delta {delta:.3e}s at {iters})"
        )
    run(regen, 1)
    tr, dr, kr = _resolve_delta(
        lambda k: run(regen, k), max(iters, 16), 4096, repeats, noise
    )
    if dr < noise:
        raise MeasurementUnresolved(
            f"one-shot regen loop unresolved (delta {dr:.3e}s at {kr})"
        )
    net = t - tr
    if net <= 0.0 or net * iters < noise:
        raise MeasurementUnresolved(
            f"one-shot net time {net:.3e}s/iter inside the noise band"
        )
    if device_check:
        # the drift guard for the one-shot protocol: the NET device floor
        # is the paired-delta difference of the two loops' device-op
        # totals (same discipline as the walls); a net wall below it is
        # re-measured, then floored — mirrors drivers._timed
        from capital_tpu.bench import trace as trace_mod

        def dev_total(loop, k):
            budget = trace_mod.device_budget(lambda: float(loop(eps, k)))
            budget.pop("async (overlapped)", None)
            return sum(budget.values()) / 1e3  # ms -> s

        try:
            dfull = dev_total(full, iters + 1) - dev_total(full, 1)
            dregen = dev_total(regen, iters + 1) - dev_total(regen, 1)
            dnet = max(0.0, (dfull - dregen) / iters)
        except TRACE_FLOOR_ERRORS as e:
            if isinstance(e, jax.errors.JaxRuntimeError):
                raise  # device-side failure of the measured program
            _warn(
                f"one-shot device floor unavailable ({type(e).__name__}: "
                f"{e}); wall stands unfloored"
            )
            dnet = 0.0
        if dnet > 0.0:
            tries = 0
            while net < dnet and tries < 2:
                t2, d2, _ = _resolve_delta(
                    lambda k: run(full, k), iters, 512, repeats, noise
                )
                if d2 >= noise:
                    net = t2 - tr
                tries += 1
            if net < dnet:
                return dnet, tr, {"device_ms": round(dnet * 1e3, 3),
                                  "wall_ms_below_floor": round(net * 1e3, 3)}
            return net, tr, {"device_ms": round(dnet * 1e3, 3)}
    return net, tr, {}


def report(
    metric: str,
    seconds: float,
    flops: float,
    dtype,
    device=None,
    **context,
) -> dict:
    """Print + return the one-line JSON record.

    schema_version/device/platform make the line self-identifying so
    ``obs diff`` can refuse to compare records from incompatible schemas
    or different chips (docs/OBSERVABILITY.md)."""
    from capital_tpu.obs.ledger import SCHEMA_VERSION

    device = device or jax.devices()[0]
    tflops = flops / seconds / 1e12
    target = 0.9 * peak_tflops(device, dtype)
    rec = {
        "metric": metric,
        "schema_version": SCHEMA_VERSION,
        "value": round(tflops, 3),
        "unit": "TFLOP/s",
        "vs_baseline": round(tflops / target, 4),
        "seconds": round(seconds, 5),
        "dtype": str(jnp.dtype(dtype)),
        "device": device.device_kind,
        "platform": jax.default_backend(),
        "target_tflops": round(target, 1),
        **context,
    }
    print(json.dumps(rec))
    return rec
