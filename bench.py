"""Flagship benchmark: recursive Cholesky + triangular inverse (cholinv).

Times ``cholesky.factor`` — the reference's flagship algorithm
(bench/cholesky/cholinv.cpp) — on the available device(s) and prints ONE
JSON line::

    {"metric": "cholinv_tflops", "value": N, "unit": "TFLOP/s",
     "vs_baseline": N, ...}

``vs_baseline`` is achieved throughput over the north-star target from
BASELINE.md: 90% of the chip's peak dense-matmul throughput at the bench
dtype (the reference publishes no absolute numbers — its repo ships only
the harness — so the target *is* the baseline).  Flop count for Cholesky
factor + triangular inverse: N^3/3 + N^3/3 = 2N^3/3, times 2 sweeps of
useful work counted conservatively as N^3/3 + N^3/3 (factor+inverse).

Timing discipline: the reference driver times warmup + per-iteration walls
(bench/cholesky/cholinv.cpp:44-59).  Dispatch has a fixed host overhead and
async dispatch means naive host-side walls lie, so the iteration loop runs
INSIDE one jit (lax.fori_loop with a data-dependent carry), the result is
synced by a host transfer, and the per-iteration time is the delta between
an (ITERS+1)-iteration run and a 1-iteration run.

It measures a TPU or nothing: with no TPU it exits non-zero, and a device
kind without a row in ``tracing.SPECS`` (the one peak table) is an error.

Usage: python bench.py [N] [dtype] [iters] [base_case_dim] [precision]
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp


def auto_base_case(n: int) -> int:
    """Base-case dim for the flagship: 512 is the committed sweet spot; for
    n that 512 cannot tile exactly (the aligned pallas path needs
    n = bc * 2^k), fall back to the largest 128-multiple that does rather
    than padding — at n=49152 a 512 base would pad to 65536 ((4/3)^3 ≈ 2.4x
    the flops and an HBM OOM).  Candidates must be 128-multiples (the
    pallas view path needs every window offset 128-aligned,
    ops/pallas_tpu._fit_block).  When nothing tiles exactly, pick the
    candidate minimizing the padded dim (least wasted flops), not blindly
    512 — and warn; main() also records the padded dim in the JSON line so
    non-interactive consumers see the cost."""
    from capital_tpu.bench.drivers import pick_bc
    from capital_tpu.models import cholesky

    # ONE picker shared with the drivers (padding-aware; below the
    # small-N crossovers finer leaves shorten the latency-bound potrf
    # chain — docs/PERF.md "Small-N — round 5")
    best = pick_bc(n)
    if cholesky.padded_dim(n, best) == n:
        return best
    print(
        f"# warning: no 128-multiple base tiles n={n} exactly; "
        f"padding to {cholesky.padded_dim(n, best)} with bc={best} "
        f"({cholesky.padded_dim(n, best)**3 / n**3:.2f}x the flops — "
        "pick n = bc * 2^k to avoid this)",
        file=sys.stderr,
    )
    return best


def spd_hash(n: int, dtype, salt) -> "jnp.ndarray":
    """Deterministic well-conditioned SPD matrix as ONE fused elementwise
    program — no RNG bit buffers, no transpose pass, exactly one n x n
    output allocation.  Used by the one-shot loop, which must re-materialize
    a fresh operand EVERY iteration (salt = loop index, so XLA cannot hoist
    it) while three factor-sized buffers are already resident.

    Entries: symmetric splitmix32-style hash of (min(i,j), max(i,j), salt)
    mapped to U[-1, 1]/sqrt(n), plus a 3I shift.  Spectral norm of the
    random part ≈ 2·sqrt(n·Var) = 2/sqrt(3) ≈ 1.16, so the spectrum sits in
    ~[1.8, 4.2]: safely SPD at bf16 like _spd's Wigner operand (same 3I
    margin — see capital_tpu/bench/drivers.py:_spd on why not 2I)."""
    from jax import lax

    r = lax.broadcasted_iota(jnp.uint32, (n, n), 0)
    c = lax.broadcasted_iota(jnp.uint32, (n, n), 1)
    lo, hi = jnp.minimum(r, c), jnp.maximum(r, c)
    h = lo * jnp.uint32(0x9E3779B1) ^ hi * jnp.uint32(0x85EBCA77)
    h = h + jnp.asarray(salt).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    u = h.astype(jnp.float32) * jnp.float32(2.0**-32)  # [0, 1)
    v = (2.0 * u - 1.0) * jnp.float32(1.0 / float(n) ** 0.5)
    v = v + jnp.where(r == c, jnp.float32(3.0), jnp.float32(0.0))
    return v.astype(dtype)


def main() -> None:
    from capital_tpu.utils import compile_cache, tracing

    compile_cache.enable()
    # default 49152, not 32768: the larger size amortizes the diagonal-band
    # masking and base-case latency floors (169.3-169.9 TF/s = 0.955-0.958
    # vs 156.8-157.1 = 0.886 at 32768, three runs each) and is the largest
    # bc·2^k that fits one v5e in the one-shot 3-buffer protocol below
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 49152
    dtype = jnp.dtype(sys.argv[2]) if len(sys.argv) > 2 else jnp.bfloat16
    iters = int(sys.argv[3]) if len(sys.argv) > 3 else 5
    # argv[5]: matmul precision override for >= f32 dtypes ('high' = the
    # in-kernel bf16x3 3-pass — f32-grade residuals at ~1.6x the default
    # 6-pass 'highest' rate; docs/PERF.md "f32 round 4")
    precision = sys.argv[5] if len(sys.argv) > 5 else None

    from capital_tpu.models import cholesky
    from capital_tpu.parallel.topology import Grid

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures a TPU; JAX found {dev.platform}")
    spec = tracing.device_spec(dev)
    grid = Grid.square(c=1, devices=[dev])

    # argv bc of 0 (or absent) means auto-pick
    bc = (int(sys.argv[4]) if len(sys.argv) > 4 else 0) or auto_base_case(n)
    padded = cholesky.padded_dim(n, bc)

    # One-shot mode for sizes whose 3-buffer resident set (operand carry +
    # R + Rinv + the materialized Schur chain, ~3.35 n² at bf16) cannot fit
    # one chip's HBM: the loop re-materializes a fresh operand per iteration
    # (spd_hash of the loop index — one fused n² write) and factors it with
    # schur_in_place, so peak memory is exactly 3 n² buffers (operand — dead
    # after its last Schur read — plus the two factor buffers with every
    # Schur update aliased in place).  n=49152 bf16: 14.5 GB vs 15.75;
    # round-2's carry-mode attempt measured "Used 19.42G".  The regen cost
    # is measured by a second loop with the factor removed and subtracted.
    oneshot = (
        3.35 * padded * padded * jnp.dtype(dtype).itemsize > spec.hbm_bytes
    )
    if os.environ.get("CAPITAL_BENCH_ONESHOT") in ("0", "1"):  # A/B override
        oneshot = os.environ["CAPITAL_BENCH_ONESHOT"] == "1"
    cfg = cholesky.CholinvConfig(
        base_case_dim=bc,
        mode="pallas",
        precision=(
            None if jnp.dtype(dtype).itemsize < 4 else (precision or "highest")
        ),
        schur_in_place=oneshot,
    )

    from capital_tpu.bench import harness

    eps = jnp.asarray(0.0, jnp.float32)

    if oneshot:
        if padded != n:
            # cropped outputs cannot serve as the next iteration's p x p
            # buffers; untileable n pays the hoisted-zeros copies instead
            raise SystemExit(
                f"oneshot mode needs n = bc * 2^k (n={n} pads to {padded}); "
                "pick a tiling size — see auto_base_case"
            )

        @jax.jit
        def loop(eps, iters):
            def body(i, carry):
                acc, Rp, RIp = carry
                # optimization_barrier pins the generator as a materialized
                # n² buffer in BOTH loops (without it the regen-only loop's
                # one-element consumption would let XLA narrow the fused
                # generator to a single element and the subtraction would
                # over-credit the factor)
                a = jax.lax.optimization_barrier(spd_hash(n, dtype, i))
                # the factor buffers are loop CARRIES: each iteration
                # factors into the previous outputs (every upper tile is
                # rewritten, the dead lower zeros are never touched) —
                # without this, XLA hoists the loop-invariant zero-init
                # and re-copies both buffers every iteration before the
                # first aliased write (2 x 3.27 ms/iter at n=49152)
                R, Rinv = cholesky.factor(grid, a, cfg, out_buffers=(Rp, RIp))
                d = R[0, 0] + Rinv[0, 0]
                return acc + eps * d.astype(jnp.float32), R, Rinv

            Rp0, RIp0 = cholesky.factor_buffers(grid, n, dtype, cfg)
            out, _, _ = jax.lax.fori_loop(
                0, iters, body, (jnp.float32(0.0), Rp0, RIp0)
            )
            return out

        @jax.jit
        def loop_regen(eps, iters):
            def body(i, carry):
                a = jax.lax.optimization_barrier(spd_hash(n, dtype, i))
                return carry + eps * a[0, 0].astype(jnp.float32)

            return jax.lax.fori_loop(0, iters, body, jnp.float32(0.0))

        def timed(k: int) -> float:
            t0 = time.perf_counter()
            float(loop(eps, k))
            return time.perf_counter() - t0

        def timed_regen(k: int) -> float:
            t0 = time.perf_counter()
            float(loop_regen(eps, k))
            return time.perf_counter() - t0
    else:
        # standard mode: the operand is the loop carry (no per-iteration
        # regeneration; ~3.35 n² resident is fine at these sizes)
        from capital_tpu.bench.drivers import _spd

        # well-conditioned SPD operand, generated on device (shared helper:
        # 3I diagonal shift — the Wigner edge sits at exactly 2, so a 2I
        # shift can graze a zero eigenvalue and NaN an f32/bf16 factorization
        # depending on the RNG stream; an f32 host staging array would also
        # be a 4.3GB transient at n=32768)
        A = _spd(n, dtype)

        @jax.jit
        def loop(a, eps, iters):
            def body(_, carry):
                R, Rinv = cholesky.factor(grid, carry, cfg)
                # data-dependent carry consuming BOTH outputs: eps is a
                # runtime scalar (0.0 at call time) so XLA cannot fold the
                # perturbation away and dead-code-eliminate the
                # factorization.  Consuming one element of each output is
                # sufficient — R/Rinv are produced by chains of aliased
                # pallas custom calls XLA cannot slice through, so every
                # kernel still runs (verified on-device: elem-coupling 37.6
                # ms/iter vs 38.3 for full-sum consumption vs 18.0 when the
                # Rinv chain is *actually* DCE'd, n=16k).  Consuming only R
                # would kill the inverse-completion half of the work; a
                # full-matrix carry add (carry + eps*(R+Rinv)) costs ~4
                # extra HBM passes of pure harness overhead (~10 ms/iter at
                # n=32k).
                d = R[0, 0] + Rinv[0, 0]
                return carry.at[0, 0].add(eps.astype(carry.dtype) * d)

            out = jax.lax.fori_loop(0, iters, body, a)
            return jnp.sum(out, dtype=jnp.float32)

        def timed(k: int) -> float:
            t0 = time.perf_counter()
            float(loop(A, eps, k))  # host transfer = real sync
            return time.perf_counter() - t0

        timed_regen = None

    timed(1)  # warmup: compile (dynamic trip count -> one executable)
    timed(1)  # second warmup: let clocks settle post-compile
    # Interleaved (base, full) pairs + median — the one protocol shared with
    # harness.timed_loop; see paired_median_delta for the drift-bias story.
    t, delta = harness.paired_median_delta(timed, iters, 8)
    noise = harness.NOISE_BAND_S
    while iters < 512 and delta < noise:
        # small-n runs: grow the in-jit loop until the delta clears the band
        grow = int(3.0 * noise / t) if t > 0.0 else iters * 8
        iters = min(512, max(iters * 2, grow))
        t, delta = harness.paired_median_delta(timed, iters, 5)
    if t <= 0.0 or delta < noise:
        raise SystemExit(
            f"measurement unresolved: delta {delta:.3e}s at {iters} "
            "iterations is inside the dispatch-noise band"
        )

    t_regen = 0.0
    if oneshot:
        timed_regen(1)  # compile the regen-only loop
        # the regen step (~one fused n² write) is far below the factor but
        # must clear the noise band on its own; grow its trip count
        # independently (cheap — no factor inside)
        kr = max(iters, 16)
        t_regen, dr = harness.paired_median_delta(timed_regen, kr, 8)
        while kr < 4096 and dr < noise:
            kr = min(4096, max(kr * 2, int(3.0 * noise / max(t_regen, 1e-9))))
            t_regen, dr = harness.paired_median_delta(timed_regen, kr, 5)
        if t_regen < 0.0 or dr < noise:
            raise SystemExit(
                f"regen measurement unresolved: delta {dr:.3e}s at {kr} "
                "iterations is inside the dispatch-noise band"
            )
        t = t - t_regen
        # the SUBTRACTED time is the reported quantity: it must itself be
        # positive and clear the band over the measured trip count, else
        # the factor is measurement noise riding on two valid loops (small
        # n under the A/B override: two medians can jitter past each other
        # and print a negative or infinite TF/s)
        if t <= 0.0 or t * iters < noise:
            raise SystemExit(
                f"oneshot measurement unresolved: factor-only time "
                f"{t:.3e}s/iter after regen subtraction is inside the "
                "dispatch-noise band"
            )

    flops = 2.0 * n**3 / 3.0  # factor (n^3/3) + full triangular inverse (n^3/3)
    tflops = flops / t / 1e12
    target = 0.9 * spec.peak_tflops(dtype)

    rec = {
        "metric": "cholinv_tflops",
        "value": round(tflops, 3),
        "unit": "TFLOP/s",
        "vs_baseline": round(tflops / target, 4),
        "n": n,
        "bc": bc,
        "dtype": str(jnp.dtype(dtype)),
        "seconds": round(t, 4),
        "device": dev.device_kind,
        "target_tflops": round(target, 1),
    }
    if padded != n:
        rec["padded"] = padded  # flops above count n³, not the executed padded³
    if oneshot:
        rec["oneshot"] = True
        rec["regen_seconds"] = round(t_regen, 4)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
