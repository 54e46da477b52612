"""mxp_solve: one dense SPD system after another, each solved to HPL-MxP's
FP64 residual check in one dispatch, blocked on its answer (closed loop,
concurrency 1, as HPL-MxP times one solve after another).

The configuration's ``entry`` is ``refine.posv_dense``.  A is made once at
set-up (``spd_hash`` in the configuration's dtype) and stays resident.
Each solve gets a fresh b, one column of ``tall_hash`` (float32 U[-1, 1)),
made inside the solve's dispatch from a salt drawn from the seed and the
solve's index; the dispatch factors A (only reading it: the Schur
complements go into fresh trailing windows) and refines.

factor_tflops is HPL-MxP's rate, its fixed flop count for one solve (the
reference's n³/3 + 2n²), times the solves completed in the window, over
the window's wall, as the other drivers count theirs.  The median and the
longest solve interval (answer to answer) are printed beside it, so that
a stalling solve shows.  A solve whose refinement did not converge counts
as failed.  Counters for
the per-layer readers: ``solves``, ``sweeps`` (the program's
RefineInfo.iters summed over the window's solves), ``residuals`` (each
solve evaluates one residual more than it corrects) and the residual route
the program counted.  Once the window has closed, the last solve's x is
checked by the plain reference (``hpl_resid``).  In a control run the
check is made on the program's answer before its first correction sweep
(the same entry with ``max_iters=0``): the bf16 factor's own solve, which
must fail it.

A program without the entry fails at once with a non-zero exit.
"""

from __future__ import annotations

import statistics
import sys
import time

import common


def _entry(ctx):
    try:
        from capital_tpu.robust import refine

        return refine, refine.posv_dense
    except (ImportError, AttributeError) as e:
        raise SystemExit(f"benchmark: the program has no "
                         f"{ctx.config['entry']} ({e})") from None


def _routes() -> dict:
    try:
        from capital_tpu.obs import spans
    except ImportError:
        return {}
    routes = getattr(spans, "REFINE_ROUTES", None)
    return {} if routes is None else routes.snapshot()


def run(ctx, control: bool = False) -> common.Outcome:
    refine, posv_dense = _entry(ctx)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from capital_tpu.models import cholesky
    from capital_tpu.parallel import summa
    from capital_tpu.parallel.topology import Grid

    gen = common.generator_module()
    ref = ctx.reference
    c = ctx.config
    n, dt = int(c["n"]), jnp.dtype(c["dtype"])
    if cholesky.pick_base_case(n) != int(c["base_case_dim"]):
        raise SystemExit(f"benchmark: the program picks base case "
                         f"{cholesky.pick_base_case(n)} at n={n}, the "
                         f"configuration says {c['base_case_dim']}")
    grid = Grid.square(c=1, devices=ctx.devices[:1])
    if summa.resolve_mode("auto", grid) != c["mode"]:
        raise SystemExit(f"benchmark: the program picks mode "
                         f"{summa.resolve_mode('auto', grid)} here, the "
                         f"configuration says {c['mode']}")
    salt_a = common.mix(ctx.seed, 1)
    A = jax.jit(lambda s: gen.spd_hash(n, dt, s),
                out_shardings=NamedSharding(grid.mesh, PartitionSpec()))(
        jnp.uint32(salt_a))

    def program(max_iters):
        def step(a, salt):
            b = gen.tall_hash(n, 1, jnp.dtype(c["rhs_dtype"]), salt)[:, 0]
            (xh, xl), _, ri = posv_dense(grid, a, b, max_iters=max_iters)
            return xh, xl, ri.iters[0], ri.converged[0], ri.resid[0]

        return jax.jit(step).lower(A, jnp.uint32(0)).compile()

    exe = program(refine.DEFAULT_MAX_ITERS)
    mem_plan = exe.memory_analysis()
    print(f"benchmark: the solve's compiled memory: arguments "
          f"{getattr(mem_plan, 'argument_size_in_bytes', None)}, "
          f"temporaries {getattr(mem_plan, 'temp_size_in_bytes', None)} "
          f"bytes", file=sys.stderr)
    flops = ref.flops(c)
    marks = [("program", common.elapsed(ctx.t_process))]  # compiled
    for k in range(int(ctx.traffic.get("warmup_solves", 2))):
        jax.block_until_ready(exe(A, np.uint32(common.mix(ctx.seed, 2, k))))
        marks.append((f"warm{k}", common.elapsed(ctx.t_process)))
    hlo = exe.as_text() if ctx.trace else ""  # op names -> phases
    setup_s = common.elapsed(ctx.t_process)
    print("benchmark: set-up marks " + ", ".join(
        f"{k} {v:.3f}" for k, v in marks), file=sys.stderr)
    built = common.Builds.now()
    done, stats, out, salt, ends = 0, [], None, 0, []
    with ctx.window():
        t0 = time.perf_counter()
        while True:
            salt = common.mix(ctx.seed, 3, done)
            with ctx.span("dispatch"):
                out = exe(A, np.uint32(salt))
            with ctx.span("block"):
                jax.block_until_ready(out)
            ends.append(time.perf_counter() - t0)
            stats.append(out[2:])
            done += 1
            wall = ends[-1]
            if wall >= ctx.seconds:
                break
    steps = [b - a for a, b in zip([0.0] + ends, ends)]
    builds = common.Builds.since(built)
    mem = common.memory_peak(ctx.devices)
    iters = [int(s[0]) for s in stats]
    failed = sum(1 for s in stats if int(s[1]) == 0)
    print(f"benchmark: {done} solves in {wall:.3f} s, a solve mean "
          f"{wall / done:.5f} s, median {statistics.median(steps):.5f}, "
          f"min {min(steps):.5f}, max {max(steps):.5f} (solve "
          f"{steps.index(max(steps))}); sweeps {iters}, program's scaled residual "
          f"of the last {float(out[4]):.4g}, residual route {_routes()}",
          file=sys.stderr)
    t_check = time.perf_counter()
    xh, xl = np.asarray(out[0]), np.asarray(out[1])
    del out, stats
    if control:
        ctl = program(0)
        xh, xl = ref.control(
            c, lambda: [np.asarray(v) for v in ctl(A, np.uint32(salt))[:2]])
    gaps = ref.compare(c, salt_a, salt, xh, xl)
    print(f"benchmark: comparison took {time.perf_counter() - t_check:.3f} "
          f"s", file=sys.stderr)
    limits = ctx.workload["limits"]
    return common.Outcome(
        setup_s=setup_s, attempted=done, failed=failed,
        e2e={"setup_s": setup_s,
             "factor_tflops": done * flops / wall / 1e12},
        counters={"window_flops": done * flops, "solves": done,
                  "sweeps": sum(iters), "residuals": sum(iters) + done,
                  "routes": _routes(), "builds": builds, "hlo": hlo},
        checks={k: (v, float(limits[k])) for k, v in gaps.items()},
        memory_peak_bytes=mem)
