"""mesh_factor: cholinv over a square face of the cell's chips, one factor
after another, one dispatch per factor, each blocked on completion.

The grid is ``Grid.square(c=1)`` over the cell's chips (a 2x2 face on four).
Every factor makes a fresh ``spd_hash`` operand inside its dispatch, with
the face's block sharding, so each chip makes its own block of A with no
communication, and factors it in place into the previous factor's R and
R⁻¹ (the one-shot in-place protocol of dense_factor, three n² buffers in
all, a quarter of each a chip).  Those buffers are donated and carry the
step's output shardings, so the second call runs the first one's program.
The configuration's ``mode`` goes through the program's own rule
(``summa.resolve_mode``); its ``balance`` is passed as it stands.

factor_tflops is the algorithm's flops (2n³/3) for every factor completed
in the window over the window's wall time: the whole grid's rate.  On
stderr: the schedule and the route each trmm/syrk site took, and each
chip's peak memory.  Once the window has closed, the last factor's outputs
are compared with the configuration's plain reference.
"""

from __future__ import annotations

import json
import sys
import time

import common


def _routes() -> dict:
    """The program's trmm/syrk route counter, or {} where it keeps none."""
    try:
        from capital_tpu.obs import spans
    except ImportError:
        return {}
    routes = getattr(spans, "SUMMA_ROUTES", None)
    return {} if routes is None else routes.snapshot()


class _MeshCholinv:
    def __init__(self, ctx, gen):
        import jax
        import jax.numpy as jnp

        from capital_tpu.models import cholesky
        from capital_tpu.parallel import summa
        from capital_tpu.parallel.topology import Grid

        c = ctx.config
        n, dt = int(c["n"]), jnp.dtype(c["dtype"])
        self.grid = grid = Grid.square(
            c=1, devices=ctx.devices[:int(ctx.workload["chips"])])
        self.mode = summa.resolve_mode(c["mode"], grid)
        cfg = cholesky.CholinvConfig(
            base_case_dim=int(c["base_case_dim"]), mode=self.mode,
            balance=c["balance"], precision=c["precision"],
            schur_in_place=True)
        face = grid.face_sharding()

        def step(salt, rp, rip):
            a = jax.lax.with_sharding_constraint(gen.spd_hash(n, dt, salt),
                                                 face)
            return cholesky.factor(grid, a, cfg, out_buffers=(rp, rip))

        self.out = jax.jit(
            lambda: cholesky.factor_buffers(grid, n, dt, cfg),
            out_shardings=(face, face))()
        self.exe = jax.jit(step, donate_argnums=(1, 2),
                           out_shardings=(face, face)).lower(
            jnp.uint32(0), *self.out).compile()
        self.salt = None

    def step(self, salt: int):
        import numpy as np

        self.salt = salt
        self.out = self.exe(np.uint32(salt), *self.out)

    def block(self):
        for x in self.out:
            x.block_until_ready()

    def compare(self, ref, ctx, ctl=False):
        outs, self.out = list(self.out), None
        if ctl:
            for x in outs:
                x.delete()
            outs = ref.control(ctx.config, self.salt, ctx.seed)
        return ref.compare(ctx.config, self.salt, outs, ctx.seed)


def run(ctx, control: bool = False) -> common.Outcome:
    gen = common.generator_module()
    ref = ctx.reference
    prog = _MeshCholinv(ctx, gen)
    flops = ref.flops(ctx.config)
    marks = [("program", common.elapsed(ctx.t_process))]  # compiled
    warm = int(ctx.traffic.get("warmup_factors", 2))
    for k in range(warm):
        prog.step(common.mix(ctx.seed, 2, k))
        prog.block()
        marks.append((f"warm{k}", common.elapsed(ctx.t_process)))
    hlo = prog.exe.as_text() if ctx.trace else ""  # op names -> phases
    setup_s = common.elapsed(ctx.t_process)
    print("benchmark: set-up marks " + ", ".join(
        f"{k} {v:.3f}" for k, v in marks), file=sys.stderr)
    print(f"benchmark: grid {prog.grid.dx}x{prog.grid.dy}x{prog.grid.c}, "
          f"mode {prog.mode}, balance {ctx.config['balance']}; routes "
          f"{json.dumps(_routes(), sort_keys=True)}", file=sys.stderr)
    built = common.Builds.now()
    done = 0
    with ctx.window():
        t0 = time.perf_counter()
        while True:
            with ctx.span("dispatch"):
                prog.step(common.mix(ctx.seed, 3, done))
            with ctx.span("block"):
                prog.block()
            done += 1
            wall = time.perf_counter() - t0
            if wall >= ctx.seconds:
                break
    builds = common.Builds.since(built)
    peaks = [common.memory_peak([d]) for d in ctx.devices]
    print(f"benchmark: memory_peak_bytes per chip {peaks}", file=sys.stderr)
    t_check = time.perf_counter()
    gaps = prog.compare(ref, ctx, ctl=control)
    print(f"benchmark: {done} factors in {wall:.3f} s; comparison took "
          f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    limits = ctx.workload["limits"]
    return common.Outcome(
        setup_s=setup_s, attempted=done, failed=0,
        e2e={"setup_s": setup_s,
             "factor_tflops": done * flops / wall / 1e12},
        counters={"window_flops": done * flops, "factors": done,
                  "builds": builds, "hlo": hlo},
        checks={k: (v, float(limits[k])) for k, v in gaps.items()},
        memory_peak_bytes=max(peaks))
