"""dense_factor: one dense factorization after another, one dispatch per
factor, each blocked on completion.

The configuration's ``entry`` picks the program:

* ``cholesky.factor``: cholinv on one chip.  Every factor gets a fresh
  ``spd_hash`` operand, made on the device inside the same dispatch, and
  writes into the previous factor's output buffers (bench.py's one-shot
  in-place protocol: three n² buffers in all).
* ``qr.factor``: CholeskyQR2 on one resident operand, rows sharded over the
  cell's chips with ``Grid.flat``.

factor_tflops is the algorithm's flops for every factor completed in the
window over the window's wall time, summed over the cell's chips.  Once the
window has closed, the last factor's outputs are compared with the
configuration's plain reference.
"""

from __future__ import annotations

import sys
import time

import common


class _Cholinv:
    def __init__(self, ctx, gen):
        import jax
        import jax.numpy as jnp

        from jax.sharding import NamedSharding, PartitionSpec

        from capital_tpu.models import cholesky
        from capital_tpu.parallel.topology import Grid

        c = ctx.config
        self.n, dt = int(c["n"]), jnp.dtype(c["dtype"])
        n = self.n
        grid = Grid.square(c=1, devices=ctx.devices[:1])
        cfg = cholesky.CholinvConfig(
            base_case_dim=int(c["base_case_dim"]), mode=c["mode"],
            precision=c["precision"], schur_in_place=True)

        def step(salt, rp, rip):
            a = gen.spd_hash(n, dt, salt)
            return cholesky.factor(grid, a, cfg, out_buffers=(rp, rip))

        # the buffers get the sharding the step's outputs have, so that the
        # second call is the first one's program and nothing compiles twice
        same = NamedSharding(grid.mesh, PartitionSpec())
        self.out = jax.device_put(jax.jit(
            lambda: cholesky.factor_buffers(grid, n, dt, cfg))(), same)
        self.exe = jax.jit(step, donate_argnums=(1, 2)).lower(
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


class _Cacqr:
    def __init__(self, ctx, gen):
        import jax
        import jax.numpy as jnp

        from capital_tpu.models import qr
        from capital_tpu.parallel.topology import Grid

        c = ctx.config
        m, n, dt = int(c["m"]), int(c["n"]), jnp.dtype(c["dtype"])
        grid = Grid.flat(ctx.devices[:int(ctx.workload["chips"])])
        cfg = qr.CacqrConfig(num_iter=int(c["num_iter"]), regime=c["regime"],
                             mode=c["mode"])
        self.salt = common.mix(ctx.seed, 1)
        self.A = jax.jit(lambda s: gen.tall_hash(m, n, dt, s),
                         out_shardings=grid.rows_sharding())(
            jnp.uint32(self.salt))
        self.exe = jax.jit(lambda a: qr.factor(grid, a, cfg)).lower(
            self.A).compile()
        self.out = None

    def step(self, salt: int):
        self.out = self.exe(self.A)

    def block(self):
        for x in self.out:
            x.block_until_ready()

    def compare(self, ref, ctx, ctl=False):
        outs, self.out = list(self.out), None
        if ctl:
            for x in outs:
                x.delete()
            outs = ref.control(ctx.config, self.A)
        return ref.compare(ctx.config, self.A, outs)


ENTRIES = {"cholesky.factor": _Cholinv, "qr.factor": _Cacqr}


def run(ctx, control: bool = False) -> common.Outcome:
    gen = common.generator_module()
    ref = ctx.reference
    prog = ENTRIES[ctx.config["entry"]](ctx, gen)
    flops = ref.flops(ctx.config)
    marks = [("program", common.elapsed(ctx.t_process))]  # compiled
    warm = int(ctx.traffic.get("warmup_factors", 2))
    for k in range(warm):  # compiles (or loads) and runs every program once
        prog.step(common.mix(ctx.seed, 2, k))
        prog.block()
        marks.append((f"warm{k}", common.elapsed(ctx.t_process)))
    hlo = prog.exe.as_text() if ctx.trace else ""  # op names -> phases
    setup_s = common.elapsed(ctx.t_process)
    print("benchmark: set-up marks " + ", ".join(
        f"{k} {v:.3f}" for k, v in marks), file=sys.stderr)
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
    mem = common.memory_peak(ctx.devices)
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
        memory_peak_bytes=mem)
