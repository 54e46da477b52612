"""AOT witness for the N=65536 / v5e-8 BASELINE north-star row.

The chip tool offers at most four v5e chips; the BASELINE.md target names
"Cholesky & QR throughput, N=65536 ... TPU v5e-8".  What CAN be produced
without 8 chips (VERDICT r3 #2) is the real 8-chip program, compiled by the
real TPU toolchain: `jax.experimental.topologies.get_topology_desc` builds
a deviceless v5e-8 topology, the full distributed cholinv factor step
(explicit shard_map SUMMA schedule, tile-cyclic balancing, in-place Schur)
is jitted against it, and XLA's memory analysis + the emitted collective
schedule are committed as the artifact — per-chip peak HBM, argument/
output/temp footprints, and the collective op census, plus the cost-model
step-time projection against measured single-chip kernel rates.

CLI::

    python -m capital_tpu.bench.aot65536 [--n 65536] [--bc 512] [--c 2]
        [--out docs/N65536_V5E8.md]

Reference: the 8-rank schedule this witnesses is the reference's
cholinv.hpp:87-165 recursion over a d x d x c topology (topology.h:77-94).
"""

from __future__ import annotations

import argparse
import collections
import json
import re

import jax
import jax.numpy as jnp


def build(n: int, bc: int, c: int, balance: str, schur_in_place: bool):
    from jax.experimental import topologies

    from capital_tpu.models import cholesky
    from capital_tpu.parallel.topology import Grid

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x4")
    devs = topo.devices
    grid = Grid.square(c=c, devices=devs)
    cfg = cholesky.CholinvConfig(
        base_case_dim=bc, split=1, mode="explicit", balance=balance,
        schur_in_place=schur_in_place,
    )

    def fn(A):
        return cholesky.factor(grid, A, cfg)

    shape = jax.ShapeDtypeStruct((n, n), jnp.bfloat16, sharding=grid.face_sharding())
    return grid, cfg, fn, shape


def build_cacqr(m: int, n: int, bc: int):
    """The 8-rank CQR2 program for BASELINE's QR north-star row (2M x 1024
    "8-rank" configuration): tall-skinny X row-sharded over all 8 chips of
    the deviceless v5e-8 topology, the 1d tree regime (reference
    cacqr.hpp:103's panel pipeline over the flat communicator)."""
    from jax.experimental import topologies

    from capital_tpu.models import cholesky, qr
    from capital_tpu.parallel.topology import Grid

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x4")
    grid = Grid.flat(devices=topo.devices)
    # mode='pallas': the fused tall-pass kernels run PER SHARD inside the
    # shard_map pipeline (qr._cqr2_fused_sharded) — this witness is the
    # compile certificate that Mosaic custom calls work under the manual
    # partitioning (round-5; the GSPMD path cannot partition them)
    cfg = qr.CacqrConfig(
        num_iter=2, regime="1d", mode="pallas",
        cholinv=cholesky.CholinvConfig(base_case_dim=bc),
    )

    def fn(X):
        Q, R = qr.factor(grid, X, cfg)
        return Q, R

    shape = jax.ShapeDtypeStruct((m, n), jnp.bfloat16, sharding=grid.rows_sharding())
    return grid, cfg, fn, shape


def collective_census(text: str) -> dict[str, int]:
    """Count collective HLO *instructions* in the compiled module text.

    Only opcode positions count: the token right after the `=` of an
    instruction definition (`%all-gather.1 = bf16[...] all-gather(...)`
    names the instruction after its opcode, and operand references repeat
    the name — matching bare words over-counted every collective 2-3x,
    round-4 review finding).  Async pairs count once, at -start."""
    pat = re.compile(
        r"= *[^=\n]*?\b(all-gather|all-reduce|reduce-scatter|"
        r"collective-permute|all-to-all|collective-broadcast)"
        r"(-start)?\("
    )
    counts: collections.Counter = collections.Counter()
    for line in text.splitlines():
        m = pat.search(line)
        if m:
            counts[m.group(1)] += 1
    return dict(counts)


def cost_projection(grid, fn, shape, n: int, useful_flops: float | None = None) -> dict:
    """Trace-time cost-model projection: per-chip executed flops and comm
    bytes from the tracing Recorder, turned into a step-time band with the
    measured kernel rates (docs/PERF.md: 169-186 TF/s sustained executed on
    the balanced kernels) and the framework's own DeviceSpec ICI figure
    (utils/tracing.py — the same constant every other cost table uses)."""
    from capital_tpu.utils import tracing

    with tracing.Recorder() as rec:
        jax.eval_shape(fn, shape)
    # the cost model (tracing.gemm_cost etc.) emits PER-DEVICE flops and
    # comm bytes — the Recorder totals are already per-chip
    per_chip_flops = sum(s.flops for s in rec.stats.values())
    per_chip_comm = sum(s.comm_bytes for s in rec.stats.values())
    ncoll = sum(s.collectives for s in rec.stats.values())
    lo, hi = 169e12, 186e12  # measured sustained executed TF/s band
    ici = tracing.device_spec().ici_gbps * 1e9
    comp_ms = (per_chip_flops / hi * 1e3, per_chip_flops / lo * 1e3)
    comm_ms = per_chip_comm / ici * 1e3
    useful = useful_flops if useful_flops is not None else 2.0 * n**3 / 3.0
    return {
        "useful_flops": useful,
        "per_chip_executed_tflop": per_chip_flops / 1e12,
        "per_chip_comm_bytes": per_chip_comm,
        "collective_calls_modeled": ncoll,
        "comp_ms_band": [round(comp_ms[0], 1), round(comp_ms[1], 1)],
        "comm_ms": round(comm_ms, 1),
        "step_ms_band": [
            round(comp_ms[0] + comm_ms, 1),
            round(comp_ms[1] + comm_ms, 1),
        ],
        "useful_tflops_per_chip_band": [
            round(useful / grid.num_devices / (comp_ms[1] + comm_ms) / 1e9, 1),
            round(useful / grid.num_devices / (comp_ms[0] + comm_ms) / 1e9, 1),
        ],
    }


def _compile_and_measure(fn, shape):
    """lower -> compile -> per-chip memory analysis -> collective census:
    the one copy of the compile-and-measure sequence both witness paths
    share."""
    lowered = jax.jit(fn).lower(shape)
    print("# lowered OK")
    compiled = lowered.compile()
    print("# compiled OK (real XLA:TPU codegen for the 8-chip program)")
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_memory_bytes": ma.peak_memory_in_bytes,
        "generated_code_bytes": ma.generated_code_size_in_bytes,
    }
    print("# per-chip memory:", json.dumps(mem))
    census = collective_census(compiled.as_text())
    print("# collective census:", json.dumps(census))
    return mem, census


# XLA's per-chip byte limit on v5e as it reports it (decimal GB: the
# round-3 OOM messages read "Used 16.01G of 15.75G")
HBM_V5E = 15.75e9


def _gib(b):
    return b / 1e9


def _mem_table(mem, arg_label, out_label):
    """The per-chip memory markdown table both witness artifacts share."""
    return (
        "| quantity | bytes | GB |\n"
        "|---|---|---|\n"
        f"| arguments ({arg_label}) | {mem['argument_bytes']} | {_gib(mem['argument_bytes']):.2f} |\n"
        f"| outputs ({out_label}) | {mem['output_bytes']} | {_gib(mem['output_bytes']):.2f} |\n"
        f"| temporaries | {mem['temp_bytes']} | {_gib(mem['temp_bytes']):.2f} |\n"
        f"| **peak HBM** | **{mem['peak_memory_bytes']}** | **{_gib(mem['peak_memory_bytes']):.2f}** |"
    )


def main(argv=None):
    p = argparse.ArgumentParser(prog="capital_tpu.bench.aot65536")
    p.add_argument("--alg", choices=["cholinv", "cacqr"], default="cholinv")
    p.add_argument("--n", type=int, default=None,
                   help="65536 for cholinv, 1024 for cacqr unless set")
    p.add_argument("--m", type=int, default=1 << 21, help="cacqr: rows")
    p.add_argument("--bc", type=int, default=512)
    p.add_argument("--c", type=int, default=2)
    p.add_argument("--balance", default="tile_cyclic")
    p.add_argument("--no-schur-in-place", action="store_true")
    p.add_argument("--out", default=None, help="write the markdown artifact here")
    args = p.parse_args(argv)

    if args.alg == "cacqr":
        n = args.n or 1024
        grid, cfg, fn, shape = build_cacqr(args.m, n, min(args.bc, n // 2))
        print(f"# grid {grid} over deviceless v5e-8 topology; m={args.m} n={n}")
        useful = 2.0 * args.m * n * n * cfg.num_iter
        proj = cost_projection(grid, fn, shape, n, useful_flops=useful)
        return _run_aot(args, grid, cfg, fn, shape, proj, n)

    args.n = args.n or 65536
    grid, cfg, fn, shape = build(
        args.n, args.bc, args.c, args.balance, not args.no_schur_in_place
    )
    print(f"# grid {grid} over deviceless v5e-8 topology; n={args.n} bc={args.bc}")

    proj = cost_projection(grid, fn, shape, args.n)
    print("# cost projection:", json.dumps(proj))

    return _run_cholinv_tail(args, grid, cfg, fn, shape, proj)


def _run_aot(args, grid, cfg, fn, shape, proj, n):
    """Compile the cacqr 8-chip program and write its witness artifact."""
    print("# cost projection:", json.dumps(proj))
    mem, census = _compile_and_measure(fn, shape)
    rec = {
        "metric": "aot_v5e8_cacqr",
        "m": args.m, "n": n, "grid": repr(grid), "regime": cfg.regime,
        "per_chip": mem, "collectives": census, "projection": proj,
    }
    print(json.dumps(rec))
    if args.out:
        hbm = HBM_V5E
        with open(args.out, "w") as f:
            f.write(
                f"""# CQR2 {args.m}x{n} on v5e-8 — AOT-compiled witness (round 4)

BASELINE.md's QR north-star row ("2M x 1024, 8 ranks") cannot be
*executed* on this one-chip rig; the single-chip one-shot row
(160.0-160.5 TF/s, docs/BENCH_SUITE_v5e.md) bounds the kernels, and
this artifact witnesses the DISTRIBUTED program: the full 8-chip CQR2,
compiled by the real XLA:TPU toolchain against a deviceless v5e-8
topology, with XLA's per-chip memory analysis and the emitted
collective schedule.

Reproduce: `python -m capital_tpu.bench.aot65536 --alg cacqr --out {args.out}`

## Program

CholeskyQR2, X {args.m} x {n} bf16 row-sharded over {grid!r} (the flat
8-rank topology the reference's cacqr tree runs on, cacqr.hpp:103),
regime='1d', num_iter=2.

## Per-chip memory (XLA buffer assignment, bytes are PER CHIP)

{_mem_table(mem, "X block", "Q block, R")}

Peak = {100 * mem['peak_memory_bytes'] / hbm:.0f}% of a v5e chip's
15.75 GB XLA byte limit — the 8-chip row fits with room to spare (the
single-chip run needed the one-shot regen protocol precisely because
~4 Q-sized buffers did NOT fit one chip).

## Collective schedule (compiled HLO census, per-step)

```json
{json.dumps(census, indent=2)}
```

The all-reduces are the gram-tree merges (the reference's
MPI_Allreduce over the flat communicator, cacqr.hpp:118-131); Q stays
row-local end to end.

## Cost-model projection (measured single-chip constants)

```json
{json.dumps(proj, indent=2)}
```

The program is the PER-SHARD FUSED pipeline (round 5, VERDICT r4 #2):
every chip runs the Mosaic tall-pass kernels on its own m/8 rows
inside one shard_map — Mosaic custom calls cannot be GSPMD-partitioned
(the round-4 AOT finding), but under shard_map's manual partitioning
they compile, and this artifact IS that compile certificate.  The
projection prices the emitted schedule's executed flops (the fused
(g+1)/2g column-split saving on every chip) with the measured
single-chip sustained band; round 4's unfused projection was
96.3-105.9 TF/s/chip — the per-shard kernels close the gap to the
single-chip one-shot row (160 TF/s).
"""
            )
        print(f"# wrote {args.out}")


def _run_cholinv_tail(args, grid, cfg, fn, shape, proj):
    mem, census = _compile_and_measure(fn, shape)
    rec = {
        "metric": "aot_v5e8_cholinv",
        "n": args.n,
        "bc": args.bc,
        "grid": repr(grid),
        "mode": cfg.mode,
        "balance": cfg.balance,
        "schur_in_place": cfg.schur_in_place,
        "per_chip": mem,
        "collectives": census,
        "projection": proj,
    }
    print(json.dumps(rec))
    if args.out:
        hbm = HBM_V5E
        with open(args.out, "w") as f:
            f.write(
                f"""# N=65536 on v5e-8 — AOT-compiled witness (round 4)

BASELINE.md's north star ("Cholesky & QR throughput, N=65536 ... TPU
v5e-8") cannot be *executed* here (the chip tool offers at most four
chips).  This artifact is the strongest producible witness short of
execution: the **full 8-chip program, compiled by the real XLA:TPU
toolchain** against a deviceless v5e-8 topology
(`jax.experimental.topologies.get_topology_desc('v5e:2x4')`), with XLA's
own per-chip memory analysis and the emitted collective schedule.

Reproduce: `python -m capital_tpu.bench.aot65536 --out {args.out}`

## Program

cholinv factor, n={args.n} bf16, grid {grid!r} (2x2 face, c={args.c}
replication — the 8-chip BASELINE topology), mode='explicit' (shard_map
SUMMA schedule), balance='{cfg.balance}', schur_in_place={cfg.schur_in_place},
bc={args.bc}, split=1.  This is the same configuration family the
single-chip flagship runs, distributed.

## Per-chip memory (XLA buffer assignment, bytes are PER CHIP)

{_mem_table(mem, "A block", "R, R⁻¹ blocks")}

Peak = {100 * mem['peak_memory_bytes'] / hbm:.0f}% of a v5e chip's
15.75 GB XLA byte limit — the program **fits**; the single-chip wall
(3 x n² buffers = 25.8 GB at n=65536, docs/PERF.md) falls to the 8-chip
distribution exactly as designed.

## Collective schedule (compiled HLO census, per-step)

```json
{json.dumps(census, indent=2)}
```

The schedule is the explicit-mode SUMMA pipeline: all-gathers ride the
row/column axes (the reference's MPI_Bcast distribute, summa.hpp:185-193),
all-reduces the depth axis (the collect, summa.hpp:236), and
collective-permutes the grid transposes (util.hpp:232-247's
MPI_Sendrecv_replace pairs).

## Cost-model projection (measured single-chip constants)

```json
{json.dumps(proj, indent=2)}
```

Projected step time {proj['step_ms_band'][0]}-{proj['step_ms_band'][1]} ms
-> **{proj['useful_tflops_per_chip_band'][0]}-{proj['useful_tflops_per_chip_band'][1]}
useful TF/s/chip** against the 177.3 TF/s/chip target (90% of v5e bf16
peak).  Constants: 169-186 TF/s sustained executed kernel rate (the
measured flagship band, docs/PERF.md), DeviceSpec ICI bandwidth
(utils/tracing.py — the same constant every cost table uses).  The
projection prices the same schedule family the compiled HLO above emits
(tests/test_collective_audit.py pins emission = cost model on the CPU
mesh).
"""
            )
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
