"""AOT witness for the N=65536 BASELINE north-star row.

The chip tool offers at most four v5e chips; the BASELINE.md target names
"Cholesky & QR throughput, N=65536 ... TPU v5e-8".  What the compiler can
say without the chips is the real program for a described topology:
`jax.experimental.topologies.get_topology_desc` builds a deviceless v5e
slice (``--topology``, default the one four-chip host ``v5e:2x2``, whose
2x2 face gives c = 1; ``v5e:2x4`` gives the 2x2x2 grid), the distributed
cholinv factor (in place: the Schur updates written back into A, R and
R⁻¹ carried in as donated buffers, as the benchmark's mesh cell runs it)
is jitted against it, and XLA's memory analysis + the emitted collective
schedule are the artifact — per-chip peak HBM, argument/output/temp
footprints, and the collective op census, plus the cost-model step-time
projection against measured single-chip kernel rates.

``--candidates`` compiles each SUMMA schedule a mesh can run (GSPMD's
``xla`` and the explicit ``block``, ``tile_cyclic`` and
``tile_cyclic_persistent`` balances) and writes one row each.

CLI::

    python -m capital_tpu.bench.aot65536 [--n 65536] [--bc 512]
        [--topology v5e:2x2] [--mode explicit] [--balance tile_cyclic]
        [--candidates] [--out docs/N65536_V5E4.md]

Reference: the schedule this witnesses is the reference's
cholinv.hpp:87-165 recursion over a d x d x c topology (topology.h:77-94).
"""

from __future__ import annotations

import argparse
import collections
import json
import re
import time

import jax
import jax.numpy as jnp

#: the SUMMA schedules a multi-chip cholinv can run: (mode, balance)
CANDIDATES = (
    ("xla", "block"),
    ("explicit", "block"),
    ("explicit", "tile_cyclic"),
    ("explicit", "tile_cyclic_persistent"),
)


def _devices(topology: str):
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices


def build(n: int, bc: int, topology: str, mode: str, balance: str,
          schur_in_place: bool = True):
    """The cholinv program on a described slice: a 2x2 face, c the devices
    over four.  It factors A into R and R⁻¹ carried in as buffers (the
    benchmark's in-place protocol), bf16, precision None."""
    from capital_tpu.models import cholesky
    from capital_tpu.parallel.topology import Grid

    devs = _devices(topology)
    grid = Grid.square(c=max(1, len(devs) // 4), devices=devs)
    cfg = cholesky.CholinvConfig(
        base_case_dim=bc, split=1, mode=mode, balance=balance,
        schur_in_place=schur_in_place, precision=None,
    )

    def fn(A, Rp, RIp):
        return cholesky.factor(grid, A, cfg, out_buffers=(Rp, RIp))

    shape = jax.ShapeDtypeStruct((n, n), jnp.bfloat16,
                                 sharding=grid.face_sharding())
    return grid, cfg, fn, (shape, shape, shape)


def build_cacqr(m: int, n: int, bc: int, topology: str):
    """The CQR2 program for BASELINE's QR north-star row (2M x 1024
    "8-rank" configuration): tall-skinny X row-sharded over every chip of
    the described topology, the 1d tree regime (reference cacqr.hpp:103's
    panel pipeline over the flat communicator)."""
    from capital_tpu.models import cholesky, qr
    from capital_tpu.parallel.topology import Grid

    grid = Grid.flat(devices=_devices(topology))
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
    return grid, cfg, fn, (shape,)


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


def cost_projection(grid, fn, shapes, n: int,
                    useful_flops: float | None = None) -> dict:
    """Trace-time cost-model projection: per-chip executed flops and comm
    bytes from the tracing Recorder, turned into a step-time band with the
    measured kernel rates (docs/PERF.md: 169-186 TF/s sustained executed on
    the balanced kernels) and the framework's own DeviceSpec ICI figure
    (utils/tracing.py — the same constant every other cost table uses)."""
    from capital_tpu.utils import tracing

    with tracing.Recorder() as rec:
        # a fresh wrapper: jax caches traces by function identity, and a
        # cached trace would leave both the Recorder and a later lowering's
        # trace time empty
        jax.eval_shape(lambda *a: fn(*a), *shapes)
    # the cost model (tracing.gemm_cost etc.) emits PER-DEVICE flops and
    # comm bytes — the Recorder totals are already per-chip
    per_chip_flops = sum(s.flops_max for s in rec.stats.values())
    per_chip_comm = sum(s.comm_bytes for s in rec.stats.values())
    ncoll = sum(s.collectives for s in rec.stats.values())
    lo, hi = 169e12, 186e12  # measured sustained executed TF/s band
    # the described chip's interconnect, not the CPU's the compile runs on
    ici = tracing.device_spec(grid.mesh.devices.flat[0]).ici_gbps * 1e9
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


def _compile_and_measure(fn, shapes, donate=()):
    """lower -> compile -> per-chip memory analysis -> collective census,
    with the seconds each build step took: the one copy of the
    compile-and-measure sequence every witness path shares."""
    t0 = time.perf_counter()
    lowered = jax.jit(fn, donate_argnums=donate).lower(*shapes)
    t1 = time.perf_counter()
    print(f"# lowered OK ({t1 - t0:.1f} s)", flush=True)
    compiled = lowered.compile()
    t2 = time.perf_counter()
    print(f"# compiled OK ({t2 - t1:.1f} s, real XLA:TPU codegen)", flush=True)
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_memory_bytes": ma.peak_memory_in_bytes,
        "generated_code_bytes": ma.generated_code_size_in_bytes,
        "trace_lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
    }
    print("# per-chip memory:", json.dumps(mem), flush=True)
    census = collective_census(compiled.as_text())
    print("# collective census:", json.dumps(census), flush=True)
    return mem, census


# XLA's per-chip byte limit on v5e as it reports it (decimal GB: the
# round-3 OOM messages read "Used 16.01G of 15.75G")
HBM_V5E = 15.75e9


def _gib(b):
    return b / 1e9


def _mem_table(mem, arg_label, out_label):
    """The per-chip memory markdown table the witness artifacts share."""
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
    p.add_argument("--topology", default="v5e:2x2",
                   help="described TPU slice; the grid's c is its chips / 4")
    p.add_argument("--mode", default="explicit")
    p.add_argument("--balance", default="tile_cyclic")
    p.add_argument("--candidates", action="store_true",
                   help="compile every (mode, balance) of CANDIDATES")
    p.add_argument("--no-schur-in-place", action="store_true")
    p.add_argument("--out", default=None, help="write the markdown artifact here")
    args = p.parse_args(argv)

    if args.alg == "cacqr":
        n = args.n or 1024
        grid, cfg, fn, shapes = build_cacqr(args.m, n, min(args.bc, n // 2),
                                            args.topology)
        print(f"# grid {grid} over deviceless {args.topology}; m={args.m} n={n}")
        useful = 2.0 * args.m * n * n * cfg.num_iter
        proj = cost_projection(grid, fn, shapes, n, useful_flops=useful)
        return _run_aot(args, grid, cfg, fn, shapes, proj, n)

    args.n = args.n or 65536
    pairs = CANDIDATES if args.candidates else ((args.mode, args.balance),)
    rows = []
    for mode, balance in pairs:
        grid, cfg, fn, shapes = build(
            args.n, args.bc, args.topology, mode, balance,
            not args.no_schur_in_place,
        )
        print(f"# grid {grid} over deviceless {args.topology}; n={args.n} "
              f"bc={args.bc} mode={mode} balance={balance}", flush=True)
        proj = cost_projection(grid, fn, shapes, args.n)
        print("# cost projection:", json.dumps(proj), flush=True)
        try:
            mem, census = _compile_and_measure(fn, shapes, donate=(1, 2))
            refused = None
        except Exception as e:  # noqa: BLE001 — a refusal is the reading
            mem, census, refused = None, None, str(e).splitlines()[0][:300]
            print(f"# refused: {refused}", flush=True)
        rec = {
            "metric": "aot_cholinv", "topology": args.topology, "n": args.n,
            "bc": args.bc, "grid": repr(grid), "mode": cfg.mode,
            "balance": cfg.balance, "schur_in_place": cfg.schur_in_place,
            "per_chip": mem, "collectives": census, "projection": proj,
            "refused": refused,
        }
        print(json.dumps(rec), flush=True)
        rows.append(rec)
    if args.out:
        with open(args.out, "w") as f:
            f.write(_cholinv_doc(args, rows))
        print(f"# wrote {args.out}")
    return rows


def _run_aot(args, grid, cfg, fn, shapes, proj, n):
    """Compile the cacqr program and write its witness artifact."""
    print("# cost projection:", json.dumps(proj))
    mem, census = _compile_and_measure(fn, shapes)
    rec = {
        "metric": "aot_cacqr", "topology": args.topology,
        "m": args.m, "n": n, "grid": repr(grid), "regime": cfg.regime,
        "per_chip": mem, "collectives": census, "projection": proj,
    }
    print(json.dumps(rec))
    if args.out:
        hbm = HBM_V5E
        with open(args.out, "w") as f:
            f.write(
                f"""# CQR2 {args.m}x{n} on {args.topology} — AOT-compiled witness

BASELINE.md's QR north-star row ("2M x 1024, 8 ranks") cannot be
*executed* on this one-chip rig; the single-chip one-shot row
(160.0-160.5 TF/s, docs/BENCH_SUITE_v5e.md) bounds the kernels, and
this artifact witnesses the DISTRIBUTED program: the full 8-chip CQR2,
compiled by the real XLA:TPU toolchain against a deviceless v5e-8
topology, with XLA's per-chip memory analysis and the emitted
collective schedule.

Reproduce: `python -m capital_tpu.bench.aot65536 --alg cacqr --topology {args.topology} --out {args.out}`

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


def _cholinv_doc(args, rows) -> str:
    """The markdown artifact of the cholinv witness: one section per
    compiled (mode, balance)."""
    out = [
        f"# N={args.n} cholinv on {args.topology}: AOT-compiled witness\n",
        "The distributed cholinv factor, compiled by the real XLA:TPU "
        "toolchain against a deviceless",
        f"`{args.topology}` topology (`jax.experimental.topologies."
        "get_topology_desc`), with XLA's",
        "own per-chip memory analysis and the emitted collective schedule. "
        "Nothing runs: no time",
        "here is a chip time.\n",
        "Reproduce: `python -m capital_tpu.bench.aot65536 --topology "
        f"{args.topology} --n {args.n} --bc {args.bc}"
        + (" --candidates" if len(rows) > 1 else
           f" --mode {rows[0]['mode']} --balance {rows[0]['balance']}")
        + f" --out {args.out}`\n",
        f"Program: cholinv, n={args.n} bf16, bc={args.bc}, split=1, "
        "precision None, the Schur updates in place, R and R⁻¹ carried in "
        "as donated buffers (`cholesky.factor(..., out_buffers=...)`).\n",
        "| mode | balance | trace+lower s | compile s | arguments GB | "
        "outputs GB | temporaries GB | peak GB (of 15.75) | collectives |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        m = r["per_chip"]
        if m is None:
            out.append(f"| {r['mode']} | {r['balance']} | — | — | — | — | — "
                       f"| refused: {r['refused']} | — |")
            continue
        coll = ", ".join(f"{k} {v}" for k, v in sorted(r["collectives"].items()))
        out.append(
            f"| {r['mode']} | {r['balance']} | {m['trace_lower_s']} | "
            f"{m['compile_s']} | {_gib(m['argument_bytes']):.2f} | "
            f"{_gib(m['output_bytes']):.2f} | {_gib(m['temp_bytes']):.2f} | "
            f"{_gib(m['peak_memory_bytes']):.2f} | {coll} |")
    out.append("")
    for r in rows:
        out.append(f"## {r['mode']} / {r['balance']}\n")
        if r["per_chip"] is not None:
            out.append(_mem_table(r["per_chip"], "A, R, R⁻¹ blocks",
                                  "R, R⁻¹ blocks, aliased"))
            out.append("")
        out.append("```json")
        out.append(json.dumps({"per_chip": r["per_chip"],
                               "collectives": r["collectives"],
                               "projection": r["projection"],
                               "refused": r["refused"]}, indent=2))
        out.append("```\n")
    return "\n".join(out)


if __name__ == "__main__":
    main()
