"""The benchmark's one entry point.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

It finds the cell's files by name (common.Catalog), checks the device, runs
the cell's driver (set-up, the measured window, the comparison with the
plain reference) and prints one JSON line as the last line of its standard
output.  With --trace 0 the metrics are the cell's end-to-end metrics of
BENCHMARK.json; with --trace 1 the profiler runs around the window and the
metrics are the cell's per-layer metrics, each from its own reader in
metrics/.  The numbers compared, each beside its limit, are the last lines
on standard error and the last key of the line.

It exits non-zero and prints no result when JAX finds no TPU, a TPU kind
without a row in peaks.py, or fewer chips than the cell asks for, and when
the program is not next to it.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import common  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402


class Reading:
    """What a per-layer metric's reader gets: the reduced trace of the
    window, the driver's counters, and the cell's chips and peak."""

    def __init__(self, trace, counters, chips, peak):
        self.trace = trace
        self.counters = counters
        self.chips = chips
        self.peak = peak


def _devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise SystemExit(f"benchmark: needs a TPU, JAX found "
                             f"{devs[0].platform}")
        peaks.peak(devs[0].device_kind)
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX "
                         f"sees {len(devs)}")
    return devs[:chips]


def _phase_tags() -> tuple:
    from capital_tpu.utils import tracing

    return tuple(t.replace("::", ".") for t in tracing.PHASE_REGISTRY)


def main(argv=None, catalog=None, require_tpu=True, control=False) -> dict:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    cat = catalog or common.Catalog()
    cell = cat.workload(args.workload)
    config = cat.config(cell["config"])
    chips = int(cell["chips"])
    if common.ROOT not in sys.path:
        sys.path.insert(0, common.ROOT)
    try:
        import capital_tpu
    except ImportError as e:
        raise SystemExit(f"benchmark: no program next to it ({e})") from None
    if not os.path.abspath(capital_tpu.__file__).startswith(common.ROOT):
        raise SystemExit(f"benchmark: capital_tpu comes from "
                         f"{capital_tpu.__file__}, not {common.ROOT}")

    import jax

    jax.config.update("jax_compilation_cache_dir", common.JAX_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    common.Builds.start()
    devs = _devices(chips, require_tpu)
    dev0 = devs[0]
    if args.trace:
        shutil.rmtree(common.TRACE_DIR, ignore_errors=True)
    ctx = common.Ctx(
        cell=args.workload, workload=cell, config=config,
        traffic=cat.traffic(cell["traffic"]),
        reference=cat.reference(cell["config"]), seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), devices=devs,
        t_process=T_PROCESS, trace_dir=common.TRACE_DIR)
    driver = cat.driver(cell["driver"])
    out = driver.run(ctx, control=True) if control else driver.run(ctx)

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak_bytes}
    metrics: dict = {}
    line: dict = {"correct": out.correct, "attempted": out.attempted,
                  "failed": out.failed, "metrics": metrics, "device": device}
    if args.trace:
        sel = ({} if dev0.platform == "tpu" else
               {"select": trace_reduce.cpu_ops_line,
                "keep": trace_reduce.cpu_keep})
        red = trace_reduce.reduce(
            common.TRACE_DIR, tags=_phase_tags(), spans=common.SPANS,
            phases=trace_reduce.hlo_phase_map(out.counters.get("hlo", ""),
                                              _phase_tags()), **sel)
        shutil.rmtree(common.TRACE_DIR, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        pk = peaks.PEAKS.get(dev0.device_kind)
        reading = Reading(red, out.counters, chips, pk)
        for m in cat.metrics_for(args.workload, "per_layer"):
            v = cat.reader(m["name"]).read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        line["breakdown"] = {
            "device_ops": [[k, v] for k, v in red.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps[:10]]}
    else:
        for m in cat.metrics_for(args.workload, "end_to_end"):
            metrics[m["name"]] = {"value": out.e2e[m["name"]],
                                  "unit": m["unit"]}
    print(f"benchmark: programs built in the window "
          f"{out.counters.get('builds')}", file=sys.stderr)
    print(f"benchmark: setup_s {out.setup_s:.3f}, run ends at "
          f"{common.elapsed(T_PROCESS):.3f} s", file=sys.stderr)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    for k, (v, lim) in out.checks.items():
        print(f"check {k}: {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
