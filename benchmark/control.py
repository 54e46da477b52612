"""Readings that set the limits of `correct`: the program on a dozen seeds
and more, and the control on three seeds or more, in one process.

    python3 benchmark/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 --control-seeds 4,5,6

Each seed is one run of the cell (a short window at the cell's own load),
through run.main.  The control stands in the program's place: for a dense
cell the plain reference computed in float8, for a serve cell the program
with its own lower-precision path switched on (the configuration's
``control`` settings).  The benchmark's own runs never run it.  Prints one
JSON line per run and a summary: each compared number's largest program
reading and smallest control reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def readings(workload: str, seconds: float, seeds, control_seeds,
             catalog=None, require_tpu: bool = True) -> dict:
    import jax

    prog: dict = {}
    ctl: dict = {}
    for seeds_, into, is_ctl in ((seeds, prog, False),
                                 (control_seeds, ctl, True)):
        for s in seeds_:
            line = run.main(["--workload", workload, "--seed", str(s),
                             "--seconds", str(seconds)], catalog=catalog,
                            require_tpu=require_tpu, control=is_ctl)
            jax.clear_caches()  # drop this run's programs from the chip
            gc.collect()
            for k, v in line["checks"].items():
                into.setdefault(k, []).append(v["value"])
            print(json.dumps({"seed": s, "control": is_ctl,
                              "correct": line["correct"],
                              "checks": line["checks"]}), flush=True)
    return {"program_max": {k: max(v) for k, v in prog.items()},
            "control_min": {k: min(v) for k, v in ctl.items()},
            "program": prog, "control": ctl}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", required=True)
    a = p.parse_args(argv)
    out = readings(a.workload, a.seconds,
                   [int(s) for s in a.seeds.split(",") if s],
                   [int(s) for s in a.control_seeds.split(",")])
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
