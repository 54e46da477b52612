"""CPU tests of the benchmark: JAX on the CPU with four virtual devices, the
small copies of the cells in tests/data, caches in a temporary directory."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import pytest  # noqa: E402

import common  # noqa: E402

#: the small copy of each cell
SMALL = {"cholinv.n49152": "cholinv.tiny", "cacqr.2Mx1024.x4": "cacqr.tiny.x4",
         "serve.small-n": "serve.tiny", "serve.mid-n": "serve.tiny"}


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    """A Catalog over tests/data with BENCHMARK.json's metrics pointed at
    the small cells, and the benchmark's caches under a temporary dir."""
    tmp = tmp_path_factory.mktemp("bench")
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({SMALL[c] for c in m["workloads"]})
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    mp = pytest.MonkeyPatch()
    mp.setattr(common, "JAX_CACHE", str(tmp / "jax"))
    mp.setattr(common, "TRACE_DIR", str(tmp / "trace"))
    yield common.Catalog(roots=[DATA], spec_path=str(path))
    mp.undo()


def run_cell(catalog, cell, seed=3000000001, seconds=1.0, trace=0,
             control=False):
    import run

    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace)], catalog=catalog,
                    require_tpu=False, control=control)
