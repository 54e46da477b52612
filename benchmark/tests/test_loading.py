"""Every file the harness finds by name loads, every name and unit keeps to
the allowed characters, and a later PR adds a cell, a configuration and a
metric as new files only."""

import glob
import json
import os
import re

import pytest

import common
from conftest import run_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = json.load(open(os.path.join(common.ROOT, "BENCHMARK.json")))


def _names(sub):
    return sorted(os.path.basename(p)[:-5]
                  for p in glob.glob(os.path.join(common.HERE, sub, "*.json")))


def test_spec_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    every = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [w["config"] for w in SPEC["workloads"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    for name in every:
        assert NAME.match(name), name
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_metric_workloads_name_real_cells():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), m["name"]
        assert os.path.isfile(os.path.join(common.HERE, "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("cell", _names("workloads"))
def test_cell_files_load(cell):
    cat = common.Catalog()
    w = cat.workload(cell)
    cfg = cat.config(w["config"])
    cat.traffic(w["traffic"])
    cat.driver(w["driver"])
    ref = cat.reference(w["config"])
    assert w["chips"] in (1, 4) and w["limits"]
    entry = [x for x in SPEC["workloads"] if x["name"] == cell]
    if entry:  # a cell of BENCHMARK.json says what its file says
        e = entry[0]
        assert (e["config"], e["traffic"], e["chips"], e["why"]) == (
            w["config"], w["traffic"], w["chips"], w["why"])
    assert hasattr(ref, "compare") or hasattr(ref, "solve")
    assert set(cfg.get("reduced", {})) == set(
        next((c["reduced"] for c in SPEC["configs"]
              if c["name"] == w["config"]), cfg.get("reduced", {})))


@pytest.mark.parametrize("config", _names("configs"))
def test_config_source(config):
    cfg = common.Catalog().config(config)
    assert 1 <= len(cfg["source"]) <= 200 and "\n" not in cfg["source"]
    entry = [c for c in SPEC["configs"] if c["name"] == config]
    if entry:
        assert entry[0]["source"] == cfg["source"]
        assert entry[0]["file"] == f"benchmark/configs/{config}.json"


def test_later_pr_adds_files_only(tmp_path, tiny):
    """A throwaway cell, configuration and metric live in a new directory;
    no file here changes, and the harness finds and reports them."""
    for sub in ("configs", "workloads", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "cholinv-other.json").write_text(json.dumps(
        {"name": "cholinv-other", "reference": "cholinv-dense",
         "entry": "cholesky.factor", "n": 256, "dtype": "bfloat16",
         "mode": "pallas", "precision": None, "base_case_dim": 128,
         "ref_block": 128, "ref_cols": 8}))
    (tmp_path / "workloads" / "cholinv.other.json").write_text(json.dumps(
        {"config": "cholinv-other", "traffic": "fresh_operand",
         "driver": "dense_factor", "chips": 1, "why": "a later PR's cell",
         "limits": {"R_gap": 0.02, "Rinv_gap": 0.02}}))
    (tmp_path / "metrics" / "factors_done.py").write_text(
        "def read(r):\n    return float(r.counters['factors'])\n")
    spec = json.load(open(tiny.spec_path))
    spec["workloads"].append({"name": "cholinv.other"})
    spec["per_layer"].append({
        "name": "factors_done", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "models",
        "moves": "factor_tflops", "workloads": ["cholinv.other"]})
    for m in spec["end_to_end"]:
        if m["name"] == "factor_tflops":
            m["workloads"].append("cholinv.other")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    before = {p: os.path.getmtime(p) for p in glob.glob(
        os.path.join(common.HERE, "**", "*"), recursive=True)
        if os.path.isfile(p) and ".cache" not in p}
    cat = common.Catalog(roots=[tmp_path] + list(tiny.roots[:-1]),
                         spec_path=str(tmp_path / "BENCHMARK.json"))
    line = run_cell(cat, "cholinv.other", trace=1)
    assert line["correct"]
    assert line["metrics"]["factors_done"]["value"] >= 1
    line = run_cell(cat, "cholinv.other", trace=0)
    assert set(line["metrics"]) == {"setup_s", "factor_tflops"}
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before


def test_trace_0_starts_no_profiler(tiny, monkeypatch):
    import jax

    def refuse(*a, **k):
        raise AssertionError("the profiler started in a --trace 0 run")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(jax.profiler, "trace", refuse)
    line = run_cell(tiny, "cholinv.tiny", trace=0)
    assert line["correct"] and "busy_s" not in line["device"]
    assert list(line)[-1] == "checks"
