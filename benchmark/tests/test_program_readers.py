"""The readers of the program's own spans (program_spans.py and the build
metrics), the unphased share and the per-kernel table on the recorded CPU
trace, and the readings that trace gave before them."""

import os

import pytest

import common
import kernel_ops
import peaks
import program_spans
import trace_reduce as tr
from conftest import DATA, run_cell

TRACE = os.path.join(DATA, "trace")
BUILD_READERS = ("build_trace_s.factor", "build_lower_s.factor",
                 "build_compile_s.factor", "builds_in_window.factor")
NEW = BUILD_READERS + ("unphased_share.cholinv",)
COUNTERS = {"window_flops": 3 * 2 * 512 * 256 * 256.0, "factors": 3,
            "occupancies": [0.5, 1.0, 0.25],
            "queue_waits_s": [0.001, 0.003, 0.002]}


class _Reading:
    def __init__(self, trace, counters=COUNTERS):
        self.trace, self.counters, self.chips = trace, counters, 1
        self.peak = peaks.PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def red():
    return tr.reduce(TRACE, spans=common.SPANS, select=tr.cpu_ops_line,
                     keep=tr.cpu_keep)


def _read(metric, red):
    return common.Catalog().reader(metric).read(_Reading(red))


@pytest.fixture
def log(monkeypatch):
    """A fresh span log in place of the process's, filled by the test."""
    from capital_tpu.obs import spans

    fresh = spans.SpanLog()
    monkeypatch.setattr(spans, "SPAN_LOG", fresh)
    return fresh


def _add(log, name, t0_s, t1_s, **tags):
    from capital_tpu.obs import spans

    log.add(spans.SpanRecord(len(log) + 1, name, int(t0_s * 1e9),
                             int(t1_s * 1e9), None, tags))


def test_existing_readings_unchanged(red):
    """What the recorded trace read before the program's spans existed."""
    assert red.window_s == pytest.approx(0.006214691, rel=1e-9)
    assert red.busy_s == pytest.approx(0.005214491, rel=1e-9)
    assert dict(red.top_ops()) == pytest.approx(
        {"collective": 0.005124325, "other": 0.003291882,
         "copy": 0.001311985}, rel=1e-9)
    assert red.idle_gaps[:3] == [("dispatch", pytest.approx(0.000291443)),
                                 ("block", pytest.approx(0.000202058)),
                                 ("block", pytest.approx(0.0001254))]
    want = {"device_idle.factor": 16.09412278100393,
            "device_idle.serve": 16.09412278100393,
            "factor_roofline": 0.01959850723568391,
            "collective_share.cacqr": 98.2708571172143,
            "batch_fill.serve": 58.333333333333336,
            "queue_wait_ms.serve": 2.0}
    for metric, v in want.items():
        assert _read(metric, red) == pytest.approx(v, rel=1e-12), metric
    assert _read("base_case_share.cholinv", red) is None


def test_unphased_share_on_recorded_trace(red):
    """No op of the recorded programs carries a phase: everything but the
    all-reduce (a bucket of its own) is unphased."""
    v = _read("unphased_share.cholinv", red)
    want = 100 * red.bucket_s(*("fusion", "copy", "custom-call", "other"))
    assert v == pytest.approx(want / red.busy_s)
    assert 0 < v < 100


def test_build_readers_take_the_union_before_the_window(red, log):
    # set-up: a traced program with a nested jit inside, its lowering and
    # one cache load; then a build-free stretch longer than the window;
    # then the reference's builds, which must not count
    _add(log, "build.trace", 100.0, 104.0, fun_name="step")
    _add(log, "build.trace", 101.0, 102.0, fun_name="inner")
    _add(log, "build.lower", 104.0, 105.5)
    _add(log, "build.compile", 105.5, 106.0, cache="load")
    _add(log, "build.trace", 200.0, 201.0, fun_name="reference")
    _add(log, "build.compile", 201.0, 203.0, cache="compile")
    assert red.window_s < 1.0
    assert _read("build_trace_s.factor", red) == pytest.approx(4.0)  # not 5
    assert _read("build_lower_s.factor", red) == pytest.approx(1.5)
    assert _read("build_compile_s.factor", red) == pytest.approx(0.5)
    assert _read("builds_in_window.factor", red) == 0


def test_a_build_while_profiling_bounds_the_window(red, log):
    _add(log, "build.trace", 10.0, 11.0)
    _add(log, "build.compile", 11.0, 11.5, cache="compile")
    # built inside the traced window: counted there, not in set-up
    _add(log, "build.trace", 11.6, 11.7, profiled=True)
    _add(log, "build.compile", 11.7, 11.9, cache="compile", profiled=True)
    _add(log, "build.compile", 12.0, 12.1, cache="load")
    assert program_spans.window_open_ns(log.records("build."), 1.0) == \
        pytest.approx(11.6e9)
    assert _read("build_trace_s.factor", red) == pytest.approx(1.0)
    assert _read("build_compile_s.factor", red) == pytest.approx(0.5)
    assert _read("builds_in_window.factor", red) == 1


def test_build_readers_without_a_span_log(red, monkeypatch):
    """A program that keeps no span log (an older checkout) reads nothing,
    and nothing raises."""
    from capital_tpu.obs import spans

    monkeypatch.delattr(spans, "SPAN_LOG")
    for metric in BUILD_READERS:
        assert _read(metric, red) is None, metric


def test_kernel_table(red):
    tags = ("CI.inv", "CI.tmu", "SV.fused_posv")
    assert kernel_ops.kernel_of("%CI.inv.trmm_left.12 = bf16[8] custom-call("
                                "%a)", tags) == "CI.inv.trmm_left"
    assert kernel_ops.kernel_of("SV.fused_posv.posv.3", tags) == \
        "SV.fused_posv.posv"
    # a scope-named custom call without a kernel part, and XLA's own ops
    assert kernel_ops.kernel_of("CI.tmu.7", tags) is None
    assert kernel_ops.kernel_of("fusion.3", tags) is None
    assert kernel_ops.kernel_of("custom-call.770", tags) is None
    # the recorded programs run no Pallas kernel
    assert kernel_ops.kernel_seconds(TRACE, tags, select=tr.cpu_ops_line,
                                     keep=tr.cpu_keep) == {}


def test_traced_cell_reports_the_program_metrics(tiny, log):
    line = run_cell(tiny, "cholinv.tiny", trace=1)  # `log` holds its spans
    assert line["correct"]
    got = {k: line["metrics"][k]["value"] for k in NEW}
    assert got["builds_in_window.factor"] == 0
    assert got["build_trace_s.factor"] > 0  # the factor was traced in set-up
    assert got["build_lower_s.factor"] > 0
    assert got["build_compile_s.factor"] > 0
    assert 0 <= got["unphased_share.cholinv"] <= 100
    kinds = {r.tags.get("cache") for r in log.records("build.compile")}
    assert kinds <= {"load", "compile"} and kinds


def test_trace_0_prints_only_the_end_to_end_metrics(tiny, monkeypatch):
    import jax

    def refuse(*a, **k):
        raise AssertionError("the profiler started in a --trace 0 run")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    line = run_cell(tiny, "cholinv.tiny", trace=0)
    assert set(line["metrics"]) == {"setup_s", "factor_tflops"}
