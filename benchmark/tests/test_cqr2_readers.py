"""The readers of the cacqr cell's per-layer metrics: each on a synthetic
reduced trace with known bucket seconds and counters against its value
computed by hand, None where its phase or counter is absent, and all of
them in a traced run of the small CholeskyQR2 cells on four CPU devices."""

import json

import pytest

import common
import cqr2_work
import peaks
import trace_reduce as tr
from conftest import DATA, run_cell

M, N = 2097152, 1024  # configs/cacqr-tall.json
FACTORS = 200
V5E = peaks.PEAKS["TPU v5 lite"]
NEW = ("collective_share.cacqr", "chol_share.cacqr", "gram_roofline.cacqr",
       "scale_gram_roofline.cacqr", "scale_roofline.cacqr",
       "cqr2_fallbacks.cacqr")
APPENDED = ("device_idle.factor", "factor_roofline", "builds_in_window.factor")


class _Reading:
    def __init__(self, trace, peak=V5E, counters=None):
        self.trace, self.chips, self.peak = trace, 4, peak
        self.counters = {"factors": FACTORS} if counters is None else counters


def _trace(**own):
    """Four chips, each busy 5 s of a 5.5 s window; `own` gives each
    bucket's own seconds on the four chips."""
    devs = [tr.Device(busy_s=5.0, own_s={b: s[i] for b, s in own.items()})
            for i in range(4)]
    return tr.Reduced(window_s=5.5, devices=devs, idle_gaps=[])


CQR = _trace(**{"CQR::gram": (1.2, 0.8, 1.0, 1.0),
                "CQR::fused": (2.0, 2.0, 2.0, 2.0),
                "CQR::formR": (1.0, 1.1, 0.9, 1.0),
                "CQR::chol": (0.5, 0.5, 0.5, 0.5),
                "collective": (0.25, 0.3, 0.2, 0.25)})


def _read(metric, reading):
    return common.Catalog().reader(metric).read(reading)


def test_work_of_the_three_passes():
    assert cqr2_work.work("CQR::gram") == (M * N * N, M * N * 2)
    assert cqr2_work.work("CQR::fused") == (2 * M * N * N, 2 * M * N * 2)
    assert cqr2_work.work("CQR::formR") == (M * N * N, 2 * M * N * 2)
    # the three passes are the tall part of the reference's count
    ref = common.Catalog().reference("cacqr-tall")
    cfg = common.Catalog().config("cacqr-tall")
    tall = sum(cqr2_work.work(p)[0] for p in cqr2_work.PASSES)
    assert ref.flops(cfg) == pytest.approx(tall + 2 * N**3 / 3, rel=1e-15)


@pytest.mark.parametrize("metric,flops,own_s", [
    # n/2 and n/4 flop/byte are both above v5e's 197e12/819e9: the peak bounds
    ("gram_roofline.cacqr", M * N * N, 1.0),
    ("scale_gram_roofline.cacqr", 2 * M * N * N, 2.0),
    ("scale_roofline.cacqr", M * N * N, 1.0),
])
def test_roofline_shares(metric, flops, own_s):
    want = 100 * FACTORS * flops / (4 * own_s * 197e12)
    assert _read(metric, _Reading(CQR)) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(55.81277, rel=1e-6)


def test_a_bandwidth_bound_pass():
    """At 600 GB/s the final scale (n/4 = 256 flop/byte) is bound by bytes,
    the two n/2 passes still by the peak."""
    slow = peaks.Peak(197e12, 600e9, 16e9)
    assert _read("scale_roofline.cacqr", _Reading(CQR, slow)) == \
        pytest.approx(100 * FACTORS * M * N * N / (4 * 1.0 * 600e9 * 256))
    assert _read("gram_roofline.cacqr", _Reading(CQR, slow)) == \
        pytest.approx(100 * FACTORS * M * N * N / (4 * 1.0 * 197e12))


def test_shares_of_busy_time():
    assert _read("chol_share.cacqr", _Reading(CQR)) == pytest.approx(10.0)
    assert _read("collective_share.cacqr", _Reading(CQR)) == \
        pytest.approx(100 * 0.25 / 5.0)


@pytest.mark.parametrize("metric", NEW[:5])
def test_absent_phase_reads_none(metric):
    other = _trace(**{"CI::inv": (1.0, 1.0, 1.0, 1.0)})
    assert _read(metric, _Reading(other)) is None


@pytest.mark.parametrize("metric", NEW[2:5])
def test_roofline_without_factors_reads_none(metric):
    assert _read(metric, _Reading(CQR, counters={})) is None
    assert _read(metric, _Reading(None)) is None


@pytest.fixture
def routes(monkeypatch):
    from capital_tpu.obs import spans

    fresh = spans.RouteCounter()
    monkeypatch.setattr(spans, "ROUTES", fresh)
    return fresh


def test_fallbacks_count_the_other_routes(routes):
    read = common.Catalog().reader("cqr2_fallbacks.cacqr").read
    assert read(None) == 0  # no qr.factor built
    routes.take("fused_sharded/full", rows=524288, g=8, bm=1024)
    assert read(None) == 0
    routes.take("sweeps_1d", rows=524288, g=2, bm=None)
    routes.take("sweeps_1d", rows=524288, g=2, bm=None)
    routes.take("fused/full", rows=2097152, g=8, bm=1024)
    assert read(None) == 3


def test_fallbacks_without_the_counter(monkeypatch):
    """An older program keeps no route counter: None, and nothing raises."""
    from capital_tpu.obs import spans

    monkeypatch.delattr(spans, "ROUTES")
    assert common.Catalog().reader("cqr2_fallbacks.cacqr").read(None) is None


@pytest.fixture
def cpu_peak(monkeypatch):
    """The v5e row under the CPU's device kind, so that the roofline readers
    have a peak to read in a CPU run (whose values mean nothing)."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", V5E)


@pytest.fixture(scope="module")
def cqr_cells(tiny, tmp_path_factory):
    """The tiny catalog, with the cacqr metrics also on the small cell that
    takes the fused route per shard (n = 512)."""
    spec = json.load(open(tiny.spec_path))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "cacqr.tiny.x4" in m.get("workloads", ()):
            m["workloads"].append("cacqr.fused-tiny.x4")
    path = tmp_path_factory.mktemp("cqr") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return common.Catalog(roots=[DATA], spec_path=str(path))


def test_traced_tiny_cell_reports_the_metrics(cqr_cells, routes, cpu_peak):
    """cacqr.tiny.x4 (n = 128) is too narrow for the fused kernels: its
    build takes the unfused sweeps, which the fallback counter sees, and
    which have no CQR::fused phase."""
    line = run_cell(cqr_cells, "cacqr.tiny.x4", trace=1)
    assert line["correct"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(NEW + APPENDED) - {"scale_gram_roofline.cacqr"}
    assert got["cqr2_fallbacks.cacqr"] == 1
    assert set(routes.snapshot()) == {"sweeps_1d"}


def test_traced_fused_tiny_cell_reports_every_metric(cqr_cells, routes,
                                                    cpu_peak):
    line = run_cell(cqr_cells, "cacqr.fused-tiny.x4", trace=1)
    assert line["correct"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(NEW + APPENDED)
    assert got["cqr2_fallbacks.cacqr"] == 0
    assert routes.snapshot() == {
        "fused_sharded/full": {"builds": 1, "rows": 1024, "g": 4, "bm": 1024}}
    for k in NEW[:5]:
        assert got[k] > 0, k
    line = run_cell(cqr_cells, "cacqr.fused-tiny.x4", trace=0)
    assert set(line["metrics"]) == {"setup_s", "factor_tflops"}
