"""PR 16 observability tests: per-request span tracing (obs/spans.py),
rolling-window live telemetry (serve/telemetry.py), their ledger
validators and CLI gates, the reservoir-capped stats populations, and the
nearest-rank percentile edge cases.

The acceptance properties of ISSUE 16 / docs/OBSERVABILITY.md
"Per-request tracing and live windows" are asserted directly:

* **complete chains** — every request a traced engine admits exports a
  span chain that `trace_dict_problems` accepts, for all three kinds
  (batched / oversize-single / failed), and the in-run verdicts equal the
  ledger validator's recount (TestEngineTraceIntegration,
  TestLedgerValidators);
* **deadline attribution** — a violated request reports
  slack_at_dispatch_ms and names the span that ate the budget
  (TestSpanChains, TestEngineTraceIntegration);
* **loud-when-dead gates** — `obs serve-report --min-trace-complete /
  --min-windows` and `obs timeline` fail on ledgers with no trace/window
  records, exit 2 on malformed ones (TestServeReportTraceGates);
* **honest degradation** — a reservoir-capped sample population marks
  its snapshot and merge_snapshots refuses to pool the subsample,
  degrading to the elementwise worst-tail max (TestReservoirAndMerge).

Window tests drive the aggregator with an injected fake clock so window
boundaries are exact, not wall-time races.
"""

import time

import numpy as np
import pytest

from capital_tpu.serve.stats import percentiles
from capital_tpu.obs import __main__ as obs_main
from capital_tpu.obs import ledger, spans
from capital_tpu.serve import ServeConfig, SolveEngine, telemetry
from capital_tpu.serve import stats as serve_stats


# ---------------------------------------------------------------------------
# helpers: synthetic traces with explicit timestamps (no wall clock)
# ---------------------------------------------------------------------------


def _mk_trace(rid=0, op="posv", kind="batched", t0=100.0, dur_s=0.001,
              deadline_ms=None, **tags):
    """A complete chain of `kind` with uniform span durations, stamped at
    explicit span-clock offsets."""
    tr = spans.RequestTrace(rid, op, t0, deadline_ms=deadline_ms, **tags)
    tr.kind = kind
    t = t0
    for name in spans.REQUIRED[kind]:
        t += dur_s
        tr.extend(name, t)
    return tr


def _spd(rng, n, dtype=np.float32):
    M = rng.standard_normal((n, n))
    return (M @ M.T / n + 3.0 * np.eye(n)).astype(dtype)


def _ecfg(**kw):
    kw.setdefault("buckets", (8,))
    kw.setdefault("rows_buckets", (32,))
    kw.setdefault("nrhs_buckets", (1,))
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_delay_s", 10.0)
    kw.setdefault("small_n_impl", "pallas")
    return ServeConfig(**kw)


# ---------------------------------------------------------------------------
# spans: chain validation, derived deadline signals, export round-trip
# ---------------------------------------------------------------------------


class TestSpanChains:
    @pytest.mark.parametrize("kind", ["batched", "single", "failed"])
    def test_required_chain_is_complete(self, kind):
        tr = _mk_trace(kind=kind)
        assert tr.problems() == []
        assert tr.complete()

    def test_refine_is_optional_everywhere(self):
        tr = spans.RequestTrace(1, "posv", 100.0)
        t = 100.0
        for name in ("admit", "enqueue", "cache_lookup", "batch_form",
                     "device", "refine", "respond"):
            t += 0.001
            tr.extend(name, t)
        assert tr.problems() == []

    def test_missing_span_is_incomplete(self):
        tr = spans.RequestTrace(1, "posv", 100.0)
        t = 100.0
        for name in ("admit", "enqueue", "device", "respond"):  # no lookup
            t += 0.001
            tr.extend(name, t)
        probs = tr.problems()
        assert any("incomplete chain" in p for p in probs)

    def test_out_of_order_names_rejected(self):
        tr = spans.RequestTrace(1, "posv", 100.0)
        tr.extend("device", 100.001)
        tr.extend("admit", 100.002)
        assert any("out of chain order" in p for p in tr.problems())

    def test_unknown_span_name_rejected(self):
        tr = spans.RequestTrace(1, "posv", 100.0)
        tr.extend("teleport", 100.001)
        assert any("unknown span name" in p for p in tr.problems())

    def test_empty_chain_rejected(self):
        tr = spans.RequestTrace(1, "posv", 100.0)
        assert any("empty span chain" in p for p in tr.problems())

    def test_bubble_gap_beyond_tolerance(self):
        tr = _mk_trace()
        # re-stamp the device span 100ms after batch_form ended
        names = [sp.name for sp in tr.spans]
        i = names.index("device")
        sp = tr.spans[i]
        tr.spans[i] = spans.Span("device", sp.t_start + 0.1, sp.t_end + 0.2)
        for later in range(i + 1, len(tr.spans)):
            old = tr.spans[later]
            tr.spans[later] = spans.Span(old.name, old.t_start + 0.2,
                                         old.t_end + 0.2)
        assert any("bubble tolerance" in p for p in tr.problems(25.0))
        # a generous tolerance absorbs the same gap
        assert tr.problems(bubble_tol_ms=500.0) == []

    def test_overlapping_spans_rejected(self):
        tr = spans.RequestTrace(1, "posv", 100.0)
        tr.span("admit", 100.0, 100.010)
        tr.span("device", 100.002, 100.020)  # starts inside admit
        tr.span("respond", 100.020, 100.021)
        assert any("non-monotonic" in p for p in tr.problems())

    def test_negative_duration_rejected(self):
        tr = spans.RequestTrace(1, "posv", 100.0)
        tr.span("admit", 100.010, 100.001)
        assert any("ends before it starts" in p for p in tr.problems())

    def test_latency_and_slack(self):
        tr = _mk_trace(kind="batched", dur_s=0.002, deadline_ms=50.0)
        # 6 required spans x 2ms
        assert tr.latency_ms == pytest.approx(12.0, abs=1e-6)
        # device starts after admit/enqueue/cache_lookup/batch_form = 8ms
        assert tr.slack_at_dispatch_ms == pytest.approx(42.0, abs=1e-6)
        assert not tr.violated and tr.attribution is None

    def test_violation_attributes_longest_span(self):
        tr = spans.RequestTrace(7, "posv", 100.0, deadline_ms=5.0)
        t = 100.0
        for name, d in [("admit", 0.001), ("enqueue", 0.001),
                        ("cache_lookup", 0.001), ("batch_form", 0.001),
                        ("device", 0.020), ("respond", 0.001)]:
            t += d
            tr.extend(name, t)
        assert tr.violated
        assert tr.attribution == "device"
        assert tr.slack_at_dispatch_ms == pytest.approx(1.0, abs=1e-6)

    def test_no_deadline_means_no_slack_no_violation(self):
        tr = _mk_trace()
        assert tr.slack_at_dispatch_ms is None
        assert not tr.violated

    def test_asdict_roundtrips_through_dict_validator(self):
        tr = _mk_trace(rid=3, deadline_ms=1000.0, bucket="posv/f32/n8",
                       tier="balanced", replica_id="r0", cfg_hash="abc")
        d = tr.asdict()
        assert spans.trace_dict_problems(d) == []
        assert d["bucket"] == "posv/f32/n8" and d["replica_id"] == "r0"
        assert d["violated"] is False

    def test_dict_validator_catches_corruption(self):
        d = _mk_trace().asdict()
        bad = dict(d, request_id="nope")
        assert any("request_id" in p
                   for p in spans.trace_dict_problems(bad))
        bad = dict(d, spans="nope")
        assert any("not a list" in p
                   for p in spans.trace_dict_problems(bad))
        bad = dict(d, spans=[dict(d["spans"][0], dur_ms=-1.0)]
                   + d["spans"][1:])
        assert any("negative duration" in p
                   for p in spans.trace_dict_problems(bad))
        bad = dict(d, spans=[dict(d["spans"][0], t_start_s="x")]
                   + d["spans"][1:])
        assert any("non-numeric" in p
                   for p in spans.trace_dict_problems(bad))


class TestTraceLog:
    def test_cap_drops_oldest_visibly(self):
        log = spans.TraceLog(cap=3)
        for i in range(5):
            log.start(i, "posv", 100.0 + i)
        assert len(log) == 3 and log.total == 5 and log.dropped == 2
        ids = [t["request_id"] for t in log.trace_dicts()]
        assert ids == [2, 3, 4]  # oldest two gone

    def test_cap_must_be_positive(self):
        with pytest.raises(ValueError):
            spans.TraceLog(cap=0)

    def test_block_counts_complete_and_violations(self):
        log = spans.TraceLog()
        log.add(_mk_trace(rid=0).asdict())  # complete
        log.add(_mk_trace(rid=1, deadline_ms=0.5).asdict())  # violated
        incomplete = spans.RequestTrace(2, "posv", 100.0)
        incomplete.extend("admit", 100.001)
        log.add(incomplete.asdict())  # batched kind missing most spans
        blk = log.block()
        assert blk["requests"] == 3
        assert blk["complete"] == 2
        assert blk["violations"] == 1
        assert blk["dropped"] == 0
        assert ledger.validate_serve_trace(blk) == []

    def test_emit_appends_valid_record(self, tmp_path):
        p = tmp_path / "t.jsonl"
        log = spans.TraceLog()
        log.add(_mk_trace().asdict())
        rec = log.emit(str(p))
        assert rec["kind"] == "serve:trace"
        assert ledger.validate_serve_trace(rec["serve_trace"]) == []
        assert len(ledger.read(str(p))) == 1


# ---------------------------------------------------------------------------
# telemetry: rolling windows on an injected clock
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class TestWindowAggregator:
    def test_rejects_bad_params(self):
        with pytest.raises(ValueError):
            telemetry.WindowAggregator(0.0)
        with pytest.raises(ValueError):
            telemetry.WindowAggregator(1.0, sample_cap=0)

    def test_windows_close_on_the_clock(self):
        clk = _FakeClock()
        agg = telemetry.WindowAggregator(1.0, clock=clk)
        for i in range(4):
            agg.note_request("posv", 0.002, bucket="b8")
        clk.t += 1.5  # past the first window's end
        agg.note_request("inv", 0.004)
        assert len(agg.windows()) == 1  # first window closed
        agg.flush()
        ws = agg.windows()
        assert len(ws) == 2
        w0, w1 = ws
        assert w0["requests"] == 4 and w0["ok"] == 4
        assert w0["ops"] == {"posv": 4}
        assert w1["requests"] == 1 and w1["ops"] == {"inv": 1}
        # closed-window end is clamped to the window boundary
        assert w0["t_end_s"] - w0["t_start_s"] == pytest.approx(1.0)

    def test_window_internal_coherence(self):
        clk = _FakeClock()
        agg = telemetry.WindowAggregator(1.0, clock=clk)
        rng = np.random.default_rng(0)
        for lat in rng.uniform(0.001, 0.2, size=40):
            agg.note_request("posv", float(lat), bucket="b8")
        agg.note_request("posv", 0.01, ok=False, failed=True)
        agg.note_request("posv", None, shed=True, bucket="b8")
        agg.note_batch(0.75, bucket="b8")
        agg.note_queue_depth(5)
        agg.flush()
        (w,) = agg.windows()
        assert ledger.validate_serve_window(w) == []
        assert w["requests"] == 42 and w["ok"] == 40
        assert w["failed"] == 1 and w["shed"] == 1
        assert sum(w["hist_ms"]["counts"]) == 41  # shed carries no latency
        lat = w["latency_ms"]
        assert lat["p50"] <= lat["p95"] <= lat["p99"]
        assert w["queue_depth_max"] == 5 and w["batches"] == 1
        assert w["per_bucket"]["b8"]["shed"] == 1
        assert w["per_bucket"]["b8"]["occupancy_mean"] == pytest.approx(0.75)

    def test_empty_windows_are_skipped(self):
        clk = _FakeClock()
        agg = telemetry.WindowAggregator(0.5, clock=clk)
        agg.note_request("posv", 0.001)
        clk.t += 10.0  # nine idle windows elapse
        agg.note_request("posv", 0.001)
        agg.flush()
        assert len(agg.windows()) == 2  # only the two with traffic

    def test_batches_only_window_is_valid(self):
        clk = _FakeClock()
        agg = telemetry.WindowAggregator(1.0, clock=clk)
        agg.note_batch(0.5, bucket="b8")  # dispatch; requests land later
        agg.flush()
        (w,) = agg.windows()
        assert w["requests"] == 0 and w["batches"] == 1
        assert ledger.validate_serve_window(w) == []

    def test_sample_cap_marks_window_honestly(self):
        clk = _FakeClock()
        agg = telemetry.WindowAggregator(1.0, sample_cap=8, clock=clk)
        for i in range(50):
            agg.note_request("posv", 0.001 * (i + 1))
        agg.flush()
        (w,) = agg.windows()
        assert w["samples_capped"] is True and w["sampled"] == 8
        assert sum(w["hist_ms"]["counts"]) == 50  # hist stays exact
        assert ledger.validate_serve_window(w) == []

    def test_emit_is_incremental(self, tmp_path):
        p = tmp_path / "w.jsonl"
        clk = _FakeClock()
        agg = telemetry.WindowAggregator(1.0, clock=clk)
        agg.note_request("posv", 0.001)
        clk.t += 1.5
        agg.note_request("posv", 0.001)
        recs1 = agg.emit(str(p))
        assert len(recs1) == 2
        clk.t += 1.5
        agg.note_request("posv", 0.001)
        recs2 = agg.emit(str(p))
        assert len(recs2) == 1  # only the fresh window
        rows = ledger.read(str(p))
        assert len(rows) == 3
        assert all(r["kind"] == "serve:window" for r in rows)
        assert all(ledger.validate_serve_window(r["serve_window"]) == []
                   for r in rows)


# ---------------------------------------------------------------------------
# ledger: the serve_trace / serve_window validators and diff's posture
# ---------------------------------------------------------------------------


class TestLedgerValidators:
    def _trace_block(self):
        log = spans.TraceLog()
        log.add(_mk_trace(rid=0).asdict())
        log.add(_mk_trace(rid=1, deadline_ms=0.5).asdict())
        return log.block()

    def _window_block(self):
        clk = _FakeClock()
        agg = telemetry.WindowAggregator(1.0, clock=clk)
        agg.note_request("posv", 0.002, bucket="b8")
        agg.note_batch(0.5, bucket="b8")
        agg.flush()
        return agg.windows()[0]

    def test_valid_blocks_pass(self):
        assert ledger.validate_serve_trace(self._trace_block()) == []
        assert ledger.validate_serve_window(self._window_block()) == []

    def test_trace_complete_recount_disagreement(self):
        blk = dict(self._trace_block(), complete=999)
        assert any("disagrees with recount" in p
                   for p in ledger.validate_serve_trace(blk))

    def test_trace_violations_recount_disagreement(self):
        blk = dict(self._trace_block(), violations=0)
        assert any("violations" in p
                   for p in ledger.validate_serve_trace(blk))

    def test_trace_count_and_type_checks(self):
        blk = dict(self._trace_block(), requests=99)
        assert any("requests" in p
                   for p in ledger.validate_serve_trace(blk))
        blk = dict(self._trace_block(), dropped=-1)
        assert ledger.validate_serve_trace(blk)
        blk = dict(self._trace_block(), traces="nope")
        assert ledger.validate_serve_trace(blk)

    def test_incomplete_chain_is_data_not_schema_problem(self):
        # an honest trace block whose chain is incomplete must VALIDATE —
        # completeness is the serve-report gate's job, not diff's
        tr = spans.RequestTrace(0, "posv", 100.0)
        tr.extend("admit", 100.001)
        blk = spans.build_block([tr.asdict()])
        assert blk["complete"] == 0
        assert ledger.validate_serve_trace(blk) == []

    def test_window_percentile_order_enforced(self):
        blk = dict(self._window_block())
        blk["latency_ms"] = {"p50": 10.0, "p95": 5.0, "p99": 20.0}
        assert any("p50" in p or "order" in p
                   for p in ledger.validate_serve_window(blk))

    def test_window_count_identity_enforced(self):
        blk = dict(self._window_block(), shed=7)
        assert any("requests" in p
                   for p in ledger.validate_serve_window(blk))

    def test_window_hist_shape_enforced(self):
        blk = dict(self._window_block())
        h = dict(blk["hist_ms"])
        h["counts"] = h["counts"][:-1]
        blk["hist_ms"] = h
        assert ledger.validate_serve_window(blk)

    def test_window_occupancy_range_enforced(self):
        blk = dict(self._window_block(), occupancy_mean=1.5)
        assert ledger.validate_serve_window(blk)

    def test_diff_exempts_but_validates(self, tmp_path):
        trec = ledger.record("serve:trace", ledger.manifest(),
                             serve_trace=self._trace_block())
        wrec = ledger.record("serve:window", ledger.manifest(),
                             serve_window=self._window_block())
        regs = ledger.diff([trec, wrec], [trec, wrec])
        assert regs == []
        bad = dict(trec, serve_trace=dict(self._trace_block(),
                                          complete=999))
        with pytest.raises(ledger.LedgerIncompatible,
                           match="malformed serve_trace"):
            ledger.diff([bad], [bad])
        badw = dict(wrec, serve_window=dict(self._window_block(), shed=7))
        with pytest.raises(ledger.LedgerIncompatible,
                           match="malformed serve_window"):
            ledger.diff([badw], [badw])


# ---------------------------------------------------------------------------
# engine integration: real traced requests end to end
# ---------------------------------------------------------------------------


class TestEngineTraceIntegration:
    def test_batched_requests_trace_completely(self, tmp_path):
        eng = SolveEngine(cfg=_ecfg())
        rng = np.random.default_rng(0)
        tickets = [eng.submit("posv", _spd(rng, 8),
                              rng.standard_normal((8, 1)).astype(np.float32))
                   for _ in range(4)]
        eng.drain()
        assert all(t.result().ok for t in tickets)
        rec = eng.emit_trace(str(tmp_path / "t.jsonl"))
        st = rec["serve_trace"]
        assert st["requests"] == 4
        assert st["complete"] == 4, [
            p for t in st["traces"]
            for p in spans.trace_dict_problems(t)]
        assert st["violations"] == 0 and st["dropped"] == 0
        for t in st["traces"]:
            assert t["kind"] == "batched"
            assert t["bucket"] and t["cfg_hash"]
            assert t["tier"] == "balanced"
        assert ledger.validate_serve_trace(st) == []

    def test_oversize_single_and_failed_kinds(self):
        # oversize with models fallback -> "single"; with reject -> "failed"
        rng = np.random.default_rng(1)
        A = _spd(rng, 12, np.float64).astype(np.float32)
        B = rng.standard_normal((12, 1)).astype(np.float32)

        eng = SolveEngine(cfg=_ecfg(oversize="models"))
        assert eng.solve("posv", A, B).ok
        (tr,) = eng.emit_trace()["serve_trace"]["traces"]
        assert tr["kind"] == "single"
        assert spans.trace_dict_problems(tr) == []

        eng = SolveEngine(cfg=_ecfg(oversize="reject"))
        assert not eng.solve("posv", A, B).ok
        st = eng.emit_trace()["serve_trace"]
        (tr,) = st["traces"]
        assert tr["kind"] == "failed"
        assert st["complete"] == 1  # failed chains still validate

    def test_deadline_violation_attributed(self, tmp_path):
        eng = SolveEngine(cfg=_ecfg())
        rng = np.random.default_rng(2)
        args = (_spd(rng, 8), rng.standard_normal((8, 1)).astype(np.float32))
        assert eng.solve("posv", *args, deadline_ms=1e-4).ok  # late, landed
        assert eng.solve("posv", *args, deadline_ms=60000.0).ok
        st = eng.emit_trace()["serve_trace"]
        assert st["violations"] == 1
        late, met = st["traces"]
        assert late["violated"] and late["attribution"] in spans.CHAIN
        assert late["slack_at_dispatch_ms"] < 0  # doomed before dispatch
        assert not met["violated"] and met["attribution"] is None
        assert met["slack_at_dispatch_ms"] > 0

    def test_telemetry_windows_from_real_traffic(self, tmp_path):
        p = tmp_path / "w.jsonl"
        eng = SolveEngine(cfg=_ecfg())
        agg = eng.enable_telemetry(window_s=60.0)
        rng = np.random.default_rng(3)
        for _ in range(5):
            assert eng.solve(
                "posv", _spd(rng, 8),
                rng.standard_normal((8, 1)).astype(np.float32)).ok
        recs = agg.emit(str(p))
        assert len(recs) >= 1
        total = sum(r["serve_window"]["requests"] for r in recs)
        assert total == 5
        assert all(ledger.validate_serve_window(r["serve_window"]) == []
                   for r in recs)
        assert sum(r["serve_window"]["batches"] for r in recs) >= 1


class TestRouterTraceRoundtrip:
    def test_replica_traces_ride_back_tagged(self, tmp_path):
        from capital_tpu.serve.replica import ThreadReplica
        from capital_tpu.serve.router import Router, RouterConfig

        p = tmp_path / "r.jsonl"
        r = Router(RouterConfig())
        r.add_replica(ThreadReplica("r0", _ecfg(max_delay_s=0.005)))
        r.start()
        try:
            rng = np.random.default_rng(4)
            A = _spd(rng, 8)
            B = rng.standard_normal((8, 1)).astype(np.float32)
            tks = [r.submit("posv", A, B) for _ in range(3)]
            deadline = time.monotonic() + 60.0
            while not all(t.done for t in tks):
                r.pump()
                if time.monotonic() > deadline:
                    raise TimeoutError("tickets never landed")
                time.sleep(1e-3)
            assert all(t.result().ok for t in tks)
            srecs = r.emit_stats(str(p))
            trec = r.emit_trace(str(p))
        finally:
            r.stop()
        # emit_stats stays pure request_stats (its consumers iterate it)
        assert all(x.get("request_stats") for x in srecs)
        st = trec["serve_trace"]
        assert st["requests"] == 3 and st["complete"] == 3
        assert all(t["replica_id"] == "r0" for t in st["traces"])
        assert ledger.validate_serve_trace(st) == []


# ---------------------------------------------------------------------------
# CLI gates: serve-report trace/window gates and the timeline tool
# ---------------------------------------------------------------------------


class TestServeReportTraceGates:
    def _write(self, path, n_traces=2, n_windows=3, complete=True):
        log = spans.TraceLog()
        for i in range(n_traces):
            if complete:
                log.add(_mk_trace(rid=i).asdict())
            else:
                tr = spans.RequestTrace(i, "posv", 100.0)
                tr.extend("admit", 100.001)
                log.add(tr.asdict())
        if n_traces:
            log.emit(str(path))
        clk = _FakeClock()
        agg = telemetry.WindowAggregator(1.0, clock=clk)
        for _ in range(n_windows):
            agg.note_request("posv", 0.002)
            clk.t += 1.5
        agg.emit(str(path))

    def test_gates_pass_on_healthy_ledger(self, tmp_path, capsys):
        p = tmp_path / "l.jsonl"
        self._write(p)
        rc = obs_main.main(["serve-report", str(p),
                            "--min-trace-complete", "1.0",
                            "--min-windows", "3"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "serve_trace" in out and "serve_window" in out

    def test_trace_gate_fails_loudly_without_records(self, tmp_path):
        p = tmp_path / "l.jsonl"
        self._write(p, n_traces=0, n_windows=1)
        assert obs_main.main(["serve-report", str(p),
                              "--min-trace-complete", "1.0"]) == 1

    def test_trace_gate_fails_on_incomplete_chains(self, tmp_path):
        p = tmp_path / "l.jsonl"
        self._write(p, complete=False)
        assert obs_main.main(["serve-report", str(p),
                              "--min-trace-complete", "1.0"]) == 1

    def test_window_gate_fails_short(self, tmp_path):
        p = tmp_path / "l.jsonl"
        self._write(p, n_windows=2)
        assert obs_main.main(["serve-report", str(p),
                              "--min-windows", "3"]) == 1

    def test_malformed_trace_record_exits_2(self, tmp_path):
        p = tmp_path / "l.jsonl"
        log = spans.TraceLog()
        log.add(_mk_trace().asdict())
        rec = log.emit()
        rec["serve_trace"]["complete"] = 999
        ledger.append(str(p), rec)
        assert obs_main.main(["serve-report", str(p)]) == 2

    def test_timeline_summary(self, tmp_path, capsys):
        p = tmp_path / "l.jsonl"
        self._write(p)
        rc = obs_main.main(["timeline", str(p)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "timeline OK" in out
        assert "admit" in out  # the per-span split is rendered

    def test_timeline_fails_loudly_without_traces(self, tmp_path):
        p = tmp_path / "l.jsonl"
        self._write(p, n_traces=0, n_windows=1)
        assert obs_main.main(["timeline", str(p)]) == 1


class TestServeReportAggregateNaming:
    def test_hit_rate_failure_names_the_replica(self, tmp_path, capsys):
        # r0's cache went cold (hit_rate 0.5); the fleet message must say
        # so instead of reporting only the anonymous merged number
        p = tmp_path / "l.jsonl"
        snaps = []
        for rid, (h, m) in [("r0", (1, 1)), ("r1", (4, 0))]:
            c = serve_stats.Collector(replica_id=rid)
            c.record_request("posv", 0.01, ok=True)
            cache = {"hits": h, "misses": m, "warmup_compiles": 0,
                     "hit_rate": h / (h + m)}
            snaps.append(c.snapshot(cache, samples=True))
            clean = {k: v for k, v in snaps[-1].items() if k != "samples"}
            ledger.append(str(p), ledger.record(
                "serve:request_stats", ledger.manifest(),
                request_stats=clean))
        ledger.append(str(p), ledger.record(
            "serve:request_stats", ledger.manifest(),
            request_stats=serve_stats.merge_snapshots(snaps)))
        rc = obs_main.main(["serve-report", str(p), "--aggregate",
                            "--min-hit-rate", "0.9"])
        captured = capsys.readouterr()
        text = captured.out + captured.err
        assert rc == 1
        assert "r0" in text and "offending" in text
        assert "r0=0.500" in text and "r1=1.000" in text


# ---------------------------------------------------------------------------
# stats: reservoir capping and the merge's honest degradation
# ---------------------------------------------------------------------------


class TestReservoirAndMerge:
    def test_under_cap_is_exact(self):
        r = serve_stats.Reservoir(cap=10)
        for v in range(5):
            r.append(float(v))
        assert list(r) == [0.0, 1.0, 2.0, 3.0, 4.0]
        assert not r.capped and r.count == 5

    def test_over_cap_bounds_memory_and_marks(self):
        r = serve_stats.Reservoir(cap=16)
        for v in range(1000):
            r.append(float(v))
        assert len(r) == 16 and r.count == 1000 and r.capped
        assert set(r) <= {float(v) for v in range(1000)}

    def test_deterministic_across_instances(self):
        a, b = serve_stats.Reservoir(cap=8), serve_stats.Reservoir(cap=8)
        for v in range(100):
            a.append(float(v))
            b.append(float(v))
        assert list(a) == list(b)

    def test_bad_cap_rejected(self):
        with pytest.raises(ValueError):
            serve_stats.Reservoir(cap=0)

    def test_collector_snapshot_marks_capped_populations(self):
        c = serve_stats.Collector(sample_cap=4)
        for i in range(10):
            c.record_request("posv", 0.001 * (i + 1), ok=True)
        snap = c.snapshot(samples=True)
        assert snap["samples_capped"] is True
        assert len(snap["samples"]["latency_s"]) == 4
        # an uncapped collector carries no marker at all (schema unchanged)
        c2 = serve_stats.Collector()
        c2.record_request("posv", 0.001, ok=True)
        assert "samples_capped" not in c2.snapshot(samples=True)

    def test_merge_pools_exact_when_uncapped(self):
        snaps = []
        pool = []
        for rid, lats in [("r0", [0.001, 0.002]), ("r1", [0.010, 0.020])]:
            c = serve_stats.Collector(replica_id=rid)
            for v in lats:
                c.record_request("posv", v, ok=True)
                pool.append(v * 1e3)
            snaps.append(c.snapshot(samples=True))
        merged = serve_stats.merge_snapshots(snaps)
        expect = {k: round(v, 4) for k, v in percentiles(pool).items()}
        assert merged["latency_ms"] == expect

    def test_merge_degrades_to_worst_tail_when_capped(self):
        # r0's population outgrew its reservoir: its samples are a uniform
        # subsample, so pooling them would bias the union's tail — the
        # merge must fall back to the elementwise max instead
        c0 = serve_stats.Collector(replica_id="r0", sample_cap=4)
        for i in range(50):
            c0.record_request("posv", 0.001 * (i + 1), ok=True)
        c1 = serve_stats.Collector(replica_id="r1")
        for v in [0.002, 0.004]:
            c1.record_request("posv", v, ok=True)
        s0 = c0.snapshot(samples=True)
        s1 = c1.snapshot(samples=True)
        merged = serve_stats.merge_snapshots([s0, s1])
        for p in ("p50", "p95", "p99"):
            assert merged["latency_ms"][p] == max(
                s0["latency_ms"][p], s1["latency_ms"][p])


# ---------------------------------------------------------------------------
# serve/stats.percentiles: nearest-rank on tiny samples
# ---------------------------------------------------------------------------


class TestPercentilesTinySamples:
    def test_single_sample_is_every_percentile(self):
        assert percentiles([7.0]) == {"p50": 7.0, "p95": 7.0, "p99": 7.0}

    def test_two_samples_nearest_rank(self):
        # rank = ceil(p/100 * 2): p50 -> rank 1 (the min), p95/p99 -> rank 2
        got = percentiles([3.0, 9.0])
        assert got == {"p50": 3.0, "p95": 9.0, "p99": 9.0}
        assert percentiles([9.0, 3.0]) == got  # order-independent

    def test_all_equal_samples(self):
        assert percentiles([5.0] * 17) == {"p50": 5.0, "p95": 5.0,
                                           "p99": 5.0}

    def test_empty_rejected(self):
        with pytest.raises(ValueError):
            percentiles([])

    def test_reported_values_are_actual_samples(self):
        rng = np.random.default_rng(5)
        s = list(rng.uniform(0, 1, size=13))
        got = percentiles(s)
        assert all(v in s for v in got.values())
