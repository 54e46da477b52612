"""Serving layer tests: potrs, identity-tail padding, bucketing, the
SolveEngine's AOT cache + flush policy + fault containment, and the
request_stats ledger/CLI seam.

The acceptance properties of ISSUE 4 / docs/SERVING.md are asserted
directly on the counters here:

* after warmup over >= 3 shape buckets, a 50-request mixed workload shows
  misses == 0 and hit_rate == 1.0 (TestEngineAcceptance);
* batched posv/lstsq match the unbatched models/ paths within dtype
  tolerance (TestEngineAcceptance, TestEngineResults);
* a fault-injected request comes back flagged with a RobustInfo while its
  batch neighbors and every subsequent request succeed (TestEngineFaults).

Everything runs on the conftest CPU rig (x64 on); engines default to a
1-device grid so the batched kernels compile fast, and models-path
comparisons reuse the same grid.
"""


import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import cholesky
from capital_tpu.obs import __main__ as obs_main
from capital_tpu.obs import ledger, spans
from capital_tpu.ops import lapack, masking
from capital_tpu.robust import faultinject
from capital_tpu.robust.config import RobustConfig, RobustInfo
from capital_tpu.serve import ServeConfig, SolveEngine, batching, stats
from capital_tpu.serve.stats import percentiles

# Small ladders so every executable compiles in well under a second; the
# huge max_delay_s means the deadline path only fires when a test passes an
# explicit `now` to pump() — flush timing stays deterministic.
CFG = ServeConfig(
    buckets=(8, 16, 32),
    rows_buckets=(32, 64, 128),
    nrhs_buckets=(1, 4),
    max_batch=3,
    max_delay_s=10.0,
)


def _spd(rng, n, dtype=np.float64):
    M = rng.standard_normal((n, n))
    return (M @ M.T / n + 3.0 * np.eye(n)).astype(dtype)


# ---------------------------------------------------------------------------
# ops/lapack.potrs + models/cholesky.solve (satellite a)
# ---------------------------------------------------------------------------


class TestPotrs:
    @pytest.mark.parametrize("uplo", ["U", "L"])
    def test_matches_dense_solve(self, uplo):
        rng = np.random.default_rng(0)
        A = _spd(rng, 24)
        B = rng.standard_normal((24, 3))
        C = np.linalg.cholesky(A)  # lower
        T = jnp.asarray(C if uplo == "L" else C.T)
        X = lapack.potrs(T, jnp.asarray(B), uplo=uplo)
        np.testing.assert_allclose(np.asarray(X), np.linalg.solve(A, B),
                                   rtol=0, atol=1e-11)

    def test_roundtrips_potrf(self):
        rng = np.random.default_rng(1)
        A = _spd(rng, 16)
        B = rng.standard_normal((16, 2))
        R = lapack.potrf(jnp.asarray(A), uplo="U")
        X = lapack.potrs(R, jnp.asarray(B), uplo="U")
        np.testing.assert_allclose(np.asarray(A @ X), B, rtol=0, atol=1e-11)

    def test_bad_uplo_rejected(self):
        with pytest.raises(ValueError, match="uplo"):
            lapack.potrs(jnp.eye(4), jnp.ones((4, 1)), uplo="X")


class TestCholeskySolve:
    def test_matches_numpy(self, grid2x2x1):
        rng = np.random.default_rng(2)
        A = _spd(rng, 32)
        B = rng.standard_normal((32, 4))
        X = cholesky.solve(grid2x2x1, jnp.asarray(A), jnp.asarray(B))
        np.testing.assert_allclose(np.asarray(X), np.linalg.solve(A, B),
                                   rtol=0, atol=1e-10)

    def test_robust_returns_info(self, grid2x2x1):
        rng = np.random.default_rng(3)
        A = _spd(rng, 16)
        B = rng.standard_normal((16, 1))
        cfg = cholesky.CholinvConfig(robust=RobustConfig())
        X, info = cholesky.solve(grid2x2x1, jnp.asarray(A), jnp.asarray(B),
                                 cfg)
        assert int(info) == 0
        np.testing.assert_allclose(np.asarray(X), np.linalg.solve(A, B),
                                   rtol=0, atol=1e-10)

    def test_shape_mismatch_rejected(self, grid2x2x1):
        with pytest.raises(ValueError, match="shape mismatch"):
            cholesky.solve(grid2x2x1, jnp.eye(8), jnp.ones((6, 1)))


# ---------------------------------------------------------------------------
# ops/masking.embed_identity_tail + serve/batching
# ---------------------------------------------------------------------------


class TestEmbedIdentityTail:
    def test_square_is_block_diag(self):
        rng = np.random.default_rng(4)
        A = _spd(rng, 5)
        P = np.asarray(masking.embed_identity_tail(jnp.asarray(A), 8, 8))
        np.testing.assert_array_equal(P[:5, :5], A)
        np.testing.assert_array_equal(P[5:, 5:], np.eye(3))
        np.testing.assert_array_equal(P[:5, 5:], 0)
        # stays SPD: Cholesky of diag(A, I) succeeds with finite entries
        assert np.all(np.isfinite(np.linalg.cholesky(P)))

    def test_tall_keeps_full_rank_gram(self):
        rng = np.random.default_rng(5)
        A = rng.standard_normal((12, 3))
        P = np.asarray(masking.embed_identity_tail(jnp.asarray(A), 16, 6))
        # unit columns live in the appended rows: gram is diag(AᵀA, I)
        G = P.T @ P
        np.testing.assert_allclose(G[:3, :3], A.T @ A, rtol=0, atol=1e-12)
        np.testing.assert_array_equal(G[3:, 3:], np.eye(3))
        np.testing.assert_array_equal(G[:3, 3:], 0)

    def test_noop_when_already_sized(self):
        A = jnp.ones((4, 4))
        assert masking.embed_identity_tail(A, 4, 4) is A

    def test_contract_violations_raise(self):
        A = jnp.ones((4, 2))
        with pytest.raises(ValueError):  # shrink
            masking.embed_identity_tail(A, 3, 2)
        with pytest.raises(ValueError):  # more new cols than new rows
            masking.embed_identity_tail(A, 5, 6)


class TestBucketing:
    def test_ladder_pick(self):
        b = batching.bucket_for("posv", (10, 10), (10, 2), "float64", CFG)
        assert b.a_shape == (16, 16) and b.b_shape == (16, 4)
        assert b.capacity == CFG.max_batch
        b = batching.bucket_for("inv", (8, 8), None, "float64", CFG)
        assert b.a_shape == (8, 8) and b.b_shape is None

    def test_lstsq_rows_include_column_pad(self):
        # m=30, n=10 -> nb=16; rows bucket at 30 + (16 - 10) = 36 -> 64
        b = batching.bucket_for("lstsq", (30, 10), (30, 1), "float64", CFG)
        assert b.a_shape == (64, 16) and b.b_shape == (64, 1)
        # contract holds: rows - m >= cols - n for the embed
        assert b.a_shape[0] - 30 >= b.a_shape[1] - 10

    def test_oversize_is_none(self):
        assert batching.bucket_for("posv", (40, 40), (40, 1), "float64",
                                   CFG) is None
        assert batching.bucket_for("lstsq", (200, 8), (200, 1), "float64",
                                   CFG) is None

    def test_unknown_op_rejected(self):
        with pytest.raises(ValueError, match="unknown serve op"):
            batching.bucket_for("gesv", (8, 8), (8, 1), "float64", CFG)

    def test_pad_assemble_crop_roundtrip(self):
        rng = np.random.default_rng(6)
        A = _spd(rng, 10)
        B = rng.standard_normal((10, 2))
        b = batching.bucket_for("posv", A.shape, B.shape, "float64", CFG)
        pa, pb = batching.pad_operands("posv", jnp.asarray(A),
                                       jnp.asarray(B), b)
        assert pa.shape == b.a_shape and pb.shape == b.b_shape
        Ab, Bb, occ = batching.assemble([pa], [pb], b)
        assert Ab.shape == (b.capacity,) + b.a_shape
        assert occ == pytest.approx(1 / b.capacity)
        # fill problems are benign identities against zero RHS
        np.testing.assert_array_equal(np.asarray(Ab[1]), np.eye(16))
        np.testing.assert_array_equal(np.asarray(Bb[1]), 0)
        # padded problem solves to the original solution + exact-zero tail
        Xp = np.linalg.solve(np.asarray(Ab[0]), np.asarray(Bb[0]))
        np.testing.assert_allclose(Xp[:10, :2], np.linalg.solve(A, B),
                                   rtol=0, atol=1e-12)
        np.testing.assert_array_equal(Xp[10:], 0)
        X = batching.crop("posv", jnp.asarray(Xp), A.shape, B.shape)
        assert X.shape == (10, 2)


# ---------------------------------------------------------------------------
# SolveEngine: results, cache, flush policy, faults
# ---------------------------------------------------------------------------


class TestEngineResults:
    def test_posv_matches_models_path(self, grid2x2x1):
        rng = np.random.default_rng(7)
        eng = SolveEngine(cfg=CFG)
        A = _spd(rng, 12)
        B = rng.standard_normal((12, 2))
        r = eng.solve("posv", A, B)
        assert r.ok and r.batched and r.bucket is not None
        ref = cholesky.solve(grid2x2x1, jnp.asarray(A), jnp.asarray(B))
        np.testing.assert_allclose(np.asarray(r.x), np.asarray(ref),
                                   rtol=0, atol=1e-10)

    def test_lstsq_matches_numpy(self):
        rng = np.random.default_rng(8)
        eng = SolveEngine(cfg=CFG)
        A = rng.standard_normal((40, 10))
        B = rng.standard_normal((40, 2))
        r = eng.solve("lstsq", A, B)
        assert r.ok and r.batched
        ref, *_ = np.linalg.lstsq(A, B, rcond=None)
        np.testing.assert_allclose(np.asarray(r.x), ref, rtol=0, atol=1e-9)

    def test_inv_matches_numpy(self):
        rng = np.random.default_rng(9)
        eng = SolveEngine(cfg=CFG)
        A = _spd(rng, 20)
        r = eng.solve("inv", A)
        assert r.ok and r.batched
        np.testing.assert_allclose(np.asarray(r.x), np.linalg.inv(A),
                                   rtol=0, atol=1e-10)

    def test_mixed_shapes_share_one_batch(self):
        # two different true shapes land in the SAME bucket and flush as one
        # batch, each cropping back to its own solution
        rng = np.random.default_rng(10)
        eng = SolveEngine(cfg=CFG)
        probs = [(_spd(rng, n), rng.standard_normal((n, 1))) for n in (9, 14)]
        tickets = [eng.submit("posv", A, B) for A, B in probs]
        assert eng.drain() == 1
        for (A, B), t in zip(probs, tickets):
            r = t.result()
            assert r.bucket[2] == (16, 16)
            np.testing.assert_allclose(np.asarray(r.x),
                                       np.linalg.solve(A, B),
                                       rtol=0, atol=1e-10)

    def test_submit_validation(self):
        eng = SolveEngine(cfg=CFG)
        with pytest.raises(ValueError, match="unknown serve op"):
            eng.submit("gesv", np.eye(4), np.ones((4, 1)))
        with pytest.raises(ValueError, match="RHS"):
            eng.submit("posv", np.eye(4), np.ones((3, 1)))
        with pytest.raises(ValueError, match="square"):
            eng.submit("inv", np.ones((4, 3)))
        with pytest.raises(ValueError, match="tall"):
            eng.submit("lstsq", np.ones((3, 5)), np.ones((3, 1)))


class TestEngineCache:
    def test_second_request_hits(self):
        rng = np.random.default_rng(11)
        eng = SolveEngine(cfg=CFG)
        A, B = _spd(rng, 8), rng.standard_normal((8, 1))
        eng.solve("posv", A, B)
        c = eng.cache_stats()
        assert (c["hits"], c["misses"], c["entries"]) == (0, 1, 1)
        eng.solve("posv", _spd(rng, 7), rng.standard_normal((7, 1)))
        c = eng.cache_stats()  # different true shape, same bucket -> hit
        assert (c["hits"], c["misses"], c["entries"]) == (1, 1, 1)
        assert c["hit_rate"] == pytest.approx(0.5)

    def test_warmup_compiles_do_not_count_as_misses(self):
        eng = SolveEngine(cfg=CFG)
        n = eng.warmup([("posv", (8, 8), (8, 1), "float64"),
                        ("posv", (6, 6), (6, 1), "float64"),  # same bucket
                        ("inv", (8, 8), None, "float64")])
        assert n == 2  # the duplicate bucket warms once
        c = eng.cache_stats()
        assert c == {"hits": 0, "misses": 0, "warmup_compiles": 2,
                     "compiles": 2, "entries": 2, "hit_rate": 1.0}

    def test_distinct_configs_never_share_entries(self):
        e1 = SolveEngine(cfg=CFG)
        e2 = SolveEngine(
            cfg=ServeConfig(buckets=CFG.buckets,
                            rows_buckets=CFG.rows_buckets,
                            nrhs_buckets=CFG.nrhs_buckets,
                            max_batch=2, max_delay_s=10.0)
        )
        assert e1._cfg_hash != e2._cfg_hash

    def test_oversize_routes_through_models(self):
        rng = np.random.default_rng(12)
        eng = SolveEngine(cfg=CFG)
        A = _spd(rng, 40)  # beyond the 32 ladder
        B = rng.standard_normal((40, 1))
        r = eng.solve("posv", A, B)
        assert r.ok and not r.batched and r.bucket is None
        np.testing.assert_allclose(np.asarray(r.x), np.linalg.solve(A, B),
                                   rtol=0, atol=1e-10)
        c = eng.cache_stats()
        assert (c["hits"], c["misses"]) == (0, 1)
        # identical oversize shape: exact-shape single-route cache hit
        r2 = eng.solve("posv", _spd(rng, 40), rng.standard_normal((40, 1)))
        assert r2.ok and not r2.batched
        c = eng.cache_stats()
        assert (c["hits"], c["misses"]) == (1, 1)

    def test_oversize_reject_policy(self):
        rng = np.random.default_rng(13)
        cfg = ServeConfig(buckets=(8,), rows_buckets=(32,), nrhs_buckets=(1,),
                          max_batch=2, max_delay_s=10.0, oversize="reject")
        eng = SolveEngine(cfg=cfg)
        r = eng.solve("posv", _spd(rng, 16), rng.standard_normal((16, 1)))
        assert not r.ok and r.x is None and "reject" in r.error
        assert eng.stats.failed == 1

    def test_unknown_oversize_policy_rejected(self):
        with pytest.raises(ValueError, match="oversize"):
            SolveEngine(cfg=ServeConfig(oversize="panic"))


class TestEngineFlush:
    def test_capacity_flush_inside_submit(self):
        rng = np.random.default_rng(14)
        eng = SolveEngine(cfg=CFG)
        tickets = [
            eng.submit("posv", _spd(rng, 8), rng.standard_normal((8, 1)))
            for _ in range(CFG.max_batch)
        ]
        # the max_batch-th submit flushed the bucket: no pump/drain needed
        assert all(t.done for t in tickets)
        assert eng.queue_depth() == 0
        assert eng.stats.batches == 1
        assert eng.stats.occupancies == [1.0]

    def test_deadline_flush_via_pump(self):
        rng = np.random.default_rng(15)
        eng = SolveEngine(cfg=CFG)
        t = eng.submit("posv", _spd(rng, 8), rng.standard_normal((8, 1)))
        assert not t.done and eng.queue_depth() == 1
        assert eng.pump() == 0  # younger than max_delay_s: stays queued
        assert not t.done
        # age the queue past the deadline with an explicit clock
        assert eng.pump(now=spans.now() + CFG.max_delay_s + 1) == 1
        assert t.done and t.result().ok
        assert eng.stats.occupancies == [pytest.approx(1 / CFG.max_batch)]

    def test_unflushed_ticket_raises(self):
        rng = np.random.default_rng(16)
        eng = SolveEngine(cfg=CFG)
        t = eng.submit("posv", _spd(rng, 8), rng.standard_normal((8, 1)))
        with pytest.raises(RuntimeError, match="not flushed"):
            t.result()
        eng.drain()
        assert t.result().ok


class TestEngineFaults:
    def _robust_cfg(self):
        return ServeConfig(buckets=CFG.buckets, rows_buckets=CFG.rows_buckets,
                           nrhs_buckets=CFG.nrhs_buckets, max_batch=3,
                           max_delay_s=10.0, robust=RobustConfig())

    def test_fault_flags_one_request_only(self):
        rng = np.random.default_rng(17)
        eng = SolveEngine(cfg=self._robust_cfg())
        probs = [(_spd(rng, 8), rng.standard_normal((8, 1)))
                 for _ in range(3)]
        with faultinject.active_plan(
            faultinject.Fault(tag="serve::ingest", kind="rank_deficient",
                              index=1)
        ) as plan:
            tickets = [eng.submit("posv", A, B) for A, B in probs]
            eng.drain()
        assert plan.fired == [("serve::ingest", 1)]
        rs = [t.result() for t in tickets]
        assert [r.ok for r in rs] == [True, False, True]
        # the poisoned neighbor carries a RobustInfo naming the breakdown
        assert isinstance(rs[1].info, RobustInfo)
        assert rs[1].info.breakdown == 1 and rs[1].info.info != 0
        for (A, B), r in ((probs[0], rs[0]), (probs[2], rs[2])):
            assert r.info.breakdown == 0
            np.testing.assert_allclose(np.asarray(r.x),
                                       np.linalg.solve(A, B),
                                       rtol=0, atol=1e-10)
        assert eng.stats.flagged == 1 and eng.stats.ok == 2

    def test_raise_fault_fails_request_engine_survives(self):
        rng = np.random.default_rng(18)
        eng = SolveEngine(cfg=self._robust_cfg())
        A, B = _spd(rng, 8), rng.standard_normal((8, 1))
        with faultinject.active_plan(
            faultinject.Fault(tag="serve::ingest", kind="raise")
        ):
            r = eng.solve("posv", A, B)
        assert not r.ok and r.x is None and "injected fault" in r.error
        assert eng.stats.failed == 1
        # the engine is not wedged: the next request succeeds normally
        r2 = eng.solve("posv", A, B)
        assert r2.ok
        np.testing.assert_allclose(np.asarray(r2.x), np.linalg.solve(A, B),
                                   rtol=0, atol=1e-10)


class TestEngineAcceptance:
    """The ISSUE 4 acceptance workload: warmup over >= 3 shape buckets,
    then a 50-request mixed stream -> zero steady-state recompiles, with
    every batched result checked against an unbatched reference."""

    def test_mixed_50_request_workload_zero_recompiles(self, grid2x2x1):
        rng = np.random.default_rng(19)
        eng = SolveEngine(cfg=CFG)
        ns = (6, 12, 24)  # -> buckets 8 / 16 / 32
        ops = ("posv", "inv", "lstsq", "posv", "lstsq")
        work = []
        for i in range(50):
            op, n, k = ops[i % 5], ns[i % 3], (1, 3)[i % 2]
            if op == "lstsq":
                A = rng.standard_normal((4 * n, n))
                B = rng.standard_normal((4 * n, k))
            else:
                A = _spd(rng, n)
                B = rng.standard_normal((n, k)) if op == "posv" else None
            work.append((op, A, B))
        compiled = eng.warmup(
            (op, A.shape, B.shape if B is not None else None, "float64")
            for op, A, B in work
        )
        assert compiled >= 3
        buckets = {
            batching.bucket_for(op, A.shape,
                                B.shape if B is not None else None,
                                "float64", CFG).a_shape
            for op, A, B in work
        }
        assert len(buckets) >= 3  # the ISSUE's >= 3 shape buckets

        tickets = [eng.submit(op, A, B) for op, A, B in work]
        eng.drain()
        c = eng.cache_stats()
        assert c["misses"] == 0 and c["hits"] > 0
        assert c["hit_rate"] == 1.0
        assert c["warmup_compiles"] == compiled == c["entries"]

        for (op, A, B), t in zip(work, tickets):
            r = t.result()
            assert r.ok and r.batched, (op, r.error)
            if op == "posv":
                ref = cholesky.solve(grid2x2x1, jnp.asarray(A),
                                     jnp.asarray(B))
            elif op == "lstsq":
                ref, *_ = np.linalg.lstsq(A, B, rcond=None)
            else:
                ref = np.linalg.inv(A)
            np.testing.assert_allclose(np.asarray(r.x), np.asarray(ref),
                                       rtol=0, atol=1e-8)

        rs = eng.emit_stats()["request_stats"]
        assert rs["requests"] == 50 and rs["ok"] == 50
        assert rs["cache"]["hit_rate"] == 1.0
        assert 0.0 < rs["batch_occupancy_mean"] <= 1.0


# ---------------------------------------------------------------------------
# stats + ledger + CLI (satellites b, c)
# ---------------------------------------------------------------------------


class TestPercentiles:
    def test_nearest_rank(self):
        out = percentiles(range(1, 101))
        assert out == {"p50": 50, "p95": 95, "p99": 99}
        # every reported value is a sample that actually occurred
        assert percentiles([40.0, 10.0, 30.0, 20.0]) == {
            "p50": 20.0, "p95": 40.0, "p99": 40.0,
        }

    def test_single_sample(self):
        assert percentiles([7.0]) == {"p50": 7.0, "p95": 7.0, "p99": 7.0}

    def test_custom_points(self):
        out = percentiles(range(1, 11), points=(10.0, 100.0))
        assert out == {"p10": 1, "p100": 10}

    def test_errors(self):
        with pytest.raises(ValueError, match="at least one"):
            percentiles([])
        with pytest.raises(ValueError, match="outside"):
            percentiles([1.0], points=(0.0,))


class TestStatsCollector:
    def test_snapshot_counts(self):
        c = stats.Collector()
        c.record_request("posv", 0.010, ok=True)
        c.record_request("posv", 0.030, ok=False, flagged=True)
        c.record_request("inv", 0.020, ok=False, failed=True)
        c.note_batch(0.5)
        c.note_batch(1.0)
        c.note_queue_depth(4)
        snap = c.snapshot({"hits": 3, "misses": 1, "warmup_compiles": 2,
                           "entries": 3, "hit_rate": 0.75})
        assert snap["requests"] == 3 and snap["ok"] == 1
        assert snap["flagged"] == 1 and snap["failed"] == 1
        assert snap["ops"] == {"posv": 2, "inv": 1}
        assert snap["latency_ms"]["p50"] == pytest.approx(20.0)
        assert snap["batch_occupancy_mean"] == pytest.approx(0.75)
        assert snap["queue_depth_max"] == 4
        assert ledger.validate_request_stats(snap) == []

    def test_empty_snapshot_is_valid(self):
        snap = stats.Collector().snapshot()
        assert snap["latency_ms"] == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
        assert ledger.validate_request_stats(snap) == []


def _mk_bench_record(value=1.0):
    return ledger.record(
        "bench:test", ledger.manifest(dtype=jnp.float32),
        measured={"metric": "test_tflops", "value": value, "unit": "TFLOP/s",
                  "shape": [64, 64]},
    )


class TestRequestStatsLedger:
    def _emit(self, path=None, latency=0.01, hit_rate=1.0):
        c = stats.Collector()
        c.record_request("posv", latency, ok=True)
        return c.emit(str(path) if path else None,
                      cache={"hits": 4, "misses": 0, "warmup_compiles": 2,
                             "entries": 2, "hit_rate": hit_rate})

    def test_emit_roundtrip(self, tmp_path):
        path = tmp_path / "serve.jsonl"
        rec = self._emit(path)
        assert rec["kind"] == "serve:request_stats"
        (read,) = ledger.read(str(path))
        assert read["request_stats"] == rec["request_stats"]
        assert ledger.validate_request_stats(read["request_stats"]) == []

    def test_diff_exempts_request_stats_latency(self):
        # wildly different latency mixes: workload property, not a kernel
        # regression -> diff stays clean
        a, b = self._emit(latency=0.001), self._emit(latency=5.0)
        assert ledger.diff([a], [b]) == []

    def test_diff_still_flags_real_metric_drop(self):
        # exemption must not swallow a genuine bench regression riding in
        # the same ledgers
        a = [self._emit(), _mk_bench_record(value=1.0)]
        b = [self._emit(), _mk_bench_record(value=0.5)]
        regs = ledger.diff(a, b)
        assert [r.field for r in regs] == ["measured.value"]

    def test_diff_refuses_malformed_block(self):
        a, b = self._emit(), self._emit()
        b["request_stats"]["cache"]["hit_rate"] = 1.5
        with pytest.raises(ledger.LedgerIncompatible, match="hit_rate"):
            ledger.diff([a], [b])
        del a["request_stats"]["latency_ms"]
        with pytest.raises(ledger.LedgerIncompatible, match="latency_ms"):
            ledger.diff([a], [self._emit()])

    def test_validate_rejects_non_dict(self):
        assert ledger.validate_request_stats([1, 2]) != []


class TestServeReportCLI:
    def _emit(self, path, hit_rate=1.0, p99=None):
        c = stats.Collector()
        c.record_request("posv", (p99 or 10.0) / 1e3, ok=True)
        c.emit(str(path), cache={"hits": 9, "misses": 0, "warmup_compiles": 3,
                                 "entries": 3, "hit_rate": hit_rate})

    def test_report_ok(self, tmp_path, capsys):
        path = tmp_path / "serve.jsonl"
        self._emit(path)
        assert obs_main.main(["serve-report", str(path)]) == 0
        out = capsys.readouterr().out
        assert "hit_rate=1.000" in out and "serve-report OK" in out

    def test_hit_rate_gate_fails(self, tmp_path, capsys):
        path = tmp_path / "serve.jsonl"
        self._emit(path, hit_rate=0.5)
        assert obs_main.main(["serve-report", str(path),
                              "--min-hit-rate", "1.0"]) == 1
        assert "hit_rate 0.500 < 1.0" in capsys.readouterr().err

    def test_p99_gate_fails(self, tmp_path, capsys):
        path = tmp_path / "serve.jsonl"
        self._emit(path, p99=500.0)
        assert obs_main.main(["serve-report", str(path),
                              "--max-p99-ms", "100"]) == 1
        assert "p99" in capsys.readouterr().err

    def test_malformed_record_exits_2(self, tmp_path, capsys):
        path = tmp_path / "serve.jsonl"
        rec = stats.Collector().emit(None)
        rec["request_stats"]["schema_version"] = 999
        ledger.append(str(path), rec)
        assert obs_main.main(["serve-report", str(path)]) == 2
        assert "malformed" in capsys.readouterr().err

    def test_no_records_with_gates_fails(self, tmp_path, capsys):
        path = tmp_path / "empty.jsonl"
        ledger.append(str(path), _mk_bench_record())
        assert obs_main.main(["serve-report", str(path)]) == 0
        assert obs_main.main(["serve-report", str(path),
                              "--min-hit-rate", "1.0"]) == 1


@pytest.mark.slow
class TestSmokeCLI:
    def test_smoke_end_to_end(self, tmp_path, capsys):
        from capital_tpu.serve import __main__ as serve_main

        path = tmp_path / "smoke.jsonl"
        rc = serve_main.main(["smoke", "--requests", "24",
                              "--ledger", str(path)])
        assert rc == 0
        assert "serve-smoke OK" in capsys.readouterr().out
        assert obs_main.main(["serve-report", str(path),
                              "--min-hit-rate", "1.0"]) == 0
