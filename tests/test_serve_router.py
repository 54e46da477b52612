"""PR 9 multi-replica serving tests: the Router over N EngineReplicas,
the failure paths (crash re-dispatch, drain under load, affinity
rebalance as a disk hit), cross-replica stats merging, the benign
disk-write race counter, and the serve-report aggregation gates.

The acceptance properties of ISSUE 9 / docs/SERVING.md "Multi-replica
serving" are asserted directly:

* **crash re-dispatch** — a killed replica's in-flight requests land on
  the survivors, every submitted ticket completes exactly once (first
  result wins; late crash-race results count as `duplicates`, never as
  a second client-visible landing);
* **drain under load** — draining one replica lands its whole window
  while the rest keep admitting; nothing is dropped, and the drained
  replica admits again after resume;
* **rebalance = disk hit** — with bucket_affinity and a shared
  persist_dir, the replacement for a killed replica warms its remapped
  buckets from disk (zero fresh compiles), the cache-locality half of
  the rendezvous-hash story;
* **aggregation** — merge_snapshots sums counts, pools percentiles from
  raw samples (exact) or takes the worst tail, never a mean of
  percentiles; `obs serve-report --aggregate --min-replicas N` gates
  the same merge from ledger records alone.

Thread replicas throughout (full router semantics, no process-spawn
flakiness); one slow-marked ProcessReplica roundtrip pins the pipe
transport + env-before-jax spawn contract.
"""

import os
import time

import numpy as np
import pytest

from capital_tpu.obs import __main__ as obs_main
from capital_tpu.obs import ledger
from capital_tpu.serve import stats as serve_stats
from capital_tpu.serve.replica import ProcessReplica, ThreadReplica
from capital_tpu.serve.router import (
    Router,
    RouterConfig,
    _rendezvous,
    _rung,
    bucket_signature,
)

# one tiny pallas-route f32 bucket: pure-HLO executables (persistable on
# the CPU rig), 1-2 compiles per replica.  Tight max_delay_s keeps the
# replica worker's deadline flushes fast (no client-side pump forcing —
# the worker loop owns the engine).
def _cfg(persist_dir=None, **kw):
    from capital_tpu.serve.engine import ServeConfig

    return ServeConfig(
        buckets=(8,), rows_buckets=(32,), nrhs_buckets=(1,),
        max_batch=2, max_delay_s=0.005, small_n_impl="pallas",
        persist_dir=str(persist_dir) if persist_dir else None, **kw,
    )


_SPECS = [("posv", (8, 8), (8, 1), "float32")]


def _posv(rng):
    G = rng.standard_normal((8, 8)).astype(np.float32)
    A = (G @ G.T + 8 * np.eye(8, dtype=np.float32)).astype(np.float32)
    B = rng.standard_normal((8, 1)).astype(np.float32)
    return A, B


def _router(n, persist_dir=None, policy="least_loaded", prefix="r"):
    r = Router(RouterConfig(policy=policy))
    for i in range(n):
        r.add_replica(ThreadReplica(f"{prefix}{i}", _cfg(persist_dir)))
    return r


def _pump_until_done(router, tickets, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not all(t.done for t in tickets):
        router.pump()
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"{sum(not t.done for t in tickets)} tickets never landed"
            )
        time.sleep(1e-3)


class TestBucketSignature:
    def test_rung_smallest_fit(self):
        assert _rung((8, 16, 32), 9) == 16
        assert _rung((32, 8, 16), 8) == 8  # order-independent
        assert _rung((8, 16), 17) is None

    def test_posv_and_lstsq_signatures(self):
        lad = {"buckets": (8, 16), "rows_buckets": (32,),
               "nrhs_buckets": (1, 4)}
        assert bucket_signature("posv", (8, 8), (8, 1), "float32", lad) \
            == ("posv", "float32", 8, 1, 0, "balanced")
        assert bucket_signature("lstsq", (30, 7), (30, 3), "float32", lad) \
            == ("lstsq", "float32", 8, 4, 32, "balanced")
        assert bucket_signature("inv", (5, 5), None, "float32", lad) \
            == ("inv", "float32", 8, None, 0, "balanced")
        # the accuracy tier joins the key: a guaranteed request must not
        # share affinity with the same-shape balanced bucket
        assert bucket_signature("posv", (8, 8), (8, 1), "float32", lad,
                                tier="guaranteed") \
            == ("posv", "float32", 8, 1, 0, "guaranteed")

    def test_oversize_keys_on_exact_shape(self):
        lad = {"buckets": (8,), "rows_buckets": (32,), "nrhs_buckets": (1,)}
        sig = bucket_signature("posv", (64, 64), (64, 1), "float32", lad)
        assert sig[0] == "oversize" and sig[3] == (64, 64)

    def test_rendezvous_removal_remaps_only_owner(self):
        ids = ["a", "b", "c"]
        sigs = [("posv", "float32", 8, 1, 0), ("inv", "float32", 8, None, 0),
                ("lstsq", "float32", 8, 4, 32)]
        for sig in sigs:
            owner = _rendezvous(sig, ids)
            survivor_sets = [[i for i in ids if i != gone]
                             for gone in ids if gone != owner]
            for rest in survivor_sets:
                # removing a NON-owner never moves the signature
                assert _rendezvous(sig, rest) == owner


class TestRouterBasics:
    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="dispatch policy"):
            Router(RouterConfig(policy="round_robin"))

    def test_no_healthy_replica_refuses_admission(self):
        r = Router()
        with pytest.raises(RuntimeError, match="no healthy replica"):
            r.submit("posv", np.eye(8, dtype=np.float32),
                     np.ones((8, 1), np.float32))

    def test_submit_result_roundtrip_and_invariant(self):
        rng = np.random.default_rng(0)
        r = _router(2)
        try:
            fresh = r.warmup(_SPECS)
            assert set(fresh) == {"r0", "r1"}
            work = [_posv(rng) for _ in range(6)]
            tickets = [r.submit("posv", A, B) for A, B in work]
            _pump_until_done(r, tickets)
            for (A, B), t in zip(work, tickets):
                res = t.result(timeout=1.0)
                assert res.ok and res.replica_id in ("r0", "r1")
                x = np.asarray(res.x, dtype=np.float64)
                resid = np.linalg.norm(A.astype(np.float64) @ x - B) \
                    / np.linalg.norm(B)
                assert resid < 1e-4
            c = r.counters()
            assert c["completed"] == 6 and c["parked"] == 0
            assert c["duplicates"] == 0 and c["redispatched"] == 0
            # no-drop invariant: everything dispatched is accounted for
            out = sum(v["outstanding"] for v in c["per_replica"].values())
            assert c["completed"] + c["parked"] + out == c["dispatched"]
        finally:
            r.stop()

    def test_least_loaded_spreads(self):
        rng = np.random.default_rng(1)
        r = _router(2)
        try:
            r.warmup(_SPECS)
            tickets = [r.submit("posv", *_posv(rng)) for _ in range(8)]
            per = r.counters()["per_replica"]
            # fewest-outstanding wins: both replicas carry load (exact split
            # depends on how fast results land between submits)
            assert per["r0"]["dispatched"] + per["r1"]["dispatched"] == 8
            assert per["r0"]["dispatched"] >= 1 and per["r1"]["dispatched"] >= 1
            _pump_until_done(r, tickets)
        finally:
            r.stop()

    def test_ladder_disagreement_rejected(self):
        r = _router(1)
        try:
            from capital_tpu.serve.engine import ServeConfig

            other = ServeConfig(buckets=(16,), rows_buckets=(32,),
                                nrhs_buckets=(1,), small_n_impl="pallas")
            with pytest.raises(ValueError, match="ladders"):
                r.add_replica(ThreadReplica("rX", other))
        finally:
            r.stop()


class TestPlacement:
    """One replica per chip: thread replicas pinned with device=i run
    their whole engine (compiles, staging, answers) on jax.devices()[i];
    process children cannot be pinned, so the router admits at most one
    that may take the accelerator."""

    def test_pinned_thread_replicas_answer_on_their_device(self):
        import jax

        from capital_tpu.serve.replica import make_replica

        rng = np.random.default_rng(5)
        r = Router(RouterConfig(policy="least_loaded"))
        reps = [r.add_replica(make_replica("thread", f"d{i}", _cfg(),
                                           device=i)) for i in range(4)]
        try:
            for i, rep in enumerate(reps):
                assert rep.warmup(_SPECS)["device"] == jax.devices()[i].id
            work = [_posv(rng) for _ in range(16)]
            tickets = [r.submit("posv", A, B) for A, B in work]
            _pump_until_done(r, tickets)
            served = set()
            for (A, B), t in zip(work, tickets):
                res = t.result(timeout=1.0)
                assert res.ok, res.error
                i = int(res.replica_id[1:])
                assert res.devices == (jax.devices()[i].id,)
                served.add(i)
                x = np.asarray(res.x, dtype=np.float64)
                assert np.linalg.norm(A @ x - B) / np.linalg.norm(B) < 1e-4
            assert len(served) >= 2
        finally:
            r.stop()

    def test_disk_tier_is_per_device(self, tmp_path):
        # a program compiled for one chip does not load onto another: a
        # replica pinned elsewhere misses cleanly (never a disk error) and
        # compiles; a later replica on that same chip disk-hits it
        from capital_tpu.serve.replica import make_replica

        infos = []
        for rid, dev in (("a", 0), ("b", 1), ("c", 1)):
            rep = make_replica("thread", rid, _cfg(tmp_path), device=dev)
            rep.start()
            try:
                infos.append(rep.warmup(_SPECS))
            finally:
                rep.stop()
        (a, b, c) = infos
        assert a["fresh"] > 0 and b["fresh"] == a["fresh"]
        assert b["cache"]["disk"]["errors"] == 0
        assert c["fresh"] == 0 and c["cache"]["disk"]["hits"] == a["fresh"]

    def test_process_replica_cannot_be_pinned(self):
        from capital_tpu.serve.replica import make_replica

        with pytest.raises(ValueError, match="thread replicas"):
            make_replica("process", "p0", _cfg(), device=1)

    @pytest.mark.parametrize("platforms,admitted", [("tpu", 1), ("cpu", 2)])
    def test_one_accelerator_process_per_host(self, platforms, admitted):
        r = Router()
        reps = [ProcessReplica(f"p{i}", _cfg(),
                               env={"JAX_PLATFORMS": platforms})
                for i in range(2)]
        r.add_replica(reps[0], start=False)
        if admitted == 1:
            with pytest.raises(ValueError, match="one process replica"):
                r.add_replica(reps[1], start=False)
        else:
            r.add_replica(reps[1], start=False)
        assert len(r.replica_ids()) == admitted
        assert not any(rep.alive() for rep in reps)


class TestFailurePaths:
    def test_crash_redispatch_loses_nothing(self):
        rng = np.random.default_rng(2)
        r = _router(2)
        try:
            r.warmup(_SPECS)
            work = [_posv(rng) for _ in range(10)]
            tickets = [r.submit("posv", A, B) for A, B in work]
            # abrupt death with a half-full window on r0; the next pump
            # observes it and re-dispatches everything unanswered
            r.kill_replica("r0")
            _pump_until_done(r, tickets)
            c = r.counters()
            assert c["failed_replicas"] == 1
            assert c["completed"] == 10 and c["parked"] == 0
            # exactly one client-visible result per ticket, all from the
            # survivor or swept from the victim's outbox pre-kill
            for (A, B), t in zip(work, tickets):
                assert t.response is not None and t.response.ok
            # first-wins: duplicates (crash-raced second results) never
            # inflate completed
            assert c["completed"] + c["duplicates"] >= c["redispatched"]
            assert "r0" not in c["per_replica"]
        finally:
            r.stop()

    def test_kill_all_parks_then_new_replica_flushes(self):
        rng = np.random.default_rng(3)
        r = _router(1)
        try:
            r.warmup(_SPECS)
            tickets = [r.submit("posv", *_posv(rng)) for _ in range(3)]
            r.kill_replica("r0")
            r.pump()
            c = r.counters()
            # admitted work parks (never drops); NEW admission refuses
            assert c["parked"] + c["completed"] == 3
            if c["parked"]:
                with pytest.raises(RuntimeError, match="no healthy"):
                    r.submit("posv", *_posv(rng))
            r.add_replica(ThreadReplica("r1", _cfg()))
            r.warmup(_SPECS)
            _pump_until_done(r, tickets)
            assert r.counters()["parked"] == 0
            assert all(t.response.ok for t in tickets)
        finally:
            r.stop()

    def test_drain_under_load_lands_everything(self):
        rng = np.random.default_rng(4)
        r = _router(2)
        try:
            r.warmup(_SPECS)
            first = [r.submit("posv", *_posv(rng)) for _ in range(6)]
            assert r.drain_replica("r0", timeout=60.0)
            per = r.counters()["per_replica"]["r0"]
            assert per["draining"] and per["outstanding"] == 0
            # admission continues on the survivor while r0 is draining
            second = [r.submit("posv", *_posv(rng)) for _ in range(4)]
            assert all(t.replica_id == "r1" for t in second)
            _pump_until_done(r, first + second)
            assert all(t.response.ok for t in first + second)
            r.resume_replica("r0")
            t = r.submit("posv", *_posv(rng))
            # least_loaded sends the next request to the idle, resumed r0
            assert t.replica_id == "r0"
            _pump_until_done(r, [t])
        finally:
            r.stop()

    def test_drain_all_refuses_admission(self):
        r = _router(1)
        try:
            r.warmup(_SPECS)
            r.drain_replica("r0")
            with pytest.raises(RuntimeError, match="no healthy"):
                r.submit("posv", np.eye(8, dtype=np.float32),
                         np.ones((8, 1), np.float32))
            r.resume_replica("r0")
        finally:
            r.stop()

    def test_first_wins_counts_duplicate(self):
        r = _router(1)
        try:
            r.warmup(_SPECS)
            rng = np.random.default_rng(5)
            t = r.submit("posv", *_posv(rng))
            _pump_until_done(r, [t])
            st = r._states["r0"]
            payload = {
                "request_id": t.request_id, "op": "posv", "ok": True,
                "x": np.asarray(t.response.x), "info": None, "error": None,
                "bucket": None, "batched": True, "latency_s": 0.0,
                "queue_wait_s": None, "device_s": None,
            }
            # a crash-raced second landing for the same ticket: dropped,
            # counted, and completed does not double
            assert r._land(st, t.request_id, payload) == 0
            assert r.duplicates == 1 and r.completed == 1
        finally:
            r.stop()


class TestAffinityRebalance:
    def test_rebalance_is_disk_hit_not_compile(self, tmp_path):
        rng = np.random.default_rng(6)
        r = _router(2, persist_dir=tmp_path, policy="bucket_affinity")
        try:
            fresh = r.warmup(_SPECS)
            # shared dir: exactly one replica compiled, the other disk-hit
            vals = sorted(fresh.values())
            assert vals[0] == 0 and vals[-1] > 0
            work = [_posv(rng) for _ in range(4)]
            tickets = [r.submit("posv", A, B) for A, B in work]
            # affinity: one signature in this workload -> ONE owner
            owners = {t.replica_id for t in tickets}
            assert len(owners) == 1
            _pump_until_done(r, tickets)
            before = {rid: s["cache"]["compiles"]
                      for rid, s in r.replica_stats().items()}

            r.kill_replica(owners.pop())
            r.pump()
            rep = ThreadReplica("r2", _cfg(tmp_path))
            r.add_replica(rep)
            rep_fresh = r.warmup(_SPECS)
            # the replacement (and the remapped bucket's new owner) warm
            # from the SHARED disk tier: zero fresh XLA compiles anywhere
            assert all(v == 0 for v in rep_fresh.values() if v is not None)
            more = [r.submit("posv", *_posv(rng)) for _ in range(4)]
            _pump_until_done(r, more)
            assert all(t.response.ok for t in more)
            snaps = r.replica_stats()
            for rid, snap in snaps.items():
                # rebalance cost ZERO new XLA compiles: the survivor keeps
                # whatever cold-warmup count it had, the replacement has none
                assert snap["cache"]["compiles"] == before.get(rid, 0), rid
                assert snap["cache"]["misses"] == 0, rid
        finally:
            r.stop()


class TestMergeSnapshots:
    def _snap(self, replica_id, lat_s, batches=2, occ=0.5, samples=True):
        c = serve_stats.Collector(replica_id=replica_id)
        for v in lat_s:
            c.record_request("posv", v, ok=True, queue_wait_s=v / 2,
                             device_s=v / 2)
        for _ in range(batches):
            c.note_batch(occ)
        cache = {"hits": 3, "misses": 1, "warmup_compiles": 2,
                 "compiles": 2, "entries": 2, "hit_rate": 0.75,
                 "disk": {"hits": 1, "misses": 1, "errors": 0, "skips": 0,
                          "races": 1}}
        return c.snapshot(cache, samples=samples)

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            serve_stats.merge_snapshots([])

    def test_pooled_percentiles_exact(self):
        a = self._snap("r0", [0.001, 0.002, 0.003])
        b = self._snap("r1", [0.100, 0.200, 0.300])
        m = serve_stats.merge_snapshots([a, b])
        assert m["requests"] == 6 and m["replicas"] == 2
        assert m["replica_ids"] == ["r0", "r1"]
        # exact pooled p50 of the union, NOT a mean of the two p50s
        from capital_tpu.serve.stats import percentiles

        pool = [0.001, 0.002, 0.003, 0.1, 0.2, 0.3]
        want = round(percentiles(pool)["p50"] * 1e3, 4)
        assert m["latency_ms"]["p50"] == want
        assert "samples" not in m and "replica_id" not in m

    def test_max_of_tails_without_samples(self):
        a = self._snap("r0", [0.001, 0.002], samples=False)
        b = self._snap("r1", [0.100, 0.200], samples=True)
        m = serve_stats.merge_snapshots([a, b])
        # one contributor lacks populations -> worst-tail bound (max),
        # elementwise, never a mean
        assert m["latency_ms"]["p99"] == max(
            a["latency_ms"]["p99"], b["latency_ms"]["p99"])

    def test_cache_and_occupancy_merge(self):
        a = self._snap("r0", [0.001], batches=1, occ=1.0)
        b = self._snap("r1", [0.002], batches=3, occ=0.5)
        m = serve_stats.merge_snapshots([a, b])
        assert m["cache"]["hits"] == 6 and m["cache"]["misses"] == 2
        assert m["cache"]["hit_rate"] == 0.75
        assert m["cache"]["disk"]["races"] == 2
        # batch-weighted, not a plain mean: (1*1.0 + 3*0.5) / 4
        assert m["batch_occupancy_mean"] == 0.625
        assert not ledger.validate_request_stats(m)

    def test_merged_block_valid_under_ledger(self):
        snaps = [self._snap(f"r{i}", [0.001 * (i + 1)]) for i in range(3)]
        m = serve_stats.merge_snapshots(snaps)
        assert ledger.validate_request_stats(m) == []


class TestLedgerValidation:
    def _base(self):
        return serve_stats.Collector(replica_id="r0").snapshot()

    def test_replica_tags_validate(self):
        snap = self._base()
        assert ledger.validate_request_stats(snap) == []
        bad = dict(snap, replica_id=7)
        assert any("replica_id" in p
                   for p in ledger.validate_request_stats(bad))
        bad = dict(snap, replicas=0)
        assert any("replicas" in p
                   for p in ledger.validate_request_stats(bad))
        bad = dict(snap, replica_ids="r0")
        assert any("replica_ids" in p
                   for p in ledger.validate_request_stats(bad))

    def test_samples_block_flagged_in_records(self):
        snap = serve_stats.Collector(replica_id="r0").snapshot(samples=True)
        assert any("samples" in p
                   for p in ledger.validate_request_stats(snap))


class TestDiskRaces:
    def _exe(self):
        import jax
        import jax.numpy as jnp

        return jax.jit(lambda x: x + 1).lower(
            jnp.ones((4,), np.float32)).compile()

    def test_lost_race_counts_race_not_error(self, tmp_path):
        from capital_tpu.serve.cache import ExecutableCache

        exe = self._exe()
        key = ("k", 1)
        c1 = ExecutableCache(persist_dir=str(tmp_path))
        c2 = ExecutableCache(persist_dir=str(tmp_path))
        c1._store(key, exe)
        assert c1.disk_races == 0 and os.path.exists(c1.entry_path(key))
        # the multi-replica warmup pattern: a second engine compiled the
        # same program and finds a valid entry already on disk
        c2._store(key, exe)
        assert c2.disk_races == 1 and c2.disk_errors == 0
        assert c2.stats()["disk"]["races"] == 1

    def test_store_failure_with_valid_entry_is_race(self, tmp_path,
                                                    monkeypatch):
        from jax.experimental import serialize_executable

        from capital_tpu.serve.cache import ExecutableCache

        exe = self._exe()
        key = ("k", 2)
        c1 = ExecutableCache(persist_dir=str(tmp_path))
        c1._store(key, exe)
        c2 = ExecutableCache(persist_dir=str(tmp_path))
        # make c2 lose the race mid-write: the pre-store peek misses (first
        # call forced False), its serialize explodes, and the post-failure
        # peek finds c1's valid entry -> benign race, NOT a disk error
        monkeypatch.setattr(
            serialize_executable, "serialize",
            lambda _exe: (_ for _ in ()).throw(RuntimeError("boom")))
        real_peek = ExecutableCache._peek_valid

        calls = {"n": 0}

        def peek(self, k):
            calls["n"] += 1
            if calls["n"] == 1:
                return False  # lose the pre-store check, enter the write
            return real_peek(self, k)

        monkeypatch.setattr(ExecutableCache, "_peek_valid", peek)
        c2._store(key, exe)
        assert c2.disk_races == 1 and c2.disk_errors == 0

    def test_store_failure_without_entry_is_error(self, tmp_path,
                                                  monkeypatch):
        from jax.experimental import serialize_executable

        from capital_tpu.serve.cache import ExecutableCache

        c = ExecutableCache(persist_dir=str(tmp_path))
        monkeypatch.setattr(
            serialize_executable, "serialize",
            lambda _exe: (_ for _ in ()).throw(RuntimeError("boom")))
        c._store(("k", 3), self._exe())
        assert c.disk_errors == 1 and c.disk_races == 0


class TestServeReportAggregate:
    def _write_ledger(self, path, replica_ids, router_block=None):
        recs = []
        snaps = []
        for rid in replica_ids:
            c = serve_stats.Collector(replica_id=rid)
            c.record_request("posv", 0.01, ok=True)
            c.note_batch(0.5)
            snaps.append(c.snapshot(samples=True))
            clean = {k: v for k, v in snaps[-1].items() if k != "samples"}
            recs.append(ledger.record("serve:request_stats",
                                      ledger.manifest(),
                                      request_stats=clean))
        if snaps:
            agg = serve_stats.merge_snapshots(snaps)
            extra = {"router": router_block} if router_block else {}
            recs.append(ledger.record("serve:request_stats",
                                      ledger.manifest(),
                                      request_stats=agg, **extra))
        for r in recs:
            ledger.append(str(path), r)

    def test_aggregate_gate_passes(self, tmp_path, capsys):
        p = tmp_path / "l.jsonl"
        self._write_ledger(p, ["r0", "r1"], router_block={"qps": 12.5})
        rc = obs_main.main(["serve-report", str(p), "--aggregate",
                            "--min-replicas", "2", "--min-hit-rate", "1.0"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "aggregate[" in out and "qps_sum=12.5" in out

    def test_min_replicas_fails_short(self, tmp_path):
        p = tmp_path / "l.jsonl"
        self._write_ledger(p, ["r0", "r1"])
        assert obs_main.main(["serve-report", str(p),
                              "--min-replicas", "3"]) == 1

    def test_aggregate_fails_loudly_without_tags(self, tmp_path):
        p = tmp_path / "l.jsonl"
        c = serve_stats.Collector()  # untagged single-engine record
        c.record_request("posv", 0.01, ok=True)
        ledger.append(str(p), ledger.record(
            "serve:request_stats", ledger.manifest(),
            request_stats=c.snapshot()))
        assert obs_main.main(["serve-report", str(p), "--aggregate"]) == 1
        assert obs_main.main(["serve-report", str(p),
                              "--min-replicas", "1"]) == 1

    def test_gates_with_empty_ledger_fail(self, tmp_path):
        p = tmp_path / "empty.jsonl"
        p.write_text("")
        assert obs_main.main(["serve-report", str(p), "--aggregate"]) == 1


class TestHostOnlyLint:
    def test_module_level_jax_import_flagged(self):
        from capital_tpu.lint import source

        bad = ("import jax\n"
               "def f():\n"
               "    import jax.numpy as jnp\n"
               "    return jnp\n")
        fs = source.lint_source("pkg/serve/router.py", text=bad)
        assert [(f.rule, f.line) for f in fs] == [("host-only-dispatch", 1)]
        fs = source.lint_source("pkg/serve/replica.py",
                                text="from jax import numpy\n")
        assert fs and fs[0].rule == "host-only-dispatch"
        # only the dispatch plane is constrained
        assert not source.lint_source("pkg/serve/engine.py",
                                      text="import jax\n")

    def test_real_dispatch_plane_is_clean(self):
        from capital_tpu.lint import source

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for name in ("router.py", "replica.py"):
            path = os.path.join(root, "capital_tpu", "serve", name)
            hits = [f for f in source.lint_source(path)
                    if f.rule == "host-only-dispatch"]
            assert hits == []


class TestScalingAB:
    def test_compare_replicas_records_efficiency(self, tmp_path):
        from capital_tpu.serve import loadgen

        cfg = _cfg(tmp_path / "cache")
        wl = loadgen.Workload(requests=6, concurrency=2, ops=("posv",),
                              ns=(8,), nrhs=(1,))
        res = loadgen.compare_replicas(
            cfg, wl, replica_counts=(1, 2),
            ledger_path=str(tmp_path / "ab.jsonl"))
        for n in (1, 2):
            assert res[n]["failed"] == 0
            assert res[n]["requests"] == 6 * n
        blk = res[2]["router_block"]
        assert blk["baseline_qps"] == res[1]["qps"]
        assert blk["scaling_efficiency"] == pytest.approx(
            (res[2]["qps"] / 2) / res[1]["qps"], rel=1e-3)
        recs = ledger.read(str(tmp_path / "ab.jsonl"))
        for r in recs:
            assert ledger.validate_request_stats(r["request_stats"]) == []
        aggs = [r for r in recs if r.get("router")]
        assert len(aggs) == 2
        assert "scaling_efficiency" in aggs[-1]["router"]


@pytest.mark.slow
class TestProcessReplica:
    def test_pipe_roundtrip(self, tmp_path):
        rep = ProcessReplica("p0", _cfg(tmp_path),
                             env={"JAX_PLATFORMS": "cpu"})
        rep.start()
        try:
            info = rep.warmup(_SPECS, timeout=600.0)
            assert info is not None and info["fresh"] >= 1
            rng = np.random.default_rng(7)
            A, B = _posv(rng)
            rep.submit(0, "posv", A, B)
            deadline = time.monotonic() + 120.0
            result = None
            while result is None and time.monotonic() < deadline:
                for msg in rep.poll():
                    if msg[0] == "result":
                        result = msg[2]
                time.sleep(0.01)
            assert result is not None and result["ok"]
            x = np.asarray(result["x"], dtype=np.float64)
            assert np.linalg.norm(A.astype(np.float64) @ x - B) \
                / np.linalg.norm(B) < 1e-4
            assert rep.ping() is not None
        finally:
            rep.stop()
            assert not rep.alive()
