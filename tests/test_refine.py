"""PR 14 mixed-precision iterative refinement tests: robust/refine's three
drivers, the TSQR escalation rung, and accuracy tiers as a serve
scheduling dimension.

The acceptance properties of ISSUE 14 / docs/PERF.md round 14 /
docs/SERVING.md "Accuracy tiers" are asserted directly:

* a low-precision factor plus high-precision Wilkinson sweeps reaches the
  CORRECTION dtype's backward error inside the factor envelope — the
  cond≈2e4 point where f32 sCQR3 stalls refines clean (TestRefinePosv);
* beyond the envelope the loop freezes (progress guard) and reports
  ``converged == 0`` with the measured error — loud, finite, at most one
  wasted sweep (TestRefinePosv::test_beyond_envelope_stalls_loud);
* lstsq refines via SEMI-NORMAL corrections against the gram R, blocktri
  refines against a chain factor that can be a PR 12 RESIDENT factor —
  refinement never refactors (TestRefineLstsq, TestRefineBlocktri);
* the TSQR rung recovers cond 1e12 where the gram-forming CQR family
  cannot, both standalone (recovery.tsqr_escalate) and in-graph under
  RobustConfig.tsqr, with RobustInfo.gate naming which gate a surviving
  nonzero info describes (TestTsqrEscalation);
* accuracy_tier rides the serve bucket key: per-tier executables, zero
  steady-state recompiles per warm tier, non-convergence lands as a
  failed Response (never a silent wrong answer), and non-tier ops reject
  the vocabulary loudly (TestServeTiers);
* the telemetry seam: Collector.note_refine -> snapshot refine block ->
  merge_snapshots -> validate_request_stats / validate_refine_measured ->
  ``obs serve-report --max-refine-iters/--min-converged-frac``
  (TestStatsRefineBlock, TestValidateRefineMeasured,
  TestServeReportRefineGates).

Everything runs on the conftest CPU/x64 rig; engines use tiny bucket
ladders on the vmap/LAPACK seam so every executable compiles fast.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import blocktri, qr
from capital_tpu.models.qr import CacqrConfig
from capital_tpu.obs import __main__ as obs_main
from capital_tpu.obs import ledger
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust import RobustConfig, recovery, refine
from capital_tpu.robust.config import GATE_NONE, GATE_ORTHO
from capital_tpu.serve import ServeConfig, SolveEngine, stats


def _spd_cond(rng, n, cond, batch=1):
    """(batch, n, n) f64 SPD stack with a log-spaced spectrum spanning
    exactly `cond` — the refine drivers' conditioning knob."""
    eigs = np.logspace(0.0, -np.log10(cond), n)
    A = np.empty((batch, n, n))
    for i in range(batch):
        Q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        A[i] = (Q * eigs) @ Q.T
    return 0.5 * (A + A.transpose(0, 2, 1))


def _bwerr(A, X, B):
    """Worst per-problem normwise backward error, f64 NumPy side."""
    A, X, B = (np.asarray(v, np.float64) for v in (A, X, B))
    worst = 0.0
    for i in range(A.shape[0]):
        r = A[i] @ X[i] - B[i]
        denom = (np.linalg.norm(A[i]) * np.linalg.norm(X[i])
                 + np.linalg.norm(B[i]) + np.finfo(np.float64).tiny)
        worst = max(worst, float(np.linalg.norm(r) / denom))
    return worst


# One jitted entry per (driver, static-config), shared by every test
# below: a bare refine.* call re-traces its while_loop body (fresh
# closure identity per call), so routing all same-shape calls through
# these module-level wrappers is what keeps the file inside the tier-1
# wall-clock budget — tests that can share an operand shape do.
_F32_KW = dict(factor_dtype=jnp.float32, correction_dtype=jnp.float64)
_posv = jax.jit(functools.partial(refine.posv, **_F32_KW))
_posv_mi0 = jax.jit(functools.partial(refine.posv, max_iters=0, **_F32_KW))
_lstsq = jax.jit(functools.partial(refine.lstsq, **_F32_KW))
_bt = jax.jit(functools.partial(refine.posv_blocktri, impl="xla", **_F32_KW))


# --------------------------------------------------------------------------
# tier plans + tolerance (the static resolution serve hashes)
# --------------------------------------------------------------------------


class TestTierPlans:
    def test_balanced_is_identity(self):
        for dt in (jnp.bfloat16, jnp.float32, jnp.float64):
            p = refine.plan("balanced", dt)
            assert p.factor_dtype == jnp.dtype(dt)
            assert p.correction_dtype == jnp.dtype(dt)
            assert p.max_iters == 0

    def test_fast_downgrades_factor(self):
        assert refine.plan("fast", jnp.float64).factor_dtype == jnp.float32
        assert refine.plan("fast", jnp.float32).factor_dtype == jnp.bfloat16
        assert refine.plan("fast", jnp.bfloat16).factor_dtype == jnp.bfloat16
        assert refine.plan("fast", jnp.float64).max_iters == 0

    def test_guaranteed_pairs_low_factor_high_correction(self):
        p64 = refine.plan("guaranteed", jnp.float64)
        assert (p64.factor_dtype, p64.correction_dtype) == \
            (jnp.dtype(jnp.float32), jnp.dtype(jnp.float64))
        p32 = refine.plan("guaranteed", jnp.float32)
        assert (p32.factor_dtype, p32.correction_dtype) == \
            (jnp.dtype(jnp.float32), jnp.dtype(jnp.float64))
        p16 = refine.plan("guaranteed", jnp.bfloat16)
        assert (p16.factor_dtype, p16.correction_dtype) == \
            (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
        assert p64.max_iters == refine.DEFAULT_MAX_ITERS

    def test_unknown_tier_raises(self):
        with pytest.raises(ValueError, match="accuracy_tier"):
            refine.plan("turbo", jnp.float32)

    def test_tolerance_scales_with_correction_dtype(self):
        t64 = refine.tolerance(64, jnp.float64)
        t32 = refine.tolerance(64, jnp.float32)
        assert t64 == pytest.approx(0.5 * 8.0 * np.finfo(np.float64).eps)
        assert t32 / t64 == pytest.approx(
            np.finfo(np.float32).eps / np.finfo(np.float64).eps)


# --------------------------------------------------------------------------
# refine.posv — the flagship driver
# --------------------------------------------------------------------------


class TestRefinePosv:
    @pytest.mark.parametrize("cond", [1e2, 1e4, 2e4])
    def test_f32_factor_reaches_f64_grade(self, cond):
        # 2e4 is the documented f32 sCQR3 stall point (ROBUSTNESS.md):
        # comfortably inside the refinement envelope
        rng = np.random.default_rng(int(cond) % 97)
        n, k, batch = 48, 3, 2
        A = _spd_cond(rng, n, cond, batch)
        B = rng.standard_normal((batch, n, k))
        X, info, ri = _posv(jnp.asarray(A), jnp.asarray(B))
        assert not np.any(np.asarray(info))
        assert np.all(np.asarray(ri.converged) == 1)
        assert np.all(np.asarray(ri.iters) >= 1)  # f32 X0 alone is not f64
        assert X.dtype == jnp.float64
        assert _bwerr(A, X, B) < refine.tolerance(n, jnp.float64)

    def test_refined_beats_unrefined_factor(self):
        rng = np.random.default_rng(5)
        n, k, batch = 48, 3, 2
        A = _spd_cond(rng, n, 2e4, batch)
        B = rng.standard_normal((batch, n, k))
        X0, _, r0 = _posv_mi0(jnp.asarray(A), jnp.asarray(B))
        X, _, ri = _posv(jnp.asarray(A), jnp.asarray(B))
        e0, e = _bwerr(A, X0, B), _bwerr(A, X, B)
        assert np.all(np.asarray(r0.iters) == 0)
        assert np.all(np.asarray(r0.converged) == 0)  # honest: not there yet
        assert e < 1e-3 * e0  # sweeps bought >= 3 digits back

    def test_beyond_envelope_stalls_loud(self):
        # cond 1e8 > 1/u32: the f32 factor still completes (info 0) but
        # the error floors orders of magnitude above the f64 tolerance,
        # so the progress guard freezes the problem and reports it —
        # never a spin, never a silent wrong answer.  Beyond the envelope
        # whether a sweep gains depends on the factor's rounding, so the
        # probed operand is one whose first sweep GROWS the error (2.6x
        # through the batched-grid Pallas factor, 5.1x through LAPACK's):
        # the freeze holds with room on either factor route.  Seed 7, the
        # case probed before, halves its error sweep after sweep to a
        # true f64-grade answer (test_beyond_envelope_reports_honestly).
        rng = np.random.default_rng(21)
        n = 16
        bad = _spd_cond(rng, n, 1e8)
        A = np.concatenate([bad, bad])  # (2, n, n): the shared-shape class
        b1 = rng.standard_normal((1, n, 2))
        B = np.concatenate([b1, b1])  # both problems ARE the probed case
        X, info, ri = _posv(jnp.asarray(A), jnp.asarray(B))
        assert not np.any(np.asarray(info))  # the factor is NOT the story
        assert np.all(np.asarray(ri.converged) == 0)
        assert np.all(np.asarray(ri.iters) <= 2)  # froze, didn't spin
        assert np.all(np.asarray(ri.resid) > refine.tolerance(
            n, jnp.float64))  # the measured error says why

    @pytest.mark.parametrize("seed", [7, 8, 21])
    def test_beyond_envelope_reports_honestly(self, seed):
        # beyond the envelope a problem may stall or may still converge;
        # either way the report is true: converged means the f64
        # backward error, measured here in NumPy, is under tolerance, and
        # not converged comes with a measured error above it
        rng = np.random.default_rng(seed)
        n = 16
        bad = _spd_cond(rng, n, 1e8)
        A = np.concatenate([bad, bad])
        b1 = rng.standard_normal((1, n, 2))
        B = np.concatenate([b1, b1])
        X, info, ri = _posv(jnp.asarray(A), jnp.asarray(B))
        tol = refine.tolerance(n, jnp.float64)
        assert not np.any(np.asarray(info))
        for i in range(2):
            if int(ri.converged[i]):
                assert _bwerr(A[i:i + 1], X[i:i + 1], B[i:i + 1]) < tol
            else:
                assert float(ri.resid[i]) > tol
            assert int(ri.iters[i]) <= refine.DEFAULT_MAX_ITERS

    def test_per_problem_freeze_is_independent(self):
        # batch mixing a clean problem with a beyond-envelope one: the
        # clean one converges, the bad one reports, neither perturbs the
        # other (the serve batching containment property)
        rng = np.random.default_rng(9)
        n = 16
        A = np.concatenate([_spd_cond(rng, n, 1e2), _spd_cond(rng, n, 1e8)])
        B = rng.standard_normal((2, n, 2))
        X, info, ri = _posv(jnp.asarray(A), jnp.asarray(B))
        conv = np.asarray(ri.converged)
        assert conv[0] == 1 and conv[1] == 0
        assert _bwerr(A[:1], X[:1], B[:1]) < refine.tolerance(n, jnp.float64)

    def test_nan_operand_freezes_immediately(self):
        rng = np.random.default_rng(11)
        n = 16
        A = _spd_cond(rng, n, 10.0, 2)
        B = rng.standard_normal((2, n, 2))
        B[0, 0, 0] = np.nan
        X, info, ri = _posv(jnp.asarray(A), jnp.asarray(B))
        # NaN error fails every comparison: not active, never converged —
        # and the clean batch neighbor is untouched by the poisoned one
        assert int(np.asarray(ri.converged)[0]) == 0
        assert int(np.asarray(ri.iters)[0]) == 0
        assert int(np.asarray(ri.converged)[1]) == 1

    def test_jit_and_fixed_output_arity(self):
        rng = np.random.default_rng(13)
        n = 16
        A = _spd_cond(rng, n, 1e2, 2)
        B = rng.standard_normal((2, n, 2))
        X, info, ri = _posv(jnp.asarray(A), jnp.asarray(B))
        assert ri.iters.shape == (2,) and ri.resid.dtype == jnp.float32
        assert _bwerr(A, X, B) < refine.tolerance(n, jnp.float64)


class TestRefineLstsq:
    def test_semi_normal_corrections_converge(self):
        rng = np.random.default_rng(17)
        m, n, k, batch = 96, 12, 2, 2
        A = rng.standard_normal((batch, m, n))
        B = rng.standard_normal((batch, m, k))
        X, info, ri = _lstsq(jnp.asarray(A), jnp.asarray(B))
        assert not np.any(np.asarray(info))
        assert np.all(np.asarray(ri.converged) == 1)
        for i in range(batch):
            Xr, *_ = np.linalg.lstsq(A[i], B[i], rcond=None)
            assert np.linalg.norm(np.asarray(X[i]) - Xr) \
                / np.linalg.norm(Xr) < 1e-9

    def test_gram_cond_squaring_still_refines(self):
        # cond(A) = 1e3 squares to 1e6 in the gram — hopeless for a plain
        # f32 normal-equations solve, recovered by the f64 sweeps
        rng = np.random.default_rng(19)
        m, n, k, batch = 96, 12, 2, 2
        A = np.empty((batch, m, n))
        for i in range(batch):
            Q0, _ = np.linalg.qr(rng.standard_normal((m, n)))
            V, _ = np.linalg.qr(rng.standard_normal((n, n)))
            A[i] = (Q0 * np.logspace(0, -3, n)) @ V.T
        B = rng.standard_normal((batch, m, k))
        X, _, ri = _lstsq(jnp.asarray(A), jnp.asarray(B))
        assert np.all(np.asarray(ri.converged) == 1)
        for i in range(batch):
            Xr, *_ = np.linalg.lstsq(A[i], B[i], rcond=None)
            assert np.linalg.norm(np.asarray(X[i]) - Xr) \
                / np.linalg.norm(Xr) < 1e-8


class TestRefineBlocktri:
    def _chain(self, rng, nblocks, b, batch=2):
        # diag-dominant blocks (the test_update chain recipe): ‖C‖ ~ 0.1
        # against diagonal eigenvalues >= 3 keeps the CHAIN SPD
        def blk():
            G = rng.standard_normal((b, b))
            return G @ G.T / b + 3.0 * np.eye(b)

        D = np.stack([
            np.stack([blk() for _ in range(nblocks)]) for _ in range(batch)
        ])
        C = 0.1 * rng.standard_normal((batch, nblocks, b, b))
        C[:, 0] = 0.0
        return D, C

    def _dense(self, D, C):
        nblocks, b = D.shape[0], D.shape[-1]
        n = nblocks * b
        A = np.zeros((n, n))
        for i in range(nblocks):
            A[i * b:(i + 1) * b, i * b:(i + 1) * b] = D[i]
            if i:
                A[i * b:(i + 1) * b, (i - 1) * b:i * b] = C[i]
                A[(i - 1) * b:i * b, i * b:(i + 1) * b] = C[i].T
        return A

    def test_matches_dense_solve(self):
        rng = np.random.default_rng(23)
        nblocks, b, batch, k = 3, 4, 2, 2
        D, C = self._chain(rng, nblocks, b, batch)
        B = rng.standard_normal((batch, nblocks, b, k))
        X, info, ri = _bt(jnp.asarray(D), jnp.asarray(C), jnp.asarray(B))
        assert not np.any(np.asarray(info))
        assert np.all(np.asarray(ri.converged) == 1)
        for i in range(batch):
            A = self._dense(D[i], C[i])
            Xr = np.linalg.solve(A, B[i].reshape(-1, k))
            assert np.linalg.norm(
                np.asarray(X[i], np.float64).reshape(-1, k) - Xr
            ) / np.linalg.norm(Xr) < 1e-10

    def test_resident_factor_reuse_is_bitwise(self):
        # the PR 12 composition: a resident (L, Wt) factor skips the
        # refactor entirely, and since the in-driver factor would compute
        # the identical values, the refined answers agree bitwise
        rng = np.random.default_rng(29)
        nblocks, b, batch, k = 3, 4, 2, 2
        D, C = self._chain(rng, nblocks, b, batch)
        B = rng.standard_normal((batch, nblocks, b, k))
        L, Wt, finfo = blocktri.factor(
            jnp.asarray(D, jnp.float32), jnp.asarray(C, jnp.float32),
            impl="xla")
        assert not np.any(np.asarray(finfo))
        X1, i1, r1 = _bt(jnp.asarray(D), jnp.asarray(C), jnp.asarray(B))
        X2, i2, r2 = _bt(jnp.asarray(D), jnp.asarray(C), jnp.asarray(B),
                         factor=(L, Wt))
        np.testing.assert_array_equal(np.asarray(X1), np.asarray(X2))
        assert not np.any(np.asarray(i2))  # resident factors install clean
        np.testing.assert_array_equal(
            np.asarray(r1.iters), np.asarray(r2.iters))


# --------------------------------------------------------------------------
# TSQR escalation: ops/tsqr + the in-graph rung + RobustInfo.gate
# --------------------------------------------------------------------------


def _illcond(m, n, cond, dtype, seed=0):
    rng = np.random.default_rng(seed)
    Q0, _ = np.linalg.qr(rng.standard_normal((m, n)))
    V, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.logspace(0, -np.log10(cond), n)
    return jnp.asarray(Q0 @ np.diag(s) @ V.T, dtype=dtype)


class TestTsqrEscalation:
    def test_escalate_recovers_cond_1e12(self):
        from capital_tpu.ops import tsqr as tsqr_mod

        A = _illcond(2048, 64, 1e12, jnp.float32)
        Q, R, ortho = recovery.tsqr_escalate(A)
        assert Q.dtype == recovery.escalation_dtype(jnp.float32)
        assert float(ortho) <= 1e-13  # the bench-refine gate
        assert float(tsqr_mod.ortho_gate(Q)) == pytest.approx(
            float(ortho), rel=1e-3)
        A64 = np.asarray(A, np.float64)
        resid = np.linalg.norm(
            A64 - np.asarray(Q, np.float64) @ np.asarray(R, np.float64))
        assert resid / np.linalg.norm(A64) < 1e-6  # f32 input rounding

    def test_in_graph_rung_recovers_beyond_envelope(self):
        # the f32 cond 1e12 case is FUNDAMENTALLY beyond the shift/sCQR3
        # envelope (test_robust BEYOND_ENVELOPE): without the rung it must
        # come back with the honest-failure sentinel and gate=GATE_ORTHO;
        # with RobustConfig.tsqr the f64 rung recovers it in-graph
        g = Grid.square(c=1, devices=[jax.devices()[0]])
        M, N = 384, 48
        A = _illcond(M, N, 1e12, jnp.float32)
        cfg0 = CacqrConfig(regime="1d", robust=RobustConfig())
        _, _, ri0 = qr.factor(g, A, cfg0)
        assert int(ri0.info) == N + 2
        assert int(ri0.gate) == GATE_ORTHO

        cfg = CacqrConfig(regime="1d", robust=RobustConfig(tsqr=True))
        Q, R, ri = qr.factor(g, A, cfg)
        assert int(ri.info) == 0
        assert int(ri.gate) == GATE_NONE
        tol64 = 100.0 * N * recovery.unit_roundoff(jnp.dtype(jnp.float64))
        assert float(ri.ortho) <= tol64
        resid = np.linalg.norm(
            np.asarray(A, np.float64)
            - np.asarray(Q, np.float64) @ np.asarray(R, np.float64))
        assert resid / np.linalg.norm(np.asarray(A, np.float64)) < 1e-4

    def test_healthy_path_gate_none(self):
        g = Grid.square(c=1, devices=[jax.devices()[0]])
        A = _illcond(384, 48, 1e3, jnp.float64)
        _, _, ri = qr.factor(
            g, A, CacqrConfig(regime="1d", robust=RobustConfig(tsqr=True)))
        assert int(ri.info) == 0 and int(ri.breakdown) == 0
        assert int(ri.gate) == GATE_NONE


# --------------------------------------------------------------------------
# accuracy_tier through serve (docs/SERVING.md "Accuracy tiers")
# --------------------------------------------------------------------------


CFG = ServeConfig(
    buckets=(16,), rows_buckets=(64,), nrhs_buckets=(2,),
    max_batch=2, max_delay_s=0.0, small_n_impl="vmap",
)


@pytest.fixture(scope="module")
def engine():
    return SolveEngine(cfg=CFG)


@pytest.fixture(scope="module")
def tier_problem():
    rng = np.random.default_rng(31)
    n, nrhs = 16, 2
    G = rng.standard_normal((n, n))
    A = (G @ G.T / n + 3.0 * np.eye(n)).astype(np.float32)
    B = rng.standard_normal((n, nrhs)).astype(np.float32)
    return A, B


class TestServeTiers:
    def test_guaranteed_tier_end_to_end(self, engine, tier_problem):
        A, B = tier_problem
        r = engine.solve("posv", A, B, accuracy_tier="guaranteed")
        assert r.ok, r.error
        Xr = np.linalg.solve(np.asarray(A, np.float64), B)
        # f32 request, f64 sweeps: the answer is f32-representation-grade
        assert np.asarray(r.x).dtype == np.float32
        np.testing.assert_allclose(np.asarray(r.x), Xr, rtol=2e-6, atol=2e-6)

    def test_fast_tier_downcast_factor(self, engine, tier_problem):
        A, B = tier_problem
        r = engine.solve("posv", A, B, accuracy_tier="fast")
        assert r.ok, r.error
        assert np.asarray(r.x).dtype == np.float32  # request dtype out
        Xr = np.linalg.solve(np.asarray(A, np.float64), B)
        # bf16 factor on a cond~3 operand: coarse but correct
        assert np.linalg.norm(np.asarray(r.x) - Xr) / np.linalg.norm(Xr) < 0.1

    def test_tiers_compile_separate_buckets_then_stay_warm(
            self, engine, tier_problem):
        A, B = tier_problem
        compiles = {}
        for tier in ("balanced", "fast", "guaranteed"):
            before = engine.cache_stats()["compiles"]
            assert engine.solve("posv", A, B, accuracy_tier=tier).ok
            compiles[tier] = engine.cache_stats()["compiles"] - before
        # each tier owns its executable (first use may compile; a tier
        # warmed by an earlier test legitimately reports 0)
        warm = engine.cache_stats()["compiles"]
        for _ in range(2):
            for tier in ("balanced", "fast", "guaranteed"):
                assert engine.solve("posv", A, B, accuracy_tier=tier).ok
        assert engine.cache_stats()["compiles"] == warm  # zero recompiles

    def test_nonconvergence_is_a_failed_response(self, engine):
        rng = np.random.default_rng(37)
        A = np.asarray(_spd_cond(rng, 16, 1e8)[0], np.float32)
        B = rng.standard_normal((16, 2)).astype(np.float32)
        r = engine.solve("posv", A, B, accuracy_tier="guaranteed")
        assert not r.ok
        assert "did not converge" in r.error

    def test_non_tier_op_rejects_vocabulary(self, engine, tier_problem):
        A, _ = tier_problem
        with pytest.raises(ValueError, match="accuracy_tier"):
            engine.solve("inv", A, accuracy_tier="guaranteed")

    def test_oversize_tiered_request_fails_loud(self, engine):
        # the oversize route's one tiered program is the guaranteed posv
        # (robust/refine.posv_dense); a 'fast' posv or a guaranteed lstsq
        # beyond the ladder has none and fails loud
        rng = np.random.default_rng(41)
        n = 64  # beyond the (16,) ladder
        G = rng.standard_normal((n, n)).astype(np.float32)
        A = (G @ G.T / n + 3.0 * np.eye(n, dtype=np.float32))
        B = rng.standard_normal((n, 2)).astype(np.float32)
        r = engine.solve("posv", A, B, accuracy_tier="fast")
        assert not r.ok
        assert "no oversize route" in r.error
        T = rng.standard_normal((4 * n, n)).astype(np.float32)
        r = engine.solve("lstsq", T, T[:, :2], accuracy_tier="guaranteed")
        assert not r.ok
        assert "no oversize route" in r.error

    def test_stats_carry_refine_block(self, engine):
        rec = engine.emit_stats()
        rs = rec["request_stats"]
        assert "refine" in rs  # guaranteed traffic happened above
        blk = rs["refine"]
        assert blk["requests"] == blk["converged"] + blk["nonconverged"]
        assert blk["nonconverged"] >= 1  # the loud-failure test landed here
        assert ledger.validate_request_stats(rs) == []

    def test_warmup_specs_accept_tier(self):
        eng = SolveEngine(cfg=CFG)
        n_compiles = eng.warmup(
            [("posv", (16, 16), (16, 2), "float32", "guaranteed")])
        assert n_compiles >= 1
        before = eng.cache_stats()["compiles"]
        rng = np.random.default_rng(43)
        G = rng.standard_normal((16, 16))
        A = (G @ G.T / 16 + 3.0 * np.eye(16)).astype(np.float32)
        B = rng.standard_normal((16, 2)).astype(np.float32)
        assert eng.solve("posv", A, B, accuracy_tier="guaranteed").ok
        assert eng.cache_stats()["compiles"] == before  # warmup covered it


class TestRouterTierPassThrough:
    def test_guaranteed_through_router(self):
        from capital_tpu.serve.replica import ThreadReplica
        from capital_tpu.serve.router import Router, RouterConfig

        import time

        router = Router(RouterConfig(policy="bucket_affinity"))
        router.add_replica(ThreadReplica("ra", CFG))
        router.add_replica(ThreadReplica("rb", CFG))
        try:
            rng = np.random.default_rng(47)
            G = rng.standard_normal((16, 16))
            A = (G @ G.T / 16 + 3.0 * np.eye(16)).astype(np.float32)
            B = rng.standard_normal((16, 2)).astype(np.float32)
            tickets = [
                router.submit("posv", A, B, accuracy_tier=t)
                for t in ("balanced", "guaranteed", "guaranteed")
            ]
            deadline = time.monotonic() + 120.0
            while not all(t.done for t in tickets):
                router.pump()
                assert time.monotonic() < deadline, "tickets never landed"
                time.sleep(1e-3)
            for t in tickets:
                res = t.result()
                assert res.ok, res.error
            Xr = np.linalg.solve(np.asarray(A, np.float64), B)
            np.testing.assert_allclose(
                np.asarray(tickets[1].result().x), Xr, rtol=2e-6, atol=2e-6)
            # the aggregate record (last) carries the merged refine block
            merged = router.emit_stats()[-1]["request_stats"]
            assert merged["refine"]["requests"] == 2
            assert merged["refine"]["converged_frac"] == 1.0
        finally:
            router.stop()


# --------------------------------------------------------------------------
# stats / obs seams
# --------------------------------------------------------------------------


class TestStatsRefineBlock:
    def test_absent_without_guaranteed_traffic(self):
        c = stats.Collector()
        c.record_request("posv", 0.01, ok=True)
        assert "refine" not in c.snapshot()

    def test_block_contents_and_nan_filter(self):
        c = stats.Collector()
        c.record_request("posv", 0.01, ok=True)
        c.note_refine(2, True, 1e-15)
        c.note_refine(3, True, 4e-15)
        c.note_refine(8, False, float("nan"))  # factor breakdown shape
        blk = c.snapshot()["refine"]
        assert blk["requests"] == 3
        assert blk["converged"] == 2 and blk["nonconverged"] == 1
        assert blk["converged_frac"] == pytest.approx(0.6667, abs=1e-4)
        assert blk["iters_max"] == 8
        # NaN resid counts as nonconverged but stays out of the max
        assert blk["resid_max"] == pytest.approx(4e-15)
        assert blk["iters"]["p50"] >= 2.0

    def test_merge_sums_counts_and_maxes_tails(self):
        c = stats.Collector()
        c.record_request("posv", 0.01, ok=True)
        c.note_refine(2, True, 1e-15)
        s1 = c.snapshot()
        c2 = stats.Collector()
        c2.record_request("posv", 0.01, ok=True)
        c2.note_refine(5, False, 3e-12)
        s2 = c2.snapshot()
        merged = stats.merge_snapshots([s1, s2])["refine"]
        assert merged["requests"] == 2
        assert merged["converged"] == 1 and merged["nonconverged"] == 1
        assert merged["converged_frac"] == pytest.approx(0.5)
        assert merged["iters_max"] == 5
        assert merged["resid_max"] == pytest.approx(3e-12)
        # replicas without guaranteed traffic don't erase the block
        c3 = stats.Collector()
        c3.record_request("posv", 0.01, ok=True)
        assert "refine" in stats.merge_snapshots([s1, c3.snapshot()])
        assert "refine" not in stats.merge_snapshots(
            [c3.snapshot(), c3.snapshot()])

    def test_validate_request_stats_refine_block(self):
        c = stats.Collector()
        c.record_request("posv", 0.01, ok=True)
        c.note_refine(2, True, 1e-15)
        good = c.snapshot()
        assert ledger.validate_request_stats(good) == []
        bad = dict(good, refine=dict(good["refine"], converged_frac=1.5))
        assert any("converged_frac" in p
                   for p in ledger.validate_request_stats(bad))
        bad = dict(good, refine=dict(good["refine"], iters_max=-1))
        assert any("iters_max" in p
                   for p in ledger.validate_request_stats(bad))


def _refine_measured(**over):
    m = {
        "metric": "refine_speedup", "value": 0.008, "unit": "TFLOP/s",
        "n": 1024, "nrhs": 4, "batch": 4,
        "factor_dtype": "float32", "correction_dtype": "float64",
        "speedup": 1.8, "refined_ms": 220.0, "baseline_ms": 130.0,
        "end_to_end_speedup": 0.59, "resid_ratio": 1.7, "iters": 3,
        "tsqr_ortho": 4.6e-16,
        "wall_ms": {"p50": 266.0, "p95": 268.0, "p99": 268.0},
        "serve_smoke": {"requests": 24, "recompiles": 0},
    }
    m.update(over)
    return m


class TestValidateRefineMeasured:
    def test_valid(self):
        assert ledger.validate_refine_measured(_refine_measured()) == []
        bare = _refine_measured()
        del bare["tsqr_ortho"], bare["serve_smoke"]
        assert ledger.validate_refine_measured(bare) == []

    @pytest.mark.parametrize("field,value,frag", [
        ("n", 0, "n must be"),
        ("factor_dtype", "", "factor_dtype"),
        ("speedup", -1.0, "speedup must be"),
        ("resid_ratio", -0.5, "resid_ratio"),
        ("iters", 2.5, "iters"),
        ("tsqr_ortho", -1e-16, "tsqr_ortho"),
        ("wall_ms", {"p50": 1.0}, "wall_ms.p9"),
        ("serve_smoke", {"requests": 24, "recompiles": -1}, "recompiles"),
    ])
    def test_invalid(self, field, value, frag):
        m = _refine_measured(**{field: value})
        assert any(frag in p for p in ledger.validate_refine_measured(m))

    def test_diff_validates_refine_records(self):
        rec = {"manifest": {"schema_version": ledger.SCHEMA_VERSION,
                            "device": "cpu"},
               "measured": _refine_measured(speedup=-1.0)}
        with pytest.raises(ledger.LedgerIncompatible, match="refine"):
            ledger.diff([rec], [rec])


class TestServeReportRefineGates:
    def _emit(self, path, iters=(2, 3), nonconv=0):
        c = stats.Collector()
        c.record_request("posv", 0.01, ok=True)
        for it in iters:
            c.note_refine(it, True, 1e-15)
        for _ in range(nonconv):
            c.note_refine(8, False, 1e-3)
        c.emit(str(path))

    def test_gates_pass(self, tmp_path, capsys):
        path = tmp_path / "serve.jsonl"
        self._emit(path)
        assert obs_main.main(["serve-report", str(path),
                              "--max-refine-iters", "6",
                              "--min-converged-frac", "0.99"]) == 0
        assert "refine requests=2" in capsys.readouterr().out

    def test_iters_gate_fails(self, tmp_path, capsys):
        path = tmp_path / "serve.jsonl"
        self._emit(path, iters=(2, 7))
        assert obs_main.main(["serve-report", str(path),
                              "--max-refine-iters", "6"]) == 1
        assert "iters_max" in capsys.readouterr().err

    def test_converged_frac_gate_fails(self, tmp_path, capsys):
        path = tmp_path / "serve.jsonl"
        self._emit(path, nonconv=1)
        assert obs_main.main(["serve-report", str(path),
                              "--min-converged-frac", "0.99"]) == 1
        assert "converged_frac" in capsys.readouterr().err

    def test_fails_loudly_when_block_missing(self, tmp_path, capsys):
        path = tmp_path / "serve.jsonl"
        c = stats.Collector()
        c.record_request("posv", 0.01, ok=True)
        c.emit(str(path))
        assert obs_main.main(["serve-report", str(path),
                              "--max-refine-iters", "6"]) == 1
        assert "no record carries a refine block" in capsys.readouterr().err


class TestLintTarget:
    def test_refine_target_registered(self):
        from capital_tpu.lint import targets

        assert "refine" in targets.TARGET_NAMES
