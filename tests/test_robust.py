"""Robustness tests: breakdown detection, shifted-CholeskyQR recovery, and
the honest-failure contract (docs/ROBUSTNESS.md).

Calibrated on the CPU/x64 rig at m=384, n=48, seed 0: f64 recovers fully at
cond=1e12 (one shifted sweep contracts cond by ~7e-6, then sCQR3 polishes);
f32 recovers at cond=1e4 but is FUNDAMENTALLY beyond the shift envelope at
cond>=1e6 (contraction/sweep is only ~0.165 and repeated shifts stall), so
those cases must come back finite with the `info = n + 2` sentinel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import cholesky, qr
from capital_tpu.models.cholesky import CholinvConfig
from capital_tpu.models.qr import CacqrConfig
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust import RobustConfig, detect, recovery

M, N = 384, 48


def _illcond(m, n, cond, dtype, seed=0):
    """Tall matrix with a log-spaced spectrum spanning exactly `cond`."""
    rng = np.random.default_rng(seed)
    Q0, _ = np.linalg.qr(rng.standard_normal((m, n)))
    V, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.logspace(0, -np.log10(cond), n)
    return jnp.asarray(Q0 @ np.diag(s) @ V.T, dtype=dtype)


def _grid1():
    return Grid.square(c=1, devices=[jax.devices()[0]])


def _cfg(regime, robust=True):
    return CacqrConfig(
        regime=regime, robust=RobustConfig() if robust else None
    )


def _tol(dtype):
    return 100.0 * N * recovery.unit_roundoff(jnp.dtype(dtype))


# --------------------------------------------------------------------------
# detection
# --------------------------------------------------------------------------


class TestDetect:
    def test_healthy(self):
        R = jnp.triu(jnp.eye(4) + 0.1)
        assert int(detect.factor_info(R)) == 0

    def test_first_bad_diagonal(self):
        R = jnp.diag(jnp.array([1.0, 2.0, jnp.nan, -1.0]))
        assert int(detect.factor_info(R)) == 3  # 1-based, FIRST bad entry

    def test_nonpositive_diagonal(self):
        R = jnp.diag(jnp.array([1.0, 0.0, 2.0]))
        assert int(detect.factor_info(R)) == 2

    def test_offdiag_nonfinite(self):
        R = jnp.eye(4).at[0, 3].set(jnp.inf)
        assert int(detect.factor_info(R)) == 5  # n + 1

    def test_nan_filled_cholesky_is_flagged(self):
        # the real failure shape: lax.linalg.cholesky NaN-fills silently
        G = jnp.eye(4).at[0, 0].set(-1.0)
        R = jnp.linalg.cholesky(G).T
        assert int(detect.factor_info(R)) != 0

    def test_jit_and_ops_with_info(self):
        from capital_tpu.ops import lapack

        G = jnp.asarray(np.diag([4.0, 1.0, -9.0]), dtype=jnp.float64)
        T, info = jax.jit(lambda a: lapack.potrf(a, with_info=True))(G)
        assert int(info) != 0
        G2 = jnp.eye(3, dtype=jnp.float64) * 4.0
        _, _, info2 = lapack.potrf_trtri(G2, with_info=True)
        assert int(info2) == 0


class TestGuardedChol:
    def test_healthy_no_shift(self):
        from capital_tpu.ops import lapack

        A = _illcond(64, 8, 10.0, jnp.float64)
        G = A.T @ A
        R, Rinv, ev = recovery.guarded_chol(
            G, 64, RobustConfig(), lapack.potrf_trtri
        )
        assert int(ev.info) == 0 and float(ev.sigma) == 0.0
        np.testing.assert_allclose(
            np.asarray(R.T @ R), np.asarray(G), atol=1e-12
        )

    def test_breakdown_shifts_and_repairs(self):
        from capital_tpu.ops import lapack

        A = _illcond(64, 8, 1e12, jnp.float64)
        G = (A.T @ A).astype(jnp.float64)
        R, Rinv, ev = recovery.guarded_chol(
            G, 64, RobustConfig(), lapack.potrf_trtri
        )
        assert int(ev.info) != 0          # raw factorization broke
        assert float(ev.sigma) > 0.0      # a shift was applied
        assert int(ev.info_after) == 0    # shifted factorization is clean
        assert bool(jnp.all(jnp.isfinite(R)))

    def test_indefinite_stays_flagged(self):
        # the shift repairs roundoff-induced breakdown only; a genuinely
        # indefinite matrix must keep a nonzero residual info
        from capital_tpu.ops import lapack

        G = jnp.asarray(np.diag([1.0, -5.0, 2.0]), dtype=jnp.float64)
        _, _, ev = recovery.guarded_chol(G, 3, RobustConfig(), lapack.potrf_trtri)
        assert int(ev.info) != 0 and int(ev.info_after) != 0


# --------------------------------------------------------------------------
# qr.factor under RobustConfig — the acceptance matrix
# --------------------------------------------------------------------------


HEALTHY = [
    (1e3, jnp.float32),
    (1e3, jnp.float64),
    (1e6, jnp.float64),
]
RECOVERS = [
    (1e4, jnp.float32),
    (1e12, jnp.float64),
]
BEYOND_ENVELOPE = [  # f32 shift stall: finite + sentinel, never NaN
    (1e6, jnp.float32),
    (1e12, jnp.float32),
]


class TestRobustQR:
    @pytest.mark.parametrize("cond,dtype", HEALTHY)
    @pytest.mark.parametrize("regime", ["1d", "dist"])
    def test_healthy_matches_unguarded(self, cond, dtype, regime):
        g = _grid1()
        A = _illcond(M, N, cond, dtype)
        Q, R, ri = qr.factor(g, A, _cfg(regime))
        assert int(ri.breakdown) == 0
        assert int(ri.info) == 0
        assert float(ri.sigma) == 0.0
        Q0, R0 = qr.factor(g, A, _cfg(regime, robust=False))
        np.testing.assert_allclose(np.asarray(Q), np.asarray(Q0))
        np.testing.assert_allclose(np.asarray(R), np.asarray(R0))

    @pytest.mark.parametrize("cond,dtype", RECOVERS)
    @pytest.mark.parametrize("regime", ["1d", "dist"])
    def test_breakdown_recovers_to_tolerance(self, cond, dtype, regime):
        g = _grid1()
        A = _illcond(M, N, cond, dtype)
        Q, R, ri = qr.factor(g, A, _cfg(regime))
        assert int(ri.breakdown) > 0
        assert int(ri.shifted) > 0
        assert float(ri.sigma) > 0.0
        assert int(ri.escalated) == 1
        assert int(ri.info) == 0
        assert bool(jnp.all(jnp.isfinite(Q)))
        # the gate RobustInfo reports is the post-escalation measurement
        assert 0.0 <= float(ri.ortho) <= _tol(dtype)
        # and it agrees with a from-scratch measurement of the returned Q
        I = np.eye(N)
        gate = np.linalg.norm(I - np.asarray(Q, np.float64).T @ np.asarray(Q, np.float64)) / np.sqrt(N)
        assert gate <= _tol(dtype)
        # R still reproduces A
        resid = np.linalg.norm(np.asarray(A, np.float64) - np.asarray(Q, np.float64) @ np.asarray(R, np.float64))
        rtol = 1e-4 if dtype == jnp.float32 else 1e-10
        assert resid / np.linalg.norm(np.asarray(A, np.float64)) < rtol

    @pytest.mark.parametrize("cond,dtype", BEYOND_ENVELOPE)
    def test_beyond_envelope_finite_with_sentinel(self, cond, dtype):
        g = _grid1()
        A = _illcond(M, N, cond, dtype)
        Q, R, ri = qr.factor(g, A, _cfg("1d"))
        assert bool(jnp.all(jnp.isfinite(Q)))     # no NaN propagation, ever
        assert int(ri.breakdown) > 0
        assert int(ri.info) == N + 2              # honest-failure sentinel
        assert float(ri.ortho) > _tol(dtype)      # the gate says why

    def test_f64_cond1e12_nans_without_robust(self):
        # the baseline behavior the tentpole exists to fix
        g = _grid1()
        A = _illcond(M, N, 1e12, jnp.float64)
        Q, R = qr.factor(g, A, _cfg("1d", robust=False))
        assert not bool(jnp.all(jnp.isfinite(Q)))

    def test_jit_roundtrip(self):
        g = _grid1()
        A = _illcond(M, N, 1e12, jnp.float64)
        cfg = _cfg("1d")
        Q, R, ri = jax.jit(lambda a: qr.factor(g, a, cfg))(A)
        assert int(ri.breakdown) > 0 and int(ri.info) == 0
        assert float(ri.ortho) <= _tol(jnp.float64)

    def test_multidevice_1d_routes_unfused(self, grid_flat8):
        g = grid_flat8
        A = jax.device_put(
            _illcond(1024, 64, 1e12, jnp.float64), g.rows_sharding()
        )
        Q, R, ri = qr.factor(g, A, _cfg("1d"))
        assert int(ri.breakdown) > 0 and int(ri.info) == 0
        assert float(ri.ortho) <= 100.0 * 64 * recovery.unit_roundoff(
            jnp.dtype(jnp.float64)
        )

    @pytest.mark.skipif(
        not hasattr(jax, "typeof"),
        reason="fused qr tier needs a newer jax (jax.typeof)",
    )
    def test_fused_regime_robust(self):
        g = _grid1()
        A = _illcond(M, N, 1e12, jnp.float64)
        cfg = CacqrConfig(regime="1d", mode="pallas", robust=RobustConfig())
        Q, R, ri = qr.factor(g, A, cfg)
        assert int(ri.info) == 0 and int(ri.breakdown) > 0


class TestRobustCholesky:
    def test_non_spd_flags_instead_of_nan(self, grid2x2x1):
        n = 64
        rng = np.random.default_rng(3)
        Mx = rng.standard_normal((n, n))
        A = jnp.asarray(Mx + Mx.T, dtype=jnp.float64)  # symmetric, indefinite
        cfg = CholinvConfig(robust=RobustConfig())
        R, Rinv, info = cholesky.factor(grid2x2x1, A, cfg)
        assert int(info) != 0

    def test_spd_info_zero_and_values_unchanged(self, grid2x2x1):
        from capital_tpu.utils.residual import spd_operand

        A = spd_operand(64, jnp.float64)
        cfg = CholinvConfig(robust=RobustConfig())
        R, Rinv, info = cholesky.factor(grid2x2x1, A, cfg)
        assert int(info) == 0
        R0, Rinv0 = cholesky.factor(grid2x2x1, A, CholinvConfig())
        np.testing.assert_allclose(np.asarray(R), np.asarray(R0))


class TestRegimeValidation:
    def test_unknown_regime_raises(self):
        g = _grid1()
        A = _illcond(128, 16, 10.0, jnp.float64)
        with pytest.raises(ValueError, match="unknown regime"):
            qr.factor(g, A, CacqrConfig(regime="2d"))

    def test_pick_regime_rejects_directly(self):
        with pytest.raises(ValueError, match="unknown regime"):
            qr._pick_regime(_grid1(), 64, CacqrConfig(regime="bogus"))


class TestLedgerExemption:
    def test_recovery_record_roundtrips_diff(self):
        # satellite 6: a breakdown-recovery record must not read as a
        # metric regression, while the same drop without the status must
        from capital_tpu.obs import ledger

        man = ledger.manifest(dtype="float64", config_id="robust_rt")
        base = ledger.record(
            "bench:cacqr", dict(man),
            measured={"metric": "cacqr", "value": 10.0, "unit": "TFLOP/s"},
        )
        recov = ledger.record(
            "bench:cacqr", dict(man),
            measured={"metric": "cacqr", "value": 4.0, "unit": "TFLOP/s"},
            robust={"breakdown": 1, "shifted": 1, "escalated": 1, "info": 0},
            event={"status": "recovered"},
        )
        assert ledger.diff([base], [recov]) == []
        plain = dict(recov)
        plain.pop("robust")
        plain.pop("event")
        assert ledger.diff([base], [plain])  # the check is alive

    def test_robust_gate_cli(self):
        from capital_tpu.obs.__main__ import main

        assert main(["robust-gate"]) == 0
