"""End-to-end collective audit of the explicit schedule (VERDICT r2 #5).

TestExplicitEmission (test_summa.py) pins single gemms; these tests pin the
collective inventory of WHOLE programs — a full cholinv factor and a
dist-regime CQR2 — compiled for the 2x2x{1,2} grids, against (a) structural
invariants of the schedule and (b) exact emitted-count snapshots.

Since the obs layer landed, the inventory is taken through
capital_tpu.obs.xla_audit (the library the ledger and the audit CLI use)
rather than a private regex here — so these pins also exercise the
production scan path.

Why snapshots and not model equality: the Recorder prices the *schedule's*
collectives (panel gathers / masked-psum broadcasts / depth collects /
base-case replications — e.g. 43 for the c=2 factor below), while the
compiled HLO additionally carries GSPMD data-motion the model deliberately
does not book as collectives (collective-permutes from sharding
constraints, window slices and dynamic-update-slices of face-sharded
buffers, base-case panel replication gathers).  Those extras are a
*property of the schedule too*: a change that silently adds collectives —
the failure this test exists to catch — moves these counts.  When a
deliberate schedule change trips this test, re-run the audit probe
(docstring of each test prints the procedure) and re-pin with the new
derivation.

Invariants (version-robust):
  * no all-to-all anywhere (the schedule never uses one);
  * c=1 explicit cholinv emits ZERO all-reduce — the contraction path is
    pure ring gathers and the default base-case policy factors redundantly
    (any all-reduce appearing means a psum snuck into the c=1 path);
  * c=2 emits both gathers (window/replication motion) and all-reduces
    (masked-psum panel broadcasts + depth collects + base-case bcasts).
"""

import jax
import jax.numpy as jnp
import pytest

from capital_tpu.models import cholesky, qr
from capital_tpu.models.cholesky import CholinvConfig
from capital_tpu.models.qr import CacqrConfig
from capital_tpu.obs import xla_audit
from capital_tpu.parallel.topology import Grid
from capital_tpu.utils import rand48


def _emitted(fn, arg) -> dict[str, int]:
    return xla_audit.audit(fn, arg).collective_counts


def _model_collectives(fn, arg) -> int:
    rec = xla_audit.trace_model(fn, arg)
    return sum(s.collectives for s in rec.stats.values())


def _counts(ag=0, ar=0, rs=0, cp=0, aa=0) -> dict[str, int]:
    return {
        "all-gather": ag, "all-reduce": ar, "reduce-scatter": rs,
        "collective-permute": cp, "all-to-all": aa,
    }


class TestCholinvAudit:
    def test_c1_factor_inventory(self, grid2x2x1):
        g = grid2x2x1
        A = jax.device_put(jnp.asarray(rand48.symmetric(64)), g.face_sharding())
        cfg = CholinvConfig(base_case_dim=16, mode="explicit")
        fn = lambda a: cholesky.factor(g, a, cfg)
        got = _emitted(fn, A)
        # schedule invariants
        assert got["all-to-all"] == 0
        assert got["all-reduce"] == 0, (
            "the c=1 explicit factor has no psum in its schedule (ring "
            "gathers + redundant base cases); an all-reduce appeared: "
            f"{got}"
        )
        # snapshot (jax 0.9.0, 8-dev CPU mesh): 44 gathers = the model's
        # 31 schedule collectives (6 trsm + 9 tmu + 12 inv ring gathers +
        # 4 base-case replications) plus GSPMD window materializations; 55
        # permutes are sharding-constraint/window motion (jax 0.4.37
        # lowered the same schedule to 51 — the model total did not move,
        # so the 51 → 55 step is lowering drift).  Re-pin only after
        # re-deriving (see module docstring).
        assert _model_collectives(fn, A) == 31
        assert got == _counts(ag=44, cp=55), got

    def test_c1_drift_totals(self, grid2x2x1):
        # the drift report must carry the SAME totals the snapshots pin —
        # model 31 vs compiled 99 (44 gathers + 55 permutes) — and every
        # phase lands in one of the three classifications (drift() is the
        # gate `make audit` runs).  audit() runs FIRST here on the same fn
        # object: trace_model defeating jax's fn-identity trace cache is
        # part of what this pins (an empty model Recorder after a compile
        # of the same function was a real bug).
        g = grid2x2x1
        A = jax.device_put(jnp.asarray(rand48.symmetric(64)), g.face_sharding())
        cfg = CholinvConfig(base_case_dim=16, mode="explicit")
        fn = lambda a: cholesky.factor(g, a, cfg)
        rep = xla_audit.drift(xla_audit.audit(fn, A), xla_audit.trace_model(fn, A))
        assert rep.model_collectives_total == 31
        assert rep.compiled_collectives_total == 99
        kinds = {p.classification for p in rep.phases}
        assert kinds <= {xla_audit.WITHIN, xla_audit.UNDERCOUNT, xla_audit.EXTRA}

    def test_c2_factor_inventory(self, grid2x2x2):
        g = grid2x2x2
        A = jax.device_put(jnp.asarray(rand48.symmetric(64)), g.face_sharding())
        cfg = CholinvConfig(base_case_dim=16, mode="explicit")
        fn = lambda a: cholesky.factor(g, a, cfg)
        got = _emitted(fn, A)
        assert got["all-to-all"] == 0
        assert got["all-reduce"] > 0  # masked-psum bcasts + depth collects
        # model: 43 = 4 factor_diag + 9 trsm + 12 tmu + 18 inv
        assert _model_collectives(fn, A) == 43
        # snapshot (jax 0.9.0, 8-dev CPU mesh; jax 0.4.37 gave ar=36
        # cp=51 for the same schedule — GSPMD lowering drift of the depth
        # motion, not a schedule change: the model total above is
        # version-independent and unchanged)
        assert got == _counts(ag=20, ar=32, cp=55), got

    def test_c2_skipping_does_not_change_collectives(self, grid2x2x2):
        # dead-segment skipping guards ONLY local matmuls; disabling the
        # triangular flags (dense gemm of the same shapes) must not change
        # the collective inventory of a single explicit product — a cond
        # around a collective would desynchronize the mesh and typically
        # shows up here as a different gather/psum count
        from capital_tpu.parallel import summa

        g = grid2x2x2
        M = jax.device_put(jnp.asarray(rand48.random(64, 64, key=3)), g.face_sharding())
        tri = _emitted(
            lambda a: summa.trmm(
                g, a, a, summa.TrmmArgs(side="L", uplo="U"), mode="explicit"
            ),
            M,
        )
        dense = _emitted(
            lambda a: summa.gemm(g, a, a, mode="explicit"), M
        )
        assert tri["all-reduce"] == dense["all-reduce"]
        assert tri["all-gather"] == dense["all-gather"]


class TestCacqrAudit:
    def test_dist_cqr2_inventory(self, grid2x2x2):
        g = grid2x2x2
        cfg = CacqrConfig(
            num_iter=2, regime="dist", mode="explicit",
            cholinv=CholinvConfig(base_case_dim=16, mode="explicit"),
        )
        A = jax.device_put(
            jnp.asarray(rand48.random(256, 64, key=9)), g.face_sharding()
        )
        fn = lambda a: qr.factor(g, a, cfg)
        got = _emitted(fn, A)
        assert got["all-to-all"] == 0
        # model: 103 = 8 gram + (43 + 43 both sweeps' cholinv) + 6 formR +
        # 3 merge — the two full cholinv factors dominate, as upstream
        # (cacqr.hpp:103)
        assert _model_collectives(fn, A) == 103
        # snapshot (jax 0.9.0, 8-dev CPU mesh; jax 0.4.37 gave ar=87
        # cp=106 — the same GSPMD lowering drift as the c=2 factor row;
        # the model total above is version-independent and unchanged)
        assert got == _counts(ag=40, ar=74, cp=114), got
