"""The fused recursion-tail megakernel (pallas_tpu.fused_tail) and its
trace-time gates, plus the double-buffered base-case write-back.

The claims under test, each a contract the 3%-gap work leans on:

* fusing a plan() subtree into ONE pallas_call changes the launch
  structure, NOT the numbers — fused and unfused factors agree at the
  compute dtype's tolerance across depths, dtypes and window positions;
* the kernel symmetrizes from the UPPER half, so Schur windows carrying
  garbage below the diagonal factor identically to fully-symmetric input
  (the "both uplos" contract of the in-kernel sweep);
* f64 falls back to the unfused recursion AT TRACE TIME (the PR 6
  dispatch-gate lesson) — bitwise-equal to tail_fuse_depth=0;
* a fully-fused factor really is exactly one pallas_call in the jaxpr
  (with out_buffers threading, which removes the dead-lower zero inits);
* breakdown info survives fusion: the in-kernel 0/k/n+1 status combines
  with the post-hoc scan so a fault inside a fused window reports the
  TRUE pivot, not the NaN backward-pollution position, and the dead
  lower triangle stays exactly zero even under a fault;
* the VMEM eligibility envelope has the boundary the config comments
  promise (n=512 f32 in, n=768 out, interpret bypasses);
* transpose_pair, the base case's write-back, is bitwise-equal to the
  sequential two-kernel spelling.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import cholesky
from capital_tpu.models.cholesky import CholinvConfig
from capital_tpu.ops import batched_small, pallas_tpu
from capital_tpu.parallel.topology import Grid
from capital_tpu.robust import RobustConfig
from capital_tpu.utils import rand48


@pytest.fixture(scope="module")
def grid1():
    return Grid.square(c=1, devices=jax.devices("cpu")[:1])


def _spd(n, dtype=jnp.float32):
    return jnp.asarray(rand48.symmetric(n)).astype(dtype)


def _count_pallas_calls(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                total += _count_pallas_calls(v.jaxpr)
    return total


class TestFusedUnfusedParity:
    @pytest.mark.parametrize("depth", [1, 2])
    @pytest.mark.parametrize("mode", ["pallas", "xla"])
    def test_f32(self, grid1, depth, mode):
        A = _spd(512)
        base = CholinvConfig(base_case_dim=128, mode=mode)
        fused = CholinvConfig(base_case_dim=128, mode=mode,
                              tail_fuse_depth=depth)
        R0, RI0 = jax.jit(lambda a: cholesky.factor(grid1, a, base))(A)
        R1, RI1 = jax.jit(lambda a: cholesky.factor(grid1, a, fused))(A)
        np.testing.assert_allclose(np.asarray(R1), np.asarray(R0),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(RI1), np.asarray(RI0),
                                   rtol=1e-4, atol=1e-5)

    def test_bf16(self, grid1):
        A = _spd(256, jnp.bfloat16)
        base = CholinvConfig(base_case_dim=128)
        fused = CholinvConfig(base_case_dim=128, tail_fuse_depth=1)
        R0, _ = cholesky.factor(grid1, A, base)
        R1, _ = cholesky.factor(grid1, A, fused)
        # both paths compute in f32 and cast once at the write-back; the
        # bf16 rounding of two algebraically-equal sweeps stays within a
        # couple of ulps
        np.testing.assert_allclose(
            np.asarray(R1, np.float32), np.asarray(R0, np.float32),
            rtol=3e-2, atol=3e-2,
        )

    def test_partial_depth_fuses_subtrees_only(self, grid1):
        # depth=1 at n=512/bc=128 fuses the 256-windows, leaving the
        # top-level trsm/syrk/completion unfused — the mixed schedule
        # must still agree with both pure spellings
        A = _spd(512)
        cfg = CholinvConfig(base_case_dim=128, tail_fuse_depth=1)
        node = cholesky.plan(512, cfg)
        assert not cholesky._tail_fusible(
            grid1, A, 0, node, cfg, True, jnp.zeros((512, 512)), 0
        )
        assert cholesky._tail_fusible(
            grid1, A, 0, node.top[0], cfg, False, jnp.zeros((512, 512)), 0
        )


class TestSymmetrization:
    def test_garbage_lower_half_ignored(self):
        # Schur windows carry only a valid upper triangle; the kernel
        # must symmetrize from it, so poisoning the strict lower half
        # (even with NaN) cannot change the result
        A = _spd(128)
        r, c = np.tril_indices(128, -1)
        bad = np.asarray(A).copy()
        bad[r, c] = np.nan
        Rp = jnp.zeros((128, 128), jnp.float32)
        RIp = jnp.zeros((128, 128), jnp.float32)
        outs = []
        for w in (A, jnp.asarray(bad)):
            R, RI, info = pallas_tpu.fused_tail(
                w, Rp, RIp, off=0, n=128, dest=0, precision="highest"
            )
            assert int(info) == 0
            outs.append((np.asarray(R), np.asarray(RI)))
        np.testing.assert_array_equal(outs[0][0], outs[1][0])
        np.testing.assert_array_equal(outs[0][1], outs[1][1])

    def test_chol_uplo_agreement(self):
        # the sweep the kernel reuses: U and L factors of the same S are
        # transposes of each other
        S = jnp.asarray(rand48.symmetric(64)).astype(jnp.float32)
        R, iu = batched_small._chol(S, uplo="U", block=8,
                                    precision="highest")
        L, il = batched_small._chol(S, uplo="L", block=8,
                                    precision="highest")
        assert int(iu) == 0 and int(il) == 0
        np.testing.assert_allclose(np.asarray(R), np.asarray(L).T,
                                   rtol=1e-5, atol=1e-6)


class TestF64Fallback:
    def test_gate_rejects_f64_at_trace_time(self, grid1):
        A = _spd(256, jnp.float64)
        cfg = CholinvConfig(base_case_dim=128, tail_fuse_depth=2)
        node = cholesky.plan(256, cfg)
        assert not cholesky._tail_fusible(
            grid1, A, 0, node, cfg, True, jnp.zeros((256, 256), A.dtype), 0
        )

    def test_f64_bitwise_equals_unfused(self, grid1):
        A = _spd(256, jnp.float64)
        R0, RI0 = cholesky.factor(
            grid1, A, CholinvConfig(base_case_dim=128)
        )
        R1, RI1 = cholesky.factor(
            grid1, A, CholinvConfig(base_case_dim=128, tail_fuse_depth=2)
        )
        np.testing.assert_array_equal(np.asarray(R1), np.asarray(R0))
        np.testing.assert_array_equal(np.asarray(RI1), np.asarray(RI0))


class TestOnePallasCall:
    def test_fully_fused_factor_is_one_kernel(self, grid1):
        # depth=1 at n=bc<<1 fuses the whole tree from the root; with
        # out_buffers threading (no dead-lower zero-init kernels) the
        # factor lowers to EXACTLY one pallas_call
        n = 256
        cfg = CholinvConfig(base_case_dim=128, tail_fuse_depth=1)
        A = _spd(n)
        bufs = cholesky.factor_buffers(grid1, n, jnp.float32, cfg)
        jx = jax.make_jaxpr(
            lambda a, bs: cholesky.factor(grid1, a, cfg, out_buffers=bs)
        )(A, bufs)
        assert _count_pallas_calls(jx.jaxpr) == 1
        # and the unfused spelling of the same problem is strictly wider
        cfg0 = CholinvConfig(base_case_dim=128)
        jx0 = jax.make_jaxpr(
            lambda a, bs: cholesky.factor(grid1, a, cfg0, out_buffers=bs)
        )(A, bufs)
        assert _count_pallas_calls(jx0.jaxpr) > 1


class TestRobustInfo:
    def _factor_info(self, grid, A, depth):
        cfg = CholinvConfig(base_case_dim=128, tail_fuse_depth=depth,
                            robust=RobustConfig())
        _, _, info = cholesky.factor(grid, A, cfg)
        return int(info)

    def test_healthy_reports_zero(self, grid1):
        A = _spd(256)
        assert self._factor_info(grid1, A, 1) == 0

    def test_fault_in_left_fused_window(self, grid1):
        # breaking pivot 41 (0-based 40) inside the first fused window:
        # the in-kernel info must report 41, not the backward-pollution
        # position the post-hoc NaN scan would see
        A = np.asarray(_spd(256)).copy()
        A[40, 40] = -1.0
        assert self._factor_info(grid1, jnp.asarray(A), 1) == 41

    def test_fault_in_right_subtree(self, grid1):
        A = np.asarray(_spd(256)).copy()
        A[200, 200] = -1.0
        assert self._factor_info(grid1, jnp.asarray(A), 1) == 201

    def test_fused_info_beats_the_polluted_scan(self, grid1):
        # the unfused path only has the post-hoc diagonal scan, and the
        # sweep's backward NaN pollution drags its verdict to an earlier
        # position; the fused path's in-kernel info recovers the TRUE
        # pivot.  Both must flag SOME fault — detection never regresses.
        A = np.asarray(_spd(256)).copy()
        A[40, 40] = -1.0
        fused = self._factor_info(grid1, jnp.asarray(A), 1)
        unfused = self._factor_info(grid1, jnp.asarray(A), 0)
        assert fused == 41
        assert 0 < unfused <= 41

    def test_lower_triangle_stays_zero_under_fault(self, grid1):
        # the kernel's write-back mask contains the contamination: even
        # with garbage filling the fused window's sweep, nothing below
        # the diagonal escapes
        A = np.asarray(_spd(256)).copy()
        A[40, 40] = -1.0
        cfg = CholinvConfig(base_case_dim=128, tail_fuse_depth=1,
                            robust=RobustConfig())
        R, Rinv, _ = cholesky.factor(grid1, jnp.asarray(A), cfg)
        for X in (np.asarray(R), np.asarray(Rinv)):
            low = X[np.tril_indices(256, -1)]
            np.testing.assert_array_equal(low, np.zeros_like(low))


class TestEligibility:
    def test_vmem_boundary(self):
        # need = 3n² x 4B + 4 x 5n² = 32n² against 0.85 x 16MiB: n=512
        # fits (8.4M), n=768 does not (18.9M)
        assert batched_small.tail_eligible(512, jnp.float32,
                                           interpret=False)
        assert not batched_small.tail_eligible(768, jnp.float32,
                                               interpret=False)

    def test_interpret_bypasses(self):
        assert batched_small.tail_eligible(768, jnp.float32,
                                           interpret=True)

    def test_fusible_tracks_the_boundary(self, grid1):
        # the factor-level gate inherits the envelope: the same subtree
        # flips unfusible when the window outgrows VMEM
        cfg = CholinvConfig(base_case_dim=128, tail_fuse_depth=3)
        for n, want in ((512, True), (1024, False)):
            node = cholesky.plan(n, cfg)
            buf = jnp.zeros((n, n), jnp.float32)
            got = (
                cholesky._tail_fusible(grid1, buf, 0, node, cfg, True,
                                       buf, 0)
                and batched_small.tail_eligible(n, jnp.float32,
                                                interpret=False)
            )
            assert got == want


class TestServeCacheKey:
    def test_tail_fuse_depth_is_part_of_cache_identity(self):
        # a fused and an unfused oversize program are different
        # executables; reusing one for the other across the persistent
        # cache would silently serve the wrong launch structure
        from capital_tpu.serve.engine import ServeConfig, SolveEngine

        e1 = SolveEngine(cfg=ServeConfig())
        e2 = SolveEngine(cfg=ServeConfig(tail_fuse_depth=2))
        assert e1._cfg_hash != e2._cfg_hash


class TestTransposePair:
    def test_bitwise_equal_to_sequential(self):
        rng = np.random.default_rng(7)
        n, p, dest = 256, 512, 256
        L = jnp.asarray(rng.standard_normal((n, n)), dtype=jnp.float32)
        Li = jnp.asarray(rng.standard_normal((n, n)), dtype=jnp.float32)
        Rp0 = jnp.zeros((p, p), jnp.float32)
        RIp0 = jnp.zeros((p, p), jnp.float32)
        R_seq = pallas_tpu.transpose(L, out_uplo="U", out=Rp0,
                                     out_off=(dest, dest))
        RI_seq = pallas_tpu.transpose(Li, out_uplo="U", out=RIp0,
                                      out_off=(dest, dest))
        R_pair, RI_pair = pallas_tpu.transpose_pair(
            L, Li, jnp.zeros((p, p), jnp.float32),
            jnp.zeros((p, p), jnp.float32), dest=dest,
        )
        np.testing.assert_array_equal(np.asarray(R_pair), np.asarray(R_seq))
        np.testing.assert_array_equal(np.asarray(RI_pair),
                                      np.asarray(RI_seq))
