"""Fused CQR2 tall-pass kernels (ops/qr_fused.py) — interpret mode on CPU.

The fused pipeline must agree with the unfused blocked pipeline (same
grams-from-rounded-Q math, different reduction association) and pass the
reference residual gates."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import qr
from capital_tpu.models.qr import CacqrConfig
from capital_tpu.ops import pallas_tpu, qr_fused
from capital_tpu.parallel.topology import Grid
from capital_tpu.utils import rand48, residual


@pytest.fixture(scope="module")
def grid1():
    return Grid.square(c=1, devices=jax.devices("cpu")[:1])


def _tall(m, n, key=11):
    return jnp.asarray(rand48.random(m, n, key=key))


class TestKernels:
    def test_gram_blocked_matches_dense(self):
        A = _tall(2048, 512).astype(jnp.float32)
        Gu = qr_fused.gram_blocked(A, bm=512)
        G = qr_fused.assemble_sym(Gu, 256)
        want = np.asarray(A, np.float64).T @ np.asarray(A, np.float64)
        np.testing.assert_allclose(np.asarray(G), want, rtol=1e-5, atol=1e-4)
        # lower-left of the raw form is zero (never computed)
        np.testing.assert_array_equal(np.asarray(Gu)[256:, :256], 0.0)

    def test_scale_gram_matches_separate(self):
        rng = np.random.default_rng(5)
        A = _tall(1024, 512, key=7).astype(jnp.float32)
        Rinv = jnp.asarray(
            np.triu(rng.standard_normal((512, 512)) * 0.1 + np.eye(512))
        ).astype(jnp.float32)
        Q, Gu = qr_fused.scale_gram(A, Rinv, bm=512)
        wantQ = np.asarray(A, np.float64) @ np.asarray(Rinv, np.float64)
        np.testing.assert_allclose(np.asarray(Q), wantQ, rtol=1e-4, atol=1e-4)
        # the gram is of the ROUNDED Q (the contract: sweep 2 sees what it
        # would have re-read)
        Qr = np.asarray(Q, np.float64)
        G = qr_fused.assemble_sym(Gu, 256)
        np.testing.assert_allclose(
            np.asarray(G), Qr.T @ Qr, rtol=1e-5, atol=1e-4
        )

    # the last case runs each kernel at tall_bm's block (4096 for the Gram
    # and the final scale, 1024 for scale_gram) and holds it to bm=512
    @pytest.mark.parametrize("g,m,bm", [
        pytest.param(4, 2048, 512, id="4"),
        pytest.param(8, 2048, 512, id="8"),
        pytest.param(8, 8192, None, id="8-tall_bm"),
    ])
    def test_gram_blocked_finer_splits(self, g, m, bm):
        # in-kernel g=4/8 column blocking (VERDICT r3 #1): same gram,
        # fewer executed flops, block-triangular valid region
        A = _tall(m, 1024).astype(jnp.float32)
        c = 1024 // g
        Gu = qr_fused.gram_blocked(A, bm=bm, g=g)
        G = qr_fused.assemble_sym(Gu, c)
        want = np.asarray(A, np.float64).T @ np.asarray(A, np.float64)
        np.testing.assert_allclose(np.asarray(G), want, rtol=1e-5, atol=1e-4)
        Gu_np = np.asarray(Gu)
        for i in range(1, g):
            np.testing.assert_array_equal(Gu_np[i * c:(i + 1) * c, : i * c], 0.0)
        np.testing.assert_allclose(
            Gu_np, np.asarray(qr_fused.gram_blocked(A, bm=512, g=g)),
            rtol=1e-5, atol=1e-4,
        )

    @pytest.mark.parametrize("g,m,bm", [
        pytest.param(4, 1024, 512, id="4"),
        pytest.param(8, 1024, 512, id="8"),
        pytest.param(8, 8192, None, id="8-tall_bm"),
    ])
    def test_scale_gram_finer_splits(self, g, m, bm):
        rng = np.random.default_rng(9)
        A = _tall(m, 1024, key=8).astype(jnp.float32)
        n = 1024
        c = n // g
        Rinv = jnp.asarray(
            np.triu(rng.standard_normal((n, n)) * 0.1 + np.eye(n))
        ).astype(jnp.float32)
        Q, Gu = qr_fused.scale_gram(A, Rinv, bm=bm, g=g)
        wantQ = np.asarray(A, np.float64) @ np.asarray(Rinv, np.float64)
        np.testing.assert_allclose(np.asarray(Q), wantQ, rtol=1e-4, atol=1e-3)
        Qr = np.asarray(Q, np.float64)
        G = qr_fused.assemble_sym(Gu, c)
        np.testing.assert_allclose(np.asarray(G), Qr.T @ Qr, rtol=1e-5, atol=1e-3)
        Qs = qr_fused.scale_blocked(A, Rinv, bm=bm, g=g)
        np.testing.assert_allclose(np.asarray(Qs), wantQ, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(
            np.asarray(Qs),
            np.asarray(qr_fused.scale_blocked(A, Rinv, bm=512, g=g)),
            rtol=1e-5, atol=1e-5,
        )

    def test_f32_precision_high_three_pass(self):
        # precision='high' on f32 operands must take the in-kernel bf16x3
        # split (Mosaic has no HIGH lowering — passing it through crashed
        # with NotImplementedError on hardware) and land f32-grade results
        A = _tall(1024, 512, key=13).astype(jnp.float32)
        Gu = qr_fused.gram_blocked(A, bm=512, precision="high")
        G = qr_fused.assemble_sym(Gu, 256)
        want = np.asarray(A, np.float64).T @ np.asarray(A, np.float64)
        np.testing.assert_allclose(
            np.asarray(G), want, rtol=2e-4, atol=2e-3
        )
        # 3-pass must beat a 1-pass bf16 product by orders of magnitude
        Gd = qr_fused.assemble_sym(qr_fused.gram_blocked(
            A.astype(jnp.bfloat16).astype(jnp.float32), bm=512
        ), 256)
        err3 = np.max(np.abs(np.asarray(G) - want))
        err1 = np.max(np.abs(np.asarray(Gd) - want))
        assert err3 < err1 / 50, (err3, err1)

    def test_pick_g(self):
        assert qr_fused.pick_g(1024) == 8
        assert qr_fused.pick_g(2048) == 16  # 128-wide blocks still eligible
        assert qr_fused.pick_g(4096) == 32
        assert qr_fused.pick_g(512) == 4
        assert qr_fused.pick_g(768) == 2  # 768 % 512 != 0, g=2 slabs OK
        assert qr_fused.pick_g(256) == 0  # g=2 demands n/2 >= 256
        assert qr_fused.pick_g(192) == 0  # no 128-aligned split
        assert qr_fused.pick_g(1024, override=4) == 4
        assert qr_fused.pick_g(384, override=8) == 0  # override ineligible

    @pytest.mark.parametrize("kernel,m,n,dtype,want", [
        # the cacqr.2Mx1024.x4 shard: the depths the v5e sweep chose
        ("gram", 524288, 1024, jnp.bfloat16, 4096),
        ("scale", 524288, 1024, jnp.bfloat16, 4096),
        ("scale_gram", 524288, 1024, jnp.bfloat16, 1024),
        # halves until it divides m, and never exceeds m
        ("gram", 524288 + 2048, 1024, jnp.bfloat16, 2048),
        ("gram", 3 * 128, 1024, jnp.bfloat16, 128),
        ("scale", 2048, 512, jnp.bfloat16, 2048),
        ("gram", 1000, 1024, jnp.bfloat16, 0),
        # f32 shrinks the block: four 4-byte blocks and the copies of the
        # f32 passes, in the 85 MiB the rule allows of v5e's 100 MiB
        ("scale", 524288, 2048, jnp.bfloat16, 4096),
        ("scale", 524288, 2048, jnp.float32, 1024),
        ("scale_gram", 524288, 2048, jnp.float32, 512),
        # n = 4096: the 64 MiB f32 Gram leaves room for 512 bf16 rows (the
        # block twice, the loaded block and its transpose), 128 f32 rows;
        # scale_gram fits at no depth (the 'split' tier)
        ("gram", 524288, 4096, jnp.bfloat16, 512),
        ("gram", 524288, 4096, jnp.float32, 128),
        ("scale_gram", 524288, 4096, jnp.bfloat16, 0),
    ])
    def test_tall_bm_on_v5e(self, kernel, m, n, dtype, want):
        v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        with pallas_tpu.device_scope(v5e):
            assert qr_fused.tall_bm(kernel, m, n, dtype) == want

    def test_tall_bm_in_interpret_mode(self):
        # no VMEM: the measured depth, capped by m, at any width or dtype
        assert qr_fused.tall_bm("gram", 524288, 8192, jnp.float32) == 4096
        assert qr_fused.tall_bm("scale_gram", 524288, 8192, jnp.float32) == 1024
        assert qr_fused.tall_bm("scale", 2048, 8192, jnp.float32) == 2048

    def test_shape_gates(self):
        A = _tall(1000, 512).astype(jnp.float32)  # 1000 not tileable
        with pytest.raises(ValueError):
            qr_fused.gram_blocked(A, bm=512)
        g1 = Grid.square(c=1, devices=jax.devices("cpu")[:1])
        assert not qr_fused.fused_ok(g1, 1000, 512, "pallas", dtype=jnp.float32)
        assert not qr_fused.fused_ok(g1, 1024, 192, "pallas", dtype=jnp.float32)  # no g=2 split
        assert not qr_fused.fused_ok(g1, 1024, 512, "xla", dtype=jnp.float32)
        assert qr_fused.fused_ok(g1, 1024, 512, "pallas", dtype=jnp.float32)


class TestFusedPipeline:
    def test_fused_cqr2_matches_unfused(self, grid1):
        A = _tall(2048, 512).astype(jnp.float64)
        fused_cfg = CacqrConfig(num_iter=2, regime="1d", mode="pallas")
        assert qr_fused.fused_ok(grid1, *A.shape, "pallas", dtype=A.dtype)
        Qf, Rf = jax.jit(lambda a: qr.factor(grid1, a, fused_cfg))(A)
        # unfused reference: xla mode takes the separate-pass pipeline
        Qu, Ru = jax.jit(
            lambda a: qr.factor(grid1, a, CacqrConfig(num_iter=2, regime="1d"))
        )(A)
        np.testing.assert_allclose(np.asarray(Qf), np.asarray(Qu), atol=1e-10)
        np.testing.assert_allclose(
            np.triu(np.asarray(Rf)), np.triu(np.asarray(Ru)), atol=1e-8
        )
        assert float(residual.qr_orthogonality(Qf)) < 1e-14
        assert float(residual.qr_residual(A, Qf, Rf)) < 1e-13

    def test_split_plan_matches_full(self, grid1):
        # the wide-n streaming tier ('split': scale and sweep-2 gram as two
        # kernels) must agree with the 'full' scale_gram tier exactly — the
        # gram is taken from the SAME rounded Q1 either way
        from capital_tpu.models.qr import _cqr2_fused

        A = _tall(2048, 512).astype(jnp.float64)
        cfg = CacqrConfig(num_iter=2, regime="1d", mode="pallas")
        g = qr_fused.pick_g(512)
        Qf, Rf = jax.jit(lambda a: _cqr2_fused(grid1, a, cfg, g, "full"))(A)
        Qs, Rs = jax.jit(lambda a: _cqr2_fused(grid1, a, cfg, g, "split"))(A)
        np.testing.assert_allclose(np.asarray(Qs), np.asarray(Qf), atol=1e-12)
        np.testing.assert_allclose(np.asarray(Rs), np.asarray(Rf), atol=1e-10)
        assert float(residual.qr_orthogonality(Qs)) < 1e-14

    def test_fused_plan_tiers(self, grid1, monkeypatch):
        # envelope arithmetic on v5e's budget: narrow n -> 'full';
        # n=4096 exceeds scale_gram's envelope but not the per-kernel ones
        # -> 'split'; n=8192's gram alone exceeds VMEM -> 'panels'
        monkeypatch.setattr(pallas_tpu, "_default_backend", lambda: "tpu")
        monkeypatch.setattr(
            qr_fused, "_interpret_default", lambda: False
        )
        monkeypatch.setattr(
            qr_fused, "_device_budget",
            lambda: pallas_tpu._TILE_BUDGET["TPU v5 lite"],
        )
        bf = jnp.bfloat16
        assert qr_fused.fused_plan(
            grid1, 1 << 21, 1024, "pallas", g=8, dtype=bf
        ) == "full"
        assert qr_fused.fused_plan(
            grid1, 262144, 4096, "pallas", g=32, dtype=bf
        ) == "split"
        assert qr_fused.fused_plan(
            grid1, 65536, 8192, "pallas", g=64, dtype=bf
        ) == "panels"

    @pytest.mark.slow  # ~24s (n=2048 f64 on the 1-core rig); the
    # wide-n route's cheaper dispatch pins stay in tier-1
    def test_wide_n_cholinv_route_matches_unfused(self, grid1):
        # n >= 2048 routes the gram factor through the recursive cholinv
        # on the UNASSEMBLED gram (zeros below the valid upper triangle) —
        # the branch's correctness rests on cholinv never reading the
        # lower half; this is the CI tripwire for that contract
        m, n = 2304, 2048
        A = _tall(m, n).astype(jnp.float64)
        cfg = CacqrConfig(num_iter=2, regime="1d", mode="pallas")
        g = qr_fused.pick_g(n)
        assert qr_fused.fused_ok(grid1, m, n, "pallas", g=g, dtype=A.dtype)
        Qf, Rf = jax.jit(lambda a: qr.factor(grid1, a, cfg))(A)
        Qu, Ru = jax.jit(
            lambda a: qr.factor(grid1, a, CacqrConfig(num_iter=2, regime="1d"))
        )(A)
        assert float(residual.qr_orthogonality(Qf)) < 1e-14
        assert float(residual.qr_residual(A, Qf, Rf)) < 1e-13
        np.testing.assert_allclose(np.asarray(Qf), np.asarray(Qu), atol=1e-9)
        np.testing.assert_allclose(
            np.triu(np.asarray(Rf)), np.triu(np.asarray(Ru)), atol=1e-7
        )

    def test_panels_tier_matches_unfused(self, grid1):
        # the very-wide-n XLA panel pipeline (fused_plan 'panels'): same
        # grams-from-rounded-Q math as the sweeps, checked at a small
        # shape by calling the tier directly
        from capital_tpu.models.qr import _cqr2_panels

        m, n = 2048, 1024
        A = _tall(m, n).astype(jnp.float64)
        cfg = CacqrConfig(num_iter=2, regime="1d", mode="pallas")
        Qp, Rp = jax.jit(lambda a: _cqr2_panels(grid1, a, cfg, 256))(A)
        assert float(residual.qr_orthogonality(Qp)) < 1e-14
        assert float(residual.qr_residual(A, Qp, Rp)) < 1e-13
        Qu, Ru = jax.jit(
            lambda a: qr.factor(grid1, a, CacqrConfig(num_iter=2, regime="1d"))
        )(A)
        np.testing.assert_allclose(np.asarray(Qp), np.asarray(Qu), atol=1e-9)
        np.testing.assert_allclose(
            np.triu(np.asarray(Rp)), np.triu(np.asarray(Ru)), atol=1e-7
        )

    def test_fused_bf16_gates(self, grid1):
        A = _tall(1024, 512).astype(jnp.bfloat16)
        cfg = CacqrConfig(num_iter=2, regime="1d", mode="pallas")
        Q, R = jax.jit(lambda a: qr.factor(grid1, a, cfg))(A)
        assert float(residual.qr_orthogonality(Q)) < 5e-2
        assert float(residual.qr_residual(A, Q, R)) < 5e-2

    def test_cqr1_stays_unfused_and_mesh_gates_hold(self, grid_flat8, grid1):
        # num_iter=1 keeps the sweep pipeline; on the mesh the per-shard
        # kernels engage (128-row shards pick bm=128) and must still gate
        A = _tall(1024, 512).astype(jnp.float64)
        cfg1 = CacqrConfig(num_iter=1, regime="1d", mode="pallas")
        Q, R = qr.factor(grid1, A, cfg1)
        assert float(residual.qr_residual(A, Q, R)) < 1e-13
        Ad = jax.device_put(A, grid_flat8.rows_sharding())
        cfgm = CacqrConfig(num_iter=2, regime="1d", mode="pallas")
        Qm, Rm = jax.jit(lambda a: qr.factor(grid_flat8, a, cfgm))(Ad)
        assert float(residual.qr_orthogonality(Qm)) < 1e-13


class TestFusedSharded:
    """The per-shard fused pipeline on a mesh (qr._cqr2_fused_sharded):
    same kernels, run inside shard_map with the grams psum-merged
    (VERDICT r4 #2 — the reference's per-rank local-BLAS saving,
    blas/interface.hpp:74-97)."""

    def test_sharded_matches_single_device(self, grid_flat8, grid1):
        m, n = 4096, 512  # 512 rows per shard: per-shard eligible
        A = _tall(m, n).astype(jnp.float64)
        cfg = CacqrConfig(num_iter=2, regime="1d", mode="pallas")
        g = qr_fused.pick_g(n)
        assert qr_fused.fused_ok(grid_flat8, m, n, "pallas", g=g, dtype=A.dtype)
        Ad = jax.device_put(A, grid_flat8.rows_sharding())
        Qm, Rm = jax.jit(lambda a: qr.factor(grid_flat8, a, cfg))(Ad)
        Q1, R1 = jax.jit(lambda a: qr.factor(grid1, a, cfg))(A)
        assert float(residual.qr_orthogonality(Qm)) < 1e-14
        assert float(residual.qr_residual(Ad, Qm, Rm)) < 1e-13
        # identical math up to the psum's reduction association order
        np.testing.assert_allclose(np.asarray(Qm), np.asarray(Q1), atol=1e-10)
        np.testing.assert_allclose(
            np.triu(np.asarray(Rm)), np.triu(np.asarray(R1)), atol=1e-8
        )

    def test_sharded_bf16_gates(self, grid_flat8):
        m, n = 4096, 512
        A = _tall(m, n, key=3).astype(jnp.bfloat16)
        Ad = jax.device_put(A, grid_flat8.rows_sharding())
        cfg = CacqrConfig(num_iter=2, regime="1d", mode="pallas")
        Q, R = jax.jit(lambda a: qr.factor(grid_flat8, a, cfg))(Ad)
        assert float(residual.qr_orthogonality(Q)) < 5e-2
        assert float(residual.qr_residual(Ad, Q, R)) < 5e-2

    def test_uneven_rows_fall_back_to_sweeps(self, grid_flat8):
        # m not divisible by the device count: the m % p guard must refuse
        # (4100 % 8 = 4 — hits the guard itself, not the bm-tiling rule)
        # and the factor must still produce a correct result via the sweeps
        m, n = 4100, 512
        assert not qr_fused.fused_ok(
            grid_flat8, m, n, "pallas", dtype=jnp.float64
        )
        # uneven rows cannot even be device_put row-sharded (NamedSharding
        # demands divisibility); the factor's in-jit constraint handles the
        # placement, exactly how an uneven caller would reach it
        A = _tall(m, n).astype(jnp.float64)
        cfg = CacqrConfig(num_iter=2, regime="1d", mode="pallas")
        Q, R = jax.jit(lambda a: qr.factor(grid_flat8, a, cfg))(A)
        assert float(residual.qr_orthogonality(Q)) < 1e-13
        assert float(residual.qr_residual(A, Q, R)) < 1e-13
