"""Block-arrowhead Cholesky fast-path tests (ISSUE 15 acceptance).

The properties pinned here, mapped to the issue's criteria:

* posv matches the dense reference on the assembled arrowhead across
  geometry ladders, xla f64 and pallas f32, and the sequential scan and
  the partitioned Spike chain drivers produce matching answers UNDER THE
  BORDER SOLVE — the widened-chain design's whole point (TestParity);
* schur()'s corner factor reconstructs an f64 NumPy-side Schur reference
  (the bench-arrowhead factor gate's seam), assemble/pack/unpack round-
  trip, and the bordered-banded adapter solves to dense-NumPy parity on
  both band storage forms (TestParity, TestBordered);
* breakdown infos land in whole-matrix LAPACK coordinates: chain pivots
  pass through in [1, n_T], corner pivots are offset past n_T
  (docs/ROBUSTNESS.md corner-pivot note), healthy problems report 0 and
  batch neighbors stay contained (TestInfo);
* the serve pad is structure-safe: appended identity chain blocks leave
  the real solution BITWISE unchanged (chain-length padding is inert —
  the PR-10 contract extended to the bordered op), in-block / border /
  nrhs pads are tight, fill problems solve to exact zeros (TestPadding);
* the engine buckets posv_arrowhead on its three ladders with the
  zero-recompile invariant, counts it in request_stats.ops, keeps
  border_buckets in the config hash, flattens the two-part solution
  into the documented (n_T + s, k) response, and routes oversize
  geometry through the single path (TestServeArrowhead);
* bench:arrowhead ledger records validate structurally — malformed ones
  are LedgerIncompatible and a speedup row without its residual proof
  bundle is rejected (TestLedgerSeam);
* the AH::* phases are registered with executed-flop helpers and
  estimate_seconds prices refine sweeps from the serve stats feed — the
  round-15 cost-model satellite (TestTracing).

Same rig notes as test_blocktri: conftest CPU, x64 on, f32 asked for
explicitly when the pallas kernels are the point.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import arrowhead, banded
from capital_tpu.obs import ledger
from capital_tpu.serve import ServeConfig, SolveEngine, batching
from capital_tpu.utils import tracing

# Small ladders so every executable compiles fast (the BT_CFG posture)
# plus the new border ladder.
AH_CFG = ServeConfig(
    buckets=(8, 16),
    rows_buckets=(32,),
    nrhs_buckets=(1, 4),
    max_batch=2,
    max_delay_s=10.0,
    nblocks_buckets=(2, 4),
    block_buckets=(4, 8),
    border_buckets=(2, 4),
)


def _arrow(rng, batch, nblocks, b, s, k, dtype=np.float64):
    """A well-conditioned SPD arrowhead + RHS (the driver recipe: the
    blocktri chain family, border coupling shrinking with chain length,
    corner with a 5I margin)."""
    G = rng.standard_normal((batch, nblocks, b, b))
    D = G @ G.transpose(0, 1, 3, 2) / b + 3.0 * np.eye(b)
    C = 0.3 / np.sqrt(b) * rng.standard_normal((batch, nblocks, b, b))
    C[:, 0] = 0.0
    F = 0.3 / np.sqrt(nblocks * b) * rng.standard_normal(
        (batch, nblocks, s, b))
    S0 = rng.standard_normal((batch, s, s))
    S = S0 @ S0.transpose(0, 2, 1) / s + 5.0 * np.eye(s)
    B = rng.standard_normal((batch, nblocks, b, k))
    Bs = rng.standard_normal((batch, s, k))
    return tuple(x.astype(dtype) for x in (D, C, F, S, B, Bs))


def _np_dense(D, C, F, S):
    """NumPy-side dense assembly of ONE problem's arrowhead — independent
    of arrowhead.assemble (the bench-driver discipline)."""
    nblocks, b = D.shape[0], D.shape[1]
    s = F.shape[1]
    n_t = nblocks * b
    A = np.zeros((n_t + s, n_t + s), dtype=np.float64)
    for i in range(nblocks):
        sl = slice(i * b, (i + 1) * b)
        A[sl, sl] = D[i]
        if i:
            up = slice((i - 1) * b, i * b)
            A[sl, up] = C[i]
            A[up, sl] = C[i].T
        A[n_t:, sl] = F[i]
        A[sl, n_t:] = F[i].T
    A[n_t:, n_t:] = S
    return A


def _dense_solve(D, C, F, S, B, Bs):
    """f64 flat dense reference (batch, n_T + s, k)."""
    out = []
    for j in range(D.shape[0]):
        A = _np_dense(*(np.asarray(o[j], np.float64) for o in (D, C, F, S)))
        rhs = np.concatenate(
            [np.asarray(B[j], np.float64).reshape(-1, B.shape[-1]),
             np.asarray(Bs[j], np.float64)])
        out.append(np.linalg.solve(A, rhs))
    return np.stack(out)


def _posv(D, C, F, S, B, Bs, **kw):
    return arrowhead.posv(*(jnp.asarray(o) for o in (D, C, F, S, B, Bs)),
                          **kw)


def _flat(X, Xs):
    X, Xs = np.asarray(X), np.asarray(Xs)
    return np.concatenate(
        [X.reshape(X.shape[0], -1, X.shape[-1]), Xs], axis=1)


# ---------------------------------------------------------------------------
# numerical parity: arrowhead vs dense, scan vs partitioned
# ---------------------------------------------------------------------------


class TestParity:
    @pytest.mark.parametrize("nblocks,b,s", [(2, 3, 1), (4, 4, 3),
                                             (6, 8, 5)])
    def test_posv_matches_dense_xla_f64(self, nblocks, b, s):
        rng = np.random.default_rng(50)
        ops = _arrow(rng, 2, nblocks, b, s, 2)
        X, Xs, info = _posv(*ops, impl="xla")
        assert np.all(np.asarray(info) == 0)
        ref = _dense_solve(*ops)
        assert np.abs(_flat(X, Xs) - ref).max() < 1e-11 * np.abs(ref).max()

    def test_posv_matches_dense_pallas_f32(self):
        rng = np.random.default_rng(51)
        ops = _arrow(rng, 2, 4, 8, 3, 2, dtype=np.float32)
        X, Xs, info = _posv(*ops, impl="pallas")
        assert np.all(np.asarray(info) == 0)
        ref = _dense_solve(*ops)
        assert np.abs(_flat(X, Xs) - ref).max() < 5e-5 * np.abs(ref).max()

    def test_scan_and_partitioned_match(self):
        # the acceptance criterion: the partitioned Spike chain driver
        # serves the border columns too (the ONE widened solve design),
        # and both impls land the same answers
        rng = np.random.default_rng(52)
        ops = _arrow(rng, 2, 16, 4, 3, 2)
        Xa, Xsa, ia = _posv(*ops, impl="xla")
        Xb, Xsb, ib = _posv(*ops, impl="partitioned", partitions=4,
                            partition_inner="xla")
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
        a, b_ = _flat(Xa, Xsa), _flat(Xb, Xsb)
        assert np.abs(a - b_).max() < 1e-11 * np.abs(a).max()
        ref = _dense_solve(*ops)
        assert np.abs(b_ - ref).max() < 1e-11 * np.abs(ref).max()

    def test_schur_matches_numpy_reference(self):
        # the bench-arrowhead factor gate's seam: L_S·L_Sᵀ reconstructs
        # an f64 Schur complement built WITHOUT models code
        rng = np.random.default_rng(53)
        D, C, F, S, _, _ = _arrow(rng, 2, 3, 4, 3, 1)
        Zb, St, Ls, info = arrowhead.schur(
            jnp.asarray(D), jnp.asarray(C), jnp.asarray(F), jnp.asarray(S),
            impl="xla")
        assert np.all(np.asarray(info) == 0)
        for j in range(2):
            A = _np_dense(D[j], C[j], F[j], S[j])
            n_t = 12
            ref = S[j] - A[n_t:, :n_t] @ np.linalg.solve(
                A[:n_t, :n_t], A[:n_t, n_t:])
            L = np.asarray(Ls)[j]
            assert np.abs(L @ L.T - ref).max() < 1e-11

    def test_assemble_matches_numpy(self):
        rng = np.random.default_rng(54)
        D, C, F, S, _, _ = _arrow(rng, 1, 3, 2, 2, 1)
        A = arrowhead.assemble(jnp.asarray(D), jnp.asarray(C),
                               jnp.asarray(F), jnp.asarray(S))
        np.testing.assert_allclose(np.asarray(A)[0],
                                   _np_dense(D[0], C[0], F[0], S[0]),
                                   rtol=0, atol=0)

    def test_pack_unpack_roundtrip(self):
        rng = np.random.default_rng(55)
        _, _, F, S, B, Bs = _arrow(rng, 2, 3, 4, 2, 3)
        P = arrowhead.pack(jnp.asarray(F), jnp.asarray(S),
                           jnp.asarray(B), jnp.asarray(Bs))
        assert P.shape == (2, 3 * 4 + 2, 2 + 3)
        F2, S2, B2, Bs2 = arrowhead.unpack(P, 3, 4)
        for a, b_ in ((F, F2), (S, S2), (B, B2), (Bs, Bs2)):
            np.testing.assert_array_equal(a, np.asarray(b_))

    def test_unpack_rejects_bad_geometry(self):
        with pytest.raises(ValueError, match="cannot carry"):
            arrowhead.unpack(jnp.zeros((1, 10, 3)), 3, 4)


# ---------------------------------------------------------------------------
# breakdown coordinates: chain pass-through, corner offset
# ---------------------------------------------------------------------------


class TestInfo:
    def test_corner_pivot_offset_past_chain(self):
        # poison the corner only: the chain factors clean, the corner
        # cholesky fails, and the combined info lands PAST n_T in
        # whole-matrix coordinates (jax NaN-fills the failed factor, so
        # the exact corner index is the first corner diagonal — the
        # blocktri xla convention; the pinned property is the offset)
        rng = np.random.default_rng(60)
        D, C, F, S, B, Bs = _arrow(rng, 1, 3, 4, 3, 1)
        S[0] = np.diag([4.0, -50.0, 4.0])
        F[0] = 0.0
        X, Xs, info = _posv(D, C, F, S, B, Bs, impl="xla")
        n_t = 12
        assert n_t < int(info[0]) <= n_t + 3 + 1

    def test_chain_pivot_passes_through(self):
        # poison chain block 1 with a zeroed incoming coupling: the
        # arrowhead info is the blocktri info verbatim (the (0, n_T)
        # window is exact) and stays <= n_T
        rng = np.random.default_rng(61)
        D, C, F, S, B, Bs = _arrow(rng, 1, 3, 4, 3, 1)
        D[0, 1] = np.diag([1.0, 1.0, -5.0, 1.0])
        C[0, 1] = 0.0
        C[0, 2] = 0.0
        X, Xs, info = _posv(D, C, F, S, B, Bs, impl="xla")
        assert 4 < int(info[0]) <= 8

    def test_bad_problem_contained_in_batch(self):
        rng = np.random.default_rng(62)
        D, C, F, S, B, Bs = _arrow(rng, 2, 3, 4, 2, 2)
        S[1] = -np.eye(2)
        X, Xs, info = _posv(D, C, F, S, B, Bs, impl="xla")
        info = np.asarray(info)
        assert info[0] == 0 and info[1] > 12
        ref = _dense_solve(D[:1], C[:1], F[:1], S[:1], B[:1], Bs[:1])
        got = _flat(X, Xs)[:1]
        assert np.abs(got - ref).max() < 1e-11 * np.abs(ref).max()


# ---------------------------------------------------------------------------
# serve padding contract
# ---------------------------------------------------------------------------


def _bucket(nbb, bb, sb, kb, dtype="float64", cap=2):
    return batching.Bucket("posv_arrowhead", dtype, (2, nbb, bb, bb),
                           (nbb * bb + sb, sb + kb), cap)


class TestPadding:
    def test_appended_chain_blocks_are_bitwise_inert(self):
        # same b/s/k, nblocks 3 -> 4: trailing identity chain blocks with
        # ZERO border columns never feed the sweeps or the Schur
        # reduction's accumulation prefix, so the cropped solution is
        # BITWISE the unpadded one (the PR-10 chain contract extended
        # through the border solve and the completion gemms)
        rng = np.random.default_rng(63)
        D, C, F, S, B, Bs = _arrow(rng, 1, 3, 4, 2, 2)
        A = jnp.asarray(np.stack([D[0], C[0]]))
        P = arrowhead.pack(jnp.asarray(F), jnp.asarray(S),
                           jnp.asarray(B), jnp.asarray(Bs))[0]
        bucket = _bucket(4, 4, 2, 2)
        pa, pp = batching.pad_operands("posv_arrowhead", A, P, bucket)
        Fp, Sp, Bp, Bsp = arrowhead.unpack(pp[None], 4, 4)
        Xp, Xsp, ip = arrowhead.posv(pa[None, 0], pa[None, 1], Fp, Sp,
                                     Bp, Bsp, impl="xla")
        X0, Xs0, i0 = _posv(D, C, F, S, B, Bs, impl="xla")
        Xc = batching.crop("posv_arrowhead", Xp[0], A.shape, P.shape)
        np.testing.assert_array_equal(np.asarray(Xc), np.asarray(X0)[0])
        np.testing.assert_array_equal(np.asarray(Xsp)[0], np.asarray(Xs0)[0])
        # the identity tail solves to exact zeros, info stays clean
        np.testing.assert_array_equal(np.asarray(Xp)[0, 3:], 0.0)
        assert int(ip[0]) == int(i0[0]) == 0

    def test_block_border_nrhs_pad_is_tight(self):
        # b 3 -> 4, s 2 -> 4, k 1 -> 4, nblocks 3 -> 4 all at once:
        # identity embeds everywhere, the padded operand stays a valid
        # SPD arrowhead, and the cropped solution matches the dense
        # reference tightly (not bitwise: contraction lengths change)
        rng = np.random.default_rng(64)
        D, C, F, S, B, Bs = _arrow(rng, 1, 3, 3, 2, 1)
        A = jnp.asarray(np.stack([D[0], C[0]]))
        P = arrowhead.pack(jnp.asarray(F), jnp.asarray(S),
                           jnp.asarray(B), jnp.asarray(Bs))[0]
        bucket = _bucket(4, 4, 4, 4)
        pa, pp = batching.pad_operands("posv_arrowhead", A, P, bucket)
        # chain blocks completed to diag(D_i, I), appended block pure I
        np.testing.assert_array_equal(np.asarray(pa)[0, 0, 3, :],
                                      np.eye(4)[3])
        np.testing.assert_array_equal(np.asarray(pa)[0, 3], np.eye(4))
        Fp, Sp, Bp, Bsp = arrowhead.unpack(pp[None], 4, 4)
        # corner embedded as diag(S, I), border zero over all padding
        np.testing.assert_array_equal(np.asarray(Sp)[0, 2:, 2:], np.eye(2))
        np.testing.assert_array_equal(np.asarray(Sp)[0, :2, 2:], 0.0)
        np.testing.assert_array_equal(np.asarray(Fp)[0, :, 2:], 0.0)
        np.testing.assert_array_equal(np.asarray(Fp)[0, :, :, 3], 0.0)
        np.testing.assert_array_equal(np.asarray(Fp)[0, 3], 0.0)
        Xp, Xsp, ip = arrowhead.posv(pa[None, 0], pa[None, 1], Fp, Sp,
                                     Bp, Bsp, impl="xla")
        assert int(ip[0]) == 0
        Xc = batching.crop("posv_arrowhead", Xp[0], A.shape, P.shape)
        ref = _dense_solve(D, C, F, S, B, Bs)[0]
        got = np.concatenate([np.asarray(Xc).reshape(9, 1),
                              np.asarray(Xsp)[0, :2, :1]])
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-12)

    def test_fill_problem_is_identity_arrowhead(self):
        bucket = _bucket(4, 4, 2, 2)
        fa, fb = batching.fill_problem(bucket)
        np.testing.assert_array_equal(np.asarray(fa)[0],
                                      np.broadcast_to(np.eye(4), (4, 4, 4)))
        np.testing.assert_array_equal(np.asarray(fa)[1], 0.0)
        F, S, B, Bs = arrowhead.unpack(fb[None], 4, 4)
        np.testing.assert_array_equal(np.asarray(S)[0], np.eye(2))
        np.testing.assert_array_equal(np.asarray(F), 0.0)
        X, Xs, info = arrowhead.posv(fa[None, 0], fa[None, 1], F, S, B, Bs,
                                     impl="xla")
        np.testing.assert_array_equal(np.asarray(X), 0.0)
        np.testing.assert_array_equal(np.asarray(Xs), 0.0)
        assert int(info[0]) == 0


# ---------------------------------------------------------------------------
# serve engine: bucketing, zero-recompile, flat response, config hash
# ---------------------------------------------------------------------------


def _submit_ops(rng, nblocks, b, s, k):
    D, C, F, S, B, Bs = _arrow(rng, 1, nblocks, b, s, k)
    A = np.stack([D[0], C[0]])
    P = np.asarray(arrowhead.pack(jnp.asarray(F), jnp.asarray(S),
                                  jnp.asarray(B), jnp.asarray(Bs))[0])
    ref = _dense_solve(D, C, F, S, B, Bs)[0]
    return A, P, ref


class TestServeArrowhead:
    def test_engine_matches_dense_flat_response(self):
        rng = np.random.default_rng(65)
        A, P, ref = _submit_ops(rng, 2, 3, 2, 1)
        eng = SolveEngine(cfg=AH_CFG)
        r = eng.solve("posv_arrowhead", A, P)
        assert r.ok and r.batched and r.bucket is not None
        assert np.asarray(r.x).shape == (2 * 3 + 2, 1)
        np.testing.assert_allclose(np.asarray(r.x), ref, rtol=0, atol=1e-10)

    def test_same_bucket_zero_recompile(self):
        # (2, 3, 2) and (2, 4, 1) geometries land in the same
        # (2, 4, 2)-bucket: one compile, then steady-state hits
        rng = np.random.default_rng(66)
        eng = SolveEngine(cfg=AH_CFG)
        for b, s in ((3, 2), (4, 1)):
            A, P, ref = _submit_ops(rng, 2, b, s, 1)
            r = eng.solve("posv_arrowhead", A, P)
            assert r.ok
            np.testing.assert_allclose(np.asarray(r.x), ref,
                                       rtol=0, atol=1e-10)
        c = eng.cache_stats()
        assert (c["hits"], c["misses"]) == (1, 1)
        assert eng.stats.ops["posv_arrowhead"] == 2

    def test_submit_validation(self):
        eng = SolveEngine(cfg=AH_CFG)
        with pytest.raises(ValueError, match="chain pack"):
            eng.submit("posv_arrowhead", np.zeros((3, 2, 4, 4)),
                       np.zeros((10, 3)))
        with pytest.raises(ValueError, match="packed tail"):
            eng.submit("posv_arrowhead", np.zeros((2, 2, 4, 4)),
                       np.zeros((8, 3)))

    def test_border_ladder_joins_config_hash(self):
        e1 = SolveEngine(cfg=AH_CFG)
        e2 = SolveEngine(cfg=ServeConfig(
            buckets=AH_CFG.buckets, rows_buckets=AH_CFG.rows_buckets,
            nrhs_buckets=AH_CFG.nrhs_buckets, max_batch=AH_CFG.max_batch,
            max_delay_s=AH_CFG.max_delay_s,
            nblocks_buckets=AH_CFG.nblocks_buckets,
            block_buckets=AH_CFG.block_buckets,
            border_buckets=(2, 8),
        ))
        assert e1._cfg_hash != e2._cfg_hash

    def test_oversize_routes_single(self):
        # border past the ladder: unbatched single route, same flat
        # client-visible layout, still correct
        rng = np.random.default_rng(67)
        A, P, ref = _submit_ops(rng, 2, 3, 6, 1)
        eng = SolveEngine(cfg=AH_CFG)
        r = eng.solve("posv_arrowhead", A, P)
        assert r.ok and not r.batched and r.bucket is None
        assert np.asarray(r.x).shape == ref.shape
        np.testing.assert_allclose(np.asarray(r.x), ref, rtol=0, atol=1e-10)


# ---------------------------------------------------------------------------
# bordered-banded adapter (models/banded.solveh_bordered)
# ---------------------------------------------------------------------------


class TestBordered:
    def _system(self, rng, n=23, u=2, s=3, k=2):
        A = np.zeros((n, n))
        for d in range(1, u + 1):
            v = 0.3 * rng.standard_normal(n - d)
            A[np.arange(n - d) + d, np.arange(n - d)] = v
            A[np.arange(n - d), np.arange(n - d) + d] = v
        A[np.diag_indices(n)] = 4.0 + rng.random(n)
        ab = np.zeros((u + 1, n))
        for d in range(u + 1):
            ab[d, :n - d] = A[np.arange(n - d) + d, np.arange(n - d)]
        B = 0.2 * rng.standard_normal((s, n))
        S0 = rng.standard_normal((s, s))
        S = S0 @ S0.T / s + 5.0 * np.eye(s)
        rhs = rng.standard_normal((n, k))
        rhs_c = rng.standard_normal((s, k))
        full = np.block([[A, B.T], [B, S]])
        ref = np.linalg.solve(full, np.concatenate([rhs, rhs_c]))
        return ab, B, S, rhs, rhs_c, ref

    def test_matches_dense_numpy_both_forms(self):
        rng = np.random.default_rng(70)
        ab, B, S, rhs, rhs_c, ref = self._system(rng)
        u, n = ab.shape[0] - 1, ab.shape[1]
        ab_up = np.zeros_like(ab)
        for d in range(u + 1):
            ab_up[u - d, d:] = ab[d, :n - d]
        for lower, a in ((True, ab), (False, ab_up)):
            x, xs = banded.solveh_bordered(jnp.asarray(a), B, S, rhs,
                                           rhs_c, lower=lower)
            got = np.concatenate([np.asarray(x), np.asarray(xs)])
            assert np.abs(got - ref).max() < 1e-11

    def test_vector_rhs_roundtrip(self):
        rng = np.random.default_rng(71)
        ab, B, S, rhs, rhs_c, ref = self._system(rng, k=1)
        x, xs = banded.solveh_bordered(jnp.asarray(ab), B, S, rhs[:, 0],
                                       rhs_c[:, 0], lower=True)
        assert x.shape == (23,) and xs.shape == (3,)
        got = np.concatenate([np.asarray(x), np.asarray(xs)])
        assert np.abs(got - ref[:, 0]).max() < 1e-11

    def test_corner_breakdown_reports_unpadded_order(self):
        rng = np.random.default_rng(72)
        ab, B, S, rhs, rhs_c, _ = self._system(rng)
        Sbad = S.copy()
        Sbad[0, 0] = -99.0
        with pytest.raises(ValueError, match="order 24"):
            banded.solveh_bordered(jnp.asarray(ab), B, Sbad, rhs, rhs_c,
                                   lower=True)

    def test_border_shape_validated(self):
        rng = np.random.default_rng(73)
        ab, B, S, rhs, rhs_c, _ = self._system(rng)
        with pytest.raises(ValueError, match="dense rows"):
            banded.solveh_bordered(jnp.asarray(ab), B[:, :-1], S, rhs,
                                   rhs_c, lower=True)


# ---------------------------------------------------------------------------
# ledger seam: exemption-with-validation for bench:arrowhead records
# ---------------------------------------------------------------------------


def _ah_measured(**over):
    m = {"metric": "arrowhead_tflops", "value": 0.5, "nblocks": 4,
         "block": 8, "border": 2, "n": 34, "batch": 2, "nrhs": 1,
         "impl": "xla", "speedup": 12.0, "arrow_ms": 1.0, "dense_ms": 12.0,
         "factor_resid": 1e-7, "solve_resid": 1e-7}
    m.update(over)
    return m


class TestLedgerSeam:
    def test_valid_record_passes_diff(self):
        rec = ledger.record("bench:arrowhead", ledger.manifest(),
                            measured=_ah_measured())
        assert ledger.diff([rec], [rec]) == []

    def test_validate_flags_geometry_mismatch(self):
        probs = ledger.validate_arrowhead_measured(_ah_measured(n=33))
        assert any("nblocks*block+border" in p for p in probs)

    def test_malformed_record_is_incompatible(self):
        rec = ledger.record("bench:arrowhead", ledger.manifest(),
                            measured=_ah_measured(impl="cuda"))
        with pytest.raises(ledger.LedgerIncompatible, match="arrowhead"):
            ledger.diff([rec], [rec])

    def test_speedup_row_requires_residual_proof(self):
        m = _ah_measured()
        del m["factor_resid"]
        probs = ledger.validate_arrowhead_measured(m)
        assert any("factor_resid" in p for p in probs)

    def test_latency_metric_validated_without_speedup(self):
        m = _ah_measured(metric="arrowhead_latency")
        for key in ("speedup", "arrow_ms", "dense_ms", "factor_resid",
                    "solve_resid"):
            del m[key]
        assert ledger.validate_arrowhead_measured(m) == []
        rec = ledger.record("bench:arrowhead", ledger.manifest(),
                            measured=_ah_measured(metric="arrowhead_latency",
                                                  border=0))
        with pytest.raises(ledger.LedgerIncompatible, match="border"):
            ledger.diff([rec], [rec])

    def test_arrowhead_op_known_to_request_stats(self):
        assert "posv_arrowhead" in ledger._REQ_STATS_OPS


# ---------------------------------------------------------------------------
# cost model: AH phases, executed-flop pricing, refine-sweep satellite
# ---------------------------------------------------------------------------


class TestTracing:
    def test_ah_phases_registered_and_priced(self):
        ops = _arrow(np.random.default_rng(80), 1, 3, 4, 2, 1)
        with tracing.Recorder() as rec:
            X, Xs, info = _posv(*ops, impl="xla")
        assert rec.stats["AH::schur"].flops == pytest.approx(
            tracing.arrowhead_schur_flops(3, 4, 2))
        assert rec.stats["AH::border"].flops == pytest.approx(
            tracing.arrowhead_border_flops(3, 4, 2, 1))

    def test_estimate_seconds_scales_refine_sweeps(self):
        # the round-15 cost-model satellite: IR::* phases price by the
        # measured sweep count, every other phase is untouched
        rec = tracing.Recorder()
        with rec:
            with tracing.scope("IR::residual"):
                tracing.emit(flops=1e9)
            with tracing.scope("AH::schur"):
                tracing.emit(flops=1e9)
        spec = tracing.DeviceSpec("test", 100.0, 1000.0, 100.0, 16e9)
        one = rec.estimate_seconds(spec, jnp.float32, refine_sweeps=1.0)
        three = rec.estimate_seconds(spec, jnp.float32, refine_sweeps=3.0)
        assert three["IR::residual"][0] == pytest.approx(
            3.0 * one["IR::residual"][0])
        assert three["AH::schur"][0] == pytest.approx(one["AH::schur"][0])

    def test_refine_sweeps_from_stats_feed(self):
        assert tracing.refine_sweeps_from_stats(None) == 1.0
        assert tracing.refine_sweeps_from_stats(
            {"iters": {"p50": 2.5}}) == 2.5
        assert tracing.refine_sweeps_from_stats(
            {"iters": {"p50": 0.0}}) == 1.0
        assert tracing.refine_sweeps_from_stats({"iters": {}}) == 1.0
