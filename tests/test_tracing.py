"""Tracing / cost-model subsystem tests.

The reference's profiling layer (critter, SURVEY §5.1) decomposes cost per
algorithm phase; here the equivalent is trace-time cost attribution under
named scopes.  These tests check the attribution wiring, the analytic model's
arithmetic, and the table writers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import cholesky, qr
from capital_tpu.parallel.topology import Grid
from capital_tpu.utils import tracing


def _spd(n, dtype=jnp.float64, seed=0):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((n, n))
    return jnp.asarray(M @ M.T + n * np.eye(n), dtype=dtype)


def test_gemm_cost_arithmetic(grid2x2x2):
    M = N = K = 64
    flops, comm, ncoll = tracing.gemm_cost(grid2x2x2, M, N, K, jnp.float32)
    # flops split evenly over 8 devices
    assert flops == pytest.approx(2 * M * N * K / 8)
    # c=2 takes the masked-psum branch: d/c = 1 step, one psum-bcast pair of
    # the (M/2, K/2) and (K/2, N/2) panels (2x ring bytes each), plus the z
    # allreduce of the C block — what _explicit_matmul emits for c>1
    # (TestExplicitEmission::test_psum_bcast_path_matches_model_c2)
    a_pan = (M / 2) * (K / 2) * 4
    b_pan = (K / 2) * (N / 2) * 4
    c_blk = (M / 2) * (N / 2) * 4
    expect = 2 * a_pan * 0.5 + 2 * b_pan * 0.5 + 2 * c_blk * 0.5
    assert comm == pytest.approx(expect)
    assert ncoll == 3
    # and the c=1 branch prices the amortized gathers
    g1 = Grid.square(c=1, devices=jax.devices("cpu")[:4])
    _, comm1, ncoll1 = tracing.gemm_cost(g1, M, N, K, jnp.float32)
    a_row = (M / 2) * K * 4
    b_col = K * (N / 2) * 4
    assert comm1 == pytest.approx(a_row * 0.5 + b_col * 0.5)
    assert ncoll1 == 2


def test_single_device_costs_no_comm():
    g1 = Grid.square(c=1, devices=jax.devices("cpu")[:1])
    flops, comm, ncoll = tracing.gemm_cost(g1, 32, 32, 32, jnp.float32)
    assert comm == 0.0 and ncoll == 0
    assert flops == pytest.approx(2 * 32**3)


def test_recorder_captures_cholinv_phases(grid2x2x1):
    n = 64
    A = _spd(n)
    cfg = cholesky.CholinvConfig(base_case_dim=16)
    with tracing.Recorder() as rec:
        R, Rinv = jax.jit(lambda a: cholesky.factor(grid2x2x1, a, cfg))(A)
    jax.block_until_ready((R, Rinv))
    tags = set(rec.stats)
    assert {"CI::factor_diag", "CI::trsm", "CI::tmu", "CI::inv"} <= tags
    total = rec.total()
    assert total.flops > 0 and total.calls > 0
    # base case: at least one panel factorization worth of flops
    assert rec.stats["CI::factor_diag"].flops >= tracing.potrf_trtri_flops(16)
    # distributed trmm moves bytes on a 2x2 grid
    assert rec.stats["CI::trsm"].comm_bytes > 0


def test_recorder_captures_cacqr_phases(grid_flat8):
    m, n = 256, 16
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.standard_normal((m, n)))
    with tracing.Recorder() as rec:
        Q, R = jax.jit(
            lambda a: qr.factor(grid_flat8, a, qr.CacqrConfig(num_iter=2, regime="1d"))
        )(A)
    jax.block_until_ready((Q, R))
    assert {"CQR::gram", "CQR::chol", "CQR::formR", "CQR::merge"} <= set(rec.stats)
    # two sweeps -> gram recorded twice
    assert rec.stats["CQR::gram"].calls == 2
    # gram flops: 2mn^2/P per sweep
    assert rec.stats["CQR::gram"].flops == pytest.approx(2 * 2 * m * n * n / 8)
    # the gram allreduce is the only collective of the 1D sweep
    assert rec.stats["CQR::gram"].collectives == 2


def test_recorder_inactive_is_free(grid2x2x1):
    # emit with no active recorder must not raise or leak state
    tracing.emit(flops=1.0)
    with tracing.Recorder() as rec:
        pass
    assert rec.total().flops == 0


def test_estimate_and_tables(tmp_path, grid2x2x1):
    A = _spd(32)
    cfg = cholesky.CholinvConfig(base_case_dim=16)
    with tracing.Recorder() as rec:
        out = jax.jit(lambda a: cholesky.factor(grid2x2x1, a, cfg))(A)
    jax.block_until_ready(out)
    est = rec.estimate_seconds(tracing.device_spec(), jnp.float64)
    assert all(c >= 0 and m >= 0 for c, m in est.values())

    times = tmp_path / "cp_times.txt"
    costs = tmp_path / "cp_costs.txt"
    tracing.write_times_table(str(times), [("cfg0", 0.123, est)])
    tracing.write_costs_table(str(costs), [("cfg0", rec)])
    t_lines = times.read_text().splitlines()
    c_lines = costs.read_text().splitlines()
    assert len(t_lines) == 2 and t_lines[0].startswith("Config")
    assert "Raw" in t_lines[0] and "0.123" in t_lines[1]
    assert len(c_lines) == 2 and "CI::trsm-comp" in c_lines[0]


def test_note_counts_under_own_tag():
    with tracing.Recorder() as rec:
        tracing.note("layout_fallback")
        tracing.note("layout_fallback")
    assert rec.stats["layout_fallback"].calls == 2
    assert rec.stats["layout_fallback"].flops == 0.0
    tracing.note("layout_fallback")  # no active recorder: must be a no-op


def test_tables_with_empty_rows(tmp_path):
    # an all-UNRESOLVED sweep still writes its tables; header-only output,
    # no max() crash on the empty column set
    times = tmp_path / "t.txt"
    costs = tmp_path / "c.txt"
    tracing.write_times_table(str(times), [])
    tracing.write_costs_table(str(costs), [])
    assert times.read_text().splitlines() == ["Config  Raw     "]
    assert costs.read_text().splitlines()[0].startswith("Config")


def test_estimate_seconds_prices_alpha_latency():
    # the comm term is beta (bytes/bandwidth) PLUS alpha per collective;
    # same bytes at a higher synchronization count must cost more
    spec = tracing.DeviceSpec("test", 100.0, 1000.0, 100.0, 16e9, alpha_s=1e-6)
    few, many = tracing.Recorder(), tracing.Recorder()
    with few:
        with tracing.scope("CI::trsm"):
            tracing.emit(1e9, 1e6, collectives=1)
    with many:
        with tracing.scope("CI::trsm"):
            tracing.emit(1e9, 1e6, collectives=100)
    _, comm_few = few.estimate_seconds(spec, jnp.bfloat16)["CI::trsm"]
    _, comm_many = many.estimate_seconds(spec, jnp.bfloat16)["CI::trsm"]
    assert comm_many == pytest.approx(comm_few + 99 * spec.alpha_s)
    beta = 1e6 / (spec.ici_gbps * 1e9)
    assert comm_few == pytest.approx(beta + spec.alpha_s)


def test_scope_rejects_unregistered_tag():
    with pytest.raises(ValueError, match="unregistered phase tag"):
        with tracing.scope("XX::nope"):
            pass


def test_register_phase_extends_live_registry():
    tag = "XX::test_only"
    assert tag not in tracing.PHASE_REGISTRY
    try:
        tracing.register_phase(tag)
        assert tag in tracing.PHASE_REGISTRY
        with tracing.Recorder() as rec:
            with tracing.scope(tag):
                tracing.emit(flops=1.0)
        assert rec.stats[tag].flops == 1.0
        # the trace tool's dot-form buckets see live registrations
        from capital_tpu.bench import trace as trace_tool

        assert "XX.test_only" in trace_tool._phase_tags()
    finally:
        # registry is module-global: restore to keep other tests order-free
        tracing.PHASE_REGISTRY = tuple(
            t for t in tracing.PHASE_REGISTRY if t != tag
        )
        tracing._PHASE_SET.discard(tag)


def test_trace_tool_tags_derive_from_registry():
    from capital_tpu.bench import trace as trace_tool

    # _phase_tags() is the live derivation (PHASE_TAGS is a snapshot frozen
    # at import, which another test's transient registration may predate)
    assert set(trace_tool._phase_tags()) == {
        t.replace("::", ".") for t in tracing.PHASE_REGISTRY
    }
    # the tag the old hardcoded list silently dropped to 'other'
    assert "RT.batch_write" in trace_tool.PHASE_TAGS


def test_device_spec_lookup():
    import types

    s = tracing.device_spec(jax.devices("cpu")[0])
    assert s.name == "cpu"
    assert tracing.device_spec().peak_tflops(jnp.float32) > 0
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert tracing.device_spec(v5e).peak_tflops(jnp.bfloat16) == 197.0
    with pytest.raises(ValueError, match="no DeviceSpec"):
        tracing.device_spec(types.SimpleNamespace(
            platform="tpu", device_kind="TPU v99"))


class TestTriFractions:
    """VERDICT r2 #4: the max-per-process vs volumetric executed-flop views
    of the explicit schedule's dead-segment skipping, verified against an
    independent element-level enumeration of triangle/rectangle
    intersections."""

    @staticmethod
    def _brute(M, K, N, d, c, q, a_uplo=None, b_uplo=None, out_uplo=None):
        import numpy as np

        lk, w = K // d, K // d // max(1, q)
        mb, nb = M // d, N // d
        spl = d // c
        fracs = []
        for zi in range(c):
            segs = range(d) if c == 1 else [zi * spl + i for i in range(spl)]
            for xi in range(d):
                for yi in range(d):
                    if out_uplo is not None:
                        rows = np.arange(xi * mb, (xi + 1) * mb)[:, None]
                        cols = np.arange(yi * nb, (yi + 1) * nb)[None, :]
                        live_o = (
                            (rows <= cols) if out_uplo == "U" else (rows >= cols)
                        ).any()
                        if not live_o:
                            fracs.append(0.0)
                            continue
                    live = 0
                    for s in segs:
                        for ch in range(q):
                            klo = s * lk + ch * w
                            ks = np.arange(klo, klo + w)
                            ok = True
                            if a_uplo is not None:
                                rows = np.arange(xi * mb, (xi + 1) * mb)[:, None]
                                tri = (
                                    (rows <= ks[None, :])
                                    if a_uplo == "U"
                                    else (rows >= ks[None, :])
                                )
                                ok = ok and bool(tri.any())
                            if b_uplo is not None:
                                cols = np.arange(yi * nb, (yi + 1) * nb)[None, :]
                                tri = (
                                    (ks[:, None] <= cols)
                                    if b_uplo == "U"
                                    else (ks[:, None] >= cols)
                                )
                                ok = ok and bool(tri.any())
                            live += bool(ok)
                    fracs.append(live / (len(segs) * q))
        return sum(fracs) / len(fracs), max(fracs)

    @pytest.mark.parametrize("d", [2, 4])
    def test_matches_brute_force_and_closed_form(self, d):
        import types

        from capital_tpu.parallel import summa

        # tri_fractions is pure shape arithmetic: a stub grid covers the
        # d=4 face (16 devices) the 8-device rig cannot build
        g = types.SimpleNamespace(dx=d, dy=d, c=1, num_chunks=0, num_devices=d * d)
        n = 64
        mean_f, max_f = summa.tri_fractions(g, n, n, n, a_uplo="U")
        bm, bx = self._brute(n, n, n, d, 1, 1, a_uplo="U")
        assert (mean_f, max_f) == (bm, bx)
        # closed form: device row xi executes (d-xi)/d of the segments
        assert max_f == 1.0
        assert mean_f == pytest.approx((d + 1) / (2 * d))

    def test_c2_and_chunks_match_brute_force(self, grid2x2x2):
        from capital_tpu.parallel import summa

        g = grid2x2x2
        for kw in (dict(a_uplo="L"), dict(b_uplo="U"), dict(out_uplo="U")):
            got = summa.tri_fractions(g, 64, 64, 64, **kw)
            want = self._brute(64, 64, 64, g.dx, g.c, 1, **kw)
            assert got == want, (kw, got, want)

    def test_recorder_carries_three_views(self, grid2x2x1):
        from capital_tpu.parallel import summa

        g = grid2x2x1
        M = jax.device_put(
            jnp.asarray(np.random.default_rng(0).standard_normal((64, 64))),
            g.face_sharding(),
        )
        with tracing.Recorder() as rec:
            jax.jit(
                lambda a: summa.trmm(
                    g, a, a, summa.TrmmArgs(side="L", uplo="U"), mode="explicit"
                )
            ).lower(M)
        st = rec.total()
        # homogeneous model: dense; executed: mean 3/4, critical path full
        assert st.flops_max == pytest.approx(st.flops)
        assert st.flops_vol == pytest.approx(0.75 * st.flops)
