"""Bench-driver smoke tests: every driver runs + validates at tiny sizes.

The reference's drivers ARE its integration tests (validation blocks in
bench/*/*.cpp, SURVEY §4); here they run under pytest on the virtual CPU
mesh so the whole driver surface stays green.
"""

import pytest

from capital_tpu.bench import drivers


def _run(argv):
    drivers.main(argv)


@pytest.mark.parametrize(
    "argv",
    [
        ["cholinv", "--n", "192", "--bc", "64", "--devices", "1"],
        ["cholinv", "--n", "128", "--bc", "32", "--c", "2", "--no-complete-inv"],
        ["cacqr", "--m", "1024", "--n", "32", "--variant", "2"],
        ["cacqr", "--m", "512", "--n", "16", "--variant", "1", "--devices", "1"],
        ["summa_gemm", "--m", "128", "--n", "128", "--k", "128", "--c", "2"],
        ["rectri", "--n", "128", "--bc", "32", "--devices", "1"],
        ["newton", "--n", "96", "--newton-iters", "25", "--devices", "1"],
        ["spd_inverse", "--n", "128", "--bc", "32", "--devices", "4"],
    ],
    ids=lambda a: "-".join(a[:1] + [x for x in a[1:] if not x.startswith("-")]),
)
def test_driver(argv):
    _run(argv + ["--dtype", "float32", "--iters", "1", "--validate"])


def test_suite_scaled():
    _run(["suite", "--dtype", "float32", "--iters", "1", "--scale", "64", "--validate"])


def test_flagship_auto_base_case():
    # the base-case pick must keep the flagship n tiled exactly — a wrong
    # pick silently pads (up to 2.4x flops) or misaligns every pallas
    # view window
    from capital_tpu.models.cholesky import padded_dim, pick_base_case

    assert pick_base_case(32768) == 512
    assert pick_base_case(49152) == 384
    assert pick_base_case(16384) == 512
    assert pick_base_case(24576) == 384
    for n in (32768, 49152, 24576):
        bc = pick_base_case(n)
        assert padded_dim(n, bc) == n and bc % 128 == 0
    # untileable n: falls back to the least-padding candidate
    # (40000 pads to 49152 under bc=384 vs 65536 under 512/256)
    assert pick_base_case(40000) == 384


def test_newton_reports_executed_iters():
    """VERDICT r2 weak #3: the newton driver must report flops for the
    iterations actually executed (early exit), not the max_iter budget —
    a run converging in 12 of 30 budgeted steps would otherwise print ~2.5x
    the true throughput."""
    args = drivers.build_parser().parse_args(
        ["newton", "--n", "96", "--newton-iters", "40", "--dtype", "float32",
         "--iters", "1", "--devices", "1"]
    )
    rec = drivers.newton(args)
    it = rec["iters_executed"]
    # a well-conditioned 96x96 f32 operand converges far inside 40 steps
    assert 0 < it < 40
    # reported TF/s must be derived from executed work: 2n³(2·it + 1).
    # rec["seconds"] is rounded to 5 decimals while rec["value"] came from
    # the unrounded time — widen the tolerance by the worst-case rounding
    # error so a fast backend cannot flake the comparison.
    want_flops = 2.0 * 96**3 * (2 * it + 1)
    got_flops = rec["value"] * 1e12 * rec["seconds"]
    tol = 0.05 + 0.5e-5 / rec["seconds"]
    assert abs(got_flops - want_flops) / want_flops < tol


def test_timed_oneshot_refuses_noise_floor():
    """The one-shot protocol must REFUSE (MeasurementUnresolved) rather than
    print a noise artifact when the step never clears the dispatch band —
    the same no-fake-numbers contract as timed_loop."""
    import jax.numpy as jnp
    import pytest as _pytest

    from capital_tpu.bench import harness

    def gen(i):
        return jnp.full((8, 8), 1.0, jnp.float32) * (1.0 + 0.0 * i)

    def step(a):
        return a[0, 0] * 2.0  # trivially below any noise band

    with _pytest.raises(harness.MeasurementUnresolved):
        harness.timed_oneshot(gen, step, iters=2, repeats=2)


def test_hbm_bytes_sane():
    """_hbm_bytes returns the runtime figure when available, else the
    conservative fallback — either way a plausible per-chip capacity."""
    v = drivers._hbm_bytes()
    assert 4e9 <= v <= 1e12
