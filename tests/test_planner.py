"""The autotune planner: the alpha-beta model the `--top-k` prefilter ranks
cholinv configs with (autotune/planner.py)."""

import numpy as np

from capital_tpu.autotune import planner
from capital_tpu.utils.config import BaseCasePolicy


def test_predict_copy_term():
    """The copy-bytes term mirrors the runtime's emissions: materializing
    whole-buffer round-trips on a mesh, band-sized residue under the
    persistent layout, nothing at all on one device (the copy-free d==1
    route)."""
    bcs = [128]
    pols = [BaseCasePolicy.REPLICATE_COMM_COMP]
    kw = dict(peak_flops=1e14)
    blk, _ = planner.cholinv_predict(8192, (2, 2, 1), bcs, pols, **kw)
    per, _ = planner.cholinv_predict(
        8192, (2, 2, 1), bcs, pols, balance="tile_cyclic_persistent", **kw
    )
    # the persistent layout's band-sized residue + 3 lifetime permutes
    # must undercut the materializing schedule's per-phase P^2 round-trips
    assert per[0, 0] < blk[0, 0]
    # d==1: balance changes nothing — there is no copy term to remove
    one_b, _ = planner.cholinv_predict(8192, (1, 1, 1), bcs, pols, **kw)
    one_p, _ = planner.cholinv_predict(
        8192, (1, 1, 1), bcs, pols, balance="tile_cyclic_persistent", **kw
    )
    np.testing.assert_allclose(one_b, one_p)
    # and the term is real: an infinitely fast HBM recovers the old model
    fast, _ = planner.cholinv_predict(
        8192, (2, 2, 1), bcs, pols, hbm_bytes_per_s=1e30, **kw
    )
    assert fast[0, 0] < blk[0, 0]


def test_predict_chunks_axis():
    """num_chunks moves ONLY the alpha (collective-launch) term: monotone in
    q on a mesh, identical bytes (round-4: the planner previously ignored
    chunks, ranking every q identically), no-op on one device."""
    bcs = [128, 256]
    pols = [BaseCasePolicy.REPLICATE_COMM_COMP]
    prev = None
    for q in (0, 2, 4):
        out, _ = planner.cholinv_predict(
            2048, (2, 2, 2), bcs, pols, peak_flops=1e14, num_chunks=q,
        )
        ref = np.array(
            [[
                planner.config_seconds(
                    2048, 2, 2, 2, 1e14, 4.5e10, 1e-6, 2, bc, 0, 1, True, q
                )
                for bc in bcs
            ]]
        )
        np.testing.assert_allclose(out, ref, rtol=1e-12)
        if prev is not None:
            assert np.all(out > prev)
        prev = out
    one, _ = planner.cholinv_predict(
        2048, (1, 1, 1), bcs, pols, peak_flops=1e14, num_chunks=4,
    )
    one0, _ = planner.cholinv_predict(
        2048, (1, 1, 1), bcs, pols, peak_flops=1e14,
    )
    np.testing.assert_allclose(one, one0)


def test_predict_model_sanity():
    """Replicated base case should beat gather-to-root in predicted collective
    count; distributed grids pay communication a 1x1x1 grid does not."""
    bcs = [128]
    out_multi, _ = planner.cholinv_predict(
        4096, (2, 2, 2), bcs,
        [BaseCasePolicy.REPLICATE_COMM_COMP, BaseCasePolicy.NO_REPLICATION],
        peak_flops=1e14,
    )
    assert out_multi[0, 0] < out_multi[1, 0]  # fewer collective rounds
    out_single, _ = planner.cholinv_predict(
        4096, (1, 1, 1), bcs, [BaseCasePolicy.REPLICATE_COMM_COMP],
        peak_flops=1e14,
    )
    assert out_single[0, 0] < out_multi[0, 0]  # no comm term
