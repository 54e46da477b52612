"""The control, at a size a test run holds: the program's readings on a few
seeds stay under each cell's limits, and the control's read above one of
them.  control.py makes the same readings on the chip at the cells' own
sizes (PERF.md gives them).  On the CPU a float32 matmul ignores its
precision, so the serve control ('high' in place of 'highest') reads like
the program here: its test only checks that it runs and compares."""

import pytest

import control


@pytest.mark.parametrize("cell", ["cholinv.tiny", "cacqr.tiny.x4"])
def test_control_fails_program_passes(cell, tiny):
    out = control.readings(cell, 1.0, [3000000001, 3000000002],
                           [3000000003], catalog=tiny, require_tpu=False)
    limits = tiny.workload(cell)["limits"]
    for k, lim in limits.items():
        assert out["program_max"][k] <= lim, (k, out["program_max"][k])
    assert any(out["control_min"][k] > lim for k, lim in limits.items()), \
        out["control_min"]


def test_serve_control_runs(tiny):
    out = control.readings("serve.tiny", 1.0, [3000000001], [3000000002],
                           catalog=tiny, require_tpu=False)
    assert set(out["control_min"]) == set(tiny.workload("serve.tiny")
                                          ["limits"])
