"""Phase-level wall-time attribution (bench/trace.phase_attribution).

Three layers, none needing a TPU:

* pure logic — hlo_phase_map parsing, the host-plane bucketing over
  synthesized xplane protos, and the check_bubble_fraction gate math;
* the ledger validation contract (obs/ledger.validate_phase_seconds),
  including backward compatibility with records that predate the block;
* one real end-to-end attribution on the CPU rig: a traced cholinv loop
  must attribute nonzero seconds to registered CI:: phases with
  attributed <= wall (after the documented clamp), and synthetic work
  stamped under one scope must land in that scope's bucket.
"""

import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: E402

from capital_tpu.bench import trace  # noqa: E402
from capital_tpu.obs import ledger  # noqa: E402
from capital_tpu.utils import tracing  # noqa: E402


class TestHloPhaseMap:
    def test_maps_instruction_to_registered_tag(self):
        text = (
            '%dot.5 = f32[64,64] dot(%a, %b), metadata={'
            'op_name="jit(loop)/jit(main)/CI.tmu/dot_general" '
            'source_file="x.py"}\n'
        )
        assert trace.hlo_phase_map(text) == {"dot.5": "CI::tmu"}

    def test_longest_tag_wins(self):
        # an op_name mentioning a nested scope chain attributes to the
        # innermost (longest) registered tag, same as _bucket
        text = (
            '%f.1 = f32[8] add(%x, %y), metadata={'
            'op_name="jit(f)/CI.inv/CI.factor_diag/add"}\n'
        )
        assert trace.hlo_phase_map(text)["f.1"] == "CI::factor_diag"

    def test_entry_computation_wins_name_collision(self):
        # the entry computation is printed last; its binding must win a
        # name collision with a nested computation (the runtime's thunk
        # events carry ENTRY instruction names)
        text = (
            '%dot.1 = f32[8] dot(%a, %b), metadata={op_name="jit(f)/CI.trsm/dot"}\n'
            'ENTRY %main {\n'
            '%dot.1 = f32[8] dot(%a, %b), metadata={op_name="jit(f)/CI.tmu/dot"}\n'
            '}\n'
        )
        assert trace.hlo_phase_map(text)["dot.1"] == "CI::tmu"

    def test_unregistered_scopes_absent(self):
        text = '%c.1 = f32[8] copy(%x), metadata={op_name="jit(f)/transpose"}\n'
        assert trace.hlo_phase_map(text) == {}


def _host_space(events, stat_mid=7):
    """One host plane whose line carries `events` =
    [(off_ps, dur_ps, mid, name, has_hlo_stat)]."""
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/host:CPU (pid 1)")
    plane.stat_metadata[stat_mid].name = "hlo_op"
    line = plane.lines.add(name="tf_XLATfrtCpuClient/1")
    for off, dur, mid, name, has_stat in events:
        ev = line.events.add(offset_ps=off, duration_ps=dur, metadata_id=mid)
        if has_stat:
            ev.stats.add(metadata_id=stat_mid, str_value=name)
        plane.event_metadata[mid].name = name
    return space


class TestHostPlaneBudget:
    def test_buckets_through_phase_map(self):
        ps = 1_000_000  # 1 us -> 1e-3 ms
        space = _host_space([
            (0, 4 * ps, 1, "dot.5", True),
            (4 * ps, 2 * ps, 2, "broadcast_add_fusion", True),
        ])
        pm = {"dot.5": "CI::tmu", "broadcast_add_fusion": "CI::trsm"}
        budget = trace._host_plane_budget([("t", space)], pm)
        assert budget == {
            "CI::tmu": pytest.approx(4e-3),
            "CI::trsm": pytest.approx(2e-3),
        }

    def test_bookkeeping_events_dropped_before_sweep(self):
        # a ThunkExecutor wait-region spanning everything carries no
        # hlo_op stat: it must neither bucket anywhere nor absorb the op
        # events' durations as children
        ps = 1_000_000
        space = _host_space([
            (0, 100 * ps, 9, "ThunkExecutor::Execute (wait)", False),
            (10 * ps, 4 * ps, 1, "dot.5", True),
        ])
        budget = trace._host_plane_budget([("t", space)], {"dot.5": "CI::tmu"})
        assert budget == {"CI::tmu": pytest.approx(4e-3)}

    def test_unmapped_ops_fall_to_kind_buckets(self):
        ps = 1_000_000
        space = _host_space([
            (0, 1 * ps, 1, "copy.3", True),
            (1 * ps, 1 * ps, 2, "loop_fusion.2", True),
            (2 * ps, 1 * ps, 3, "tuple.1", True),
        ])
        budget = trace._host_plane_budget([("t", space)], {})
        assert budget == {
            "copy": pytest.approx(1e-3),
            "fusion": pytest.approx(1e-3),
            "other": pytest.approx(1e-3),
        }

    def test_tpu_planes_ignored(self):
        space = _host_space([(0, 1_000_000, 1, "dot.5", True)])
        space.planes[0].name = "/device:TPU:0 (pid 1)"
        assert trace._host_plane_budget([("t", space)], {"dot.5": "CI::tmu"}) == {}


class TestBubbleGate:
    def test_within_budget_returns_fraction(self):
        frac = trace.check_bubble_fraction({"CI::tmu": 1.0}, 0.2, 0.5)
        assert frac == 0.2

    def test_over_budget_raises(self):
        with pytest.raises(RuntimeError, match="bubble-budget regression"):
            trace.check_bubble_fraction({"CI::tmu": 1.0}, 0.6, 0.5)

    def test_empty_attribution_is_a_dead_gate(self):
        # nothing attributed -> the gate must fail LOUDLY, not pass
        with pytest.raises(RuntimeError, match="dead"):
            trace.check_bubble_fraction({}, 0.0, 0.5)

    def test_clamp_math(self):
        # CPU thunk concurrency can attribute more op-seconds than wall;
        # phase_attribution clamps at 0 rather than reporting a negative
        # bubble.  Reproduce the formula on synthetic budgets.
        wall, attributed = 1.0, 1.3
        bubble = max(0.0, (wall - attributed) / wall)
        assert bubble == 0.0
        assert trace.check_bubble_fraction({"x": attributed}, bubble, 0.5) == 0.0


class TestLedgerValidation:
    def _meas(self, **over):
        meas = {
            "metric": "trace_cholinv_attributed",
            "value": 0.76,
            "unit": "frac",
            "phase_seconds": {"CI::tmu": 0.004, "copy": 0.001},
            "bubble_frac": 0.24,
        }
        meas.update(over)
        return meas

    def test_valid_block(self):
        assert ledger.validate_phase_seconds(self._meas()) == []

    def test_records_without_the_block_stay_valid(self):
        # backward compatibility: a measured block that predates the
        # fields validates clean
        assert ledger.validate_phase_seconds(
            {"metric": "cholinv_tflops", "value": 171.7}
        ) == []

    def test_negative_and_nan_phase_seconds_flagged(self):
        probs = ledger.validate_phase_seconds(
            self._meas(phase_seconds={"CI::tmu": -1.0})
        )
        assert any("non-negative" in p for p in probs)
        probs = ledger.validate_phase_seconds(
            self._meas(phase_seconds={"CI::tmu": float("nan")})
        )
        assert probs

    def test_bubble_frac_range(self):
        assert ledger.validate_phase_seconds(self._meas(bubble_frac=1.5))
        assert ledger.validate_phase_seconds(self._meas(bubble_frac=-0.1))

    def test_bubble_without_phases_flagged(self):
        meas = self._meas()
        del meas["phase_seconds"]
        probs = ledger.validate_phase_seconds(meas)
        assert any("without phase_seconds" in p for p in probs)

    def test_diff_rejects_malformed_attribution_record(self):
        man = ledger.manifest(dtype="float32")
        good = ledger.record("bench:trace:cholinv", dict(man),
                             measured=self._meas())
        bad = ledger.record("bench:trace:cholinv", dict(man),
                            measured=self._meas(bubble_frac=2.0))
        assert ledger.diff([good], [good]) == []
        with pytest.raises(ledger.LedgerIncompatible, match="phase"):
            ledger.diff([good], [bad])

    def test_diff_watches_attributed_fraction_drift(self):
        # the drift watch the ISSUE names: measured.value is the
        # attributed fraction, so a bubble growth reads as a value drop
        man = ledger.manifest(dtype="float32")
        a = ledger.record("bench:trace:cholinv", dict(man),
                          measured=self._meas(value=0.9, bubble_frac=0.1))
        b = ledger.record("bench:trace:cholinv", dict(man),
                          measured=self._meas(value=0.5, bubble_frac=0.5))
        regs = ledger.diff([a], [b], tol_metric=0.10)
        assert len(regs) == 1 and regs[0].field == "measured.value"


class TestEndToEndAttribution:
    def test_cholinv_loop_attributes_to_registered_phases(self):
        run = trace._cholinv_run(
            256, jnp.float32, 128, 1, "highest", mode="xla"
        )
        phase_s, bubble, wall = trace.phase_attribution(run, 1)
        assert phase_s, "nothing attributed on the CPU rig"
        assert 0.0 <= bubble <= 1.0
        assert wall > 0.0
        # the attributed seconds respect the wall after the clamp:
        # bubble == max(0, 1 - attributed/wall)
        attributed = sum(phase_s.values())
        assert bubble == pytest.approx(
            max(0.0, (wall - attributed) / wall), abs=1e-12
        )
        # real cholinv phases must appear — attribution through the
        # compiled metadata, not just kind catch-alls
        assert any(k.startswith("CI::") for k in phase_s)

    def test_synthetic_work_lands_in_its_scope(self):
        # a loop whose only heavy op is stamped CI::tmu must put CI::tmu
        # at the top of the attribution
        a = jnp.ones((512, 512), jnp.float32)

        @jax.jit
        def loop(a, k):
            def body(_, c):
                with tracing.scope("CI::tmu"):
                    c = jnp.dot(c, c, precision="highest") / 512.0
                return c

            return jnp.sum(jax.lax.fori_loop(0, k, body, a),
                           dtype=jnp.float32)

        run = trace._aot_run(loop, a, jnp.int32(4))
        run()
        phase_s, bubble, _wall = trace.phase_attribution(run, 4)
        assert phase_s
        assert max(phase_s, key=phase_s.get) == "CI::tmu"
        assert 0.0 <= bubble <= 1.0
