"""Per-group execution streams + one-sided signal gossip (DESIGN.md §13).

Three layers, mirroring how the subsystem can fail:

* ``TestSignalBoard`` — the one-sided protocol primitive: version-exact
  payload delivery under ``signal >= v`` waits, monotone signals, bounded
  retention.
* ``TestExecAccounting`` — the timeline arithmetic with a synthetic
  clock: ``exec_overlap_s`` is positive IFF busy spans from *different*
  streams interleave; same-stream pipelining never counts.
* ``TestStreamParity`` / ``TestStreamMechanics`` — the engine itself:
  ``streams > 1`` must be loss/staleness/param-EXACT vs the single-stream
  pipeline engine (which is itself exact vs the monolithic oracle, so the
  stream engine transitively inherits the PR-3 parity contract), plus the
  plumbing guards.
"""
import itertools
import json

import jax
import numpy as np
import pytest

from _fixtures import mlp_batch as _batch, mlp_problem as _mlp_problem
from repro.core import make_backend
from repro.launch.pipeline import StageTimeline
from repro.launch.streams import SignalBoard
from repro.optim import constant, momentum


class TestSignalBoard:
    def test_wait_returns_version_exact_payload(self):
        b = SignalBoard()
        b.put_signal("plane:g", 3, "v3")
        b.put_signal("plane:g", 4, "v4")
        # a consumer of version 3 that wakes up after 4 landed must still
        # read 3's buffer — the lagging-fwd-slice race the board exists for
        assert b.wait_until("plane:g", 3) == "v3"
        assert b.wait_until("plane:g", 4) == "v4"
        assert b.read("plane:g") == 4

    def test_signals_are_monotone(self):
        b = SignalBoard()
        b.put_signal("s", 5)
        with pytest.raises(ValueError, match="monotone"):
            b.put_signal("s", 4)

    def test_wait_timeout_raises_not_hangs(self):
        b = SignalBoard()
        with pytest.raises(TimeoutError, match="signal_wait_until"):
            b.wait_until("never", 1, timeout=0.05)

    def test_retention_window_eviction(self):
        b = SignalBoard(keep=2)
        for v in range(5):
            b.put_signal("s", v, f"v{v}")
        assert b.wait_until("s", 4) == "v4"
        assert b.wait_until("s", 3) == "v3"
        with pytest.raises(KeyError, match="evicted"):
            b.wait_until("s", 1)

    def test_reset_clears_slots(self):
        b = SignalBoard()
        b.put_signal("s", 9, "x")
        b.reset()
        assert b.read("s") is None
        b.put_signal("s", 0, "fresh")  # monotonicity restarts
        assert b.wait_until("s", 0) == "fresh"


class TestExecAccounting:
    """The per-stream overlap arithmetic, pinned with a synthetic clock —
    no threads, no jax, no timing flakes."""

    @staticmethod
    def _tl():
        clk = itertools.count()
        return StageTimeline(clock=lambda: float(next(clk)))

    def test_overlap_iff_spans_interleave_across_streams(self):
        tl = self._tl()
        # fwd busy [0, 10]; gossip busy [4, 8] — 4s of true concurrency
        tl.record_exec("fwd", 0, stream="fwd", enqueue=0.0,
                       exec_start=0.0, complete=10.0)
        tl.record_exec("gossip", 0, stream="gossip", enqueue=1.0,
                       exec_start=4.0, complete=8.0, group="l1")
        s = tl.summary()
        assert s["streams"] == 2
        assert s["exec_overlap_s"] == pytest.approx(4.0)
        assert s["stream_busy_s"] == {"fwd": pytest.approx(10.0),
                                      "gossip": pytest.approx(4.0)}

    def test_no_overlap_when_spans_disjoint(self):
        tl = self._tl()
        tl.record_exec("fwd", 0, stream="fwd", enqueue=0.0,
                       exec_start=0.0, complete=5.0)
        tl.record_exec("gossip", 0, stream="gossip", enqueue=0.0,
                       exec_start=5.0, complete=9.0)
        s = tl.summary()
        assert s["streams"] == 2
        assert s["exec_overlap_s"] == 0.0

    def test_same_stream_spans_never_count(self):
        tl = self._tl()
        # two overlapping records on ONE stream (merged busy interval):
        # pipelining inside a stream is not execution concurrency
        tl.record_exec("gossip", 0, stream="gossip", enqueue=0.0,
                       exec_start=0.0, complete=6.0, group="l1")
        tl.record_exec("gossip", 0, stream="gossip", enqueue=0.0,
                       exec_start=3.0, complete=9.0, group="l2")
        s = tl.summary()
        assert s["streams"] == 1
        assert s["exec_overlap_s"] == 0.0
        assert s["stream_busy_s"]["gossip"] == pytest.approx(9.0)

    def test_three_streams_integrate_busy_minus_one(self):
        tl = self._tl()
        # a [0,6], b [2,6], c [4,6]: ∫(k−1) = 0*2 + 1*2 + 2*2 = 6
        tl.record_exec("fwd", 0, stream="a", enqueue=0.0,
                       exec_start=0.0, complete=6.0)
        tl.record_exec("update", 0, stream="b", enqueue=0.0,
                       exec_start=2.0, complete=6.0)
        tl.record_exec("gossip", 0, stream="c", enqueue=0.0,
                       exec_start=4.0, complete=6.0)
        assert tl.summary()["exec_overlap_s"] == pytest.approx(6.0)

    def test_signal_wait_time_sums(self):
        tl = self._tl()
        tl.record_exec("fwd", 0, stream="fwd", enqueue=0.0,
                       exec_start=1.0, complete=2.0, wait_s=1.0)
        tl.record_exec("gossip", 0, stream="gossip", enqueue=0.0,
                       exec_start=2.5, complete=3.0, wait_s=2.5)
        assert tl.summary()["signal_wait_s"] == pytest.approx(3.5)

    def test_single_stream_engine_reports_streams_1(self):
        # dispatch-only events (the PipelineEngine path) must keep the
        # stream fields at their single-stream defaults
        tl = self._tl()
        ev = tl.begin("fwd", 0)
        class F:
            def is_ready(self):
                return True
        tl.commit(ev, F())
        tl.finalize()
        s = tl.summary()
        assert s["streams"] == 1
        assert s["exec_overlap_s"] == 0.0
        assert s["stream_busy_s"] == {}

    def test_dump_normalizes_stream_timestamps(self, tmp_path):
        tl = self._tl()
        tl.record_exec("fwd", 0, stream="fwd", enqueue=100.0,
                       exec_start=101.0, complete=103.0, wait_s=1.0)
        tl.record_exec("gossip", 0, stream="gossip", enqueue=100.5,
                       exec_start=102.0, complete=104.0, group="l1")
        path = tl.dump(str(tmp_path / "streams.json"))
        with open(path) as f:
            doc = json.load(f)
        ev = doc["events"][0]
        assert ev["stream"] == "fwd"
        assert ev["dispatch"] == pytest.approx(0.0)
        assert ev["enqueue"] == pytest.approx(-1.0)
        assert ev["exec_start"] == pytest.approx(0.0)
        assert doc["summary"]["streams"] == 2


class TestSummaryEdgeCases:
    """Degenerate timelines must aggregate to clean zeros — never divide
    by zero, never KeyError (ISSUE 10 hardening; the autotuner feeds
    these summaries straight into its overlap-efficiency term)."""

    @staticmethod
    def _tl():
        clk = itertools.count()
        return StageTimeline(clock=lambda: float(next(clk)))

    def test_zero_recorded_steps_full_default_summary(self):
        s = self._tl().summary()
        assert s == {"events": 0, "steps": 0, "wall_s": 0.0,
                     "overlap_events": 0, "overlap_s": 0.0,
                     "fwd_gossip_overlap_s": 0.0, "stage_s": {},
                     "streams": 1, "exec_overlap_s": 0.0,
                     "stream_busy_s": {}, "signal_wait_s": 0.0}

    def test_open_events_only_count_but_aggregate_to_zero(self):
        # a dispatch whose fence never retired: the event is counted but
        # no closed span exists — every aggregate stays at its default
        tl = self._tl()
        class Never:
            def is_ready(self):
                return False
        ev = tl.begin("fwd", 0)
        tl.commit(ev, Never())
        s = tl.summary()
        assert s["events"] == 1 and s["steps"] == 0
        assert s["wall_s"] == 0.0 and s["exec_overlap_s"] == 0.0

    def test_single_stream_single_event(self):
        tl = self._tl()
        tl.record_exec("fwd", 0, stream="fwd", enqueue=0.0,
                       exec_start=0.0, complete=3.0)
        s = tl.summary()
        assert s["streams"] == 1
        assert s["exec_overlap_s"] == 0.0
        assert s["stream_busy_s"] == {"fwd": pytest.approx(3.0)}

    def test_many_streams_never_interleaving_is_exactly_zero(self):
        # back-to-back spans across three streams sharing endpoints:
        # touching at a point is not overlap, and the sweep must not
        # accumulate rounding residue
        tl = self._tl()
        for i, name in enumerate(("a", "b", "c")):
            tl.record_exec("fwd", 0, stream=name, enqueue=0.0,
                           exec_start=float(2 * i),
                           complete=float(2 * i + 2))
        s = tl.summary()
        assert s["streams"] == 3
        assert s["exec_overlap_s"] == 0.0

    def test_zero_width_spans_no_division_by_zero(self):
        # two streams, both with instantaneous spans at the same tick:
        # wall_s == 0.0 and the sweep integral must still be exactly 0.0
        tl = self._tl()
        tl.record_exec("update", 0, stream="a", enqueue=5.0,
                       exec_start=5.0, complete=5.0)
        tl.record_exec("gossip", 0, stream="b", enqueue=5.0,
                       exec_start=5.0, complete=5.0)
        s = tl.summary()
        assert s["wall_s"] == 0.0
        assert s["exec_overlap_s"] == 0.0
        assert s["stream_busy_s"] == {"a": 0.0, "b": 0.0}
        # and the tuner's consumer of this summary stays finite on it
        from repro.launch.tuner import overlap_efficiency
        assert overlap_efficiency(s) == 0.0


def _run_backend(R, D, streams, steps=5):
    loss_fn, params = _mlp_problem()
    be = make_backend("prod", "layup", M=1, loss_fn=loss_fn,
                      optimizer=momentum(0.9), schedule=constant(0.05),
                      fb_ratio=R, update_delay=D, overlap=True,
                      streams=streams, measure_drift=True)
    st = be.init(jax.random.PRNGKey(0), params)
    hist = []
    for t in range(steps):
        st, m = be.step(st, _batch(t, 1, 4 * R), None)
        hist.append((float(m["loss"]), float(m["update_staleness"]),
                     np.asarray(m["layer_staleness"]).copy(),
                     float(m["disagreement"])))
    tree = jax.tree.map(np.asarray, be.export_params(st))
    summary = be.summary()
    if hasattr(be.engine, "close"):
        be.engine.close()
    return hist, tree, summary


class TestStreamParity:
    """streams > 1 is loss/staleness/param-EXACT vs the single-stream
    engine at the required operating points — the acceptance criterion.
    (The single-stream engine is exact vs the monolithic oracle, so the
    stream engine transitively matches the monolithic step too.)"""

    @pytest.mark.parametrize("R,D", [(1, 1), (2, 1)])
    def test_exact_vs_single_stream(self, R, D):
        base_hist, base_tree, _ = _run_backend(R, D, streams=1)
        got_hist, got_tree, summary = _run_backend(R, D, streams=3)
        for i, (a, b) in enumerate(zip(base_hist, got_hist)):
            assert a[0] == b[0], f"loss diverged at step {i}"
            assert a[1] == b[1], f"update_staleness diverged at step {i}"
            assert np.array_equal(a[2], b[2]), \
                f"layer_staleness diverged at step {i}"
            assert a[3] == b[3], f"disagreement diverged at step {i}"
        for la, lb in zip(jax.tree.leaves(base_tree),
                          jax.tree.leaves(got_tree)):
            assert np.array_equal(la, lb), "final params diverged"
        # R+2 capped: (1,1) → 3 streams; (2,1) → 3 streams
        assert summary["streams"] >= 2


class TestStreamMechanics:
    def test_streams_require_overlap(self):
        loss_fn, params = _mlp_problem()
        with pytest.raises(ValueError, match="overlap=True"):
            make_backend("prod", "layup", M=1, loss_fn=loss_fn,
                         optimizer=momentum(0.9), schedule=constant(0.05),
                         streams=2)

    def test_streams_require_flat_plane(self):
        loss_fn, params = _mlp_problem()
        with pytest.raises(ValueError, match="flat"):
            make_backend("prod", "layup", M=1, loss_fn=loss_fn,
                         optimizer=momentum(0.9), schedule=constant(0.05),
                         overlap=True, streams=2, flat=False)

    def test_timeline_records_execution_events(self):
        loss_fn, params = _mlp_problem()
        be = make_backend("prod", "layup", M=1, loss_fn=loss_fn,
                          optimizer=momentum(0.9), schedule=constant(0.05),
                          fb_ratio=2, update_delay=1, overlap=True,
                          streams=3, measure_drift=False)
        st = be.init(jax.random.PRNGKey(0), params)
        for t in range(3):
            st, _ = be.step(st, _batch(t, 1, 8), None)
        s = be.summary()  # finalizes the engine + timeline
        evs = be.timeline.events
        stages = {e["stage"] for e in evs}
        assert {"fwd", "update", "gossip", "clock"} <= stages
        streams_seen = {e["stream"] for e in evs}
        assert {"fwd", "update", "gossip"} <= streams_seen
        # one gossip (mix) event per plane group per step
        groups = {e.get("group") for e in evs if e["stage"] == "gossip"}
        assert groups == set(be.part.group_sizes)
        for e in evs:
            assert e["complete"] >= e["exec_start"] >= 0
            assert e["wait_s"] >= 0.0
        assert s["streams"] == 3
        assert s["signal_wait_s"] >= 0.0
        be.engine.close()

    def test_export_params_materializes_futures(self):
        loss_fn, params = _mlp_problem()
        be = make_backend("prod", "layup", M=1, loss_fn=loss_fn,
                          optimizer=momentum(0.9), schedule=constant(0.05),
                          overlap=True, streams=2, measure_drift=False)
        st = be.init(jax.random.PRNGKey(0), params)
        st, _ = be.step(st, _batch(0, 1, 4), None)
        tree = be.export_params(st)
        for leaf, ref in zip(jax.tree.leaves(tree), jax.tree.leaves(params)):
            assert np.asarray(leaf).shape[1:] == np.asarray(ref).shape
        be.engine.close()

    def test_board_retains_two_plane_versions(self):
        """Each plane payload is a whole parameter plane: after several
        steps the board holds only the two newest versions, so device
        memory stays flat instead of growing one plane per step."""
        loss_fn, params = _mlp_problem()
        be = make_backend("prod", "layup", M=1, loss_fn=loss_fn,
                          optimizer=momentum(0.9), schedule=constant(0.05),
                          fb_ratio=2, update_delay=1, overlap=True,
                          streams=3, measure_drift=False)
        st = be.init(jax.random.PRNGKey(0), params)
        steps = 6
        for t in range(steps):
            st, m = be.step(st, _batch(t, 1, 8), None)
            assert np.isfinite(float(m["loss"]))
        be.engine.finalize()
        slot = be.engine._plane_slot(next(iter(be.part.group_sizes)))
        board = be.engine.board
        assert board.read(slot) == steps
        for v in (steps, steps - 1):
            assert board.wait_until(slot, v, timeout=0.1) is not None
        with pytest.raises(KeyError, match="evicted"):
            board.wait_until(slot, steps - 2, timeout=0.1)
        be.engine.close()

    def test_reinit_resets_board_and_timeline(self):
        loss_fn, params = _mlp_problem()
        be = make_backend("prod", "layup", M=1, loss_fn=loss_fn,
                          optimizer=momentum(0.9), schedule=constant(0.05),
                          overlap=True, streams=2, measure_drift=False)
        st = be.init(jax.random.PRNGKey(0), params)
        st, m1 = be.step(st, _batch(0, 1, 4), None)
        first = float(m1["loss"])
        st = be.init(jax.random.PRNGKey(0), params)  # fresh measured run
        assert be.timeline.events == []
        st, m2 = be.step(st, _batch(0, 1, 4), None)
        assert float(m2["loss"]) == first
        be.engine.close()
