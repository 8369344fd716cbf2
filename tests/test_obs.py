"""Round telemetry (repro/obs): streaming sinks, trace capture, alarms.

The load-bearing contracts pinned here:

  * BIT-NEUTRALITY — attaching sinks (or the AlarmMonitor) to a run leaves
    every computed row and the final params bit-identical to the sink-free
    run, in BOTH runtimes including cohort sampling and the int8 wire. Sinks
    consume host data the driver already fetched; they never touch the graph.
  * ONE HOST SYNC PER CHUNK — the engine's single ``jax.device_get`` per
    chunk is counted directly; sinks add zero transfers.
  * LIVE TAP — the opt-in ``jax.debug.callback`` tap observes the compiled
    math's own values: chunk results stay bit-exact with the tapless runner,
    and non-live slots are dropped.
  * TRACE CAPTURE — the round-phase ``jax.named_scope`` annotations reach
    the compiled runner's op metadata (fl.cohort_plan / cohort_gather /
    anchor_grad / local_trajectory / aa_step / uplink / scatter /
    round_metrics / stop_check; fl.psum is sharded-only), and a static
    window produces a loadable xplane.pb holding the engine's host spans
    (fl.chunk and its fl.engine.* children).
  * ROW SCHEMA — the JSONL emission passes scripts/check_metrics_jsonl.py,
    and the engine emits one row per EXECUTED round (header/footer framed).
"""
import glob
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (
    AlgoHParams,
    init_state,
    make_chunk_runner,
    make_round_fn,
    run_federated,
    run_rounds,
    solve_reference,
)
from repro.core.sharded import make_sharded_round_fn
from repro.data import make_binary_classification, partition
from repro.launch.mesh import make_host_mesh
from repro.models.logreg import make_logreg_problem
from repro.obs import (
    ROW_FIELDS,
    SCHEMA_VERSION,
    AlarmMonitor,
    AlarmRule,
    JsonlSink,
    LiveTap,
    MemorySink,
    MetricsSink,
    StdoutSink,
    TraceCapture,
    TraceConfig,
    make_sink,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def setup():
    X, y = make_binary_classification("synthetic_small", n=400, seed=0)
    clients = partition(X, y, num_clients=8, scheme="iid")
    prob = make_logreg_problem(clients, gamma=1e-3)
    wstar = solve_reference(prob, iters=50)
    return prob, wstar, make_host_mesh()


def _round_fn(prob, mesh, algo, hp, runtime, channel=None):
    if runtime == "sharded":
        return make_sharded_round_fn(algo, prob, hp, mesh, channel=channel)
    return make_round_fn(algo, prob, hp, channel)


def _history_identical(h0, h1, what=""):
    """Sinks must be bit-neutral: EXACT equality, not a tolerance."""
    np.testing.assert_array_equal(h1.loss, h0.loss, err_msg=what)
    np.testing.assert_array_equal(h1.grad_norm, h0.grad_norm, err_msg=what)
    np.testing.assert_array_equal(h1.rel_error, h0.rel_error, err_msg=what)
    np.testing.assert_array_equal(h1.gram_cond_max, h0.gram_cond_max,
                                  err_msg=what)
    np.testing.assert_array_equal(h1.comm_bytes, h0.comm_bytes, err_msg=what)
    for la, lb in zip(jax.tree.leaves(h0.final_params),
                      jax.tree.leaves(h1.final_params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=what)


class TestSinkUnits:
    def test_memory_sink_frames(self):
        s = MemorySink()
        s.open({"kind": "header"})
        s.emit([{"kind": "round", "round": 0}])
        s.emit([{"kind": "round", "round": 1}])
        s.close({"kind": "footer"})
        assert s.header["kind"] == "header"
        assert [r["round"] for r in s.rows] == [0, 1]
        assert s.footer["kind"] == "footer"

    def test_make_sink_specs(self, tmp_path):
        assert isinstance(make_sink("memory"), MemorySink)
        assert isinstance(make_sink("stdout"), StdoutSink)
        assert make_sink("stdout:5").every == 5
        js = make_sink(f"jsonl:{tmp_path}/m.jsonl")
        assert isinstance(js, JsonlSink)
        with pytest.raises(ValueError, match="path"):
            make_sink("jsonl")
        with pytest.raises(ValueError, match="unknown sink"):
            make_sink("carrier_pigeon")

    def test_sinks_satisfy_protocol(self):
        for s in (MemorySink(), StdoutSink(), JsonlSink("x"), AlarmMonitor()):
            assert isinstance(s, MetricsSink)

    def test_jsonl_nonfinite_to_null(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        s = JsonlSink(path)
        s.open({"v": SCHEMA_VERSION, "kind": "header"})
        s.emit([{"v": SCHEMA_VERSION, "kind": "round", "round": 0,
                 "loss": float("nan"), "grad_norm": float("inf")}])
        s.close({"v": SCHEMA_VERSION, "kind": "footer", "rounds": 1})
        lines = open(path).read().splitlines()
        assert len(lines) == 3
        row = json.loads(lines[1], parse_constant=lambda c: pytest.fail(
            f"non-strict constant {c}"))
        assert row["loss"] is None and row["grad_norm"] is None

    def test_jsonl_flushes_per_emit(self, tmp_path):
        """A crashed run must still hold every drained chunk on disk."""
        path = str(tmp_path / "m.jsonl")
        s = JsonlSink(path)
        s.open({"kind": "header"})
        s.emit([{"kind": "round", "round": 0, "loss": 1.0}])
        # file readable BEFORE close
        assert len(open(path).read().splitlines()) == 2
        s.close({"kind": "footer"})


class TestBitNeutrality:
    """Attached sinks leave runs bit-identical — the tentpole invariant."""

    @pytest.mark.parametrize("runtime", ["vmap", "sharded"])
    def test_engine_with_sinks_bit_identical(self, setup, runtime):
        prob, wstar, _ = setup
        # the adversarial config: cohort sampling + int8 wire + AA history
        hp = AlgoHParams(eta=0.5, local_epochs=3, cohort_size=4)
        kw = dict(w_star=wstar, runtime=runtime, channel="int8", chunk=2)
        h0 = run_federated(prob, "fedosaa_svrg", hp, 4, **kw)
        sink = MemorySink()
        h1 = run_federated(prob, "fedosaa_svrg", hp, 4, **kw,
                           sinks=[sink, AlarmMonitor()])
        _history_identical(h0, h1, what=f"engine/{runtime}")
        assert len(sink.rows) == 4

    def test_loop_with_sinks_bit_identical(self, setup):
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3, cohort_size=4)
        kw = dict(w_star=wstar, channel="int8")  # chunk=None: per-round loop
        h0 = run_federated(prob, "fedosaa_svrg", hp, 4, **kw)
        sink = MemorySink()
        h1 = run_federated(prob, "fedosaa_svrg", hp, 4, **kw,
                           sinks=[sink, AlarmMonitor()])
        _history_identical(h0, h1, what="loop/vmap")
        assert len(sink.rows) == 4

    def test_loop_and_engine_emit_matching_metric_rows(self, setup):
        """Same run through both drivers: the sink sees the same metric
        columns (documented rtol 1e-6, like tests/test_engine.py — the two
        paths are separate executables; wall attribution may differ)."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        s_loop, s_eng = MemorySink(), MemorySink()
        run_federated(prob, "fedosaa_svrg", hp, 4, w_star=wstar,
                      sinks=[s_loop])
        run_federated(prob, "fedosaa_svrg", hp, 4, w_star=wstar, chunk=2,
                      sinks=[s_eng])
        for f in ("loss", "grad_norm", "rel_error", "theta_mean",
                  "gram_cond_max", "gram_cond_mean", "aa_used_min",
                  "cohort_ess", "comm_bytes", "comm_bytes_total"):
            a = [r[f] for r in s_loop.rows]
            b = [r[f] for r in s_eng.rows]
            np.testing.assert_allclose(a, b, rtol=1e-5, err_msg=f)


class TestOneSyncPerChunk:
    def test_exactly_one_device_get_per_chunk(self, setup, monkeypatch):
        """Sinks are fed from the chunk's ONE existing host sync — attaching
        them must not add any device→host transfer."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        calls = []
        orig = jax.device_get

        def counting(x):
            calls.append(1)
            return orig(x)

        monkeypatch.setattr(jax, "device_get", counting)
        sink = MemorySink()
        _, trace = run_rounds(rf, state, 8, chunk=4, w_star=wstar,
                              sinks=[sink, AlarmMonitor()])
        assert trace.num_rounds == 8
        assert len(sink.rows) == 8
        assert len(calls) == 2  # 8 rounds / chunk 4 = 2 chunks = 2 syncs

    def test_row_indices_contiguous_and_cumulative(self, setup):
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        sink = MemorySink()
        run_rounds(rf, state, 5, chunk=2, w_star=wstar, sinks=[sink])
        assert [r["round"] for r in sink.rows] == [0, 1, 2, 3, 4]
        for f in ("comm_bytes_total", "wall_time_s"):
            col = [r[f] for r in sink.rows]
            assert all(b >= a for a, b in zip(col, col[1:])), f
        assert sink.header["fields"] == list(ROW_FIELDS)
        assert sink.footer["rounds"] == 5 and sink.footer["stopped"] is False

    def test_start_round_offsets_rows(self, setup):
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        sink = MemorySink()
        run_rounds(rf, state, 3, chunk=2, w_star=wstar, sinks=[sink],
                   start_round=10)
        assert [r["round"] for r in sink.rows] == [10, 11, 12]
        assert sink.header["start_round"] == 10


class TestLiveTap:
    def test_tap_matches_tapless_and_drops_nonlive(self, setup):
        """The debug.callback tap observes the compiled math's own values —
        tap rows equal the SAME run's stacked metrics bit-for-bit — while
        the tapped executable matches the tapless one at the engine's
        documented rtol 1e-6 (the inserted callback shifts XLA fusion by an
        ulp; see make_chunk_runner). Slots past n_live never reach the tap."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        tap = LiveTap()
        r_plain = make_chunk_runner(rf, 4, w_star=wstar, donate=False)
        r_tap = make_chunk_runner(rf, 4, w_star=wstar, donate=False, tap=tap)
        s0 = init_state(prob, jax.random.PRNGKey(0), hp, None, "fedosaa_svrg")
        s1 = init_state(prob, jax.random.PRNGKey(0), hp, None, "fedosaa_svrg")
        out0 = r_plain(s0, np.int32(3))  # short chunk: slot 3 not live
        out1 = r_tap(s1, np.int32(3))
        jax.effects_barrier()
        for la, lb in zip(jax.tree.leaves(out0), jax.tree.leaves(out1)):
            a, b = np.asarray(la), np.asarray(lb)
            if a.dtype.kind == "f":
                mask = ~(np.isnan(a) & np.isnan(b))
                np.testing.assert_allclose(b[mask], a[mask], rtol=1e-6,
                                           atol=1e-7)
            else:
                np.testing.assert_array_equal(a, b)
        assert [r["slot"] for r in tap.rows] == [0, 1, 2]
        # vs the SAME (tapped) executable: exactly the values it computed
        _, _, ms, rels, _ = out1
        for i, row in enumerate(tap.rows):
            assert row["loss"] == float(np.asarray(ms.loss)[i])
            assert row["rel_error"] == float(np.asarray(rels)[i])


class TestTraceCapture:
    def test_static_window_produces_scoped_trace(self, setup, tmp_path):
        """--trace-rounds acceptance: the runner's compiled HLO carries
        every vmap round-phase scope in its op metadata, and the window's
        xplane.pb holds the engine's host spans for the one chunk it
        covers — each fl.engine.* span inside its fl.chunk step span."""
        from jax.profiler import ProfileData

        prob, _, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3, cohort_size=4)
        rf = make_round_fn("fedosaa_svrg", prob, hp, "int8")
        state = init_state(prob, jax.random.PRNGKey(0), hp, "int8",
                           "fedosaa_svrg")
        runner = make_chunk_runner(rf, 2)
        txt = runner.lower(state, np.int32(2)).compile().as_text()
        for scope in ("fl.cohort_plan", "fl.cohort_gather", "fl.anchor_grad",
                      "fl.local_trajectory", "fl.aa_step", "fl.uplink",
                      "fl.scatter", "fl.round_metrics", "fl.stop_check"):
            assert scope in txt, scope
        tdir = str(tmp_path / "trace")
        tc = TraceCapture(TraceConfig(trace_dir=tdir, start_round=0,
                                      num_rounds=2))
        _, trace = run_rounds(rf, state, 4, chunk=2, runner=runner,
                              trace_capture=tc)
        assert trace.num_rounds == 4
        assert tc.windows == [(0, 2)]
        assert not tc.active
        [path] = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                        "*.xplane.pb"))
        spans = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("fl."):
                            spans.setdefault(ev.name, []).append(ev)
        [chunk] = spans.pop("fl.chunk")
        assert dict(chunk.stats)["step_num"] == 0
        assert sorted(spans) == ["fl.engine.dispatch", "fl.engine.fetch",
                                 "fl.engine.rows", "fl.engine.wait"]
        lo, hi = chunk.start_ns, chunk.start_ns + chunk.duration_ns
        order = sorted((ev.start_ns, name) for name, evs in spans.items()
                       for ev in evs)
        assert [name for _, name in order] == [
            "fl.engine.dispatch", "fl.engine.wait", "fl.engine.fetch",
            "fl.engine.rows"]
        for [ev] in spans.values():
            assert lo <= ev.start_ns and ev.start_ns + ev.duration_ns <= hi

    def test_psum_scope_in_sharded_hlo(self, setup):
        """fl.psum wraps the sharded all-reduce; cheap compiled-HLO check
        instead of a second profiler run."""
        prob, _, mesh = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_sharded_round_fn("fedosaa_svrg", prob, hp, mesh)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        txt = jax.jit(rf).lower(state).compile().as_text()
        assert "fl.psum" in txt
        assert "fl.aa_step" in txt

    def test_trigger_file_arms_one_window(self, tmp_path):
        tdir = str(tmp_path / "trace")
        trigger = str(tmp_path / "TRACE_NOW")
        tc = TraceCapture(TraceConfig(trace_dir=tdir, trigger_file=trigger))
        tc.on_chunk_start(0, 4)   # no trigger yet: stays off
        tc.on_chunk_end(4)
        assert not tc.active and tc.windows == []
        open(trigger, "w").close()
        tc.on_chunk_start(4, 4)   # trigger consumed, window opens
        assert tc.active and not os.path.exists(trigger)
        tc.on_chunk_end(8)
        assert not tc.active and tc.windows == [(4, 8)]
        tc.on_chunk_start(8, 4)   # one touch = one window
        assert not tc.active
        tc.close()

    def test_close_stops_leaked_window(self, tmp_path):
        tc = TraceCapture(TraceConfig(trace_dir=str(tmp_path / "t"),
                                      start_round=0, num_rounds=100))
        tc.on_chunk_start(0, 4)
        assert tc.active
        tc.close()  # early exit: never leak an open profiler session
        assert not tc.active and tc.windows == [(0, -1)]

    def test_disabled_config(self, tmp_path):
        assert not TraceConfig(trace_dir=str(tmp_path)).enabled
        assert TraceConfig(trace_dir=str(tmp_path), num_rounds=2).enabled
        assert TraceConfig(trace_dir=str(tmp_path),
                           trigger_file="x").enabled


def _row(t, **kw):
    # a real row always carries a loss; a missing/null loss IS the
    # loss_nonfinite condition, so give unit tests a healthy default
    base = {"v": SCHEMA_VERSION, "kind": "round", "round": t, "loss": 0.5}
    base.update(kw)
    return base


class TestAlarms:
    def test_nonfinite_loss_requests_stop(self):
        mon = AlarmMonitor()
        mon.emit([_row(0, loss=0.5)])
        assert not mon.stop_requested
        mon.emit([_row(1, loss=float("nan"))])
        assert mon.stop_requested
        assert mon.events[0]["rule"] == "loss_nonfinite"
        # null (serialized non-finite) also counts
        mon2 = AlarmMonitor()
        mon2.emit([_row(0, loss=None)])
        assert mon2.stop_requested

    def test_gram_cond_blowup_warns_not_stops(self, caplog):
        mon = AlarmMonitor()
        with caplog.at_level("WARNING", logger="repro.obs.alarms"):
            mon.emit([_row(0, gram_cond_max=1e13)])
        assert not mon.stop_requested
        assert mon.events[0]["rule"] == "gram_cond_blowup"
        assert "gram_cond_blowup" in caplog.text

    def test_nan_never_satisfies_gt_lt(self):
        """Non-AA algos report nan gram_cond/aa_used — must not alarm."""
        mon = AlarmMonitor()
        mon.emit([_row(0, gram_cond_max=float("nan"),
                       aa_used_min=float("nan"))])
        assert mon.events == []

    def test_aa_column_collapse(self):
        mon = AlarmMonitor()
        mon.emit([_row(0, aa_used_min=0.0)])
        assert mon.events[0]["rule"] == "aa_columns_collapsed"

    def test_plateau_fires_after_window(self):
        rule = AlarmRule("plat", "rel_error", "no_improve", window=5,
                         min_improve=1e-3)
        mon = AlarmMonitor(rules=(rule,))
        mon.emit([_row(t, rel_error=1.0) for t in range(5)])
        assert mon.events == []  # needs window+1 rows
        mon.emit([_row(5, rel_error=1.0)])
        assert mon.events[0]["rule"] == "plat"
        # an improving run never plateaus
        mon2 = AlarmMonitor(rules=(rule,))
        mon2.emit([_row(t, rel_error=1.0 * 0.9 ** t) for t in range(20)])
        assert mon2.events == []

    def test_cooldown_suppresses_refires(self):
        rule = AlarmRule("hot", "loss", "gt", threshold=0.0)
        mon = AlarmMonitor(rules=(rule,), cooldown=10)
        mon.emit([_row(t, loss=1.0) for t in range(12)])
        assert [e["round"] for e in mon.events] == [0, 10]

    def test_rule_validation(self):
        with pytest.raises(ValueError, match="op"):
            AlarmRule("x", "loss", "between")
        with pytest.raises(ValueError, match="threshold"):
            AlarmRule("x", "loss", "gt")
        with pytest.raises(ValueError, match="action"):
            AlarmRule("x", "loss", "nonfinite", action="explode")

    def test_stop_rule_halts_engine_at_chunk_boundary(self, setup):
        """The host-side twin of the in-graph stop criteria: a stop alarm
        ends the run at the next chunk boundary, and the footer records it."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        mon = AlarmMonitor(rules=(
            AlarmRule("tripwire", "loss", "gt", threshold=-1e30,
                      action="stop"),))
        sink = MemorySink()
        _, trace = run_rounds(rf, state, 8, chunk=2, w_star=wstar,
                              sinks=[sink, mon])
        assert mon.stop_requested
        assert trace.num_rounds == 2  # stopped after the first chunk
        assert trace.stopped
        assert sink.footer["stopped"] is True
        assert sink.footer["rounds"] == 2
        assert any(e["rule"] == "tripwire" for e in sink.footer["alarms"])


class TestJsonlEndToEnd:
    def test_engine_jsonl_passes_validator(self, setup, tmp_path):
        """Acceptance: a chunked engine run streams one row per executed
        round to JSONL and the schema validator passes it."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3, cohort_size=4)
        path = str(tmp_path / "metrics.jsonl")
        h = run_federated(prob, "fedosaa_svrg", hp, 5, w_star=wstar,
                          channel="int8", chunk=2, sinks=[JsonlSink(path)])
        lines = open(path).read().splitlines()
        assert len(lines) == 7  # header + 5 rounds + footer
        header = json.loads(lines[0])
        assert header["kind"] == "header" and header["v"] == SCHEMA_VERSION
        assert header["algo"] == "fedosaa_svrg"
        assert header["runtime"] == "vmap"
        assert header["channel"] == "int8+ef"  # resolved channel name
        assert header["num_clients"] == 8
        assert header["cohort_size"] == 4
        assert isinstance(header["uplink_bytes"], dict)
        assert sum(header["uplink_bytes"].values()) > 0
        rows = [json.loads(l) for l in lines[1:-1]]
        np.testing.assert_array_equal([r["loss"] for r in rows], h.loss)
        np.testing.assert_array_equal(
            [r["gram_cond_max"] for r in rows], h.gram_cond_max)
        res = subprocess.run(
            [sys.executable, "scripts/check_metrics_jsonl.py", path],
            cwd=REPO_ROOT, capture_output=True, text=True)
        assert res.returncode == 0, res.stderr

    def test_validator_rejects_corrupt_file(self, setup, tmp_path):
        good = str(tmp_path / "good.jsonl")
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        run_federated(prob, "fedsvrg", hp, 3, w_star=wstar, chunk=2,
                      sinks=[JsonlSink(good)])
        lines = open(good).read().splitlines()
        for mutant, expect in [
            (lines[:-1], "footer"),                      # truncated footer
            (lines[:1] + lines[2:], "round"),            # gap in rounds
            (lines[1:], "header"),                       # missing header
            (lines[:-1] + ['{"bad json'], "invalid JSON"),
        ]:
            bad = str(tmp_path / "bad.jsonl")
            with open(bad, "w") as f:
                f.write("\n".join(mutant) + "\n")
            res = subprocess.run(
                [sys.executable, "scripts/check_metrics_jsonl.py", bad],
                cwd=REPO_ROOT, capture_output=True, text=True)
            assert res.returncode == 1, expect
            assert expect in res.stderr


class TestHistoryGramCond:
    @pytest.mark.parametrize("chunk", [None, 3])
    def test_gram_cond_in_history_and_summary(self, setup, chunk):
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        h = run_federated(prob, "fedosaa_svrg", hp, 4, w_star=wstar,
                          chunk=chunk)
        assert h.gram_cond_max.shape == (4,)
        assert np.isfinite(h.gram_cond_max).all()
        assert "gcond=" in h.summary()
        assert "wall=" in h.summary()

    def test_non_aa_algo_reports_nan_not_zero(self, setup):
        """fedsvrg has no AA step: gram_cond/aa_used columns are nan (absent)
        rather than a fake 0 — the alarm rules rely on this."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        sink = MemorySink()
        h = run_federated(prob, "fedsvrg", hp, 3, w_star=wstar, chunk=2,
                          sinks=[sink, AlarmMonitor()])
        assert np.isnan(h.gram_cond_max).all()
        assert all(r["aa_used_min"] is None or np.isnan(r["aa_used_min"])
                   for r in sink.rows)
        assert "gcond=nan" in h.summary()
