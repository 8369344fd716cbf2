"""The trace reducer: its interval arithmetic, and a trace recorded on a TPU
v5e chip (one covtype-k100 job with the benchmark's host spans), with the
instruction-to-phase map parsed from the same process's compiled runner."""
import json
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).parent / "data"
FIXTURE = DATA / "covtype-k100.xplane.pb"
SCOPES_OF = DATA / "covtype-k100.scopes.json"
#: a later recording (one covtype-k100 job) whose runner carries the scopes
#: fl.anchor_grad, fl.round_metrics and fl.stop_check as well
SPANS_FIXTURE = DATA / "covtype-k100-spans.xplane.pb"
SPANS_MAPS = DATA / "covtype-k100-spans.maps.json"


def test_union_merges_overlaps_and_clip_keeps_the_window():
    u = T._union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [[0, 3], [5, 8]]
    assert T._length(u) == 6
    assert T._clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_leaves_drop_an_op_that_contains_others():
    ops = [(0, 10, "while", None), (1, 3, "a", "fl.x"), (4, 6, "b", "fl.x"),
           (11, 12, "c", None)]
    assert [o[2] for o in T._leaves(ops)] == ["a", "b", "c"]


def test_gaps_are_labelled_by_the_host_span_that_covers_them_most():
    busy = [[10, 20], [30, 40]]
    spans = {"bench.sync": [(18, 29)], "bench.job_init": [(0, 9)]}
    gaps = dict(T._label_gaps(busy, 0, 50, spans))
    assert gaps["bench.sync"] == pytest.approx(10e-9)
    assert gaps["bench.job_init"] == pytest.approx(10e-9)
    assert gaps["host"] == pytest.approx(10e-9)


def test_scope_map_takes_the_innermost_phase_of_each_instruction():
    hlo = "\n".join([
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(chunk_fn)/while/body/fl.uplink/fl.psum/add" '
        'stack_frame_id=3}',
        '  ROOT %custom-call.19 = f32[8] custom-call(%a), '
        'metadata={op_name="jit(chunk_fn)/vmap(fl.aa_step)/pallas_call"}',
        '  %copy.2 = f32[4]{0} copy(%x), metadata={op_name="jit(f)/copy"}',
        '  %add.1 = f32[4]{0} add(%x, %y)',
    ])
    assert T.scope_map([hlo]) == {"fusion.3": "fl.psum",
                                  "custom-call.19": "fl.aa_step"}


@pytest.fixture(scope="module")
def summary():
    scope_of = json.loads(SCOPES_OF.read_text())
    return T.reduce(str(FIXTURE), [0], scope_of)


def test_recorded_trace_finds_the_round_phases(summary):
    for scope in ("fl.local_trajectory", "fl.aa_step", "fl.uplink"):
        assert scope in summary.phase_s and summary.phase_s[scope][0] > 0
    assert "fl.psum" not in summary.phase_s      # one chip, vmap runtime
    # the phases are the scopes the runner's HLO carries, and no others
    scopes = set(json.loads(SCOPES_OF.read_text()).values())
    assert set(summary.phase_s) == scopes


def test_every_scope_in_the_runner_is_a_phase():
    scope_of = json.loads(SPANS_MAPS.read_text())["scope_of"]
    summary = T.reduce(str(SPANS_FIXTURE), [0], scope_of)
    for scope in ("fl.local_trajectory", "fl.aa_step", "fl.anchor_grad",
                  "fl.round_metrics", "fl.stop_check"):
        assert summary.phase_s[scope][0] > 0
    # each op counts towards its innermost scope alone
    assert sum(v[0] for v in summary.phase_s.values()) <= summary.busy_s
    # a scope of any name is read: rename one and its time follows it
    renamed = {k: "fl.new_phase" if v == "fl.anchor_grad" else v
               for k, v in scope_of.items()}
    again = T.reduce(str(SPANS_FIXTURE), [0], renamed)
    assert again.phase_s["fl.new_phase"] == summary.phase_s["fl.anchor_grad"]
    assert "fl.anchor_grad" not in again.phase_s


def test_recorded_trace_times_fit_in_the_window(summary):
    assert 0 < summary.busy_s <= summary.window_s
    assert sum(v[0] for v in summary.phase_s.values()) <= summary.busy_s
    idle = sum(s for _, s in summary.idle_gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    ops = summary.breakdown()["device_ops"]
    assert 0 < len(ops) <= 10 and all(s > 0 for _, s in ops)
