"""The trace reducer: its interval arithmetic, and a trace recorded on a TPU
v5e chip (one covtype-k100 job with the benchmark's host spans), with the
instruction-to-phase map parsed from the same process's compiled runner."""
import json
from pathlib import Path

import pytest

from bench import trace as T

FIXTURE = Path(__file__).parent / "data" / "covtype-k100.xplane.pb"
SCOPES_OF = Path(__file__).parent / "data" / "covtype-k100.scopes.json"
SCOPES = ("fl.local_trajectory", "fl.aa_step", "fl.uplink", "fl.psum")


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
    return T.reduce(str(FIXTURE), [0], SCOPES, scope_of)


def test_recorded_trace_finds_the_round_phases(summary):
    for scope in ("fl.local_trajectory", "fl.aa_step", "fl.uplink"):
        assert scope in summary.phase_s and summary.phase_s[scope][0] > 0
    assert "fl.psum" not in summary.phase_s      # one chip, vmap runtime


def test_recorded_trace_times_fit_in_the_window(summary):
    assert 0 < summary.busy_s <= summary.window_s
    assert sum(v[0] for v in summary.phase_s.values()) <= summary.busy_s
    idle = sum(s for _, s in summary.idle_gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    ops = summary.breakdown()["device_ops"]
    assert 0 < len(ops) <= 10 and all(s > 0 for _, s in ops)
