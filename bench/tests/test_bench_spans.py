"""The program's spans and kernel names in a trace (bench/spans.py) and the
kernel_ms.local_trajectory reader: innermost-span attribution of idle time,
the kernel lookup from compiled HLO, and two traces recorded on a TPU v5e
chip: the older fixture (a program without kernel names: the reader stays
silent) and one covtype-k100 job of the program with its spans and names,
run as run.py's job loop without a runner wrapper, with its scope and
kernel maps parsed from the same process's compiled runner."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness as H
from bench import spans as S
from bench import trace as T

DATA = Path(__file__).parent / "data"
SPANS_TRACE = DATA / "covtype-k100-spans.xplane.pb"
SPANS_MAPS = DATA / "covtype-k100-spans.maps.json"


def _reader():
    return H.load_module(H.BENCH / "metrics" / "kernel_ms.local_trajectory.py")


def test_idle_goes_to_the_innermost_span_and_sums_to_the_idle_total():
    busy = [[10, 20], [40, 50]]
    spans = {"bench.job_init": [(0, 4)], "fl.init_state": [(1, 4)],
             "fl.chunk": [(5, 55)], "fl.engine.dispatch": [(5, 8)],
             "fl.engine.wait": [(8, 25)], "fl.engine.fetch": [(25, 30)],
             "fl.engine.rows": [(30, 35)]}
    got = S.attribute_idle(busy, 0, 60, spans)
    want = {"bench.job_init": 1, "fl.init_state": 3, "host": 6,
            "fl.engine.dispatch": 3, "fl.engine.wait": 7,
            "fl.engine.fetch": 5, "fl.engine.rows": 5, "fl.chunk": 10}
    assert got == pytest.approx({k: 1e-9 * v for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(1e-9 * (60 - 20))


def test_a_window_without_program_spans_is_all_host():
    got = S.attribute_idle([[10, 20]], 0, 30, {n: [] for n in S.PROGRAM_SPANS})
    assert got == pytest.approx({"host": 20e-9})


def test_per_chunk_reading_divides_by_the_spans_in_the_window():
    summ = S.SpanSummary(1.0, 0.9, {"fl.engine.wait": 0.006,
                                    "fl.engine.rows": 0.001,
                                    "fl.engine.dispatch": 0.002},
                         {"fl.chunk": 3, "fl.init_state": 0}, {})
    assert summ.per(("fl.engine.wait",), "fl.chunk") == pytest.approx(2.0)
    assert summ.per(("fl.engine.rows", "fl.engine.dispatch"),
                    "fl.chunk") == pytest.approx(1.0)
    assert summ.per(("fl.init_state",), "fl.init_state") is None


def test_kernel_map_finds_kernels_by_their_pallas_name():
    hlo = "\n".join([
        '  %vmap_fl_local_trajectory_kernel_.8 = (f32[100,11,128]{2,1,0}) '
        'custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(chunk_fn)/while/body/fl.local_trajectory/'
        'vmap(fl_local_trajectory_kernel)/pallas_call" stack_frame_id=3}, '
        'backend_config={"custom_call_config":{}}',
        '  %custom-call.19 = f32[100,10,10]{2,1,0} custom-call(%g), '
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(chunk_fn)/vmap(fl.aa_step)/'
        'vmap(aa_gram_kernel)/pallas_call"}',
        '  %vmap_fl.aa_step_.16 = f32[100,128]{1,0} custom-call(%w), '
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(chunk_fn)/vmap(fl.aa_step)/pallas_call"}',
        '  %pad_bitcast_fusion.2 = f32[100,5888,128]{2,1,0} fusion(%x), '
        'kind=kLoop, metadata={op_name="jit(chunk_fn)/fl.local_trajectory/'
        'fl_local_trajectory_kernel/pad"}',
    ])
    assert S.kernel_map([hlo]) == {
        "vmap_fl_local_trajectory_kernel_.8": "fl_local_trajectory_kernel",
        "custom-call.19": "aa_gram_kernel"}


def _ctx(summary, slots: int):
    cell = SimpleNamespace(chips=1)
    jobs = [H.Job(rounds=slots, slots=slots, reached=True, answer=None)]
    return H.Context(cell, jobs, 1.0, 0.0, {}, summary)


def test_kernel_reader_reads_the_named_kernel_without_the_padding_copy():
    summary = SimpleNamespace(top_ops=[
        ("fl.local_trajectory:vmap_fl_local_trajectory_kernel_.8", 0.6),
        ("fl.local_trajectory:pad_bitcast_fusion.2", 0.05),
        ("fl.anchor_grad:multiply_reduce_fusion.17", 0.01)])
    assert _reader().read(_ctx(summary, 60)) == pytest.approx(10.0)
    assert _reader().read(_ctx(None, 60)) is None


def test_kernel_reader_is_silent_on_a_program_without_kernel_names():
    scope_of = json.loads((DATA / "covtype-k100.scopes.json").read_text())
    summary = T.reduce(str(DATA / "covtype-k100.xplane.pb"), [0], scope_of)
    assert _reader().read(_ctx(summary, 15)) is None


@pytest.fixture(scope="module")
def recorded():
    maps = json.loads(SPANS_MAPS.read_text())
    return maps, S.reduce(str(SPANS_TRACE), 0, maps["scope_of"],
                          maps["kernel_of"])


def test_recorded_idle_decomposes_into_spans(recorded):
    _, summ = recorded
    idle = summ.window_s - summ.busy_s
    assert idle > 0
    assert sum(summ.idle_s.values()) == pytest.approx(idle, rel=1e-6)
    assert summ.idle_s.get("host", 0.0) <= 0.1 * idle
    for name in ("fl.init_state", "fl.engine.wait", "fl.engine.fetch",
                 "fl.engine.rows"):
        assert summ.idle_s[name] > 0, name
    # one 13-round job in chunks of 5
    assert summ.counts["fl.chunk"] == 3 and summ.counts["fl.init_state"] == 1
    assert all(summ.counts[f"fl.engine.{k}"] == 3
               for k in ("dispatch", "wait", "fetch", "rows"))


def test_recorded_runner_ops_over_one_percent_have_a_phase(recorded):
    """Every leaf op of the chunk runner's module that takes over 1% of the
    busy time carries an fl.* scope (the anchor gradient's and the metrics'
    fusions included)."""
    from jax.profiler import ProfileData

    maps, summ = recorded

    class Tagged(dict):
        def get(self, name, default=None):
            return super().get(name, "unscoped")

    ops, _ = T._device_ops(ProfileData.from_file(str(SPANS_TRACE)), 0,
                           Tagged(maps["scope_of"]))
    per_op: dict = {}
    for s, e, name, scope in T._leaves(ops):
        if scope is not None:                 # inside the runner's module
            per_op[name, scope] = per_op.get((name, scope), 0) + 1e-9 * (e - s)
    big = {k: v for k, v in per_op.items() if v > 0.01 * summ.busy_s}
    assert len(big) >= 4
    assert all(scope != "unscoped" for _, scope in big), big
    assert ("multiply_reduce_fusion.17", "fl.anchor_grad") in big


def test_recorded_kernel_is_found_by_name(recorded):
    maps, summ = recorded
    assert set(maps["kernel_of"].values()) == set(S.KERNELS)
    trajectory = summ.kernel_s["fl_local_trajectory_kernel"]
    assert 0 < trajectory < summ.busy_s
    # the reader reads the same instructions from bench/trace.py's summary
    summary = T.reduce(str(SPANS_TRACE), [0], maps["scope_of"])
    ms = _reader().read(_ctx(summary, 15))
    assert ms == pytest.approx(1e3 * trajectory / 15, rel=1e-6)
    assert ms < 1e3 * summary.phase_s["fl.local_trajectory"][0] / 15
