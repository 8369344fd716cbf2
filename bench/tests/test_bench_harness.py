"""The harness's job loop on the CPU at a tiny size, called directly (the
command refuses a CPU)."""
import jax
import numpy as np
import pytest

from bench import harness as H
from bench.tests import tiny


@pytest.fixture(scope="module")
def built():
    cell = tiny.cell()
    inputs = H.make_inputs(cell)
    prog = H.build_program(cell, inputs, jax.devices()[:1])
    keys = H.job_keys(2**31 + 977)
    H.warm_up(prog, cell.traffic, next(keys))
    return cell, inputs, prog, keys


def test_jobs_reach_the_target_against_the_reference(built):
    cell, inputs, prog, keys = built
    counter = H.CompileCounter()
    jobs, window_s = H.run_window(prog, cell.traffic, keys, 0.0, max_jobs=3)
    assert counter.compiles == 0
    assert len(jobs) == 3 and all(j.reached for j in jobs)
    assert all(j.slots % cell.traffic["chunk"] == 0 for j in jobs)
    assert all(j.rounds <= j.slots < j.rounds + cell.traffic["chunk"]
               for j in jobs)
    checks = H.check_jobs(cell, jobs, inputs)
    assert H.is_correct(checks)
    assert checks["rel_error_max"]["value"] < 1.01e-4


def test_end_to_end_metrics_read_from_the_window(built):
    cell, inputs, prog, keys = built
    jobs, window_s = H.run_window(prog, cell.traffic, keys, 0.0, max_jobs=2)
    spec = H.load_json(H.ROOT / "BENCHMARK.json")
    ctx = H.Context(cell, jobs, window_s, 1.5, H.load_peaks("TPU v5 lite"))
    got = H.read_metrics(spec["end_to_end"], ctx)
    assert got["setup_s"]["value"] == 1.5
    rounds = sum(j.rounds for j in jobs)
    assert got["round_ms"]["value"] == pytest.approx(1e3 * window_s / rounds)
    assert got["time_to_target_s"]["value"] == pytest.approx(window_s / 2)
    # per-layer metrics that read a trace stay silent without one
    silent = H.read_metrics(
        [m for m in spec["per_layer"] if m["source"] == "device_trace"], ctx)
    assert silent == {}


def test_job_keys_repeat_for_a_seed_over_32_bits():
    a, b = H.job_keys(2**31 + 5), H.job_keys(2**31 + 5)
    ka = [next(a) for _ in range(4)]
    assert ka == [next(b) for _ in range(4)]
    assert all(0 <= k < 2**31 for k in ka)
    assert ka != [next(H.job_keys(2**31 + 6)) for _ in range(4)]


def _counts_read(per_layer: list) -> set:
    """The phases of bench/counts that the cell's per-layer metrics read:
    ``roofline.<phase>`` reads ``<phase>``, ``mfu`` reads ``round``."""
    need = set()
    for m in per_layer:
        if m["name"].startswith("roofline."):
            need.add(m["name"].removeprefix("roofline."))
        elif m["name"] == "mfu":
            need.add("round")
    return need


def test_every_cell_loads_with_its_files():
    spec = H.load_json(H.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = H.load_cell(w["name"])
        assert cell.chips == w["chips"]
        for m in cell.end_to_end + cell.per_layer:
            assert (H.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert (H.BENCH / "runtimes"
                / f"{cell.traffic['runtime']}.py").is_file()
        assert (H.MODELS / f"{cell.config['model']}.py").is_file()
        # every count the cell's rooflines and mfu read exists
        ctx = H.Context(cell, [], 0.0, 0.0, H.load_peaks("TPU v5 lite"))
        assert _counts_read(cell.per_layer) <= set(ctx.work())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        H.load_peaks("TPU v99")


def test_a_loaded_module_may_define_a_dataclass(tmp_path):
    path = tmp_path / "with_dataclass.py"
    path.write_text("from __future__ import annotations\n"
                    "import dataclasses\n\n"
                    "@dataclasses.dataclass\n"
                    "class Blocks:\n"
                    "    rows: int\n")
    assert H.load_module(path).Blocks(3).rows == 3
