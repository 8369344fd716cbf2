"""A configuration's model is a module of the harness, found by name: a new
model lands as one new file. The test-only model ``bench/tests/models/
ridge_tree.py`` (pytree parameters, no w*, a fixed round count) runs through
``run.run_cell`` and the ``vmap`` runtime with the harness's module
directory pointed at it, and nothing else changed."""
import jax
import numpy as np
import pytest

from bench import harness as H
from bench import run
from bench.tests.test_bench_faults import run_broken

TEST_MODELS = H.BENCH / "tests" / "models"
CONFIG = {"name": "ridge-tree", "model": "ridge_tree", "n": 400, "d": 8,
          "gamma": 1e-2, "bias": 0.5, "data_seed": 5}
# param_gap_limit: sound runs read about 1e-7 (float32 against float64)
# after 8 rounds; a round that returns its state reads 1 (the zero init)
TRAFFIC = {"num_clients": 4, "algo": "fedosaa_svrg",
           "hparams": {"eta": 0.5, "local_epochs": 5}, "channel": "identity",
           "runtime": "vmap", "chunk": 4, "round_budget": 8,
           "param_gap_limit": 1e-5, "trace_jobs": 2}


def _cell() -> H.Cell:
    return H.Cell("ridge-tree", 1, dict(CONFIG), dict(TRAFFIC), [], [])


@pytest.fixture
def test_models(monkeypatch):
    monkeypatch.setattr(H, "MODELS", TEST_MODELS)


def test_a_new_model_runs_through_run_cell(test_models):
    result = run.run_cell(_cell(), 2**31 + 43, 0.0, False, jax.devices()[:1],
                          0.0, H.load_peaks("TPU v5 lite"))
    assert list(result)[-1] == "checks"
    assert list(result["checks"]) == ["param_gap_max", "jobs_stopped_early"]
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["param_gap_max"]["value"] < 1e-6
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_a_new_model_keeps_a_small_answer_of_its_pytree(test_models):
    cell = _cell()
    inputs = H.make_inputs(cell)
    prog = H.build_program(cell, inputs, jax.devices()[:1])
    assert set(prog.init(0).params) == {"b", "w"}
    jobs, _ = H.run_window(prog, cell.traffic, H.job_keys(9), 0.0,
                           max_jobs=2)
    for j in jobs:
        assert j.rounds == j.slots == TRAFFIC["round_budget"]
        assert j.reached
        assert isinstance(j.answer, np.ndarray)
        assert j.answer.shape == (CONFIG["d"] + 1,)


def test_a_new_model_with_a_frozen_round_is_not_correct(monkeypatch,
                                                        test_models):
    result = run_broken(monkeypatch, _cell(), "state_unchanged")
    assert list(result)[-1] == "checks"
    assert result["correct"] is False
    assert result["checks"]["param_gap_max"]["value"] == pytest.approx(1.0)


def test_a_model_with_no_module_exits_naming_its_file():
    cell = H.Cell("none", 1, {"model": "no_such_model"}, {}, [], [])
    with pytest.raises(SystemExit, match=r"bench/models/no_such_model\.py"):
        H.make_inputs(cell)
    with pytest.raises(SystemExit, match=r"no_such_model\.py"):
        H.check_jobs(cell, [], None)
