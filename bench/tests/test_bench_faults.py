"""A run with the timed path broken underneath must come out not correct.

Each test skips only the look for a chip (``run.run_cell`` on the CPU at a
tiny size) and plants one fault in the program the window drives: a round
that returns its state unchanged; half of the clients left out, the mean
taken over the rest; and the model answer altered where the round produces
it. The cells run on one chip, so there is no exchange between chips to
leave out.
"""
import jax
import jax.numpy as jnp
import pytest

from bench import harness as H
from bench import run
from bench.tests import tiny


def _frozen(prog, cell, inputs):
    from repro.core import make_chunk_runner

    def round_fn(state):
        _, metrics = prog.round_fn(state)
        return state, metrics

    runner = make_chunk_runner(
        round_fn, cell.traffic["chunk"],
        w_star=jnp.asarray(inputs.w_star, jnp.float32),
        stop_rel_error=cell.traffic["target_rel_error"])
    return H.Program(prog.init, runner, round_fn)


def _altered(prog, cell, inputs):
    from repro.core import make_chunk_runner

    def round_fn(state):
        new, metrics = prog.round_fn(state)
        return new._replace(params=new.params.at[0].add(1e-2)), metrics

    runner = make_chunk_runner(
        round_fn, cell.traffic["chunk"],
        w_star=jnp.asarray(inputs.w_star, jnp.float32),
        stop_rel_error=cell.traffic["target_rel_error"])
    return H.Program(prog.init, runner, round_fn)


FAULTS = {"state_unchanged": _frozen, "answer_altered": _altered}


def _run(monkeypatch, fault: str) -> dict:
    build = H.build_program

    def broken(cell, inputs, devices):
        if fault == "half_clients":
            K = inputs.x.shape[0] // 2
            half = H.Inputs(inputs.x[:K], inputs.y[:K], inputs.w_star, 0, 0)
            return build(cell, half, devices)
        if fault == "none":
            return build(cell, inputs, devices)
        return FAULTS[fault](build(cell, inputs, devices), cell, inputs)

    monkeypatch.setattr(H, "build_program", broken)
    return run.run_cell(tiny.cell(round_budget=30), 2**31 + 41, 0.0, False,
                        jax.devices()[:1], 0.0, H.load_peaks("TPU v5 lite"))


@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_clients",
                                   "answer_altered"])
def test_fault_makes_the_run_not_correct(monkeypatch, fault):
    result = _run(monkeypatch, fault)
    assert list(result)[-1] == "checks"
    assert result["correct"] is (fault == "none"), result["checks"]
