"""A run with the timed path broken underneath must come out not correct.

Each test skips only the look for a chip (``run.run_cell`` on the CPU at a
tiny size) and plants one fault in the program the window drives: a round
that returns its state unchanged; half of the clients left out, the mean
taken over the rest; and the model answer altered where the round produces
it. The cells run on one chip, so there is no exchange between chips to
leave out.
"""
import dataclasses

import jax
import pytest

from bench import harness as H
from bench import run
from bench.tests import tiny


def _rewrapped(prog, cell, inputs, round_fn):
    """``prog`` with ``round_fn`` in place of its round, under the runner
    and stop the model gives it."""
    from repro.core import make_chunk_runner

    runner = make_chunk_runner(
        round_fn, cell.traffic["chunk"],
        **prog.model.stop(cell.config, cell.traffic, inputs))
    return dataclasses.replace(prog, runner=runner, round_fn=round_fn)


def frozen(prog, cell, inputs):
    def round_fn(state):
        _, metrics = prog.round_fn(state)
        return state, metrics

    return _rewrapped(prog, cell, inputs, round_fn)


def _altered(prog, cell, inputs):
    def round_fn(state):
        new, metrics = prog.round_fn(state)
        return new._replace(params=new.params.at[0].add(1e-2)), metrics

    return _rewrapped(prog, cell, inputs, round_fn)


def _half_clients(inputs):
    K = inputs.data.x.shape[0] // 2
    data = inputs.data._replace(x=inputs.data.x[:K], y=inputs.data.y[:K])
    return dataclasses.replace(inputs, data=data, data_s=0, reference_s=0)


FAULTS = {"state_unchanged": frozen, "answer_altered": _altered}


def run_broken(monkeypatch, cell, fault: str) -> dict:
    """``run.run_cell`` of ``cell`` with ``fault`` planted in the program
    the runtime builds."""
    build = H.build_program

    def broken(cell, inputs, devices):
        if fault == "half_clients":
            return build(cell, _half_clients(inputs), devices)
        if fault == "none":
            return build(cell, inputs, devices)
        return FAULTS[fault](build(cell, inputs, devices), cell, inputs)

    monkeypatch.setattr(H, "build_program", broken)
    return run.run_cell(cell, 2**31 + 41, 0.0, False, jax.devices()[:1], 0.0,
                        H.load_peaks("TPU v5 lite"))


@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_clients",
                                   "answer_altered"])
def test_fault_makes_the_run_not_correct(monkeypatch, fault):
    result = run_broken(monkeypatch, tiny.cell(round_budget=30), fault)
    assert list(result)[-1] == "checks"
    assert result["correct"] is (fault == "none"), result["checks"]
