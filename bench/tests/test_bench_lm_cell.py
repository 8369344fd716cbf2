"""The LM cell's path through ``run.run_cell`` at a tiny size on the CPU:
the Granite model module, the ``lm`` runtime (the base as the runner's
operand) and the check after the window. A sound run reads ``correct``; a
run with a fault planted in the program reads not correct: a round that
returns its state (no job makes progress), the program's logits altered by
10% (its logits_scaling; the reference keeps the published one), half of
the clients left out of the program's rounds, and rounds without the
Anderson step (plain SVRG uploads, which still lower the loss)."""
import dataclasses

import jax
import pytest

from bench import harness as H
from bench import run
from bench.tests.test_bench_granite import tiny_config

CHECKS = ["logit_err_max", "grad_rel_err", "base_loss_rel_err",
          "second_loss_rel_err", "adapter_rel_err", "jobs_without_progress"]


def _cell() -> H.Cell:
    traffic = dict(H.load_json(H.BENCH / "traffic" / "lora-k4-s4096.json"),
                   seq_len=128)
    return H.Cell("tiny-lm", 1, tiny_config(), traffic, [], [])


def _frozen_round(prog, cell):
    from repro.core import make_chunk_runner

    def round_fn(state, frozen):
        _, metrics = prog.round_fn(state, frozen)
        return state, metrics

    compiled = make_chunk_runner(round_fn, cell.traffic["chunk"])
    return dataclasses.replace(
        prog, round_fn=round_fn, compiled=compiled,
        runner=lambda state, n: compiled(state, n, prog.frozen))


def _run(monkeypatch, fault: str) -> dict:
    cell = _cell()
    if fault == "state_unchanged":
        build = H.build_program
        monkeypatch.setattr(H, "build_program", lambda c, i, d: _frozen_round(
            build(c, i, d), c))
    elif fault == "half_clients":
        build = H.build_program

        def half(c, i, d):
            data = dataclasses.replace(i.data, tokens=i.data.tokens[:2])
            return build(c, dataclasses.replace(i, data=data), d)

        monkeypatch.setattr(H, "build_program", half)
    elif fault == "no_aa_step":
        build = H.build_program
        monkeypatch.setattr(H, "build_program", lambda c, i, d: build(
            dataclasses.replace(c, traffic=dict(c.traffic, algo="fedsvrg")),
            i, d))
    elif fault == "logits_altered":
        from repro.models import decoder

        build = decoder.build_model
        monkeypatch.setattr(decoder, "build_model", lambda cfg, **kw: build(
            dataclasses.replace(cfg, logits_scaling=1.1 * cfg.logits_scaling),
            **kw))
    return run.run_cell(cell, 2**31 + 53, 0.0, False, jax.devices()[:1], 0.0,
                        H.load_peaks("TPU v5 lite"))


@pytest.mark.parametrize("fault", ["none", "state_unchanged",
                                   "logits_altered", "half_clients",
                                   "no_aa_step"])
def test_lm_cell_is_correct_unless_a_fault_is_planted(monkeypatch, fault):
    result = _run(monkeypatch, fault)
    assert list(result)[-1] == "checks"
    assert list(result["checks"]) == CHECKS
    assert result["correct"] is (fault == "none"), result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    if fault == "state_unchanged":
        assert result["checks"]["jobs_without_progress"]["value"] >= 1
    c = result["checks"]
    if fault == "logits_altered":
        assert c["logit_err_max"]["value"] > c["logit_err_max"]["limit"]
    if fault in ("half_clients", "no_aa_step"):
        # the rounds' own reference sees both; the base loss sees only the
        # left-out clients
        assert c["adapter_rel_err"]["value"] > c["adapter_rel_err"]["limit"]
