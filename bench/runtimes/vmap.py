"""The one-device runtime: ``make_chunk_runner`` over ``make_round_fn``, the
objects ``run_federated(chunk=...)`` builds for a run on one chip, over the
problem and the stop of the cell's model (``bench/models/<model>.py``)."""
from __future__ import annotations


def build(cell, inputs, devices):
    import jax

    from bench.harness import Program, model_module
    from repro.core import (AlgoHParams, init_state, make_chunk_runner,
                            make_round_fn)

    tr = cell.traffic
    model = model_module(cell.config)
    problem = model.problem(cell.config, inputs)
    hp = AlgoHParams(**tr["hparams"])
    round_fn = make_round_fn(tr["algo"], problem, hp, tr["channel"])
    runner = make_chunk_runner(round_fn, tr["chunk"],
                               **model.stop(cell.config, tr, inputs))

    def init(key: int):
        return init_state(problem, jax.random.PRNGKey(key), hp, tr["channel"],
                          tr["algo"])

    return Program(init, runner, model, round_fn)
