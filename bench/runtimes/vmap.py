"""The one-device runtime: ``make_chunk_runner`` over ``make_round_fn``, the
objects ``run_federated(chunk=...)`` builds for a run on one chip."""
from __future__ import annotations


def build(cell, inputs, devices):
    import jax
    import jax.numpy as jnp

    from bench.harness import Program
    from repro.core import (AlgoHParams, init_state, make_chunk_runner,
                            make_round_fn, stack_client_arrays)
    from repro.models.logreg import make_logreg_problem

    tr = cell.traffic
    problem = make_logreg_problem(
        stack_client_arrays(list(inputs.x), list(inputs.y)),
        gamma=cell.config["gamma"])
    hp = AlgoHParams(**tr["hparams"])
    round_fn = make_round_fn(tr["algo"], problem, hp, tr["channel"])
    runner = make_chunk_runner(
        round_fn, tr["chunk"], w_star=jnp.asarray(inputs.w_star, jnp.float32),
        stop_rel_error=tr["target_rel_error"])

    def init(key: int):
        return init_state(problem, jax.random.PRNGKey(key), hp, tr["channel"],
                          tr["algo"])

    return Program(init, runner, round_fn)
