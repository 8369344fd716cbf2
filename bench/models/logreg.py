"""ℓ2-regularised logistic regression (paper Eq. 11) as a model of the
harness: the configuration's rows split IID over the traffic's clients, the
float64 optimum w* as the reference, and every job's final model checked
against it.

The harness finds this file by the configuration's ``"model": "logreg"``;
what each function must do is in ``bench/harness.py::model_module``.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from bench.data import iid_split, make_dataset
from bench.harness import Inputs
from bench.reference import newton_solve, rel_error


class Blocks(NamedTuple):
    x: np.ndarray         # [K, n_k, d] float32 client blocks
    y: np.ndarray         # [K, n_k] float32 labels in {-1, +1}


def make_inputs(config: dict, traffic: dict) -> Inputs:
    """The configuration's dataset, split IID over the traffic's clients, and
    the float64 optimum w* [d] of the objective over the rows the clients
    hold."""
    t0 = time.perf_counter()
    X, y = make_dataset(config["n"], config["d"], config["pos_frac"],
                        config["scale"], config["data_seed"])
    xs, ys = iid_split(X, y, traffic["num_clients"], traffic["split_seed"])
    t1 = time.perf_counter()
    w_star = newton_solve(xs.reshape(-1, config["d"]), ys.reshape(-1),
                          config["gamma"])
    return Inputs(Blocks(xs, ys), w_star, t1 - t0, time.perf_counter() - t1)


def problem(config: dict, inputs: Inputs):
    """The program's FLProblem over the client blocks."""
    from repro.core import stack_client_arrays
    from repro.models.logreg import make_logreg_problem

    return make_logreg_problem(
        stack_client_arrays(list(inputs.data.x), list(inputs.data.y)),
        gamma=config["gamma"])


def stop(config: dict, traffic: dict, inputs: Inputs) -> dict:
    """The runner's in-graph stop: rel-error at the traffic's target
    against w*."""
    import jax.numpy as jnp

    return {"w_star": jnp.asarray(inputs.reference, jnp.float32),
            "stop_rel_error": traffic["target_rel_error"]}


def answer(state) -> np.ndarray:
    """The job's model, float32 [d]."""
    import jax

    return np.asarray(jax.device_get(state.params))


def reached(trace) -> bool:
    """The target stop fired."""
    return trace.stopped


def check(jobs: list, inputs: Inputs, traffic: dict) -> dict:
    """Every job's model against the float64 reference, each number beside
    its limit: the worst relative error, and the jobs whose answer never
    came (the budget spent short of the target)."""
    worst = max(rel_error(j.answer, inputs.reference) for j in jobs)
    return {"rel_error_max": {"value": worst,
                              "limit": traffic["rel_error_limit"]},
            "jobs_short_of_target": {"value": sum(not j.reached for j in jobs),
                                     "limit": 0}}
