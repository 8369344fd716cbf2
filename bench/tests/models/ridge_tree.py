"""A test-only model of the harness: ridge regression whose parameters are a
pytree (``{"w": [d], "b": []}``), with no target to stop at. Every job runs
the traffic's ``round_budget``; its answer, the parameters flattened as
``[w, b]``, is checked against the float64 solution of the normal equations.

It shows that a model lands as one new file: the tests point the harness's
module directory here and drive it through ``run.run_cell`` and the ``vmap``
runtime, with no other file of the benchmark edited.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.harness import Inputs


@dataclasses.dataclass(frozen=True)
class Blocks:
    x: np.ndarray         # [K, n_k, d] float32
    y: np.ndarray         # [K, n_k] float32


def make_inputs(config: dict, traffic: dict) -> Inputs:
    """Gaussian rows with a linear target plus noise, split over the
    clients; the reference θ* = [w*, b*] of mean ½(x·w + b − y)² +
    γ/2 (‖w‖² + b²) over all rows, in float64."""
    t0 = time.perf_counter()
    K, d = traffic["num_clients"], config["d"]
    n = config["n"] // K * K
    rng = np.random.default_rng(config["data_seed"])
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) + config["bias"]
         + 0.1 * rng.standard_normal(n)).astype(np.float32)
    t1 = time.perf_counter()
    A = np.hstack([X, np.ones((n, 1), np.float32)]).astype(np.float64)
    theta = np.linalg.solve(A.T @ A / n + config["gamma"] * np.eye(d + 1),
                            A.T @ y.astype(np.float64) / n)
    return Inputs(Blocks(X.reshape(K, -1, d), y.reshape(K, -1)), theta,
                  t1 - t0, time.perf_counter() - t1)


def problem(config: dict, inputs: Inputs):
    import jax.numpy as jnp

    from repro.core import FLProblem, stack_client_arrays

    gamma, d = config["gamma"], config["d"]

    def loss(p, batch):
        z = batch.x @ p["w"] + p["b"]
        n = jnp.maximum(jnp.sum(batch.mask), 1.0)
        return (jnp.sum(0.5 * (z - batch.y) ** 2 * batch.mask) / n
                + 0.5 * gamma * (jnp.dot(p["w"], p["w"]) + p["b"] ** 2))

    def init(_rng):
        return {"w": jnp.zeros((d,), jnp.float32),
                "b": jnp.zeros((), jnp.float32)}

    return FLProblem(loss=loss, init=init, clients=stack_client_arrays(
        list(inputs.data.x), list(inputs.data.y)))


def stop(config: dict, traffic: dict, inputs: Inputs) -> dict:
    """No target: every job runs the round budget."""
    return {}


def answer(state) -> np.ndarray:
    import jax

    p = jax.device_get(state.params)
    return np.concatenate([np.ravel(p["w"]), np.ravel(p["b"])])


def reached(trace) -> bool:
    """With no target the engine stops a job only on a non-finite loss."""
    return not trace.stopped


def check(jobs: list, inputs: Inputs, traffic: dict) -> dict:
    theta = inputs.reference
    gap = max(float(np.linalg.norm(j.answer - theta) / np.linalg.norm(theta))
              for j in jobs)
    return {"param_gap_max": {"value": gap,
                              "limit": traffic["param_gap_limit"]},
            "jobs_stopped_early": {"value": sum(not j.reached for j in jobs),
                                   "limit": 0}}
