"""The control: a plain FedOSAA-SVRG in jax.numpy, put in the program's
place, computed in a chosen dtype.

Paper Algorithm 1 with a lossless wire: each client evaluates its full
gradient g_k at w^t, the server averages them into g, each client runs L
SVRG-corrected gradient steps w_{l+1} = w_l − η(∇f_k(w_l) − g_k + g) from
w^t, builds S = [Δw], Y = [Δr] from its trajectory, and takes the
multisecant step w_k = w^t − ηg − (S − ηY)Γ with Γ the least-squares
solution of YΓ ≈ g (normal equations with a 1e-10 relative Tikhonov term,
solved through their eigendecomposition, paper App. A); the server averages
the w_k. Data, model, gradients, trajectories and the AA step are computed
in ``dtype``; only the [L, L] Gram system is solved in float32 (the
eigendecomposition has no bfloat16 kernel). Nothing here imports the program
under test.

``make_runner`` gives the engine's chunk-runner interface (state, n_live) ->
(state, done, metrics, rel, live), so the harness's job loop drives it as it
drives the program. With ``dtype=jnp.float32`` (on a TPU: under
``jax.default_matmul_precision("highest")``) it is the reference algorithm
itself, which reaches the target; with ``jnp.bfloat16``, the precision below
the float32 the configuration states, it is the control, which must not: a
bfloat16 model holds each weight to 2^-9 of itself, far above the target.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: the engine's per-round metric fields (core/engine.METRIC_FIELDS); the
#: control reports none of them (nan), the job loop reads only rel and live
METRIC_FIELDS = (
    "loss", "grad_norm", "theta_mean", "gram_cond_max", "gram_cond_mean",
    "aa_used_min", "aa_clipped_max", "cohort_ess", "comm_bytes",
    "arrivals", "staleness_mean", "staleness_max",
)

#: relative Tikhonov term of the [L, L] Gram solve, as the paper's App. A
TIKHONOV = 1e-10


class State(NamedTuple):
    params: jax.Array


class Metrics(NamedTuple):
    loss: jax.Array
    grad_norm: jax.Array
    theta_mean: jax.Array
    gram_cond_max: jax.Array
    gram_cond_mean: jax.Array
    aa_used_min: jax.Array
    aa_clipped_max: jax.Array
    cohort_ess: jax.Array
    comm_bytes: jax.Array
    arrivals: jax.Array
    staleness_mean: jax.Array
    staleness_max: jax.Array


def make_round(x, y, gamma: float, eta: float, local_epochs: int, dtype):
    """One FedOSAA-SVRG round over client blocks x [K, n, d], y [K, n], in
    ``dtype`` (w -> w, both ``dtype``)."""
    x = jnp.asarray(x, dtype)
    y = jnp.asarray(y, dtype)
    n = x.shape[1]

    def grad(w, xk, yk):
        z = yk * (xk @ w)
        c = -yk * jax.nn.sigmoid(-z)
        return c @ xk / n + gamma * w

    def client(w_t, g, xk, yk):
        g_k = grad(w_t, xk, yk)

        def step(w, _):
            r = grad(w, xk, yk) - g_k + g
            return w - eta * r, (w, r)

        _, (ws, rs) = jax.lax.scan(step, w_t, None, length=local_epochs + 1)
        S = ws[1:] - ws[:-1]
        Y = rs[1:] - rs[:-1]
        gram = (Y @ Y.T).astype(jnp.float32)
        m = gram.shape[0]
        evals, evecs = jnp.linalg.eigh(
            gram + TIKHONOV * jnp.trace(gram) / m * jnp.eye(m))
        keep = evals > 1e-30 * jnp.max(evals)
        inv = jnp.where(keep, 1.0 / jnp.where(keep, evals, 1.0), 0.0)
        coef = (evecs @ (inv * (evecs.T @ (Y @ g).astype(jnp.float32))))
        coef = coef.astype(dtype)
        return w_t - eta * g - (coef @ S - eta * (coef @ Y))

    def round_fn(w):
        g = jnp.mean(jax.vmap(grad, (None, 0, 0))(w, x, y), axis=0)
        return jnp.mean(jax.vmap(client, (None, None, 0, 0))(w, g, x, y),
                        axis=0)

    return round_fn


def make_runner(round_fn, chunk: int, w_star, stop_rel_error: float):
    """The engine's runner interface around ``round_fn`` (w -> w)."""
    w_star = jnp.asarray(w_star, jnp.float32)
    nan = jnp.full((chunk,), jnp.nan, jnp.float32)
    metrics = Metrics(*([nan] * len(METRIC_FIELDS)))

    @jax.jit
    def runner(state, n_live):
        def step(carry, i):
            w, done = carry
            new = round_fn(w)
            rel = (jnp.linalg.norm(new.astype(jnp.float32) - w_star)
                   / jnp.linalg.norm(w_star))
            live = jnp.logical_and(~done, i < n_live)
            w = jnp.where(live, new, w)
            done = jnp.logical_or(done, jnp.logical_and(live,
                                                        rel < stop_rel_error))
            return (w, done), (rel, live)

        (w, done), (rels, lives) = jax.lax.scan(
            step, (state.params, jnp.zeros((), bool)), jnp.arange(chunk))
        return State(w), done, metrics, rels, lives

    return runner


def init(d: int, dtype):
    """The control's job state from any key: w = 0, as the paper starts."""
    def new_state(_key: int) -> State:
        return State(jnp.zeros((d,), dtype))
    return new_state


def as_program(inputs, config: dict, traffic: dict, dtype):
    """(init, runner) of the control for the harness's job loop, over the
    client blocks and w* of ``bench/models/logreg.py``'s inputs."""
    hp = traffic["hparams"]
    round_fn = make_round(inputs.data.x, inputs.data.y, config["gamma"],
                          hp["eta"], hp["local_epochs"], dtype)
    runner = make_runner(round_fn, traffic["chunk"],
                         np.asarray(inputs.reference, np.float32),
                         traffic["target_rel_error"])
    return init(config["d"], dtype), runner
