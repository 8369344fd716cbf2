"""Federated problem abstraction.

A federated problem = a differentiable loss + K clients' data. To make K=100
clients cheap under jit we keep client datasets *stacked*: every array leaf
has leading axis K (padded to the largest client, with a per-sample mask), so
per-client gradients are one ``vmap`` instead of a python loop — and the
stacked layout is exactly what core/sharded.py::make_sharded_round_fn
partitions over the ("pod","data") mesh axes in the distributed runtime
(the leading K axis must divide over those axes' sizes).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

Pytree = Any


class ClientBatch(NamedTuple):
    """One (possibly padded) batch of client data.

    x: [n, ...] features; y: [n, ...] targets; mask: [n] 0/1 sample validity.
    """

    x: jax.Array
    y: jax.Array
    mask: jax.Array


class LinearDesign(NamedTuple):
    """A batch's loss declared in canonical linear-design form.

    The model asserts that its per-sample loss is ``link_loss(x_jᵀw, y_j)``
    plus ``reg/2·‖w‖²``, mask-mean-reduced — which is what makes the fused
    local-trajectory kernels (kernels/local_update) applicable: both the
    live and the anchor gradient of a variance-reduced local step are then
    ``Xᵀ c(Xw) / n + reg·w`` for a cheap per-sample coefficient c, so one
    X sweep serves all four autodiff passes of the naive step.

    x: [n, d] design rows (row-aligned with the batch: row j of ``x`` must
       correspond to batch row j, so minibatch index gathers agree with the
       autodiff path); y: [n] targets (±1 for "logistic"); link: one of
       kernels.local_update.LINKS; reg: the ℓ2 coefficient.
    """

    x: jax.Array
    y: jax.Array
    link: str
    reg: float


@dataclasses.dataclass(frozen=True)
class StackedClients:
    """All K clients, padded & stacked on axis 0.

    x: [K, n_max, ...], y: [K, n_max, ...], mask: [K, n_max],
    weight: [K] = N_k / N  (aggregation weights, sums to 1).
    """

    x: jax.Array
    y: jax.Array
    mask: jax.Array
    weight: jax.Array

    @property
    def num_clients(self) -> int:
        return self.x.shape[0]

    def client(self, k: int) -> ClientBatch:
        return ClientBatch(self.x[k], self.y[k], self.mask[k])


@dataclasses.dataclass(frozen=True)
class FLProblem:
    """loss(params, batch) must return the *mean* loss over valid samples of
    the batch (mask-weighted), including any regularizer — i.e. it IS f_k when
    evaluated on client k's full data.
    """

    loss: Callable[[Pytree, ClientBatch], jax.Array]
    init: Callable[[jax.Array], Pytree]
    clients: StackedClients
    #: optional protocol: declare a batch's loss in canonical linear-design
    #: form (see LinearDesign). Models that implement it (logreg, linreg)
    #: become eligible for the fused local-trajectory kernel path
    #: (AlgoHParams.local_impl="pallas"); models that cannot (MLP, decoder)
    #: leave it None and keep the autodiff path.
    linear_design: "Callable[[ClientBatch], LinearDesign] | None" = None
    #: optional frozen operand: weights the loss reads and no algorithm
    #: updates (an LM's base under low-rank adapters, core/lm.py). ``loss``
    #: reads ``frozen``; ``bind(value)`` gives the same problem with the
    #: loss reading ``value`` instead, as a closure (its ``frozen`` None).
    #: make_round_fn then builds ``round(state, frozen)``, so the operand is
    #: an argument of the compiled round and never a constant in it.
    frozen: Pytree | None = None
    bind: "Callable[[Pytree], FLProblem] | None" = None

    # ---- single-client oracles -------------------------------------------
    def grad(self, params: Pytree, batch: ClientBatch) -> Pytree:
        return jax.grad(self.loss)(params, batch)

    def value_and_grad(self, params: Pytree, batch: ClientBatch):
        return jax.value_and_grad(self.loss)(params, batch)

    def hvp(self, params: Pytree, batch: ClientBatch, v: Pytree) -> Pytree:
        """Hessian-vector product via forward-over-reverse — the only Hessian
        access mode any algorithm in this repo uses (matches GIANT's model)."""
        g = lambda p: jax.grad(self.loss)(p, batch)
        return jax.jvp(g, (params,), (v,))[1]

    # ---- all-clients (vmapped) oracles -----------------------------------
    def client_grads(self, params: Pytree) -> Pytree:
        """[K, ...] stacked full-batch gradients ∇f_k(params) for all k."""
        return jax.vmap(lambda x, y, m: self.grad(params, ClientBatch(x, y, m)))(
            self.clients.x, self.clients.y, self.clients.mask
        )

    def global_grad(self, params: Pytree) -> Pytree:
        """∇f(params) = Σ_k (N_k/N) ∇f_k(params)."""
        grads = self.client_grads(params)
        w = self.clients.weight
        return jax.tree.map(
            lambda g: jnp.tensordot(w, g, axes=1), grads
        )

    def global_loss(self, params: Pytree) -> jax.Array:
        losses = jax.vmap(
            lambda x, y, m: self.loss(params, ClientBatch(x, y, m))
        )(self.clients.x, self.clients.y, self.clients.mask)
        return jnp.dot(self.clients.weight, losses)


def sample_minibatch_indices(
    mask: jax.Array, rng: jax.Array, batch_size: int
) -> jax.Array:
    """The row indices ``sample_minibatch`` gathers — exposed so the fused
    local-trajectory path (kernels/local_update) can draw the bit-identical
    minibatches from the design matrix."""
    n = mask.shape[0]
    p = mask / jnp.maximum(jnp.sum(mask), 1.0)
    return jax.random.choice(rng, n, shape=(batch_size,), p=p)


def sample_minibatch(
    batch: ClientBatch, rng: jax.Array, batch_size: int
) -> ClientBatch:
    """Uniformly sample ``batch_size`` valid rows (with replacement — standard
    for SVRG-style estimators and shape-static under jit)."""
    idx = sample_minibatch_indices(batch.mask, rng, batch_size)
    return ClientBatch(batch.x[idx], batch.y[idx], jnp.ones(batch_size, batch.mask.dtype))


def stack_client_arrays(
    xs: list, ys: list
) -> StackedClients:
    """Pad a ragged python list of per-client (x, y) arrays into StackedClients."""
    import numpy as np

    K = len(xs)
    n_max = max(x.shape[0] for x in xs)
    x0, y0 = np.asarray(xs[0]), np.asarray(ys[0])
    X = np.zeros((K, n_max) + x0.shape[1:], dtype=x0.dtype)
    Y = np.zeros((K, n_max) + y0.shape[1:], dtype=y0.dtype)
    M = np.zeros((K, n_max), dtype=np.float32)
    for k, (x, y) in enumerate(zip(xs, ys)):
        n = x.shape[0]
        X[k, :n] = x
        Y[k, :n] = y
        M[k, :n] = 1.0
    # Aggregation weights in float64, normalized BEFORE the f32 cast: per-
    # element f32 rounding of n_k/N leaves Σ W off 1 by O(K·eps), a bias the
    # delta-form aggregation then applies to the model every round and that
    # scales with K. Normalizing in f64 keeps the f32 sum within 1 ulp of 1
    # for ragged K=100 splits (regression-tested).
    counts = np.array([x.shape[0] for x in xs], dtype=np.float64)
    W = (counts / counts.sum()).astype(np.float32)
    return StackedClients(jnp.asarray(X), jnp.asarray(Y), jnp.asarray(M), jnp.asarray(W))
