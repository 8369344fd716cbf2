"""Bridge: federated optimization (core/algorithms) over LM models
(models/decoder) — FedOSAA training of transformers/SSMs.

Clients hold token corpora; the FLProblem's loss is the model's next-token
cross entropy over the client's documents. Everything downstream (FedSVRG /
FedOSAA rounds, AA step, server aggregation) is unchanged — the paper's
algorithm is architecture-agnostic (DESIGN.md §4).

Two parameterizations:

* full: the trainable parameters are the model's whole param tree;
* low-rank adapters (LoRA, arXiv:2106.09685; federated as in FedIT,
  arXiv:2305.05644): ``W x`` becomes ``W x + (alpha/r)·B A x`` on every
  linear map a ``LoraAdapter`` targets, over a frozen base. The trainable
  parameters are ONE flat float32 ``[d]`` vector holding every A and B, so
  the AA step's flat-buffer path (core/anderson.py) ravels nothing; the base
  is the problem's frozen operand (``FLProblem.frozen``), an argument of the
  compiled round rather than a constant in it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.problem import ClientBatch, FLProblem, StackedClients

Pytree = Any

#: the linear maps that carry an adapter, by the module that holds them
#: (models/layers.py: Mamba-2 mixer, attention, SwiGLU MLP)
LORA_TARGETS = {
    "mixer": ("wx", "wz", "wB", "wC", "wdt", "out_proj"),
    "attn": ("wq", "wk", "wv", "wo"),
    "mlp": ("wi_gate", "wi_up", "wo"),
}


def make_lm_clients(tokens: np.ndarray, num_clients: int,
                    docs_per_client: int | None = None) -> StackedClients:
    """tokens: [n_docs, S] int32. IID split into K clients."""
    n_docs = tokens.shape[0]
    per = docs_per_client or n_docs // num_clients
    xs, ys = [], []
    for k in range(num_clients):
        chunk = tokens[k * per:(k + 1) * per]
        xs.append(chunk)
        ys.append(np.zeros((chunk.shape[0],), np.float32))   # labels unused
    from repro.core.problem import stack_client_arrays
    return stack_client_arrays(xs, ys)


class LoraSlot(NamedTuple):
    """One adapted matrix ``[*lead, d_in, d_out]`` of the base (``lead`` the
    layer-stack axes): its A ``[*lead, d_in, r]`` starts at ``offset`` of the
    flat vector and its B ``[*lead, r, d_out]`` follows."""

    path: tuple           # key path of the module dict holding the matrix
    name: str             # the matrix's key in it
    lead: tuple
    d_in: int
    d_out: int
    offset: int


@dataclasses.dataclass(frozen=True)
class LoraAdapter:
    """The layout of a flat adapter vector over a base param tree."""

    rank: int
    alpha: float
    slots: tuple
    size: int             # d: the flat vector's length

    @property
    def scale(self) -> float:
        return self.alpha / self.rank

    def _sizes(self, slot: LoraSlot) -> tuple[int, int]:
        lead = int(np.prod(slot.lead, dtype=np.int64))
        return lead * slot.d_in * self.rank, lead * self.rank * slot.d_out

    def init(self, rng: jax.Array) -> jax.Array:
        """A ~ U(±1/sqrt(d_in)) (the kaiming-uniform default of the
        reference LoRA code), B = 0: the adapted model starts as the base."""
        pieces = []
        for slot, key in zip(self.slots,
                             jax.random.split(rng, len(self.slots))):
            n_a, n_b = self._sizes(slot)
            bound = 1.0 / np.sqrt(slot.d_in)
            pieces.append(jax.random.uniform(key, (n_a,), jnp.float32,
                                             -bound, bound))
            pieces.append(jnp.zeros((n_b,), jnp.float32))
        return jnp.concatenate(pieces)

    def split(self, flat: jax.Array) -> list:
        """[(A, B)] per slot, views of ``flat`` in their matrix shapes."""
        out = []
        for slot in self.slots:
            n_a, n_b = self._sizes(slot)
            o = slot.offset
            a = flat[o:o + n_a].reshape(slot.lead + (slot.d_in, self.rank))
            b = flat[o + n_a:o + n_a + n_b].reshape(
                slot.lead + (self.rank, slot.d_out))
            out.append((a, b))
        return out

    def merge(self, base: Pytree, flat: jax.Array) -> Pytree:
        """The base params with each adapter beside its matrix, under
        ``<name>_lora`` as ``(A, (alpha/r)·B)`` (models/layers.linear)."""
        extra: dict = {}
        for slot, (a, b) in zip(self.slots, self.split(flat)):
            extra.setdefault(slot.path, {})[slot.name + "_lora"] = (
                a, b * self.scale)

        def visit(node, path):
            if isinstance(node, dict):
                out = {k: visit(v, path + (k,)) for k, v in node.items()}
                out.update(extra.get(path, {}))
                return out
            if isinstance(node, list):
                return [visit(v, path + (i,)) for i, v in enumerate(node)]
            return node

        return visit(base, ())


def make_lora(base: Pytree, rank: int, alpha: float) -> LoraAdapter:
    """Adapters on every ``LORA_TARGETS`` matrix of ``base`` (arrays or
    ShapeDtypeStructs), in the order the tree lists them."""
    slots, offset = [], 0

    def visit(node, path):
        nonlocal offset
        if isinstance(node, dict):
            names = LORA_TARGETS.get(path[-1], ()) if path else ()
            for k, v in node.items():
                if k in names:
                    *lead, d_in, d_out = v.shape
                    slot = LoraSlot(path, k, tuple(lead), d_in, d_out, offset)
                    slots.append(slot)
                    n = int(np.prod(lead, dtype=np.int64))
                    offset += n * rank * (d_in + d_out)
                else:
                    visit(v, path + (k,))
        elif isinstance(node, list):
            for i, v in enumerate(node):
                visit(v, path + (i,))

    visit(base, ())
    if not slots:
        raise ValueError("the base holds none of the LoRA target matrices")
    return LoraAdapter(rank, float(alpha), tuple(slots), offset)


def _lm_batch(batch: ClientBatch) -> dict:
    # batch.x: [n, S] tokens; batch.mask: [n] doc validity
    return {
        "tokens": batch.x,
        "loss_mask": jnp.broadcast_to(
            batch.mask[:, None], batch.x.shape
        ).astype(jnp.float32),
    }


def make_lm_problem(model, clients: StackedClients, base: Pytree = None,
                    lora: LoraAdapter | None = None) -> FLProblem:
    """The clients' next-token loss. With ``lora`` (and its ``base``) the
    parameters are the adapter's flat vector and ``base`` is the problem's
    frozen operand; without, the parameters are the whole model."""
    if lora is None:
        return FLProblem(loss=lambda p, b: model.loss(p, _lm_batch(b)),
                         init=model.init, clients=clients)

    def bound(frozen: Pytree) -> FLProblem:
        def loss(flat, batch: ClientBatch) -> jax.Array:
            return model.loss(lora.merge(frozen, flat), _lm_batch(batch))

        return FLProblem(loss=loss, init=lora.init, clients=clients)

    return dataclasses.replace(bound(base), frozen=base, bind=bound)
