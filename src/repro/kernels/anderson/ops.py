"""Jit'd public wrappers: flat-vector AA passes via the Pallas kernels.

Off a TPU the kernels execute in interpret mode; on a TPU they compile
natively. The wrappers pad d up to a tile multiple and strip the padding
after: padded Y entries are zero, so they contribute nothing to the Gram
matrix. The history axis m is never padded — every block spans all m rows,
which Mosaic accepts for any m, so a [m, d] history is not copied into an
8-row buffer (at m=2 that copy would quadruple the history bytes).

Besides the one-shot ``aa_step_flat`` (kept as the flat-vector reference
entry point), this module exposes the two passes separately
(``flat_gram`` / ``flat_update``) plus dtype-preserving ravel helpers, so
the round cores can fuse the AA hot path over a *pytree*: group the leaves
by dtype, ravel each group into one flat buffer, accumulate ONE Gram system
across groups, solve once, and stream each group through the update kernel —
every S/Y element is read exactly once per pass instead of the three
HBM sweeps of the naive tree_math path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.anderson.anderson import DEFAULT_TILE, gram_pallas, update_pallas
from repro.kernels.anderson.ref import solve_gamma_ref

_ON_CPU = None


def _interpret_default() -> bool:
    global _ON_CPU
    if _ON_CPU is None:
        _ON_CPU = jax.devices()[0].platform != "tpu"
    return _ON_CPU


def _pad_to(x, n, axis):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_dims(d: int, tile: int) -> tuple[int, int]:
    """(tile, d_pad): shrink the tile for small vectors, pad d to a tile
    multiple."""
    t = min(tile, 256) if d < tile else tile
    return t, ((d + t - 1) // t) * t


# --------------------------------------------------------------------------
# the two single-pass kernels on unpadded flat buffers
# --------------------------------------------------------------------------

def flat_gram(y, g, *, tile: int = DEFAULT_TILE, interpret: bool | None = None):
    """One-pass Gram build on flat buffers: y [m,d], g [d] →
    (YᵀY [m,m] f32, Yᵀg [m] f32). Pads internally; any m ≥ 1."""
    if interpret is None:
        interpret = _interpret_default()
    t, d_pad = _pad_dims(y.shape[1], tile)
    return gram_pallas(_pad_to(y, d_pad, 1), _pad_to(g, d_pad, 0), tile=t,
                       interpret=interpret)


def flat_update(w, g, s, y, gamma, eta, beta, *, tile: int = DEFAULT_TILE,
                interpret: bool | None = None):
    """One-pass update on flat buffers: w⁺ = w − ηg − β(SᵀΓ − ηYᵀΓ).
    w,g: [d]; s,y: [m,d]; gamma: [m]. Pads internally; preserves w.dtype."""
    if interpret is None:
        interpret = _interpret_default()
    d = s.shape[1]
    t, d_pad = _pad_dims(d, tile)
    out = update_pallas(_pad_to(w, d_pad, 0), _pad_to(g, d_pad, 0),
                        _pad_to(s, d_pad, 1), _pad_to(y, d_pad, 1), gamma,
                        eta, beta, tile=t, interpret=interpret)
    return out[:d]


# --------------------------------------------------------------------------
# dtype-preserving ravel helpers (pytree ↔ per-dtype flat buffers)
# --------------------------------------------------------------------------

def dtype_leaf_groups(tree) -> list[tuple[jnp.dtype, list[int]]]:
    """Flattened-leaf indices grouped by dtype, in first-seen leaf order.

    A single-dtype model (the common case) yields exactly one group — one
    flat buffer per round through the kernels; mixed-dtype trees get one
    buffer per dtype, sharing a single Gram system across groups."""
    groups: dict = {}
    for i, leaf in enumerate(jax.tree.leaves(tree)):
        groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    return list(groups.items())


def ravel_group(leaves: list, idxs: list[int]):
    """Concatenate the selected plain leaves into one flat [d_g] buffer."""
    return jnp.concatenate([leaves[i].reshape(-1) for i in idxs])


def ravel_stack_group(leaves: list, idxs: list[int]):
    """Concatenate the selected stacked leaves ([m, ...]) into [m, d_g]."""
    m = leaves[idxs[0]].shape[0]
    return jnp.concatenate([leaves[i].reshape(m, -1) for i in idxs], axis=1)


def unravel_group_into(flat, leaves: list, idxs: list[int], out: list) -> None:
    """Scatter a flat [d_g] buffer back into ``out`` at the group's leaf
    slots, restoring each leaf's shape and dtype (dtype-preserving)."""
    off = 0
    for i in idxs:
        ref = leaves[i]
        out[i] = flat[off:off + ref.size].reshape(ref.shape).astype(ref.dtype)
        off += ref.size


@partial(jax.jit, static_argnames=("eta", "beta", "tikhonov", "tile", "interpret"))
def aa_step_flat(w, g, s, y, *, eta: float, beta: float = 1.0,
                 tikhonov: float = 1e-10, tile: int = DEFAULT_TILE,
                 interpret: bool | None = None):
    """One AA step on flat vectors. w,g: [d]; s,y: [m,d]. Returns w⁺ [d]."""
    if interpret is None:
        interpret = _interpret_default()
    gram, yg = flat_gram(y, g, tile=tile, interpret=interpret)
    gamma = solve_gamma_ref(gram, yg, tikhonov)
    return flat_update(w, g, s, y, gamma, eta, beta, tile=tile,
                       interpret=interpret)
