"""Public entry point for the fused local-trajectory kernel family.

``fused_trajectory`` is what the round cores (core/algorithms.py, under
``AlgoHParams.local_impl="pallas"``) call per client: it handles the
lane/sublane granule padding and row-tile sizing, then dispatches to

  * the Pallas kernel (local_update.py) on TPU — native compilation, X
    streamed once per local step (resident across steps when one row tile
    covers the design block);
  * the op-identical jnp oracle (ref.py) elsewhere — the SAME fused
    algorithm (one forward + one combined backward sweep per step, anchor
    coefficients hoisted for resident designs) without the interpret-mode
    emulation tax, exactly like the quant codec's CPU path.

Padded rows carry mask 0 and padded feature lanes are zero, so neither can
influence the trajectories (hypothesis-tested); n pads to the 128-lane
granule (the row axis is the LAST axis of the y/mask blocks) and d to the
128-lane granule.  Interpret-mode kernel runs are for parity tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.local_update.local_update import (
    DEFAULT_ROW_TILE,
    LINKS,
    trajectory_pallas,
)
from repro.kernels.local_update.ref import trajectory_ref

#: execution backends of the fused path ("auto" = kernel on TPU, ref off it)
FUSED_IMPLS = ("auto", "kernel", "ref")

#: module default, monkeypatchable by tests to force the interpret-mode
#: kernel through full rounds
DEFAULT_IMPL = "auto"

#: keep one X row tile comfortably inside VMEM (bytes, f32)
TILE_BUDGET = 2 * 1024 * 1024
#: designs up to this many bytes use ONE row tile — the Pallas pipeline
#: then elides the X re-fetch across local steps (fully resident loop)
RESIDENT_BUDGET = 4 * 1024 * 1024

_ON_TPU = None


def _use_kernel_default() -> bool:
    global _ON_TPU
    if _ON_TPU is None:
        _ON_TPU = jax.devices()[0].platform == "tpu"
    return _ON_TPU


def _granule(v: int, g: int = 128) -> int:
    return ((v + g - 1) // g) * g


def _pick_row_tile(S: int, n_pad: int, d_pad: int, itemsize: int) -> int:
    """Row-tile height: the whole block when it fits the resident budget
    (S==1 → X stays in VMEM across every local step), else the fewest
    128-granule tiles inside the per-tile budget, balanced so the rows
    padded onto the last tile stay under one granule per tile."""
    if S == 1 and n_pad * d_pad * itemsize <= RESIDENT_BUDGET:
        return n_pad
    t_max = max(128, (TILE_BUDGET // max(d_pad * itemsize, 1)) // 128 * 128)
    tiles = -(-n_pad // t_max)
    return _granule(-(-n_pad // tiles))


def _pad_axis(a, n, axis):
    pad = n - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def fused_trajectory(x, y, mask, w0, u, *, link: str, reg: float, eta: float,
                     anchor_scale: float, steps: int,
                     impl: str | None = None, interpret: bool | None = None,
                     row_tile: int | None = None):
    """Run ``steps`` fused corrected-GD steps; see local_update.py for the
    math.  x: [S, n, d] with S ∈ {1, steps}; y, mask: [S, n]; w0, u: [d].
    Returns (w_traj, r_traj), each [steps, d] in w0.dtype.
    """
    if link not in LINKS:
        raise ValueError(f"unknown link {link!r}; choose from {LINKS}")
    impl = DEFAULT_IMPL if impl is None else impl
    if impl not in FUSED_IMPLS:
        raise ValueError(f"unknown impl {impl!r}; choose from {FUSED_IMPLS}")
    if impl == "auto":
        impl = "kernel" if _use_kernel_default() else "ref"
    if interpret is None:
        interpret = not _use_kernel_default()
    S, n, d = x.shape
    x = x.astype(w0.dtype)
    # the loss's masked-mean denominator; every step's block has the same
    # valid count (full batch: the one design block; minibatch: B ones).
    # Divide in the COMPUTE dtype (the f32 reciprocal is 1e-8 off, which the
    # AA Gram solve amplifies macroscopically in f64 runs)
    inv_dtype = jnp.float64 if w0.dtype == jnp.float64 else jnp.float32
    invn = (1.0 / jnp.maximum(jnp.sum(mask[0]).astype(inv_dtype),
                              1.0)).reshape(1, 1)
    w0r, ur = w0.reshape(1, d), u.reshape(1, d)

    if impl == "ref":
        return trajectory_ref(x, y, mask, w0r, ur, invn, link=link, eta=eta,
                              reg=reg, anchor_scale=anchor_scale, steps=steps)

    d_pad, n_pad = _granule(d), _granule(n)
    if row_tile is None:
        row_tile = _pick_row_tile(S, n_pad, d_pad, x.dtype.itemsize)
    n_pad = _granule(n_pad, row_tile)
    xp = _pad_axis(_pad_axis(x, n_pad, 1), d_pad, 2).reshape(S * n_pad, d_pad)
    yp = _pad_axis(y, n_pad, 1)[:, None, :]
    mp = _pad_axis(mask, n_pad, 1)[:, None, :]
    w_traj, r_traj = trajectory_pallas(
        xp, yp, mp, _pad_axis(w0r, d_pad, 1), _pad_axis(ur, d_pad, 1), invn,
        link=link, eta=eta, reg=reg, anchor_scale=anchor_scale, steps=steps,
        row_tile=row_tile, interpret=interpret)
    return w_traj[:, :d], r_traj[:, :d]
