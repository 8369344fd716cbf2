"""Pallas TPU kernels for the FedOSAA Anderson-acceleration step.

Hardware adaptation (DESIGN.md §3): the AA step is *memory-bound* — O(L)
arithmetic intensity over a parameter vector of up to 10¹⁰ elements. The
naive jnp implementation streams S and Y from HBM THREE times (Gram build,
projection, update). These kernels stream them exactly once per pass, tiled
through VMEM:

  pass 1 (``gram_kernel``):   accumulate YᵀY [m,m] and Yᵀg [m] tile-by-tile
  pass 2 (``update_kernel``): w⁺ = w − ηg − (S − ηY)Γ       tile-by-tile

The [m,m] solve between the passes is negligible (m = local epochs ≤ ~30) and
stays in plain jnp. Tiles are (m, T) with T=2048 lanes; each block spans
all m history rows, so m needs no padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 2048

#: f32 contractions at full f32 precision: the [m,m] solve amplifies the
#: Gram's rounding by its condition number (1e10-1e12 on covtype at L=10),
#: and at Mosaic's default an f32 dot rounds its operands to bf16.
PRECISION = jax.lax.Precision.HIGHEST


def _acc_dtype(dtype):
    """Accumulation dtype: at least f32; f64 (interpret mode) stays f64."""
    return jnp.promote_types(dtype, jnp.float32)


def _gram_kernel(y_ref, g_ref, gram_ref, yg_ref):
    """Grid: (d // T,). Accumulates into the single output block.

    y_ref:   [m, T] VMEM tile of Y
    g_ref:   [1, T] VMEM tile of the gradient
    gram_ref:[m, m] output (same block every step -> accumulate)
    yg_ref:  [1, m] output
    """
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        yg_ref[...] = jnp.zeros_like(yg_ref)

    acc = gram_ref.dtype
    y = y_ref[...].astype(acc)
    g = g_ref[...].astype(acc)
    gram_ref[...] += jax.lax.dot_general(
        y, y, (((1,), (1,)), ((), ())), precision=PRECISION,
        preferred_element_type=acc
    )
    yg_ref[...] += jax.lax.dot_general(
        g, y, (((1,), (1,)), ((), ())), precision=PRECISION,
        preferred_element_type=acc
    )


def gram_pallas(y: jax.Array, g: jax.Array, tile: int = DEFAULT_TILE,
                interpret: bool = False):
    """y: [m, d]; g: [d]. Returns (YᵀY [m,m], Yᵀg [m]) accumulated in
    at least f32 (f64 inputs stay f64). d % tile == 0."""
    m, d = y.shape
    assert d % tile == 0, (d, tile)
    acc = _acc_dtype(y.dtype)
    grid = (d // tile,)
    gram, yg = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, m), acc),
            jax.ShapeDtypeStruct((1, m), acc),
        ],
        interpret=interpret,
    )(y, g.reshape(1, d))
    return gram, yg[0]


def _update_kernel(w_ref, g_ref, s_ref, y_ref, gamma_ref, eta_ref, beta_ref,
                   out_ref):
    """w⁺ tile = w − η·g − β·(Sᵀγ − η·Yᵀγ) over a [1, T] tile.

    gamma_ref: [1, m] SMEM-resident coefficients; eta/beta scalars [1,1].
    """
    acc = gamma_ref.dtype
    w = w_ref[...].astype(acc)               # [1, T]
    g = g_ref[...].astype(acc)               # [1, T]
    s = s_ref[...].astype(acc)               # [m, T]
    y = y_ref[...].astype(acc)               # [m, T]
    gamma = gamma_ref[...]                   # [1, m]
    eta = eta_ref[0, 0]
    beta = beta_ref[0, 0]
    s_g = jax.lax.dot_general(
        gamma, s, (((1,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=acc
    )                                         # [1, T]
    y_g = jax.lax.dot_general(
        gamma, y, (((1,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=acc
    )
    out = w - eta * g - beta * (s_g - eta * y_g)
    out_ref[...] = out.astype(out_ref.dtype)


def update_pallas(w, g, s, y, gamma, eta, beta, tile: int = DEFAULT_TILE,
                  interpret: bool = False):
    """w,g: [d]; s,y: [m,d]; gamma: [m]. Returns w⁺ [d], computed in at
    least f32 (f64 inputs stay f64)."""
    m, d = s.shape
    assert d % tile == 0, (d, tile)
    acc = _acc_dtype(s.dtype)
    grid = (d // tile,)
    out = pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((m, tile), lambda i: (0, i)),
            pl.BlockSpec((m, tile), lambda i: (0, i)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), w.dtype),
        interpret=interpret,
    )(
        w.reshape(1, d), g.reshape(1, d), s, y,
        gamma.reshape(1, m).astype(acc),
        jnp.full((1, 1), eta, acc),
        jnp.full((1, 1), beta, acc),
    )
    return out[0]
