"""Pallas TPU kernels for the fused dual-gradient local trajectory.

Hardware adaptation of the FedOSAA hot loop: for linear-design models
(logistic/linear regression — the paper's workload), one local step of the
variance-reduced GD trajectory is

    r(w) = Xᵀ(c_live(Xw) − a·c_anchor(Xw_t)) / n + γ·w + u
    w   ←  w − η·r

where ``c_live``/``c_anchor`` are the per-sample link derivatives evaluated
at the live iterate and the round anchor, ``a`` selects the SVRG dual-
gradient form (a=1) or the constant-correction form (SCAFFOLD/FedAvg, a=0),
and ``u`` folds every minibatch-independent term (global gradient, control
variates, the anchor's ℓ2 term).  The autodiff path realizes this with TWO
loss autodiffs per step — four X sweeps (forward+backward × live+anchor)
from HBM.  This kernel computes both coefficient vectors from the SAME X
tile and accumulates the single combined backward product, so X streams
ONCE per local step — and when the whole design block fits in VMEM (one row
tile), the Pallas pipeline elides the re-fetch across grid steps entirely:
the L-step loop runs on-chip with X resident.

Every contraction here is a matrix-vector product (one row of logits, one
gradient column), which would run the 128-row matrix unit at 1/128 of its
rows.  So none of them is a dot: the kernel multiplies and reduces on the
vector unit, in exact f32, over a FEATURE-MAJOR design block.  Features sit
on sublanes (d padded only to the 8-sublane granule) and rows on lanes, so
for each 128-row lane group of the tile

    z  = Σ_sublanes(xt · w)       the live logits, one [1, 128] row
    z0 = Σ_sublanes(xt · w0)      the anchor logits, from the same vregs
    acc += xt · c                 the backward, [d8, 128] partial sums

and the accumulator is reduced across lanes once, at the last row tile of
each step, into the gradient column.  The logits land as rows, the layout
the targets and mask already have.

Layout (one client; the round cores vmap this over K):

    xt:   [S, d8, n]   design blocks, feature-major: S == 1 (full batch:
                       every step revisits block 0, which is what keeps it
                       resident) or S == steps (per-step minibatch gathers)
    y:    [S, 1, n]    targets (±1 for the logistic link)
    mask: [S, 1, n]    0/1 row validity (padded rows contribute exactly 0)
    w0:   [d8, 1]      start == anchor w^t, a column
    u:    [d8, 1]      constant additive correction (see above)
    invn: [1, 1]       1 / n_eff (the loss's masked-mean denominator)

Grid is (steps, row_tiles) — row tiles iterate fastest; a VMEM scratch pair
(w_cur [d8, 1], acc [d8, 128]) carries the iterate and the gradient partial
sums across grid steps.  Every block's last two dims are (8, 128)-aligned or
span the whole array, as Mosaic requires: y/mask carry a unit middle axis so
a step's (1, row_tile) slice is a full-height block, and the (w_traj,
r_traj) outputs are ONE [steps, d8, 1] block each, resident across the grid,
written one column at the last row tile of every step and flushed to HBM
once (ops.py turns them into [steps, d] rows).

The trajectories, w_cur and the accumulator live in VMEM; only X (once per
step, at worst) and the emitted trajectory columns touch HBM.  ``ref.py`` is
the op-identical jnp oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: links the kernel family knows how to differentiate
LINKS = ("logistic", "linear")

#: default row-tile width (lane-granule multiple; see ops.py for sizing)
DEFAULT_ROW_TILE = 512

#: rows per inner step of the kernel: one lane-width vreg column
LANES = 128

#: lane groups per iteration of the kernel's inner loop (Mosaic unrolls a
#: loop only fully).  On a v5e the covtype K=100 kernel took 2.84 ms a round
#: at 1, 1.32 ms at 8 and 1.24 ms fully unrolled, whose code grows with the
#: tile; at K=10 every unroll from 2 reads 1.99 ms, the HBM stream's time.
UNROLL = 8


def link_coeff(link: str, z: jax.Array, y: jax.Array, mask: jax.Array):
    """Per-sample gradient coefficient c(z) with d loss_j/dw = c_j · x_j.

    logistic: loss_j = softplus(−y_j z_j)   → c_j = −y_j σ(−y_j z_j)
    linear:   loss_j = ½ (z_j − y_j)²       → c_j = z_j − y_j

    Shared (re-exported) by ref.py so kernel and oracle stay op-identical.
    """
    if link == "logistic":
        return (-y) * jax.nn.sigmoid(-(z * y)) * mask
    if link == "linear":
        return (z - y) * mask
    raise ValueError(f"unknown link {link!r}; choose from {LINKS}")


def _make_traj_kernel(link: str, eta: float, reg: float, anchor: bool,
                      compute_dtype):
    """Kernel body with the static knobs closed over (baked constants)."""

    def kernel(x_ref, y_ref, m_ref, w0_ref, u_ref, invn_ref,
               wt_ref, rt_ref, wcur, acc):
        step = pl.program_id(0)
        i = pl.program_id(1)
        n_tiles = pl.num_programs(1)
        first = jnp.logical_and(step == 0, i == 0)

        @pl.when(first)
        def _init():
            wcur[...] = w0_ref[...].astype(compute_dtype)

        @pl.when(i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        d8, tile = x_ref.shape
        w = jnp.broadcast_to(wcur[...], (d8, LANES))
        w0 = jnp.broadcast_to(w0_ref[...].astype(compute_dtype), (d8, LANES))

        def lane_group(g, part):
            cols = pl.ds(pl.multiple_of(g * LANES, LANES), LANES)
            xt = x_ref[:, cols].astype(compute_dtype)        # [d8, 128]
            yv = y_ref[:, cols].astype(compute_dtype)        # [1, 128]
            mv = m_ref[:, cols].astype(compute_dtype)        # [1, 128]
            # forward: live logits from the vregs just loaded ...
            z = jnp.sum(xt * w, axis=0, keepdims=True)       # [1, 128]
            c = link_coeff(link, z, yv, mv)
            if anchor:
                # ... and the anchor logits from the SAME vregs — the second
                # gradient of the dual-gradient residual costs no extra load
                z0 = jnp.sum(xt * w0, axis=0, keepdims=True)
                c = c - link_coeff(link, z0, yv, mv)
            # one combined backward accumulation: both residual
            # contributions ride a single Xᵀ(·) sweep of the lane group
            return part + xt * c                             # [d8, 128]

        def unrolled(j, part):
            for k in range(UNROLL):
                part = lane_group(j * UNROLL + k, part)
            return part

        groups = tile // LANES
        part = jax.lax.fori_loop(0, groups // UNROLL, unrolled, acc[...])
        for g in range(groups - groups % UNROLL, groups):
            part = lane_group(g, part)
        acc[...] = part

        @pl.when(i == n_tiles - 1)
        def _emit():
            w_now = wcur[...]                                # [d8, 1]
            grad = jnp.sum(acc[...], axis=1, keepdims=True)  # [d8, 1]
            r = (grad * invn_ref[0, 0].astype(compute_dtype)
                 + reg * w_now + u_ref[...].astype(compute_dtype))
            wt_ref[step] = w_now.astype(wt_ref.dtype)
            rt_ref[step] = r.astype(rt_ref.dtype)
            wcur[...] = w_now - eta * r

    return kernel


def trajectory_pallas(x, y, mask, w0, u, invn, *, link: str, eta: float,
                      reg: float, anchor_scale: float, steps: int,
                      row_tile: int = DEFAULT_ROW_TILE,
                      interpret: bool = False):
    """x: [S, d8, n] feature-major; y, mask: [S, 1, n]; w0, u: [d8, 1];
    invn: [1, 1].

    S must be 1 (resident full-batch design) or ``steps`` (per-step
    minibatch blocks); n % row_tile == 0 and row_tile % 128 == 0.  Returns
    (w_traj, r_traj), each [steps, d8, 1] in w0.dtype.
    """
    S, _, n = y.shape
    d8 = x.shape[1]
    if x.shape != (S, d8, n):
        raise ValueError(f"x shape {x.shape} != (S, d8, n) = ({S}, d8, {n})")
    if S not in (1, steps):
        raise ValueError(f"S={S} must be 1 or steps={steps}")
    if n % row_tile or row_tile % LANES:
        raise ValueError(f"n={n} not a multiple of row_tile={row_tile}, or "
                         f"row_tile not a multiple of {LANES}")
    if anchor_scale not in (0.0, 1.0):
        raise ValueError(f"anchor_scale must be 0.0 or 1.0, got {anchor_scale}")
    compute_dtype = jnp.float64 if w0.dtype == jnp.float64 else jnp.float32
    n_tiles = n // row_tile
    sidx = (lambda l: l) if S > 1 else (lambda l: 0)
    kernel = _make_traj_kernel(link, float(eta), float(reg),
                               anchor_scale == 1.0, compute_dtype)
    row_block = pl.BlockSpec((pl.squeezed, 1, row_tile),
                             lambda l, i: (sidx(l), 0, i))
    column = pl.BlockSpec((d8, 1), lambda l, i: (0, 0))
    traj = pl.BlockSpec((steps, d8, 1), lambda l, i: (0, 0, 0))
    w_traj, r_traj = pl.pallas_call(
        kernel,
        grid=(steps, n_tiles),
        in_specs=[
            pl.BlockSpec((pl.squeezed, d8, row_tile),
                         lambda l, i: (sidx(l), 0, i)),
            row_block,
            row_block,
            column,
            column,
            pl.BlockSpec((1, 1), lambda l, i: (0, 0)),
        ],
        out_specs=[traj, traj],
        out_shape=[jax.ShapeDtypeStruct((steps, d8, 1), w0.dtype)] * 2,
        scratch_shapes=[
            pltpu.VMEM((d8, 1), compute_dtype),       # w_cur
            pltpu.VMEM((d8, LANES), compute_dtype),   # gradient partial sums
        ],
        interpret=interpret,
        name="fl_local_trajectory_kernel",
    )(x, y, mask, w0, u, invn)
    return w_traj, r_traj
