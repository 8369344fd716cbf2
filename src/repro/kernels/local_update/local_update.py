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

Layout (one client; the round cores vmap this over K):

    x:    [S·n, d]     design blocks, S stacked on the row axis — S == 1
                       (full batch: every step revisits block 0, which is
                       what keeps it resident) or S == steps (per-step
                       minibatch gathers).  Kept 2-D: the row tile is a
                       plain (row_tile, d) block
    y:    [S, 1, n]    targets (±1 for the logistic link)
    mask: [S, 1, n]    0/1 row validity (padded rows contribute exactly 0)
    w0:   [1, d]       start == anchor w^t
    u:    [1, d]       constant additive correction (see above)
    invn: [1, 1]       1 / n_eff (the loss's masked-mean denominator)

Grid is (steps, row_tiles) — row tiles iterate fastest; a VMEM scratch pair
(w_cur, acc) carries the iterate and the gradient accumulator across grid
steps.  Every block's last two dims are (8, 128)-aligned or span the whole
array, as Mosaic requires: y/mask carry a unit middle axis so a step's
(1, row_tile) slice is a full-height block, and the [steps, d] (w_traj,
r_traj) outputs are ONE block resident across the grid, written one row at
the last row tile of every step and flushed to HBM once.

The [steps, d] trajectories, w_cur and the dual logit/coefficient buffers
live in VMEM; only X (once per step, at worst) and the emitted trajectory
rows touch HBM.  ``ref.py`` is the op-identical jnp oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: links the kernel family knows how to differentiate
LINKS = ("logistic", "linear")

#: default row-tile height (lane-granule multiple; see ops.py for sizing)
DEFAULT_ROW_TILE = 512

#: f32 contractions at full f32 precision. At Mosaic's default an f32 dot
#: rounds its operands to bf16: the logits, and so the local fixed point,
#: drift, and FedOSAA-SVRG on the paper covtype split (K=100) bottomed out
#: at rel-error 9e-3 on a v5e instead of 1.1e-5.
PRECISION = jax.lax.Precision.HIGHEST

#: scoped-VMEM limit of one kernel call. At PRECISION the resident K=100
#: covtype block (5,888x128 f32, double-buffered) needs 18.9 MB of scoped
#: VMEM, past Mosaic's 16 MB default; a v5e core has 128 MiB.
VMEM_LIMIT = 64 * 1024 * 1024


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

        x = x_ref[...].astype(compute_dtype)        # [Tn, d]
        yv = y_ref[...].astype(compute_dtype)       # [1, Tn]
        mv = m_ref[...].astype(compute_dtype)       # [1, Tn]
        w = wcur[...]                               # [1, d]

        # forward: live logits from the tile already in VMEM ...
        z = jax.lax.dot_general(
            w, x, (((1,), (1,)), ((), ())), precision=PRECISION,
            preferred_element_type=compute_dtype)   # [1, Tn]
        c = link_coeff(link, z, yv, mv)
        if anchor:
            # ... and the anchor logits from the SAME tile — the second
            # gradient of the dual-gradient residual costs no extra X fetch
            z0 = jax.lax.dot_general(
                w0_ref[...].astype(compute_dtype), x,
                (((1,), (1,)), ((), ())), precision=PRECISION,
                preferred_element_type=compute_dtype)
            c = c - link_coeff(link, z0, yv, mv)
        # one combined backward accumulation: both residual contributions
        # ride a single Xᵀ(·) sweep of the tile
        acc[...] += jax.lax.dot_general(
            c, x, (((1,), (0,)), ((), ())), precision=PRECISION,
            preferred_element_type=compute_dtype)   # [1, d]

        @pl.when(i == n_tiles - 1)
        def _emit():
            w_now = wcur[...]
            r = (acc[...] * invn_ref[0, 0].astype(compute_dtype)
                 + reg * w_now + u_ref[...].astype(compute_dtype))
            row = pl.ds(step, 1)
            wt_ref[row, :] = w_now.astype(wt_ref.dtype)
            rt_ref[row, :] = r.astype(rt_ref.dtype)
            wcur[...] = w_now - eta * r

    return kernel


def trajectory_pallas(x, y, mask, w0, u, invn, *, link: str, eta: float,
                      reg: float, anchor_scale: float, steps: int,
                      row_tile: int = DEFAULT_ROW_TILE,
                      interpret: bool = False):
    """x: [S·n, d] (S stacked on rows); y, mask: [S, 1, n]; w0, u: [1, d];
    invn: [1, 1].

    S must be 1 (resident full-batch design) or ``steps`` (per-step
    minibatch blocks); n % row_tile == 0.  Returns (w_traj, r_traj), each
    [steps, d] in w0.dtype.
    """
    S, _, n = y.shape
    d = x.shape[1]
    if x.shape[0] != S * n:
        raise ValueError(f"x rows {x.shape[0]} != S*n = {S}*{n}")
    if S not in (1, steps):
        raise ValueError(f"S={S} must be 1 or steps={steps}")
    if n % row_tile:
        raise ValueError(f"n={n} not a multiple of row_tile={row_tile}")
    if anchor_scale not in (0.0, 1.0):
        raise ValueError(f"anchor_scale must be 0.0 or 1.0, got {anchor_scale}")
    compute_dtype = jnp.float64 if w0.dtype == jnp.float64 else jnp.float32
    n_tiles = n // row_tile
    sidx = (lambda l: l) if S > 1 else (lambda l: 0)
    kernel = _make_traj_kernel(link, float(eta), float(reg),
                               anchor_scale == 1.0, compute_dtype)
    w_traj, r_traj = pl.pallas_call(
        kernel,
        grid=(steps, n_tiles),
        in_specs=[
            pl.BlockSpec((row_tile, d),
                         lambda l, i: (sidx(l) * n_tiles + i, 0)),
            pl.BlockSpec((pl.squeezed, 1, row_tile),
                         lambda l, i: (sidx(l), 0, i)),
            pl.BlockSpec((pl.squeezed, 1, row_tile),
                         lambda l, i: (sidx(l), 0, i)),
            pl.BlockSpec((1, d), lambda l, i: (0, 0)),
            pl.BlockSpec((1, d), lambda l, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda l, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((steps, d), lambda l, i: (0, 0)),
            pl.BlockSpec((steps, d), lambda l, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((steps, d), w0.dtype),
            jax.ShapeDtypeStruct((steps, d), w0.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, d), compute_dtype),   # w_cur
            pltpu.VMEM((1, d), compute_dtype),   # gradient accumulator
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="fl_local_trajectory_kernel",
    )(x, y, mask, w0, u, invn)
    return w_traj, r_traj
