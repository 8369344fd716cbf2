"""Op-identical jnp oracle for the fused local-trajectory kernel.

Mirrors ``local_update.py`` operation for operation — same ``link_coeff``
coefficients, same multiply-and-reduce contractions (the logits a sum of
x·w over the features, the gradient a sum of x·c over the rows; no dot),
same cast points, same emit expression — so an interpret-mode kernel run
agrees with this reference to f32 reordering noise (pinned in
tests/test_local_update).  The kernel sums in its own order (features by
sublane, rows by 128-lane group, then across lanes); the oracle keeps the
row-major design the round cores hold, so it never pays the kernel's
layout copy.

It doubles as the CPU executor of ``local_impl="pallas"`` (see ops.py):
like the quant codec, interpret-mode Pallas inside a vmapped round core
would dominate CPU round time, while this oracle IS the fused algorithm —
the anchor coefficients of a resident full-batch design are computed once
and every local step costs one forward and one combined backward X sweep
instead of the autodiff path's four.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.local_update.local_update import link_coeff


def _logits(w, x):
    """[1, d] against [n, d] → [1, n]  (the kernel's forward reduction)."""
    return jnp.sum(x * w, axis=1)[None, :]


def _grad(c, x):
    """[1, n] against [n, d] → [1, d]  (the kernel's backward reduction)."""
    return jnp.sum(x * c[0][:, None], axis=0, keepdims=True)


def trajectory_ref(x, y, mask, w0, u, invn, *, link: str, eta: float,
                   reg: float, anchor_scale: float, steps: int):
    """x: [S, n, d]; y, mask: [S, n]; w0, u: [1, d]; invn: [1, 1] (S ∈ {1,
    steps}).  Returns (w_traj, r_traj), each [steps, d] in w0.dtype —
    exactly ``local_update.trajectory_pallas`` on a single row tile.
    """
    S = x.shape[0]
    if S not in (1, steps):
        raise ValueError(f"S={S} must be 1 or steps={steps}")
    out_dtype = w0.dtype
    compute = jnp.float64 if out_dtype == jnp.float64 else jnp.float32
    eta = jnp.asarray(eta, compute)
    reg = jnp.asarray(reg, compute)
    xc = x.astype(compute)
    yc = y.astype(compute)[:, None, :]       # [S, 1, n]
    mc = mask.astype(compute)[:, None, :]    # [S, 1, n]
    w0c = w0.astype(compute)
    uc = u.astype(compute)
    inv = invn[0, 0].astype(compute)
    anchor = anchor_scale == 1.0

    def residual(w, xs, ys, ms, c_anc):
        c = link_coeff(link, _logits(w, xs), ys, ms)
        if anchor:
            c = c - c_anc
        return _grad(c, xs) * inv + reg * w + uc

    if S == 1:
        xs, ys, ms = xc[0], yc[0], mc[0]
        # resident design: the anchor coefficients are step-invariant —
        # computed once here, recomputed (bit-identically) per tile visit
        # by the kernel
        c_anc = link_coeff(link, _logits(w0c, xs), ys, ms) if anchor else None

        def step(w, _):
            r = residual(w, xs, ys, ms, c_anc)
            return w - eta * r, (w.astype(out_dtype)[0], r.astype(out_dtype)[0])

        _, (w_traj, r_traj) = jax.lax.scan(step, w0c, None, length=steps)
    else:

        def step(w, blk):
            xs, ys, ms = blk
            c_anc = (link_coeff(link, _logits(w0c, xs), ys, ms)
                     if anchor else None)
            r = residual(w, xs, ys, ms, c_anc)
            return w - eta * r, (w.astype(out_dtype)[0], r.astype(out_dtype)[0])

        _, (w_traj, r_traj) = jax.lax.scan(step, w0c, (xc, yc, mc))
    return w_traj, r_traj
