"""Anderson acceleration core (paper §2.2, Eq. 2–7).

Everything is pytree-native: history stacks S, Y are pytrees whose leaves carry
a leading history axis [m, ...]; the only dense objects are the [m, m] Gram
matrix and length-m coefficient vectors, so the same code path serves a
300-parameter logistic regression and a tensor-parallel 76B transformer
(where each Gram contraction compiles to per-shard matmuls + a psum).

Two mathematically equivalent formulations are provided:

* ``aa_mixing_step``   — the classical constrained-LS mixing form (Eq. 2–3),
* ``multisecant_update`` — the quasi-Newton form actually used by FedOSAA
  (Eq. 4–5 / Algorithm 1 Eq. 7):

      w⁺ = w − H⁻¹ g,   H⁻¹ = ηI + (S − ηY)(YᵀY)⁻¹Yᵀ .

Stability options from paper Appendix A are first-class:
Tikhonov regularization of the Gram system, spectral filtering of nearly
linearly-dependent Y columns (Pollock & Rebholz 2023, adapted to fixed-shape
jit via truncated-eigenvalue pseudo-inverse), and damping of the quasi-Newton
correction (Wei et al. 2021).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.utils import tree_math as tm

Pytree = Any


@dataclasses.dataclass(frozen=True)
class AAConfig:
    """Knobs for one Anderson-acceleration step.

    Attributes:
      tikhonov: relative Tikhonov regularization λ; the Gram system solved is
        (YᵀY + λ·tr(YᵀY)/m·I). 0 disables. Paper default experiments use 0
        (f64 on CPU); we default to 1e-10 which is invisible at f32 scale but
        guards rank-deficient trajectories.
      filter_rtol: drop (zero out) eigen-directions of the Gram matrix whose
        eigenvalue is below filter_rtol × λ_max — the jit-friendly analogue of
        column filtering [34]. 0 disables.
      damping: scale on the quasi-Newton correction term (S−ηY)Γ. 1.0 = paper.
      min_history: below this many valid columns the AA step falls back to the
        plain damped-gradient step (returned unchanged).
      clip_rtol: byzantine-column screen — drop history columns whose residual
        norm ‖y_i‖ exceeds median(‖y‖)/clip_rtol before the Gram solve (a
        column is kept iff clip_rtol·‖y_i‖ ≤ median). The median is the robust
        scale: with ≤ half the columns poisoned it sits at the clean scale, so
        one stale/byzantine column (which can otherwise steer the extrapolation
        arbitrarily through (YᵀY)Γ = Yᵀg) is screened out and the step degrades
        toward the plain damped-gradient step instead of diverging. Values in
        (0, 1] keep at least half the columns (0.1 ≈ "drop columns 10× the
        median"). 0 disables — and is an exact no-op: the default path's
        compiled graph is unchanged. The same screen doubles as an AGE
        screen under the deadline gate (repro.robust.async_agg): a
        stale-folded client's residual columns drift off the cohort median
        and get clipped the same way — the measured alternative to
        ``AsyncConfig.guard_history``, which instead bit-freezes the
        folded rows' history writes (benchmarks/ext_async.py records both;
        at the committed scale they converge in the same round count).
    """

    tikhonov: float = 1e-10
    filter_rtol: float = 0.0
    damping: float = 1.0
    min_history: int = 1
    residual_ema: float = 0.0   # EMA over residuals before building Y
                                # (Pasini et al. [28]; App. A option 3) —
                                # smooths stochastic-gradient noise that
                                # otherwise stalls AA at the noise floor
    clip_rtol: float = 0.0


class AAStats(NamedTuple):
    """Diagnostics of one AA step (all scalars)."""

    theta: jax.Array          # optimization gain ‖(I−Proj_Y)g‖/‖g‖  (Eq. 9)
    gamma_norm: jax.Array     # ‖Γ‖ of the LS solution
    gram_cond: jax.Array      # rough condition estimate of the Gram matrix
    used_columns: jax.Array   # how many eigen-directions survived filtering
    clipped_columns: jax.Array  # history columns dropped by the clip_rtol
                                # residual screen (0 when the screen is off)


def _solve_gram(gram: jax.Array, rhs: jax.Array, cfg: AAConfig,
                col_mask: jax.Array | None = None):
    """Solve (YᵀY) Γ = Yᵀg robustly; returns (Γ, stats pieces).

    Uses a symmetric eigendecomposition so filtering and conditioning fall out
    for free. m is tiny (≤ local epochs L), so this is negligible work.

    col_mask (bool [m], optional) zeroes the masked columns out of the system
    entirely — their Gram rows/cols, their rhs entries, AND their Tikhonov
    diagonal — so a screened column contributes exactly nothing to Γ and does
    not count toward used_columns (its eigenvalue is exactly 0 and falls to
    the near-zero guard).

    Degenerate systems are well-defined, never NaN: if filtering plus the
    near-zero guard drop every direction (all-filtered, or a rank-0 Gram from
    identical history columns) then Γ is exactly 0 — the caller's update
    degrades bit-exactly to the plain damped-gradient step — and cond reports
    1.0 (a zero system is not ill-conditioned, it is empty).
    """
    m = gram.shape[0]
    tik_diag = jnp.eye(m, dtype=gram.dtype)
    if col_mask is not None:
        # select, don't multiply: a byzantine column can carry inf/nan Gram
        # entries and 0·inf = nan would leak the poison back into the masked
        # system; jnp.where zeroes the row/column unconditionally
        cm2 = jnp.logical_and(col_mask[:, None], col_mask[None, :])
        gram = jnp.where(cm2, gram, 0.0)
        rhs = jnp.where(col_mask, rhs, 0.0)
        tik_diag = jnp.where(col_mask[:, None], tik_diag, 0.0)
    trace = jnp.trace(gram)
    lam = cfg.tikhonov * trace / m
    evals, evecs = jnp.linalg.eigh(gram + lam * tik_diag)
    evals = jnp.maximum(evals, 0.0)
    emax = jnp.max(evals)
    keep = evals > cfg.filter_rtol * emax
    # guard: never invert a (near-)zero eigenvalue even when filtering is off
    safe = evals > 1e-30 * jnp.maximum(emax, 1e-30)
    keep = jnp.logical_and(keep, safe)
    inv = jnp.where(keep, 1.0 / jnp.where(keep, evals, 1.0), 0.0)
    gamma = evecs @ (inv * (evecs.T @ rhs))
    used = jnp.sum(keep)
    emin_kept = jnp.min(jnp.where(keep, evals, emax))
    cond = jnp.where(used > 0, emax / jnp.maximum(emin_kept, 1e-30), 1.0)
    return gamma, cond, used


def _residual_clip_mask(gram: jax.Array, cfg: AAConfig) -> jax.Array:
    """Bool [m] keep-mask for the clip_rtol byzantine-column screen.

    The per-column residual norms ‖y_i‖ are read off the Gram diagonal (so the
    screen is identical for the tree and pallas paths, which both have the
    accumulated Gram in hand), and compared against the jit-friendly robust
    scale median(‖y‖): keep iff clip_rtol·‖y_i‖ ≤ median. Non-finite columns
    (an overflowed byzantine column drives ‖y‖² past f32 max) are always
    dropped and excluded from the median so they cannot poison the scale
    estimate itself.
    """
    norms = jnp.sqrt(jnp.maximum(jnp.diagonal(gram), 0.0))
    finite = jnp.isfinite(norms)
    med = jnp.nanmedian(jnp.where(finite, norms, jnp.nan))
    return jnp.logical_and(finite, cfg.clip_rtol * norms <= med)


def _screened_solve(gram: jax.Array, rhs: jax.Array, cfg: AAConfig):
    """clip_rtol screen (python-gated: off → graph unchanged) + Gram solve.

    Returns (Γ, cond, used_columns, clipped_columns, keep_cols). keep_cols is
    None when the screen is off; when it is a mask, callers MUST also zero the
    screened columns out of their own downstream contractions (YΓ, Yᵀg·Γ):
    Γ's masked entries are exactly 0, but the contraction kernels run in f32
    where an overflowed byzantine column is ±inf and 0·inf = nan — the poison
    must never reach a matmul at all.
    """
    if cfg.clip_rtol > 0.0:
        keep_cols = _residual_clip_mask(gram, cfg)
        clipped = (gram.shape[0] - jnp.sum(keep_cols)).astype(jnp.int32)
        gamma, cond, used = _solve_gram(gram, rhs, cfg, keep_cols)
        return gamma, cond, used, clipped, keep_cols
    clipped = jnp.zeros((), jnp.int32)
    gamma, cond, used = _solve_gram(gram, rhs, cfg)
    return gamma, cond, used, clipped, None


def _mask_stack_columns(stack: Pytree, keep: jax.Array) -> Pytree:
    """Zero the non-kept history columns of a stacked pytree ([m, ...] leaves)."""
    return jax.tree.map(
        lambda l: jnp.where(
            keep.reshape((-1,) + (1,) * (l.ndim - 1)), l,
            jnp.zeros((), l.dtype)),
        stack)


#: legal values of the AA-step implementation knob (AlgoHParams.aa_impl)
AA_IMPLS = ("auto", "tree", "pallas")


def resolve_aa_impl(impl: str, runtime: str = "vmap") -> str:
    """Resolve the ``aa_impl`` knob to a concrete implementation.

    "auto" picks the fused Pallas kernels where they compile natively (TPU)
    and the pytree path elsewhere. The sharded runtime ALWAYS resolves to
    "tree" — its leaves may be sharded across the mesh, where leaf-wise
    contraction (see tree_math sharding notes) is the correct hot path —
    so an explicit "pallas" falls back without error, as documented.
    """
    if impl not in AA_IMPLS:
        raise ValueError(f"unknown aa_impl {impl!r}; choose from {AA_IMPLS}")
    if runtime == "sharded":
        return "tree"
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "tree"
    return impl


def multisecant_update(
    w: Pytree,
    g: Pytree,
    s_stack: Pytree,
    y_stack: Pytree,
    eta: float,
    cfg: AAConfig = AAConfig(),
    impl: str = "tree",
) -> tuple[Pytree, AAStats]:
    """FedOSAA's one-step AA update (Algorithm 1, lines 15–18).

    Args:
      w: anchor point w^t (pytree).
      g: the gradient the update is taken against — ∇f(w^t) for FedOSAA-SVRG,
         the server control variate c for FedOSAA-SCAFFOLD.
      s_stack / y_stack: histories with leading axis m:
         s_ℓ = w_{ℓ+1} − w_ℓ,  y_ℓ = r_{ℓ+1} − r_ℓ  (r = corrected gradients).
      eta: local learning rate η.
      impl: "tree" (leaf-wise tree_math contractions — streams S/Y three
         times, but keeps sharded leaves sharded), "pallas" (ravel into
         per-dtype flat buffers and run the single-pass fused Gram/update
         kernels from kernels/anderson — the vmap-runtime hot path), or
         "auto" (pallas on TPU, tree elsewhere).

    Returns (w⁺, stats) with
      w⁺ = w − η g − damping · (S − ηY) Γ + ... ,  Γ = (YᵀY)⁻¹ Yᵀ g.
    """
    with jax.named_scope("fl.aa_step"):
        if resolve_aa_impl(impl) == "pallas":
            return _multisecant_update_pallas(w, g, s_stack, y_stack, eta, cfg)
        gram = tm.tree_gram(y_stack, y_stack)          # [m, m] YᵀY
        yg = tm.tree_vdot_stacked(y_stack, g)          # [m]    Yᵀg
        gamma, cond, used, clipped, keep = _screened_solve(gram, yg, cfg)
        if keep is not None:
            y_stack = _mask_stack_columns(y_stack, keep)
            s_stack = _mask_stack_columns(s_stack, keep)
            yg = jnp.where(keep, yg, 0.0)

        # optimization gain θ² = 1 − (Yᵀg·Γ)/‖g‖²   (Eq. 9, via Pythagoras)
        g_norm2 = tm.tree_dot(g, g)
        proj2 = jnp.dot(yg, gamma)
        theta = jnp.sqrt(
            jnp.clip(1.0 - proj2 / jnp.maximum(g_norm2, 1e-30), 0.0, 1.0))

        s_gamma = tm.tree_combine_stacked(s_stack, gamma)   # S Γ
        y_gamma = tm.tree_combine_stacked(y_stack, gamma)   # Y Γ

        beta = cfg.damping
        new_w = jax.tree.map(
            lambda wi, gi, sg, yg_: wi - eta * gi - beta * (sg - eta * yg_),
            w, g, s_gamma, y_gamma,
        )
        stats = AAStats(theta=theta, gamma_norm=jnp.linalg.norm(gamma),
                        gram_cond=cond, used_columns=used,
                        clipped_columns=clipped)
        return new_w, stats


def _multisecant_update_pallas(
    w: Pytree, g: Pytree, s_stack: Pytree, y_stack: Pytree,
    eta: float, cfg: AAConfig,
) -> tuple[Pytree, AAStats]:
    """Fused AA step: same math and stats as the tree path, via the
    single-pass Pallas kernels on per-dtype flat buffers.

    The leaves are grouped by dtype and each group raveled once into a
    [m, d_g] buffer; the Gram system accumulates ACROSS groups (YᵀY is a sum
    over all components, so per-group Grams add exactly), the [m,m] solve —
    including Tikhonov/filtering, shared with the tree path via _solve_gram —
    happens once, and each group streams through the update kernel. S and Y
    are read once per pass instead of the tree path's three HBM sweeps.
    """
    from repro.kernels.anderson import ops

    w_leaves, treedef = jax.tree.flatten(w)
    g_leaves = jax.tree.leaves(g)
    s_leaves = jax.tree.leaves(s_stack)
    y_leaves = jax.tree.leaves(y_stack)
    m = y_leaves[0].shape[0]
    groups = ops.dtype_leaf_groups(w)
    # the Gram system accumulates in at least f32, f64 groups in f64 (as
    # tree_math does on the tree path)
    acc = jnp.result_type(jnp.float32, *(dt for dt, _ in groups))

    gram = jnp.zeros((m, m), acc)
    yg = jnp.zeros((m,), acc)
    g_norm2 = jnp.zeros((), acc)
    flats = []
    for _, idxs in groups:
        wf = ops.ravel_group(w_leaves, idxs)
        gf = ops.ravel_group(g_leaves, idxs)
        sf = ops.ravel_stack_group(s_leaves, idxs)
        yf = ops.ravel_stack_group(y_leaves, idxs)
        gm, ygv = ops.flat_gram(yf, gf)
        gram += gm.astype(acc)
        yg += ygv.astype(acc)
        ga = gf.astype(acc)
        g_norm2 += jnp.dot(ga, ga)
        flats.append((idxs, wf, gf, sf, yf))

    gamma, cond, used, clipped, keep = _screened_solve(gram, yg, cfg)
    if keep is not None:
        yg = jnp.where(keep, yg, 0.0)
    proj2 = jnp.dot(yg, gamma)
    theta = jnp.sqrt(jnp.clip(1.0 - proj2 / jnp.maximum(g_norm2, 1e-30), 0.0, 1.0))

    out_leaves = list(w_leaves)
    for idxs, wf, gf, sf, yf in flats:
        if keep is not None:
            # see _screened_solve: a screened column must not reach the f32
            # update matmul (0·inf = nan)
            sf = jnp.where(keep[:, None], sf, jnp.zeros((), sf.dtype))
            yf = jnp.where(keep[:, None], yf, jnp.zeros((), yf.dtype))
        of = ops.flat_update(wf, gf, sf, yf, gamma, eta, cfg.damping)
        ops.unravel_group_into(of, w_leaves, idxs, out_leaves)
    new_w = jax.tree.unflatten(treedef, out_leaves)
    stats = AAStats(theta=theta, gamma_norm=jnp.linalg.norm(gamma),
                    gram_cond=cond, used_columns=used,
                    clipped_columns=clipped)
    return new_w, stats


def aa_mixing_step(
    w_hist: Pytree,
    r_hist: Pytree,
    cfg: AAConfig = AAConfig(),
) -> tuple[Pytree, jax.Array]:
    """Classical AA mixing (Eq. 2–3) on stacked histories (newest first).

    w_hist, r_hist: pytrees with leading axis m+1 of iterates w^{t-i} and
    residuals r(w^{t-i}).  Solves the sum-to-one constrained LS for α, returns
      w⁺ = Σ αᵢ (w^{t-i} + r^{t-i})            and α.

    Provided for the property test asserting equivalence with
    ``multisecant_update`` (they are algebraically the same update), and as a
    reference implementation for readers of the paper.
    """
    # Reduce the constrained problem to an unconstrained one in differences:
    # α = e₀ − ... standard trick: with F = [r₀, …, r_m], minimize ‖F α‖ s.t.
    # Σα=1. Substitute α = e₀ + Dξ where D maps ξ∈R^m to differences.
    def diffs(stack):
        return jax.tree.map(lambda s: s[1:] - s[:-1], stack)   # [m, ...]

    dR = diffs(r_hist)   # rows: r^{t-i-1}−r^{t-i} ... sign convention immaterial
    r0 = tm.tree_unstack_index(r_hist, 0)
    gram = tm.tree_gram(dR, dR)
    rhs = tm.tree_vdot_stacked(dR, r0)
    xi, _, _ = _solve_gram(gram, rhs, cfg)
    # α₀ = 1 − Σ contributions handled implicitly:
    w0 = tm.tree_unstack_index(w_hist, 0)
    dW = diffs(w_hist)
    w_corr = tm.tree_combine_stacked(dW, xi)
    r_corr = tm.tree_combine_stacked(dR, xi)
    new_w = jax.tree.map(
        lambda wi, ri, wc, rc: wi + ri - (wc + rc), w0, r0, w_corr, r_corr
    )
    # recover alpha for diagnostics: α = e0 - scatter(xi diffs)
    m = xi.shape[0]
    alpha = jnp.zeros(m + 1).at[0].set(1.0)
    alpha = alpha.at[:-1].add(-xi).at[1:].add(xi)
    return new_w, alpha


def trajectory_to_sy(
    w_traj: Pytree, r_traj: Pytree, residual_ema: float = 0.0
) -> tuple[Pytree, Pytree]:
    """Build S, Y stacks from a local trajectory.

    w_traj: [L+1, ...] iterates w_{k,0..L};  r_traj: [L+1, ...] corrected
    gradients r_{k,0..L}.  Returns S, Y with leading axis L.

    residual_ema > 0 smooths the residual sequence with an exponential
    moving average before differencing (beyond-paper stabilizer for
    stochastic gradients; paper App. A / [28]).
    """
    if residual_ema > 0.0:
        rho = residual_ema

        def smooth(t):
            def step(prev, cur):
                new = rho * prev + (1 - rho) * cur
                return new, new
            _, smoothed = jax.lax.scan(step, t[0], t[1:])
            return jnp.concatenate([t[:1], smoothed], axis=0)

        r_traj = jax.tree.map(smooth, r_traj)
    s = jax.tree.map(lambda t: t[1:] - t[:-1], w_traj)
    y = jax.tree.map(lambda t: t[1:] - t[:-1], r_traj)
    return s, y


def lbfgs_two_loop(
    g: Pytree, s_stack: Pytree, y_stack: Pytree, eta: float
) -> Pytree:
    """Classic L-BFGS two-loop recursion over the SAME S/Y data FedOSAA uses.

    This is the paper's 'one-step L-BFGS' baseline (Appendix D.1): collect
    local points as in FedOSAA, then apply H_lbfgs⁻¹ to g. History axis is m,
    oldest first (index 0 = s_0 from the first local step).
    """
    m = jax.tree.leaves(s_stack)[0].shape[0]

    def si(i):
        return tm.tree_unstack_index(s_stack, i)

    def yi(i):
        return tm.tree_unstack_index(y_stack, i)

    q = g
    alphas = []
    rhos = []
    # first loop: newest -> oldest
    for i in range(m - 1, -1, -1):
        sy = tm.tree_dot(si(i), yi(i))
        rho = 1.0 / jnp.where(jnp.abs(sy) < 1e-30, jnp.inf, sy)
        a = rho * tm.tree_dot(si(i), q)
        q = tm.tree_axpy(-a, yi(i), q)
        alphas.append(a)
        rhos.append(rho)
    alphas.reverse()
    rhos.reverse()
    # initial Hessian scaling γ = s·y/y·y of the newest pair; fall back to η
    sy_last = tm.tree_dot(si(m - 1), yi(m - 1))
    yy_last = tm.tree_dot(yi(m - 1), yi(m - 1))
    gamma0 = jnp.where(yy_last > 1e-30, sy_last / jnp.maximum(yy_last, 1e-30), eta)
    r = tm.tree_scale(gamma0, q)
    # second loop: oldest -> newest
    for i in range(m):
        b = rhos[i] * tm.tree_dot(yi(i), r)
        r = tm.tree_axpy(alphas[i] - b, si(i), r)
    return r
