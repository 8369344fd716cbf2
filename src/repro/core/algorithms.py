"""Federated optimization algorithms (paper §2, §4, Appendix D.1).

Implemented, all under one jittable round API:

  fedavg            — McMahan et al. baseline (no correction)
  fedsvrg           — SVRG-corrected local steps (= FedLin)
  scaffold          — control-variate corrected local steps (paper's variant:
                      c = ∇f(w^{t-1}), c_k = ∇f_k(w^{t-1}))
  fedosaa_svrg      — THE PAPER: FedSVRG local steps + one AA step (Alg. 1)
  fedosaa_scaffold  — SCAFFOLD local steps + one AA step (Alg. 2)
  fedosaa_avg       — negative control (Appendix D.4): AA on uncorrected steps
  lbfgs             — one-step L-BFGS on the same S/Y data (App. D.1)
  giant             — local Newton-CG on the global gradient (Wang et al.)
  newton_gmres      — GIANT with GMRES in place of CG (= Newton-MINRES)
  dane              — exact local minimization of the DANE surrogate

Every round function has signature  round(state) -> (state, RoundMetrics)
and is a pure jax function: K clients are vmapped (stacked data), so a full
round is ONE XLA computation. The distributed runtime (core/sharded.py) runs
the SAME per-client bodies and round cores, but partitions the client axis
over the ("pod","data") mesh axes with shard_map and reduces via psum.

Layering (shared between the two runtimes):

  _client_*            per-client update bodies (one client's arrays in)
  _*_round_core        one round's cross-client math, written against a
                       CrossClientReduce so the SAME code runs under vmap
                       (plain reductions) and shard_map (psum reductions)
  make_round_fn        vmap runtime: prologue (rng/participation) + core
  make_sharded_round_fn(core/sharded.py): same prologue, core under shard_map
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import CommChannel, IDENTITY_CHANNEL, IdentityCodec, make_channel
from repro.comm.schema import (
    CTRL_UPLINK,
    DELTA_UPLINK,
    DIR_UPLINK,
    GRAD_UPLINK,
    UplinkSpec,
    init_schema_state,
    uplink_byte_breakdown,
    validate_schema,
)
from repro.core.anderson import (
    AAConfig,
    AAStats,
    lbfgs_two_loop,
    multisecant_update,
    resolve_aa_impl,
    trajectory_to_sy,
)
from repro.core.client_store import ClientStateStore
from repro.core.problem import (
    ClientBatch,
    FLProblem,
    sample_minibatch,
    sample_minibatch_indices,
)
from repro.utils import tree_math as tm

Pytree = Any

ALGORITHMS = (
    "fedavg", "fedsvrg", "scaffold",
    "fedosaa_svrg", "fedosaa_scaffold", "fedosaa_avg",
    "lbfgs", "giant", "newton_gmres", "dane",
)

class CommCost(NamedTuple):
    """Per-round communication accounting (paper Table 1).

    round_trips — synchronous server↔client exchanges per aggregation round.
      Methods needing the global gradient ∇f(w^t) before local work (SVRG
      family, L-BFGS, GIANT, Newton-GMRES, DANE) pay 2: one to collect local
      gradients, one to broadcast (w^t, ∇f) and collect results. FedAvg and
      SCAFFOLD piggyback everything on a single exchange.
    float_units — client-uplink floats per round, in units of d (the Table 1
      'cost' column): 1 for a model delta alone, 2 when a gradient or a
      control variate travels alongside it.
    """

    round_trips: int
    float_units: float

COMM_TABLE = {
    "fedavg":           CommCost(1, 1.0),
    "fedsvrg":          CommCost(2, 2.0),
    "scaffold":         CommCost(1, 2.0),
    "fedosaa_svrg":     CommCost(2, 2.0),
    "fedosaa_scaffold": CommCost(1, 2.0),
    "fedosaa_avg":      CommCost(1, 1.0),
    "lbfgs":            CommCost(2, 2.0),
    "giant":            CommCost(2, 2.0),
    "newton_gmres":     CommCost(2, 2.0),
    "dane":             CommCost(2, 2.0),
}


# --------------------------------------------------------------------------
# declarative uplink schemas (comm/schema.py)
#
# One UplinkSpec record per wire crossing of a round, in round order. The
# schema is what makes every algorithm's wire STATEFUL under a lossy channel:
# init_comm_state allocates exactly the buffers each record needs, and
# CrossClientReduce.uplink resolves error-feedback residuals and diff-coding
# references from ServerState.comm by the record's tag — uniformly, for the
# SVRG/SCAFFOLD families and the Newton family alike. A new algorithm gets a
# stateful wire by declaring its schema here; it cannot silently opt out.
# --------------------------------------------------------------------------

_SVRG_UPLINKS = validate_schema((GRAD_UPLINK, DELTA_UPLINK))
_SCAFFOLD_UPLINKS = validate_schema((DELTA_UPLINK, CTRL_UPLINK))
_AVG_UPLINKS = validate_schema((DELTA_UPLINK,))
_NEWTON_UPLINKS = validate_schema((GRAD_UPLINK, DIR_UPLINK))

UPLINK_SCHEMAS: "dict[str, tuple[UplinkSpec, ...]]" = {
    "fedavg":           _AVG_UPLINKS,
    "fedosaa_avg":      _AVG_UPLINKS,
    "fedsvrg":          _SVRG_UPLINKS,
    "fedosaa_svrg":     _SVRG_UPLINKS,
    "scaffold":         _SCAFFOLD_UPLINKS,
    "fedosaa_scaffold": _SCAFFOLD_UPLINKS,
    "lbfgs":            _SVRG_UPLINKS,
    "giant":            _NEWTON_UPLINKS,
    "newton_gmres":     _NEWTON_UPLINKS,
    "dane":             _SVRG_UPLINKS,
}

#: union of every tag — the allocation for algorithm-agnostic callers
#: (init_state(algo=None)); unused tags ride through rounds untouched
DEFAULT_SCHEMA = validate_schema(
    (GRAD_UPLINK, DELTA_UPLINK, CTRL_UPLINK, DIR_UPLINK))


def comm_floats_per_round(algo: str, d: int, line_search: bool = False) -> float:
    """Floats on the wire for one round of ``algo`` on a d-parameter model.

    The GIANT-style backtracking line search needs the *aggregated* direction
    p broadcast back to clients before the step size is chosen — one extra
    d-float downlink on top of the Table 1 units.
    """
    cost = COMM_TABLE[algo]
    extra = float(d) if (line_search and algo in ("giant", "newton_gmres")) else 0.0
    return cost.float_units * d + extra


def comm_bytes_per_round(algo: str, params: Pytree,
                         channel: "CommChannel | str | None" = None,
                         line_search: bool = False) -> float:
    """Bytes on the wire for one round of ``algo`` through ``channel``.

    Accounted from the algorithm's declarative uplink schema: each UplinkSpec
    is charged its codec-exact bytes at its kind's rate (int8 pays 1
    byte/value plus one f32 scale per chunk, topk pays 8 bytes per kept
    entry, aux uploads of a delta-only codec pay fp32 — repro/comm), plus the
    GIANT line-search extra broadcast at the downlink codec's rate.
    Per-client scalar uplinks (losses, AA stats) are ignored, as the paper's
    Table 1 ignores them; the schema lengths equal Table 1's float_units
    (asserted in tests), so the identity channel reproduces the historical
    counters exactly: bytes == 4 × comm_floats_per_round.
    """
    channel = make_channel(channel)
    total = sum(
        uplink_byte_breakdown(channel, UPLINK_SCHEMAS[algo], params).values())
    if line_search and algo in ("giant", "newton_gmres"):
        total += channel.downlink_bytes(params)
    return float(total)


@dataclasses.dataclass(frozen=True)
class AlgoHParams:
    """Tuning knobs shared by all algorithms (paper §4 / Appendix D.1)."""

    eta: float = 1.0            # local learning rate η
    local_epochs: int = 10      # L (== q CG/GMRES iterations for Newton-type)
    batch_size: int | None = None   # None => full-batch local gradients
    aa: AAConfig = AAConfig()
    line_search: bool = False   # GIANT-style global backtracking
    participation: float = 1.0  # fraction of clients active per round (ext.):
                                # < 1 samples a ⌈pK⌉-client cohort each round
                                # (resolve_cohort_size / _sample_cohort)
    cohort_size: int | None = None  # explicit per-round cohort size C: the
                                # round computes on C gathered clients over
                                # the K-sized ClientStateStore (O(C·d) round
                                # compute, O(K·d) store); None derives C from
                                # ``participation`` (full participation keeps
                                # the dense all-K path). Takes precedence
                                # over ``participation`` when both are set.
    carry_history: int = 0      # extra (s,y) columns carried ACROSS rounds
                                # (paper App. A option 1; FedOSAA-SVRG only)
    dane_newton_iters: int = 20
    dane_cg_iters: int = 100
    aa_impl: str = "auto"       # AA-step implementation: "tree" (leaf-wise
                                # tree_math), "pallas" (fused single-pass
                                # kernels on per-dtype flat buffers; vmap
                                # runtime only), "auto" (pallas on TPU).
                                # The sharded runtime always falls back to
                                # "tree" (see core/anderson.resolve_aa_impl).
    local_impl: str = "auto"    # local-trajectory implementation: "tree"
                                # (autodiff residuals — 2 loss autodiffs =
                                # 4 design-matrix sweeps per local step),
                                # "pallas" (fused dual-gradient kernels,
                                # kernels/local_update — ONE X sweep per
                                # step, at best fully VMEM-resident; only
                                # for linear-design models, see
                                # resolve_local_impl), "auto" (pallas on
                                # TPU where eligible). The sharded runtime
                                # always falls back to "tree", like aa_impl.


class ServerState(NamedTuple):
    params: Pytree
    c: Pytree        # server control variate (SCAFFOLD family; zeros otherwise)
    c_k: Pytree      # [K, ...] client control variates
    t: jax.Array
    rng: jax.Array
    hist_s: Pytree = None   # [K, H, ...] carried AA columns (App. A opt. 1)
    hist_y: Pytree = None
    comm: Pytree = None     # client-side wire-compression state (repro/comm):
                            # {tag: {...}} keyed by the algorithm's uplink
                            # schema (UPLINK_SCHEMAS), per-client [K, ...]
                            # buffers per tag —
                            #   "ef":  error-feedback residuals, re-injected
                            #          into the next upload (lossy codecs)
                            #   "ref": difference-coding reference for
                            #          absolute-state ("aux") uploads
                            #          (gradients, control variates): the
                            #          wire carries g_k − h_k so quantization
                            #          noise decays with the diff instead of
                            #          staying O(1)


class RoundMetrics(NamedTuple):
    loss: jax.Array          # global f(w^t) before the update
    grad_norm: jax.Array     # ‖∇f(w^t)‖ (or control-variate norm for scaffold)
    theta_mean: jax.Array    # mean AA optimization gain across clients (nan if n/a)
    gram_cond_max: jax.Array # worst AA Gram conditioning (nan if n/a)
    gram_cond_mean: jax.Array  # mean AA Gram conditioning (nan if n/a)
    aa_used_min: jax.Array   # fewest AA columns surviving filtering on any
                             # client (nan if n/a; 0 = filtering collapse)
    aa_clipped_max: jax.Array  # most history columns the clip_rtol byzantine
                             # screen dropped on any client (nan if n/a;
                             # 0 whenever the screen is off or inactive)
    cohort_ess: jax.Array    # effective sample size 1/Σw² of the round's
                             # aggregation weights (== C for a uniform cohort)
    comm_bytes: jax.Array    # bytes on the wire this round (codec-exact;
                             # == 4 × Table 1 float units on the fp32 channel)
    arrivals: jax.Array      # deadline-gated rounds: clients whose update
                             # landed this round, fresh or buffered (nan when
                             # AsyncConfig is off — the barriered round)
    staleness_mean: jax.Array  # mean buffer age over this round's landed
                             # contributions, fresh counting as 0 (nan when
                             # async is off or nothing landed)
    staleness_max: jax.Array   # oldest landed contribution's buffer age (nan
                             # when async is off or nothing landed); feeds the
                             # staleness_runaway alarm


# a host span: a run's first state is a dozen small eager dispatches
@partial(jax.profiler.annotate_function, name="fl.init_state")
def init_state(problem: FLProblem, rng: jax.Array,
               hp: "AlgoHParams | None" = None,
               channel: "CommChannel | str | None" = None,
               algo: str | None = None) -> ServerState:
    rng, init_rng = jax.random.split(rng)
    params = problem.init(init_rng)
    zeros = tm.tree_zeros_like(params)
    K = problem.clients.num_clients
    c_k = jax.tree.map(lambda z: jnp.zeros((K,) + z.shape, z.dtype), zeros)
    hist_s = hist_y = None
    if hp is not None and hp.carry_history > 0:
        H = hp.carry_history
        hist_s = jax.tree.map(
            lambda z: jnp.zeros((K, H) + z.shape, z.dtype), zeros)
        hist_y = jax.tree.map(
            lambda z: jnp.zeros((K, H) + z.shape, z.dtype), zeros)
    channel = make_channel(channel)
    comm = init_comm_state(channel, params, K, algo)
    return ServerState(params, zeros, c_k, jnp.zeros((), jnp.int32), rng,
                       hist_s, hist_y, comm)


def init_comm_state(channel: CommChannel, params: Pytree, K: int,
                    algo: str | None = None) -> Pytree:
    """Per-client carried comm state, allocated from the algorithm's
    declarative uplink schema (None when no uplink carries buffers).

    See ServerState.comm. ``algo`` selects its UPLINK_SCHEMAS entry so
    buffers its round function never reads are not allocated — the AVG family
    has no aux uplink, the Newton family carries "grad"/"dir" instead of
    "grad"/"delta"; at LM scale each skipped buffer is a K×d array.
    ``algo=None`` allocates the union DEFAULT_SCHEMA for algorithm-agnostic
    callers. The store is allocated ONCE at K; a cohort round (participation
    < 1 or an explicit ``cohort_size``) gathers only its C sampled rows into
    the compiled round body and scatters the updated rows back, so a client
    outside the cohort keeps its error-feedback residual / diff-coding
    reference bit-frozen — exactly the offline-client semantics of a real
    deployment (pinned in tests/test_cohort.py).
    """
    schema = DEFAULT_SCHEMA if algo is None else UPLINK_SCHEMAS[algo]
    return init_schema_state(channel, schema, params, K)


# --------------------------------------------------------------------------
# local trajectories
# --------------------------------------------------------------------------

#: legal values of the local-trajectory implementation knob
#: (AlgoHParams.local_impl)
LOCAL_IMPLS = ("auto", "tree", "pallas")

#: private, benchmark-only value: the SEED driver's trajectory form
#: (pre-PR5 L-step scan + standalone r_L dispatch + per-leaf concatenate
#: epilogue). bench_round.py's seed_loop mode replays it so the committed
#: "vs seed" timings stay comparable across PRs; bit-identical VALUES to
#: the folded scan, deliberately not in LOCAL_IMPLS.
LOCAL_IMPL_SEED = "tree_seed"

#: algorithms whose local work is the L-step corrected-GD trajectory — the
#: only ones the fused kernels apply to (the Newton family runs CG/GMRES
#: matvecs, not a trajectory)
TRAJECTORY_ALGOS = ("fedavg", "fedsvrg", "scaffold", "fedosaa_svrg",
                    "fedosaa_scaffold", "fedosaa_avg", "lbfgs")


def fused_local_eligible(problem: FLProblem, algo: str | None = None,
                         params: Pytree | None = None) -> bool:
    """Can ``algo`` on ``problem`` run the fused local-trajectory kernels?

    Requires the model to declare the linear-design protocol
    (FLProblem.linear_design — logreg/linreg do, MLP/decoder do not), the
    params pytree to BE a single flat [d] array (not merely contain one —
    the fused path returns [steps, d] arrays in the params' structure), and
    a trajectory-based algorithm. Everything else keeps the autodiff path.
    """
    if problem.linear_design is None:
        return False
    if algo is not None and algo not in TRAJECTORY_ALGOS:
        return False
    if params is None:
        params = problem.init(jax.random.PRNGKey(0))
    return isinstance(params, jax.Array) and params.ndim == 1


def resolve_local_impl(impl: str, runtime: str = "vmap",
                       problem: FLProblem | None = None,
                       algo: str | None = None,
                       params: Pytree | None = None) -> str:
    """Resolve the ``local_impl`` knob to a concrete "tree"/"pallas".

    Mirrors core/anderson.resolve_aa_impl: "auto" picks the fused path
    where the kernels compile natively (TPU) and the autodiff path
    elsewhere; the sharded runtime ALWAYS resolves to "tree" (client data
    shards stay put; the fused ravel assumes whole per-client designs), and
    an ineligible problem/algorithm (see fused_local_eligible) falls back
    to "tree" without error, as documented — so MLP/decoder and the Newton
    family simply keep autodiff even under an explicit "pallas".
    """
    if impl not in LOCAL_IMPLS + (LOCAL_IMPL_SEED,):
        raise ValueError(f"unknown local_impl {impl!r}; choose from {LOCAL_IMPLS}")
    if impl == LOCAL_IMPL_SEED:   # benchmark-only seed replay, any runtime
        return impl
    if runtime == "sharded" or impl == "tree":
        return "tree"
    if problem is not None and not fused_local_eligible(problem, algo, params):
        return "tree"
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "tree"
    return impl


def _local_trajectory(
    hp: AlgoHParams,
    w0: Pytree,
    residual_fn: Callable[[Pytree, jax.Array], Pytree],
    rng: jax.Array,
    r0: Pytree | None = None,
):
    """Run L corrected-GD steps from w0 and return the full trajectory.

    Returns (w_traj, r_traj) with leading axis L+1 — FedOSAA evaluates L+1
    gradients (Alg. 1 needs r_L for the last Y column). One scan over L+1
    step keys emits every (w_ℓ, r_ℓ) pair directly: the final residual is
    just the last scan iteration (its unused w_{L+1} is a single axpy), so
    there is no per-leaf concatenate epilogue and no standalone r_L
    dispatch in either runtime.

    ``r0`` — the residual at w0 when the caller already holds it (full-batch
    SVRG: from the anchor gradient): the steps then start at w_1 and
    evaluate L gradients, not L+1, and (w0, r0) heads the trajectory.
    """
    rngs = jax.random.split(rng, hp.local_epochs + 1)

    def step(w, step_rng):
        r = residual_fn(w, step_rng)
        # the residual may carry f32 terms (the aggregated global gradient)
        # into bf16 params; the iterate keeps the params' dtype
        w_next = jax.tree.map(lambda a, b: a.astype(b.dtype),
                              tm.tree_axpy(-hp.eta, r, w), w)
        return w_next, (w, r)

    def steps(w, keys):
        if hp.local_impl != LOCAL_IMPL_SEED:
            _, traj = jax.lax.scan(step, w, keys)
            return traj
        # the seed form, replayed for bench_round's baseline: the scan stops
        # one short, the last residual dispatches standalone, the history is
        # concatenated per leaf
        w_last, (w_hist, r_hist) = jax.lax.scan(step, w, keys[:-1])
        r_last = residual_fn(w_last, keys[-1])
        append = lambda hist, last: jnp.concatenate([hist, last[None]], axis=0)
        return (jax.tree.map(append, w_hist, w_last),
                jax.tree.map(append, r_hist, r_last))

    with jax.named_scope("fl.local_trajectory"):
        if r0 is None:
            return steps(w0, rngs)
        w1 = jax.tree.map(lambda a, b: a.astype(b.dtype),
                          tm.tree_axpy(-hp.eta, r0, w0), w0)
        w_rest, r_rest = steps(w1, rngs[1:])
        head = lambda t0, rest: jnp.concatenate(
            [t0.astype(rest.dtype)[None], rest], axis=0)
        return (jax.tree.map(head, w0, w_rest),
                jax.tree.map(head, r0, r_rest))


def _fused_trajectory(
    problem: FLProblem,
    hp: AlgoHParams,
    w0: Pytree,
    batch: ClientBatch,
    anchor_scale: float,
    corr: Pytree | None,
    rng: jax.Array,
):
    """The fused linear-design twin of _local_trajectory
    (kernels/local_update): both residual gradients of every local step ride
    ONE design-matrix sweep, with the L-step loop VMEM-resident when the
    client's block fits.

    The residual family is r(w;ζ) = ∇f_k(w;ζ) − a·∇f_k(w^t;ζ) + corr, which
    in linear-design form collapses to Xᵀ(c(Xw) − a·c(Xw^t))/n + reg·w + u
    with u = corr − a·reg·w^t:  a=1/corr=∇f(w^t) is the SVRG family,
    a=0/corr=c−c_k is SCAFFOLD, a=0/corr=None is FedAvg. Minibatch mode
    draws the bit-identical per-step row gathers the autodiff path draws
    (sample_minibatch_indices) and evaluates live and anchor on the same
    rows, exactly like _make_residual_fn.
    """
    from repro.kernels.local_update import fused_trajectory

    design = problem.linear_design(batch)
    steps = hp.local_epochs + 1
    if hp.batch_size is None:
        x, y, mask = design.x[None], design.y[None], batch.mask[None]
    else:
        rngs = jax.random.split(rng, steps)
        idx = jax.vmap(
            lambda r: sample_minibatch_indices(batch.mask, r, hp.batch_size)
        )(rngs)
        x, y = design.x[idx], design.y[idx]
        mask = jnp.ones(idx.shape, batch.mask.dtype)
    u = tm.tree_zeros_like(w0) if corr is None else corr
    if anchor_scale:
        u = u - design.reg * w0
    with jax.named_scope("fl.local_trajectory"):
        return fused_trajectory(
            x, y, mask, w0, u, link=design.link, reg=design.reg, eta=hp.eta,
            anchor_scale=anchor_scale, steps=steps)


def _make_residual_fn(
    problem: FLProblem, hp: AlgoHParams, batch: ClientBatch, correction: Pytree | None
):
    """r(w; ζ) = ∇f_k(w; ζ) + correction(ζ).

    correction is either
      * a pytree  (SCAFFOLD: c − c_k — minibatch independent), or
      * a callable (w_anchor-based SVRG term: −∇f_k(w^t;ζ) + ∇f(w^t)), or
      * None (FedAvg).
    """
    def residual(w, rng):
        if hp.batch_size is None:
            mb = batch
        else:
            mb = sample_minibatch(batch, rng, hp.batch_size)
        g = problem.grad(w, mb)
        if correction is None:
            return g
        if callable(correction):
            return tm.tree_add(g, correction(mb))
        return tm.tree_add(g, correction)

    return residual


# --------------------------------------------------------------------------
# per-client updates (to be vmapped over the stacked client axis)
# --------------------------------------------------------------------------

def _svrg_trajectory(problem, hp, w_t, g_global, batch, rng, g_anchor=None):
    """SVRG-corrected trajectory: fused dual-gradient kernels when resolved,
    else the autodiff residual path. ``g_anchor`` is the client's own
    full-batch gradient at w^t (the round's anchor gradient, before the
    wire; computed here when not given): with full batches the correction
    ∇f(w^t) − ∇f_k(w^t) is then one constant, the residual at w^t is
    ∇f(w^t) itself, and the trajectory costs L gradient evaluations. A
    minibatch step corrects with the anchor gradient on its own minibatch
    ζ, two evaluations a step."""
    if hp.local_impl == "pallas":
        return _fused_trajectory(problem, hp, w_t, batch, 1.0, g_global, rng)
    if hp.batch_size is None:
        if g_anchor is None:
            g_anchor = problem.grad(w_t, batch)
        correction = tm.tree_sub(g_global, g_anchor)
        residual_fn = _make_residual_fn(problem, hp, batch, correction)
        # r_0 summed as every later residual is, not taken as ∇f(w^t): the
        # AA Gram solve at f32 turns a last-ulp change into another run
        r0 = tm.tree_add(g_anchor, correction)
        return _local_trajectory(hp, w_t, residual_fn, rng, r0=r0)

    def svrg_correction(mb):
        # −∇f_k(w^t; ζ) + ∇f(w^t): the SAME minibatch ζ as the live gradient.
        return tm.tree_sub(g_global, problem.grad(w_t, mb))

    residual_fn = _make_residual_fn(problem, hp, batch, svrg_correction)
    return _local_trajectory(hp, w_t, residual_fn, rng)


def _client_svrg(problem, hp, use_aa, w_t, g_global, x, y, mask, rng,
                 g_anchor, hist_s=None, hist_y=None, poison=None):
    batch = ClientBatch(x, y, mask)
    w_traj, r_traj = _svrg_trajectory(problem, hp, w_t, g_global, batch, rng,
                                      g_anchor)
    nan_st = AAStats(jnp.nan, jnp.nan, jnp.nan, jnp.array(0), jnp.array(0))
    if not use_aa:
        w_k = jax.tree.map(lambda t: t[-1], w_traj)
        return (w_k, nan_st) if hist_s is None else (w_k, nan_st, hist_s, hist_y)
    s, y_stack = trajectory_to_sy(w_traj, r_traj, hp.aa.residual_ema)
    if poison is not None:
        # byzantine history fault (robust/faults.py, byz_mode="history"):
        # the client's dynamics ran clean but the recorded last residual
        # column is corrupted — injected AFTER the trajectory so exactly one
        # column is poisoned, the regime the clip_rtol screen defends (a
        # mid-flight corruption would propagate through the remaining local
        # steps and poison a majority of columns, defeating any per-client
        # median statistic)
        from repro.robust.faults import poison_last_column
        flag, fkey, scale = poison
        y_stack = poison_last_column(y_stack, flag, fkey, scale)
    if hist_s is not None:
        # App. A option 1: prepend columns carried from previous rounds
        # (stale anchors — valid secant pairs of nearby Jacobians; the
        # filtered/regularized LS solve absorbs the inconsistency)
        s_all = jax.tree.map(lambda h, f: jnp.concatenate([h, f], 0), hist_s, s)
        y_all = jax.tree.map(lambda h, f: jnp.concatenate([h, f], 0), hist_y, y_stack)
        w_k, stats = multisecant_update(w_t, g_global, s_all, y_all, hp.eta,
                                        hp.aa, impl=hp.aa_impl)
        Hn = hp.carry_history
        new_hs = jax.tree.map(lambda f: f[-Hn:], s)
        new_hy = jax.tree.map(lambda f: f[-Hn:], y_stack)
        return w_k, stats, new_hs, new_hy
    w_k, stats = multisecant_update(w_t, g_global, s, y_stack, hp.eta, hp.aa,
                                    impl=hp.aa_impl)
    return w_k, stats


def _client_scaffold(problem, hp, use_aa, w_t, c, x, y, mask, c_k, rng):
    batch = ClientBatch(x, y, mask)
    correction = tm.tree_sub(c, c_k)
    if hp.local_impl == "pallas":
        w_traj, r_traj = _fused_trajectory(problem, hp, w_t, batch, 0.0,
                                           correction, rng)
    else:
        residual_fn = _make_residual_fn(problem, hp, batch, correction)
        w_traj, r_traj = _local_trajectory(hp, w_t, residual_fn, rng)
    if use_aa:
        s, y_stack = trajectory_to_sy(w_traj, r_traj, hp.aa.residual_ema)
        w_k, stats = multisecant_update(w_t, c, s, y_stack, hp.eta, hp.aa,
                                        impl=hp.aa_impl)
    else:
        w_k = jax.tree.map(lambda t: t[-1], w_traj)
        stats = AAStats(jnp.nan, jnp.nan, jnp.nan, jnp.array(0), jnp.array(0))
    new_c_k = problem.grad(w_t, batch)     # c_k ← ∇f_k(w^t), full batch (Alg. 2)
    return w_k, new_c_k, stats


def _client_avg(problem, hp, use_aa, w_t, x, y, mask, rng):
    batch = ClientBatch(x, y, mask)
    if hp.local_impl == "pallas":
        w_traj, r_traj = _fused_trajectory(problem, hp, w_t, batch, 0.0,
                                           None, rng)
    else:
        residual_fn = _make_residual_fn(problem, hp, batch, None)
        w_traj, r_traj = _local_trajectory(hp, w_t, residual_fn, rng)
    if not use_aa:
        w_k = jax.tree.map(lambda t: t[-1], w_traj)
        return w_k, AAStats(jnp.nan, jnp.nan, jnp.nan, jnp.array(0), jnp.array(0))
    s, y_stack = trajectory_to_sy(w_traj, r_traj)
    # negative control: AA against the LOCAL gradient (no correction exists)
    g_local = jax.tree.map(lambda t: t[0], r_traj)
    w_k, stats = multisecant_update(w_t, g_local, s, y_stack, hp.eta, hp.aa,
                                    impl=hp.aa_impl)
    return w_k, stats


def _client_lbfgs(problem, hp, w_t, g_global, x, y, mask, rng, g_anchor):
    batch = ClientBatch(x, y, mask)
    w_traj, r_traj = _svrg_trajectory(problem, hp, w_t, g_global, batch, rng,
                                      g_anchor)
    s, y_stack = trajectory_to_sy(w_traj, r_traj)
    direction = lbfgs_two_loop(g_global, s, y_stack, hp.eta)
    w_k = tm.tree_sub(w_t, direction)
    return w_k, AAStats(jnp.nan, jnp.nan, jnp.nan, jnp.array(0), jnp.array(0))


def _cg_solve(matvec, b, iters: int):
    """Plain CG on a pytree SPD system, fixed iteration count (GIANT's q)."""
    x = tm.tree_zeros_like(b)
    r = b
    p = r
    rs = tm.tree_dot(r, r)

    def body(_, carry):
        x, r, p, rs = carry
        ap = matvec(p)
        denom = tm.tree_dot(p, ap)
        alpha = rs / jnp.maximum(denom, 1e-30)
        x = tm.tree_axpy(alpha, p, x)
        r = tm.tree_axpy(-alpha, ap, r)
        rs_new = tm.tree_dot(r, r)
        beta = rs_new / jnp.maximum(rs, 1e-30)
        p = tm.tree_axpy(beta, p, r)
        return x, r, p, rs_new

    x, _, _, _ = jax.lax.fori_loop(0, iters, body, (x, r, p, rs))
    return x


def _client_giant(problem, hp, w_t, g_global, x, y, mask):
    batch = ClientBatch(x, y, mask)
    matvec = lambda v: problem.hvp(w_t, batch, v)
    p_k = _cg_solve(matvec, g_global, hp.local_epochs)
    return p_k


def _client_newton_gmres(problem, hp, w_t, g_global, x, y, mask):
    batch = ClientBatch(x, y, mask)
    matvec = lambda v: problem.hvp(w_t, batch, v)
    p_k, _ = jax.scipy.sparse.linalg.gmres(
        matvec, g_global, maxiter=1, restart=hp.local_epochs, tol=0.0,
        solve_method="incremental",
    )
    return p_k


def _client_dane(problem, hp, w_t, g_global, x, y, mask):
    """Exact local minimization of h_k(w)=f_k(w) − <∇f_k(w^t) − ∇f(w^t), w>
    via damped Newton with backtracking (App. D.1: 'no tuning parameter')."""
    batch = ClientBatch(x, y, mask)
    g_k_t = problem.grad(w_t, batch)
    shift = tm.tree_sub(g_k_t, g_global)        # ∇h_k = ∇f_k(w) − shift

    def h_val(w):
        return problem.loss(w, batch) - tm.tree_dot(shift, w)

    def h_grad(w):
        return tm.tree_sub(problem.grad(w, batch), shift)

    def newton_step(w, _):
        g = h_grad(w)
        matvec = lambda v: problem.hvp(w, batch, v)
        p = _cg_solve(matvec, g, hp.dane_cg_iters)
        # backtracking on h along p
        f0 = h_val(w)
        gTp = tm.tree_dot(g, p)

        def try_step(a):
            return h_val(tm.tree_axpy(-a, p, w))

        steps = jnp.array([1.0, 0.5, 0.25, 0.125, 0.0625])
        vals = jnp.stack([try_step(a) for a in steps])
        ok = vals < f0 - 1e-4 * steps * gTp
        idx = jnp.argmax(ok)          # first satisfying Armijo; 0 if none true
        a = jnp.where(jnp.any(ok), steps[idx], 0.0)
        return tm.tree_axpy(-a, p, w), None

    w_k, _ = jax.lax.scan(newton_step, w_t, None, length=hp.dane_newton_iters)
    return w_k


# --------------------------------------------------------------------------
# cohort sampling (extension: partial client participation as the MEMORY
# model, not just an aggregation mask)
#
# A round with C < K computes on a sampled cohort: client data, rng keys and
# the per-client state rows (ClientStateStore: control variates, carried AA
# columns, comm buffers) are GATHERED to [C, ...] before the round core runs,
# and the updated rows are SCATTERED back afterwards — non-sampled clients'
# state is bit-frozen and the compiled round touches O(C·d), not O(K·d).
# The historical dense path (every client computes, which full participation
# still uses) remains the csize=None branch of _plan_round.
# --------------------------------------------------------------------------

def resolve_cohort_size(hp: AlgoHParams, num_clients: int) -> int | None:
    """The per-round cohort size C, or None for the dense full-K path.

    An explicit ``hp.cohort_size`` always wins (C == K still runs the
    cohort gather/scatter machinery — the identity cohort, bit-identical to
    the dense path and pinned so in tests/test_cohort.py). Otherwise
    ``participation < 1`` derives C = max(1, round(p·K)): a fixed-size
    weighted draw without replacement, replacing the historical Bernoulli
    mask whose inactive clients still computed (and, worse, still advanced
    their comm buffers — the wart init_comm_state used to document).
    """
    if hp.cohort_size is not None:
        c = int(hp.cohort_size)
        if not 1 <= c <= num_clients:
            raise ValueError(
                f"cohort_size={c} must be in [1, num_clients={num_clients}]")
        return c
    if hp.participation >= 1.0:
        return None
    return max(1, int(round(hp.participation * num_clients)))


def _sample_cohort(weight: jax.Array, cohort_size: int, rng: jax.Array):
    """Draw the round's cohort: ([C] indices, [C] renormalized weights).

    Sampling is without replacement, data-size weighted (p ∝ N_k/N), and the
    drawn weights renormalize to sum 1 so the delta-form aggregation stays
    exact. C == K short-circuits to the identity cohort with the RAW
    weights — renormalizing would perturb the last ulp and break the
    bit-identity of the C=K path with the dense path.
    """
    K = weight.shape[0]
    if cohort_size >= K:
        return jnp.arange(K), weight
    idx = jax.random.choice(rng, K, shape=(cohort_size,), replace=False,
                            p=weight)
    cw = weight[idx]
    return idx, cw / jnp.maximum(jnp.sum(cw), 1e-30)


class CohortPlan(NamedTuple):
    """One round's resolved client axis: the [C, ...] views the round core
    consumes plus what the epilogue needs to scatter updates back."""

    idx: jax.Array | None    # [C] cohort indices; None = dense full-K round
    x: jax.Array             # [C, ...] client data views
    y: jax.Array
    mask: jax.Array
    dweight: jax.Array       # [C] reduction weights (losses, global grad)
    pweight: jax.Array       # [C] aggregation weights for the model update
    rngs: jax.Array          # [C, 2] per-client round keys
    store: ClientStateStore  # the FULL K-sized store (scatter target)
    cohort: ClientStateStore # the gathered [C, ...] rows the core reads


def _plan_round(problem: FLProblem, csize: int | None, state: ServerState,
                part_rng: jax.Array, rngs_K: jax.Array) -> CohortPlan:
    """Resolve the round's client axis.

    Dense (csize None): the full stacks and store pass through untouched —
    byte-for-byte the historical round. Cohort: sample C indices, gather
    data + state rows + the C of the K prologue-split client keys
    (``rngs_K[idx]``, NOT a fresh split — cohort client k sees the same key
    the dense path would hand client k, which is what makes the masked-dense
    equivalence in tests/test_cohort.py exact per client).
    """
    C = problem.clients
    store = ClientStateStore.from_state(state)
    if csize is None:
        return CohortPlan(None, C.x, C.y, C.mask, C.weight, C.weight, rngs_K,
                          store, store)
    with jax.named_scope("fl.cohort_plan"):
        idx, cw = _sample_cohort(C.weight, csize, part_rng)
    if csize >= C.num_clients:
        # identity cohort (C == K): gathers at arange are value-identical but
        # perturb XLA fusion by an ulp, which the ill-conditioned AA Gram
        # solve amplifies — so the original arrays ARE the cohort view. The
        # scatter epilogue still runs (an exact write of the computed rows,
        # bit-safe), keeping the commit machinery under test.
        return CohortPlan(idx, C.x, C.y, C.mask, cw, cw, rngs_K, store, store)
    with jax.named_scope("fl.cohort_gather"):
        return CohortPlan(idx, C.x[idx], C.y[idx], C.mask[idx], cw, cw,
                          rngs_K[idx], store, store.gather(idx))


def _commit_plan(plan: CohortPlan, **updates) -> dict:
    """ServerState field updates from a round core's per-client outputs.

    Dense: passed through unchanged. Cohort: the [C, ...] rows scatter into
    the K-sized store — rows outside the cohort are bit-frozen, and fields
    the core did not touch (None here) emit no scatter op at all.
    """
    if plan.idx is None:
        return updates
    rows = ClientStateStore(
        c_k=updates.get("c_k"), hist_s=updates.get("hist_s"),
        hist_y=updates.get("hist_y"), comm=updates.get("comm"))
    with jax.named_scope("fl.scatter"):
        new = plan.store.scatter(plan.idx, rows)
    return {k: getattr(new, k) for k in updates}


def _aggregate(weights: jax.Array, stacked: Pytree, anchor: Pytree | None = None) -> Pytree:
    """Σ_k weights_k · stacked_k.

    When ``anchor`` is given, uses the delta form anchor + Σ w_k(x_k − anchor):
    identical when Σweights = 1, and degrades to a no-op (instead of zeroing
    the model) if a partial-participation round draws no clients. The sum
    accumulates in at least the weights' f32 and returns in each leaf's own
    dtype, so bf16 params stay bf16.
    """
    if anchor is None:
        return jax.tree.map(
            lambda s: jnp.tensordot(weights, s, axes=1).astype(s.dtype),
            stacked)
    return jax.tree.map(
        lambda a, s: (a + jnp.tensordot(weights, s - a[None], axes=1)
                      ).astype(a.dtype),
        anchor, stacked)


class CrossClientReduce:
    """Cross-client reductions + the comm channel, single-process (vmap) runtime.

    The round cores below are written against this interface so the identical
    code runs distributed: core/sharded.py subclasses it to reduce each
    shard's partial result with psum/pmax over the ("pod","data") mesh axes.
    On a 1-device mesh the psum is an identity, so the two runtimes agree
    bit-for-bit.

    The channel methods (``uplink``/``broadcast``) simulate the wire: every
    client→server quantity passes an encode/decode roundtrip BEFORE the
    cross-client reduction (so the psum in the sharded runtime reduces
    dequantized values), and every server→client broadcast passes the
    (deterministic) downlink codec. They are per-client local ops — no
    collective inside — so the shared implementation serves both runtimes.
    """

    def __init__(self, channel: CommChannel | None = None):
        self.channel = channel if channel is not None else IDENTITY_CHANNEL

    def wsum(self, weights: jax.Array, stacked: Pytree,
             anchor: Pytree | None = None) -> Pytree:
        """Σ_k weights_k · stacked_k over every client (all shards)."""
        return _aggregate(weights, stacked, anchor)

    def nanmean(self, x: jax.Array) -> jax.Array:
        """Mean of the non-nan entries of a per-client vector; nan if none."""
        return jnp.nanmean(x)

    def nanmax(self, x: jax.Array) -> jax.Array:
        """Max of the non-nan entries of a per-client vector; nan if none."""
        return jnp.nanmax(x)

    def nanmin(self, x: jax.Array) -> jax.Array:
        """Min of the non-nan entries of a per-client vector; nan if none."""
        return jnp.nanmin(x)

    def ess(self, weights: jax.Array) -> jax.Array:
        """Effective sample size 1/Σw² of the per-client reduction weights
        (== C for a uniform C-client cohort; 1 when one client dominates)."""
        return 1.0 / jnp.maximum(jnp.sum(weights * weights), 1e-30)

    # ---- the wire ----------------------------------------------------------
    def uplink(self, stacked: Pytree, rngs: jax.Array, spec: UplinkSpec,
               anchor: Pytree | None = None, state: Pytree | None = None,
               post_codec=None, post_rngs: jax.Array | None = None):
        """Channel roundtrip of every client's upload, declared by ``spec``.

        The wire quantity is ``stacked_k − anchor`` for anchored specs (model
        uploads travel as deltas — that is what the codecs' relative scaling
        assumes), else ``stacked_k`` itself, further re-based on the carried
        reference ``state[spec.tag]["ref"]`` when present (difference coding:
        the wire carries v_k − h_k, both ends advance h_k by the decoded
        diff). ``state[spec.tag]["ef"]`` is the error-feedback residual,
        added before encoding, with the new residual carried forward. rngs
        are the per-client round keys; ``spec.fold`` is folded in so distinct
        uploads of one round never share draws.

        ``state`` is the WHOLE ServerState.comm dict (or None): the spec's
        tag selects its buffers, tags an algorithm's round never uplinks pass
        through untouched. Returns (reconstructed stacked — the server's
        view, the comm dict with this tag's buffers advanced).

        ``post_codec(dec_k, post_rngs_k)`` — when given — transforms each
        client's DECODED wire value after the codec roundtrip and BEFORE the
        error-feedback residual is taken, so EF and difference-coding
        references track the transformed wire (this is how the robustness
        layer composes client-side DP noise with the codecs: the client adds
        calibrated noise to its payload, so both ends see the noised stream).
        """
        if spec.anchored != (anchor is not None):
            raise ValueError(
                f"uplink {spec.tag!r}: anchored={spec.anchored} but anchor "
                f"{'missing' if anchor is None else 'given'}")
        codec = self.channel.up_codec(spec.kind)
        if isinstance(codec, IdentityCodec) and post_codec is None:
            return stacked, state
        sub = state.get(spec.tag) if state is not None else None
        if not codec.deterministic:
            rngs = jax.vmap(lambda r: jax.random.fold_in(r, spec.fold))(rngs)
        ef = sub.get("ef") if sub else None
        ref = sub.get("ref") if sub else None

        def one(w_k, rng, e, h, pr):
            v = tm.tree_sub(w_k, anchor) if anchor is not None else w_k
            if h is not None:
                v = tm.tree_sub(v, h)
            if e is not None:
                v = tm.tree_add(v, e)
            dec = codec.tree_roundtrip(v, rng)
            if post_codec is not None:
                dec = post_codec(dec, pr)
            new_e = tm.tree_sub(v, dec) if e is not None else None
            if h is not None:
                # h tracks the reconstructed stream on BOTH ends of the wire
                dec = tm.tree_add(dec, h)
            new_h = dec if h is not None else None
            if anchor is not None:
                dec = tm.tree_add(dec, anchor)
            return dec, new_e, new_h

        with jax.named_scope("fl.uplink"):
            dec, new_e, new_h = jax.vmap(one)(stacked, rngs, ef, ref,
                                              post_rngs)
        if not sub:
            return dec, state
        new_sub = {}
        if "ef" in sub:
            new_sub["ef"] = new_e
        if "ref" in sub:
            new_sub["ref"] = new_h
        return dec, {**state, spec.tag: new_sub}

    def broadcast(self, tree: Pytree) -> Pytree:
        """Server→client broadcast through the (deterministic) downlink codec."""
        if isinstance(self.channel.down, IdentityCodec):
            return tree
        return self.channel.broadcast(tree)


VMAP_REDUCE = CrossClientReduce()


# --------------------------------------------------------------------------
# round cores: one round's cross-client math, runtime-agnostic
#
# Each core takes the broadcast server quantities, the (possibly local shard
# of the) stacked client arrays, and a CrossClientReduce. Under the vmap
# runtime the arrays are the full [K, ...] stacks and R reduces in-process;
# under shard_map (core/sharded.py) they are the [K/n_shards, ...] local
# slices and R finishes every reduction with a psum, so a core never needs to
# know which runtime it is running in.
# --------------------------------------------------------------------------

class MetricParts(NamedTuple):
    """Cross-client metric reductions, before comm accounting is attached."""

    loss: jax.Array
    grad_norm: jax.Array
    theta_mean: jax.Array
    gram_cond_max: jax.Array
    gram_cond_mean: jax.Array
    aa_used_min: jax.Array
    aa_clipped_max: jax.Array
    cohort_ess: jax.Array


def _stack_losses(problem: FLProblem, w: Pytree, x, y, mask) -> jax.Array:
    return jax.vmap(lambda xx, yy, mm: problem.loss(w, ClientBatch(xx, yy, mm)))(
        x, y, mask
    )


@jax.named_scope("fl.anchor_grad")
def _stack_grads(problem: FLProblem, w: Pytree, x, y, mask) -> Pytree:
    """Every client's full-batch gradient at the round's anchor w^t."""
    return jax.vmap(lambda xx, yy, mm: problem.grad(w, ClientBatch(xx, yy, mm)))(
        x, y, mask
    )


def _nan_stats(k: int) -> AAStats:
    return AAStats(
        jnp.full((k,), jnp.nan), jnp.full((k,), jnp.nan),
        jnp.full((k,), jnp.nan), jnp.zeros((k,), jnp.int32),
        jnp.zeros((k,), jnp.int32),
    )


@jax.named_scope("fl.round_metrics")
def _metric_parts(problem, R, w, g, stats, x, y, mask, dweight,
                  pweight) -> MetricParts:
    """f(w), ‖g‖ and AA/cohort health stats, reduced across every client."""
    # used_columns is 0 (not nan) when a client ran no AA step; key the
    # n/a-ness off theta's nan so non-AA algorithms report nan, and the
    # column-collapse alarm (obs/alarms.py) only ever fires on a real AA run
    used = jnp.where(jnp.isnan(stats.theta), jnp.nan,
                     stats.used_columns.astype(jnp.float32))
    clipped = jnp.where(jnp.isnan(stats.theta), jnp.nan,
                        stats.clipped_columns.astype(jnp.float32))
    return MetricParts(
        loss=R.wsum(dweight, _stack_losses(problem, w, x, y, mask)),
        grad_norm=tm.tree_norm(g),
        theta_mean=R.nanmean(stats.theta),
        gram_cond_max=R.nanmax(stats.gram_cond),
        gram_cond_mean=R.nanmean(stats.gram_cond),
        aa_used_min=R.nanmin(used),
        aa_clipped_max=R.nanmax(clipped),
        cohort_ess=R.ess(pweight),
    )


def _svrg_round_core(problem, hp, use_aa, R, w_t, x, y, mask, dweight, pweight,
                     rngs, hist_s=None, hist_y=None, comm=None, poison=None,
                     poison_scale=0.0):
    """SVRG family: corrected local steps (+ optional AA), delta aggregation.

    Two wire crossings: the local full-batch gradients travel up (round trip
    1), then w^t and ∇f travel down and the model deltas travel up (round
    trip 2, with error feedback). The carried AA history is client-local
    state — it never touches the wire.

    ``poison`` — when the robustness layer injects byz_mode="history" faults
    — is ``(flags [C] bool, keys [C] prng)``: flagged clients' last recorded
    AA history column is corrupted at magnitude ``poison_scale`` before the
    multisecant solve (see _client_svrg).
    """
    w_t = R.broadcast(w_t)
    # each client's own anchor gradient, before the wire, seeds its local
    # trajectory (_svrg_trajectory)
    g_own = _stack_grads(problem, w_t, x, y, mask)
    g_k, comm = R.uplink(g_own, rngs, GRAD_UPLINK, state=comm)
    g_global = R.broadcast(R.wsum(dweight, g_k))
    client = partial(_client_svrg, problem, hp, use_aa, w_t, g_global)
    if hist_s is not None and poison is not None:
        flags, fkeys = poison
        w_k, stats, new_hs, new_hy = jax.vmap(
            lambda xx, yy, mm, rr, ga, hs, hy, fl, fk: client(
                xx, yy, mm, rr, ga, hs, hy, poison=(fl, fk, poison_scale))
        )(x, y, mask, rngs, g_own, hist_s, hist_y, flags, fkeys)
    elif hist_s is not None:
        w_k, stats, new_hs, new_hy = jax.vmap(client)(
            x, y, mask, rngs, g_own, hist_s, hist_y)
    elif poison is not None:
        flags, fkeys = poison
        w_k, stats = jax.vmap(
            lambda xx, yy, mm, rr, ga, fl, fk: client(
                xx, yy, mm, rr, ga, poison=(fl, fk, poison_scale))
        )(x, y, mask, rngs, g_own, flags, fkeys)
        new_hs = new_hy = None
    else:
        w_k, stats = jax.vmap(client)(x, y, mask, rngs, g_own)
        new_hs = new_hy = None
    w_k, comm = R.uplink(w_k, rngs, DELTA_UPLINK, anchor=w_t, state=comm)
    new_params = R.wsum(pweight, w_k, anchor=w_t)
    parts = _metric_parts(problem, R, w_t, g_global, stats, x, y, mask, dweight, pweight)
    return new_params, parts, new_hs, new_hy, comm


def _scaffold_round_core(problem, hp, use_aa, R, w_t, c, x, y, mask, c_k,
                         dweight, pweight, rngs, comm=None):
    """SCAFFOLD family: control-variate steps; c aggregated with data weights.

    Single exchange: (w^t, c) travel down, (Δw_k, c_k) travel up together.
    The server keeps the decoded wire view only in the aggregates; the
    client's own control variate stays client-side uncompressed (new_c_k).
    """
    w_t = R.broadcast(w_t)
    c = R.broadcast(c)
    w_k, new_c_k, stats = jax.vmap(
        partial(_client_scaffold, problem, hp, use_aa, w_t, c)
    )(x, y, mask, c_k, rngs)
    w_k, comm = R.uplink(w_k, rngs, DELTA_UPLINK, anchor=w_t, state=comm)
    c_up, comm = R.uplink(new_c_k, rngs, CTRL_UPLINK, state=comm)
    new_params = R.wsum(pweight, w_k, anchor=w_t)
    new_c = R.wsum(dweight, c_up)
    parts = _metric_parts(problem, R, w_t, new_c, stats, x, y, mask, dweight, pweight)
    return new_params, new_c, new_c_k, parts, comm


def _avg_round_core(problem, hp, use_aa, R, w_t, x, y, mask, dweight, pweight,
                    rngs, comm=None):
    """FedAvg family (incl. the fedosaa_avg negative control)."""
    w_t = R.broadcast(w_t)
    w_k, stats = jax.vmap(
        partial(_client_avg, problem, hp, use_aa, w_t)
    )(x, y, mask, rngs)
    w_k, comm = R.uplink(w_k, rngs, DELTA_UPLINK, anchor=w_t, state=comm)
    new_params = R.wsum(pweight, w_k, anchor=w_t)
    # diagnostics only — FedAvg ships no gradients, so no wire crossing here
    g = R.wsum(dweight, _stack_grads(problem, w_t, x, y, mask))
    parts = _metric_parts(problem, R, w_t, g, stats, x, y, mask, dweight, pweight)
    return new_params, parts, comm


def _lbfgs_round_core(problem, hp, R, w_t, x, y, mask, dweight, pweight, rngs,
                      comm=None):
    w_t = R.broadcast(w_t)
    g_own = _stack_grads(problem, w_t, x, y, mask)
    g_k, comm = R.uplink(g_own, rngs, GRAD_UPLINK, state=comm)
    g_global = R.broadcast(R.wsum(dweight, g_k))
    w_k, _ = jax.vmap(
        partial(_client_lbfgs, problem, hp, w_t, g_global)
    )(x, y, mask, rngs, g_own)
    w_k, comm = R.uplink(w_k, rngs, DELTA_UPLINK, anchor=w_t, state=comm)
    new_params = R.wsum(pweight, w_k, anchor=w_t)
    parts = _metric_parts(problem, R, w_t, g_global, _nan_stats(x.shape[0]),
                          x, y, mask, dweight, pweight)
    return new_params, parts, comm


def _newton_round_core(problem, hp, client_fn, R, w_t, x, y, mask, dweight,
                       pweight, rngs, comm=None):
    """GIANT / Newton-GMRES: aggregate directions, optional global backtrack.

    Both uplinks are stateful (schema: "grad" aux + "dir" delta): the
    gradient collection is difference-coded against the carried per-client
    reference and the Newton direction carries an error-feedback residual, so
    lossy codecs ride quantities that vanish at the optimum instead of
    flooring on the O(1) local gradients (benchmarks/ext_compression.py).
    """
    w_t = R.broadcast(w_t)
    g_k, comm = R.uplink(_stack_grads(problem, w_t, x, y, mask), rngs,
                         GRAD_UPLINK, state=comm)
    g_global = R.broadcast(R.wsum(dweight, g_k))
    p_k = jax.vmap(partial(client_fn, problem, hp, w_t, g_global))(x, y, mask)
    p_k, comm = R.uplink(p_k, rngs, DIR_UPLINK, state=comm)
    p = R.wsum(pweight, p_k)
    if hp.line_search:
        # GIANT line search on the aggregated direction: clients evaluate
        # f_k along the BROADCAST view of p (one extra downlink — see
        # comm_bytes_per_round); the server then steps with its exact p.
        p_b = R.broadcast(p)
        steps = jnp.array([4.0, 2.0, 1.0, 0.5, 0.25, 0.125, 0.0625])
        vals = jax.vmap(
            lambda a: R.wsum(
                dweight,
                _stack_losses(problem, tm.tree_axpy(-a, p_b, w_t), x, y, mask),
            )
        )(steps)
        a = steps[jnp.argmin(vals)]
    else:
        a = jnp.asarray(1.0)
    new_params = tm.tree_axpy(-a, p, w_t)
    parts = _metric_parts(problem, R, w_t, g_global, _nan_stats(x.shape[0]),
                          x, y, mask, dweight, pweight)
    return new_params, parts, comm


def _dane_round_core(problem, hp, R, w_t, x, y, mask, dweight, pweight, rngs,
                     comm=None):
    """DANE: stateful wire like the SVRG family (schema: "grad" + "delta")."""
    w_t = R.broadcast(w_t)
    g_k, comm = R.uplink(_stack_grads(problem, w_t, x, y, mask), rngs,
                         GRAD_UPLINK, state=comm)
    g_global = R.broadcast(R.wsum(dweight, g_k))
    w_k = jax.vmap(partial(_client_dane, problem, hp, w_t, g_global))(x, y, mask)
    w_k, comm = R.uplink(w_k, rngs, DELTA_UPLINK, anchor=w_t, state=comm)
    # delta-form aggregation: identical when Σpweight = 1, and a partial-
    # participation round with no active clients keeps w^t instead of zeroing
    new_params = R.wsum(pweight, w_k, anchor=w_t)
    parts = _metric_parts(problem, R, w_t, g_global, _nan_stats(x.shape[0]),
                          x, y, mask, dweight, pweight)
    return new_params, parts, comm


def finalize_metrics(parts: MetricParts, comm_bytes: float,
                     async_stats=None) -> RoundMetrics:
    """Assemble the round's metrics row. ``async_stats`` is the deadline
    gate's (arrivals, staleness_mean, staleness_max) triple
    (repro.robust.async_agg.async_round_stats); None — the barriered round —
    reports NaN for all three (the theta_mean n/a convention)."""
    if async_stats is None:
        nan = jnp.asarray(jnp.nan, jnp.float32)
        arrivals = s_mean = s_max = nan
    else:
        arrivals, s_mean, s_max = (
            jnp.asarray(v, jnp.float32) for v in async_stats)
    return RoundMetrics(
        loss=parts.loss,
        grad_norm=parts.grad_norm,
        theta_mean=parts.theta_mean,
        gram_cond_max=parts.gram_cond_max,
        gram_cond_mean=parts.gram_cond_mean,
        aa_used_min=parts.aa_used_min,
        aa_clipped_max=parts.aa_clipped_max,
        cohort_ess=parts.cohort_ess,
        comm_bytes=jnp.asarray(comm_bytes, jnp.float32),
        arrivals=arrivals,
        staleness_mean=s_mean,
        staleness_max=s_max,
    )


# --------------------------------------------------------------------------
# round functions (vmap runtime)
# --------------------------------------------------------------------------

def make_round_fn(algo: str, problem: FLProblem, hp: AlgoHParams,
                  channel: "CommChannel | str | None" = None,
                  faults: "FaultPlan | None" = None,
                  async_cfg: "AsyncConfig | None" = None):
    """Return a jittable round(state) -> (state, RoundMetrics), or
    round(state, frozen) for a problem with a frozen operand
    (``FLProblem.frozen``: pass the operand's value on every call).

    Single-process runtime: the K stacked clients are vmapped. The distributed
    runtime with identical numerics is core/sharded.py::make_sharded_round_fn.
    ``channel`` (repro/comm) compresses every wire crossing; None keeps the
    historical lossless fp32 wire. ``faults`` (repro/robust) injects the
    plan's dropout/stale/byzantine/DP/latency perturbations inside the
    compiled body; None (or an inactive plan) compiles the exact fault-free
    graph. ``async_cfg`` (repro.robust.async_agg) replaces the barriered
    round close with the deadline gate — only clients whose realized latency
    beats the deadline land, late updates buffer and fold in later with
    staleness-discounted weight; None (or ``deadline == 0``) compiles the
    byte-identical synchronous graph.
    """
    if algo not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {algo!r}; choose from {ALGORITHMS}")
    if problem.frozen is not None:
        # the frozen operand (FLProblem.frozen) is an argument of the round:
        # the round over a problem that closes over it is built while the
        # caller's jit traces, so the operand enters as a parameter and no
        # copy of it is compiled in
        def frozen_round_fn(state: ServerState, frozen):
            return make_round_fn(algo, problem.bind(frozen), hp, channel,
                                 faults, async_cfg)(state)

        return frozen_round_fn
    # resolve the AA and local-trajectory implementations once for this
    # runtime, so the client bodies see a concrete "tree"/"pallas" (never
    # "auto") and ineligible problems/algos fall back before tracing
    p0 = problem.init(jax.random.PRNGKey(0))
    hp = dataclasses.replace(
        hp, aa_impl=resolve_aa_impl(hp.aa_impl, "vmap"),
        local_impl=resolve_local_impl(hp.local_impl, "vmap", problem, algo, p0))
    channel = make_channel(channel)
    comm_bytes = comm_bytes_per_round(algo, p0, channel, hp.line_search)
    C = problem.clients
    csize = resolve_cohort_size(hp, C.num_clients)
    R = CrossClientReduce(channel)

    def prologue(state: ServerState):
        """Shared round prologue: rng splits + the resolved client axis.
        The split order matches the historical dense round exactly, and the
        dense branch of _plan_round forwards the original arrays — so the
        csize=None graph is byte-identical to the pre-cohort round."""
        rng, part_rng, cl_rng = jax.random.split(state.rng, 3)
        rngs_K = jax.random.split(cl_rng, C.num_clients)
        return rng, _plan_round(problem, csize, state, part_rng, rngs_K)

    # ---------------- fault injection (repro/robust) ----------------
    # python-gated: an absent/inactive plan leaves every closure below
    # compiling the identical fault-free graph
    faults = faults if (faults is not None and faults.active) else None
    if faults is not None:
        from repro.robust.faults import (FAULT_ANCHOR_KEY, FaultyReduce,
                                         advance_anchor, drop_weights,
                                         freeze_dropped, realize)

    def fault_ctx(plan: CohortPlan, t):
        """(reduce, dweight, pweight, realization) for this round: realize
        the plan's per-client draws (keyed by global client id — identical
        across runtimes and runs), zero + renormalize dropped clients'
        aggregation weights, and wrap the reduce so uplinks see the
        byzantine/stale/DP perturbations."""
        if faults is None:
            return R, plan.dweight, plan.pweight, None
        fr = realize(faults, t, C.num_clients, plan.idx)
        dw, pw = plan.dweight, plan.pweight
        if faults.drop_rate > 0.0:
            pw = drop_weights(fr.drop, pw)
            if algo in ("scaffold", "fedosaa_scaffold"):
                # scaffold's single exchange: the control variates ride the
                # lost uplink, so the dweight aggregation drops too; the
                # two-round-trip families' gradient collection landed before
                # the mid-round drop, so their dweight keeps every client
                dw = drop_weights(fr.drop, dw)
        anchors = None
        if faults.stale_rate > 0.0:
            anchors = plan.cohort.comm[FAULT_ANCHOR_KEY]
        return FaultyReduce(R, faults, fr, anchors), dw, pw, fr

    def fault_epilogue(plan: CohortPlan, fr, w_t, upd: dict) -> dict:
        """Post-core state landing: stale-anchor refresh first, then the
        dropped-row bit-freeze (order matters — a dropped client's refreshed
        anchor must freeze back to its pre-round value too)."""
        if faults is None:
            return upd
        if faults.stale_rate > 0.0 and upd.get("comm") is not None:
            upd = {**upd, "comm": advance_anchor(upd["comm"], fr.stale, w_t)}
        if faults.drop_rate > 0.0:
            upd = freeze_dropped(fr.drop, plan.cohort, upd)
        return upd

    # ---------------- deadline gate (repro/robust/async_agg) ----------------
    # python-gated exactly like the fault plan: an absent/inactive config
    # compiles the byte-identical synchronous (barriered) round
    async_cfg = async_cfg if (async_cfg is not None and async_cfg.active) \
        else None
    if async_cfg is not None:
        if algo in ("giant", "newton_gmres"):
            raise ValueError(
                f"AsyncConfig requires a delta-form model aggregation; "
                f"{algo!r} aggregates Newton directions and cannot buffer "
                "client deltas")
        from repro.robust.async_agg import (ASYNC_AGE_KEY, ASYNC_BUF_KEY,
                                            CaptureReduce, advance_buffer,
                                            async_round_stats, fold_buffered,
                                            guard_history_rows, plan_async)
        from repro.robust.faults import _bc

    def async_ctx(plan: CohortPlan, Rr, fr, dw, pw):
        """Deadline-gate this round: partition the cohort by realized latency
        vs the (possibly extended) deadline, hand the core only the fresh
        contributors' discounted weights, and wrap the reduce so the anchored
        model uplink's post-codec rows are captured for the buffer write. A
        run without a latency plan gates on all-zero latencies (everyone on
        time — the gate still exercises the buffer machinery under drops)."""
        if async_cfg is None:
            return Rr, dw, pw, None
        latency = fr.latency if fr is not None else jnp.zeros_like(pw)
        drop = fr.drop if (faults is not None and faults.drop_rate > 0.0) \
            else None
        ar = plan_async(async_cfg, latency,
                        plan.cohort.comm[ASYNC_AGE_KEY], pw, drop=drop)
        if algo in ("scaffold", "fedosaa_scaffold"):
            # the control variates ride the model uplink, so only fresh
            # arrivals contribute to the c aggregation (the buffer carries
            # model deltas only — a fold's c_up is lost on the floor); the
            # two-round-trip families' gradient collection is a cheap sync
            # that lands before the deadline applies to the local-update leg
            dwz = jnp.where(ar.fresh, dw, jnp.zeros_like(dw))
            dw = dwz / jnp.maximum(jnp.sum(dwz), 1e-30)
        return CaptureReduce(Rr), dw, ar.fresh_weights, ar

    def async_epilogue(plan: CohortPlan, ar, Rc, w_t, new_params, upd):
        """Jit-level buffer fold + transition, run AFTER fault_epilogue so
        the dropped-row freeze cannot clobber this round's buffer/age writes
        (drop-awareness lives in the plan_async masks instead). Returns the
        folded params, the patched updates, and the round's async stats."""
        if async_cfg is None:
            return new_params, upd, None
        comm_in = plan.cohort.comm
        new_params = fold_buffered(new_params, ar.fold_weights,
                                   comm_in[ASYNC_BUF_KEY])
        # encode-at-send: the deferred client's buffered row is its post-codec
        # delta against this round's anchor, captured off the model uplink
        delta = jax.tree.map(lambda c, w: c - w, Rc.captured, w_t)
        new_buf, new_age = advance_buffer(ar, delta, comm_in[ASYNC_BUF_KEY],
                                          comm_in[ASYNC_AGE_KEY])
        comm = dict(upd["comm"] if upd.get("comm") is not None else comm_in)
        comm[ASYNC_BUF_KEY] = new_buf
        comm[ASYNC_AGE_KEY] = new_age
        upd = {**upd, "comm": comm}
        if upd.get("c_k") is not None:
            # a non-fresh client's control-variate update never arrived
            old_ck = plan.cohort.c_k
            upd["c_k"] = jax.tree.map(
                lambda o, n: jnp.where(_bc(~ar.fresh, n), o, n),
                old_ck, upd["c_k"])
        if async_cfg.guard_history:
            upd = guard_history_rows(ar.fold | ar.retain, plan.cohort, upd)
        return new_params, upd, async_round_stats(ar)

    # ---------------- SVRG family ----------------
    if algo in ("fedsvrg", "fedosaa_svrg"):
        use_aa = algo == "fedosaa_svrg"

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            Rr, dw, pw, fr = fault_ctx(plan, state.t)
            Rr, dw, pw, ar = async_ctx(plan, Rr, fr, dw, pw)
            carry = hp.carry_history > 0 and state.hist_s is not None
            core_kw = {}
            if faults is not None and faults.poisons_history and use_aa:
                core_kw = dict(poison=(fr.byz, fr.keys),
                               poison_scale=faults.byz_scale)
            new_params, parts, new_hs, new_hy, new_comm = _svrg_round_core(
                problem, hp, use_aa, Rr, state.params, plan.x, plan.y,
                plan.mask, dw, pw, plan.rngs,
                plan.cohort.hist_s if carry else None,
                plan.cohort.hist_y if carry else None,
                plan.cohort.comm, **core_kw,
            )
            upd = dict(comm=new_comm)
            if carry:
                upd.update(hist_s=new_hs, hist_y=new_hy)
            upd = fault_epilogue(plan, fr, state.params, upd)
            new_params, upd, astats = async_epilogue(
                plan, ar, Rr, state.params, new_params, upd)
            metrics = finalize_metrics(parts, comm_bytes, astats)
            upd = _commit_plan(plan, **upd)
            return state._replace(params=new_params, t=state.t + 1, rng=rng,
                                  **upd), metrics

        return round_fn

    # ---------------- SCAFFOLD family ----------------
    if algo in ("scaffold", "fedosaa_scaffold"):
        use_aa = algo == "fedosaa_scaffold"

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            Rr, dw, pw, fr = fault_ctx(plan, state.t)
            Rr, dw, pw, ar = async_ctx(plan, Rr, fr, dw, pw)
            new_params, new_c, new_c_k, parts, new_comm = _scaffold_round_core(
                problem, hp, use_aa, Rr, state.params, state.c,
                plan.x, plan.y, plan.mask, plan.cohort.c_k,
                dw, pw, plan.rngs, plan.cohort.comm,
            )
            upd = fault_epilogue(plan, fr, state.params,
                                 dict(c_k=new_c_k, comm=new_comm))
            new_params, upd, astats = async_epilogue(
                plan, ar, Rr, state.params, new_params, upd)
            if ar is not None:
                # c's aggregation is not delta-form: a zero-fresh round would
                # zero the server control variate, so keep the old c instead
                any_fresh = jnp.any(ar.fresh)
                new_c = jax.tree.map(
                    lambda n, o: jnp.where(any_fresh, n, o), new_c, state.c)
            metrics = finalize_metrics(parts, comm_bytes, astats)
            upd = _commit_plan(plan, **upd)
            return (
                state._replace(params=new_params, c=new_c, t=state.t + 1,
                               rng=rng, **upd),
                metrics,
            )

        return round_fn

    # ---------------- AVG family (incl. negative control) ----------------
    if algo in ("fedavg", "fedosaa_avg"):
        use_aa = algo == "fedosaa_avg"

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            Rr, dw, pw, fr = fault_ctx(plan, state.t)
            Rr, dw, pw, ar = async_ctx(plan, Rr, fr, dw, pw)
            new_params, parts, new_comm = _avg_round_core(
                problem, hp, use_aa, Rr, state.params, plan.x, plan.y,
                plan.mask, dw, pw, plan.rngs,
                plan.cohort.comm,
            )
            upd = fault_epilogue(plan, fr, state.params, dict(comm=new_comm))
            new_params, upd, astats = async_epilogue(
                plan, ar, Rr, state.params, new_params, upd)
            metrics = finalize_metrics(parts, comm_bytes, astats)
            upd = _commit_plan(plan, **upd)
            return state._replace(params=new_params, t=state.t + 1, rng=rng,
                                  **upd), metrics

        return round_fn

    # ---------------- one-step L-BFGS ----------------
    if algo == "lbfgs":

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            Rr, dw, pw, fr = fault_ctx(plan, state.t)
            Rr, dw, pw, ar = async_ctx(plan, Rr, fr, dw, pw)
            new_params, parts, new_comm = _lbfgs_round_core(
                problem, hp, Rr, state.params, plan.x, plan.y, plan.mask,
                dw, pw, plan.rngs, plan.cohort.comm,
            )
            upd = fault_epilogue(plan, fr, state.params, dict(comm=new_comm))
            new_params, upd, astats = async_epilogue(
                plan, ar, Rr, state.params, new_params, upd)
            metrics = finalize_metrics(parts, comm_bytes, astats)
            upd = _commit_plan(plan, **upd)
            return state._replace(params=new_params, t=state.t + 1, rng=rng,
                                  **upd), metrics

        return round_fn

    # ---------------- Newton-type ----------------
    if algo in ("giant", "newton_gmres"):
        client_fn = _client_giant if algo == "giant" else _client_newton_gmres

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            Rr, dw, pw, fr = fault_ctx(plan, state.t)
            new_params, parts, new_comm = _newton_round_core(
                problem, hp, client_fn, Rr, state.params, plan.x, plan.y,
                plan.mask, dw, pw, plan.rngs,
                plan.cohort.comm,
            )
            metrics = finalize_metrics(parts, comm_bytes)
            upd = fault_epilogue(plan, fr, state.params, dict(comm=new_comm))
            upd = _commit_plan(plan, **upd)
            return state._replace(params=new_params, t=state.t + 1, rng=rng,
                                  **upd), metrics

        return round_fn

    # ---------------- DANE ----------------
    assert algo == "dane"

    def round_fn(state: ServerState):
        rng, plan = prologue(state)
        Rr, dw, pw, fr = fault_ctx(plan, state.t)
        Rr, dw, pw, ar = async_ctx(plan, Rr, fr, dw, pw)
        new_params, parts, new_comm = _dane_round_core(
            problem, hp, Rr, state.params, plan.x, plan.y, plan.mask,
            dw, pw, plan.rngs, plan.cohort.comm,
        )
        upd = fault_epilogue(plan, fr, state.params, dict(comm=new_comm))
        new_params, upd, astats = async_epilogue(
            plan, ar, Rr, state.params, new_params, upd)
        metrics = finalize_metrics(parts, comm_bytes, astats)
        upd = _commit_plan(plan, **upd)
        return state._replace(params=new_params, t=state.t + 1, rng=rng,
                              **upd), metrics

    return round_fn
