"""Distributed FL round runtime: the client fan-out under shard_map.

``make_sharded_round_fn`` is the drop-in distributed twin of
``core/algorithms.py::make_round_fn``: the same per-client bodies
(``_client_svrg``, ``_client_scaffold``, ``_client_avg``, ``_client_lbfgs``,
``_client_giant``, ``_client_newton_gmres``, ``_client_dane``) and the same
round cores, but with the K stacked clients partitioned over the ("pod",
"data") mesh axes of a launch/mesh.py mesh instead of vmapped on one device.

How it maps:

  * every [K, ...] client array (data, rngs, control variates, carried AA
    history) enters the shard_map body sharded on its leading axis — each
    shard vmaps over its K / n_shards local clients;
  * every server quantity (params, server control variate, participation
    weights already normalized on the host) enters replicated;
  * all cross-client reductions — ``_aggregate`` deltas, the global gradient,
    control-variate means, metric reductions — finish with a psum/pmax over
    the client mesh axes (see ``ShardReduce``), inside the mapped body;
  * per-client outputs (new c_k, carried history) leave sharded, aggregates
    leave replicated.

One jit of the returned round_fn therefore compiles the full round as a
single XLA computation: no per-client Python loop, no host round-trips.
On a 1-device ``make_host_mesh()`` every psum is an identity and the sharded
round agrees with the vmap round to float precision from any given state
(allclose rtol 1e-6 — tests/test_sharded_runtime.py; the shard_map boundary
changes XLA fusion, so agreement is not bit-for-bit, and the ill-conditioned
AA gram solve can amplify that last-ulp difference across many rounds).

The unused "model" mesh axis (tensor parallelism for the LM workloads) is
simply not mentioned in any spec: the round is replicated over it.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import CommChannel, make_channel
from repro.core.algorithms import (
    ALGORITHMS,
    AlgoHParams,
    CrossClientReduce,
    ServerState,
    _avg_round_core,
    _client_giant,
    _client_newton_gmres,
    _dane_round_core,
    _commit_plan,
    _lbfgs_round_core,
    _newton_round_core,
    _plan_round,
    _scaffold_round_core,
    _svrg_round_core,
    comm_bytes_per_round,
    finalize_metrics,
    resolve_cohort_size,
    resolve_local_impl,
)
from repro.core.anderson import resolve_aa_impl
from repro.core.problem import FLProblem

#: mesh axes the client axis is partitioned over, slowest (inter-pod) first.
CLIENT_MESH_AXES = ("pod", "data")


class ShardReduce(CrossClientReduce):
    """Cross-client reductions for the shard_map runtime.

    Each method reduces over the *local* client slice exactly like the vmap
    runtime, then finishes with a psum/pmax over the client mesh axes — so on
    a 1-shard mesh the arithmetic is identical to CrossClientReduce.
    """

    def __init__(self, axes: tuple[str, ...],
                 channel: CommChannel | None = None):
        super().__init__(channel)
        self.axes = axes

    def wsum(self, weights, stacked, anchor=None):
        with jax.named_scope("fl.psum"):
            # in each leaf's own dtype, as algorithms._aggregate
            if anchor is None:
                return jax.tree.map(
                    lambda s: jax.lax.psum(
                        jnp.tensordot(weights, s, axes=1), self.axes
                    ).astype(s.dtype),
                    stacked,
                )
            return jax.tree.map(
                lambda a, s: (a + jax.lax.psum(
                    jnp.tensordot(weights, s - a[None], axes=1), self.axes
                )).astype(a.dtype),
                anchor, stacked,
            )

    def nanmean(self, x):
        finite = ~jnp.isnan(x)
        total = jax.lax.psum(jnp.sum(jnp.where(finite, x, 0.0)), self.axes)
        count = jax.lax.psum(jnp.sum(finite.astype(x.dtype)), self.axes)
        return jnp.where(count > 0, total / jnp.maximum(count, 1), jnp.nan)

    def nanmax(self, x):
        m = jax.lax.pmax(jnp.max(jnp.where(jnp.isnan(x), -jnp.inf, x)), self.axes)
        return jnp.where(jnp.isneginf(m), jnp.nan, m)

    def nanmin(self, x):
        m = jax.lax.pmin(jnp.min(jnp.where(jnp.isnan(x), jnp.inf, x)), self.axes)
        return jnp.where(jnp.isposinf(m), jnp.nan, m)

    def ess(self, weights):
        w2 = jax.lax.psum(jnp.sum(weights * weights), self.axes)
        return 1.0 / jnp.maximum(w2, 1e-30)


def client_mesh_axes(mesh) -> tuple[str, ...]:
    """The subset of ("pod","data") present in ``mesh``, slowest first."""
    return tuple(a for a in CLIENT_MESH_AXES if a in mesh.axis_names)


def _split_client_rngs(cl_rng, K: int, mesh):
    """K per-client keys, forced REPLICATED before they enter shard_map.

    Without the constraint GSPMD partitions the threefry split across the
    mesh (its consumer is sharded) and stitches the key halves back with
    512-participant collective-permutes — ~40 B of traffic that deadlocks
    the emulated-CPU collective rendezvous and would be pure latency on real
    pods. Replicating the split is a few µs of redundant compute per device;
    the shard_map entry then slices each shard's keys locally, collective-
    free. Only stochastic codecs (int8) keep the keys live, which is why the
    permutes never showed up in the bf16/identity dryruns.
    """
    from jax.sharding import NamedSharding

    rngs = jax.random.split(cl_rng, K)
    return jax.lax.with_sharding_constraint(rngs, NamedSharding(mesh, P()))


def num_client_shards(mesh, axes: tuple[str, ...] | None = None) -> int:
    axes = client_mesh_axes(mesh) if axes is None else axes
    return math.prod(mesh.shape[a] for a in axes)


# -- shard addressability (the checkpoint subsystem's view of an array) -----

def leaf_addressable_shards(leaf) -> "list[tuple[tuple[tuple[int, int], ...], object]]":
    """The shards of ``leaf`` THIS process can read, as
    ``[(box, host_copy), ...]`` — ``box`` is one ``(start, stop)`` pair per
    dimension and ``host_copy`` a fresh numpy COPY of that shard's data.

    This is the primitive the per-shard checkpoint save is built on: each
    host saves exactly the boxes it holds, so no cross-host ``device_get``
    (and no full-array gather through one process) ever happens on the save
    path. Replicated leaves yield one shard per local device with identical
    boxes — callers dedupe by box. The copy is deliberate: the engine DONATES
    state buffers to the next chunk's jit, so a zero-copy view taken at the
    chunk boundary would silently alias memory XLA is about to reuse.
    """
    import numpy as np

    shards = getattr(leaf, "addressable_shards", None)
    if shards is None:  # plain numpy / scalar leaf: one process-local box
        arr = np.array(leaf, copy=True)
        return [(tuple((0, n) for n in arr.shape), arr)]
    out = []
    for sh in shards:
        data = np.array(sh.data, copy=True)
        box = tuple(
            (0 if idx.start is None else int(idx.start),
             dim if idx.stop is None else int(idx.stop))
            for idx, dim in zip(sh.index, leaf.shape))
        if not box:  # 0-d leaf
            box = ()
        out.append((box, data))
    return out


def dedupe_shard_boxes(shards):
    """Drop replicated copies: keep the first shard seen per distinct box
    (replication puts bit-identical data at every copy, so which copy wins
    is immaterial)."""
    seen, out = set(), []
    for box, data in shards:
        if box in seen:
            continue
        seen.add(box)
        out.append((box, data))
    return out


def make_sharded_round_fn(algo: str, problem: FLProblem, hp: AlgoHParams,
                          mesh, client_axes: tuple[str, ...] | None = None,
                          channel: "CommChannel | str | None" = None,
                          faults: "FaultPlan | None" = None,
                          async_cfg: "AsyncConfig | None" = None):
    """Return a jittable round(state) -> (state, RoundMetrics) whose client
    fan-out is shard_mapped over ``mesh``'s ("pod","data") axes.

    Requires num_clients to divide evenly over the client shards (pad the
    client stack with stack_client_arrays if it does not).

    ``channel`` (repro/comm) compresses the wire exactly as in the vmap
    runtime: each shard encode/decodes its local clients' uploads, so the
    dequantized representation is what the client-axis psum reduces; the
    error-feedback residuals stay sharded with their clients.

    ``faults`` (repro/robust) injects the plan's perturbations exactly as
    the vmap runtime does: the per-round realization is drawn at jit level
    (keyed by global client id, so both runtimes inject identical rounds)
    and enters the shard_map body as extra [C] client-sharded arrays; every
    fault op inside the body is per-client row-local, so no new collectives
    appear. The weight adjustment, dropped-row freeze and stale-anchor
    refresh run at jit level outside the shard_map, shared with the vmap
    builder's logic verbatim.

    ``async_cfg`` (repro.robust.async_agg) deadline-gates the round close the
    same way: the gate's partition and discounted weights are computed at jit
    level from the realized latencies (identical to the vmap builder), the
    body's only change is capturing the anchored model uplink's post-codec
    rows as one extra client-sharded output, and the buffer fold/transition
    runs at jit level. None (or ``deadline == 0``) compiles the byte-identical
    barriered graph.
    """
    if algo not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {algo!r}; choose from {ALGORITHMS}")
    if problem.frozen is not None:
        raise ValueError("a problem with a frozen operand runs on the vmap "
                         "runtime (make_round_fn); the sharded round closes "
                         "over its problem")
    # the sharded runtime always takes the leaf-wise tree AA path (leaves may
    # be sharded across the mesh, where the flat-buffer Pallas ravel would
    # force an all-gather) AND the autodiff local-trajectory path:
    # aa_impl/local_impl "pallas"/"auto" fall back without error
    hp = dataclasses.replace(
        hp, aa_impl=resolve_aa_impl(hp.aa_impl, "sharded"),
        local_impl=resolve_local_impl(hp.local_impl, "sharded"))
    axes = client_mesh_axes(mesh) if client_axes is None else tuple(client_axes)
    if not axes:
        raise ValueError(
            f"mesh axes {mesh.axis_names} contain none of {CLIENT_MESH_AXES}; "
            "build the mesh with launch/mesh.py"
        )
    n_shards = num_client_shards(mesh, axes)
    C = problem.clients
    K = C.num_clients
    csize = resolve_cohort_size(hp, K)
    if csize is None and K % n_shards != 0:
        raise ValueError(
            f"num_clients={K} does not divide over {n_shards} client shards "
            f"(mesh axes {axes}); pad the client stack to a multiple"
        )
    if csize is not None and csize % n_shards != 0:
        raise ValueError(
            f"cohort_size={csize} does not divide over {n_shards} client "
            f"shards (mesh axes {axes}); pick a cohort that is a multiple"
        )
    channel = make_channel(channel)
    R = ShardReduce(axes, channel)
    comm_bytes = comm_bytes_per_round(algo, problem.init(jax.random.PRNGKey(0)),
                                      channel, hp.line_search)

    csh = P(axes)   # leading (client) dim split over the client mesh axes
    rep = P()       # replicated

    def prologue(state: ServerState):
        """Shared round prologue: rng splits + the cohort (or dense) plan.

        The gather stays at jit level, OUTSIDE shard_map: GSPMD reshards the
        gathered [C, ...] rows onto the client shards, so the mapped bodies
        and their in_specs are identical for both paths — only the leading
        axis extent changes. The scatter in _commit_plan likewise runs at jit
        level, writing the cohort rows back into the K-sized store."""
        rng, part_rng, cl_rng = jax.random.split(state.rng, 3)
        rngs_K = _split_client_rngs(cl_rng, K, mesh)
        return rng, _plan_round(problem, csize, state, part_rng, rngs_K)

    def smap(body, in_specs, out_specs):
        # check_vma off: the bodies close over `problem`/`hp` and batch psums
        # under vmap (line search), which the replication check rejects.
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # ---------------- fault injection (repro/robust) ----------------
    # python-gated exactly like the vmap builder: an absent/inactive plan
    # compiles the identical fault-free graph (no extra smap args)
    faults = faults if (faults is not None and faults.active) else None
    if faults is not None:
        from repro.robust.faults import (FAULT_ANCHOR_KEY, FaultRealization,
                                         FaultyReduce, advance_anchor,
                                         drop_weights, freeze_dropped,
                                         realize)

    def fault_ctx(plan, t):
        """jit-level (OUTSIDE shard_map) realization + weight adjustment.
        Returns (dweight, pweight, realization, extra-smap-args): the [C]
        fault arrays ride into the body client-sharded like every other
        per-client row."""
        if faults is None:
            return plan.dweight, plan.pweight, None, ()
        fr = realize(faults, t, K, plan.idx)
        dw, pw = plan.dweight, plan.pweight
        if faults.drop_rate > 0.0:
            pw = drop_weights(fr.drop, pw)
            if algo in ("scaffold", "fedosaa_scaffold"):
                # single exchange: the control variates ride the lost uplink
                dw = drop_weights(fr.drop, dw)
        return dw, pw, fr, tuple(fr)

    def fault_reduce(e, fxa):
        """Inside the body: rebuild the shard-local realization and wrap the
        reduce. Returns (reduce, realization-or-None)."""
        if not fxa:
            return R, None
        frl = FaultRealization(*fxa)
        anchors = e[FAULT_ANCHOR_KEY] if faults.stale_rate > 0.0 else None
        return FaultyReduce(R, faults, frl, anchors), frl

    def fault_epilogue(plan, fr, w_t, upd):
        """jit-level post-core landing: stale-anchor refresh, then the
        dropped-row bit-freeze (a dropped client's refreshed anchor must
        freeze back too) — same order as the vmap builder."""
        if faults is None:
            return upd
        if faults.stale_rate > 0.0 and upd.get("comm") is not None:
            upd = {**upd, "comm": advance_anchor(upd["comm"], fr.stale, w_t)}
        if faults.drop_rate > 0.0:
            upd = freeze_dropped(fr.drop, plan.cohort, upd)
        return upd

    fsp = () if faults is None else (csh,) * len(FaultRealization._fields)

    # ---------------- deadline gate (repro/robust/async_agg) ----------------
    # python-gated exactly like the fault plan: an absent/inactive config
    # compiles the byte-identical barriered round (no extra smap outputs)
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

    asp = () if async_cfg is None else (csh,)

    def async_ctx(plan, fr, dw, pw):
        """jit-level (OUTSIDE shard_map) deadline-gate partition + discounted
        weights — the same plan_async call the vmap builder makes, so both
        runtimes gate identical rounds."""
        if async_cfg is None:
            return dw, pw, None
        latency = fr.latency if fr is not None else jnp.zeros_like(pw)
        drop = fr.drop if (faults is not None and faults.drop_rate > 0.0) \
            else None
        ar = plan_async(async_cfg, latency,
                        plan.cohort.comm[ASYNC_AGE_KEY], pw, drop=drop)
        if algo in ("scaffold", "fedosaa_scaffold"):
            # control variates ride the model uplink: only fresh arrivals
            # contribute to the c aggregation (the buffer holds model deltas
            # only — a fold's c_up is lost on the floor)
            dwz = jnp.where(ar.fresh, dw, jnp.zeros_like(dw))
            dw = dwz / jnp.maximum(jnp.sum(dwz), 1e-30)
        return dw, ar.fresh_weights, ar

    def async_reduce(Rb):
        """Inside the body: wrap the (possibly faulty) reduce so the anchored
        model uplink's post-codec rows can leave as an extra sharded output."""
        return CaptureReduce(Rb) if async_cfg is not None else Rb

    def async_out(Rb):
        return (Rb.captured,) if async_cfg is not None else ()

    def async_epilogue(plan, ar, captured, w_t, new_params, upd):
        """jit-level buffer fold + transition, run AFTER fault_epilogue —
        identical logic to the vmap builder (see make_round_fn)."""
        if async_cfg is None:
            return new_params, upd, None
        comm_in = plan.cohort.comm
        new_params = fold_buffered(new_params, ar.fold_weights,
                                   comm_in[ASYNC_BUF_KEY])
        delta = jax.tree.map(lambda cap, w: cap - w, captured, w_t)
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

    # NOTE: optional per-client state (carried AA history, error-feedback
    # residuals) passes through shard_map as None when absent — None is an
    # empty pytree, so the csh spec sharding it has no leaves to act on and
    # one body covers every combination.

    # ---------------- SVRG family ----------------
    if algo in ("fedsvrg", "fedosaa_svrg"):
        use_aa = algo == "fedosaa_svrg"

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            dw, pw, fr, fx = fault_ctx(plan, state.t)
            dw, pw, ar = async_ctx(plan, fr, dw, pw)
            carry = hp.carry_history > 0 and state.hist_s is not None

            def body(w_t, x, y, mask, dw_, pw_, r, hs, hy, e, *fxa):
                Rb, frl = fault_reduce(e, fxa)
                Rb = async_reduce(Rb)
                kw = {}
                if frl is not None and faults.poisons_history and use_aa:
                    kw = dict(poison=(frl.byz, frl.keys),
                              poison_scale=faults.byz_scale)
                out = _svrg_round_core(
                    problem, hp, use_aa, Rb, w_t, x, y, mask, dw_, pw_, r,
                    hs, hy, e, **kw)
                return out + async_out(Rb)

            outs = smap(
                body,
                in_specs=(rep, csh, csh, csh, csh, csh, csh, csh, csh, csh)
                + fsp,
                out_specs=(rep, rep, csh, csh, csh) + asp,
            )(state.params, plan.x, plan.y, plan.mask, dw, pw, plan.rngs,
              plan.cohort.hist_s if carry else None,
              plan.cohort.hist_y if carry else None,
              plan.cohort.comm, *fx)
            captured = None
            if async_cfg is not None:
                *outs, captured = outs
            new_params, parts, new_hs, new_hy, new_comm = outs
            upd = dict(comm=new_comm)
            if carry:
                upd.update(hist_s=new_hs, hist_y=new_hy)
            upd = fault_epilogue(plan, fr, state.params, upd)
            new_params, upd, astats = async_epilogue(
                plan, ar, captured, state.params, new_params, upd)
            upd = _commit_plan(plan, **upd)
            return state._replace(params=new_params, t=state.t + 1, rng=rng,
                                  **upd), finalize_metrics(parts, comm_bytes,
                                                           astats)

        return round_fn

    # ---------------- SCAFFOLD family ----------------
    if algo in ("scaffold", "fedosaa_scaffold"):
        use_aa = algo == "fedosaa_scaffold"

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            dw, pw, fr, fx = fault_ctx(plan, state.t)
            dw, pw, ar = async_ctx(plan, fr, dw, pw)

            def body(w_t, c, x, y, mask, c_k, dw_, pw_, r, e, *fxa):
                Rb, _ = fault_reduce(e, fxa)
                Rb = async_reduce(Rb)
                out = _scaffold_round_core(
                    problem, hp, use_aa, Rb, w_t, c, x, y, mask, c_k, dw_,
                    pw_, r, e)
                return out + async_out(Rb)

            outs = smap(
                body,
                in_specs=(rep, rep, csh, csh, csh, csh, csh, csh, csh, csh)
                + fsp,
                out_specs=(rep, rep, csh, rep, csh) + asp,
            )(state.params, state.c, plan.x, plan.y, plan.mask,
              plan.cohort.c_k, dw, pw, plan.rngs, plan.cohort.comm, *fx)
            captured = None
            if async_cfg is not None:
                *outs, captured = outs
            new_params, new_c, new_c_k, parts, new_comm = outs
            upd = fault_epilogue(plan, fr, state.params,
                                 dict(c_k=new_c_k, comm=new_comm))
            new_params, upd, astats = async_epilogue(
                plan, ar, captured, state.params, new_params, upd)
            if ar is not None:
                # c's aggregation is not delta-form: a zero-fresh round would
                # zero the server control variate, so keep the old c instead
                any_fresh = jnp.any(ar.fresh)
                new_c = jax.tree.map(
                    lambda n, o: jnp.where(any_fresh, n, o), new_c, state.c)
            upd = _commit_plan(plan, **upd)
            return (
                state._replace(params=new_params, c=new_c, t=state.t + 1,
                               rng=rng, **upd),
                finalize_metrics(parts, comm_bytes, astats),
            )

        return round_fn

    # ---------------- AVG family (incl. negative control) ----------------
    if algo in ("fedavg", "fedosaa_avg"):
        use_aa = algo == "fedosaa_avg"

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            dw, pw, fr, fx = fault_ctx(plan, state.t)
            dw, pw, ar = async_ctx(plan, fr, dw, pw)

            def body(w_t, x, y, mask, dw_, pw_, r, e, *fxa):
                Rb, _ = fault_reduce(e, fxa)
                Rb = async_reduce(Rb)
                out = _avg_round_core(
                    problem, hp, use_aa, Rb, w_t, x, y, mask, dw_, pw_, r, e)
                return out + async_out(Rb)

            outs = smap(
                body,
                in_specs=(rep, csh, csh, csh, csh, csh, csh, csh) + fsp,
                out_specs=(rep, rep, csh) + asp,
            )(state.params, plan.x, plan.y, plan.mask, dw, pw, plan.rngs,
              plan.cohort.comm, *fx)
            captured = None
            if async_cfg is not None:
                *outs, captured = outs
            new_params, parts, new_comm = outs
            upd = fault_epilogue(plan, fr, state.params, dict(comm=new_comm))
            new_params, upd, astats = async_epilogue(
                plan, ar, captured, state.params, new_params, upd)
            upd = _commit_plan(plan, **upd)
            return state._replace(params=new_params, t=state.t + 1,
                                  rng=rng, **upd), finalize_metrics(
                                      parts, comm_bytes, astats)

        return round_fn

    # ---------------- one-step L-BFGS ----------------
    if algo == "lbfgs":

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            dw, pw, fr, fx = fault_ctx(plan, state.t)
            dw, pw, ar = async_ctx(plan, fr, dw, pw)

            def body(w_t, x, y, mask, dw_, pw_, r, e, *fxa):
                Rb, _ = fault_reduce(e, fxa)
                Rb = async_reduce(Rb)
                out = _lbfgs_round_core(
                    problem, hp, Rb, w_t, x, y, mask, dw_, pw_, r, e)
                return out + async_out(Rb)

            outs = smap(
                body,
                in_specs=(rep, csh, csh, csh, csh, csh, csh, csh) + fsp,
                out_specs=(rep, rep, csh) + asp,
            )(state.params, plan.x, plan.y, plan.mask, dw, pw, plan.rngs,
              plan.cohort.comm, *fx)
            captured = None
            if async_cfg is not None:
                *outs, captured = outs
            new_params, parts, new_comm = outs
            upd = fault_epilogue(plan, fr, state.params, dict(comm=new_comm))
            new_params, upd, astats = async_epilogue(
                plan, ar, captured, state.params, new_params, upd)
            upd = _commit_plan(plan, **upd)
            return state._replace(params=new_params, t=state.t + 1,
                                  rng=rng, **upd), finalize_metrics(
                                      parts, comm_bytes, astats)

        return round_fn

    # ---------------- Newton-type ----------------
    if algo in ("giant", "newton_gmres"):
        client_fn = _client_giant if algo == "giant" else _client_newton_gmres

        def round_fn(state: ServerState):
            rng, plan = prologue(state)
            dw, pw, fr, fx = fault_ctx(plan, state.t)

            def body(w_t, x, y, mask, dw_, pw_, r, e, *fxa):
                Rb, _ = fault_reduce(e, fxa)
                return _newton_round_core(
                    problem, hp, client_fn, Rb, w_t, x, y, mask, dw_, pw_,
                    r, e)

            new_params, parts, new_comm = smap(
                body,
                in_specs=(rep, csh, csh, csh, csh, csh, csh, csh) + fsp,
                out_specs=(rep, rep, csh),
            )(state.params, plan.x, plan.y, plan.mask, dw, pw, plan.rngs,
              plan.cohort.comm, *fx)
            upd = fault_epilogue(plan, fr, state.params, dict(comm=new_comm))
            upd = _commit_plan(plan, **upd)
            return state._replace(params=new_params, t=state.t + 1,
                                  rng=rng, **upd), finalize_metrics(parts, comm_bytes)

        return round_fn

    # ---------------- DANE ----------------
    assert algo == "dane"

    def round_fn(state: ServerState):
        rng, plan = prologue(state)
        dw, pw, fr, fx = fault_ctx(plan, state.t)
        dw, pw, ar = async_ctx(plan, fr, dw, pw)

        def body(w_t, x, y, mask, dw_, pw_, r, e, *fxa):
            Rb, _ = fault_reduce(e, fxa)
            Rb = async_reduce(Rb)
            out = _dane_round_core(problem, hp, Rb, w_t, x, y, mask, dw_,
                                   pw_, r, e)
            return out + async_out(Rb)

        outs = smap(
            body,
            in_specs=(rep, csh, csh, csh, csh, csh, csh, csh) + fsp,
            out_specs=(rep, rep, csh) + asp,
        )(state.params, plan.x, plan.y, plan.mask, dw, pw,
          plan.rngs, plan.cohort.comm, *fx)
        captured = None
        if async_cfg is not None:
            *outs, captured = outs
        new_params, parts, new_comm = outs
        upd = fault_epilogue(plan, fr, state.params, dict(comm=new_comm))
        new_params, upd, astats = async_epilogue(
            plan, ar, captured, state.params, new_params, upd)
        upd = _commit_plan(plan, **upd)
        return state._replace(params=new_params, t=state.t + 1,
                              rng=rng, **upd), finalize_metrics(
                                  parts, comm_bytes, astats)

    return round_fn
