"""Federated training driver: the server-side orchestration loop.

``run_federated`` is the single entry point used by the examples and every
benchmark. It compiles one round of the chosen algorithm and iterates it,
collecting the metric history the paper plots (relative error vs. aggregation
round, communication, wall time).
"""
from __future__ import annotations

import dataclasses
import json
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.algorithms import AlgoHParams, init_state, make_round_fn
from repro.core.problem import FLProblem
from repro.utils import tree_math as tm

Pytree = Any


@dataclasses.dataclass
class History:
    algo: str
    rounds: np.ndarray            # [T]
    loss: np.ndarray              # f(w^t)
    grad_norm: np.ndarray
    rel_error: np.ndarray         # ‖w^t − w*‖/‖w*‖  (nan if w* not given)
    theta_mean: np.ndarray        # AA gain per round (nan for non-AA algos)
    comm_bytes: np.ndarray        # cumulative bytes on the wire (codec-exact)
    wall_time: np.ndarray         # cumulative seconds (per-round, measured)
    final_params: Pytree = None
    channel: str = "identity"     # repro/comm channel name
    gram_cond_max: np.ndarray = None  # worst AA Gram conditioning per round
                                  # (nan for non-AA algos) — the divergence
                                  # predictor, kept in the history so plots
                                  # and logs can correlate it with rel_error
    arrivals: np.ndarray = None   # deadline-gated landings per round (nan
                                  # everywhere when async_cfg is off)
    staleness_mean: np.ndarray = None  # mean landed buffer age (nan if n/a)
    staleness_max: np.ndarray = None   # oldest landed buffer age (nan if n/a)
    aa_used_min: np.ndarray = None     # fewest AA columns surviving filtering
                                       # on any client (nan for non-AA algos)

    @property
    def comm_floats(self) -> np.ndarray:
        """fp32-equivalent floats (bytes/4) — the paper's Table 1 unit, kept
        so historical comparisons (table1_comm.json) stay directly readable.
        Equal to the old float counters on the identity channel."""
        return self.comm_bytes / 4.0

    def summary(self) -> str:
        last = -1
        gcond = (f"gcond={self.gram_cond_max[last]:.2e} "
                 if self.gram_cond_max is not None
                 and len(self.gram_cond_max) else "")
        return (
            f"{self.algo:18s} rounds={len(self.rounds):4d} "
            f"loss={self.loss[last]:.6e} |g|={self.grad_norm[last]:.3e} "
            f"relerr={self.rel_error[last]:.3e} {gcond}"
            f"comm={self.comm_bytes[last]:.3e}B[{self.channel}] "
            f"wall={self.wall_time[last]:.2f}s"
        )


def checkpoint_config_fingerprint(algo: str, runtime: str, channel_name: str,
                                  num_clients: int, cohort_size: int,
                                  faults=None, async_cfg=None) -> dict:
    """The run-identity dict embedded in every checkpoint manifest and
    demanded back at resume: a checkpoint written under one algorithm /
    runtime / channel / cohort / fault schedule / async gate must not be
    silently continued under another (the carried AA history, EF residuals
    and buffers would be statistically meaningless). JSON-normalized so the
    comparison survives the manifest's serialization round-trip."""
    fp = {
        "algo": algo,
        "runtime": runtime,
        "channel": channel_name,
        "num_clients": int(num_clients),
        "cohort_size": int(cohort_size) if cohort_size is not None else None,
        "faults": dataclasses.asdict(faults) if faults is not None else None,
        "async": dataclasses.asdict(async_cfg)
        if async_cfg is not None else None,
    }
    return json.loads(json.dumps(fp))


def run_federated(
    problem: FLProblem,
    algo: str,
    hp: AlgoHParams,
    num_rounds: int,
    rng: jax.Array | int = 0,
    w_star: Pytree | None = None,
    w0: Pytree | None = None,
    stop_rel_error: float | None = None,
    stop_grad_norm: float | None = None,
    runtime: str = "vmap",
    mesh=None,
    channel=None,
    chunk: int | None = None,
    sinks=(),
    trace_capture=None,
    tap=None,
    faults=None,
    async_cfg=None,
    checkpoint=None,
    resume=None,
    checkpoint_fs=None,
) -> History:
    """Iterate ``num_rounds`` of ``algo`` and collect the metric history.

    runtime — "vmap" (default): the K clients are vmapped on one device;
              "sharded": the client fan-out runs under shard_map over the
              ("pod","data") axes of ``mesh`` (core/sharded.py). ``mesh``
              defaults to launch/mesh.py::make_host_mesh(): every device of
              the host on "data" (1×1 on one chip or a CPU).
    channel — repro/comm wire-compression channel (a CommChannel or a spec
              string like "int8", "topk:0.05", "bf16/bf16"); None = lossless
              fp32. Both runtimes honor it, and ``History.comm_bytes`` counts
              exactly what the chosen codecs put on the wire.
    chunk   — None (default): the per-round loop — one jit dispatch and one
              host metric sync per round. chunk >= 1: the device-resident
              round engine (core/engine.py) compiles ``chunk`` rounds into
              one lax.scan jit with DONATED state, stacks metrics on device,
              and evaluates the stop criteria in-graph, syncing the host
              once per chunk. The History rows are identical either way
              (tests/test_engine.py, rtol 1e-6); only the wall_time
              attribution differs — the engine divides each chunk's measured
              time equally over its rounds.

    Telemetry (repro/obs — all optional and off by default; sinks and
    trace_capture are bit-neutral — attaching them leaves the computed
    rounds bit-identical, pinned in tests/test_obs.py. The tap is the one
    exception: it compiles a callback into the chunk and matches the tapless
    run at rtol 1e-6, see make_chunk_runner):
    sinks         — MetricsSinks (obs/sinks) opened with a run header
                    (algo/runtime/channel/cohort/per-UplinkSpec byte
                    breakdown), fed one versioned row per executed round —
                    at chunk boundaries on the engine path, per round on the
                    loop path — and closed with a footer. A sink exposing a
                    truthy ``stop_requested`` (obs/alarms.AlarmMonitor) stops
                    the run at the next boundary.
    trace_capture — obs/profiling.TraceCapture: on-demand jax.profiler trace
                    windows around chunk (or round) execution.
    tap           — live in-chunk jax.debug.callback (obs/sinks.LiveTap);
                    engine path only.
    faults        — repro/robust.FaultPlan: inject the plan's dropout/stale/
                    byzantine/DP perturbations inside the compiled round on
                    either runtime (None or an inactive plan compiles the
                    exact fault-free graph). Stale-update plans attach the
                    per-client lagged-anchor rows to the comm state here, so
                    they ride the cohort gather/scatter and checkpoints like
                    any other per-client buffer.
    async_cfg     — repro.robust.async_agg.AsyncConfig: replace the barriered
                    round close with the deadline gate — only clients whose
                    realized latency (``faults.latency_*``) beats the
                    deadline land each round; late updates park in per-client
                    buffer rows (attached to the comm state here, riding
                    gather/scatter and checkpoints) and fold in later with
                    staleness-discounted weight. None or ``deadline == 0``
                    compiles the byte-identical synchronous graph on either
                    runtime. ``History.arrivals``/``staleness_*`` surface the
                    gate's per-round activity.
    checkpoint    — checkpoint/policy.CheckpointPolicy: preemption-tolerant
                    saves of the full ServerState (params + control variates
                    + AA history + codec EF/ref buffers + fault anchors +
                    async buffers). On the engine path saves dispatch from
                    the chunk-boundary host sync to a background thread
                    (policy.mode="async"); the per-round loop saves inline.
                    Every checkpoint's manifest embeds this run's config
                    fingerprint (algo/runtime/channel/cohort/faults/async),
                    and the save telemetry rides the v4 footer.
    resume        — None: fresh start. "auto": restore the newest COMPLETE
                    checkpoint under ``checkpoint.directory`` (torn/corrupt
                    saves are skipped; nothing restorable → fresh start). A
                    path: restore exactly that checkpoint directory (raises
                    if torn). Either way the restored manifest's config
                    fingerprint must match this run's — a resumed run
                    REFUSES to continue under different hyperparameters/
                    faults (CheckpointConfigMismatch) instead of silently
                    blending histories. Round numbering continues from the
                    checkpoint round: ``num_rounds`` stays the TOTAL budget,
                    so a run preempted at round r executes rounds
                    r..num_rounds-1 and History/rows stay contiguous.
    checkpoint_fs — filesystem override for the save/restore path (the
                    crash-injection harness passes a
                    repro.robust.fs_faults.FaultyFs here); None = the real
                    filesystem.
    """
    from repro.comm import make_channel
    from repro.comm.schema import uplink_byte_breakdown
    from repro.core.algorithms import UPLINK_SCHEMAS, resolve_cohort_size

    if runtime not in ("vmap", "sharded"):
        raise ValueError(f"unknown runtime {runtime!r}; choose 'vmap' or 'sharded'")
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    channel = make_channel(channel)
    state = init_state(problem, rng, hp, channel, algo)
    if w0 is not None:
        # the engine path DONATES the state; copy so the caller's w0 buffers
        # are never consumed (the loop path aliases them harmlessly)
        state = state._replace(
            params=jax.tree.map(jnp.array, w0) if chunk is not None else w0)
    if faults is not None and faults.active and faults.stale_rate > 0.0:
        # every client's lagged anchor starts at the actual starting point
        from repro.robust.faults import init_fault_comm

        state = state._replace(comm=init_fault_comm(
            state.comm, state.params, problem.clients.num_clients))
    if async_cfg is not None and async_cfg.active:
        # every client starts with an empty buffer (age 0)
        from repro.robust.async_agg import init_async_comm

        state = state._replace(comm=init_async_comm(
            state.comm, state.params, problem.clients.num_clients))
    if runtime == "sharded":
        from repro.core.sharded import make_sharded_round_fn

        if mesh is None:
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh()
        round_fn = make_sharded_round_fn(algo, problem, hp, mesh,
                                         channel=channel, faults=faults,
                                         async_cfg=async_cfg)
    else:
        round_fn = make_round_fn(algo, problem, hp, channel, faults=faults,
                                 async_cfg=async_cfg)

    sinks = list(sinks)
    run_info = {
        "algo": algo,
        "runtime": runtime,
        "channel": channel.name,
        "backend": jax.default_backend(),
        "num_clients": problem.clients.num_clients,
        "cohort_size": resolve_cohort_size(hp, problem.clients.num_clients),
        "uplink_bytes": uplink_byte_breakdown(
            channel, UPLINK_SCHEMAS[algo], state.params),
    }

    ckpt_mgr = None
    start_round = 0
    if checkpoint is not None or resume not in (None, "none"):
        from repro.checkpoint import (
            LOCAL_FS, CheckpointManager, load_checkpoint, load_latest,
        )

        ckpt_fs = checkpoint_fs if checkpoint_fs is not None else LOCAL_FS
        fingerprint = checkpoint_config_fingerprint(
            algo, runtime, channel.name, problem.clients.num_clients,
            run_info["cohort_size"], faults, async_cfg)
        if resume not in (None, "none"):
            # the freshly-initialized state (incl. fault-anchor/async-buffer
            # comm attachments) is the shape/dtype/sharding template
            if resume == "auto":
                if checkpoint is None:
                    raise ValueError(
                        'resume="auto" needs a checkpoint policy (it names '
                        "the directory to scan)")
                found = load_latest(checkpoint.directory, state, fs=ckpt_fs,
                                    expect_config=fingerprint)
            else:
                found = (load_checkpoint(resume, state, fs=ckpt_fs,
                                         expect_config=fingerprint))
            if found is not None:
                state, manifest = found
                start_round = int(manifest["round"])
        if checkpoint is not None:
            ckpt_mgr = CheckpointManager(
                checkpoint, config=fingerprint, fs=ckpt_fs,
                last_saved=start_round)

    if chunk is not None:
        if chunk < 1:
            # the CLIs map their 0-means-loop knob to None before calling;
            # a direct chunk=0 should not silently pick either path
            raise ValueError(
                f"chunk must be >= 1 (or None for the per-round loop), "
                f"got {chunk}")
        from repro.core import engine

        state, trace = engine.run_rounds(
            round_fn, state, max(0, num_rounds - start_round), chunk=chunk,
            w_star=w_star, frozen=problem.frozen,
            stop_rel_error=stop_rel_error, stop_grad_norm=stop_grad_norm,
            sinks=sinks, run_info=run_info, trace_capture=trace_capture,
            tap=tap, start_round=start_round, checkpoint=ckpt_mgr,
        )
        return History(
            algo=algo,
            rounds=np.arange(start_round, start_round + trace.num_rounds,
                             dtype=np.float64),
            loss=trace.loss,
            grad_norm=trace.grad_norm,
            rel_error=trace.rel_error,
            theta_mean=trace.theta_mean,
            comm_bytes=np.cumsum(trace.comm_bytes),
            wall_time=trace.wall_time,
            final_params=jax.device_get(state.params),
            channel=channel.name,
            gram_cond_max=trace.gram_cond_max,
            arrivals=trace.arrivals,
            staleness_mean=trace.staleness_mean,
            staleness_max=trace.staleness_max,
            aa_used_min=trace.aa_used_min,
        )

    round_fn = jax.jit(round_fn)
    if problem.frozen is not None:
        round_fn = partial(round_fn, frozen=problem.frozen)
    w_star_norm = None
    rel_fn = None
    if w_star is not None:
        w_star_norm = float(tm.tree_norm(w_star))
        # jit once, reuse every round: un-jitted tree_norm(tree_sub(...))
        # eagerly dispatched O(n_leaves) kernels per round
        rel_fn = jax.jit(lambda p: tm.tree_norm(tm.tree_sub(p, w_star)))

    from repro.obs.compiles import CompileCounter
    from repro.obs.sinks import (
        ROW_FIELDS, SCHEMA_VERSION, build_footer, build_round_row,
    )

    for s in sinks:
        s.open({
            "v": SCHEMA_VERSION, "kind": "header", "fields": list(ROW_FIELDS),
            "num_rounds": num_rounds, "chunk": None,
            "start_round": start_round, **run_info,
        })
    rows = []
    comm_total = 0.0
    t_total = 0.0
    stopped = False
    counter = CompileCounter()
    try:
        for t in range(start_round, num_rounds):
            if trace_capture is not None:
                trace_capture.on_chunk_start(t, 1)
            t0 = time.perf_counter()
            state, m = round_fn(state)
            m = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), m)
            dt = time.perf_counter() - t0
            t_total += dt
            mdict = {f: float(getattr(m, f)) for f in m._fields}
            comm_total += mdict["comm_bytes"]
            if rel_fn is not None:
                rel = float(rel_fn(state.params)) / max(w_star_norm, 1e-30)
            else:
                rel = float("nan")
            rows.append((t, mdict["loss"], mdict["grad_norm"], rel,
                         mdict["theta_mean"], mdict["gram_cond_max"],
                         comm_total, t_total, mdict["arrivals"],
                         mdict["staleness_mean"], mdict["staleness_max"],
                         mdict["aa_used_min"]))
            for s in sinks:
                s.emit([build_round_row(t, mdict, rel, comm_total, dt,
                                        t_total)])
            if trace_capture is not None:
                trace_capture.on_chunk_end(t + 1)
            if ckpt_mgr is not None:
                # loop path: no donation hazard, but the same snapshot-copy
                # save path as the engine (inline here, async per policy)
                ckpt_mgr.maybe_save(state, t + 1, dt)
            if not np.isfinite(m.loss):
                stopped = True
                break
            if stop_rel_error is not None and rel < stop_rel_error:
                stopped = True
                break
            if stop_grad_norm is not None and m.grad_norm < stop_grad_norm:
                stopped = True
                break
            if any(getattr(s, "stop_requested", False) for s in sinks):
                stopped = True
                break
    finally:
        counter.close()
        if trace_capture is not None:
            trace_capture.close()
        if ckpt_mgr is not None:
            ckpt_mgr.finalize()
        alarms = [e for s in sinks for e in getattr(s, "events", [])]
        if ckpt_mgr is not None:
            alarms.extend(ckpt_mgr.events)
        footer = build_footer(
            len(rows), stopped, alarms,
            checkpoint=ckpt_mgr.telemetry() if ckpt_mgr is not None
            else None, compiles=counter.compiles)
        for s in sinks:
            s.close(footer)

    arr = np.asarray(rows, dtype=np.float64)
    if arr.size == 0:
        # resumed at (or past) the round budget: nothing left to run
        arr = arr.reshape(0, 12)
    return History(
        algo=algo,
        rounds=arr[:, 0],
        loss=arr[:, 1],
        grad_norm=arr[:, 2],
        rel_error=arr[:, 3],
        theta_mean=arr[:, 4],
        comm_bytes=arr[:, 6],
        wall_time=arr[:, 7],
        final_params=jax.device_get(state.params),
        channel=channel.name,
        gram_cond_max=arr[:, 5],
        arrivals=arr[:, 8],
        staleness_mean=arr[:, 9],
        staleness_max=arr[:, 10],
        aa_used_min=arr[:, 11],
    )


def solve_reference(
    problem: FLProblem, iters: int = 2000, tol: float = 1e-12
) -> Pytree:
    """Compute w* to high precision with centralized Newton-CG (for the
    relative-error metric). Works for any smooth strongly-convex problem."""
    from repro.core.algorithms import _cg_solve
    from repro.core.problem import ClientBatch

    params = problem.init(jax.random.PRNGKey(0))

    @jax.jit
    def newton_step(w):
        g = problem.global_grad(w)

        def matvec(v):
            # global HVP = weighted sum of client HVPs
            hv = jax.vmap(lambda x, y, m: problem.hvp(w, ClientBatch(x, y, m), v))(
                problem.clients.x, problem.clients.y, problem.clients.mask
            )
            return jax.tree.map(
                lambda h: jnp.tensordot(problem.clients.weight, h, axes=1), hv
            )

        p = _cg_solve(matvec, g, 100)
        return tm.tree_sub(w, p), tm.tree_norm(g)

    for _ in range(iters):
        params, gnorm = newton_step(params)
        if float(gnorm) < tol:
            break
    return params
