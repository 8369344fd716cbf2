"""Device-resident round engine: multi-round scan chunking with donated state.

The seed driver (core/server.py) dispatched ONE jit per aggregation round and
host-synced every metric — per-round Python/dispatch overhead plus a blocking
device→host transfer per round, with the K×d-heavy ServerState (params +
control variates + per-client EF residuals + diff-coding refs) re-uploaded
conceptually every call. This engine compiles ``chunk`` rounds into one XLA
computation:

  * ``jax.lax.scan`` over the rounds, so B rounds are one dispatch;
  * the ServerState argument is DONATED (``donate_argnums``), so XLA reuses
    the K×d client-state buffers in place instead of doubling peak memory —
    this holds for the sharded runtime too, whose round_fn carries the
    stacked per-client buffers through shard_map;
  * per-round ``RoundMetrics`` (plus the rel-error against a device-resident
    ``w_star``) stack ON DEVICE; the host syncs once per chunk;
  * stopping criteria — rel-error target, grad-norm target, non-finite
    loss — are evaluated IN-GRAPH: once one fires, the carried state passes
    through the remaining rounds of the chunk untouched (a leaf-wise
    select), so the final state is identical to the per-round loop that
    breaks immediately.

Stop criteria therefore resolve at CHUNK granularity from the host's point
of view (the driver learns about the stop one chunk-sync later) but at ROUND
granularity numerically: no extra round is ever applied to the carried
state, and the emitted per-round rows are exactly the rows the Python loop
would have produced (guarded by tests/test_engine.py in both runtimes).

Why a select and not ``lax.cond``: the scan body applies the round
UNCONDITIONALLY and selects between old and new state afterwards. Measured
on this container, that keeps the chunked round BIT-EXACT with the
standalone per-round jit — wrapping the round in a runtime-predicated cond
changes XLA's fusion choices by an ulp, which the ill-conditioned AA Gram
solve then amplifies arbitrarily (the same chaos documented for
vmap-vs-sharded agreement in core/sharded.py). The price is that scan slots
past an early stop (or past ``n_live`` in a short final chunk) burn a
round's FLOPs on a discarded result — bounded by chunk−1 rounds per run,
zero when no stop criterion fires and chunk divides num_rounds.

``run_rounds`` works with any ``round(state) -> (state, RoundMetrics)`` —
the vmap runtime's ``make_round_fn`` and the sharded runtime's
``make_sharded_round_fn`` alike. Pass the UN-jitted round function; the
engine owns the jit (and its donation).

Cohort rounds compose with all of the above: a round_fn built with
``cohort_size`` (or participation < 1) gathers its C sampled rows from the
K-sized client store inside the scan body and scatters the updated rows
back (core/client_store.py), so donation still reuses the O(K·d) store in
place while each scan slot computes O(C·d). The live/stop select passes
untouched store fields through by OBJECT IDENTITY (see tree_math.tree_where)
— no [K, ...] select op enters the compiled chunk, which is what the
no-dense-compute jaxpr assertion in tests/test_cohort.py pins.

NOTE donation semantics: with ``donate=True`` (default) the caller's input
``state`` buffers are consumed by the first chunk — re-init (same PRNGKey
gives an identical state) if the initial state is needed afterwards.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.compiles import CompileCounter
from repro.utils import tree_math as tm

Pytree = Any

_span = jax.profiler.TraceAnnotation


#: RoundMetrics fields mirrored into RoundTrace columns, in order — the
#: engine reads them off the stacked metrics generically, so a new device-side
#: metric becomes a trace column (and a telemetry row field) by being added to
#: RoundMetrics and here.
METRIC_FIELDS = (
    "loss", "grad_norm", "theta_mean", "gram_cond_max", "gram_cond_mean",
    "aa_used_min", "aa_clipped_max", "cohort_ess", "comm_bytes",
    "arrivals", "staleness_mean", "staleness_max",
)


@dataclasses.dataclass
class RoundTrace:
    """Per-round history of an engine run (host-side numpy, one row per
    EXECUTED round — padded/skipped scan slots are dropped)."""

    loss: np.ndarray           # [T]
    grad_norm: np.ndarray      # [T]
    theta_mean: np.ndarray     # [T]
    gram_cond_max: np.ndarray  # [T]
    gram_cond_mean: np.ndarray # [T]
    aa_used_min: np.ndarray    # [T]
    aa_clipped_max: np.ndarray # [T] clip_rtol screen activity (nan if n/a)
    cohort_ess: np.ndarray     # [T]
    comm_bytes: np.ndarray     # [T] per-round (NOT cumulative) wire bytes
    arrivals: np.ndarray       # [T] deadline-gated landings (nan: async off)
    staleness_mean: np.ndarray # [T] mean landed buffer age (nan if n/a)
    staleness_max: np.ndarray  # [T] oldest landed buffer age (nan if n/a)
    rel_error: np.ndarray      # [T] ‖w−w*‖/‖w*‖ (nan when w_star not given)
    round_wall: np.ndarray     # [T] seconds attributed to this round (each
                               # chunk's measured wall time divided equally
                               # over its executed rounds)
    wall_time: np.ndarray      # [T] cumulative seconds
    stopped: bool              # a stop criterion fired (vs round budget spent)
    computed_rounds: int       # scan slots dispatched: chunk × chunks run,
                               # with the slots past a stop or past the budget
    chunk_compiles: np.ndarray # [chunks] backend compiles during each chunk

    @property
    def num_rounds(self) -> int:
        return len(self.loss)


def make_chunk_runner(
    round_fn: Callable,
    chunk: int,
    *,
    w_star: Pytree | None = None,
    stop_rel_error: float | None = None,
    stop_grad_norm: float | None = None,
    donate: bool = True,
    tap: Callable | None = None,
):
    """Compile ``chunk`` rounds of ``round_fn`` into one donated jit.

    Returns ``runner(state, n_live) -> (state, done, metrics, rel, live)``:
      state   — after min(n_live, first-stop) rounds; the INPUT state buffers
                are donated (consumed) when ``donate``;
      done    — scalar bool: a stop criterion fired inside the chunk;
      metrics — RoundMetrics stacked [chunk];
      rel     — [chunk] f32 rel-error after each round (nan w/o w_star);
      live    — [chunk] bool: the round's result entered the carried state.
                Non-live slots (past ``n_live`` or past a stop) computed a
                round on the frozen state and DISCARDED it — their metric
                rows are garbage and must be dropped.

    ``n_live`` is a device scalar, so a short final chunk reuses the SAME
    executable (no recompile); slots with i >= n_live behave exactly like
    post-stop slots.

    ``tap`` — optional live tap (obs/sinks.LiveTap or any host callable
    ``(slot, metrics, rel, live)``) invoked via ``jax.debug.callback`` as
    each scan slot executes, for sub-chunk visibility into a long chunk.
    OFF by default: the callback re-enters the host mid-chunk, which is
    exactly what the one-sync-per-chunk contract otherwise rules out. It
    receives the compiled math's own values; note the inserted callback can
    shift XLA's fusion choices by an ulp (the same sensitivity documented
    above for lax.cond), so a tapped chunk matches the tapless one at the
    documented rtol 1e-6, not bit-exactly (pinned in tests/test_obs.py) —
    leave the tap off for runs that must be bit-reproducible.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    w_star_norm = (
        jnp.maximum(tm.tree_norm(w_star), 1e-30) if w_star is not None else None
    )

    def chunk_fn(state, n_live):
        def step(carry, i):
            s, done = carry
            # unconditional round + select (NOT lax.cond) — see module
            # docstring: this keeps the chunk bit-exact with the loop
            new_s, m = round_fn(s)
            with jax.named_scope("fl.stop_check"):
                if w_star is not None:
                    rel = (tm.tree_norm(tm.tree_sub(new_s.params, w_star))
                           / w_star_norm)
                else:
                    rel = jnp.full((), jnp.nan, jnp.float32)
                live = jnp.logical_and(~done, i < n_live)
                new_s = tm.tree_where(live, new_s, s)
                if tap is not None:
                    jax.debug.callback(tap, i, m, rel, live, ordered=False)
                # mirror the loop's break order: the row is emitted, THEN the
                # stop fires — so the stopping round's row is kept
                stop = ~jnp.isfinite(m.loss)
                if stop_rel_error is not None:
                    stop = jnp.logical_or(stop, rel < stop_rel_error)
                if stop_grad_norm is not None:
                    stop = jnp.logical_or(stop, m.grad_norm < stop_grad_norm)
                done = jnp.logical_or(done, jnp.logical_and(live, stop))
            return (new_s, done), (m, rel, live)

        (state, done), (ms, rels, lives) = jax.lax.scan(
            step, (state, jnp.zeros((), bool)), jnp.arange(chunk)
        )
        return state, done, ms, rels, lives

    return jax.jit(chunk_fn, donate_argnums=(0,) if donate else ())


def run_rounds(
    round_fn: Callable,
    state,
    num_rounds: int,
    *,
    chunk: int = 8,
    w_star: Pytree | None = None,
    stop_rel_error: float | None = None,
    stop_grad_norm: float | None = None,
    donate: bool = True,
    runner: Callable | None = None,
    tap: Callable | None = None,
    sinks=(),
    run_info: "dict | None" = None,
    trace_capture=None,
    start_round: int = 0,
    checkpoint=None,
):
    """Run up to ``num_rounds`` rounds in chunks of ``chunk``; one host sync
    per chunk. Returns ``(final_state, RoundTrace)`` — the state stays
    device-resident, the trace is host numpy with one row per executed round
    (identical to the per-round Python loop's rows), plus the scan slots
    dispatched and the backend compiles of each chunk.

    Each chunk runs under the host span ``fl.chunk`` (a profiler step span,
    ``step_num`` = global chunk index) with children ``fl.engine.dispatch``,
    ``fl.engine.wait``, ``fl.engine.fetch`` and ``fl.engine.rows``, so a
    profiler trace tells the device's idle gaps apart (obs/profiling.py).

    ``runner`` — optionally a prebuilt ``make_chunk_runner(...)`` whose
    compiled executable should be reused (e.g. pre-compiled via
    ``runner.lower(state, np.int32(n)).compile()`` so the trace excludes
    compile time). It MUST have been built from the same ``round_fn`` with
    the same chunk/stop configuration (incl. ``tap``); when omitted, one is
    built here.

    Telemetry (repro/obs — every hook is optional and None/() by default):
      tap           — live in-chunk callback, compiled into the runner (see
                      make_chunk_runner); ignored when ``runner`` is given.
      sinks         — MetricsSinks. Opened with a header row (run_info merged
                      in), fed one row per executed round from THIS chunk
                      sync — attaching sinks adds no device→host transfer and
                      leaves the chunk math untouched (pinned in
                      tests/test_obs.py) — and closed with a footer. A sink
                      whose ``stop_requested`` turns truthy (health alarms)
                      stops the run at the next chunk boundary.
      run_info      — extra header fields (algo/runtime/channel/uplink byte
                      breakdown — see core/server.py).
      trace_capture — obs/profiling.TraceCapture; notified at chunk
                      boundaries to open/close jax.profiler windows.
      start_round   — global index of the first round (resumed runs), offsets
                      the "round" field of emitted rows.
      checkpoint    — checkpoint/policy.CheckpointManager; its ``maybe_save``
                      is called at every chunk boundary (from THIS one host
                      sync — the save path copies the state's addressable
                      shards host-side and never calls jax.device_get, so the
                      one-sync-per-chunk contract holds with checkpointing
                      on), and it is finalized (in-flight save joined) when
                      the run ends. Its telemetry and alarm events ride the
                      footer.
    """
    from repro.obs.sinks import ROW_FIELDS, SCHEMA_VERSION, build_footer, \
        build_round_row

    chunk = max(1, min(chunk, num_rounds))
    if runner is None:
        runner = make_chunk_runner(
            round_fn, chunk, w_star=w_star, stop_rel_error=stop_rel_error,
            stop_grad_norm=stop_grad_norm, donate=donate, tap=tap,
        )
    sinks = list(sinks)
    for s in sinks:
        s.open({
            "v": SCHEMA_VERSION, "kind": "header", "fields": list(ROW_FIELDS),
            "num_rounds": num_rounds, "chunk": chunk,
            "start_round": start_round, **(run_info or {}),
        })
    cols: dict[str, list] = {f: [] for f in METRIC_FIELDS}
    rel_col: list[float] = []
    rw_col: list[float] = []
    wall_col: list[float] = []
    chunk_compiles: list[int] = []
    t_total = 0.0
    comm_total = 0.0
    executed = 0
    stopped = False
    counter = CompileCounter()
    try:
        while executed < num_rounds and not stopped:
            n_live = min(chunk, num_rounds - executed)
            # the profiler window opens and closes outside the chunk's span,
            # so a traced window holds whole fl.chunk spans
            if trace_capture is not None:
                trace_capture.on_chunk_start(start_round + executed, n_live)
            compiles0 = counter.compiles
            with jax.profiler.StepTraceAnnotation(
                    "fl.chunk",
                    step_num=start_round // chunk + len(chunk_compiles)):
                t0 = time.perf_counter()
                with _span("fl.engine.dispatch"):
                    state, *out = runner(state, np.int32(n_live))
                # waiting apart from the fetch adds no sync: device_get would
                # block on the same results
                with _span("fl.engine.wait"):
                    jax.block_until_ready(out)
                # the ONE host sync of this chunk
                with _span("fl.engine.fetch"):
                    done, ms, rels, lives = jax.device_get(out)
                elapsed = time.perf_counter() - t0
                with _span("fl.engine.rows"):
                    idx = np.flatnonzero(lives)
                    per_round = elapsed / max(len(idx), 1)
                    stacked = {f: np.asarray(getattr(ms, f))
                               for f in METRIC_FIELDS}
                    rows = []
                    for i in idx:
                        t_total += per_round
                        mrow = {f: float(stacked[f][i]) for f in METRIC_FIELDS}
                        comm_total += mrow["comm_bytes"]
                        for f in METRIC_FIELDS:
                            cols[f].append(mrow[f])
                        rel_col.append(float(rels[i]))
                        rw_col.append(per_round)
                        wall_col.append(t_total)
                        if sinks:
                            rows.append(build_round_row(
                                start_round + executed + len(rows), mrow,
                                float(rels[i]), comm_total, per_round,
                                t_total))
                    executed += len(idx)
                    stopped = bool(done)
                    for s in sinks:
                        s.emit(rows)
                    if any(getattr(s, "stop_requested", False) for s in sinks):
                        stopped = True
                    if checkpoint is not None:
                        # state buffers are about to be donated to the NEXT
                        # chunk: maybe_save snapshots host copies before
                        # dispatching the (async) write
                        checkpoint.maybe_save(state, start_round + executed,
                                              elapsed)
            chunk_compiles.append(counter.compiles - compiles0)
            if trace_capture is not None:
                trace_capture.on_chunk_end(start_round + executed)
    finally:
        counter.close()
        if trace_capture is not None:
            trace_capture.close()
        if checkpoint is not None:
            checkpoint.finalize()
        alarms = [e for s in sinks for e in getattr(s, "events", [])]
        if checkpoint is not None:
            alarms.extend(checkpoint.events)
        footer = build_footer(
            executed, stopped, alarms,
            checkpoint=checkpoint.telemetry() if checkpoint is not None
            else None, compiles=counter.compiles)
        for s in sinks:
            s.close(footer)
    trace = RoundTrace(
        **{f: np.asarray(cols[f]) for f in METRIC_FIELDS},
        rel_error=np.asarray(rel_col),
        round_wall=np.asarray(rw_col),
        wall_time=np.asarray(wall_col),
        stopped=stopped,
        computed_rounds=chunk * len(chunk_compiles),
        chunk_compiles=np.asarray(chunk_compiles, np.int64),
    )
    return state, trace
