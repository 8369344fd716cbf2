"""On-demand profiler trace windows around engine chunk execution.

``TraceCapture`` wraps the drivers' chunk (or round) boundaries in
``jax.profiler.start_trace``/``stop_trace``. Because the engine executes
whole chunks inside one jit, the window is aligned OUTWARD to chunk
boundaries: asking for rounds [T, T+N) starts the trace before the first
chunk that overlaps the window and stops it after the first chunk boundary
at or past T+N.

What a window holds, all on the profiler's one clock:

  * device time by round phase — the ``jax.named_scope`` annotations that
    reach each compiled instruction's ``op_name``: ``fl.cohort_plan``,
    ``fl.cohort_gather``, ``fl.anchor_grad`` (the anchor gradient each round
    sends up), ``fl.local_trajectory``, ``fl.aa_step``, ``fl.uplink``,
    ``fl.psum`` (sharded), ``fl.scatter``, ``fl.round_metrics`` (loss, norms
    and AA health reduced for the row) and ``fl.stop_check`` (the engine's
    rel-error, live/stop select and stop test) in core/algorithms.py,
    core/anderson.py, core/sharded.py and core/engine.py;
  * the Pallas kernels by name (``pallas_call(name=...)``):
    ``fl_local_trajectory_kernel`` (kernels/local_update), ``aa_gram_kernel``
    and ``aa_update_kernel`` (kernels/anderson);
  * host spans (``jax.profiler.TraceAnnotation``), which tell the device's
    idle gaps apart: ``fl.init_state`` around a run's first state, and per
    chunk a ``fl.chunk`` step span (``step_num`` = global chunk index) with
    children ``fl.engine.dispatch`` (enqueue the compiled chunk),
    ``fl.engine.wait`` (until its results are ready), ``fl.engine.fetch``
    (the one device→host transfer) and ``fl.engine.rows`` (row building,
    sinks, alarms, checkpoint hook). A window opens before a chunk's span
    and closes after it, so it holds whole chunks.

Two arming modes:

  * static window — ``TraceConfig(start_round=T, num_rounds=N)`` (the
    ``fl_train --trace-rounds N --trace-start T`` path);
  * trigger file — touch ``TraceConfig.trigger_file`` while a long run is in
    flight and the next chunk gets traced (the file is consumed/unlinked so
    each touch yields one window).

The profiler writes ``<dir>/plugins/profile/<ts>/<host>.xplane.pb``;
``jax.profiler.ProfileData.from_file`` reads it (planes, lines, events).
"""
from __future__ import annotations

import logging
import os
from dataclasses import dataclass

import jax

logger = logging.getLogger("repro.obs.profiling")


@dataclass(frozen=True)
class TraceConfig:
    """Trace-window request. ``num_rounds=0`` with no trigger file disables
    capture entirely (the drivers skip constructing a TraceCapture)."""

    trace_dir: str
    start_round: int = 0
    num_rounds: int = 0
    trigger_file: str | None = None

    @property
    def enabled(self) -> bool:
        return self.num_rounds > 0 or self.trigger_file is not None


class TraceCapture:
    """Chunk-boundary state machine driving jax.profiler.trace windows.

    Drivers call ``on_chunk_start(first_round, n_live)`` before launching a
    chunk and ``on_chunk_end(next_round)`` after its host sync; the per-round
    loop uses the same hooks with ``n_live=1``. ``close()`` is a safety stop
    for early exits so a run never leaks an open profiler session.
    """

    def __init__(self, config: TraceConfig):
        self.config = config
        self.active = False
        self.windows: list[tuple[int, int]] = []
        self._started_at: int | None = None
        # remaining static window; trigger file arms one extra chunk window
        self._pending_start = config.start_round
        self._pending_rounds = config.num_rounds

    def _trigger_pulled(self) -> bool:
        path = self.config.trigger_file
        if not path or not os.path.exists(path):
            return False
        try:
            os.unlink(path)
        except OSError:
            pass
        return True

    def on_chunk_start(self, first_round: int, n_live: int) -> None:
        if self.active:
            return
        window_hit = (
            self._pending_rounds > 0
            and first_round + n_live > self._pending_start
            and first_round < self._pending_start + self._pending_rounds
        )
        if window_hit:
            stop_after = self._pending_start + self._pending_rounds
        elif self._trigger_pulled():
            stop_after = first_round + n_live
        else:
            return
        os.makedirs(self.config.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.config.trace_dir)
        self.active = True
        self._started_at = first_round
        self._stop_after = stop_after
        logger.info("trace started at round %d (stop after round %d) -> %s",
                    first_round, stop_after - 1, self.config.trace_dir)

    def on_chunk_end(self, next_round: int) -> None:
        if not self.active or next_round < self._stop_after:
            return
        jax.profiler.stop_trace()
        self.active = False
        self.windows.append((self._started_at, next_round))
        if self._pending_rounds > 0 and next_round >= (
                self._pending_start + self._pending_rounds):
            self._pending_rounds = 0  # static window fully covered
        logger.info("trace stopped before round %d", next_round)

    def close(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self.windows.append((self._started_at, -1))


__all__ = ["TraceCapture", "TraceConfig"]
