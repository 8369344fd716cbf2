"""Round telemetry for the device-resident engine (ROADMAP: engine
observability).

Four layers, all fed from the ONE host sync per engine chunk — attaching
telemetry never adds a device→host transfer to the hot loop (pinned in
tests/test_obs.py):

  * ``sinks``     — MetricsSink protocol + in-memory / stdout / JSONL file
                    sinks with a versioned row schema, drained at chunk
                    boundaries by ``core/engine.run_rounds`` and per round by
                    the legacy loop in ``core/server.run_federated``; plus the
                    OFF-by-default ``LiveTap`` (a ``jax.debug.callback`` tap
                    inside the compiled scan for sub-chunk visibility; the
                    inserted callback perturbs XLA fusion at ulp level, so
                    tapped runs match tapless ones at rtol 1e-6 rather than
                    bit-exactly — see sinks.LiveTap).
  * ``profiling`` — on-demand ``jax.profiler.trace`` windows around chunk
                    execution ("trace rounds T..T+N", armed by flag or a
                    trigger file); device time is attributed to the
                    ``jax.named_scope`` round phases and the named Pallas
                    kernels, the device's idle gaps to the engine's host
                    spans (``fl.chunk``, ``fl.engine.*``, ``fl.init_state``;
                    the full list is in profiling.py).
  * ``compiles``  — backend compile counts from ``jax.monitoring``: the
                    engine records them per chunk (``RoundTrace``) and the
                    footer carries the run's total.
  * ``alarms``    — declarative health rules over the streamed rows
                    (non-finite loss, AA Gram conditioning, column-filtering
                    collapse, rel-error plateau) that log structured warnings
                    and can request early stop at the next chunk boundary.
"""
from repro.obs.alarms import (  # noqa: F401
    DEFAULT_RULES,
    AlarmMonitor,
    AlarmRule,
)
from repro.obs.compiles import CompileCounter  # noqa: F401
from repro.obs.profiling import (  # noqa: F401
    TraceCapture,
    TraceConfig,
)
from repro.obs.sinks import (  # noqa: F401
    ROW_FIELDS,
    SCHEMA_VERSION,
    JsonlSink,
    LiveTap,
    MemorySink,
    MetricsSink,
    StdoutSink,
    build_round_row,
    make_sink,
)
