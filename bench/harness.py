"""The benchmark's harness: one cell of ``BENCHMARK.json``, run once.

A cell names a configuration (``bench/configs/<file>.json``: the dataset and
model) and a traffic mix (``bench/traffic/<name>.json``: how the data is split
over clients and how each training job runs). The configuration's model is a
module of its own, ``bench/models/<model>.py`` (``model_module``): it makes
the cell's data and its reference, the program's problem and stop, and checks
the jobs' answers. A run builds those inputs, builds the program's round
engine once (``bench/runtimes/<runtime>.py``), runs one whole job as warm-up,
and then runs jobs back to back for the window's seconds. Each job starts
from ``init_state`` with its own key drawn from the run's seed and runs
through ``run_rounds`` on the one compiled runner until the model's stop
fires or the round budget is spent. Every metric is a reader in
``bench/metrics/<name>.py``.

Nothing here compiles for or touches a device until ``build_program``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from contextlib import nullcontext
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = BENCH / ".jax_cache"
#: one module per model, ``<model>.py`` (``model_module``)
MODELS = BENCH / "models"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # metric entries this cell reports with --trace 0
    per_layer: list       # ... and with --trace 1


def _reported(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, spec: dict | None = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json, with its configuration and
    traffic files read."""
    spec = load_json(ROOT / "BENCHMARK.json") if spec is None else spec
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; choose from "
                         f"{sorted(work)}")
    w = work[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(ROOT / cfg_entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=_reported(spec["end_to_end"], name),
        per_layer=_reported(spec["per_layer"], name))


# --------------------------------------------------------------------------
# the model: inputs, reference and check
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Inputs:
    data: object          # the model module's data (the clients' blocks)
    reference: object     # the model module's reference (logreg: w*)
    data_s: float         # seconds to make the data
    reference_s: float    # seconds to make the reference


def model_module(config: dict):
    """The module of the configuration's model, ``bench/models/<model>.py``.

    It defines:

    * ``make_inputs(config, traffic) -> Inputs``: the cell's data from the
      configuration and the traffic (never from the run's seed), and what
      of the reference is made before the window (logreg: w*, which its stop
      reads too), by code that imports nothing of the program; both timed,
      as set-up. A reference that needs the chip runs in ``check`` instead,
      after the window, once the program's state is freed.
    * ``problem(config, inputs)``: the program's ``FLProblem`` over the data.
    * ``stop(config, traffic, inputs) -> dict``: the keyword arguments of the
      runner's in-graph stop (``make_chunk_runner``); ``{}`` runs every job
      to the traffic's ``round_budget``.
    * ``answer(state)``: the small host value a job keeps from its final
      state, fetched once a job (under ``bench.job_end``); never the whole
      weights of a large model.
    * ``reached(trace) -> bool``: the job's answer came (its stop fired, or
      its fixed rounds ran), from the engine's ``RoundTrace``.
    * ``check(jobs, inputs, traffic) -> dict``: ``{name: {"value", "limit"}}``
      over the jobs' answers against the reference; the run is correct when
      every value is finite and at most its limit.
    """
    path = MODELS / f"{config['model']}.py"
    if not path.is_file():
        raise SystemExit(f"no module for model {config['model']!r}: add "
                         f"{path}")
    return load_module(path)


def make_inputs(cell: Cell) -> Inputs:
    """The cell's data and reference, made by its model's module."""
    return model_module(cell.config).make_inputs(cell.config, cell.traffic)


def check_jobs(cell: Cell, jobs: list, inputs: Inputs) -> dict:
    """The jobs' answers against the reference, by the cell's model's
    module: each number compared beside its limit."""
    return model_module(cell.config).check(jobs, inputs, cell.traffic)


# --------------------------------------------------------------------------
# the program under test
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Program:
    """What the job loop drives: ``init(key)`` makes a job's first state,
    ``runner`` is the compiled chunk runner (the engine's interface),
    ``model`` the model module that reads a job's answer and whether it
    came."""

    init: object
    runner: object
    model: object
    round_fn: object = None

    def hlo_texts(self, chunk: int) -> list:
        """The compiled HLO of the runner as the window calls it (the
        executable is in the runner's compile cache: nothing compiles)."""
        first = self.init(0)
        return [self.runner.lower(first, np.int32(chunk)).compile().as_text()]


def build_program(cell: Cell, inputs: Inputs, devices: list) -> Program:
    """The round function and the one chunk runner every job of the run
    uses, built by the traffic's runtime (``bench/runtimes/<runtime>.py``)."""
    runtime = load_module(BENCH / "runtimes" / f"{cell.traffic['runtime']}.py")
    return runtime.build(cell, inputs, devices)


@dataclasses.dataclass
class Job:
    rounds: int           # live rounds (rows of the run's trace)
    slots: int            # rounds computed, with those past the stop
    reached: bool         # the job's answer came (model.reached)
    answer: object        # the job's answer (model.answer)


def _annotate(name: str, on: bool):
    if not on:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_job(prog: Program, traffic: dict, key: int,
            spans: bool = False) -> Job:
    """One training job from ``init_state`` to the stop or the budget."""
    import jax

    from repro.core import run_rounds

    runner = prog.runner
    if spans:
        def runner(state, n_live, _run=prog.runner):
            with _annotate("bench.dispatch", True):
                out = _run(state, n_live)
            with _annotate("bench.sync", True):
                jax.block_until_ready(out)
            return out

    with _annotate("bench.job_init", spans):
        state = prog.init(key)
    state, trace = run_rounds(prog.round_fn, state, traffic["round_budget"],
                              chunk=traffic["chunk"], runner=runner)
    with _annotate("bench.job_end", spans):
        answer = prog.model.answer(state)
    chunk = traffic["chunk"]
    return Job(rounds=trace.num_rounds,
               slots=chunk * -(-trace.num_rounds // chunk),
               reached=bool(prog.model.reached(trace)), answer=answer)


def job_keys(seed: int):
    """The run's job keys, drawn from its seed (any non-negative int)."""
    rng = np.random.default_rng(seed)
    while True:
        yield int(rng.integers(0, 2**31 - 1))


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included: each is a
    compile request the jit cache missed) and persistent-cache hits."""

    def __init__(self):
        from jax._src import monitoring

        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, _secs, **_kw):
            if event == BACKEND_COMPILE_EVENT:
                self.compiles += 1

        def on_event(event, **_kw):
            if event == CACHE_HIT_EVENT:
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def warm_up(prog: Program, traffic: dict, key: int) -> tuple[float, float]:
    """Compile the runner (one chunk on a throwaway state) and run one whole
    job, so every program the window calls is compiled. Returns the seconds
    of each."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(prog.runner(prog.init(key),
                                      np.int32(traffic["chunk"])))
    t1 = time.perf_counter()
    run_job(prog, traffic, key)
    return t1 - t0, time.perf_counter() - t1


def run_window(prog: Program, traffic: dict, keys, seconds: float,
               max_jobs: int | None = None,
               spans: bool = False) -> tuple[list, float]:
    """Jobs back to back: a job starts while the window's seconds last (or,
    with ``max_jobs``, until that many ran). Returns (jobs, window seconds
    from the first job's start to the last one's end)."""
    jobs = []
    t0 = time.perf_counter()
    while True:
        if max_jobs is not None:
            if len(jobs) >= max_jobs:
                break
        elif jobs and time.perf_counter() - t0 >= seconds:
            break
        jobs.append(run_job(prog, traffic, next(keys), spans))
    return jobs, time.perf_counter() - t0


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def is_correct(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a metric reader reads. ``trace`` is bench/trace.TraceSummary in
    a traced run, else None."""

    cell: Cell
    jobs: list
    window_s: float
    setup_s: float
    peaks: dict
    trace: object = None

    @property
    def live_rounds(self) -> int:
        return sum(j.rounds for j in self.jobs)

    @property
    def slots(self) -> int:
        return sum(j.slots for j in self.jobs)

    def work(self) -> dict:
        """{phase: (flops, bytes)} one round requires (bench/counts; {} for
        a model with no counts)."""
        path = BENCH / "counts" / f"{self.cell.config['model']}.py"
        if not path.is_file():
            return {}
        return load_module(path).round_work(self.cell.config, self.cell.traffic)

    def phase_s_per_slot(self, scope: str, reduce=np.mean):
        """Device seconds per computed round under ``scope`` (over the
        chips, reduced with ``reduce``), or None when the trace has none."""
        if self.trace is None or self.slots == 0:
            return None
        per_chip = self.trace.phase_s.get(scope)
        if not per_chip or max(per_chip) <= 0.0:
            return None
        return float(reduce(per_chip)) / self.slots

    def roofline(self, scope: str, phase: str):
        """(share in %, bound) of ``phase``'s required work against the
        device time under ``scope`` per computed round, or None."""
        t = self.phase_s_per_slot(scope)
        work = self.work().get(phase)
        if t is None or work is None:
            return None
        flops, nbytes = work
        per_chip = 1.0 / self.cell.chips
        t_flops = flops * per_chip / self.peaks["bf16_flops_per_s"]
        t_bytes = nbytes * per_chip / self.peaks["hbm_bytes_per_s"]
        bound = "compute" if t_flops >= t_bytes else "bytes"
        least = max(t_flops, t_bytes)
        log(f"roofline {phase}: {bound}-bound, least {1e6 * least:.3f} us "
            f"against {1e3 * t:.4f} ms of device time a computed round")
        return 100.0 * least / t, bound


def load_module(path: Path):
    """The module at ``path``, registered in ``sys.modules`` under a name
    made from its directory and stem (so that a dataclass in it resolves its
    own module)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_metrics(entries: list, ctx: Context) -> dict:
    """Each metric's reader in bench/metrics/<name>.py; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def load_peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table[device_kind]
