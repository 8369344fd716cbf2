"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics (BENCHMARK.json
``end_to_end``); ``--trace 1`` runs the traffic's ``trace_jobs`` jobs under the
profiler and reports its per-layer metrics, with ``busy_s``/``window_s`` and a
breakdown. Information goes to standard error; the last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown``), and last ``checks``, each number
compared beside its limit. The run refuses to start (exit 2, no result)
without an accelerator or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """Place the compilation cache and import jax; returns the module."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(H.CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(H.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def device_info(devices) -> dict:
    import jax

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def traced_window(prog, cell, keys, devices):
    """The traffic's trace_jobs jobs under the profiler, reduced."""
    import jax

    from bench import trace as T

    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tdir)
        jobs, window_s = H.run_window(prog, cell.traffic, keys, 0.0,
                                      max_jobs=cell.traffic["trace_jobs"],
                                      spans=True)
        jax.profiler.stop_trace()
        scope_of = T.scope_map(prog.hlo_texts(cell.traffic["chunk"]))
        summary = T.reduce(T.find_xplane(tdir), [d.id for d in devices],
                           scope_of)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return jobs, window_s, summary


def run_cell(cell, seed: int, seconds: float, trace: bool, devices: list,
             t_start: float, peaks: dict | None = None) -> dict:
    """Everything a run does once its chips are found: set-up, warm-up, the
    window, the metrics and the comparison with the reference. Returns the
    result line's object. ``peaks`` defaults to the devices' kind's row of
    bench/peaks.json."""
    if peaks is None:
        peaks = H.load_peaks(devices[0].device_kind)
    counter = H.CompileCounter()
    inputs = H.make_inputs(cell)
    prog = H.build_program(cell, inputs, devices)
    keys = H.job_keys(seed)
    compile_s, warm_s = H.warm_up(prog, cell.traffic, next(keys))
    setup_s = time.perf_counter() - t_start
    H.log(f"setup_s {setup_s:.3f} = data {inputs.data_s:.3f} + reference "
          f"{inputs.reference_s:.3f} + compile {compile_s:.3f} + warm-up job "
          f"{warm_s:.3f} + imports/other; compiles {counter.compiles} "
          f"(persistent-cache hits {counter.cache_hits})")

    compiles0 = counter.compiles
    summary = None
    if trace:
        jobs, window_s, summary = traced_window(prog, cell, keys, devices)
    else:
        jobs, window_s = H.run_window(prog, cell.traffic, keys, seconds)
    H.log(f"window {window_s:.3f} s: {len(jobs)} jobs, "
          f"{sum(j.rounds for j in jobs)} rounds, "
          f"{sum(j.slots for j in jobs)} computed, compiles in window "
          f"{counter.compiles - compiles0}")
    device = device_info(devices)
    del prog

    ctx = H.Context(cell, jobs, window_s, setup_s, peaks, summary)
    metrics = H.read_metrics(cell.per_layer if trace else cell.end_to_end, ctx)
    checks = H.check_jobs(cell, jobs, inputs)
    result = {
        "correct": H.is_correct(checks),
        "attempted": len(jobs),
        "failed": sum(not j.reached for j in jobs),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
        for line in summary.notes:
            H.log(line)
    result["checks"] = checks
    for name, m in metrics.items():
        H.log(f"metric {name} = {m['value']!r} {m['unit']}")
    for name, c in checks.items():
        H.log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = H.load_cell(args.workload)
    jax = setup_jax()
    devices = jax.devices()
    if devices[0].platform == "cpu":
        H.log("bench: no accelerator found; refusing to run on the CPU")
        return 2
    if len(devices) < cell.chips:
        H.log(f"bench: cell {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}")
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
