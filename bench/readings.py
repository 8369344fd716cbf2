"""Readings that set a cell's correctness limit, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... \
        --control-seeds 101,102,103 --seconds <s> [--out <file.json>]

For each seed of ``--seeds`` it runs the cell's window as ``run.py`` does
(same set-up, runner and job loop, one set-up shared by all seeds) and reads
the numbers ``correct`` compares: the lower readings. For each seed of
``--control-seeds`` it puts the control in the program's place (the plain
reference of bench/control.py computed wholly in bfloat16: data, weights,
gradients, trajectories and AA step; only its [L, L] Gram solve in float32)
and reads the same numbers over as many jobs: the upper readings. Two
witnesses are read once, one job each with a budget of ``WITNESS_BUDGET``
rounds: the reference in float32 under full matmul precision, which must
reach the target (the control fails by its precision alone), and the
reference in float32 with its dot products in one bfloat16 pass (the TPU's
default matmul precision), the milder cut a later change might make. Needs
an accelerator, like run.py.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench.run import setup_jax  # noqa: E402


def _ints(s: str) -> list:
    return [int(v) for v in s.split(",") if v]


#: round budget of the witness jobs: enough for the bfloat16-dot reference
#: to reach the target, so its rounds are read and not cut off
WITNESS_BUDGET = 200


def read_witness(cell, inputs, dtype, precision: str) -> dict:
    """One job of the plain reference in ``dtype`` under ``precision``."""
    import jax

    from bench import control

    traffic = dict(cell.traffic, round_budget=WITNESS_BUDGET)
    with jax.default_matmul_precision(precision):
        init, runner = control.as_program(inputs, cell.config, traffic, dtype)
        t0 = time.perf_counter()
        jobs, _ = H.run_window(
            H.Program(init, runner, H.model_module(cell.config)), traffic,
            H.job_keys(0), 0.0, max_jobs=1)
    return {"dtype": str(np.dtype(dtype)),
            "precision": precision, "rounds": jobs[0].rounds,
            "reached": jobs[0].reached, "seconds": time.perf_counter() - t0,
            **{k: v["value"] for k, v in
               H.check_jobs(cell, jobs, inputs).items()}}


def read_window(prog, cell, inputs, seed: int, seconds: float) -> dict:
    jobs, window_s = H.run_window(prog, cell.traffic, H.job_keys(seed),
                                  seconds)
    checks = H.check_jobs(cell, jobs, inputs)
    return {"seed": seed, "jobs": len(jobs),
            "failed": sum(not j.reached for j in jobs),
            "rounds": [j.rounds for j in jobs], "window_s": window_s,
            **{k: v["value"] for k, v in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    jax = setup_jax()
    import jax.numpy as jnp

    from bench import control

    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        H.log("readings: needs the cell's chips on an accelerator")
        return 2
    inputs = H.make_inputs(cell)
    prog = H.build_program(cell, inputs, devices[:cell.chips])
    H.warm_up(prog, cell.traffic, 0)
    out = {"workload": cell.name, "program": [], "control": []}
    for seed in args.seeds:
        r = read_window(prog, cell, inputs, seed, args.seconds)
        H.log(f"program seed {seed}: {r}")
        out["program"].append(r)
    del prog
    if args.control_seeds:
        init, runner = control.as_program(inputs, cell.config, cell.traffic,
                                          jnp.bfloat16)
        ctl = H.Program(init, runner, H.model_module(cell.config))
        H.warm_up(ctl, cell.traffic, 0)
        for seed in args.control_seeds:
            r = read_window(ctl, cell, inputs, seed, args.seconds)
            H.log(f"control seed {seed}: {r}")
            out["control"].append(r)
        for name, dtype, precision in (
                ("reference_f32", jnp.float32, "highest"),
                ("reference_bf16_dots", jnp.float32, "bfloat16")):
            out[name] = read_witness(cell, inputs, dtype, precision)
            H.log(f"{name}: {out[name]}")
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
