"""Readings that set the LM cell's correctness limits, and its control, in
one process.

    python3 bench/control_lm.py --workload <cell> --seeds 1,2,... \
        [--out <file.json>]

For each seed it runs one job of the cell as ``run.py``'s window does (the
same set-up, runner and job loop, shared by all seeds; the job key drawn
from the seed) and reads the numbers ``correct`` compares
(``bench/models/granite_hybrid.py::compare``) two ways against the plain
reference in float32 under ``jax.default_matmul_precision("highest")``,
which also trains the job again from its first adapter:

* ``program``: the program itself, as ``check`` reads it: the lower
  readings;
* ``control``: the reference computed wholly in bfloat16 (weights,
  adapters, activations, scan, softmax, loss, its own training job; only
  the Anderson step's 3×3 solve in float64), put in the program's place,
  the precision below the float32 activations the configuration states:
  the upper readings, with the harness's ``is_correct`` on them, which
  must read false.

Each seed prints one JSON line; the last line is the summary: each number's
largest program reading and smallest control reading, and how many seeds
each side read ``correct``. Needs an accelerator, like run.py.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench.run import setup_jax  # noqa: E402


def readings(cell, seeds: list, out_path: str = "") -> list:
    import jax.numpy as jnp

    model = H.model_module(cell.config)
    inputs = H.make_inputs(cell)
    prog = H.build_program(cell, inputs, None)
    data = inputs.data
    config, traffic = cell.config, cell.traffic
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        job = H.run_job(prog, traffic, next(H.job_keys(seed)))
        job_s = time.perf_counter() - t0
        w0 = data.kept["first"]()
        got = model.program_readings(config, data, [job])
        ref = model.reference_readings(config, data, w0, got.adapter,
                                       traffic, jnp.float32, "highest")
        z, g = model.reference_side(config, data, got.adapter, jnp.bfloat16,
                                    "default")
        losses, w = model.reference_rounds(config, data, w0, traffic,
                                           jnp.bfloat16, "default")
        control = model.Readings(z, g, [losses[0]], losses[1], w)
        row = {"seed": seed, "loss": job.answer["loss"],
               "reference_loss": [ref.first_losses[0], ref.second_loss],
               "control_loss": losses}
        for name, side, rows in (("program", got, job.answer["loss"]),
                                 ("control", control, losses)):
            checks = model.compare(side, ref, w0, traffic)
            checks.update(model.progress([rows]))
            row[name] = checks
            row[name + "_correct"] = H.is_correct(checks)
        row["job_s"] = job_s
        row["seconds"] = time.perf_counter() - t0
        H.log(json.dumps(row))
        out.append(row)
        if out_path:     # after every seed, so a cut run keeps its rows
            Path(out_path).parent.mkdir(parents=True, exist_ok=True)
            Path(out_path).write_text(json.dumps({"rows": out}, indent=1))
    return out


def summary(rows: list) -> dict:
    out = {}
    for name in rows[0]["program"]:
        out[name] = {
            "program_max": max(r["program"][name]["value"] for r in rows),
            "control_min": min(r["control"][name]["value"] for r in rows),
            "limit": rows[0]["program"][name]["limit"]}
    for side in ("program", "control"):
        out[side + "_correct"] = sum(r[side + "_correct"] for r in rows)
    out["seeds"] = len(rows)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    jax = setup_jax()
    if jax.devices()[0].platform == "cpu":
        H.log("control_lm: no accelerator found; refusing to run on the CPU")
        return 2
    rows = readings(cell, [int(s) for s in args.seeds.split(",") if s],
                    args.out)
    device = jax.devices()[0]
    result = {"rows": rows, "summary": summary(rows),
              "device": device.device_kind,
              "peak_bytes_in_use": (device.memory_stats() or {}).get(
                  "peak_bytes_in_use")}
    if args.out:
        Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result["summary"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
