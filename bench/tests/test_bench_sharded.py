"""The four-chip runtime on four virtual CPU devices, and the phase readers
of the new cells from a synthetic trace summary.

The sharded run needs its device count fixed before JAX starts, so it runs
in a child process (``XLA_FLAGS=--xla_force_host_platform_device_count=4``):
a tiny covtype split over a ``data=4`` mesh through ``run.run_cell``, which
must read ``correct`` with no compile in the window, and whose runner HLO
holds the psum's all-reduce under ``fl.psum``."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness as H
from bench import trace as T

CHILD = r"""
import json, sys
sys.path[:0] = [".", "src"]
import jax
from bench import harness as H, run, trace as T
from bench.tests import tiny
cell = tiny.cell(num_clients=8, runtime="sharded", round_budget=60)
cell = H.Cell("tiny-x4", 4, cell.config, cell.traffic, [], [])
devices = jax.devices()[:4]
res = run.run_cell(cell, 2**31 + 61, 0.0, False, devices, 0.0,
                   H.load_peaks("TPU v5 lite"))
inputs = H.make_inputs(cell)
prog = H.build_program(cell, inputs, devices)
H.warm_up(prog, cell.traffic, 5)
scopes = T.scope_map(prog.hlo_texts(cell.traffic["chunk"]))
res["psum_ops"] = sorted(n for n, s in scopes.items() if s == "fl.psum")
print(json.dumps(res))
"""


def test_sharded_runtime_runs_a_cell_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=H.ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "compiles in window 0" in out.stderr
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert any(n.startswith("all-reduce") for n in res["psum_ops"])


def _ctx(cell_name: str, phase_s: dict, slots: int = 4) -> H.Context:
    cell = H.load_cell(cell_name)
    summary = T.TraceSummary(window_s=1.0, busy_s=0.5, phase_s=phase_s,
                             top_ops=[], idle_gaps=[], notes=[])
    jobs = [H.Job(rounds=slots, slots=slots, reached=True, answer=None)]
    return H.Context(cell, jobs, 1.0, 1.0, H.load_peaks("TPU v5 lite"),
                     summary)


def _read(name: str, ctx):
    return H.load_module(H.BENCH / "metrics" / f"{name}.py").read(ctx)


def test_phase_ms_psum_reads_the_slowest_chip():
    ctx = _ctx("covtype-k100-x4", {"fl.psum": [0.004, 0.012, 0.008, 0.008]})
    assert _read("phase_ms.psum", ctx) == pytest.approx(1e3 * 0.012 / 4)
    assert _read("phase_ms.psum", _ctx("covtype-k100-x4", {})) is None


def test_lm_phase_readers_read_their_scopes():
    ctx = _ctx("granite-h-micro-k4-s4096",
               {"fl.lm_ssd": [2.0], "fl.lm_attention": [0.4]}, slots=2)
    assert _read("phase_ms.lm_ssd", ctx) == pytest.approx(1e3)
    assert _read("phase_ms.lm_attention", ctx) == pytest.approx(200.0)
    flops, nbytes = ctx.work()["lm_ssd"]
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("roofline.lm_ssd", ctx) == pytest.approx(100 * least / 1.0)
    # silent without the scope: the parent's program has none
    assert _read("roofline.lm_ssd", _ctx("granite-h-micro-k4-s4096", {})) \
        is None


def test_lm_ssd_counts_follow_the_shapes():
    """One sequence-layer forward of the chunked scan at the cell's shapes
    (S 4096, chunk 256, 64 heads of 64, state 128), times 9 Mamba layers
    and 52 passes a round (16 gradients at 3 passes, 4 losses at 1)."""
    ctx = _ctx("granite-h-micro-k4-s4096", {})
    flops, nbytes = ctx.work()["lm_ssd"]
    nc, Q, H_, P, N, S = 16, 256, 64, 64, 128, 4096
    fwd = (2 * nc * Q * Q * N + 2 * nc * Q * Q * H_ * P
           + 4 * nc * Q * H_ * P * N + 3 * nc * Q * Q * H_)
    assert flops == 52 * 9 * fwd
    assert nbytes == 52 * 9 * 4 * (2 * S * H_ * P + S * H_ + 2 * S * N)
