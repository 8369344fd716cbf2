"""Beyond-paper: cohort-resident rounds — compute scales with C, not K.

The cohort memory model (core/client_store.py + the cohort plan in
core/algorithms.py) lets a round gather a sampled C-client cohort out of the
K-sized client store, compute on [C, ...] tensors only, and scatter the
updated rows back. This benchmark measures what that buys as the client
population grows: FedOSAA-SVRG engine rounds (core/engine.py, donated
lax.scan chunks) at fixed cohort size C=16 while K sweeps {32, 512, 4096},
against the dense all-K round at each K.

Two quantities per (K, mode) cell, both on the engine path:

  ms/round        — warm wall-time, interleaved reps, per-mode min (the
                    bench_round.py methodology);
  peak live bytes — XLA's own compiled-memory analysis of the chunk
                    executable (argument + output + temp − aliased), i.e.
                    what the compiled round body actually holds live. The
                    cohort row's temp bytes stay O(C·d) while the dense
                    row's grow with K; the O(K·d) client store itself sits
                    in the donated *argument* bytes either way.

The dense K=4096 cell is the honest baseline: it is exactly what every
round would cost without the cohort axis. Full runs commit the sweep to
``benchmarks/results/ext_cohort.json``; ``--smoke`` (the CI gate) runs a
reduced sweep to a scratch path so it never clobbers the committed numbers.

Standalone (not part of benchmarks/run.py's MODULES):

  PYTHONPATH=src python -m benchmarks.ext_cohort           # full sweep
  PYTHONPATH=src python -m benchmarks.ext_cohort --smoke   # CI gate
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core import (
    AlgoHParams,
    init_state,
    make_chunk_runner,
    make_round_fn,
    solve_reference,
)
from repro.data import make_binary_classification, partition
from repro.models.logreg import make_logreg_problem

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
#: the committed sweep (full mode only)
OUT_PATH = os.path.join(RESULTS_DIR, "ext_cohort.json")
#: --smoke scratch output — never clobbers the committed sweep
SMOKE_PATH = os.path.join(RESULTS_DIR, "ext_cohort_smoke.json")

ALGO = "fedosaa_svrg"
COHORT = 16


def _problem(num_clients: int):
    # 8 samples/client floor: the K=4096 convergence regime
    # (tests/test_cohort.py) — 2/client leaves local SVRG epochs too noisy
    n = max(2048, 8 * num_clients)
    X, y = make_binary_classification("synthetic_small", n=n, seed=0)
    clients = partition(X, y, num_clients=num_clients, scheme="iid")
    prob = make_logreg_problem(clients, gamma=1e-3)
    return prob, solve_reference(prob, iters=100)


def _hp(cohort: int | None) -> AlgoHParams:
    return AlgoHParams(eta=0.5, local_epochs=2, cohort_size=cohort)


def _memory(compiled) -> dict:
    """XLA's compiled-memory analysis of one chunk executable."""
    m = compiled.memory_analysis()
    arg = int(m.argument_size_in_bytes)
    out = int(m.output_size_in_bytes)
    tmp = int(m.temp_size_in_bytes)
    alias = int(m.alias_size_in_bytes)
    return {
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": tmp,
        "alias_bytes": alias,
        # what the executable holds live at once: donated args alias their
        # outputs, so the aliased bytes are counted a single time
        "peak_live_bytes": arg + out + tmp - alias,
    }


class _Mode:
    """One (K, cohort|dense) engine cell: a donated chunk runner plus its
    compiled-memory analysis, timed over warm chunks."""

    def __init__(self, prob, wstar, cohort, chunk):
        self.hp = _hp(cohort)
        self.chunk = chunk
        self.prob, self.wstar = prob, wstar
        round_fn = make_round_fn(ALGO, prob, self.hp)
        self.runner = make_chunk_runner(round_fn, chunk, w_star=wstar)
        state = init_state(prob, jax.random.PRNGKey(0), self.hp)
        self.memory = _memory(
            self.runner.lower(state, np.int32(chunk)).compile())
        out = self.runner(state, np.int32(chunk))   # compile + warm up
        jax.device_get(out[1:])
        self._warm = out[0]

    def time_rounds(self, rounds: int) -> float:
        n_chunks = max(rounds // self.chunk, 1)
        out = (self._warm,)
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            out = self.runner(out[0], np.int32(self.chunk))
            jax.device_get(out[1:])
        elapsed = time.perf_counter() - t0
        self._warm = out[0]
        return elapsed / (n_chunks * self.chunk)


def run(smoke: bool = False) -> dict:
    ks = (32, 128) if smoke else (32, 512, 4096)
    rounds = 2 if smoke else 8
    chunk = 2 if smoke else 4
    reps = 1 if smoke else 5
    rows = []
    for k in ks:
        prob, wstar = _problem(k)
        modes = {"cohort": _Mode(prob, wstar, COHORT, chunk),
                 "dense": _Mode(prob, wstar, None, chunk)}
        times = {name: [] for name in modes}
        for _ in range(reps):   # interleaved, min-taking (bench_round.py)
            for name, mode in modes.items():
                times[name].append(mode.time_rounds(rounds))
        t = {name: min(ts) for name, ts in times.items()}
        for name, mode in modes.items():
            rows.append({
                "algo": ALGO,
                "num_clients": k,
                "cohort": COHORT if name == "cohort" else None,
                "mode": name,
                "chunk": chunk,
                "rounds_timed": rounds,
                "reps": reps,
                "engine_s_per_round": t[name],
                **mode.memory,
            })
            print(f"K={k:5d} {name:6s} {t[name]*1e3:8.2f} ms/round  "
                  f"temp {mode.memory['temp_bytes']/2**10:9.1f} KiB  "
                  f"peak live {mode.memory['peak_live_bytes']/2**20:7.2f} MiB")
        rows[-2]["speedup_vs_dense"] = t["dense"] / t["cohort"]
        print(f"K={k:5d} cohort speedup vs dense: "
              f"{t['dense'] / t['cohort']:.2f}x")
    out = {
        "bench": "ext_cohort",
        "setup": {"algo": ALGO, "cohort_size": COHORT,
                  "dataset": "synthetic_small", "samples_per_client": 8,
                  "eta": 0.5, "local_epochs": 2,
                  "backend": jax.default_backend(),
                  "timing": "interleaved reps, per-mode min",
                  "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")},
        "smoke": smoke,
        "rows": rows,
    }
    path = SMOKE_PATH if smoke else OUT_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    run(smoke=ap.parse_args().smoke)
