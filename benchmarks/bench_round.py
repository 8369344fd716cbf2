"""Round-engine benchmark: measured wall-time/round, before vs after.

The seed driver paid per-round dispatch + host-sync overhead on every
aggregation round (one jit call, one blocking metric transfer, an UN-jitted
host rel-error — core/server.py pre-engine). This benchmark measures that
cost directly against the device-resident round engine (core/engine.py) on
the quick covtype setup, and commits the numbers to ``BENCH_round.json`` at
the repo root — the perf trajectory future PRs extend.

Methodology: every mode runs the same problem from the same initial state;
compile + warmup excluded; the three modes are re-measured INTERLEAVED for
several repetitions and the per-mode minimum is reported (robust to the
noisy-neighbor variance of this shared container — spreads of 2–3× between
repetitions were observed on idle cores).

Three timed modes per (algo × runtime × channel × local_impl) cell:

  seed_loop — faithful re-enactment of the seed per-round loop: jit dispatch
              per round, per-round host metric sync, eagerly-dispatched
              host rel-error — and the SEED trajectory form
              (``LOCAL_IMPL_SEED``: autodiff residuals with the pre-PR5
              concatenate epilogue + standalone r_L dispatch), so the
              committed "vs seed" numbers stay comparable across PRs;
  loop      — this PR's per-round loop (rel-error jitted once; still one
              dispatch + one sync per round);
  engine    — chunked lax.scan with donated state, metrics stacked on
              device, ONE host sync per chunk.

The ``local_impl`` axis covers the fused dual-gradient local-trajectory
path (kernels/local_update) on every eligible vmap cell: "tree" is the
autodiff residual (two loss autodiffs = four X sweeps per local step),
"pallas" the fused path — which on CPU executes the bit-exact fused jnp
oracle (ref.py), the same algorithm the TPU kernel runs (one X sweep per
step, hoisted anchor coefficients), so its win here is algorithmic
(sweep/FLOP reduction), not a kernel-emulation artifact. GIANT and the
sharded runtime have no fused path and carry "tree" rows only.

A separate micro-row exercises ``aa_impl="pallas"`` END-TO-END (full
fedosaa rounds through the fused single-pass Gram/update kernels, interpret
mode on CPU) and records its parity against the tree path — correctness
evidence, not a CPU speed claim: the fused kernels' win is HBM traffic on
TPU, while interpret mode is a Python-loop emulation. A second micro-row
does the same for ``local_impl="pallas"``: rel-error traces of full fused
rounds against the tree path (both reach the same floor; round-level
trajectories through the unregularized AA Gram solve are ulp-chaotic, see
tests/test_local_update.py) plus the ops-level trajectory parity.

  PYTHONPATH=src python -m benchmarks.bench_round            # full grid
  PYTHONPATH=src python -m benchmarks.bench_round --smoke    # CI gate
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

from repro.core import (
    AlgoHParams,
    init_state,
    make_chunk_runner,
    make_round_fn,
)
from repro.core.sharded import make_sharded_round_fn
from repro.launch.mesh import make_host_mesh
from repro.utils import tree_math as tm
from repro.utils.compile_cache import use_compile_cache

from benchmarks.common import logreg_setup

#: the committed perf-trajectory artifact (full grid only; see SMOKE_PATH)
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_round.json")
#: --smoke output: a scratch path, so CI/dev gate runs never clobber the
#: committed full-grid trajectory with 2-rep smoke numbers
SMOKE_PATH = os.path.join(os.path.dirname(__file__), "results",
                          "BENCH_round_smoke.json")

ALGOS = ("fedosaa_svrg", "fedosaa_scaffold", "giant")
RUNTIMES = ("vmap", "sharded")
CHANNELS = ("identity", "int8")


def _local_impls(algo: str, runtime: str) -> tuple:
    """The local_impl axis of one (algo, runtime) cell: fused rows exist
    only where the fused path can activate (trajectory algos, vmap)."""
    from repro.core import TRAJECTORY_ALGOS

    if runtime == "vmap" and algo in TRAJECTORY_ALGOS:
        return ("tree", "pallas")
    return ("tree",)


def _hp(local_impl: str = "tree", cohort: int | None = None) -> AlgoHParams:
    # fig6's quick-covtype hyperparameters for every cell (η=1, L=10 —
    # L doubles as GIANT's CG iteration count), so the timer bases agree
    # across benchmarks
    return AlgoHParams(eta=1.0, local_epochs=10, local_impl=local_impl,
                       cohort_size=cohort)


def _make_round_fn(algo, prob, hp, runtime, channel, mesh):
    if runtime == "sharded":
        return make_sharded_round_fn(algo, prob, hp, mesh, channel=channel)
    return make_round_fn(algo, prob, hp, channel)


def _fresh_state(prob, hp, channel, algo):
    return init_state(prob, jax.random.PRNGKey(0), hp, channel, algo)


class _Cell:
    """One (algo × runtime × channel × local_impl) cell: three interleavable
    timed modes over identical rounds from identical states. The seed-loop
    re-enactment always runs the seed trajectory form (LOCAL_IMPL_SEED);
    loop and engine run the cell's local_impl. Sibling
    tree/pallas cells of one (algo, runtime, channel) share ONE seed-loop
    measurement (it is the same computation), taken interleaved with both —
    see _bench_cell."""

    def __init__(self, prob, wstar, algo, runtime, channel, mesh, rounds,
                 chunk, local_impl="tree", seed_cell=None, cohort=None):
        # cohort cells time the sampled-cohort round (AlgoHParams.cohort_size)
        # in loop/engine; the seed replay below stays DENSE — "vs seed"
        # then measures cohort compute reduction + engine against the true
        # pre-cohort driver
        hp = _hp(local_impl, cohort)
        self.prob, self.hp, self.algo, self.channel = prob, hp, algo, channel
        self.rounds, self.chunk = rounds, chunk
        self.wstar = wstar
        self.wstar_norm = float(tm.tree_norm(wstar))
        round_fn = _make_round_fn(algo, prob, hp, runtime, channel, mesh)
        self.jf = jax.jit(round_fn)
        # the seed replay runs the SEED trajectory form (concatenate
        # epilogue + standalone r_L dispatch, LOCAL_IMPL_SEED) so the
        # committed "vs seed" trajectory stays comparable across PRs —
        # PR 5 folded that epilogue into the scan for every live path
        from repro.core.algorithms import LOCAL_IMPL_SEED

        self.jf_seed = seed_cell.jf_seed if seed_cell is not None else (
            jax.jit(_make_round_fn(algo, prob, _hp(LOCAL_IMPL_SEED),
                                   runtime, channel, mesh)))
        self.rel_fn = jax.jit(
            lambda p: tm.tree_norm(tm.tree_sub(p, wstar)))
        self.runner = make_chunk_runner(round_fn, chunk, w_star=wstar)

    def _state(self):
        return _fresh_state(self.prob, self.hp, self.channel, self.algo)

    def seed_loop(self) -> float:
        """The SEED per-round loop, re-enacted: jit per round, host metric
        sync per round, un-jitted (eagerly dispatched) host rel-error."""
        state, m = self.jf_seed(self._state())
        jax.block_until_ready(m.loss)
        t0 = time.perf_counter()
        for _ in range(self.rounds):
            state, m = self.jf_seed(state)
            m_host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), m)
            diff = tm.tree_norm(tm.tree_sub(state.params, self.wstar))
            rel = float(diff) / max(self.wstar_norm, 1e-30)
        elapsed = time.perf_counter() - t0
        del m_host, rel
        return elapsed / self.rounds

    def loop(self) -> float:
        """This PR's per-round loop: rel-error jitted once and reused."""
        state, m = self.jf(self._state())
        float(self.rel_fn(state.params))
        jax.block_until_ready(m.loss)
        t0 = time.perf_counter()
        for _ in range(self.rounds):
            state, m = self.jf(state)
            m_host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), m)
            rel = float(self.rel_fn(state.params)) / max(self.wstar_norm, 1e-30)
        elapsed = time.perf_counter() - t0
        del m_host, rel
        return elapsed / self.rounds

    def engine(self) -> float:
        """The chunked engine: donated scan, one host sync per chunk."""
        out = self.runner(self._state(), np.int32(self.chunk))
        jax.device_get(out[1:])
        n_chunks = max(self.rounds // self.chunk, 1)
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            out = self.runner(out[0], np.int32(self.chunk))
            jax.device_get(out[1:])
        elapsed = time.perf_counter() - t0
        return elapsed / (n_chunks * self.chunk)


def _bench_cell(prob, wstar, algo, runtime, channel, mesh, rounds, chunk,
                reps, local_impls=("tree",), cohort=None):
    """Bench every local_impl of one (algo, runtime, channel) together:
    ONE seed-loop baseline (the LOCAL_IMPL_SEED seed trajectory replay —
    identical for every row) and per-impl loop/engine modes, all
    interleaved across the reps so sibling tree/pallas rows see the same
    machine load. Returns one row per impl."""
    cells, seed_cell = {}, None
    for li in local_impls:
        cells[li] = _Cell(prob, wstar, algo, runtime, channel, mesh, rounds,
                          chunk, li, seed_cell, cohort)
        seed_cell = seed_cell or cells[li]
    modes = {"seed_loop": cells[local_impls[0]].seed_loop}
    for li in local_impls:
        modes[f"loop:{li}"] = cells[li].loop
        modes[f"engine:{li}"] = cells[li].engine
    for f in modes.values():   # warmup/compile every mode first
        f()
    times = {k: [] for k in modes}
    for _ in range(reps):      # interleaved, min-taking (see docstring)
        for k, f in modes.items():
            times[k].append(f())
    t_seed = min(times["seed_loop"])
    rows = []
    for li in local_impls:
        t_loop = min(times[f"loop:{li}"])
        t_eng = min(times[f"engine:{li}"])
        rows.append({
            "algo": algo,
            "runtime": runtime,
            "channel": channel,
            "local_impl": li,
            "cohort": cohort,
            "rounds_timed": rounds,
            "chunk": chunk,
            "reps": reps,
            "seed_loop_s_per_round": t_seed,
            "loop_s_per_round": t_loop,
            "engine_s_per_round": t_eng,
            "seed_loop_rounds_per_sec": 1.0 / t_seed,
            "engine_rounds_per_sec": 1.0 / t_eng,
            "engine_speedup_vs_seed_loop": t_seed / t_eng,
            "engine_speedup_vs_loop": t_loop / t_eng,
        })
    return rows


def _pallas_row(prob, wstar, rounds):
    """aa_impl="pallas" end-to-end: full fedosaa_svrg rounds through the
    fused kernels (interpret mode on CPU), parity-checked against "tree"."""
    import dataclasses

    hp = AlgoHParams(eta=1.0, local_epochs=10, aa_impl="tree")
    results = {}
    for impl in ("tree", "pallas"):
        rf = make_round_fn("fedosaa_svrg", prob,
                           dataclasses.replace(hp, aa_impl=impl))
        runner = make_chunk_runner(rf, rounds, w_star=wstar, donate=False)
        state = _fresh_state(prob, hp, None, "fedosaa_svrg")
        state, done, ms, rels, lives = runner(state, np.int32(rounds))
        results[impl] = (np.asarray(jax.device_get(rels)),
                         jax.device_get(state.params))
    rel_t, p_t = results["tree"]
    rel_p, p_p = results["pallas"]
    max_param_diff = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree.leaves(p_t), jax.tree.leaves(p_p))
    )
    return {
        "algo": "fedosaa_svrg",
        "runtime": "vmap",
        "aa_impl": "pallas",
        "interpret_mode": jax.default_backend() != "tpu",
        "rounds": rounds,
        "rel_error_tree": [float(v) for v in rel_t],
        "rel_error_pallas": [float(v) for v in rel_p],
        "max_abs_param_diff_vs_tree": max_param_diff,
    }


def _local_row(prob, wstar, rounds):
    """local_impl="pallas" end-to-end: full fedosaa_svrg rounds through the
    fused dual-gradient trajectory (the bit-exact jnp oracle on CPU, the
    kernel on TPU), recorded as rel-error traces against the tree path plus
    the ops-level trajectory parity at the round-0 state. The traces reach
    the same floor; per-round params are NOT compared — the unregularized
    AA Gram solve amplifies last-ulp trajectory reorderings arbitrarily
    (PR 4 finding; pinned in f64 in tests/test_local_update.py instead)."""
    import dataclasses

    from repro.core.algorithms import _svrg_trajectory

    hp = _hp("tree")
    rels = {}
    for impl in ("tree", "pallas"):
        rf = make_round_fn("fedosaa_svrg", prob,
                           dataclasses.replace(hp, local_impl=impl))
        runner = make_chunk_runner(rf, rounds, w_star=wstar, donate=False)
        state = _fresh_state(prob, hp, None, "fedosaa_svrg")
        state, done, ms, rel, lives = runner(state, np.int32(rounds))
        rels[impl] = np.asarray(jax.device_get(rel))
    w0 = prob.init(jax.random.PRNGKey(0))
    g = prob.global_grad(w0)
    batch = prob.clients.client(0)
    rng = jax.random.PRNGKey(1)
    wt_t, rt_t = _svrg_trajectory(prob, hp, w0, g, batch, rng)
    wt_p, rt_p = _svrg_trajectory(prob, dataclasses.replace(hp, local_impl="pallas"),
                                  w0, g, batch, rng)
    traj_diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                    for a, b in ((wt_t, wt_p), (rt_t, rt_p)))
    return {
        "algo": "fedosaa_svrg",
        "runtime": "vmap",
        "local_impl": "pallas",
        "executor": "kernel" if jax.default_backend() == "tpu" else "fused-ref",
        "rounds": rounds,
        "rel_error_tree": [float(v) for v in rels["tree"]],
        "rel_error_pallas": [float(v) for v in rels["pallas"]],
        "trajectory_max_abs_diff_vs_tree": traj_diff,
    }


def run(smoke: bool = False) -> dict:
    rounds = 4 if smoke else 16
    chunk = 2 if smoke else 8
    reps = 2 if smoke else 7   # 7: the noisy-neighbor spikes of this shared
                               # container occasionally last a whole 5-rep
                               # cell; min-of-7 keeps sibling rows comparable
    prob, wstar = logreg_setup("covtype", n=10_000, k=10)
    mesh = make_host_mesh()
    algos = ("fedosaa_svrg",) if smoke else ALGOS
    channels = ("identity",) if smoke else CHANNELS
    rows = []
    for algo in algos:
        for runtime in RUNTIMES:
            for channel in channels:
                cell_rows = _bench_cell(prob, wstar, algo, runtime, channel,
                                        mesh, rounds, chunk, reps,
                                        _local_impls(algo, runtime))
                for row in cell_rows:
                    rows.append(row)
                    print(f"{algo:18s} {runtime:7s} {channel:8s} "
                          f"{row['local_impl']:6s} "
                          f"seed {row['seed_loop_s_per_round']*1e3:7.2f} "
                          f"ms/round -> engine "
                          f"{row['engine_s_per_round']*1e3:7.2f}"
                          f"  ({row['engine_speedup_vs_seed_loop']:.2f}x)")
    # cohort cells: the sampled-cohort round (C=4 of K=10) against the SAME
    # dense seed-loop baseline — the participation-as-memory-model row of
    # the trajectory (benchmarks/ext_cohort.py sweeps the K axis)
    for runtime in (("vmap",) if smoke else RUNTIMES):
        for row in _bench_cell(prob, wstar, "fedosaa_svrg", runtime,
                               "identity", mesh, rounds, chunk, reps,
                               ("tree",), cohort=4):
            rows.append(row)
            print(f"{'fedosaa_svrg':18s} {runtime:7s} {'identity':8s} "
                  f"{row['local_impl']:6s} cohort=4 "
                  f"seed {row['seed_loop_s_per_round']*1e3:7.2f} "
                  f"ms/round -> engine "
                  f"{row['engine_s_per_round']*1e3:7.2f}"
                  f"  ({row['engine_speedup_vs_seed_loop']:.2f}x)")
    pallas = _pallas_row(prob, wstar, rounds=2 if smoke else 4)
    print(f"aa_impl=pallas parity: max |Δparams| vs tree "
          f"{pallas['max_abs_param_diff_vs_tree']:.2e}")
    local = _local_row(prob, wstar, rounds=4 if smoke else 8)
    print(f"local_impl=pallas trajectory parity vs tree: "
          f"{local['trajectory_max_abs_diff_vs_tree']:.2e}; final rel-error "
          f"tree {local['rel_error_tree'][-1]:.2e} vs pallas "
          f"{local['rel_error_pallas'][-1]:.2e}")
    headline = next(
        r for r in rows
        if (r["algo"], r["runtime"], r["channel"], r["local_impl"])
        == ("fedosaa_svrg", "vmap", "identity", "pallas"))
    out = {
        "bench": "round_engine",
        "setup": {"dataset": "covtype-quick", "n": 10_000, "k": 10,
                  "eta": 1.0, "local_epochs": 10,
                  "backend": jax.default_backend(),
                  "timing": "interleaved reps, per-mode min",
                  "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")},
        "smoke": smoke,
        "rows": rows,
        "aa_impl_pallas": pallas,
        "local_impl_pallas": local,
        "headline": {
            "cell": "fedosaa_svrg/vmap/identity/local_impl=pallas",
            "engine_speedup_vs_seed_loop":
                headline["engine_speedup_vs_seed_loop"],
            "seed_loop_s_per_round": headline["seed_loop_s_per_round"],
            "engine_s_per_round": headline["engine_s_per_round"],
        },
    }
    path = SMOKE_PATH if smoke else OUT_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"headline: {out['headline']['engine_speedup_vs_seed_loop']:.2f}x "
          f"({path})")
    return out


def main() -> None:
    use_compile_cache()
    run(smoke="--smoke" in sys.argv)


if __name__ == "__main__":
    main()
