"""Bring-up smoke of the FedOSAA round engine on one TPU chip.

    python chip_smoke.py               # phases lm + paper, one chip
    python chip_smoke.py --four-chips  # sharded (4 chips) vs vmap (1 chip)

Phase ``lm``: full-width smollm-135m (162.8M parameters, bf16) trained with
FedOSAA-SVRG through ``launch/fl_train.main`` — 2 clients x 4 docs x seq
128, L=2, 3 rounds in one engine chunk. Checks a finite loss every round
and at least one AA column surviving (``aa_used_min > 0``).

Phase ``paper``: the paper's workload at the paper's size — covtype-shaped
synthetic data (n=581,012, d=54, gamma=1e-3), FedOSAA-SVRG with eta=1, L=10
and the int8 uplink through ``run_federated(..., chunk=5)``, on two IID
splits: K=100 (each client's design block stays VMEM-resident in the fused
local kernel) and K=10 (the block streams in row tiles). Each split runs
twice from one seed: local/AA impl "pallas" (all three Pallas kernels) and
"tree". Checks, from the op metadata of each compiled chunk, which round
phases call a Mosaic kernel (``tpu_custom_call``): local trajectory, AA
step and uplink on the pallas path; only the uplink (the int8 codec) on the
tree path. And checks that both runs reach rel-error <= 1e-4 against
``solve_reference``'s w* within 30 rounds.

``--four-chips`` runs only the K=100 split, with FedOSAA-SCAFFOLD (the
paper's Algorithm 2, eta=1, L=2, lossless uplink), whose per-client control
variates c_k are the client-axis state the sharded runtime partitions: from
the same state each round, one round of the sharded runtime over a
("data"=4, "model"=1) mesh against one round of the vmap runtime on the
first chip, for 5 rounds, both at full f32 matmul precision. Checks that
the rel-errors agree to rtol 1e-5, that the iterates lie within
1e-5·||w*|| of each other, and that c_k is split in 4 shards of K/4 rows
over 4 devices. Why not the paper phase's settings: at L=10 the f32 AA
Gram reaches condition numbers near 1e12, and its solve turns the
runtimes' different reduction orders (1e-7) into iterates 24% apart after
one round (measured on 4 virtual CPU devices); an int8 uplink flips
stochastic roundings on such ulp differences (3e-5 apart after one round),
and so do the bf16 roundings of XLA's default-precision dots. These are
properties of f32 AA, of the codec and of the precision, not of the
runtimes.

Every result line names the device kind. The script fails without a TPU and
exits non-zero on any failed check. The last line of its output is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAPER_N = 581_012
PAPER_ROUNDS = 30
PAPER_CHUNK = 5
TARGET_REL_ERROR = 1e-4
#: Newton steps of solve_reference. Its 1e-12 gradient tolerance is out of
#: f32's reach, so left alone it runs all 2000 steps (100 CG Hessian-vector
#: sweeps each, over all 581k rows); Newton reaches the f32 floor from w=0
#: in far fewer.
REFERENCE_NEWTON_STEPS = 30
#: the phase each Pallas kernel family runs under: the fused local
#: trajectory, the AA Gram + update, and the int8 codec of the uplink
KERNEL_PHASES = ("fl.local_trajectory", "fl.aa_step", "fl.uplink")
FOUR_CHIP_ROUNDS = 5
FOUR_CHIP_RTOL = 1e-5
ALGO4 = "fedosaa_scaffold"


def _paper_hp(impl: str):
    from repro.core import AlgoHParams

    return AlgoHParams(eta=1.0, local_epochs=10, local_impl=impl,
                       aa_impl=impl)


def _paper_problem(X, y, K: int):
    from repro.data import partition
    from repro.models.logreg import make_logreg_problem

    return make_logreg_problem(partition(X, y, num_clients=K, scheme="iid"),
                               gamma=1e-3)


def _chunk_hlo(problem, hp, w_star, channel: str) -> str:
    """Compiled HLO of the engine chunk ``run_federated(chunk=5)`` runs."""
    import jax
    import numpy as np

    from repro.core import init_state, make_chunk_runner, make_round_fn

    round_fn = make_round_fn("fedosaa_svrg", problem, hp, channel)
    runner = make_chunk_runner(round_fn, PAPER_CHUNK, w_star=w_star)
    state = init_state(problem, jax.random.PRNGKey(0), hp, channel,
                       "fedosaa_svrg")
    return runner.lower(state, np.int32(PAPER_CHUNK)).compile().as_text()


def kernel_phases(hlo: str) -> set:
    """The round phases (``jax.named_scope`` in the op metadata) whose
    compiled code calls a Mosaic kernel (``tpu_custom_call``)."""
    return {p for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for p in KERNEL_PHASES if p in line}


def run_paper_split(problem, impl: str, w_star):
    """One FedOSAA-SVRG run of the paper phase; returns
    (History, resolved impls, chunk HLO)."""
    from repro.core import resolve_aa_impl, resolve_local_impl, run_federated

    hp = _paper_hp(impl)
    resolved = {
        "local_impl": resolve_local_impl(impl, "vmap", problem,
                                         "fedosaa_svrg"),
        "aa_impl": resolve_aa_impl(impl),
    }
    hlo = _chunk_hlo(problem, hp, w_star, "int8")
    h = run_federated(problem, "fedosaa_svrg", hp, PAPER_ROUNDS, rng=0,
                      w_star=w_star, channel="int8", chunk=PAPER_CHUNK)
    return h, resolved, hlo


def phase_paper(kind: str) -> None:
    import numpy as np

    from repro.core import solve_reference
    from repro.data import make_binary_classification

    X, y = make_binary_classification("covtype", n=PAPER_N)
    w_star = None
    for K in (100, 10):
        problem = _paper_problem(X, y, K)
        if w_star is None:
            # both IID splits hold every row, so they share one global
            # objective and one w*
            w_star = solve_reference(problem, iters=REFERENCE_NEWTON_STEPS)
        for impl in ("pallas", "tree"):
            h, resolved, hlo = run_paper_split(problem, impl, w_star)
            phases = kernel_phases(hlo)
            rel = np.asarray(h.rel_error)
            ms = 1e3 * np.diff(np.concatenate([[0.0], h.wall_time]))
            hit = np.flatnonzero(rel <= TARGET_REL_ERROR)
            print(f"[{kind}] paper K={K} impl={impl} resolved={resolved} "
                  f"kernels in phases {sorted(phases)}")
            print(f"[{kind}] paper K={K} impl={impl} rel_error="
                  f"{[float(f'{v:.3e}') for v in rel]}")
            print(f"[{kind}] paper K={K} impl={impl} rounds to "
                  f"{TARGET_REL_ERROR}: {hit[0] + 1 if hit.size else None}; "
                  f"ms/round (median of chunk shares) "
                  f"{float(np.median(ms)):.3f}; first chunk incl. compile "
                  f"{float(ms[0]):.1f}")
            # the tree path bypasses the local and AA kernels; the int8
            # uplink reaches the quant kernel on both paths
            want = (set(KERNEL_PHASES) if impl == "pallas"
                    else {"fl.uplink"})
            assert phases == want, (
                f"K={K} impl={impl}: kernels in phases {sorted(phases)}, "
                f"expected {sorted(want)}")
            assert hit.size, (
                f"K={K} impl={impl}: best rel-error {np.nanmin(rel):.3e} > "
                f"{TARGET_REL_ERROR} in {PAPER_ROUNDS} rounds")


def phase_lm(kind: str) -> None:
    import jax
    import numpy as np

    from repro.launch import fl_train

    res = fl_train.main([
        "--arch", "smollm-135m", "--algo", "fedosaa_svrg",
        "--clients", "2", "--docs-per-client", "4", "--seq-len", "128",
        "--local-epochs", "2", "--rounds", "3", "--round-chunk", "3",
    ])["fedosaa_svrg"]
    loss = np.asarray(res["loss_curve"])
    used = np.asarray(res["aa_used_min_curve"])
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{kind}] lm smollm-135m full width: loss={loss.tolist()} "
          f"aa_used_min={used.tolist()} wall_s={res['wall_s']:.1f}")
    print(f"[{kind}] lm peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}")
    assert loss.size == 3 and np.all(np.isfinite(loss)), loss
    assert np.all(used > 0), used


def phase_four_chips(kind: str) -> None:
    import jax
    import numpy as np

    from repro.core import (AlgoHParams, init_state, make_round_fn,
                            make_sharded_round_fn, solve_reference)
    from repro.data import make_binary_classification
    from repro.launch.mesh import make_host_mesh
    from repro.utils import tree_math as tm

    X, y = make_binary_classification("covtype", n=PAPER_N)
    problem = _paper_problem(X, y, 100)
    w_star = solve_reference(problem, iters=REFERENCE_NEWTON_STEPS)
    ws_norm = float(tm.tree_norm(w_star))
    # the sharded runtime runs the tree impls; the vmap one is held to them
    # so that only the runtime differs
    hp = AlgoHParams(eta=1.0, local_epochs=2, local_impl="tree",
                     aa_impl="tree")
    mesh = make_host_mesh()
    assert dict(mesh.shape) == {"data": 4, "model": 1}, mesh.shape
    vmap_round = jax.jit(make_round_fn(ALGO4, problem, hp))
    sharded_round = jax.jit(make_sharded_round_fn(ALGO4, problem, hp, mesh))
    state = init_state(problem, jax.random.PRNGKey(0), hp, None, ALGO4)
    for t in range(FOUR_CHIP_ROUNDS):
        # both runtimes at full f32 matmul precision: at XLA's default the
        # runtimes' f32 reordering noise (~1e-7) lands on different bf16
        # roundings, and the iterates were 4.6e-5 apart after round 2 on
        # four v5e chips
        with jax.default_matmul_precision("highest"):
            sv, _ = vmap_round(state)
            ss, _ = sharded_round(state)
        wv, wsh = np.asarray(sv.params), np.asarray(ss.params)
        rel_v = float(np.linalg.norm(wv - np.asarray(w_star))) / ws_norm
        rel_s = float(np.linalg.norm(wsh - np.asarray(w_star))) / ws_norm
        agree = float(np.linalg.norm(wsh - wv)) / ws_norm
        print(f"[{kind}] four-chips round {t}: rel_error vmap={rel_v:.6e} "
              f"sharded={rel_s:.6e} |w_s-w_v|/|w*|={agree:.3e}")
        assert agree <= FOUR_CHIP_RTOL, (t, agree)
        assert abs(rel_s - rel_v) <= FOUR_CHIP_RTOL * rel_v, (t, rel_s, rel_v)
        state = sv
    # the client-axis state: the per-client control variates c_k [K, d]
    shards = ss.c_k.addressable_shards
    devs = sorted({sh.device.id for sh in shards})
    rows = sorted({sh.data.shape[0] for sh in shards})
    print(f"[{kind}] four-chips c_k {ss.c_k.shape}: {len(shards)} shards of "
          f"{rows} rows on devices {devs}")
    assert len(shards) == 4 and len(devs) == 4, devs
    assert rows == [ss.c_k.shape[0] // 4], rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-vmap phase on 4 chips")
    args = ap.parse_args()

    from repro.utils.compile_cache import use_compile_cache

    cache = use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {dev.platform!r}")
    kind = dev.device_kind
    count = len(jax.devices())
    print(f"[{kind}] jax {jax.__version__}, {count} device(s), compile "
          f"cache {cache}")
    phases = ([phase_four_chips] if args.four_chips
              else [phase_lm, phase_paper])
    for phase in phases:
        t0 = time.perf_counter()
        phase(kind)
        print(f"[{kind}] {phase.__name__} ok in "
              f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": count}}))


if __name__ == "__main__":
    main()
