"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input shape) step on the production
meshes — 16×16 single-pod and 2×16×16 multi-pod — with ShapeDtypeStruct
inputs (no allocation), and records memory/cost/collective statistics for the
roofline analysis (deliverable g).

Also dry-runs the distributed FL round (core/sharded.py): compiles one
shard_mapped FedOSAA round with the clients partitioned over the ("pod",
"data") mesh axes and executes it on the emulated host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all              # 40 pairs, single-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod  # 40 pairs, 512 chips
  PYTHONPATH=src python -m repro.launch.dryrun --fl-round fedosaa_svrg --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --fl-round all --multi-pod
"""
# The VERY FIRST lines, before ANY other import: jax locks the device count
# at first init, and the dry-run needs 512 placeholder host devices. Only
# when run as the CLI: a process that imports a helper from here keeps its
# own device count.
import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, INPUT_SHAPES, get_arch, get_shape       # noqa: E402
from repro.launch.mesh import make_production_mesh                        # noqa: E402
from repro.launch.specs_io import (                                        # noqa: E402
    batch_specs_for, cache_len_for, caches_shape, effective_cfg, params_shape,
)
from repro.launch.steps import (                                           # noqa: E402
    make_aa_step, make_prefill_step, make_serve_step, make_train_step,
)
from repro.models.decoder import build_model                               # noqa: E402
from repro.sharding.specs import (                                         # noqa: E402
    batch_axis, cache_specs, make_plan, param_specs,
)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]))\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}


def _shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the partitioned HLO.
    (Result bytes ≈ bytes on the wire for AG/AR; a consistent, documented
    convention — see benchmarks/roofline.py.)"""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_txt, op = m.group(1), m.group(2)
        out[op] = out.get(op, 0) + _shape_bytes(shape_txt)
        out[op + "_count"] = out.get(op + "_count", 0) + 1
    return out


def _cost_dict(compiled) -> dict:
    """compiled.cost_analysis(), or {} where the backend reports none."""
    return compiled.cost_analysis() or {}


def _named(tree_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def dryrun_one(arch: str, shape_name: str, multi_pod: bool = False,
               include_aa: bool = True, extra_tag: str = "",
               plan_overrides=None) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = get_shape(shape_name)
    cfg0 = effective_cfg(get_arch(arch), shape)
    plan = make_plan(cfg0, mesh, multi_pod=multi_pod)
    if plan_overrides:
        plan = plan_overrides(plan)
    cfg = plan.cfg
    sh = plan.sharder()
    # PerfH3 iter 1 (REFUTED): disabling remat for small models makes HBM
    # traffic 2.7x WORSE (109.6 -> 298.6 GB on smollm/train_4k) — without
    # remat the quadratic attention scores are saved for backward. Remat
    # stays on for every train shape.
    model = build_model(cfg, sh, remat=(shape.kind == "train"))

    p_shape = params_shape(model)
    p_specs = param_specs(p_shape, plan)
    p_shard = _named(p_specs, mesh)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "regime": plan.regime,
        "attn_variant": "sliding_window" if cfg.sliding_window else
                        ("none" if not cfg.num_heads else "full_causal"),
        "batch_axis": str(batch_axis(plan, shape.global_batch)),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }

    if shape.kind == "train":
        step = make_train_step(model)
        batch_sds = batch_specs_for(cfg, shape)["batch"]
        ba = batch_axis(plan, shape.global_batch)
        b_shard = jax.tree.map(
            lambda s: NamedSharding(mesh, P(ba, *([None] * (len(s.shape) - 1)))),
            batch_sds,
        )
        jitted = jax.jit(
            step,
            in_shardings=(p_shard, b_shard, p_shard),
            out_shardings=(p_shard, p_shard, None),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(p_shape, batch_sds, p_shape)
    elif shape.kind == "prefill":
        step = make_prefill_step(model, cache_len=cache_len_for(cfg, shape))
        io = batch_specs_for(cfg, shape)
        ba = batch_axis(plan, shape.global_batch)
        tok_shard = NamedSharding(mesh, P(ba, None))
        args = [p_shape, io["tokens"]]
        shards = [p_shard, tok_shard]
        if "embeds" in io:
            args.append(io["embeds"])
            shards.append(NamedSharding(mesh, P(ba, None, None)))
        jitted = jax.jit(step, in_shardings=tuple(shards))
        lowered = jitted.lower(*args)
    else:  # decode
        step = make_serve_step(model)
        C = cache_len_for(cfg, shape)
        c_shape = caches_shape(model, shape.global_batch, C)
        c_specs = cache_specs(c_shape, plan, shape.global_batch)
        c_shard = _named(c_specs, mesh)
        io = batch_specs_for(cfg, shape)
        ba = batch_axis(plan, shape.global_batch)
        tok_shard = NamedSharding(mesh, P(ba, None))
        jitted = jax.jit(
            step,
            in_shardings=(p_shard, c_shard, tok_shard, tok_shard),
            out_shardings=(None, c_shard),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(p_shape, c_shape, io["tokens"], io["pos"])
        result["cache_len"] = C

    compiled = lowered.compile()
    result["compile_s"] = round(time.time() - t0, 1)

    cost = _cost_dict(compiled)
    result["flops"] = float(cost.get("flops", 0.0))
    result["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        result["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or 0),
        }
    except Exception:
        result["memory"] = None
    result["collectives"] = collective_bytes(compiled.as_text())

    # AA step (the paper's contribution) lowered per train pair
    if shape.kind == "train" and include_aa:
        hist = 3
        s_shape = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((hist,) + x.shape, x.dtype), p_shape
        )
        s_specs = jax.tree.map(
            lambda sp: P(None, *sp), p_specs, is_leaf=lambda x: isinstance(x, P)
        )
        s_shard = _named(s_specs, mesh)
        aa = jax.jit(
            make_aa_step(history=hist),
            in_shardings=(p_shard, p_shard, s_shard, s_shard),
            out_shardings=(p_shard, None),
        )
        aa_lowered = aa.lower(p_shape, p_shape, s_shape, s_shape)
        aa_compiled = aa_lowered.compile()
        aa_cost = _cost_dict(aa_compiled)
        result["aa_step"] = {
            "flops": float(aa_cost.get("flops", 0.0)),
            "bytes_accessed": float(aa_cost.get("bytes accessed", 0.0)),
            "collectives": collective_bytes(aa_compiled.as_text()),
        }
    return result


#: Newton-family dryrun workload: these methods need full-rank local
#: Hessians (n/K ≥ d) and enough CG iterations — on the 2048-sample default
#: the 32-sample clients are rank-deficient and the full Newton step
#: diverges by round 4 regardless of codec (measured).
_NEWTON_ALGOS = ("giant", "newton_gmres", "dane")


def dryrun_fl_round(algo: str, multi_pod: bool = False,
                    num_clients: int = 64, n: int | None = None,
                    comm_codec: str = "identity", rounds: int = 1,
                    round_chunk: int = 1, aa_impl: str = "auto",
                    local_impl: str = "auto",
                    cohort_size: int | None = None,
                    clip_rtol: float = 0.0,
                    drop_rate: float = 0.0, stale_rate: float = 0.0,
                    byz_clients: int = 0, byz_mode: str = "sign_flip",
                    dp_sigma: float = 0.0, fault_seed: int = 0,
                    checkpoint_dir: str = "", checkpoint_every: int = 10,
                    checkpoint_keep: int = 3, resume: str = "none") -> dict:
    """Compile + execute shard_mapped FL round(s) on the production mesh.

    Uses a synthetic logistic-regression problem (the paper's workload) with
    the K clients partitioned over the mesh's ("pod","data") axes; num_clients
    must divide over those axes (64 covers both 16 and 2x16 client shards).
    Newton-family algos get a workload sized for them (n=8192 so the local
    Hessians are full-rank, q=10 CG iterations); everything else keeps the
    historical n=2048, η=0.5, L=3.

    ``comm_codec`` threads a repro/comm channel through the sharded round —
    ``bf16`` (or ``bf16/bf16`` for a compressed downlink too) is the
    aggregation-numerics measurement the ROADMAP asks for, and ``int8`` /
    ``int8+noef`` on a Newton-family algo measures the schema'd stateful
    wire (diff-coded gradients): run several rounds and watch the recorded
    rel-error trace converge.

    ``round_chunk > 1`` executes the rounds through the device-resident
    engine (core/engine.py): one donated lax.scan jit per chunk, metrics
    stacked on device, one host sync per chunk — the sharded-runtime
    exercise of the round engine. ``aa_impl``/``local_impl`` thread
    AlgoHParams.aa_impl and .local_impl (the sharded runtime resolves both
    to "tree" — this dry-run exercises the automatic fallback).

    ``clip_rtol`` threads AAConfig.clip_rtol — the residual-clipped AA
    byzantine screen (repro/robust) — through the sharded round, so the
    defended step's compile/collective profile is measurable on the
    production mesh (0 = screen off, the bit-identical vanilla step).

    ``drop_rate``/``stale_rate``/``byz_clients``/``byz_mode``/``dp_sigma``
    build a FaultPlan (repro/robust) threaded through the sharded round —
    the fault-injected round's compile/collective profile on the production
    mesh. All zero (the default) compiles the byte-identical fault-free
    graph; ``fault_seed`` keys the injection stream.

    ``checkpoint_dir`` checkpoints the ServerState through the
    preemption-tolerant sharded format (repro/checkpoint) every
    ``checkpoint_every`` rounds; on the engine path (``round_chunk > 1``)
    saves dispatch from the chunk-boundary sync to a background thread.
    ``resume="auto"`` restores the newest COMPLETE checkpoint under the
    directory and continues toward the same total ``rounds`` (the manifest's
    config fingerprint — algo/mesh/channel/cohort/faults — must match, else
    the resume refuses).

    ``cohort_size`` samples a C-client cohort each round (AlgoHParams
    .cohort_size): the compiled round computes on [C, ...] tensors gathered
    from the K-sized client store — the scale demonstration is
    ``num_clients=4096, cohort_size=16``, where the round body never
    materializes a [K, d] float tensor (tests/test_cohort.py). C must divide
    over the mesh's client shards. At large K the default n grows to keep
    8 samples per client — fewer and the client-local SVRG full-batch
    gradient is too noisy for a 16-of-4096 cohort to converge (measured:
    2/client diverges).
    """
    from repro.comm import make_channel
    from repro.core import AlgoHParams, init_state, run_rounds, solve_reference
    from repro.core.anderson import AAConfig
    from repro.core.sharded import make_sharded_round_fn, num_client_shards
    from repro.data import make_binary_classification, partition
    from repro.models.logreg import make_logreg_problem
    from repro.utils import tree_math as tm

    t0 = time.time()
    # clamp up front so the recorded round_chunk (and main()'s artifact tag)
    # always names the chunk that actually executed
    if round_chunk > rounds:
        print(f"note: --round-chunk {round_chunk} clamped to --fl-rounds "
              f"{rounds}" + (" — the per-round loop runs, NOT the engine"
                             if rounds <= 1 else ""))
    round_chunk = max(1, min(round_chunk, rounds))
    mesh = make_production_mesh(multi_pod=multi_pod)
    aa = AAConfig(clip_rtol=clip_rtol)
    if algo in _NEWTON_ALGOS:
        n = 8192 if n is None else n
        hp = AlgoHParams(eta=1.0, local_epochs=10, aa=aa, aa_impl=aa_impl,
                         local_impl=local_impl, cohort_size=cohort_size)
    else:
        n = max(2048, 8 * num_clients) if n is None else n
        hp = AlgoHParams(eta=0.5, local_epochs=3, aa=aa, aa_impl=aa_impl,
                         local_impl=local_impl, cohort_size=cohort_size)
    X, y = make_binary_classification("synthetic_small", n=n, seed=0)
    clients = partition(X, y, num_clients=num_clients, scheme="iid")
    problem = make_logreg_problem(clients, gamma=1e-3)
    channel = make_channel(comm_codec)
    # algo-aware init: ServerState.comm gets exactly the buffers the
    # algorithm's uplink schema (UPLINK_SCHEMAS) declares for this channel
    from repro.robust import FaultPlan
    faults = FaultPlan(seed=fault_seed, drop_rate=drop_rate,
                       stale_rate=stale_rate, byz_clients=byz_clients,
                       byz_mode=byz_mode, dp_sigma=dp_sigma)
    faults = faults if faults.active else None
    state = init_state(problem, jax.random.PRNGKey(0), hp, channel, algo)
    if faults is not None and faults.stale_rate > 0.0:
        from repro.robust import init_fault_comm
        state = state._replace(comm=init_fault_comm(
            state.comm, state.params, num_clients))
    raw_round_fn = make_sharded_round_fn(algo, problem, hp, mesh,
                                         channel=channel, faults=faults)
    round_fn = jax.jit(raw_round_fn)
    compiled = round_fn.lower(state).compile()
    compile_s = time.time() - t0

    ckpt_mgr = None
    start_round = 0
    if checkpoint_dir or resume != "none":
        from repro.checkpoint import (
            CheckpointManager, CheckpointPolicy, load_checkpoint, load_latest,
        )
        from repro.core.server import checkpoint_config_fingerprint

        fingerprint = checkpoint_config_fingerprint(
            algo, "sharded", channel.name, num_clients, cohort_size, faults)
        fingerprint["mesh"] = "2x16x16" if multi_pod else "16x16"
        if resume != "none":
            if resume == "auto":
                if not checkpoint_dir:
                    raise ValueError('resume="auto" needs checkpoint_dir')
                found = load_latest(checkpoint_dir, state,
                                    expect_config=fingerprint)
            else:
                found = load_checkpoint(resume, state,
                                        expect_config=fingerprint)
            if found is not None:
                state, manifest = found
                start_round = int(manifest["round"])
                print(f"resumed from round {start_round} "
                      f"({manifest.get('inventory', {}).get('num_leaves')} "
                      "leaves)")
        if checkpoint_dir:
            ckpt_mgr = CheckpointManager(
                CheckpointPolicy(directory=checkpoint_dir,
                                 every=checkpoint_every,
                                 keep=checkpoint_keep),
                config=fingerprint, last_saved=start_round)
    rounds_left = max(0, rounds - start_round)

    # d=54 reference solve is cheap; rel-error traces make the dryrun a
    # convergence measurement, not just a compile check (ROADMAP: Newton-row
    # numerics under lossy codecs on the multi-pod mesh)
    wstar = solve_reference(problem, iters=50)
    wstar_norm = float(tm.tree_norm(wstar))

    if rounds_left == 0:
        raise ValueError(
            f"resume landed at round {start_round} of a {rounds}-round "
            "budget — nothing left to run (raise --fl-rounds)")

    engine_compile_s = None
    if round_chunk > 1:
        from repro.core.engine import make_chunk_runner

        # Warm the chunked executable with ONE real call on a throwaway
        # state so run_s measures execution only. (.lower().compile() does
        # NOT populate the jit dispatch cache on this jax — a subsequent
        # call would recompile inside the timed region.) The warmup time is
        # compile-dominated but includes one chunk's execution.
        chunk = round_chunk
        runner = make_chunk_runner(raw_round_fn, chunk, w_star=wstar)
        warm_state = init_state(problem, jax.random.PRNGKey(0), hp, channel,
                                algo)
        t0 = time.time()
        out = runner(warm_state, jnp.int32(chunk))
        jax.block_until_ready(out[1])
        engine_compile_s = round(time.time() - t0, 1)
        t0 = time.time()
        state, trace = run_rounds(raw_round_fn, state, rounds_left,
                                  chunk=chunk, w_star=wstar, runner=runner,
                                  start_round=start_round,
                                  checkpoint=ckpt_mgr)
        losses = [float(v) for v in trace.loss]
        rel_errors = [float(v) for v in trace.rel_error]
        gram_conds = [float(v) for v in trace.gram_cond_max]
        comm_bytes = float(trace.comm_bytes[-1])
        run_s = (time.time() - t0) / max(trace.num_rounds, 1)
    else:
        t0 = time.time()
        losses, rel_errors, gram_conds = [], [], []
        for t in range(start_round, rounds):
            state, metrics = round_fn(state)
            losses.append(float(metrics.loss))
            gram_conds.append(float(metrics.gram_cond_max))
            rel_errors.append(
                float(tm.tree_norm(tm.tree_sub(state.params, wstar)))
                / max(wstar_norm, 1e-30))
            if ckpt_mgr is not None:
                ckpt_mgr.maybe_save(state, t + 1)
        jax.block_until_ready(metrics.loss)
        if ckpt_mgr is not None:
            ckpt_mgr.finalize()
        comm_bytes = float(metrics.comm_bytes)
        run_s = (time.time() - t0) / rounds_left

    cost = _cost_dict(compiled)
    return {
        "fl_round": algo,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "client_shards": num_client_shards(mesh),
        "num_clients": num_clients,
        "cohort_size": cohort_size,
        "channel": channel.name,
        "round_chunk": round_chunk,
        "clip_rtol": clip_rtol,
        "faults": (None if faults is None else {
            "seed": faults.seed, "drop_rate": faults.drop_rate,
            "stale_rate": faults.stale_rate,
            "byz_clients": faults.byz_clients, "byz_mode": faults.byz_mode,
            "dp_sigma": faults.dp_sigma,
        }),
        "aa_impl": aa_impl,
        "local_impl": local_impl,
        "start_round": start_round,
        "checkpoint": (None if ckpt_mgr is None else ckpt_mgr.telemetry()),
        "compile_s": round(compile_s, 1),
        "engine_compile_s": engine_compile_s,
        "run_s": round(run_s, 2),
        "loss": losses[-1],
        "loss_curve": losses,
        "rel_error": rel_errors[-1],
        "rel_error_curve": rel_errors,
        "gram_cond_curve": gram_conds,
        "comm_bytes": comm_bytes,
        "flops": float(cost.get("flops", 0.0)),
        "collectives": collective_bytes(compiled.as_text()),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="")
    ap.add_argument("--shape", type=str, default="")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-aa", action="store_true")
    ap.add_argument("--fl-round", type=str, default="",
                    help="dry-run a shard_mapped FL round of this algorithm "
                         "('all' = the two headline FedOSAA variants)")
    ap.add_argument("--comm-codec", type=str, default="identity",
                    help="repro/comm channel for --fl-round (e.g. bf16, int8, "
                         "bf16/bf16 — the ROADMAP bf16 numerics measurement)")
    ap.add_argument("--fl-rounds", type=int, default=1,
                    help="rounds to execute in the --fl-round dry-run "
                         "(>1 records a loss trace for numerics comparisons)")
    ap.add_argument("--round-chunk", type=int, default=1,
                    help="with --fl-round: execute the rounds through the "
                         "device-resident engine (core/engine.py), this many "
                         "rounds per donated lax.scan jit; 1 = per-round loop")
    ap.add_argument("--fl-clients", type=int, default=64,
                    help="with --fl-round: number of clients K (must divide "
                         "over the mesh's client shards unless --cohort-size "
                         "is set)")
    ap.add_argument("--cohort-size", type=int, default=0,
                    help="with --fl-round: sample a C-client cohort each "
                         "round; the compiled round computes O(C·d) over the "
                         "O(K·d) client store (core/client_store.py). The "
                         "scale demo: --fl-clients 4096 --cohort-size 16. "
                         "0 = dense full-K rounds")
    ap.add_argument("--clip-rtol", type=float, default=0.0,
                    help="with --fl-round: AAConfig.clip_rtol, the residual-"
                         "clipped AA byzantine screen (repro/robust). "
                         "0 = screen off")
    ap.add_argument("--drop-rate", type=float, default=0.0,
                    help="with --fl-round: FaultPlan.drop_rate — per-round "
                         "per-client uplink drop probability")
    ap.add_argument("--stale-rate", type=float, default=0.0,
                    help="with --fl-round: FaultPlan.stale_rate — aged-anchor "
                         "upload probability")
    ap.add_argument("--byz-clients", type=int, default=0,
                    help="with --fl-round: FaultPlan.byz_clients — number of "
                         "persistently byzantine clients")
    ap.add_argument("--byz-mode", choices=("sign_flip", "noise", "history"),
                    default="sign_flip",
                    help="with --fl-round: FaultPlan.byz_mode")
    ap.add_argument("--dp-sigma", type=float, default=0.0,
                    help="with --fl-round: FaultPlan.dp_sigma — post-codec "
                         "client-side Gaussian DP noise scale")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="with --fl-round: FaultPlan.seed — keys the "
                         "injection stream (equal seeds inject bit-identical "
                         "rounds across runs and runtimes)")
    ap.add_argument("--aa-impl", choices=("auto", "tree", "pallas"),
                    default="auto",
                    help="with --fl-round: AlgoHParams.aa_impl (the sharded "
                         "runtime resolves to 'tree' — exercises the "
                         "automatic fallback)")
    ap.add_argument("--local-impl", choices=("auto", "tree", "pallas"),
                    default="auto",
                    help="with --fl-round: AlgoHParams.local_impl (the "
                         "sharded runtime resolves to 'tree' — exercises "
                         "the fused-kernel fallback path)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="with --fl-round: checkpoint the ServerState under "
                         "this directory (preemption-tolerant sharded "
                         "format, repro/checkpoint; async at chunk "
                         "boundaries under --round-chunk)")
    ap.add_argument("--checkpoint-every", type=int, default=10,
                    help="with --fl-round: rounds between checkpoint saves")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="with --fl-round: retention — GC checkpoints "
                         "beyond the newest N (0 = keep all)")
    ap.add_argument("--resume", default="none",
                    help="with --fl-round: 'auto' restores the newest "
                         "COMPLETE checkpoint under --checkpoint-dir and "
                         "continues toward the same --fl-rounds total; "
                         "'none' starts fresh; otherwise a ckpt_* path. "
                         "Mismatched manifest config refuses to resume")
    args = ap.parse_args()

    if args.fl_round:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        algos = (["fedosaa_svrg", "fedosaa_scaffold"]
                 if args.fl_round == "all" else [args.fl_round])
        failures = []
        codec_tag = ("" if args.comm_codec == "identity"
                     else f"{args.comm_codec.replace('/', '-').replace(':', '')}__")
        engine_tag = ""  # distinct artifact names for engine/pallas/cohort runs
        # same clamp as dryrun_fl_round: the tag names the EXECUTED chunk
        eff_chunk = max(1, min(args.round_chunk, args.fl_rounds))
        if args.cohort_size:
            # the cohort tag subsumes the chunk tag (the JSON records
            # round_chunk either way)
            engine_tag += f"cohort{args.cohort_size}-of-{args.fl_clients}"
        elif eff_chunk > 1:
            engine_tag += f"chunk{eff_chunk}"
        if args.clip_rtol:
            engine_tag += ("+" if engine_tag else "") + f"clip{args.clip_rtol:g}"
        # fault knobs name the artifact so injected dry-runs never clobber
        # the fault-free profile of the same algo/codec/mesh combination
        if args.drop_rate:
            engine_tag += ("+" if engine_tag else "") + f"drop{args.drop_rate:g}"
        if args.stale_rate:
            engine_tag += ("+" if engine_tag else "") + f"stale{args.stale_rate:g}"
        if args.byz_clients:
            engine_tag += ("+" if engine_tag else "") + (
                f"byz{args.byz_clients}-{args.byz_mode.replace('_', '')}")
        if args.dp_sigma:
            engine_tag += ("+" if engine_tag else "") + f"dp{args.dp_sigma:g}"
        if args.aa_impl != "auto":
            engine_tag += ("+" if engine_tag else "") + args.aa_impl
        if args.local_impl != "auto":
            engine_tag += ("+" if engine_tag else "") + f"local-{args.local_impl}"
        engine_tag = f"{engine_tag}__" if engine_tag else ""
        for algo in algos:
            tag = (f"fl_round__{algo}__{codec_tag}{engine_tag}"
                   f"{'2x16x16' if args.multi_pod else '16x16'}")
            try:
                res = dryrun_fl_round(algo, args.multi_pod,
                                      num_clients=args.fl_clients,
                                      comm_codec=args.comm_codec,
                                      rounds=args.fl_rounds,
                                      round_chunk=args.round_chunk,
                                      aa_impl=args.aa_impl,
                                      local_impl=args.local_impl,
                                      cohort_size=args.cohort_size or None,
                                      clip_rtol=args.clip_rtol,
                                      drop_rate=args.drop_rate,
                                      stale_rate=args.stale_rate,
                                      byz_clients=args.byz_clients,
                                      byz_mode=args.byz_mode,
                                      dp_sigma=args.dp_sigma,
                                      fault_seed=args.fault_seed,
                                      checkpoint_dir=args.checkpoint_dir,
                                      checkpoint_every=args.checkpoint_every,
                                      checkpoint_keep=args.checkpoint_keep,
                                      resume=args.resume)
                with open(os.path.join(RESULTS_DIR, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=1)
                print(f"OK   {tag}: compile={res['compile_s']}s "
                      f"run={res['run_s']}s loss={res['loss']:.4f} "
                      f"relerr={res['rel_error']:.2e} "
                      f"ar={res['collectives'].get('all-reduce_count', 0)}")
            except Exception as e:
                failures.append(tag)
                print(f"FAIL {tag}: {e}")
                traceback.print_exc()
        if failures:
            raise SystemExit(1)
        print("fl-round dry-runs passed")
        return

    os.makedirs(RESULTS_DIR, exist_ok=True)
    combos = []
    if args.all:
        combos = [(a, s) for a in ARCHS for s in INPUT_SHAPES]
    else:
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape in combos:
        tag = f"{arch}__{shape}__{'2x16x16' if args.multi_pod else '16x16'}"
        out_path = os.path.join(RESULTS_DIR, tag + ".json")
        try:
            res = dryrun_one(arch, shape, args.multi_pod, include_aa=not args.no_aa)
            with open(out_path, "w") as f:
                json.dump(res, f, indent=1)
            mem = (res.get("memory") or {}).get("peak_bytes", 0)
            print(f"OK   {tag}: compile={res['compile_s']}s "
                  f"flops={res['flops']:.3e} peak={mem/2**30:.2f}GiB "
                  f"coll={sum(v for k, v in res['collectives'].items() if not k.endswith('_count'))/2**30:.3f}GiB")
        except Exception as e:
            failures.append(tag)
            print(f"FAIL {tag}: {e}")
            traceback.print_exc()
    if failures:
        print(f"FAILURES ({len(failures)}): {failures}")
        raise SystemExit(1)
    print("all dry-runs passed")


if __name__ == "__main__":
    main()
