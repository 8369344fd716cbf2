"""Federated LM training driver: FedOSAA (or any core algorithm) over an
assigned architecture.

  PYTHONPATH=src python -m repro.launch.fl_train --arch smollm-135m --reduced \
      --algo fedosaa_svrg --rounds 20 --clients 4

``--reduced`` uses the smoke-scale variant (CPU-runnable); without it the
full config is built (TPU-scale — on this CPU container use the dry-run
instead). Compares against --baseline algo when given and writes a CSV.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.core import AlgoHParams, run_federated
from repro.core.lm import make_lm_clients, make_lm_problem, make_lora
from repro.data import make_lm_tokens
from repro.models.decoder import build_model
from repro.utils.compile_cache import use_compile_cache


def main(argv: "list[str] | None" = None) -> dict:
    """Parse ``argv`` (default: sys.argv), train, and return the per-algo
    results dict that ``--out`` writes."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--algo", default="fedosaa_svrg")
    ap.add_argument("--baseline", default="")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--docs-per-client", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--eta", type=float, default=0.3)
    ap.add_argument("--local-epochs", type=int, default=5)
    ap.add_argument("--damping", type=float, default=1.0)
    ap.add_argument("--clip-rtol", type=float, default=0.0,
                    help="residual-clipped AA (AAConfig.clip_rtol): drop any "
                         "history column whose residual norm exceeds the "
                         "client's median by more than 1/clip_rtol before the "
                         "Gram solve — the byzantine-history defense "
                         "(repro/robust). 0 = screen off (bit-identical to "
                         "the unscreened step)")
    # -- fault injection (repro/robust) ----------------------------------
    ap.add_argument("--drop-rate", type=float, default=0.0,
                    help="per-round per-client probability the uplink never "
                         "lands (FaultPlan.drop_rate): survivors' weights "
                         "renormalize, the dropped client's state rows stay "
                         "bit-frozen")
    ap.add_argument("--stale-rate", type=float, default=0.0,
                    help="per-round per-client probability the upload is "
                         "computed against an aged anchor w^{t-s} "
                         "(FaultPlan.stale_rate); consecutive draws compound")
    ap.add_argument("--byz-clients", type=int, default=0,
                    help="number of (lowest-id) persistently byzantine "
                         "clients (FaultPlan.byz_clients)")
    ap.add_argument("--byz-mode", choices=("sign_flip", "noise", "history"),
                    default="sign_flip",
                    help="byzantine perturbation: sign_flip/noise corrupt "
                         "the uplink, history poisons the recorded AA "
                         "column (the attack --clip-rtol screens)")
    ap.add_argument("--dp-sigma", type=float, default=0.0,
                    help="client-side Gaussian DP noise scale, applied "
                         "post-codec so error feedback tracks the noised "
                         "wire (FaultPlan.dp_sigma)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="FaultPlan.seed: keys the whole injection stream — "
                         "equal seeds inject bit-identical rounds across "
                         "runs and runtimes")
    ap.add_argument("--latency-scale", type=float, default=0.0,
                    help="simulate per-client compute latency "
                         "(FaultPlan.latency_scale; 0 = off) — feeds the "
                         "--deadline gate")
    ap.add_argument("--latency-shape", type=float, default=1.0,
                    help="latency tail heaviness (lognormal sigma / pareto "
                         "index; FaultPlan.latency_shape)")
    ap.add_argument("--latency-dist", choices=("lognormal", "pareto"),
                    default="lognormal")
    # -- deadline-gated aggregation (repro/robust/async_agg) -------------
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="deadline-gate the round close (AsyncConfig."
                         "deadline): only clients whose simulated latency "
                         "beats the deadline land; late updates buffer and "
                         "fold in later with staleness-discounted weight. "
                         "0 = the barriered (synchronous) round")
    ap.add_argument("--min-arrivals", type=int, default=0,
                    help="extend the deadline in-graph whenever fewer "
                         "latencies beat it (AsyncConfig.min_arrivals)")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="staleness discount exponent: a fold aged s rounds "
                         "weighs (1+s)^-alpha (AsyncConfig.staleness_alpha)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients active per round: <1.0 samples "
                         "a ⌈pK⌉-client cohort each round (weighted, without "
                         "replacement; weights renormalize) — the round then "
                         "computes O(C·d) over the O(K·d) client store")
    ap.add_argument("--cohort-size", type=int, default=0,
                    help="explicit per-round cohort size C (overrides "
                         "--participation): each round gathers C sampled "
                         "clients' data + state rows, computes on [C, ...] "
                         "tensors only, and scatters updates back — "
                         "non-sampled clients' state stays bit-frozen. "
                         "0 = derive from --participation")
    ap.add_argument("--comm-codec", default="identity",
                    help="wire-compression channel spec (repro/comm): "
                         "identity | bf16 | int8[:chunk] | topk[:ratio], "
                         "optional +ef/+noef and /<downlink-codec> — e.g. "
                         "int8, topk:0.05, bf16/bf16")
    ap.add_argument("--runtime", choices=("vmap", "sharded"), default="vmap",
                    help="'sharded' shard_maps the client fan-out over the "
                         "('pod','data') mesh axes (core/sharded.py)")
    ap.add_argument("--round-chunk", type=int, default=0,
                    help="compile this many rounds into ONE donated lax.scan "
                         "jit (core/engine.py): metrics stack on device and "
                         "the host syncs once per chunk. 0 = the per-round "
                         "loop")
    ap.add_argument("--aa-impl", choices=("auto", "tree", "pallas"),
                    default="auto",
                    help="AA-step implementation (AlgoHParams.aa_impl): "
                         "'pallas' ravels each client's leaves into flat "
                         "buffers and runs the fused single-pass kernels "
                         "(kernels/anderson); 'auto' = pallas on TPU, tree "
                         "elsewhere; the sharded runtime always uses tree")
    ap.add_argument("--local-impl", choices=("auto", "tree", "pallas"),
                    default="auto",
                    help="local-trajectory implementation "
                         "(AlgoHParams.local_impl): 'pallas' runs the fused "
                         "dual-gradient kernels (kernels/local_update) — "
                         "linear-design models only, so LM architectures "
                         "fall back to the autodiff path; 'auto' = pallas "
                         "on TPU where eligible; sharded always uses tree")
    ap.add_argument("--multi-pod", action="store_true",
                    help="with --runtime sharded: use the 2x16x16 two-pod "
                         "mesh instead of the single-pod 16x16 (requires "
                         "enough devices, e.g. the dryrun host-device env)")
    ap.add_argument("--lora-rank", type=int, default=0,
                    help="train low-rank adapters (alpha = 2·rank) on every "
                         "linear map of every layer over a frozen base, as "
                         "one flat vector (core/lm.py), instead of the "
                         "whole model; the base is an operand of the "
                         "compiled round. 0 = train the whole model")
    ap.add_argument("--out", default="")
    # -- preemption-tolerant checkpointing (repro/checkpoint) ------------
    ap.add_argument("--checkpoint-dir", default="",
                    help="checkpoint the full ServerState under this "
                         "directory (atomic per-shard saves with a manifest "
                         "commit marker — checkpoint/sharded_ckpt.py). "
                         "Under --round-chunk the save dispatches from the "
                         "chunk-boundary sync to a background thread and "
                         "overlaps the next chunk's compute")
    ap.add_argument("--checkpoint-every", type=int, default=10,
                    help="rounds between saves (saves land at the first "
                         "chunk boundary at/after each multiple)")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="retention: GC committed checkpoints beyond the "
                         "newest N (0 = keep all)")
    ap.add_argument("--resume", default="none",
                    help="'auto': restore the newest COMPLETE checkpoint "
                         "under --checkpoint-dir (torn/partial saves are "
                         "skipped) and continue with contiguous round "
                         "numbering; 'none': fresh start; otherwise a path "
                         "to one ckpt_* directory. Resume REFUSES a "
                         "checkpoint whose manifest config (algo/runtime/"
                         "channel/cohort/faults/async) mismatches this run")
    ap.add_argument("--checkpoint-sync", action="store_true",
                    help="save inline at the boundary instead of on the "
                         "background thread (debugging/benchmark baseline)")
    ap.add_argument("--inject-kill-save", type=int, default=0, metavar="N",
                    help="crash-injection harness: hard-exit the process "
                         "(exit code 43, robust/fs_faults.KILL_EXIT_CODE) "
                         "mid-write during the N-th checkpoint save, before "
                         "its commit rename — the kill-resume recovery smoke "
                         "(scripts/kill_resume_smoke.py). 0 = off")
    # -- telemetry (repro/obs) -------------------------------------------
    ap.add_argument("--metrics-out", default="",
                    help="stream per-round telemetry rows to this JSONL file "
                         "(versioned schema — obs/sinks.py; validate with "
                         "scripts/check_metrics_jsonl.py). Drained at chunk "
                         "boundaries under --round-chunk, per round "
                         "otherwise; attaching it never changes the computed "
                         "rounds")
    ap.add_argument("--metrics-stdout", type=int, default=0, metavar="N",
                    help="print every N-th telemetry row to stdout (0 = off)")
    ap.add_argument("--no-alarms", action="store_true",
                    help="disable the default health monitors (non-finite "
                         "loss, AA Gram conditioning blowup, AA column "
                         "collapse, rel-error plateau — obs/alarms.py); they "
                         "are attached whenever any metrics sink is")
    ap.add_argument("--trace-rounds", type=int, default=0, metavar="N",
                    help="capture a jax.profiler trace window covering N "
                         "rounds starting at --trace-start (aligned outward "
                         "to chunk boundaries under --round-chunk); named "
                         "scopes attribute time to the round phases")
    ap.add_argument("--trace-start", type=int, default=0,
                    help="first round of the --trace-rounds window")
    ap.add_argument("--trace-dir", default="",
                    help="profiler trace output dir (default "
                         "<--out dir or .>/trace)")
    ap.add_argument("--trace-trigger", default="",
                    help="arm on-demand tracing: touching this file while "
                         "the run is in flight traces the next chunk (the "
                         "file is consumed per window)")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)

    toks = make_lm_tokens(
        args.clients * args.docs_per_client, args.seq_len, cfg.vocab_size
    )
    clients = make_lm_clients(toks, args.clients)
    if args.lora_rank > 0:
        base = jax.jit(model.init)(jax.random.PRNGKey(0))
        lora = make_lora(base, args.lora_rank, 2 * args.lora_rank)
        problem = make_lm_problem(model, clients, base, lora)
    else:
        problem = make_lm_problem(model, clients)

    from repro.comm import make_channel
    from repro.core.anderson import AAConfig
    hp = AlgoHParams(eta=args.eta, local_epochs=args.local_epochs,
                     participation=args.participation,
                     cohort_size=args.cohort_size or None,
                     aa=AAConfig(damping=args.damping, tikhonov=1e-8,
                                 clip_rtol=args.clip_rtol),
                     aa_impl=args.aa_impl, local_impl=args.local_impl)
    channel = make_channel(args.comm_codec)
    chunk = args.round_chunk if args.round_chunk > 0 else None

    from repro.robust import AsyncConfig, FaultPlan
    faults = FaultPlan(
        seed=args.fault_seed, drop_rate=args.drop_rate,
        stale_rate=args.stale_rate, byz_clients=args.byz_clients,
        byz_mode=args.byz_mode, dp_sigma=args.dp_sigma,
        latency_dist=args.latency_dist, latency_scale=args.latency_scale,
        latency_shape=args.latency_shape)
    faults = faults if faults.active else None
    async_cfg = AsyncConfig(deadline=args.deadline,
                            min_arrivals=args.min_arrivals,
                            staleness_alpha=args.staleness_alpha)
    async_cfg = async_cfg if async_cfg.active else None
    if async_cfg is not None and (faults is None
                                  or not faults.simulates_latency):
        print("warning: --deadline without --latency-scale gates on all-zero "
              "latencies (every client on time)")

    ckpt_policy = None
    ckpt_fs = None
    resume = args.resume if args.resume != "none" else None
    if args.checkpoint_dir:
        from repro.checkpoint import CheckpointPolicy

        ckpt_policy = CheckpointPolicy(
            directory=args.checkpoint_dir, every=args.checkpoint_every,
            keep=args.checkpoint_keep,
            mode="sync" if args.checkpoint_sync else "async")
        if args.inject_kill_save > 0:
            from repro.robust.fs_faults import FaultyFs, FSFaultPlan

            ckpt_fs = FaultyFs(FSFaultPlan(
                kill_at_save=args.inject_kill_save, kill_after_writes=1,
                kill_hard=True))
    elif resume == "auto":
        ap.error("--resume auto needs --checkpoint-dir")

    mesh = None
    if args.runtime == "sharded":
        from repro.core.sharded import num_client_shards
        from repro.launch.mesh import make_host_mesh, make_production_mesh

        needed = 512 if args.multi_pod else 256
        mesh = (make_production_mesh(multi_pod=args.multi_pod)
                if jax.device_count() >= needed else make_host_mesh())
        shards = num_client_shards(mesh)
        if args.clients % shards:
            ap.error(
                f"--clients {args.clients} must divide over the {shards} "
                f"client shards of the {dict(mesh.shape)} mesh; use "
                f"--clients {shards} or a multiple"
            )
        print(f"sharded runtime on mesh {dict(mesh.shape)}")

    def build_sinks(algo: str):
        """Per-algo telemetry sinks + trace capture (repro/obs); fresh per
        run so each algo gets its own JSONL file and alarm state."""
        from repro.obs import (AlarmMonitor, JsonlSink, StdoutSink,
                               TraceCapture, TraceConfig)

        sinks = []
        if args.metrics_out:
            base, ext = os.path.splitext(args.metrics_out)
            path = (args.metrics_out if len(algos) == 1
                    else f"{base}.{algo}{ext or '.jsonl'}")
            sinks.append(JsonlSink(path))
        if args.metrics_stdout:
            sinks.append(StdoutSink(every=args.metrics_stdout))
        if sinks and not args.no_alarms:
            sinks.append(AlarmMonitor())
        tc = None
        if args.trace_rounds > 0 or args.trace_trigger:
            trace_dir = args.trace_dir or os.path.join(
                os.path.dirname(args.out) or ".", "trace")
            tc = TraceCapture(TraceConfig(
                trace_dir=trace_dir, start_round=args.trace_start,
                num_rounds=args.trace_rounds,
                trigger_file=args.trace_trigger or None))
        return sinks, tc

    results = {}
    algos = [args.algo] + ([args.baseline] if args.baseline else [])
    for algo in algos:
        sinks, trace_capture = build_sinks(algo)
        pol = ckpt_policy
        if pol is not None and len(algos) > 1:
            # per-algo subdir: the manifests carry per-algo config
            # fingerprints, so sharing one directory would make resume
            # refuse the second algo's checkpoints
            import dataclasses as _dc

            pol = _dc.replace(pol,
                              directory=os.path.join(pol.directory, algo))
        t0 = time.time()
        h = run_federated(problem, algo, hp, args.rounds,
                          runtime=args.runtime, mesh=mesh, channel=channel,
                          chunk=chunk, sinks=sinks,
                          trace_capture=trace_capture, faults=faults,
                          async_cfg=async_cfg,
                          checkpoint=pol, resume=resume,
                          checkpoint_fs=ckpt_fs)
        results[algo] = {
            "loss_curve": [float(v) for v in h.loss],
            "grad_norm_curve": [float(v) for v in h.grad_norm],
            "gram_cond_curve": [float(v) for v in h.gram_cond_max],
            "aa_used_min_curve": [float(v) for v in h.aa_used_min],
            "comm_bytes": float(h.comm_bytes[-1]),
            "channel": h.channel,
            "wall_s": time.time() - t0,
            # fault/async parameters travel with the artifact so a result
            # file is self-describing about what was injected
            "faults": (None if faults is None else {
                "seed": faults.seed, "drop_rate": faults.drop_rate,
                "stale_rate": faults.stale_rate,
                "byz_clients": faults.byz_clients,
                "byz_mode": faults.byz_mode, "dp_sigma": faults.dp_sigma,
                "latency_dist": faults.latency_dist,
                "latency_scale": faults.latency_scale,
                "latency_shape": faults.latency_shape,
            }),
            "async": (None if async_cfg is None else {
                "deadline": async_cfg.deadline,
                "min_arrivals": async_cfg.min_arrivals,
                "staleness_alpha": async_cfg.staleness_alpha,
                "arrivals_curve": [float(v) for v in h.arrivals],
                "staleness_max_curve": [float(v) for v in h.staleness_max],
            }),
        }
        print(f"{algo}: loss {h.loss[0]:.4f} -> {h.loss[-1]:.4f} "
              f"|g| {h.grad_norm[-1]:.2e} "
              f"wire {h.comm_bytes[-1]/2**20:.2f}MiB[{h.channel}] "
              f"({results[algo]['wall_s']:.0f}s)")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    main()
