#!/usr/bin/env bash
# Tier-1 gate, runnable offline: collection errors can never silently reland.
#
#   bash scripts/ci.sh
#
# Installs the dev extras when a network/index is available; without them the
# suite still runs (hypothesis property tests skip via tests/_hypothesis_stub).
set -euo pipefail
cd "$(dirname "$0")/.."

if python -m pip install -q -r requirements-dev.txt 2>/dev/null; then
    echo "ci: dev extras installed"
else
    echo "ci: offline — dev extras skipped (hypothesis tests will skip)"
fi

# --durations=25 surfaces the slowest tests in the workflow log so tier-1
# runtime creep is visible in every CI run, not discovered after the fact.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q --durations=25

# Benchmark smoke: every wire codec (repro/comm) runs end-to-end on a tiny
# config — SVRG family AND the stateful Newton family (giant/newton_gmres
# rows guard the schema'd diff-coded wire) — and int8 stays on the fp32
# convergence track; codec regressions fail CI here instead of surviving
# until the full benchmark run.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.ext_compression --smoke

# Fused local-trajectory kernels: the interpret-mode kernel↔oracle parity
# suite (bit-exact on granule shapes) runs inside tier-1 above; re-select it
# here by name so a kernel regression is called out as such in the CI log,
# not buried in the full-suite dots.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_local_update.py -k "KernelParity or MaskedRow"

# Round-engine smoke: the chunked/donated engine, the fused-AA path and the
# fused local_impl rows (tree vs pallas on the eligible vmap cells) run
# end-to-end, emitting a scratch artifact (benchmarks/results/
# BENCH_round_smoke.json — smoke never clobbers the committed trajectory).
# The gate validates the fresh emission AND that the committed repo-root
# BENCH_round.json is still the well-formed FULL grid (which includes the
# fused-beats-tree and headline >2x acceptance bars).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_round --smoke
python scripts/check_bench_round.py benchmarks/results/BENCH_round_smoke.json
python scripts/check_bench_round.py BENCH_round.json --require-full

# Cohort smoke: sampled-cohort engine rounds (C=16 gathered out of the
# K-sized client store, frozen non-sampled rows) run end-to-end on the
# reduced K sweep, including XLA's compiled-memory analysis of the chunk
# executable — exercising the gather/scatter round plan under CI. Scratch
# output only; the committed K∈{32,512,4096} sweep lives in
# benchmarks/results/ext_cohort.json.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.ext_cohort --smoke

# Robustness smoke (repro/robust): every fault kind (dropout / stale /
# byzantine uplink + history / DP noise) executes finitely on both defense
# settings, the clean run is bit-identical defense-on vs -off, a repeated
# FaultPlan is bit-deterministic, and the byz-history acceptance pair holds
# (undefended non-finite, clip_rtol-defended finite). The checker then
# validates the COMMITTED fault-matrix artifact's acceptance invariants
# (smoke writes nothing — the committed matrix is regenerated only by
# `python -m benchmarks.ext_robustness`).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.ext_robustness --smoke
python scripts/check_ext_robustness.py benchmarks/results/ext_robustness.json

# Straggler smoke (repro/robust/async_agg): a deadline-gated run under a
# heavy-tailed latency plan converges finitely, an inactive AsyncConfig is
# bitwise-off on both runtimes, and mixed latency+dropout gated rounds are
# bit-deterministic across repeats and runtimes. The checker then validates
# the COMMITTED straggler artifact's acceptance invariants (gated run
# reaches 1e-6 within 2x the barriered rounds at a fraction of its
# simulated wall-clock; smoke writes nothing — the committed artifact is
# regenerated only by `python -m benchmarks.ext_async`).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.ext_async --smoke
python scripts/check_ext_async.py benchmarks/results/ext_async.json

# Telemetry smoke (repro/obs): a chunked engine run streams per-round rows
# to a JSONL sink (with the default health monitors attached), then the
# schema validator checks the versioned header/round/footer contract — so a
# row-schema or sink regression fails CI before any long run depends on the
# telemetry. Scratch artifact only (gitignored).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.launch.fl_train \
    --arch smollm-135m --reduced --algo fedosaa_svrg --rounds 6 \
    --clients 4 --round-chunk 3 \
    --metrics-out benchmarks/results/metrics_smoke.jsonl
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python scripts/check_metrics_jsonl.py \
    benchmarks/results/metrics_smoke.jsonl

# Checkpoint save-overlap smoke (repro/checkpoint): all three boundary
# policies (none / async per-shard / sync_gather baseline) run the
# bit-identical math and the checkpointing modes commit clean saves with
# v4 footers. The checker then validates the COMMITTED save-overlap
# artifact's acceptance invariant (async per-chunk overhead <= 10% of the
# no-checkpoint floor; smoke writes nothing — the committed artifact is
# regenerated only by `python -m benchmarks.ext_checkpoint`).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.ext_checkpoint --smoke
python scripts/check_ext_checkpoint.py benchmarks/results/ext_checkpoint.json

# Kill-resume smoke (the preemption story end-to-end): a real fl_train
# subprocess is hard-killed MID-SAVE by the crash-injection fs (exit 43),
# leaving a committed checkpoint plus a torn staging remnant; `--resume
# auto` must skip the remnant, restore the newest complete checkpoint, and
# finish the run — both segments' JSONL rows unioning to one contiguous
# history (segment 2 passes the full v4 contract). Scratch artifacts only.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python scripts/kill_resume_smoke.py
