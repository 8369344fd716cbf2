"""Reduce a profiler trace of a benchmark window to the per-layer numbers.

Reads the ``.xplane.pb`` the JAX profiler writes, with
``jax.profiler.ProfileData`` and nothing else, and gives for the cell's chips:

* the traced window: from the first ``bench.job_init`` host span to the end of
  the last ``bench.job_end`` span (the benchmark's own spans, run.py);
* busy seconds: the union of the intervals in which an XLA operation ran on
  the chip (the trace's "XLA Ops" line), inside the window, averaged over the
  chips;
* device seconds per round phase, for every ``fl.*`` scope
  (``jax.named_scope``) that occurs in the compiled runner: the union of the
  intervals of the operations whose phase it is, per chip. A device event
  names only its HLO instruction, so the phase comes from the compiled
  runner's HLO text (``scope_map``): the innermost ``fl.*`` scope of the
  instruction's ``op_name``, looked up for the events that ran inside the
  runner's module (``jit_chunk_fn``). An operation counts towards its
  innermost scope alone, so an enclosing scope's time leaves out what its
  inner scopes ran;
* the device operations that took most time (leaf operations, summed by
  phase and name over the chips), and the device's idle gaps labelled by the
  benchmark host span that overlaps each most (``host`` where none does: the
  engine's own loop between chunks).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: the device plane of chip ``i`` and its line of XLA operations
DEVICE_PLANE = "/device:TPU:{}"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
#: asynchronous collectives: their start-to-done span counts towards their
#: phase (a collective in flight is the exchange's time); other async ops
#: (copies, slices) overlap compute and count nowhere
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
#: the module of the engine's chunk runner (jax.jit of core/engine.chunk_fn)
RUNNER_MODULE = "jit_chunk_fn("
#: host spans the benchmark opens around a job's parts (run.py, harness.py)
HOST_SPANS = ("bench.job_init", "bench.dispatch", "bench.sync",
              "bench.job_end")
SCOPE_RE = re.compile(r"fl\.[a-z]+(?:_[a-z]+)*")
HLO_OP_RE = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*op_name="([^"]*)"')
EVENT_OP_RE = re.compile(r"^%([\w.\-]+) = ")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # mean over the chips
    phase_s: dict                 # scope -> [seconds per chip]
    top_ops: list                 # [(phase:name, seconds summed over chips)]
    idle_gaps: list               # [(host span, seconds)] on the first chip
    notes: list

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def scope_map(hlo_texts: list) -> dict:
    """{instruction name: innermost fl.* scope of its op_name} over the
    compiled runner's HLO texts (instructions without a scope left out)."""
    out = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = HLO_OP_RE.match(line)
            if m:
                hits = SCOPE_RE.findall(m.group(2))
                if hits:
                    out[m.group(1)] = hits[-1]
    return out


def _host_spans(data) -> dict:
    spans = {n: [] for n in HOST_SPANS}
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    spans[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return spans


def _device_ops(data, device_id: int, scope_of: dict) -> tuple:
    """([(start_ns, end_ns, instruction, scope)] of the chip's XLA
    operations, [the same] of its asynchronous collectives); the scope of an
    op that ran inside the runner's module."""
    plane = data.find_plane_with_name(DEVICE_PLANE.format(device_id))
    if plane is None:
        return [], []
    runner, events = [], {OPS_LINE: [], ASYNC_LINE: []}
    for line in plane.lines:
        for ev in line.events:
            iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
            if line.name == MODULES_LINE and ev.name.startswith(RUNNER_MODULE):
                runner.append(iv)
            elif line.name in events:
                m = EVENT_OP_RE.match(ev.name)
                name = m.group(1) if m else ev.name
                if line.name == OPS_LINE or name.startswith(COLLECTIVES):
                    events[line.name].append((*iv, name))
    runner = _union(runner)

    def scoped(evs):
        ops, k = [], 0
        for s, e, name in sorted(evs):
            while k < len(runner) and runner[k][1] < s:
                k += 1
            inside = k < len(runner) and runner[k][0] <= s
            ops.append((s, e, name, scope_of.get(name) if inside else None))
        return ops

    return scoped(events[OPS_LINE]), scoped(events[ASYNC_LINE])


def _leaves(ops: list) -> list:
    """The ops that contain no other op (a control-flow op's interval
    covers its body's ops)."""
    out = []
    for i, op in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[0] < op[1] and nxt[1] <= op[1]:
            continue
        out.append(op)
    return out


def reduce(path: str, device_ids: list, scope_of: dict) -> TraceSummary:
    """The trace at ``path`` over the chips ``device_ids``, with the phase of
    each runner instruction from ``scope_of`` (``scope_map``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans = _host_spans(data)
    starts = [s for s, _ in spans["bench.job_init"]]
    ends = [e for _, e in spans["bench.job_end"]]
    if not starts or not ends:
        raise ValueError(f"{path}: no bench.job_init/bench.job_end spans")
    lo, hi = min(starts), max(ends)
    window_ns = hi - lo
    scopes = sorted(set(scope_of.values()))
    busy, phase = [], {s: [] for s in scopes}
    op_time: dict = {}
    first_busy = None
    notes = []
    for dev in device_ids:
        ops, collectives = (
            [op for op in found if op[1] > lo and op[0] < hi]
            for found in _device_ops(data, dev, scope_of))
        if not ops:
            notes.append(f"trace: no XLA ops on device {dev} in the window")
        union = _union(_clip([(s, e) for s, e, _, _ in ops], lo, hi))
        busy.append(_length(union))
        if first_busy is None:
            first_busy = union
        for sc in scopes:
            phase[sc].append(1e-9 * _length(_union(_clip(
                [(s, e) for s, e, _, o in ops + collectives if o == sc],
                lo, hi))))
        for s, e, name, sc in _leaves(ops):
            key = f"{sc or '-'}:{name}"
            op_time[key] = op_time.get(key, 0.0) + 1e-9 * (min(e, hi)
                                                           - max(s, lo))
    gaps = _label_gaps(first_busy or [], lo, hi, spans)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])
    summary = TraceSummary(
        window_s=1e-9 * window_ns,
        busy_s=1e-9 * sum(busy) / max(len(busy), 1),
        phase_s={k: v for k, v in phase.items() if any(v)},
        top_ops=top, idle_gaps=gaps, notes=notes)
    summary.notes.append(
        f"trace: window {summary.window_s:.6f} s, busy {summary.busy_s:.6f} s,"
        f" phases " + ", ".join(f"{k} {[round(x, 6) for x in v]}"
                                for k, v in summary.phase_s.items()))
    return summary


def _label_gaps(busy: list, lo: float, hi: float, spans: dict) -> list:
    """Idle time between busy intervals, summed by the host span that
    overlaps each gap most."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    totals: dict = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        best, label = 0.0, "host"
        for name, ivs in spans.items():
            over = sum(max(0.0, min(e, b) - max(s, a)) for a, b in ivs)
            if over > best:
                best, label = over, name
        totals[label] = totals.get(label, 0.0) + 1e-9 * (e - s)
    return sorted(totals.items(), key=lambda kv: -kv[1])
