"""The program's own spans and kernel names, read from a profiler trace.

``bench/trace.py`` reduces a traced window with the benchmark's host spans
(``bench.*``). The program records finer ones itself, on the same clock
(repro/obs/profiling.py lists them): ``fl.init_state`` around a job's first
state, and per chunk a ``fl.chunk`` step span with the children
``fl.engine.dispatch``, ``fl.engine.wait``, ``fl.engine.fetch`` and
``fl.engine.rows``. Its Pallas kernels carry stable names
(``pallas_call(name=...)``). This module gives, for one chip of a window
bounded by the ``bench.job_init``/``bench.job_end`` spans, as
``bench/trace.py`` bounds it:

* the device's idle time split by the innermost span covering each idle
  instant, the program's or the benchmark's (``host`` where none does),
  which sums to window minus busy;
* the number of ``fl.chunk`` and ``fl.init_state`` spans in the window, the
  bases of per-chunk and per-job readings;
* the device time of each named kernel's own instructions, found from the
  compiled HLO text by the kernel's name, never by the HLO's numbering.

A program without these spans or names gives empty readings, not an error.
"""
from __future__ import annotations

import dataclasses
import re

from bench import trace as T

#: host spans the engine and init_state open (core/engine.py,
#: core/algorithms.py), outermost first
PROGRAM_SPANS = ("fl.init_state", "fl.chunk", "fl.engine.dispatch",
                 "fl.engine.wait", "fl.engine.fetch", "fl.engine.rows")
#: pallas_call names of the main path's kernels (kernels/local_update,
#: kernels/anderson)
KERNELS = ("fl_local_trajectory_kernel", "aa_gram_kernel", "aa_update_kernel")
HLO_CUSTOM_CALL_RE = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*custom_call_target="tpu_custom_call"'
    r'.*op_name="([^"]*)"')


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    busy_s: float
    idle_s: dict          # innermost span (or "host") -> seconds
    counts: dict          # program span -> spans inside the window
    kernel_s: dict        # kernel name -> device seconds of its instructions

    def per(self, names: tuple, base: str):
        """Idle milliseconds under ``names`` per ``base`` span in the window,
        or None when the window holds no ``base`` span."""
        n = self.counts.get(base, 0)
        if not n:
            return None
        return 1e3 * sum(self.idle_s.get(k, 0.0) for k in names) / n


def kernel_map(hlo_texts: list, kernels: tuple = KERNELS) -> dict:
    """{instruction name: kernel name} of the Mosaic custom calls whose
    ``op_name`` holds one of ``kernels`` as a path element (``vmap(...)``
    and other transform wrappers stripped)."""
    out = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = HLO_CUSTOM_CALL_RE.match(line)
            if not m:
                continue
            parts = set(re.findall(r"[\w.\-]+", m.group(2)))
            hits = [k for k in kernels if k in parts]
            if hits:
                out[m.group(1)] = hits[0]
    return out


def program_spans(data, lo: float, hi: float) -> dict:
    """{span name: [(start_ns, end_ns)]} of the program's host spans that
    overlap [lo, hi]."""
    spans = {n: [] for n in PROGRAM_SPANS}
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e > lo and s < hi:
                        spans[ev.name].append((s, e))
    return spans


def attribute_idle(busy: list, lo: float, hi: float, spans: dict) -> dict:
    """Idle time in [lo, hi] outside ``busy`` (sorted disjoint intervals
    inside it), each idle instant given to the innermost span covering it:
    the one that started last, the shorter on a tie. Time no span covers
    goes to ``host``. Returns {name: seconds}; the values sum to the idle
    total."""
    flat = sorted((a, b, name) for name, ivs in spans.items()
                  for a, b in ivs if b > a)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    totals: dict = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        over = [sp for sp in flat if sp[0] < e and sp[1] > s]
        cuts = sorted({s, e} | {x for a, b, _ in over for x in (a, b)
                                if s < x < e})
        for p, q in zip(cuts, cuts[1:]):
            cover = [sp for sp in over if sp[0] <= p and sp[1] >= q]
            name = max(cover, key=lambda sp: (sp[0], -sp[1]))[2] \
                if cover else "host"
            totals[name] = totals.get(name, 0.0) + 1e-9 * (q - p)
    return totals


def reduce(path: str, device_id: int, scope_of: dict,
           kernel_of: dict) -> SpanSummary:
    """The window's program-span readings on chip ``device_id``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    bench_spans = T._host_spans(data)
    starts = [s for s, _ in bench_spans["bench.job_init"]]
    ends = [e for _, e in bench_spans["bench.job_end"]]
    if not starts or not ends:
        raise ValueError(f"{path}: no bench.job_init/bench.job_end spans")
    lo, hi = min(starts), max(ends)
    ops, _ = T._device_ops(data, device_id, scope_of)
    busy = T._union(T._clip([(s, e) for s, e, _, _ in ops], lo, hi))
    spans = program_spans(data, lo, hi)
    every = {**bench_spans, **spans}
    kernel_s = {}
    for k in set(kernel_of.values()):
        ivs = [(s, e) for s, e, name, _ in ops if kernel_of.get(name) == k]
        kernel_s[k] = 1e-9 * T._length(T._union(T._clip(ivs, lo, hi)))
    return SpanSummary(
        window_s=1e-9 * (hi - lo), busy_s=1e-9 * T._length(busy),
        idle_s=attribute_idle(busy, lo, hi, every),
        counts={n: sum(a >= lo and b <= hi for a, b in ivs)
                for n, ivs in spans.items()},
        kernel_s=kernel_s)
