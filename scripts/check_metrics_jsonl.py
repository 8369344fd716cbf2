#!/usr/bin/env python
"""Validate a telemetry JSONL file emitted by ``fl_train --metrics-out``
(or any JsonlSink — repro/obs/sinks.py).

Checks the versioned row contract the sink promises:

  * every line is strict JSON (no NaN/Infinity literals — non-finite values
    must have been serialized as null);
  * line 1 is a header row (kind="header") carrying the schema version,
    field list, and run metadata (algo/runtime/channel/uplink_bytes);
  * the last line is a footer row (kind="footer") whose "rounds" equals the
    number of round rows;
  * every row in between is kind="round" with all ROW_FIELDS present
    (numeric or null), matching schema version, and strictly increasing
    contiguous "round" indices from the header's start_round;
  * cumulative columns (comm_bytes_total, wall_time_s) are non-decreasing;
  * the v3 async triple (arrivals / staleness_mean / staleness_max) is
    internally consistent: arrivals is null exactly when the deadline gate
    is off (the whole run — the gate is a compile-time config, not a
    per-round toggle), a present arrivals is a non-negative count, and
    staleness_mean never exceeds staleness_max when both landed;
  * the v4 footer checkpoint triple (checkpoint_save_ms / checkpoint_bytes /
    checkpoint_failures) is present and sane: all three numeric and
    non-negative (zeros when checkpointing was off), failures an integer,
    and every checkpoint_failed alarm in the footer is reflected by a
    non-zero failure count;
  * the v5 footer compile count is a non-negative integer.

Exit 0 and a one-line summary on success; exit 1 with the first violation
otherwise.

  PYTHONPATH=src python scripts/check_metrics_jsonl.py metrics.jsonl
"""
from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, "src")

from repro.obs.sinks import ROW_FIELDS, SCHEMA_VERSION  # noqa: E402


def fail(lineno: int, msg: str) -> None:
    print(f"check_metrics_jsonl: line {lineno}: {msg}", file=sys.stderr)
    sys.exit(1)


def check_file(path: str) -> dict:
    with open(path) as f:
        lines = f.read().splitlines()
    if len(lines) < 2:
        fail(len(lines), "need at least a header and a footer row")

    rows = []
    for i, line in enumerate(lines, 1):
        try:
            # strict JSON: the nan->null sanitization is part of the contract
            rows.append(json.loads(line, parse_constant=lambda c: fail(
                i, f"non-strict JSON constant {c}")))
        except json.JSONDecodeError as e:
            fail(i, f"invalid JSON: {e}")

    header, body, footer = rows[0], rows[1:-1], rows[-1]
    if header.get("kind") != "header":
        fail(1, f"first row kind={header.get('kind')!r}, expected 'header'")
    if header.get("v") != SCHEMA_VERSION:
        fail(1, f"schema version {header.get('v')!r} != {SCHEMA_VERSION}")
    if header.get("fields") != list(ROW_FIELDS):
        fail(1, f"header fields {header.get('fields')} != {list(ROW_FIELDS)}")
    for key in ("algo", "runtime", "channel", "num_clients", "uplink_bytes"):
        if key not in header:
            fail(1, f"header missing {key!r}")
    if footer.get("kind") != "footer":
        fail(len(lines), f"last row kind={footer.get('kind')!r}, "
             "expected 'footer'")

    expected_round = int(header.get("start_round", 0))
    prev = {"comm_bytes_total": float("-inf"), "wall_time_s": float("-inf")}
    async_on = None  # per-run constant, learned from the first round row
    for off, row in enumerate(body):
        lineno = off + 2
        if row.get("kind") != "round":
            fail(lineno, f"kind={row.get('kind')!r}, expected 'round'")
        if row.get("v") != SCHEMA_VERSION:
            fail(lineno, f"schema version {row.get('v')!r}")
        if row.get("round") != expected_round:
            fail(lineno, f"round={row.get('round')}, expected "
                 f"{expected_round} (contiguous from start_round)")
        expected_round += 1
        for field in ROW_FIELDS:
            if field not in row:
                fail(lineno, f"missing field {field!r}")
            v = row[field]
            if v is not None and not isinstance(v, (int, float)):
                fail(lineno, f"field {field!r} is {type(v).__name__}, "
                     "expected number or null")
        for field in ("comm_bytes_total", "wall_time_s"):
            v = row[field]
            if v is not None:
                if v < prev[field]:
                    fail(lineno, f"{field} decreased: {v} < {prev[field]}")
                prev[field] = v
        # v3 async triple: the deadline gate is a compile-time config, so
        # arrivals is null on every row or a count on every row
        arrivals = row["arrivals"]
        if async_on is None:
            async_on = arrivals is not None
        elif (arrivals is not None) != async_on:
            fail(lineno, "arrivals flipped between null and numeric "
                 "mid-run (the deadline gate cannot toggle per round)")
        if arrivals is not None and arrivals < 0:
            fail(lineno, f"arrivals={arrivals} is negative")
        s_mean, s_max = row["staleness_mean"], row["staleness_max"]
        if s_mean is not None and s_max is not None and s_mean > s_max:
            fail(lineno, f"staleness_mean {s_mean} > staleness_max {s_max}")

    if footer.get("rounds") != len(body):
        fail(len(lines), f"footer rounds={footer.get('rounds')} but file "
             f"has {len(body)} round rows")
    # v4 footer checkpoint triple
    for field in ("checkpoint_save_ms", "checkpoint_bytes",
                  "checkpoint_failures"):
        v = footer.get(field)
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            fail(len(lines), f"footer {field}={v!r}, expected a number "
                 "(zeros when checkpointing is off)")
        if v < 0:
            fail(len(lines), f"footer {field}={v} is negative")
    if footer["checkpoint_failures"] != int(footer["checkpoint_failures"]):
        fail(len(lines), "footer checkpoint_failures="
             f"{footer['checkpoint_failures']} is not an integer count")
    compiles = footer.get("compiles")
    if not isinstance(compiles, int) or isinstance(compiles, bool) \
            or compiles < 0:
        fail(len(lines), f"footer compiles={compiles!r}, expected a "
             "non-negative integer count")
    n_failed_alarms = sum(
        1 for a in footer.get("alarms", [])
        if a.get("rule") == "checkpoint_failed")
    if n_failed_alarms and footer["checkpoint_failures"] < 1:
        fail(len(lines), f"{n_failed_alarms} checkpoint_failed alarm(s) in "
             "the footer but checkpoint_failures == 0")
    return {"rounds": len(body), "algo": header.get("algo"),
            "stopped": footer.get("stopped"),
            "alarms": len(footer.get("alarms", []))}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("paths", nargs="+")
    args = ap.parse_args()
    for path in args.paths:
        info = check_file(path)
        print(f"{path}: OK — {info['rounds']} rounds of {info['algo']}, "
              f"stopped={info['stopped']}, alarms={info['alarms']}")


if __name__ == "__main__":
    main()
