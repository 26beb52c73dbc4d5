#!/usr/bin/env python
"""Compare two+ BENCH_r*.json artifacts: per-metric value trajectory
with loud regression/hang flags — the OFFLINE complement to the
watchdog's online hang detection.

A row that measured in one round and silently came back null with a
timeout error in the next is exactly the failure a human scanning JSON
blobs misses — this tool calls a value -> null transition out as
``HANG`` and exits nonzero under ``--strict``.

Each artifact is the driver's wrapper shape ``{"n", "cmd", "rc",
"tail", "parsed"}``: every JSON line in ``tail`` is one metric row
(headline + --all extras + per-mix evidence), ``parsed`` is the
headline fallback when the tail has none. Bare ``{"metric": ...}``
JSONL files work too.

Flags per metric, per round transition:

  HANG        value -> null (or the metric vanished while its file
              reports an error) — the silent-timeout class
  REGRESSION  numeric drop beyond --threshold (default 20%) on
              higher-is-better metrics (heuristic: metrics whose unit
              mentions sec/latency/overhead/fraction are
              lower-is-better and flag on RISES instead)
  RECOVERED   null -> value
  NEW/GONE    the metric (dis)appeared between rounds

Usage:
    python tools/bench_diff.py BENCH_r01.json BENCH_r02.json ...
    python tools/bench_diff.py --json --strict BENCH_r*.json
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional

__all__ = ["load_rounds", "diff", "format_report"]

# explicit higher-is-better override, checked FIRST: cache hit rates
# and throughputs whose unit strings would otherwise trip the
# lower-is-better heuristic below (e.g. "hit fraction"). The PR 15
# metrics need no new entries — "qps" already covers
# qps_under_autoscale (name AND unit), and remediation_recovery is
# lower-is-better by both its "recovery" name and "seconds" unit —
# but both directions are pinned by tests/test_control.py. The
# sparse serving rows also need no new entries: sparse_serving_qps is
# higher-is-better by "qps" (name AND unit) and
# fresh_weight_to_served_ms lower-is-better by its "_ms" suffix (and
# "ms ..." unit) — both directions pinned by
# tests/test_sparse_serving.py. The step-engine rows likewise ride
# the existing patterns:
# composed_step_overhead is lower-is-better by its "overhead" name
# (and "% step time" unit), pipelined_sparse_throughput is
# higher-is-better by its "examples/sec" unit — both directions are
# pinned by tests/test_step_engine.py. The pipeline-stage rows (PR
# 19): pipeline_parallel_throughput rides "examples/sec"
# (higher-is-better), pipeline_bubble_fraction is lower-is-better by
# its "fraction" unit AND the explicit "bubble" token below (so a
# future rename of the unit string cannot silently flip it) — both
# directions pinned by tests/test_step_engine.py. The elastic rows are both
# lower-is-better via existing patterns — elastic_join_catchup by its
# "seconds" unit, reshard_bytes by its "bytes" unit — and both
# directions are pinned by tests/test_control.py. The PR 20
# join_commit_latency row is lower-is-better TWICE over ("latency"
# name and "seconds" unit); both directions are pinned by
# tests/test_control.py so neither pattern can silently rot.
_HIGHER_IS_BETTER = re.compile(
    r"(hit.?rate|hit.fraction|speedup|examples/sec|tokens/s|qps"
    r"|rows/s)",
    re.IGNORECASE)

# lower-is-better heuristic by unit/metric name: a drop in these is an
# improvement, a rise is the regression
_LOWER_IS_BETTER = re.compile(
    r"(seconds|_ms\b|latency|overhead|fraction|p9\d|bytes|recovery"
    r"|bubble)",
    re.IGNORECASE)


def _round_key(path: str, payload: dict):
    n = payload.get("n")
    if isinstance(n, int):
        return n
    m = re.search(r"r?(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else path


def _metric_key(row: dict) -> Optional[str]:
    metric = row.get("metric")
    if not metric:
        return None
    if "library" in row:  # per-mix evidence lines
        return "%s[%s]" % (metric, row["library"])
    return metric


def load_rounds(paths: List[str]) -> List[dict]:
    """[{round, path, rows: {metric_key: row}, error}] sorted by
    round."""
    out = []
    for path in paths:
        with open(path) as f:
            text = f.read()
        rows: Dict[str, dict] = {}
        file_error = None
        try:
            payload = json.loads(text)
        except ValueError:
            payload = None
        if isinstance(payload, dict) and ("tail" in payload
                                          or "parsed" in payload):
            rnd = _round_key(path, payload)
            for line in (payload.get("tail") or "").splitlines():
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                key = _metric_key(row)
                if key:
                    rows[key] = row
            parsed = payload.get("parsed")
            if isinstance(parsed, dict):
                key = _metric_key(parsed)
                if key and key not in rows:
                    rows[key] = parsed
            elif parsed is None and not rows:
                file_error = "no parsed headline (rc=%s)" \
                    % payload.get("rc")
        else:
            # bare JSONL of metric rows
            rnd = _round_key(path, {})
            for line in text.splitlines():
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                key = _metric_key(row)
                if key:
                    rows[key] = row
        out.append({"round": rnd, "path": path, "rows": rows,
                    "error": file_error})
    out.sort(key=lambda r: (isinstance(r["round"], str), r["round"]))
    return out


def _flag_transition(metric, prev, cur, threshold, cur_error=None):
    """-> (flag, note) for one metric between consecutive rounds
    (cur/prev are rows or None; ``cur_error`` is the newer ROUND's
    file-level failure, which makes a missing metric a hang, not a
    removal)."""
    pv = prev.get("value") if prev else None
    cv = cur.get("value") if cur else None
    if prev is None and cur is not None:
        if cv is None and cur.get("error"):
            return ("HANG", "appeared already dead: null value (%s)"
                    % cur["error"])
        return ("NEW", "appeared (value=%r)" % (cv,))
    if prev is not None and cur is None:
        if cur_error is not None:
            return ("HANG", "value %r -> whole round failed (%s)"
                    % (pv, cur_error)) if pv is not None else \
                   (None, None)
        return ("GONE", "metric vanished from this round")
    if pv is not None and cv is None:
        err = (cur.get("error") or "no value") if cur else "missing"
        return ("HANG", "value %r -> null (%s)" % (pv, err))
    if pv is None and cv is not None:
        return ("RECOVERED", "null -> %r" % (cv,))
    if pv is None and cv is None:
        return (None, None)
    try:
        pv_f, cv_f = float(pv), float(cv)
    except (TypeError, ValueError):
        return (None, None)
    if pv_f == 0:
        return (None, None)
    unit = (cur.get("unit") or "") + " " + metric
    lower_better = bool(_LOWER_IS_BETTER.search(unit)) \
        and not _HIGHER_IS_BETTER.search(unit)
    change = (cv_f - pv_f) / abs(pv_f)
    if not lower_better and change < -threshold:
        return ("REGRESSION", "%.4g -> %.4g (%.0f%%)"
                % (pv_f, cv_f, change * 100))
    if lower_better and change > threshold:
        return ("REGRESSION", "%.4g -> %.4g (+%.0f%% on a "
                "lower-is-better metric)" % (pv_f, cv_f, change * 100))
    return (None, None)


def diff(rounds: List[dict], threshold: float = 0.20) -> dict:
    """Per-metric trajectory + flagged transitions across the given
    rounds (already sorted)."""
    metrics = sorted({k for r in rounds for k in r["rows"]})
    trajectories = {}
    flags = []
    for m in metrics:
        traj = []
        for r in rounds:
            row = r["rows"].get(m)
            traj.append({"round": r["round"],
                         "value": row.get("value") if row else None,
                         "present": row is not None,
                         "error": row.get("error") if row else None})
        trajectories[m] = traj
        for a, b in zip(rounds, rounds[1:]):
            flag, note = _flag_transition(
                m, a["rows"].get(m), b["rows"].get(m), threshold,
                cur_error=b["error"])
            if flag:
                flags.append({"metric": m, "flag": flag,
                              "from_round": a["round"],
                              "to_round": b["round"], "note": note})
    order = {"HANG": 0, "REGRESSION": 1, "GONE": 2, "RECOVERED": 3,
             "NEW": 4}
    flags.sort(key=lambda f: (order.get(f["flag"], 9), f["metric"]))
    return {
        "rounds": [{"round": r["round"], "path": r["path"],
                    "metrics": len(r["rows"]), "error": r["error"]}
                   for r in rounds],
        "trajectories": trajectories,
        "flags": flags,
        "hangs": [f for f in flags if f["flag"] == "HANG"],
        "regressions": [f for f in flags
                        if f["flag"] == "REGRESSION"],
    }


def format_report(report: dict) -> str:
    lines = ["bench_diff: %d round(s): %s"
             % (len(report["rounds"]),
                ", ".join("r%s(%d rows)" % (r["round"], r["metrics"])
                          for r in report["rounds"]))]
    # flags first, LOUD — the whole point is that a hang cannot hide
    for f in report["flags"]:
        lines.append("!! %-10s %-45s r%s->r%s  %s"
                     % (f["flag"], f["metric"], f["from_round"],
                        f["to_round"], f["note"]))
    if not report["flags"]:
        lines.append("no flags: every shared metric held within "
                     "threshold")
    lines.append("")
    for m, traj in sorted(report["trajectories"].items()):
        vals = " -> ".join(
            ("%.4g" % t["value"]) if isinstance(t["value"],
                                                (int, float))
            else ("null" if t["present"] else "-")
            for t in traj)
        lines.append("  %-45s %s" % (m, vals))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("files", nargs="+",
                    help="two or more BENCH_r*.json artifacts")
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="relative change that counts as a "
                    "regression (default 0.20)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any HANG or REGRESSION flag "
                    "fires")
    args = ap.parse_args(argv)
    if len(args.files) < 2:
        ap.error("need at least two bench artifacts to diff")
    report = diff(load_rounds(args.files), threshold=args.threshold)
    if args.json:
        print(json.dumps(report, indent=2, default=repr))
    else:
        print(format_report(report))
    if args.strict and (report["hangs"] or report["regressions"]):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
