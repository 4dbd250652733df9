"""Longitudinal trend gating over banked watcher records (tier 4).

``monitor.regress`` diffs two records pairwise, so a 15% gate never trips
on a 3%-per-week drift: each hourly record sits inside tolerance of the
one before it while the series walks away. This module closes that gap
with an append-only HISTORY per watcher stage and robust drift detection
over the whole series:

* **history** — one ``trend_point`` JSONL line per banked record
  (:func:`append_history` rides ``json_record``, so entries carry the
  schema stamp and — when the emitting process set one — the shared
  provenance dict: git sha / jax version / backend / hostname, without
  which a detected drift can't be tied to what changed);
  :func:`load_history` reads it back through ``read_jsonl`` (rotation-
  and crash-tail-tolerant like every sink in the repo).
* **detection** (:func:`detect_trends`) — per flattened metric key
  (polarity from ``regress.classify_metric``; unclassifiable keys are
  skipped, never guessed):

  - *step changes*: robust z of the recent ``window`` records' median
    against the older records' median, scaled by 1.4826·MAD (floored at
    ``rel_floor`` of the baseline so a zero-variance series isn't a
    hair-trigger). Beyond ``threshold`` in the BAD direction → drift.
  - *slow drifts*: Theil–Sen slope (median of pairwise slopes — robust
    to outlier records) over the full series; a projected total move
    beyond ``threshold`` scales in the bad direction → drift, even when
    every pairwise hop stayed under the regress gate.

  Good-direction moves never flag (an improvement is not a drift), and
  the report carries a ``drift_score`` (max bad |z| / threshold; 0 when
  clean) — itself lower-better under regress.
* **CLI** — ``python -m apex_tpu.monitor.trend append HISTORY RECORD
  [--stage S]`` banks a record into the history;
  ``python -m apex_tpu.monitor.trend check HISTORY [--window W]
  [--threshold Z] [--min-records N]`` prints the verdict table to stderr,
  one ``json_record`` line to stdout, and exits 1 on drift — it runs
  next to (never instead of) the pairwise regress gate. A history shorter than ``--min-records`` passes
  trivially: the gate arms itself as evidence accumulates.
"""

from __future__ import annotations

import statistics
import sys
from typing import Any, Dict, Iterable, List, Mapping, Optional

from apex_tpu.monitor.regress import (
    classify_metric,
    flatten_record,
    load_record,
)
from apex_tpu.monitor.sink import json_record, read_jsonl

__all__ = ["append_history", "detect_trends", "load_history", "main",
           "theil_sen_slope"]

DEFAULT_WINDOW = 5
DEFAULT_THRESHOLD = 6.0
DEFAULT_MIN_RECORDS = 8
# MAD floor as a fraction of the baseline median: series quieter than
# this are treated as having this much noise (a 0.1% wiggle on a
# dead-flat series is not a changepoint)
DEFAULT_REL_FLOOR = 0.02


def append_history(path: str, record: Mapping[str, Any],
                   stage: Optional[str] = None) -> str:
    """Append one banked record to a trend history file; returns the
    written line. Provenance rides automatically when the process set
    one (``sink.set_provenance``)."""
    line = json_record(kind="trend_point", stage=stage, record=dict(record))
    import os

    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write(line + "\n")
    return line


def load_history(path: str, stage: Optional[str] = None,
                 limit: Optional[int] = None) -> List[Dict[str, Any]]:
    """The banked records (oldest first) from a history file, optionally
    filtered by stage and truncated to the newest ``limit``."""
    pts = [r["record"] for r in read_jsonl(path)
           if r.get("kind") == "trend_point"
           and isinstance(r.get("record"), dict)
           and (stage is None or r.get("stage") == stage)]
    return pts[-limit:] if limit else pts


def theil_sen_slope(ys: List[float]) -> float:
    """Median of all pairwise slopes (per record-index step) — the
    robust trend estimator: up to ~29% outlier records can't move it."""
    n = len(ys)
    if n < 2:
        return 0.0
    slopes = [(ys[j] - ys[i]) / (j - i)
              for i in range(n) for j in range(i + 1, n)]
    return statistics.median(slopes)


def _mad_scale(xs: List[float], rel_floor: float) -> float:
    m = statistics.median(xs)
    mad = statistics.median([abs(x - m) for x in xs])
    return max(1.4826 * mad, rel_floor * abs(m), 1e-12)


def detect_trends(history: Iterable[Mapping[str, Any]], *,
                  window: int = DEFAULT_WINDOW,
                  threshold: float = DEFAULT_THRESHOLD,
                  min_records: int = DEFAULT_MIN_RECORDS,
                  rel_floor: float = DEFAULT_REL_FLOOR,
                  rules: Optional[Mapping[str, str]] = None
                  ) -> Dict[str, Any]:
    """Drift report over a record series (oldest first). Returns
    ``{ok, n_records, checked, drifts: [...], drift_score, ...}``."""
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    recs = [flatten_record(r) for r in history]
    n = len(recs)
    report: Dict[str, Any] = {"ok": True, "n_records": n, "checked": 0,
                              "window": window, "threshold": threshold,
                              "min_records": min_records,
                              "drifts": [], "drift_score": 0.0}
    if n < min_records or n < window + 3:
        return report  # not armed yet — never block on a thin history
    keys = sorted(set(recs[-1]) if recs else ())
    score = 0.0
    for key in keys:
        direction = classify_metric(key, rules)
        if direction is None:
            continue
        xs = [r[key] for r in recs if key in r]
        if len(xs) < min_records or len(xs) < window + 3:
            continue
        report["checked"] += 1
        base, recent = xs[:-window], xs[-window:]
        scale = _mad_scale(base, rel_floor)
        m, r = statistics.median(base), statistics.median(recent)
        z = (r - m) / scale
        bad_z = z > 0 if direction == "lower" else -z > 0
        slope = theil_sen_slope(xs)
        projected = slope * (len(xs) - 1)
        bad_slope = projected > 0 if direction == "lower" else projected < 0
        kind = None
        if bad_z and abs(z) > threshold:
            kind = "step"
        elif bad_slope and abs(projected) > threshold * scale:
            kind = "slope"
        if kind is None:
            continue
        report["drifts"].append({
            "key": key, "direction": direction, "kind": kind,
            "baseline_median": round(m, 6), "recent_median": round(r, 6),
            "z": round(z, 3), "slope_per_record": round(slope, 6),
            "projected_move": round(projected, 6),
        })
        score = max(score, abs(z) / threshold,
                    abs(projected) / (threshold * scale))
    report["ok"] = not report["drifts"]
    report["drift_score"] = round(score if report["drifts"] else 0.0, 4)
    return report


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="longitudinal trend gate over banked bench records")
    sub = ap.add_subparsers(dest="cmd", required=True)

    ap_a = sub.add_parser("append", help="bank one record into a history")
    ap_a.add_argument("history")
    ap_a.add_argument("record", help="record file (json / jsonl / wrapper)")
    ap_a.add_argument("--stage", default=None)

    ap_c = sub.add_parser("check", help="drift-gate a history (exit 1)")
    ap_c.add_argument("history")
    ap_c.add_argument("--stage", default=None)
    ap_c.add_argument("--window", type=int, default=DEFAULT_WINDOW)
    ap_c.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD)
    ap_c.add_argument("--min-records", type=int,
                      default=DEFAULT_MIN_RECORDS)
    ap_c.add_argument("--limit", type=int, default=64,
                      help="newest records considered (default 64)")
    args = ap.parse_args(argv)

    if args.cmd == "append":
        from apex_tpu.monitor import sink as _sink

        # stamp provenance for THIS append only — an in-process caller
        # (tests, a watcher embedding main()) must not find the module
        # global mutated after we return
        prior = _sink._PROVENANCE
        if prior is None:
            _sink.set_provenance(_sink.collect_provenance())
        try:
            rec = load_record(args.record)
            append_history(args.history, rec, stage=args.stage)
            n = len(load_history(args.history, stage=args.stage))
            print(json_record(metric="trend_append", history=args.history,
                              stage=args.stage, n_records=n), flush=True)
        finally:
            _sink.set_provenance(prior)
        return 0

    history = load_history(args.history, stage=args.stage,
                           limit=args.limit)
    report = detect_trends(history, window=args.window,
                           threshold=args.threshold,
                           min_records=args.min_records)
    print(f"trend: {report['n_records']} records, "
          f"{report['checked']} metrics checked "
          f"(window {args.window}, z > {args.threshold:g}): "
          f"{len(report['drifts'])} drifts", file=sys.stderr)
    for d in report["drifts"]:
        print(f"  DRIFT[{d['kind']}] {d['key']}: "
              f"{d['baseline_median']:g} -> {d['recent_median']:g} "
              f"(z={d['z']:g}, slope={d['slope_per_record']:g}/rec, "
              f"{d['direction']}-better)", file=sys.stderr)
    print(json_record(metric="trend_report", history=args.history,
                      stage=args.stage, **report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
