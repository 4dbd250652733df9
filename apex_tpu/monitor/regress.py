"""Baseline comparison for bench records — flag metric regressions.

A bench's last good record is a file; this module closes the loop by
DIFFING a fresh record against that one so a perf regression fails loudly at bench time instead of
surfacing rounds later in a human's spreadsheet:

* :func:`load_record` — reads a record file in any of the repo's shapes:
  one JSON object, a JSONL file (last parseable line wins — the sink
  convention), or the ``BENCH_r0*.json`` wrapper whose payload sits under
  ``"parsed"``.
* :func:`compare_records` — walks the two records' shared numeric fields
  (nested dicts flattened to dotted keys), classifies each as
  higher-better (throughput/goodput/MFU/occupancy) or lower-better
  (latency ``*_ms*``, violation counts) by name — unclassifiable keys are
  skipped, never guessed — and flags changes beyond ``tol`` in the bad
  direction. Returns a JSON-serializable report.
* CLI: ``python -m apex_tpu.monitor.regress BASELINE NEW [--tol 0.1]`` —
  table to stderr, one ``json_record`` line to stdout, exit 1 on
  regression (CPU-rehearsal records are refused by the caller before
  this ever runs).
"""

from __future__ import annotations

import json
import math
import sys
from typing import Any, Dict, List, Mapping, Optional, Tuple

from apex_tpu.monitor.sink import json_record

__all__ = ["classify_metric", "compare_records", "flatten_record",
           "load_record", "main"]

# name fragments that decide polarity; first match wins, explicit rules
# override. Conservative on purpose: a key matching neither is SKIPPED.
_HIGHER = ("tokens_per_s", "goodput", "_rps", "mfu", "occupancy",
           "throughput", "hidden_fraction", "good_fraction",
           # serve throughput tier 2: a collapsing prefix-cache hit rate
           # or draft acceptance rate is a regression (stage-11 gate)
           "hit_rate", "acceptance_rate",
           # megakernel A/B: the fused-vs-per-op decode-step ratio is the
           # stage-12 headline — a shrinking speedup is a regression
           "speedup",
           # FSDP round: hidden ring bytes + the modeled HBM drop factor
           # (checked BEFORE _LOWER, so these never fall into the generic
           # *_bytes lower-is-better rules below)
           "hidden_bytes", "hbm_reduction",
           # disaggregated cluster (stage 15): admitted requests/s is the
           # router headline — already matched by "_rps", listed so the
           # gate's coverage is explicit next to its shed_rate dual
           "admitted_rps",
           # sub-8-bit round (stage 17): concurrent contexts a fixed KV
           # budget serves — the int4-KV headline (halving pool bytes
           # must double it; a drop is a capacity regression)
           "contexts_max",
           # elastic/chaos round (stage 18): the goodput the cluster
           # keeps while a worker dies mid-run, and the good-SLO
           # fraction of the surviving traffic (both already matched by
           # the generic goodput/good_fraction fragments — listed so
           # the chaos gate's coverage is explicit next to its
           # lower-is-better duals below)
           "goodput_under_chaos_rps", "survivor_good_fraction",
           # fleet observability round (stage 19): the fraction of
           # workers the FleetScraper reached (a scrape hole is a blind
           # spot) and the fleet-wide goodput roll-up (already matched
           # by the goodput fragment; listed for explicit coverage)
           "scrape_coverage", "fleet_goodput_rps",
           # per-tenant LoRA round (stage 20): registry hit rate
           # (already matched by the generic hit_rate fragment; listed
           # for explicit coverage) and the fraction of adapter-bound
           # handoffs the router landed adapter-warm — a falling warm
           # rate means the fleet-mix placement stopped working
           "adapter_hit_rate", "adapter_warm_dispatch_rate",
           # performance-forensics round (stage 21): the fraction of
           # retired requests the attribution plane decomposed with the
           # sum identity intact, and the fraction the meter charged —
           # a coverage hole is a blind billing/diagnosis spot
           "attrib_coverage", "meter_coverage",
           # megakernel tier-2 round (stage 23): the speculative-decode
           # draft acceptance rate at the fused verify step (already
           # matched by the generic acceptance_rate fragment; listed so
           # the verify A/B gate's coverage is explicit next to its
           # verify_step_ms dual in _LOWER)
           "spec_acceptance_rate")
_LOWER = ("_ms", "violation", "latency", "bubble", "exposed_bytes",
          # disaggregated cluster (stage 15): a rising shed fraction is a
          # capacity regression (transfer_ms falls under the generic
          # "_ms" rule; listed here for the same explicitness)
          "shed_rate", "transfer_ms",
          # FSDP round: the headline memory/wire accounting — growing
          # per-chip param HBM, peak HBM or FSDP bytes-on-wire is a
          # regression (hidden_fraction, the overlap headline, is in
          # _HIGHER; wire_bytes_fsdp only — the generic "wire_bytes"
          # fragment would also gate baseline-side columns such as
          # wire_bytes_off, where only the ratio matters)
          "hbm_params_bytes", "peak_hbm_bytes", "wire_bytes_fsdp",
          # analyze round (stage 16): the contract-checker record fields —
          # growing exposed collective traffic (exposed_bytes above),
          # f32↔bf16 convert round-trips, host syncs reachable from a
          # step, or new lint violations are all regressions
          "convert_churn", "host_syncs", "lint_violations",
          "fp32_dots", "donated_copied",
          # sub-8-bit round (stage 17): bits per cached KV element and
          # the int4 wire-byte column (scoped like wire_bytes_fsdp — the
          # generic "wire_bytes" fragment would gate baseline columns);
          # a rising fp8 cast-saturation fraction means the delayed
          # scales stopped tracking the dynamic range
          "kv_bits", "wire_bytes_int4", "fp8_overflow_rate",
          # elastic/chaos round (stage 18): more migrations, replayed
          # tokens, worker deaths, heartbeat misses or transfer retries
          # under the SAME deterministic chaos plan means the cluster
          # got less stable (a retry storm, flappier membership) — all
          # lower-is-better
          "migrations_total", "replayed_tokens", "worker_deaths",
          "heartbeat_misses", "transfer_retries",
          # fleet observability round (stage 19): more alert firings
          # under the same plan means a flappier fleet, scrape_ms is the
          # cost of the scrape itself (also caught by the generic "_ms"
          # rule; listed so the gate's coverage is explicit), and a
          # trace that stopped stitching across hosts is broken
          # observability, not a style issue
          "alerts_fired_total", "scrape_ms", "trace_stitch_failures",
          "series_dropped_total", "scrape_misses", "dropped_records",
          # per-tenant LoRA round (stage 20): time spent installing
          # adapters into pools (also caught by the generic "_ms" rule;
          # listed for explicit coverage) and LRU eviction churn — more
          # evictions under the same tenant mix means the pool is
          # thrashing
          "adapter_load_ms", "adapter_evictions",
          # performance-forensics round (stage 21): per-component
          # latency attribution (also caught by the generic "_ms" rule;
          # listed so the diagnosis fields' coverage is explicit), the
          # per-tenant billing headline rates, and the trend gate's own
          # drift score — a rising score means the longitudinal series
          # is walking away from its history
          "_component_ms", "cost_per_token", "cost_per_request",
          "drift_score",
          # elastic-training round (stage 22): reshard arithmetic time
          # (also caught by the generic "_ms" rule; listed so the elastic
          # gate's coverage is explicit), SDC disagreements and straggler
          # flags under the SAME deterministic chaos plan (more means the
          # sentinels got noisier or the fleet sicker), and step retries
          # (a retry storm is a regression even when every retry
          # eventually succeeds). elastic_resumes_total is deliberately
          # NOT listed: how many times a run resumed at a new topology is
          # the scheduler's business, informational either way
          "reshard_ms", "sdc_disagreements_total",
          "straggler_flags_total", "retries_total",
          # megakernel tier-2 round (stage 23): the fused-vs-unfused
          # decode/verify step latencies (also caught by the generic
          # "_ms" rule; listed so the verify A/B gate's coverage is
          # explicit — these are the headline quantiles the stage banks)
          "verify_step_ms", "decode_step_ms",
          # plan-sharded serving round (stage 24): per-layer weight
          # gather latency and the PP stage-idle fraction (both also
          # caught by the generic "_ms"/"bubble" rules; listed so the
          # serve-plan gate's coverage is explicit), and the modeled
          # model-residency bytes — a growing footprint for the same
          # checkpoint means the residency accounting (or the plan's
          # shard math) regressed; hbm_chip_bytes is the per-chip
          # residency the budget headline compares against
          "weight_gather_ms", "pp_bubble_fraction", "hbm_model_bytes",
          "hbm_chip_bytes")


def classify_metric(key: str,
                    rules: Optional[Mapping[str, str]] = None
                    ) -> Optional[str]:
    """'higher' | 'lower' | None (skip) for a flattened record key."""
    if rules:
        for pat, direction in rules.items():
            if pat in key:
                return direction
    low = key.lower()
    if any(t in low for t in _HIGHER):
        return "higher"
    if any(t in low for t in _LOWER):
        return "lower"
    return None


def flatten_record(rec: Mapping[str, Any], prefix: str = ""
                   ) -> Dict[str, float]:
    """Dotted-key flattening of a record's numeric fields (bools and
    non-numeric leaves dropped; histogram dumps skipped entirely — their
    count/sum/min would otherwise classify as '_ms' latencies through the
    dotted key and flag a fuller run as a regression; the quantile
    summaries are the comparable surface)."""
    out: Dict[str, float] = {}
    for k, v in rec.items():
        key = f"{prefix}{k}"
        if k in ("schema", "ts", "buckets", "spec", "config", "hists",
                 "provenance"):
            continue
        if isinstance(v, Mapping):
            if "buckets" in v and "spec" in v:
                continue  # an embedded Histogram.to_dict, wherever it sits
            out.update(flatten_record(v, prefix=f"{key}."))
        elif isinstance(v, bool):
            continue
        elif isinstance(v, (int, float)) and math.isfinite(v):
            out[key] = float(v)
    return out


def compare_records(baseline: Mapping[str, Any], new: Mapping[str, Any],
                    tol: float = 0.1,
                    rules: Optional[Mapping[str, str]] = None
                    ) -> Dict[str, Any]:
    """Diff two bench records. A key regresses when it moves beyond
    ``tol`` (relative) in its bad direction; a zero baseline regresses on
    ANY bad-direction move (violation counts: 0 → n must flag). Returns
    ``{ok, compared, regressions: [...], improvements: [...]}``."""
    fb, fn = flatten_record(baseline), flatten_record(new)
    regressions: List[Dict[str, Any]] = []
    improvements: List[Dict[str, Any]] = []
    compared = 0
    for key in sorted(set(fb) & set(fn)):
        direction = classify_metric(key, rules)
        if direction is None:
            continue
        b, n = fb[key], fn[key]
        compared += 1
        if b == n:
            continue
        worse = n < b if direction == "higher" else n > b
        if b == 0.0:
            delta = math.inf if n > 0 else -math.inf
        else:
            delta = (n - b) / abs(b)
        entry = {"key": key, "baseline": b, "new": n,
                 "delta_pct": (round(delta * 100, 2)
                               if math.isfinite(delta) else None),
                 "direction": direction}
        if worse and (not math.isfinite(delta) or abs(delta) > tol):
            regressions.append(entry)
        elif not worse and (not math.isfinite(delta) or abs(delta) > tol):
            improvements.append(entry)
    return {"ok": not regressions, "compared": compared, "tol": tol,
            "regressions": regressions, "improvements": improvements}


def load_record(path: str) -> Dict[str, Any]:
    """Load a bench record: whole-file JSON, else JSONL (last parseable
    line). A ``BENCH_r0*.json``-style wrapper unwraps to its ``parsed``
    payload."""
    with open(path) as f:
        text = f.read()
    try:
        rec = json.loads(text)
    except json.JSONDecodeError:
        rec = None
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
        if rec is None:
            raise ValueError(f"{path}: no parseable JSON record")
    if isinstance(rec, dict) and isinstance(rec.get("parsed"), dict):
        rec = rec["parsed"]
    if not isinstance(rec, dict):
        raise ValueError(f"{path}: record is not a JSON object")
    return rec


def _format_rows(entries: List[Dict[str, Any]], label: str) -> List[str]:
    lines = []
    for e in entries:
        d = (f"{e['delta_pct']:+.1f}%" if e["delta_pct"] is not None
             else "from 0")
        lines.append(f"  {label} {e['key']}: {e['baseline']:g} -> "
                     f"{e['new']:g} ({d}, {e['direction']}-better)")
    return lines


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="flag metric regressions between two bench records")
    ap.add_argument("baseline")
    ap.add_argument("new")
    ap.add_argument("--tol", type=float, default=0.1,
                    help="relative tolerance before flagging (default 0.1)")
    args = ap.parse_args(argv)
    report = compare_records(load_record(args.baseline),
                             load_record(args.new), tol=args.tol)
    print(f"compared {report['compared']} metrics "
          f"(tol {args.tol:.0%}): "
          f"{len(report['regressions'])} regressions, "
          f"{len(report['improvements'])} improvements", file=sys.stderr)
    for line in _format_rows(report["regressions"], "REGRESSED"):
        print(line, file=sys.stderr)
    for line in _format_rows(report["improvements"], "improved"):
        print(line, file=sys.stderr)
    print(json_record(metric="regress_report", baseline=args.baseline,
                      new=args.new, **report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
