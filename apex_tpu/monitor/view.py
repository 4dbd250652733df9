"""``python -m apex_tpu.monitor.view FILE.jsonl`` — latency/SLO summary.

The one-command read of a serve telemetry log (step records, lifecycle
events and gauges share one JSONL file — ``view`` partitions by the
``kind`` field). Human table to **stderr**, one machine-readable
``json_record`` line to **stdout**, so scripts and humans read the same
invocation.

Per-request latencies are reconstructed from the lifecycle events
(``submitted → admitted → first_token → retired``); pass SLO budgets
(``--ttft-budget`` / ``--tpot-budget`` / ``--queue-budget`` /
``--e2e-budget``, ms) to get goodput/violation accounting through
:class:`~apex_tpu.monitor.slo.SloTracker` on the same records. Rotated
sinks (``FILE.jsonl.1`` …) are read transparently via ``read_jsonl``.

Tier 4: logs whose lifecycle carries prefill/transfer anchors
additionally get the per-component **latency attribution** table
(queue/prefill/transfer/decode/stall p50/p99 via
:func:`~apex_tpu.monitor.attrib.attribution_summary`) and a per-tenant
rollup (requests / tokens / per-component time totals — "who consumed
the fleet's time" straight from the event stream, no meter required);
``--baseline OTHER.jsonl`` diffs the two logs through
:func:`~apex_tpu.monitor.attrib.explain_regression` and names the top-3
regressed components — the diagnosis, not just the verdict.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional

__all__ = ["main", "summarize"]


def _pct(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    s = sorted(vals)
    rank = max(1, int(-(-q * len(s) // 1)))  # ceil, nearest-rank
    return round(s[min(rank, len(s)) - 1], 3)


def _request_latencies(events: List[Dict[str, Any]]
                       ) -> Dict[str, Dict[str, Optional[float]]]:
    """uid -> {ttft_ms, queue_ms, e2e_ms, tpot_ms, n_tokens} from the
    lifecycle events (dimensions missing when the log lacks the events).

    Reconstruction is per TRACE, not per (uid, log): merged multi-worker
    streams are deduplicated first, and a migrated request — which
    carries a SECOND ``admitted`` (on the destination host) plus
    ``replay``-re-emitted chunks — anchors on the FIRST ``submitted`` /
    ``admitted`` / ``first_token`` and the LAST ``retired``, so its
    queue wait, TTFT and e2e are the client-observed ones, not the
    resumption bookkeeping's. (Before this, the last ``admitted`` won
    and a migrated request double-counted its queue wait.)"""
    from apex_tpu.monitor.events import _dedupe_events

    # the EARLIEST occurrence anchors every event except the terminal
    # ones, where the LATEST is the real end of the request — min/max by
    # timestamp, not stream position, so merged logs read identically in
    # any concatenation order
    _LAST = ("retired", "shed")
    by_uid: Dict[str, Dict[str, Any]] = {}
    for r in _dedupe_events(events):
        uid = r.get("uid")
        if uid is None:
            continue
        evs = by_uid.setdefault(uid, {})
        cur = evs.get(r["event"])
        if cur is None:
            evs[r["event"]] = r
        elif r["event"] in _LAST:
            if float(r["t_ms"]) > float(cur["t_ms"]):
                evs[r["event"]] = r
        elif float(r["t_ms"]) < float(cur["t_ms"]):
            evs[r["event"]] = r
    out: Dict[str, Dict[str, Optional[float]]] = {}
    for uid, evs in by_uid.items():
        t = {k: float(v["t_ms"]) for k, v in evs.items()}
        lat: Dict[str, Optional[float]] = {
            "queue_ms": (t["admitted"] - t["submitted"]
                         if {"admitted", "submitted"} <= t.keys() else None),
            "ttft_ms": (t["first_token"] - t["submitted"]
                        if {"first_token", "submitted"} <= t.keys()
                        else None),
            "e2e_ms": (t["retired"] - t["submitted"]
                       if {"retired", "submitted"} <= t.keys() else None),
        }
        ret = evs.get("retired", {})
        n = ret.get("n_tokens")
        lat["n_tokens"] = n
        lat["tpot_ms"] = (
            (t["retired"] - t["first_token"]) / (n - 1)
            if n and n > 1 and {"retired", "first_token"} <= t.keys()
            else None)
        out[uid] = lat
    return out


def summarize(records: List[Dict[str, Any]],
              slo=None) -> Dict[str, Any]:
    """The view record: event/step/gauge counts, per-request latency
    quantiles, optional SLO accounting (``slo``: an
    :class:`~apex_tpu.monitor.slo.SloSpec`)."""
    from apex_tpu.monitor.events import _dedupe_events

    # in-log flight-dump copies are marked and never counted twice
    records = [r for r in records if "flight_worker" not in r]
    events = [r for r in _dedupe_events(records)
              if r.get("kind") == "event"]
    gauges = [r for r in records if r.get("kind") == "gauge"]
    steps = [r for r in records if "kind" not in r]
    lats = _request_latencies(events)
    rec: Dict[str, Any] = {
        "n_records": len(records), "n_events": len(events),
        "n_gauges": len(gauges), "n_steps": len(steps),
        "n_requests": len(lats),
        "n_retired": sum(1 for r in events if r["event"] == "retired"),
    }
    # fleet-tier events, when the log carries them
    for name, ev in (("n_migrations", "migrate_start"),
                     ("n_replays", "replay"),
                     ("n_alerts_fired", "alert_fire"),
                     ("n_flight_dumps", "flight_dump")):
        n = sum(1 for r in events if r["event"] == ev)
        if n:
            rec[name] = n
    for dim in ("ttft_ms", "queue_ms", "tpot_ms", "e2e_ms"):
        vals = [v[dim] for v in lats.values() if v.get(dim) is not None]
        if vals:
            rec[f"{dim}_p50"] = _pct(vals, 0.5)
            rec[f"{dim}_p99"] = _pct(vals, 0.99)
    step_ms = [r["step_ms"] for r in steps if "step_ms" in r]
    if step_ms:
        rec["decode_step_ms_p50"] = _pct(step_ms, 0.5)
        rec["decode_step_ms_p99"] = _pct(step_ms, 0.99)
    occ = [r["occupancy"] for r in steps if "occupancy" in r]
    if occ:
        rec["mean_occupancy"] = round(sum(occ) / len(occ), 4)
    # serve throughput-optimization telemetry (chunked prefill backlog,
    # speculative proposed/accepted per step, cumulative prefix-cache
    # counters — see serve.engine._emit_metrics)
    backlog = [r["prefill_backlog_tokens"] for r in steps
               if "prefill_backlog_tokens" in r]
    if backlog:
        rec["prefill_backlog_mean"] = round(
            sum(backlog) / len(backlog), 2)
        rec["prefill_backlog_max"] = max(backlog)
    proposed = sum(r.get("spec_proposed", 0) for r in steps)
    if proposed:
        accepted = sum(r.get("spec_accepted", 0) for r in steps)
        rec["spec_proposed"] = proposed
        rec["spec_accepted"] = accepted
        rec["spec_acceptance_rate"] = round(accepted / proposed, 4)
    cum = [r for r in steps if "prefix_blocks_needed_total" in r]
    if cum and cum[-1]["prefix_blocks_needed_total"]:
        last = cum[-1]
        rec["prefix_blocks_hit"] = last["prefix_blocks_hit_total"]
        rec["prefix_blocks_needed"] = last["prefix_blocks_needed_total"]
        rec["prefix_hit_rate"] = round(
            last["prefix_blocks_hit_total"]
            / last["prefix_blocks_needed_total"], 4)
        rec["prefill_flops_saved"] = last.get(
            "prefill_flops_saved_total")
    # tier-4 latency attribution: only when the log's lifecycle carries
    # the anchors the decomposition needs (an engine-only log without
    # prefill_start events yields nothing — the keys just stay absent)
    from apex_tpu.monitor.attrib import (
        COMPONENTS,
        attribute_requests,
        attribution_summary,
    )

    attrib = attribute_requests(events, deduped=True)
    if attrib:
        summ = attribution_summary(events)
        rec["attrib_coverage"] = summ["attrib_coverage"]
        for c in COMPONENTS:
            for q in ("p50", "p99"):
                k = f"{c}_component_ms_{q}"
                if summ.get(k) is not None:
                    rec[k] = summ[k]
        # per-tenant rollup: requests / tokens / per-component time
        # totals from the event stream alone ("who consumed the
        # fleet's time" — the meterless half of the billing view; the
        # priced half lives on cluster.stats()["meter"])
        tenants: Dict[str, Dict[str, Any]] = {}
        for uid, comp in attrib.items():
            tname = comp.get("tenant")
            if tname is None:
                continue
            led = tenants.setdefault(
                tname, {"requests": 0, "tokens": 0, "e2e_ms_total": 0.0,
                        **{f"{c}_ms_total": 0.0 for c in COMPONENTS}})
            led["requests"] += 1
            n = lats.get(uid, {}).get("n_tokens")
            led["tokens"] += int(n or 0)
            led["e2e_ms_total"] = round(
                led["e2e_ms_total"] + comp["e2e_ms"], 3)
            for c in COMPONENTS:
                led[f"{c}_ms_total"] = round(
                    led[f"{c}_ms_total"] + max(0.0, comp[c]), 3)
        if tenants:
            rec["tenants"] = dict(sorted(tenants.items()))
    if slo is not None and slo.budgets():
        from apex_tpu.monitor.slo import SloTracker

        tracker = SloTracker(slo)
        for v in lats.values():
            if v.get("ttft_ms") is None and v.get("e2e_ms") is None:
                continue  # never admitted/retired: nothing to account
            tracker.observe(ttft_ms=v.get("ttft_ms"),
                            tpot_ms=v.get("tpot_ms"),
                            queue_ms=v.get("queue_ms"),
                            e2e_ms=v.get("e2e_ms"))
        rep = tracker.report()
        rec["slo"] = slo.to_dict()
        rec["good"] = rep["good"]
        rec["good_fraction"] = rep["good_fraction"]
        rec["violations"] = rep["violations"]
    return rec


def _table(rec: Dict[str, Any]) -> List[str]:
    lines = [f"records: {rec['n_records']} "
             f"(events {rec['n_events']}, steps {rec['n_steps']}, "
             f"gauges {rec['n_gauges']}) | requests: {rec['n_requests']} "
             f"retired: {rec['n_retired']}"]
    rows = [(d, rec.get(f"{d}_p50"), rec.get(f"{d}_p99"))
            for d in ("ttft_ms", "queue_ms", "tpot_ms", "e2e_ms",
                      "decode_step_ms")]
    rows = [r for r in rows if r[1] is not None]
    if rows:
        lines.append(f"  {'metric':<16} {'p50':>10} {'p99':>10}")
        for name, p50, p99 in rows:
            lines.append(f"  {name:<16} {p50:>10.3f} {p99:>10.3f}")
    if rec.get("mean_occupancy") is not None:
        lines.append(f"  mean occupancy: {rec['mean_occupancy']}")
    if rec.get("prefix_hit_rate") is not None:
        lines.append(
            f"  prefix cache: {rec['prefix_blocks_hit']}"
            f"/{rec['prefix_blocks_needed']} blocks "
            f"({rec['prefix_hit_rate']}) "
            f"flops saved: {rec.get('prefill_flops_saved')}")
    if rec.get("spec_acceptance_rate") is not None:
        lines.append(
            f"  speculative: {rec['spec_accepted']}"
            f"/{rec['spec_proposed']} drafts accepted "
            f"({rec['spec_acceptance_rate']})")
    if rec.get("prefill_backlog_mean") is not None:
        lines.append(
            f"  prefill backlog: mean {rec['prefill_backlog_mean']} "
            f"max {rec['prefill_backlog_max']} tokens")
    if "violations" in rec:
        v = " ".join(f"{k}={n}" for k, n in rec["violations"].items())
        lines.append(f"  SLO: good {rec['good']}/{rec['n_retired']} "
                     f"({rec['good_fraction']}) violations: {v or 'none'}")
    comp_rows = [(c, rec.get(f"{c}_component_ms_p50"),
                  rec.get(f"{c}_component_ms_p99"))
                 for c in ("queue", "prefill", "transfer", "decode",
                           "stall")]
    comp_rows = [r for r in comp_rows if r[1] is not None]
    if comp_rows:
        lines.append(f"  attribution (coverage "
                     f"{rec.get('attrib_coverage')}):")
        lines.append(f"  {'component':<16} {'p50':>10} {'p99':>10}")
        for name, p50, p99 in comp_rows:
            lines.append(f"  {name:<16} {p50:>10.3f} {p99:>10.3f}")
    if rec.get("tenants"):
        lines.append(f"  {'tenant':<16} {'reqs':>6} {'tokens':>8} "
                     f"{'e2e_s':>8} {'decode_s':>9} {'queue_s':>8}")
        for tname, led in rec["tenants"].items():
            lines.append(
                f"  {tname:<16} {led['requests']:>6} {led['tokens']:>8} "
                f"{led['e2e_ms_total'] / 1e3:>8.2f} "
                f"{led['decode_ms_total'] / 1e3:>9.2f} "
                f"{led['queue_ms_total'] / 1e3:>8.2f}")
    if rec.get("explain") is not None:
        ex = rec["explain"]
        lines.append(
            f"  vs baseline: e2e {ex['baseline_mean_ms']} -> "
            f"{ex['new_mean_ms']} ms ({ex['delta_ms']:+.3f})")
        for e in ex["components"][:3]:
            share = (f" ({e['share'] * 100:.0f}% of the move)"
                     if e["share"] is not None else "")
            lines.append(
                f"    {e['component']:<10} {e['baseline_ms']} -> "
                f"{e['new_ms']} ms ({e['delta_ms']:+.3f}){share}")
        if ex["diagnosis"] is not None:
            lines.append(f"    diagnosis: {ex['diagnosis']} grew the most")
    return lines


def main(argv=None) -> int:
    import argparse

    from apex_tpu.monitor.sink import json_record, read_jsonl
    from apex_tpu.monitor.slo import SloSpec

    ap = argparse.ArgumentParser(
        description="summarize a monitor JSONL log (events + steps)")
    ap.add_argument("path")
    ap.add_argument("--ttft-budget", type=float, default=None)
    ap.add_argument("--tpot-budget", type=float, default=None)
    ap.add_argument("--queue-budget", type=float, default=None)
    ap.add_argument("--e2e-budget", type=float, default=None)
    ap.add_argument("--baseline", default=None, metavar="FILE.jsonl",
                    help="second event log to attribute an e2e move "
                         "against (explain_regression: top-3 regressed "
                         "components + diagnosis)")
    args = ap.parse_args(argv)
    slo = SloSpec(ttft_ms=args.ttft_budget, tpot_ms=args.tpot_budget,
                  queue_ms=args.queue_budget, e2e_ms=args.e2e_budget)
    records = list(read_jsonl(args.path))
    rec = summarize(records, slo=slo if slo.budgets() else None)
    if args.baseline is not None:
        from apex_tpu.monitor.attrib import explain_regression

        base_events = [r for r in read_jsonl(args.baseline)
                       if r.get("kind") == "event"
                       and "flight_worker" not in r]
        new_events = [r for r in records if r.get("kind") == "event"
                      and "flight_worker" not in r]
        rec["explain"] = explain_regression(base_events, new_events)
    for line in _table(rec):
        print(line, file=sys.stderr)
    print(json_record(metric="monitor_view", file=args.path, **rec),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
