"""Observability overhead A/B — what does the fleet plane cost?

The ISSUE-14 gate: monitor tier 3 (distributed tracing + per-worker
flight rings + FleetScraper + alert rules) must cost ≤ ~5% tokens/s on
the loadgen serving workload, or it is not an always-on plane. This
bench runs the SAME seeded Poisson+burst workload through a 2-host
disaggregated cluster twice:

* **on** — full fleet observability: every event JSONL-sunk with trace
  ids bound, flight rings armed, FleetScraper + an alert rule evaluated
  every tick;
* **off** — the floor: no sink, no rings, no scraping, no rules.

ONE ``json_record`` line carries ``tokens_per_s_on/off``, the
``observe_overhead_pct`` delta (the ok gate, ``--overhead-tol``),
``scrape_ms_p50/p99`` (the scraper measuring itself), ``events_per_s``
written to the sink, ``alerts_fired_total`` and
``trace_stitch_failures`` (must be 0 — broken stitching is broken
observability, not overhead). CPU rehearsals carry ``_CPU_FALLBACK`` —
the ≤ 5% claim is a TPU truth, not yet measured (CPU decode steps are
~10× slower, flattering the overhead).

Run: ``python benchmarks/bench_observe.py [--out FILE]``.
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    import argparse

    import jax
    import jax.numpy as jnp

    from apex_tpu.monitor.sink import collect_provenance, set_provenance

    set_provenance(collect_provenance())  # after the pin: backend is final

    from apex_tpu.monitor import (
        AlertRule,
        Condition,
        EventLog,
        JsonlSink,
        SloSpec,
        json_record,
        stitch_traces,
    )
    from apex_tpu.serve import (
        ClusterConfig,
        RouterConfig,
        ServeCluster,
        ServeConfig,
    )
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params
    from loadgen import WorkloadConfig, build_workload, run_workload

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None)
    ap.add_argument("--n-requests", type=int, default=64)
    ap.add_argument("--rate-rps", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--overhead-tol", type=float, default=0.05,
                    help="max tokens/s fraction the full plane may cost "
                         "(the ok gate; ISSUE-14 pins 5%%)")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the ON pass's events.jsonl + trace.json "
                         "here (default: a temp dir, discarded)")
    args = ap.parse_args(argv)

    on_tpu = jax.default_backend() == "tpu"
    name = "gpt_serve_observe_ab"
    if not on_tpu:
        name += "_CPU_FALLBACK"

    # the pinned bench model (bench_serve.py / bench_serve_mh constants)
    HIDDEN, LAYERS, HEADS, VOCAB, MAX_SEQ = 128, 2, 8, 512, 256
    SLOTS, BLOCK_SIZE = 4, 16
    cfg = GPTConfig(vocab_size=VOCAB, max_seq=MAX_SEQ, hidden=HIDDEN,
                    num_layers=LAYERS, num_heads=HEADS,
                    dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    wcfg = WorkloadConfig(n_requests=args.n_requests,
                          rate_rps=args.rate_rps, seed=args.seed,
                          prompt_len_max=MAX_SEQ // 2)
    workload = build_workload(wcfg, VOCAB, MAX_SEQ)
    slo = SloSpec(ttft_ms=2000.0, tpot_ms=200.0)
    scfg = ServeConfig(num_slots=SLOTS, block_size=BLOCK_SIZE,
                       prefix_cache=False)

    class _CountingSink:
        """JsonlSink shim counting records so events/s is measured at
        the sink boundary (what durable observability actually wrote)."""

        def __init__(self, inner):
            self.inner = inner
            self.n = 0

        def write(self, **fields):
            self.n += 1
            self.inner.write(**fields)

        def flush(self):
            self.inner.flush()

    def run(observe: bool, trace_dir=None):
        if observe:
            sink = _CountingSink(JsonlSink(
                os.path.join(trace_dir, "events.jsonl"),
                buffer_steps=64, rotate_bytes=32 << 20))
            events = EventLog(sink=sink, keep=True)
            ccfg = ClusterConfig(
                n_prefill=1, n_decode=1, serve=scfg,
                router=RouterConfig(slo=slo),
                scrape_every=1, flight_capacity=2048,
                alert_rules=(AlertRule("backlog_high", conditions=(
                    Condition("queued_tokens", ">", 4.0 * MAX_SEQ),)),))
        else:
            sink = None
            events = None
            ccfg = ClusterConfig(
                n_prefill=1, n_decode=1, serve=scfg,
                router=RouterConfig(slo=slo),
                scrape_every=0, flight_capacity=0)
        cl = ServeCluster(params, cfg, ccfg, retain_streams=False,
                          events=events)
        t0 = time.perf_counter()
        stats = run_workload(cl, workload)
        wall = time.perf_counter() - t0
        if observe:
            sink.inner.close()
        return cl, stats, wall, sink

    # warm pass compiles the programs so neither timed pass pays XLA
    run(False)

    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = args.trace_dir or tmp
        os.makedirs(trace_dir, exist_ok=True)
        cl_on, st_on, wall_on, sink = run(True, trace_dir)
        stitch = stitch_traces(cl_on._events.records)
        if args.trace_dir:
            from apex_tpu.monitor import write_chrome_trace

            write_chrome_trace(os.path.join(trace_dir, "trace.json"),
                               cl_on._events.records)
    cl_off, st_off, wall_off, _ = run(False)

    tps_on = st_on.get("generated_tokens", 0) / wall_on
    tps_off = st_off.get("generated_tokens", 0) / wall_off
    overhead = (tps_off - tps_on) / tps_off if tps_off else None
    fleet = cl_on.stats()["fleet"]
    streams_equal = (st_on.get("completed") == st_off.get("completed")
                     and st_on.get("generated_tokens")
                     == st_off.get("generated_tokens"))
    ok = bool(streams_equal
              and stitch["stitch_failures"] == 0
              and overhead is not None
              and overhead <= args.overhead_tol)
    rec = {
        "metric": name,
        "ok": ok,
        "tokens_per_s_on": round(tps_on, 3),
        "tokens_per_s_off": round(tps_off, 3),
        "observe_overhead_pct": (round(100 * overhead, 2)
                                 if overhead is not None else None),
        "overhead_tol_pct": round(100 * args.overhead_tol, 2),
        # observation must never perturb the WORK: same tokens out
        "streams_equal": streams_equal,
        "events_per_s": round(sink.n / wall_on, 1) if wall_on else None,
        "events_total": sink.n,
        "scrape_ms_p50": fleet.get("scrape_ms_p50"),
        "scrape_ms_p99": fleet.get("scrape_ms_p99"),
        "scrapes_total": fleet.get("scrapes_total"),
        "scrape_coverage": fleet.get("scrape_coverage"),
        "alerts_fired_total": fleet["alerts"]["alerts_fired_total"],
        "trace_stitch_failures": stitch["stitch_failures"],
        "traces_minted": fleet.get("traces_minted"),
        "goodput_rps_on": st_on.get("goodput_rps"),
        "goodput_rps_off": st_off.get("goodput_rps"),
        "fleet_goodput_rps": st_on.get("fleet_goodput_rps"),
        "completed": st_on.get("completed"),
        "workload": {"n": wcfg.n_requests, "rate_rps": wcfg.rate_rps,
                     "seed": wcfg.seed, "mode": wcfg.mode},
        "backend": jax.default_backend(),
    }
    line = json_record(**rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
