"""Forensics overhead A/B — what does the tier-4 plane cost?

The ISSUE-17 gate: monitor tier 4 (per-request latency attribution +
per-tenant cost metering) must cost ≤ ~5% tokens/s on the loadgen
serving workload, or it is not an always-on plane. Same discipline as
``bench_observe.py`` (stage 19): run the SAME seeded multi-tenant
workload through a 2-host disaggregated cluster twice:

* **on** — ``ClusterConfig(metering=True, attribution=True)``: every
  retirement attributed into queue/prefill/transfer/decode/stall and
  charged to its tenant under the cost model;
* **off** — ``metering=False, attribution=False``: the floor.

ONE ``json_record`` line carries ``tokens_per_s_on/off``, the
``forensics_overhead_pct`` delta (the ok gate, ``--overhead-tol``),
``attrib_coverage`` (must be 1.0 — an unattributed retirement is a
broken plane, not overhead), the component p50/p99s, per-tenant cost
rollup vs fleet totals (``rollup_matches_totals`` must hold to the
unit) and ``cost_per_token``. CPU rehearsals carry ``_CPU_FALLBACK`` —
the ≤ 5% claim is a TPU truth, not yet measured.

Run: ``python benchmarks/bench_attrib_cost.py [--out FILE]``.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    import argparse

    import jax
    import jax.numpy as jnp

    from apex_tpu.monitor.sink import collect_provenance, set_provenance

    set_provenance(collect_provenance())  # after the pin: backend is final

    from apex_tpu.monitor import SloSpec, json_record
    from apex_tpu.monitor.attrib import COMPONENTS
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
    ap.add_argument("--n-tenants", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--overhead-tol", type=float, default=0.05,
                    help="max tokens/s fraction the forensics plane may "
                         "cost (the ok gate; ISSUE-17 pins 5%%)")
    args = ap.parse_args(argv)

    on_tpu = jax.default_backend() == "tpu"
    name = "gpt_serve_attrib_cost_ab"
    if not on_tpu:
        name += "_CPU_FALLBACK"

    # the pinned bench model (bench_serve.py / bench_observe constants)
    HIDDEN, LAYERS, HEADS, VOCAB, MAX_SEQ = 128, 2, 8, 512, 256
    SLOTS, BLOCK_SIZE = 4, 16
    cfg = GPTConfig(vocab_size=VOCAB, max_seq=MAX_SEQ, hidden=HIDDEN,
                    num_layers=LAYERS, num_heads=HEADS,
                    dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    wcfg = WorkloadConfig(n_requests=args.n_requests,
                          rate_rps=args.rate_rps, seed=args.seed,
                          prompt_len_max=MAX_SEQ // 2,
                          n_tenants=args.n_tenants)
    workload = build_workload(wcfg, VOCAB, MAX_SEQ)
    slo = SloSpec(ttft_ms=2000.0, tpot_ms=200.0)
    scfg = ServeConfig(num_slots=SLOTS, block_size=BLOCK_SIZE,
                       prefix_cache=False)

    def run(forensics: bool):
        # everything except the tier-4 plane is identical (no scraping,
        # no flight rings): the delta isolates attribution + metering
        ccfg = ClusterConfig(
            n_prefill=1, n_decode=1, serve=scfg,
            router=RouterConfig(slo=slo),
            scrape_every=0, flight_capacity=0,
            metering=forensics, attribution=forensics)
        cl = ServeCluster(params, cfg, ccfg, retain_streams=False)
        t0 = time.perf_counter()
        stats = run_workload(cl, workload)
        wall = time.perf_counter() - t0
        return cl, stats, wall

    # warm pass compiles the programs so neither timed pass pays XLA
    run(False)

    cl_on, st_on, wall_on = run(True)
    cl_off, st_off, wall_off = run(False)

    tps_on = st_on.get("generated_tokens", 0) / wall_on
    tps_off = st_off.get("generated_tokens", 0) / wall_off
    overhead = (tps_off - tps_on) / tps_off if tps_off else None
    streams_equal = (st_on.get("completed") == st_off.get("completed")
                     and st_on.get("generated_tokens")
                     == st_off.get("generated_tokens"))

    full = cl_on.stats()
    meter = full.get("meter", {})
    tenants = meter.get("tenants", {})
    totals = meter.get("totals", {})
    # per-tenant rollup must equal fleet totals to the unit (the ledgers
    # are exact; displayed values are rounded to 1e-6 per tenant)
    rollup = sum(t.get("cost_units", 0.0) for t in tenants.values())
    rollup_ok = (abs(rollup - totals.get("cost_units", 0.0))
                 <= max(len(tenants), 1) * 1e-6)
    coverage = full.get("attrib_coverage")

    ok = bool(streams_equal
              and coverage == 1.0
              and full.get("meter_coverage") == 1.0
              and rollup_ok
              and overhead is not None
              and overhead <= args.overhead_tol)
    rec = {
        "metric": name,
        "ok": ok,
        "tokens_per_s_on": round(tps_on, 3),
        "tokens_per_s_off": round(tps_off, 3),
        "forensics_overhead_pct": (round(100 * overhead, 2)
                                   if overhead is not None else None),
        "overhead_tol_pct": round(100 * args.overhead_tol, 2),
        # forensics must never perturb the WORK: same tokens out
        "streams_equal": streams_equal,
        "attrib_coverage": coverage,
        "meter_coverage": full.get("meter_coverage"),
        **{f"{c}_component_ms_{q}": full.get(f"{c}_component_ms_{q}")
           for c in COMPONENTS for q in ("p50", "p99")},
        "cost_per_token": full.get("cost_per_token"),
        "cost_per_request": full.get("cost_per_request"),
        "rollup_matches_totals": rollup_ok,
        "n_tenants": len(tenants),
        "tenant_cost_units": {t: v.get("cost_units")
                              for t, v in sorted(tenants.items())},
        "worker_cost_rates": meter.get("worker_cost_rates"),
        "overflow_charges_total": meter.get("overflow_charges_total"),
        "completed": st_on.get("completed"),
        "goodput_rps_on": st_on.get("goodput_rps"),
        "goodput_rps_off": st_off.get("goodput_rps"),
        "workload": {"n": wcfg.n_requests, "rate_rps": wcfg.rate_rps,
                     "seed": wcfg.seed, "mode": wcfg.mode,
                     "n_tenants": wcfg.n_tenants},
        "backend": jax.default_backend(),
    }
    line = json_record(**rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    # ok:false is a bench FAILURE (broken attribution/rollup or a plane
    # too expensive to leave on), not a slow record
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
