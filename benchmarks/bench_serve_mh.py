"""Multi-host disaggregated serving benchmark — goodput, shed, transfer.

The ROADMAP item-2 deliverable: drive ``apex_tpu.serve.cluster`` —
SLO-aware router → prefill hosts → KV-block transfer → decode hosts —
with the PR-6 closed-loop load generator (Poisson arrivals + bursts +
long-tail lengths + multi-tenant tags) at ≥ 2 simulated hosts and emit
ONE ``json_record`` line with:

* **goodput-under-SLO** (req/s meeting every latency budget), TTFT/TPOT
  p50/p99 from the merged streaming histograms, violation counts;
* **shed accounting** — ``shed_rate`` and per-tenant counters from the
  router's explicit load-shedding path, plus an ``overload`` sub-record
  from a second pass at ``--overload-factor``× the offered rate (arrival
  times compressed) showing graceful degradation: sheds recorded, kept
  traffic still inside budget, never a deadlock;
* **transfer wire accounting** — measured bytes shipped over the
  simulated transport, asserted byte-for-byte against the
  ``transfer_wire_bytes`` model (the ``comm.accounting`` convention);
  disagreement makes the record ``ok: false``;
* a **disaggregated-vs-colocated A/B**: the same workload through one
  colocated engine with the same total decode slots, so the record
  carries what the split bought (or cost) on this hardware.

``--chaos`` adds the ISSUE-13 **goodput-under-chaos** pass: the same
workload at ``--overload-factor``× (min 2×) with 1 of N decode workers
KILLED at ``--chaos-kill-step`` — its live requests migrate to the
survivors over the KV wire and the record carries
``goodput_under_chaos_rps`` / ``survivor_good_fraction`` (higher-better)
next to the recovery-noise counters (``migrations_total`` /
``replayed_tokens`` / ``worker_deaths`` / ``heartbeat_misses`` /
``transfer_retries``, lower-better). A chaos pass that fails to drain or
whose kill did not land makes the record ``ok: false``.

``--lora`` adds the PR-16 **per-tenant adapter A/B**: the same tenant
mix with every tenant bound to a LoRA adapter (loadgen's fixed
``t{i} -> ad{i % M}`` mapping) through an adapter-enabled fleet — the
record carries tokens/s + TTFT p99 next to the adapter-free pass, the
registry ``adapter_hit_rate`` and the router ``adapter_warm_dispatch_
rate`` (higher-better), ``adapter_load_ms`` / ``adapter_evictions``
(lower-better), and ``streams_equal``: the aid=0 cohort replayed
through both fleets must match BITWISE or the record is ``ok: false``.

``--plan {tp,pp,fsdp,all}`` swaps the cluster for the ISSUE-20
**plan-sharded serving pass**: one ``ParallelismPlan``-driven engine
(``apex_tpu.serve.sharded``) on a device slice, emitting the
>1-chip-HBM headline — a model whose ``hbm_model_bytes`` EXCEEDS one
simulated chip's budget (default: the midpoint of the plan-resident
and single-chip totals; the record carries all three numbers) still
serving the workload under the same SLO — next to the strategy's own
accounting (``weight_gather_ms`` + modeled wire bytes for fsdp,
``pp_bubble_fraction`` measured-vs-modeled for pp, the per-chip
residency cut for tp) and a monolithic-oracle stream pin
(``streams_equal`` — an undrained run or a stream mismatch makes the
record ``ok: false``). ``--plan all`` drives every strategy and the
flat gate fields take the worst case.

Run: ``python benchmarks/bench_serve_mh.py [--hosts 2] [--wire-mode
int8] [--out FILE]`` (also ``--hosts 3 --chaos``, ``--lora``, ``--plan
all``). CPU rehearsals carry ``_CPU_FALLBACK``; no record of this bench
has been taken on the chip.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    import argparse


    # the --plan pass shards a model over a device slice; a CPU rehearsal
    # only has the virtual devices it asks for, and the flag must land
    # before jax initializes the backend
    argv_probe = sys.argv[1:] if argv is None else list(argv)
    if (any(a == "--plan" or a.startswith("--plan=") for a in argv_probe)
            and os.environ.get("JAX_PLATFORMS") == "cpu"
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")

    import jax
    import jax.numpy as jnp

    from apex_tpu.monitor.sink import collect_provenance, set_provenance

    set_provenance(collect_provenance())  # after the pin: backend is final

    from apex_tpu.monitor import SloSpec, json_record
    from apex_tpu.serve import (
        ClusterConfig,
        InferenceEngine,
        RouterConfig,
        ServeCluster,
        ServeConfig,
        transfer_wire_bytes,
    )
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params
    from loadgen import WorkloadConfig, build_workload, run_workload

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None)
    ap.add_argument("--hosts", type=int, default=2,
                    help="total simulated hosts; split prefill/decode "
                         "(2 -> 1+1, 4 -> 2+2)")
    ap.add_argument("--prefill-hosts", type=int, default=None,
                    help="override the prefill side of the split")
    ap.add_argument("--decode-hosts", type=int, default=None,
                    help="override the decode side of the split")
    ap.add_argument("--n-requests", type=int, default=64)
    ap.add_argument("--rate-rps", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "int8", "int4"])
    ap.add_argument("--wire-mode", default="raw", choices=["raw", "int8"])
    ap.add_argument("--spec-k", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--megakernel", default="auto",
                    choices=["auto", "on", "off"])
    ap.add_argument("--n-tenants", type=int, default=2)
    ap.add_argument("--tenant-weights", default="3,1",
                    help="comma-separated WFQ weights, one per tenant")
    ap.add_argument("--ttft-budget", type=float, default=2000.0)
    ap.add_argument("--tpot-budget", type=float, default=200.0)
    ap.add_argument("--queue-budget", type=float, default=1000.0)
    ap.add_argument("--overload-factor", type=float, default=2.0,
                    help="second pass at this multiple of the offered "
                         "rate (0: skip) — the graceful-degradation "
                         "evidence")
    ap.add_argument("--chaos", action="store_true",
                    help="third pass: kill 1 of N decode workers at "
                         "--chaos-kill-step while running at "
                         "--overload-factor x — emits the goodput-under-"
                         "chaos fields (needs >= 2 decode hosts)")
    ap.add_argument("--chaos-kill-step", type=int, default=12,
                    help="cluster tick the chaos kill fires at (early "
                         "enough that even a hard-shedding overload run "
                         "is still mid-flight)")
    ap.add_argument("--link-fixed-ms", type=float, default=0.0)
    ap.add_argument("--link-gib-per-s", type=float, default=0.0,
                    help="simulated link bandwidth (0: instant)")
    ap.add_argument("--lora", action="store_true",
                    help="per-tenant LoRA A/B (PR-16): the same workload "
                         "with every tenant bound to an adapter, through "
                         "an adapter-enabled fleet — emits adapter hit/"
                         "warm-dispatch rates and asserts the aid=0 "
                         "cohort streams BITWISE the adapter-free fleet")
    ap.add_argument("--lora-rank", type=int, default=8)
    ap.add_argument("--n-adapters", type=int, default=None,
                    help="distinct adapters ad0..ad{M-1} (default: one "
                         "per tenant)")
    ap.add_argument("--plan", default=None,
                    choices=["tp", "pp", "fsdp", "all"],
                    help="plan-sharded serving pass (serve.sharded, "
                         "ISSUE-20): ONE model-parallel engine on a "
                         "device slice instead of the disaggregated "
                         "cluster — emits the >1-chip-HBM headline "
                         "(hbm_model_bytes vs a simulated per-chip "
                         "budget), goodput under the same SLO, gather/"
                         "bubble accounting and a monolithic-oracle "
                         "stream pin")
    ap.add_argument("--plan-world", type=int, default=None,
                    help="chips the plan spans (default tp=4, pp=2, "
                         "fsdp=8)")
    ap.add_argument("--chip-hbm-mb", type=float, default=0.0,
                    help="simulated per-chip HBM budget in MiB; 0 = the "
                         "midpoint of the plan-resident and single-chip "
                         "totals (the record carries all three numbers, "
                         "so the arithmetic is inspectable)")
    args = ap.parse_args(argv)

    if args.hosts < 2:
        ap.error("--hosts must be >= 2 (that is the point)")
    n_prefill = args.prefill_hosts or max(1, args.hosts // 2)
    n_decode = args.decode_hosts or max(1, args.hosts - n_prefill)
    if args.chaos and n_decode < 2:
        ap.error("--chaos kills a decode worker mid-run: it needs >= 2 "
                 "decode hosts to have a survivor (use --hosts 3)")

    on_tpu = jax.default_backend() == "tpu"
    if args.chaos:
        name = "gpt_serve_mh_chaos_goodput"
    elif args.lora:
        name = "gpt_serve_mh_lora_goodput"
    else:
        name = "gpt_serve_mh_goodput"
    if not on_tpu:
        name += "_CPU_FALLBACK"

    # the pinned bench model (bench_serve.py / loadgen canary constants)
    HIDDEN, LAYERS, HEADS, VOCAB, MAX_SEQ = 128, 2, 8, 512, 256
    SLOTS, BLOCK_SIZE = 4, 16
    cfg = GPTConfig(vocab_size=VOCAB, max_seq=MAX_SEQ, hidden=HIDDEN,
                    num_layers=LAYERS, num_heads=HEADS,
                    dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)

    weights = tuple(float(w) for w in args.tenant_weights.split(","))
    if len(weights) != args.n_tenants:
        ap.error("--tenant-weights must list one weight per tenant")
    wcfg = WorkloadConfig(n_requests=args.n_requests, rate_rps=args.rate_rps,
                          seed=args.seed, prompt_len_max=MAX_SEQ // 2,
                          n_tenants=args.n_tenants, tenant_weights=weights)
    workload = build_workload(wcfg, VOCAB, MAX_SEQ)
    slo = SloSpec(ttft_ms=args.ttft_budget, tpot_ms=args.tpot_budget,
                  queue_ms=args.queue_budget)
    scfg = ServeConfig(num_slots=SLOTS, block_size=BLOCK_SIZE,
                       kv_quant=args.kv_quant,
                       prefill_chunk=args.prefill_chunk,
                       spec_k=args.spec_k, megakernel=args.megakernel,
                       prefix_cache=False)
    # -- plan-sharded serving pass (ISSUE-20, stage 24) -------------------
    # ONE ParallelismPlan-driven engine on a device slice instead of the
    # disaggregated cluster: the record's headline is residency — a model
    # whose hbm_model_bytes EXCEEDS one simulated chip's budget still
    # serving the workload under the same SLO — next to the strategy's
    # own accounting (weight_gather_ms / pp_bubble_fraction) and a
    # monolithic-oracle stream pin (transparency, not tolerance).
    if args.plan:
        import dataclasses as _dc

        from apex_tpu.fsdp.accounting import hbm_serve_bytes
        from apex_tpu.parallel import ParallelismPlan
        from apex_tpu.serve import Request as _Req, build_engine
        from apex_tpu.serve.kv_cache import kv_cache_bytes

        name = "gpt_serve_plan_goodput"
        if not on_tpu:
            name += "_CPU_FALLBACK"
        worlds = {"tp": 4, "pp": 2, "fsdp": 8}

        oracle = InferenceEngine(params, cfg, scfg, retain_streams=False)
        cohort = [_Req(f"eq{i}", list(r.tokens),
                       max_new_tokens=min(r.max_new_tokens, 8),
                       tenant=r.tenant)
                  for i, (_, r) in enumerate(workload[:6])]
        oracle_streams = oracle.run(cohort)
        single_total = hbm_serve_bytes(
            params, strategy="single", world=1,
            kv_bytes=kv_cache_bytes(oracle.kv_cfg))["total"]

        def plan_pass(strategy):
            world = args.plan_world or worlds[strategy]
            if world > jax.device_count():
                return {"strategy": strategy, "ok": False,
                        "reason": f"plan spans {world} chips, have "
                                  f"{jax.device_count()}"}
            plan = {"tp": lambda: ParallelismPlan(tp=world,
                                                  overlap_comm=True),
                    "pp": lambda: ParallelismPlan(pp=world),
                    "fsdp": lambda: ParallelismPlan("fsdp", dp=world),
                    }[strategy]()
            eng = build_engine(params, cfg, _dc.replace(scfg, plan=plan),
                               slo=slo, retain_streams=False)
            pstats = run_workload(eng, workload)
            pslo = pstats.get("slo_report", {})
            # oracle stream pin AFTER the workload pass: the engine's
            # completed counter is cumulative, and drained below reads
            # the workload's own count
            streams_equal = eng.run(
                [_Req(r.uid, list(r.tokens),
                      max_new_tokens=r.max_new_tokens, tenant=r.tenant)
                 for r in cohort]) == oracle_streams
            st = eng.stats()
            chip_bytes = st["hbm_chip_bytes"]
            budget = (args.chip_hbm_mb * 2 ** 20
                      or (chip_bytes + single_total) / 2)
            exceeds_single = single_total > budget
            fits_plan = chip_bytes <= budget
            drained = pstats.get("completed", 0) == len(workload)
            sub = {
                "strategy": strategy,
                "plan_world": st["plan_world"],
                "ok": bool(drained and streams_equal and exceeds_single
                           and fits_plan),
                "drained": drained,
                "streams_equal": streams_equal,
                "hbm_model_bytes": st["hbm_model_bytes"],
                "hbm_chip_bytes": chip_bytes,
                "chip_budget_bytes": round(budget),
                "single_chip_total_bytes": single_total,
                "exceeds_single_chip": exceeds_single,
                "fits_plan_chip": fits_plan,
                "hbm_cut_vs_single": round(single_total / chip_bytes, 4),
                "goodput_rps": pslo.get("goodput_rps"),
                "good_fraction": pslo.get("good_fraction"),
                "violations": pslo.get("violations"),
                "completed": pstats.get("completed"),
                "tokens_per_s": pstats.get("tokens_per_s"),
                **{k: pstats.get(k) for k in (
                    "ttft_ms_p50", "ttft_ms_p99",
                    "tpot_ms_p50", "tpot_ms_p99")},
                "compilations": eng.compile_counts(),
            }
            for k in ("weight_gather_ms", "weight_gather_wire_bytes",
                      "pp_bubble_fraction", "pp_bubble_fraction_modeled",
                      "pp_microbatches", "pp_credit_waits"):
                if k in st:
                    sub[k] = st[k]
            return sub

        strategies = (["tp", "pp", "fsdp"] if args.plan == "all"
                      else [args.plan])
        passes = {s: plan_pass(s) for s in strategies}
        rec = {
            "metric": name,
            "ok": all(p["ok"] for p in passes.values()),
            "plan": args.plan,
            "hbm_model_bytes": max(
                (p["hbm_model_bytes"] for p in passes.values()
                 if "hbm_model_bytes" in p), default=None),
            "single_chip_total_bytes": single_total,
            # worst driven strategy carries the flat gate fields: the
            # budget headline must hold for EVERY plan, goodput for the
            # slowest
            "hbm_chip_bytes": max(
                (p["hbm_chip_bytes"] for p in passes.values()
                 if "hbm_chip_bytes" in p), default=None),
            "goodput_rps": min(
                (p["goodput_rps"] for p in passes.values()
                 if p.get("goodput_rps") is not None), default=None),
            "plans": passes,
            "slo": slo.to_dict(),
            "workload": {"mode": wcfg.mode, "n": wcfg.n_requests,
                         "rate_rps": wcfg.rate_rps, "seed": wcfg.seed,
                         "n_tenants": wcfg.n_tenants,
                         "kv_quant": args.kv_quant,
                         "spec_k": args.spec_k},
            "backend": jax.default_backend(),
        }
        for s, key in (("fsdp", "weight_gather_ms"),
                       ("pp", "pp_bubble_fraction")):
            if s in passes and key in passes[s]:
                rec[key] = passes[s][key]
        line = json_record(**rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0

    tenant_w = {f"t{i}": w for i, w in enumerate(weights)}
    ccfg = ClusterConfig(
        n_prefill=n_prefill, n_decode=n_decode, serve=scfg,
        wire_mode=args.wire_mode,
        router=RouterConfig(slo=slo, tenant_weights=tenant_w),
        link_fixed_ms=args.link_fixed_ms,
        link_gib_per_s=args.link_gib_per_s)

    def run_cluster(time_scale: float, chaos=None):
        cl = ServeCluster(params, cfg, ccfg, retain_streams=False,
                          chaos=chaos)
        stats = run_workload(cl, workload, time_scale=time_scale)
        return cl, stats

    # -- disaggregated pass at the offered rate ---------------------------
    cluster, stats = run_cluster(1.0)

    # wire-model agreement: every handoff's payload nbytes was asserted
    # against the model at pack time; re-derive the total independently
    # from the workload's prompt lengths
    kv = cluster.prefill_workers[0].kv_cfg
    shed_uids = set(cluster.shed)
    modeled = sum(
        transfer_wire_bytes(kv, kv.blocks_for_tokens(len(r.tokens)),
                            args.wire_mode)
        for _, r in workload if r.uid not in shed_uids)
    measured = cluster.transport.wire_bytes_total
    # agreement is meaningful only on a drained run (every non-shed
    # request made exactly one handoff)
    wire_model_agrees = (measured == modeled)

    # -- colocated A/B: one engine, same total decode slots ---------------
    colo_cfg = ServeConfig(
        num_slots=SLOTS * n_decode, block_size=BLOCK_SIZE,
        kv_quant=args.kv_quant, prefill_chunk=args.prefill_chunk,
        spec_k=args.spec_k, megakernel=args.megakernel, prefix_cache=False)
    colo = InferenceEngine(params, cfg, colo_cfg, slo=slo,
                           retain_streams=False)
    colo_stats = run_workload(colo, workload)
    colo_slo = colo_stats.get("slo_report", {})

    # -- overload pass: arrivals compressed overload-factor x -------------
    overload = None
    if args.overload_factor and args.overload_factor > 1.0:
        ov_cluster, ov = run_cluster(1.0 / args.overload_factor)
        ov_slo = ov.get("slo_report", {})
        overload = {
            "factor": args.overload_factor,
            "offered": ov.get("offered"),
            "completed": ov.get("completed"),
            "shed": ov_cluster.router.shed,
            "shed_rate": ov.get("shed_rate"),
            "goodput_rps": ov_slo.get("goodput_rps"),
            "good_fraction": ov_slo.get("good_fraction"),
            "deadlocked": False,  # run_workload returned — by contract
        }

    # -- chaos pass: kill 1 of N decode workers at overload ---------------
    # the ISSUE-13 deliverable: goodput-under-chaos — the same 2x-offered
    # workload, but a decode worker fail-stops mid-run and its live
    # requests migrate to the survivors over the KV wire. The record
    # carries what the failure cost (goodput_under_chaos_rps /
    # survivor_good_fraction, regress-gated higher-is-better) and how
    # noisy the recovery was (migrations/replays/retries, lower-better).
    chaos_rec = None
    chaos_ok = True
    if args.chaos:
        from apex_tpu.serve import ClusterChaos
        from apex_tpu.serve.cluster.chaos import KillWorker

        factor = max(args.overload_factor or 0.0, 2.0)
        plan = ClusterChaos([KillWorker(at_step=args.chaos_kill_step,
                                        worker="decode0")])
        ch_cluster, ch = run_cluster(1.0 / factor, chaos=plan)
        ch_slo = ch.get("slo_report", {})
        ch_drained = (ch.get("completed", 0) + len(ch_cluster.shed)
                      == len(workload))
        chaos_ok = bool(ch_drained and ch.get("worker_deaths") == 1)
        chaos_rec = {
            "factor": factor,
            "kill_step": args.chaos_kill_step,
            "killed": "decode0",
            "offered": ch.get("offered"),
            "completed": ch.get("completed"),
            "shed_rate": ch.get("shed_rate"),
            "goodput_under_chaos_rps": ch_slo.get("goodput_rps"),
            "survivor_good_fraction": ch_slo.get("good_fraction"),
            "worker_deaths": ch.get("worker_deaths"),
            "migrations_total": ch.get("migrations_total"),
            "replayed_tokens": ch.get("replayed_tokens"),
            "heartbeat_misses": ch.get("heartbeat_misses"),
            "transfer_retries": ch.get("transfer_retries"),
            "drained": ch_drained,
            "deadlocked": False,  # run_workload returned — by contract
            "faults": plan.summary(),
        }

    # -- per-tenant LoRA A/B: adapters off vs N tenants x M adapters ------
    # the PR-16 stage-20 record: the same tenant mix with every tenant
    # bound to an adapter (loadgen's fixed t{i} -> ad{i % M} mapping)
    # through an adapter-enabled fleet. Carries tokens/s + TTFT p99 next
    # to the baseline pass above, the registry hit rate and the router's
    # warm-dispatch rate (both regress-gated higher-is-better), and
    # asserts the aid=0 cohort streams BITWISE what an adapter-free
    # fleet streams — transparency, not tolerance.
    lora_rec = None
    lora_ok = True
    if args.lora:
        import dataclasses

        from apex_tpu.serve import make_adapter_weights

        n_adapters = args.n_adapters or args.n_tenants
        lora_scfg = dataclasses.replace(scfg, lora_rank=args.lora_rank,
                                        max_adapters=n_adapters)
        lora_ccfg = dataclasses.replace(ccfg, serve=lora_scfg)
        lora_workload = build_workload(
            dataclasses.replace(wcfg, n_adapters=n_adapters),
            VOCAB, MAX_SEQ)
        adapters = {
            f"ad{i}": make_adapter_weights(cfg, args.lora_rank,
                                           jax.random.PRNGKey(100 + i))
            for i in range(n_adapters)}
        lora_cluster = ServeCluster(params, cfg, lora_ccfg,
                                    retain_streams=False)
        for aname, w in adapters.items():
            lora_cluster.load_adapter(aname, w)
        lora_stats = run_workload(lora_cluster, lora_workload)
        lora_slo = lora_stats.get("slo_report", {})
        lora_drained = (lora_stats.get("completed", 0)
                        + len(lora_cluster.shed) == len(lora_workload))
        lst = lora_cluster.stats()

        # aid=0 transparency cohort: the first requests of the BASE
        # workload (no adapter bound), replayed through a fresh
        # adapter-free fleet and a fresh adapter-ENABLED fleet — the
        # streams must be bitwise equal or the record refuses to bank
        from apex_tpu.serve import Request as _Req

        cohort = [_Req(f"eq{i}", list(r.tokens),
                       max_new_tokens=min(r.max_new_tokens, 8),
                       tenant=r.tenant)
                  for i, (_, r) in enumerate(workload[:6])]
        base_streams = ServeCluster(params, cfg, ccfg).run(
            cohort, max_steps=200000)
        lora_fleet = ServeCluster(params, cfg, lora_ccfg)
        for aname, w in adapters.items():
            lora_fleet.load_adapter(aname, w)
        lora_streams = lora_fleet.run(cohort, max_steps=200000)
        streams_equal = base_streams == lora_streams

        lora_ok = bool(lora_drained and streams_equal)
        tps = (round(lora_stats.get("generated_tokens", 0)
                     / lora_stats["wall_s"], 3)
               if lora_stats.get("wall_s") else None)
        lora_rec = {
            "rank": args.lora_rank,
            "n_adapters": n_adapters,
            "n_tenants": args.n_tenants,
            "completed": lora_stats.get("completed"),
            "shed_rate": lora_stats.get("shed_rate"),
            "tokens_per_s": tps,
            "goodput_rps": lora_slo.get("goodput_rps"),
            "ttft_ms_p99": lora_stats.get("ttft_ms_p99"),
            "tpot_ms_p99": lora_stats.get("tpot_ms_p99"),
            "adapter_hit_rate": lst.get("adapter_hit_rate"),
            "adapter_warm_dispatch_rate":
                lst.get("adapter_warm_dispatch_rate"),
            "adapter_evictions": lst.get("adapter_evictions"),
            "adapter_load_ms": lst.get("adapter_load_ms"),
            "catalog_loads": lst["adapters"]["catalog_loads"],
            "streams_equal": streams_equal,
            "drained": lora_drained,
        }

    # -- int8-vs-int4 KV concurrency A/B (modeled, config-exact) ----------
    # at the int8 pool's byte budget, how many pool blocks — and so
    # concurrent max-length contexts — does each tier hold? (halving
    # bytes/token must double both; the stage-17 regress gate covers
    # contexts_max higher-better / kv_bits lower-better)
    import dataclasses as _dc

    from apex_tpu.serve.kv_cache import kv_cache_bytes

    kv_run = cluster.decode_workers[0].engine.kv_cfg
    max_ctx = scfg.max_context or cfg.max_seq
    kv_ab = {}
    budget = None
    for bits in (8, 4):
        kvq = _dc.replace(kv_run, quantized=True, bits=bits,
                          group_size=None)
        per_pool = kv_cache_bytes(kvq)
        if budget is None:
            budget = per_pool  # the int8 tier's budget anchors the A/B
        blocks_at_budget = budget * kvq.num_blocks // per_pool
        kv_ab[f"int{bits}"] = {
            "kv_cache_bytes": per_pool,
            "blocks_at_int8_budget": blocks_at_budget,
            "contexts_max": blocks_at_budget * kvq.block_size // max_ctx,
            "transfer_wire_bytes": sum(
                transfer_wire_bytes(kvq,
                                    kvq.blocks_for_tokens(len(r.tokens)))
                for _, r in workload),
        }
    kv_ab["hbm_cut_int8_over_int4"] = round(
        kv_ab["int8"]["kv_cache_bytes"] / kv_ab["int4"]["kv_cache_bytes"],
        4)

    slo_rep = stats.get("slo_report", {})
    drained = stats.get("completed", 0) + len(cluster.shed) == len(workload)
    rec = {
        "metric": name,
        "ok": bool(drained and wire_model_agrees and chaos_ok
                   and lora_ok),
        "hosts": {"prefill": n_prefill, "decode": n_decode,
                  "total": n_prefill + n_decode},
        "goodput_rps": slo_rep.get("goodput_rps"),
        "throughput_rps": slo_rep.get("throughput_rps"),
        "good_fraction": slo_rep.get("good_fraction"),
        "violations": slo_rep.get("violations"),
        "shed_rate": stats.get("shed_rate"),
        "admitted_rps": stats.get("admitted_rps"),
        **{k: stats.get(k) for k in (
            "offered", "submitted", "completed", "offered_rps",
            "generated_tokens", "wall_s",
            "ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50", "tpot_ms_p99",
            "queue_ms_p50", "queue_ms_p99", "e2e_ms_p50", "e2e_ms_p99",
            "decode_step_ms_p50", "decode_step_ms_p99",
            "transfer_ms_p50", "transfer_ms_p99")},
        "transfer": stats.get("transfer"),
        "wire_model_agrees": wire_model_agrees,
        "transfer_wire_bytes_modeled": modeled,
        # sub-8-bit KV headline fields (regress-gated; wire_bytes_int4 is
        # the modeled int4 handoff total for THIS workload)
        "kv_bits": (kv_run.bits if kv_run.quantized
                    else 8 * jnp.dtype(kv_run.dtype).itemsize),
        "contexts_max": kv_run.tokens_capacity // max_ctx,
        "wire_bytes_int4": kv_ab["int4"]["transfer_wire_bytes"],
        "kv_ab": kv_ab,
        "router": stats.get("router"),
        "colocated": {
            "goodput_rps": colo_slo.get("goodput_rps"),
            "good_fraction": colo_slo.get("good_fraction"),
            "tokens_per_s": colo_stats.get("tokens_per_s"),
            "ttft_ms_p50": colo_stats.get("ttft_ms_p50"),
            "ttft_ms_p99": colo_stats.get("ttft_ms_p99"),
            "tpot_ms_p50": colo_stats.get("tpot_ms_p50"),
            "tpot_ms_p99": colo_stats.get("tpot_ms_p99"),
            "completed": colo_stats.get("completed"),
        },
        "disagg_vs_colocated_goodput": (
            round(slo_rep["goodput_rps"] / colo_slo["goodput_rps"], 4)
            if slo_rep.get("goodput_rps") and colo_slo.get("goodput_rps")
            else None),
        "overload": overload,
        "chaos": chaos_rec,
        "lora": lora_rec,
        # elastic counters of the CLEAN pass (all zero unless the run
        # hit real faults — regress gates them lower-is-better)
        "elastic": stats.get("elastic"),
        "compilations": cluster.compile_counts(),
        "slo": slo.to_dict(),
        "workload": {"mode": wcfg.mode, "n": wcfg.n_requests,
                     "rate_rps": wcfg.rate_rps,
                     "burst_every_s": wcfg.burst_every_s,
                     "burst_size": wcfg.burst_size, "seed": wcfg.seed,
                     "n_tenants": wcfg.n_tenants,
                     "tenant_weights": list(weights),
                     "wire_mode": args.wire_mode,
                     "kv_quant": args.kv_quant,
                     "spec_k": args.spec_k},
        "backend": jax.default_backend(),
    }
    if chaos_rec is not None:
        # flat goodput-under-chaos headline fields (the stage-18 gate:
        # goodput/survivor fraction higher-is-better, recovery noise
        # lower-is-better)
        for k in ("goodput_under_chaos_rps", "survivor_good_fraction",
                  "migrations_total", "replayed_tokens", "worker_deaths",
                  "heartbeat_misses", "transfer_retries"):
            rec[k] = chaos_rec[k]
    if lora_rec is not None:
        # flat per-tenant LoRA headline fields (the stage-20 gate: hit
        # and warm-dispatch rates higher-is-better, load time and LRU
        # churn lower-is-better)
        for k in ("adapter_hit_rate", "adapter_warm_dispatch_rate",
                  "adapter_evictions", "adapter_load_ms",
                  "streams_equal"):
            rec[k] = lora_rec[k]
    line = json_record(**rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
