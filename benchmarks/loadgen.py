"""Deterministic closed-/open-loop load generator for the serve engine.

The ROADMAP item-2 harness half: serving numbers mean nothing without a
workload model, and averages mean nothing without arrival bursts — tail
latency IS the product of queueing (arXiv 1909.09756's scale lesson;
arXiv 2512.22219's dispatch-latency analysis). This module generates a
**seeded, reproducible** workload and drives an ``InferenceEngine``
through it:

* **open loop** — Poisson arrivals at ``rate_rps`` (exponential gaps from
  a fixed seed) with optional superimposed **bursts** (every
  ``burst_every_s``, ``burst_size`` requests arrive at the same instant —
  the queue-building event that separates p99 from p50), long-tail
  (lognormal, clipped) prompt lengths and generation lengths. Arrivals
  are wall-clock scheduled: a request is submitted when its arrival time
  passes, whether or not the engine kept up — offered load is independent
  of completion, exactly what an SLO needs to be measured against.
* **closed loop** — a fixed number of in-flight requests; each
  retirement immediately submits the next. Measures capacity without
  queueing effects (the classic loadgen dual).
* **shared prefixes** — ``prefix_pool`` distinct "system prompts" of
  ``prefix_len`` tokens, mixed into a ``prefix_ratio`` fraction of
  requests (same seed -> same pool, same mixing). This is the workload
  the engine's prefix cache exists for: run with ``--prefix-pool`` +
  ``--spec-k`` it must beat the plain record on the same hardware.
* **per-tenant adapters** — ``n_adapters`` binds tenant ``t{i}`` to LoRA
  adapter ``ad{i % n_adapters}`` deterministically (no extra rng draws:
  an ``n_adapters=0`` workload is bit-identical to the pre-adapter one).
  This is the fleet-mix workload (adapter hit rate, warm-dispatch rate,
  aid=0 ``streams_equal``).

``run_workload`` drives the engine with ``retain_streams=False`` — state
stays O(slots + backlog) no matter how many requests flow — and returns
``engine.stats()`` (histquantiles + goodput-under-SLO). ``main`` builds
the pinned bench model, runs a Poisson+burst workload against a default
SLO and prints ONE ``json_record`` line (goodput req/s, TTFT/TPOT
p50/p99, violation counts).

Run: ``python benchmarks/loadgen.py [--out FILE] [--trace-dir DIR]``.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

__all__ = ["WorkloadConfig", "build_workload", "run_workload", "main"]


@dataclasses.dataclass(frozen=True)
class WorkloadConfig:
    """Seeded workload shape. ``mode="open"`` uses Poisson arrivals +
    bursts; ``mode="closed"`` keeps ``concurrency`` requests in flight
    (arrival times all 0)."""

    n_requests: int = 64
    mode: str = "open"                 # "open" | "closed"
    rate_rps: float = 8.0              # open: mean Poisson arrival rate
    burst_every_s: Optional[float] = 2.0  # open: burst period (None: off)
    burst_size: int = 4                # open: requests per burst instant
    concurrency: int = 8               # closed: in-flight target
    prompt_len_median: int = 24        # lognormal median prompt length
    prompt_len_sigma: float = 0.8      # long-tail spread (log-space std)
    prompt_len_min: int = 2
    prompt_len_max: int = 128
    max_new_median: int = 16           # lognormal median generation length
    max_new_sigma: float = 0.5
    max_new_min: int = 2
    max_new_max: int = 64
    # shared-prefix mixing: a pool of prefix_pool distinct "system
    # prompts" of prefix_len tokens each; a prefix_ratio fraction of
    # requests open with one of them (the rest are fully random) — the
    # workload shape the engine's prefix cache exists for. 0 disables.
    prefix_pool: int = 0
    prefix_len: int = 32
    prefix_ratio: float = 1.0
    # multi-tenant mixing (the cluster router's fairness knob): each
    # request is tagged tenant "t0".."t{n-1}", drawn from the SAME seeded
    # rng with probabilities proportional to tenant_weights (None: equal)
    # — deterministic like prefix_pool, so the WFQ path is drivable from
    # the bench and tests. 0 disables (every request tenant "default").
    n_tenants: int = 0
    tenant_weights: Optional[Tuple[float, ...]] = None
    # per-tenant LoRA adapter traffic (the serve.adapters knob): tenant
    # "t{i}" is bound to adapter "ad{i % n_adapters}" — a FIXED mapping,
    # no extra rng draws, so an n_adapters=0 workload stays bit-identical
    # to the pre-adapter one and the adapter mix follows the tenant mix
    # (tenant_weights skews which adapters are hot). Requires n_tenants
    # >= 1; the driver must load_adapter() "ad0".."ad{M-1}" before the
    # run or admission sheds the bound requests. 0 disables (no request
    # carries an adapter — the aid=0 transparency cohort).
    n_adapters: int = 0
    seed: int = 0

    def validate(self) -> None:
        if self.mode not in ("open", "closed"):
            raise ValueError(f"mode must be open|closed, got {self.mode!r}")
        if self.n_requests < 1:
            raise ValueError("n_requests must be >= 1")
        if self.mode == "open" and self.rate_rps <= 0:
            raise ValueError("rate_rps must be positive for open loop")
        if self.mode == "closed" and self.concurrency < 1:
            raise ValueError("concurrency must be >= 1 for closed loop")
        if not (1 <= self.prompt_len_min <= self.prompt_len_max):
            raise ValueError("bad prompt length bounds")
        if not (1 <= self.max_new_min <= self.max_new_max):
            raise ValueError("bad max_new bounds")
        if self.prefix_pool < 0:
            raise ValueError("prefix_pool must be >= 0")
        if self.prefix_pool:
            if self.prefix_len < 1:
                raise ValueError("prefix_len must be >= 1")
            if not 0.0 < self.prefix_ratio <= 1.0:
                raise ValueError("prefix_ratio must be in (0, 1]")
        if self.n_tenants < 0:
            raise ValueError("n_tenants must be >= 0")
        if self.tenant_weights is not None:
            if len(self.tenant_weights) != self.n_tenants:
                raise ValueError(
                    f"tenant_weights has {len(self.tenant_weights)} "
                    f"entries for n_tenants={self.n_tenants}")
            if any(w <= 0 for w in self.tenant_weights):
                raise ValueError("tenant_weights must be positive")
        if self.n_adapters < 0:
            raise ValueError("n_adapters must be >= 0")
        if self.n_adapters and self.n_tenants < 1:
            raise ValueError("n_adapters > 0 needs n_tenants >= 1 "
                             "(adapters are bound per tenant)")


def _lognormal_int(rng, median: float, sigma: float, lo: int, hi: int,
                   size: int) -> np.ndarray:
    v = rng.lognormal(mean=np.log(median), sigma=sigma, size=size)
    return np.clip(np.round(v).astype(np.int64), lo, hi)


def build_workload(cfg: WorkloadConfig, vocab_size: int,
                   max_context: int) -> List[Tuple[float, Any]]:
    """The deterministic workload: ``[(arrival_s, Request), ...]`` sorted
    by arrival. Same config + seed -> identical request stream (uids,
    prompts, lengths, arrival instants), so records are comparable
    round-over-round — the canary discipline applied to load."""
    from apex_tpu.serve import Request

    cfg.validate()
    rng = np.random.default_rng(cfg.seed)
    n = cfg.n_requests
    # prompt must leave >= 1 position to generate inside max_context
    p_hi = min(cfg.prompt_len_max, max_context - 1)
    plens = _lognormal_int(rng, cfg.prompt_len_median, cfg.prompt_len_sigma,
                           cfg.prompt_len_min, p_hi, n)
    glens = _lognormal_int(rng, cfg.max_new_median, cfg.max_new_sigma,
                           cfg.max_new_min, cfg.max_new_max, n)
    # shared-prefix pool: the N "system prompts" are drawn FIRST from the
    # same seeded rng, so the pool is part of the deterministic workload
    prefixes: List[List[int]] = []
    pick = share = None
    if cfg.prefix_pool:
        plen = min(cfg.prefix_len, max_context - 2)
        prefixes = [rng.integers(0, vocab_size, size=plen).tolist()
                    for _ in range(cfg.prefix_pool)]
        pick = rng.integers(0, cfg.prefix_pool, size=n)
        share = rng.random(size=n) < cfg.prefix_ratio
    # tenant tags drawn from the same seeded stream (only when enabled, so
    # an n_tenants=0 workload is bit-identical to the pre-tenant one)
    tenants = None
    if cfg.n_tenants:
        w = np.asarray(cfg.tenant_weights
                       if cfg.tenant_weights is not None
                       else [1.0] * cfg.n_tenants, np.float64)
        tenants = rng.choice(cfg.n_tenants, size=n, p=w / w.sum())
    if cfg.mode == "closed":
        arrivals = np.zeros((n,))
    else:
        gaps = rng.exponential(1.0 / cfg.rate_rps, size=n)
        arrivals = np.cumsum(gaps)
        if cfg.burst_every_s:
            # bursts: every burst_every_s, the next burst_size arrivals
            # collapse onto the burst instant (offered load unchanged in
            # total, concentrated in time — the p99-making event)
            t, i = cfg.burst_every_s, 0
            while i < n:
                j = int(np.searchsorted(arrivals, t))
                k = min(j + cfg.burst_size, n)
                arrivals[j:k] = t
                if j >= n:
                    break
                i = k
                t += cfg.burst_every_s
            arrivals = np.sort(arrivals)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab_size, size=int(plens[i])).tolist()
        if prefixes and share[i]:
            # shared system prompt + the request's own tail, clipped to
            # leave >= 1 position to generate
            toks = (prefixes[int(pick[i])] + toks)[:max_context - 1]
        tenant = (f"t{int(tenants[i])}" if tenants is not None
                  else "default")
        adapter = (f"ad{int(tenants[i]) % cfg.n_adapters}"
                   if cfg.n_adapters and tenants is not None else None)
        out.append((float(arrivals[i]),
                    Request(f"lg{i:05d}", toks,
                            max_new_tokens=int(glens[i]),
                            tenant=tenant, adapter=adapter)))
    return out


def run_workload(engine, workload: List[Tuple[float, Any]],
                 time_scale: float = 1.0,
                 max_wall_s: float = 600.0) -> Dict[str, Any]:
    """Drive ``engine`` through the workload; returns ``engine.stats()``
    plus offered-load accounting.

    Open loop: requests are submitted when their (scaled) arrival time
    passes on the wall clock; the engine steps continuously while active
    and sleeps to the next arrival when idle. Closed loop (all arrivals
    0 with a ``concurrency``-bounded workload) degenerates to submit-all
    + drain, which is exactly the closed-loop semantics under a slot
    grid: the engine itself caps in-flight at ``num_slots``.
    ``time_scale`` compresses arrival times (tests); ``max_wall_s`` is a
    hard stop so a saturated engine still reports."""
    pending = sorted(workload, key=lambda aw: aw[0])
    t0 = time.perf_counter()
    submitted = 0
    deadline = t0 + max_wall_s
    while (pending or engine.active) and time.perf_counter() < deadline:
        now = time.perf_counter() - t0
        while pending and pending[0][0] * time_scale <= now:
            _, req = pending.pop(0)
            engine.submit(req)
            submitted += 1
        progressed = engine.step()
        if not progressed and pending:
            # idle: sleep to the next arrival instead of spinning
            wait = pending[0][0] * time_scale - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(min(wait, 0.05))
        elif not progressed and not pending:
            break  # drained
    wall = time.perf_counter() - t0
    stats = engine.stats()
    stats["offered"] = len(workload)
    stats["submitted"] = submitted
    last = workload[-1][0] * time_scale if workload else 0.0
    stats["offered_rps"] = (round(len(workload) / last, 3)
                            if last > 0 else None)
    stats["wall_s"] = round(wall, 3)
    return stats


def main(argv=None) -> int:
    import argparse

    import jax
    import jax.numpy as jnp

    from apex_tpu.monitor.sink import collect_provenance, set_provenance

    set_provenance(collect_provenance())  # after the pin: backend is final

    from apex_tpu.monitor import (
        EventLog,
        JsonlSink,
        SloSpec,
        json_record,
        read_jsonl,
        write_chrome_trace,
    )
    from apex_tpu.serve import InferenceEngine, ServeConfig
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace-dir", default=None,
                    help="also write events.jsonl + trace.json here")
    ap.add_argument("--n-requests", type=int, default=64)
    ap.add_argument("--rate-rps", type=float, default=8.0)
    ap.add_argument("--mode", default="open", choices=["open", "closed"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"])
    ap.add_argument("--ttft-budget", type=float, default=2000.0)
    ap.add_argument("--tpot-budget", type=float, default=200.0)
    ap.add_argument("--queue-budget", type=float, default=1000.0)
    # shared-prefix workload (the prefix-cache acceptance knob) + the
    # serve-throughput tier-2 engine knobs
    ap.add_argument("--prefix-pool", type=int, default=0,
                    help="N distinct shared system prompts (0: off)")
    ap.add_argument("--prefix-len", type=int, default=64)
    ap.add_argument("--prefix-ratio", type=float, default=0.75,
                    help="fraction of requests opening with a shared "
                         "prefix")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft length (0: off)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--megakernel", default="auto",
                    choices=["auto", "on", "off"],
                    help="fused per-layer decode block (serve.megakernel)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable content-addressed block reuse")
    args = ap.parse_args(argv)

    on_tpu = jax.default_backend() == "tpu"
    name = ("gpt_serve_prefix_goodput_slo" if args.prefix_pool
            else "gpt_serve_goodput_slo")
    if not on_tpu:
        name += "_CPU_FALLBACK"

    # the pinned bench model
    HIDDEN, LAYERS, HEADS, VOCAB, MAX_SEQ = 128, 2, 8, 512, 256
    SLOTS, BLOCK_SIZE = 4, 16
    cfg = GPTConfig(vocab_size=VOCAB, max_seq=MAX_SEQ, hidden=HIDDEN,
                    num_layers=LAYERS, num_heads=HEADS,
                    dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    wcfg = WorkloadConfig(n_requests=args.n_requests, mode=args.mode,
                          rate_rps=args.rate_rps, seed=args.seed,
                          prompt_len_max=MAX_SEQ // 2,
                          prefix_pool=args.prefix_pool,
                          prefix_len=args.prefix_len,
                          prefix_ratio=args.prefix_ratio)
    slo = SloSpec(ttft_ms=args.ttft_budget, tpot_ms=args.tpot_budget,
                  queue_ms=args.queue_budget)
    workload = build_workload(wcfg, VOCAB, MAX_SEQ)

    events = None
    sink = None
    events_path = None
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        events_path = os.path.join(args.trace_dir, "events.jsonl")
        sink = JsonlSink(events_path, buffer_steps=64)
        events = EventLog(sink=sink)
    eng = InferenceEngine(
        params, cfg,
        ServeConfig(num_slots=SLOTS, block_size=BLOCK_SIZE,
                    kv_quant=args.kv_quant,
                    prefill_chunk=args.prefill_chunk,
                    prefix_cache=not args.no_prefix_cache,
                    spec_k=args.spec_k, megakernel=args.megakernel),
        events=events, slo=slo, retain_streams=False)
    stats = run_workload(eng, workload)
    if sink is not None:
        sink.close()
        write_chrome_trace(os.path.join(args.trace_dir, "trace.json"),
                           read_jsonl(events_path))

    slo_rep = stats.pop("slo_report")
    hists = stats.pop("hists")
    rec = {
        "metric": name,
        "ok": stats["completed"] == len(workload),
        "goodput_rps": slo_rep["goodput_rps"],
        "throughput_rps": slo_rep["throughput_rps"],
        "good_fraction": slo_rep["good_fraction"],
        "violations": slo_rep["violations"],
        **{k: stats.get(k) for k in (
            "offered", "submitted", "completed", "offered_rps",
            "generated_tokens", "tokens_per_s", "wall_s",
            "ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50", "tpot_ms_p99",
            "queue_ms_p50", "queue_ms_p99", "decode_step_ms_p50",
            "decode_step_ms_p99")},
        # the throughput-optimization headline fields (acceptance: the
        # shared-prefix record carries hit/acceptance rates)
        "prefix_hit_rate": stats.get("prefix_hit_rate"),
        "prefix_cache": stats.get("prefix_cache"),
        "spec_acceptance_rate": stats.get("spec_acceptance_rate"),
        "speculative": stats.get("speculative"),
        "prefill": stats.get("prefill"),
        "megakernel": stats.get("megakernel"),
        "compilations": eng.compile_counts(),
        "slo": slo.to_dict(),
        "hist_rel_error": round(eng.hists["ttft_ms"].spec.rel_error, 4),
        "workload": {"mode": wcfg.mode, "n": wcfg.n_requests,
                     "rate_rps": wcfg.rate_rps,
                     "burst_every_s": wcfg.burst_every_s,
                     "burst_size": wcfg.burst_size, "seed": wcfg.seed,
                     "prefix_pool": wcfg.prefix_pool,
                     "prefix_len": wcfg.prefix_len,
                     "prefix_ratio": wcfg.prefix_ratio,
                     "spec_k": args.spec_k,
                     "prefill_chunk": args.prefill_chunk},
        "hists": {k: hists[k] for k in ("ttft_ms", "tpot_ms")},
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
