"""Serving benchmark: continuous-batching engine throughput + latency.

One ``json_record`` line (the bench.py protocol): tokens/s, TTFT p50/p99,
mean slot occupancy, decode-step p50 ms and the KV byte model for a fixed
mixed-length request workload through ``apex_tpu.serve.InferenceEngine``.
The KV/collective byte columns join the ``comm.accounting`` convention
(modeled bytes, stated as such).

Honesty notes baked into the record: the metric name gains a
``_CPU_FALLBACK`` suffix off-chip (CPU rehearsal numbers must never be
read as TPU serving throughput), and on a single chip the
``tp_sharded_serving`` column says "needs a slice" — the TP-sharded
decode path (vocab-gathered logits, sharded heads) has no ring to measure
on one chip, exactly like ``bench_overlap.py``.

Run: ``python benchmarks/bench_serve.py [--out FILE]``.

``--megakernel {auto,on,off}`` selects the fused per-layer decode block
(``serve.megakernel``; the record's ``decode_kernel`` field says which
path actually served). ``--megakernel-ab`` runs the SAME workload twice —
megakernel on, then off — and emits one A/B record whose headline fields
come from the fused side. The A/B is a TPU measurement: on
CPU the fused block only exists in interpret mode (a simulator, not a
perf number), so the record honestly says ``megakernel_ab: needs a
chip`` and carries the per-op-path numbers under the ``_CPU_FALLBACK``
metric suffix.

``--model {pinned,flagship}`` picks the served model: ``pinned`` is the
small canary above; ``flagship`` is the GPT-2-124M serve shape (768
hidden, 12 layers, 50304 vocab — per-layer bf16 weights OVER the 10 MB
VMEM budget, so only the tier-2 weight-streaming tiles can serve it
fused). ``--megakernel-ab --spec-k 4 --model flagship`` is the
lifted-gate run: the record must show
``decode_kernel`` AND ``verify_kernel`` ``== "fused"`` on the fused
side — the lifted-gate acceptance measurement.

``--loadgen`` switches to the monitor-tier-2 goodput-under-SLO bench:
``benchmarks/loadgen.py`` drives the engine with a seeded Poisson+burst
workload and the line becomes goodput req/s + TTFT/TPOT p50/p99 from the
streaming histograms + SLO violation counts.
Extra args after ``--loadgen`` pass through (``--n-requests``,
``--rate-rps``, ``--prefix-pool``, ``--trace-dir``, budgets — see
``loadgen.py``). ``--loadgen --prefix-pool 2 --spec-k 4`` is the
shared-prefix + speculative workload whose record (prefix-hit and
acceptance rates included) must materially beat the plain goodput on the
same hardware.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ON_TPU = jax.default_backend() == "tpu"

# the pinned protocol (canary discipline, see bench_comm.py): one fixed
# model + workload so the line is comparable round-over-round. The
# flagship row is the GPT-2-124M serve shape the tier-2 megakernel
# gate-lift targets (per-layer bf16 weights > 10 MB — full residency
# refuses, weight-tile streaming serves it fused).
MODELS = {
    "pinned": dict(hidden=128, layers=2, heads=8, vocab=512, max_seq=256),
    "flagship": dict(hidden=768, layers=12, heads=12, vocab=50304,
                     max_seq=1024),
}
SLOTS, BLOCK_SIZE, MAX_NEW = 4, 16, 32
PREFILL_CHUNK = 32
PROMPT_LENS = (5, 17, 40, 9, 33, 12, 60, 25)


def main() -> int:
    import argparse
    import statistics
    import tempfile

    from apex_tpu.monitor import JsonlSink, json_record, read_jsonl
    from apex_tpu.monitor.sink import collect_provenance, set_provenance

    set_provenance(collect_provenance())
    from apex_tpu.serve import InferenceEngine, Request, ServeConfig
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"])
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft length (0: off)")
    ap.add_argument("--megakernel", default="auto",
                    choices=["auto", "on", "off"],
                    help="fused per-layer decode block (serve.megakernel)")
    ap.add_argument("--megakernel-ab", action="store_true",
                    help="run the workload megakernel-on AND -off, emit "
                         "one A/B record")
    ap.add_argument("--model", default="pinned", choices=sorted(MODELS),
                    help="served model: the pinned canary or the GPT-2-"
                         "124M flagship serve shape")
    ap.add_argument("--loadgen", action="store_true",
                    help="run the goodput-under-SLO loadgen bench instead")
    args, extra = ap.parse_known_args()
    if args.megakernel_ab and args.loadgen:
        ap.error("--megakernel-ab runs the fixed A/B workload; it cannot "
                 "be combined with --loadgen")
    if args.megakernel_ab and args.megakernel == "off":
        ap.error("--megakernel-ab measures the fused side; "
                 "--megakernel off contradicts it")

    if args.loadgen:
        # the tier-2 record: loadgen drives the engine, SLO accounting
        # emits the line (same --out contract, extra args pass through)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from loadgen import main as loadgen_main

        fwd = list(extra) + ["--kv-quant", args.kv_quant,
                             "--spec-k", str(args.spec_k),
                             "--megakernel", args.megakernel]
        if args.out:
            fwd += ["--out", args.out]
        return loadgen_main(fwd)
    if extra:
        ap.error(f"unrecognized arguments: {' '.join(extra)}")

    name = ("gpt_serve_decode_fused_ab" if args.megakernel_ab
            else "gpt_serve_engine")
    if args.model == "flagship":
        name += "_124m"
    if not ON_TPU:
        name += "_CPU_FALLBACK"

    model = MODELS[args.model]
    cfg = GPTConfig(vocab_size=model["vocab"], max_seq=model["max_seq"],
                    hidden=model["hidden"], num_layers=model["layers"],
                    num_heads=model["heads"],
                    dtype=jnp.bfloat16 if ON_TPU else jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model["vocab"], size=p).tolist()
               for p in PROMPT_LENS]

    def run_engine(megakernel):
        """One full workload pass -> (measurement sub-record, streams);
        fresh Request objects each pass (the engine consumes them)."""
        requests = [Request(f"r{i}", list(p), max_new_tokens=MAX_NEW)
                    for i, p in enumerate(prompts)]
        step_log = os.path.join(tempfile.mkdtemp(), "serve_steps.jsonl")
        with JsonlSink(step_log, buffer_steps=1) as sink:
            eng = InferenceEngine(
                params, cfg,
                ServeConfig(num_slots=SLOTS, block_size=BLOCK_SIZE,
                            kv_quant=args.kv_quant,
                            prefill_chunk=PREFILL_CHUNK,
                            spec_k=args.spec_k, megakernel=megakernel),
                sink=sink)
            out = eng.run(requests)
            tokens_per_s = eng.throughput()
            stats = eng.stats()  # quantiles from the streaming hists
            kv_budget = eng.kv_budget_bytes()
            compiles = eng.compile_counts()
        steps = [r for r in read_jsonl(step_log)
                 if r.get("phase") == "decode"]
        return {
            "ok": len(out) == len(requests),
            # which decode path actually served (fused|pallas|reference):
            # lets the stage-12 gate tell a kernel fallback from a real
            # regression
            "decode_kernel": stats.get("decode_kernel"),
            "tokens_per_s": round(tokens_per_s, 3) if tokens_per_s
            else None,
            "generated_tokens": sum(len(v) for v in out.values()),
            "ttft_ms_p50": stats.get("ttft_ms_p50"),
            "ttft_ms_p99": stats.get("ttft_ms_p99"),
            "tpot_ms_p50": stats.get("tpot_ms_p50"),
            "decode_step_ms_p50": stats.get("decode_step_ms_p50"),
            "decode_step_ms_p99": stats.get("decode_step_ms_p99"),
            # the verify jit site's path + latency (None when spec_k=0
            # or no slot ever proposed): the stage-23 verify A/B columns
            "verify_kernel": stats.get("verify_kernel"),
            "verify_step_ms_p50": stats.get("verify_step_ms_p50"),
            "verify_step_ms_p99": stats.get("verify_step_ms_p99"),
            "mean_occupancy": round(
                statistics.fmean(r["occupancy"] for r in steps), 4)
            if steps else None,
            "kv_cache_budget_bytes": kv_budget,
            "kv_read_bytes_peak": max((r["kv_read_bytes"] for r in steps),
                                      default=None),
            # the tightened compile gate: 1 chunked prefill + 1 decode
            # (+ <= 1 verify when speculation is on) — no bucket ladder
            "compilations": compiles,
            "prefix_hit_rate": stats.get("prefix_hit_rate"),
            "spec_acceptance_rate": stats.get("spec_acceptance_rate"),
        }, out

    # the headline run; in A/B mode the fused side is the headline (what
    # stage 12 regression-tracks), forced on only where it is a real
    # measurement (compiled Mosaic, not the interpreter)
    mega = args.megakernel
    if args.megakernel_ab:
        mega = "on" if ON_TPU else "auto"
    head, out = run_engine(mega)

    rec = {"metric": name, **head}
    if args.megakernel_ab:
        if ON_TPU:
            # same workload, per-op layer body: the denominator. Streams
            # must be EQUAL (the parity oracle) — a divergence means the
            # A/B measured different work, so it FAILS the bench (ok:
            # false + exit 1; the stage-12 gate additionally refuses to
            # promote a record whose streams diverged).
            base, out_off = run_engine("off")
            rec["megakernel_ab"] = {"fused_on": head, "fused_off": base}
            rec["streams_equal"] = out == out_off
            rec["ok"] = bool(rec["ok"] and base["ok"]
                             and rec["streams_equal"])
            p_on, p_off = (head.get("decode_step_ms_p50"),
                           base.get("decode_step_ms_p50"))
            rec["decode_step_speedup_p50"] = (
                round(p_off / p_on, 4) if p_on and p_off else None)
        else:
            # off-chip the fused block is interpret mode — a simulator,
            # not a measurement (the stage-12 gate never promotes this)
            rec["megakernel_ab"] = "needs a chip"
    rec.update({
        "kv_quant": args.kv_quant,
        "prefill_chunk": PREFILL_CHUNK,
        "spec_k": args.spec_k,
        # the TP-sharded serving path (sharded heads, gathered logits)
        # needs a multi-chip slice; a single chip has nothing to shard
        "tp_sharded_serving": ("needs a slice"
                               if len(jax.devices()) < 2 else "untested"),
        "config": {"model": args.model, "hidden": model["hidden"],
                   "layers": model["layers"], "heads": model["heads"],
                   "vocab": model["vocab"], "slots": SLOTS,
                   "block_size": BLOCK_SIZE, "max_new": MAX_NEW,
                   "prompts": list(PROMPT_LENS),
                   "megakernel": mega},  # the mode actually run
        "backend": jax.default_backend(),
    })
    line = json_record(**rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    # ok:false (e.g. A/B stream divergence) is a bench FAILURE, not a
    # slow record — the exit code is the first gate stage 12 sees
    return 0 if rec.get("ok", True) else 1


if __name__ == "__main__":
    sys.exit(main())
