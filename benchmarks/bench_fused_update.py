"""Fused optimizer update-tail benchmark: one Pallas kernel vs the XLA
op chain.

The ZeRO half of the megakernel PR (ROADMAP item 4): after the gradient
reduce-scatter the Adam/LAMB tail is ~10 tiny elementwise ops per leaf —
dispatch-bound on a dp-sharded state exactly like the q_len=1 decode
step. This bench times BOTH tails over a GPT-2-124M-shaped ZeRO shard
(1/8 of each leaf, the dp=8 slice) through jitted steps and emits ONE
JSON line (the ``bench.py`` / ``monitor.json_record`` protocol):

* ``ref_ms`` / ``fused_ms`` — p50 per-step wall time of the unfused op
  chain vs ``ops.fused_update.fused_adam_tail`` over the same leaves
* ``speedup`` — ref / fused
* ``lamb_ref_ms`` / ``lamb_fused_ms`` — the LAMB variant (tail + local
  trust-ratio sq-sums)

Honesty: off-TPU the kernel runs the Pallas INTERPRETER (it re-expands to
the same XLA ops — no dispatch is saved) so the metric name carries the
``_CPU_FALLBACK`` suffix and the CPU numbers are a correctness rehearsal,
not a speedup claim; the TPU truth is not measured yet.

Run: ``python benchmarks/bench_fused_update.py [--out FILE]``.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ON_TPU = jax.default_backend() == "tpu"

# GPT-2-124M leaves sliced to the dp=8 ZeRO shard (ceil(size/8), the
# _sharding.py split) — the shapes the fused tail actually runs on. The
# CPU rehearsal scales them 1:16 (the interpret-mode kernel re-expands to
# XLA anyway — off-chip only correctness is being rehearsed, not speed).
DP = 8
SCALE = 1 if ON_TPU else 16
LEAVES = {
    "wte": 50257 * 768, "wpe": 1024 * 768,
    "qkv": 12 * 768 * 2304, "attn_out": 12 * 768 * 768,
    "fc1": 12 * 768 * 3072, "fc2": 12 * 3072 * 768,
    "lns": 12 * 4 * 768 + 2 * 768,
}
REPS = 30


def main() -> int:
    import argparse
    import statistics
    import time

    from apex_tpu.monitor import json_record
    from apex_tpu.monitor.sink import collect_provenance, set_provenance

    set_provenance(collect_provenance())
    from apex_tpu.ops.fused_update import (
        adam_tail_reference,
        fused_adam_tail,
        fused_lamb_tail,
        lamb_tail_reference,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--reps", type=int, default=REPS)
    args = ap.parse_args()

    name = "zero_fused_update_tail"
    if not ON_TPU:
        name += "_CPU_FALLBACK"

    key = jax.random.PRNGKey(0)
    shards = {}
    for i, (k, n) in enumerate(LEAVES.items()):
        sz = -(-n // (DP * SCALE))
        kk = jax.random.fold_in(key, i)
        shards[k] = tuple(
            jax.random.normal(jax.random.fold_in(kk, j), (sz,),
                              jnp.float32) for j in range(4))
    # moments must be valid (v >= 0)
    shards = {k: (g, m, jnp.abs(v), p) for k, (g, m, v, p) in shards.items()}
    n_elems = sum(v[0].size for v in shards.values())
    kw = dict(betas=(0.9, 0.999), eps=1e-8, weight_decay=0.01,
              adam_w_mode=True)
    c1 = jnp.float32(1 - 0.9 ** 10)
    c2 = jnp.float32(1 - 0.999 ** 10)

    def step(tail, extra=()):
        def f(sh, c1, c2):
            return {k: tail(g, m, v, p, c1, c2, **kw, **dict(extra))
                    for k, (g, m, v, p) in sh.items()}
        return jax.jit(f)

    def time_it(f):
        out = f(shards, c1, c2)          # compile
        jax.block_until_ready(out)
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(shards, c1, c2))
            times.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(times)

    lamb_kw = {k: v for k, v in kw.items() if k != "adam_w_mode"}

    def lamb_step(tail):
        def f(sh, c1, c2):
            return {k: tail(g, m, v, p, c1, c2, **lamb_kw)
                    for k, (g, m, v, p) in sh.items()}
        return jax.jit(f)

    ref_ms = time_it(step(adam_tail_reference))
    fused_ms = time_it(step(fused_adam_tail, extra=(("use_pallas", True),)))
    lamb_ref_ms = time_it(lamb_step(lamb_tail_reference))
    lamb_fused_ms = time_it(lamb_step(
        lambda *a, **k2: fused_lamb_tail(*a, use_pallas=True, **k2)))

    rec = {
        "metric": name,
        "ok": True,
        "n_elems": int(n_elems),
        "n_leaves": len(shards),
        "dp": DP,
        "scale": SCALE,
        "ref_ms": round(ref_ms, 4),
        "fused_ms": round(fused_ms, 4),
        "speedup": round(ref_ms / fused_ms, 3) if fused_ms else None,
        "lamb_ref_ms": round(lamb_ref_ms, 4),
        "lamb_fused_ms": round(lamb_fused_ms, 4),
        "lamb_speedup": (round(lamb_ref_ms / lamb_fused_ms, 3)
                         if lamb_fused_ms else None),
        "reps": args.reps,
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
