"""Sweep Pallas kernel block sizes on hardware at the bench shape.

Round-3 task: close the MFU gap by tuning the knobs the kernels expose —
flash attention ``block_q``/``block_k`` and fused LM-head
``block_n``/``block_v`` (plus ``scan_unroll`` at the step level, which
bench.py's remat auto-tune already covers). This script times each
candidate on the real chip with the value-transfer fence and prints the
winner as the GPTConfig overrides to commit.

The attention pair are upper bounds: ``ops.attention._tile_plan`` takes
the widest divisor of the sequence under them and picks the schedule
(resident: one head whole in VMEM and at most ``_RESIDENT_MAX_BODIES``
tile bodies unrolled inside each kernel; streamed: tiles on the grid), and
each attention row prints the plan it timed. At seq 1024 only 512 x 512
keeps a causal head resident (3 bodies); narrower bounds stream (10 or more).

Run: ``python benchmarks/tune_blocks.py [--steps N]``. Refuses to sweep
on a non-TPU backend (interpret-mode timings would be meaningless) and
prints the shapes it would have swept.
"""

from __future__ import annotations

import argparse
import itertools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# flagship bench shape (bench.py): GPT-2 124M, batch 32, seq 1024
B, S, HEADS, HEAD_DIM, HIDDEN, VOCAB = 32, 1024, 12, 64, 768, 50304


def _fence(x):
    leaves = jax.tree.leaves(x)
    jax.block_until_ready(leaves)
    float(jax.numpy.sum(leaves[0].ravel()[:1]))


def _time(fn, *args, steps=5):
    fn(*args)  # compile
    _fence(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _fence(out)
    return (time.perf_counter() - t0) / steps


def sweep_attention(steps: int):
    import jax.numpy as jnp

    from apex_tpu.ops.attention import _tile_plan, flash_attention

    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (B, HEADS, S, HEAD_DIM), jnp.bfloat16)
    kk = jax.random.normal(jax.random.fold_in(k, 1), q.shape, jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(k, 2), q.shape, jnp.bfloat16)

    results = []
    for bq, bk in itertools.product((128, 256, 512, 1024), repeat=2):
        def fwd_bwd(q, kk, v, bq=bq, bk=bk):
            def loss(q, kk, v):
                return jnp.sum(flash_attention(
                    q, kk, v, causal=True, use_pallas=True,
                    block_q=bq, block_k=bk).astype(jnp.float32) ** 2)

            return jax.grad(loss, argnums=(0, 1, 2))(q, kk, v)

        plan = _tile_plan(S, S, HEAD_DIM, q.dtype, True, bq, bk)
        try:
            dt = _time(jax.jit(fwd_bwd), q, kk, v, steps=steps)
        except Exception as e:  # block combo invalid/OOM on this chip
            print(f"attn bq={bq:4d} bk={bk:4d}  FAILED "
                  f"{type(e).__name__}", flush=True)
            continue
        print(f"attn bq={bq:4d} bk={bk:4d}  {dt * 1e3:8.3f} ms  "
              f"{plan.schedule} {plan.block_q}x{plan.block_k}, "
              f"{plan.visited} tiles ({plan.masked} masked) of "
              f"{plan.rectangle}, {plan.bodies} bodies a kernel", flush=True)
        results.append((dt, bq, bk))
    if results:
        dt, bq, bk = min(results)
        print(f"BEST attention: attn_block_q={bq}, attn_block_k={bk} "
              f"({dt * 1e3:.3f} ms fwd+bwd)")
    return results


def sweep_lm_head(steps: int):
    import jax.numpy as jnp

    from apex_tpu.ops.lm_head_loss import lm_head_loss

    k = jax.random.PRNGKey(0)
    n = B * S
    x = jax.random.normal(k, (n, HIDDEN), jnp.bfloat16) * 0.1
    w = jax.random.normal(jax.random.fold_in(k, 1), (VOCAB, HIDDEN),
                          jnp.bfloat16) * 0.02
    t = jax.random.randint(jax.random.fold_in(k, 2), (n,), 0, VOCAB)

    results = []
    for bn, bv in itertools.product((256, 512, 1024), (1024, 2048, 4096)):
        def fwd_bwd(x, w, bn=bn, bv=bv):
            def loss(x, w):
                return jnp.mean(lm_head_loss(x, w, t, use_pallas=True,
                                             block_n=bn, block_v=bv))

            return jax.grad(loss, argnums=(0, 1))(x, w)

        try:
            dt = _time(jax.jit(fwd_bwd), x, w, steps=steps)
        except Exception as e:
            print(f"lm_head bn={bn:4d} bv={bv:4d}  FAILED "
                  f"{type(e).__name__}", flush=True)
            continue
        print(f"lm_head bn={bn:4d} bv={bv:4d}  {dt * 1e3:8.3f} ms",
              flush=True)
        results.append((dt, bn, bv))
    if results:
        dt, bn, bv = min(results)
        print(f"BEST lm_head: lm_block_n={bn}, lm_block_v={bv} "
              f"({dt * 1e3:.3f} ms fwd+bwd)")

    # The head is ~30% of the flagship step's flops and XLA's native
    # (32768, 768) x (768, 50304) matmul is a near-peak MXU workload —
    # the fused kernel's win (never materializing the 3.2 GB logits)
    # only pays if its matmul efficiency is close. Time the REAL unfused
    # path (what GPTConfig.fused_loss=False runs: bf16 logits into
    # vocab_parallel_cross_entropy, standalone_gpt.py:666-668) at the
    # same shape so the comparison is on the record against the actual
    # alternative, not a heavier fp32 strawman.
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.transformer.tensor_parallel.cross_entropy import (
        vocab_parallel_cross_entropy,
    )

    # the real path runs under shard_map with a (size-1 here) tp axis —
    # vocab_parallel_cross_entropy's pmax needs the axis to exist
    mesh1 = build_mesh(tp=1, pp=1, sp=1, devices=jax.devices()[:1])

    def unfused(x, w):
        def body(x, w):
            def loss(x, w):
                lg = jnp.dot(x, w.T)  # model dtype; CE upcasts internally
                return jnp.mean(vocab_parallel_cross_entropy(lg, t))

            return jax.grad(loss, argnums=(0, 1))(x, w)

        return jax.shard_map(body, mesh=mesh1, in_specs=(P(), P()),
                             out_specs=(P(), P()), check_vma=False)(x, w)

    try:
        dt_un = _time(jax.jit(unfused), x, w, steps=steps)
        print(f"lm_head UNFUSED (XLA logits+CE)  {dt_un * 1e3:8.3f} ms",
              flush=True)
        if results and dt_un < min(results)[0]:
            print(f"NOTE: unfused beats the fused kernel by "
                  f"{min(results)[0] / dt_un:.2f}x — set "
                  f"GPTConfig.fused_loss=False", flush=True)
    except Exception as e:
        print(f"lm_head UNFUSED  FAILED {type(e).__name__} "
              f"(likely logits OOM — which is the fused kernel's point)",
              flush=True)
    return results


def _full_step_ab(steps: int, knob: str, values):
    """Full-step A/B of one GPTConfig knob at the quick-bench config,
    timed by bench._measure — ONE copy of the compile/warm/fence/timing
    protocol (a fix to the value-transfer fence must not need
    re-applying in three sweeps)."""
    import bench

    results = []
    for v in values:
        tps, _, err = bench._measure(True, "full", bench.BATCH, bench.SEQ,
                                     steps, **{knob: v})
        if tps is None:
            print(f"{knob}={v}  FAILED {err}", flush=True)
            continue
        dt = bench.BATCH * bench.SEQ / tps
        print(f"{knob}={v}  {dt * 1e3:8.3f} ms/step", flush=True)
        results.append((dt, v))
    if results:
        dt, v = min(results)
        print(f"BEST {knob}: {v} ({dt * 1e3:.3f} ms/step)")
    return results


def sweep_fused_loss(steps: int):
    """Full-step A/B of GPTConfig.fused_loss — the in-context answer
    (interacts with remat and XLA's scheduling) to the same question
    sweep_lm_head's unfused row answers in isolation."""
    return _full_step_ab(steps, "fused_loss", (True, False))


def sweep_ln_impl(steps: int):
    """Full-step A/B of the LayerNorm implementation (GPTConfig.ln_pallas).

    Isolated LN timing cannot answer this one: a Pallas call is an XLA
    fusion barrier, so the kernel's fewer HBM passes compete against the
    fusions XLA gives up around it."""
    return _full_step_ab(steps, "ln_pallas", (True, False))


# the serve shapes the --megakernel-tiles sweep covers: the GPT-2-124M
# flagship layer plus its nearest production neighbours
MEGA_TILE_SHAPES = ((768, 4, 64), (512, 4, 64), (1024, 4, 64))


def sweep_megakernel_tiles(steps: int, out=None):
    """Time the fused decode block (serve.megakernel) at every VMEM-
    feasible lane-aligned weight tiling per serve shape and emit ONE
    ``json_record`` line naming the best tile config per (hidden,
    ffn_mult, head_dim). The greedy ``default_tiles`` pick is timed in
    the same sweep, so the record says whether the static heuristic
    left latency on the table (the knob to commit if it did:
    ``fused_layer_decode(..., tiles=...)``)."""
    import itertools as it

    import jax.numpy as jnp

    from apex_tpu.monitor import json_record
    from apex_tpu.monitor.sink import collect_provenance, set_provenance
    from apex_tpu.serve import KVCacheConfig, init_kv_cache
    from apex_tpu.serve.megakernel import (
        _VMEM_BUDGET_BYTES,
        _tiled_dims,
        _valid_tile_counts,
        default_tiles,
        fused_layer_decode,
        fused_live_bytes,
    )
    from apex_tpu.transformer.testing import GPTConfig

    set_provenance(collect_provenance())
    sweeps = []
    for hidden, ffn_mult, head_dim in MEGA_TILE_SHAPES:
        heads = hidden // head_dim
        cfg = GPTConfig(vocab_size=512, max_seq=1024, hidden=hidden,
                        num_layers=1, num_heads=heads, ffn_mult=ffn_mult,
                        dtype=jnp.bfloat16, fused_loss=False)
        kv = KVCacheConfig(num_layers=1, num_heads=heads,
                           head_dim=head_dim, num_blocks=16,
                           block_size=128, dtype=jnp.bfloat16)
        # every lane-aligned tiling whose live set fits the budget,
        # coarsest (fewest streaming DMAs) first
        cands = [t for t in it.product(*(
            _valid_tile_counts(d, True) for d in _tiled_dims(cfg)))
            if fused_live_bytes(cfg, kv, t) <= _VMEM_BUDGET_BYTES]
        cands.sort(key=lambda t: (t[0] * t[1] * t[2], t))
        cands = cands[:24]  # bound the sweep; coarse tilings dominate
        greedy = default_tiles(cfg, kv)
        h = cfg.hidden
        dt_ = jnp.bfloat16
        f3, hd, f = 3 * h, heads * head_dim, cfg.ffn_hidden
        k = jax.random.PRNGKey(0)
        lp = {
            "ln1_w": jnp.ones((h,), dt_), "ln1_b": jnp.zeros((h,), dt_),
            "qkv_kernel": jax.random.normal(k, (h, f3), dt_) * 0.02,
            "qkv_bias": jnp.zeros((f3,), dt_),
            "out_kernel": jax.random.normal(
                jax.random.fold_in(k, 1), (hd, h), dt_) * 0.02,
            "out_bias": jnp.zeros((h,), dt_),
            "ln2_w": jnp.ones((h,), dt_), "ln2_b": jnp.zeros((h,), dt_),
            "fc1_kernel": jax.random.normal(
                jax.random.fold_in(k, 2), (h, f), dt_) * 0.02,
            "fc1_bias": jnp.zeros((f,), dt_),
            "fc2_kernel": jax.random.normal(
                jax.random.fold_in(k, 3), (f, h), dt_) * 0.02,
            "fc2_bias": jnp.zeros((h,), dt_),
        }
        cl = {kk: v[0] for kk, v in init_kv_cache(kv).items()}
        x = jax.random.normal(jax.random.fold_in(k, 4),
                              (8, h), dt_) * 0.1
        bt = jnp.tile(jnp.arange(2, dtype=jnp.int32), (8, 1))
        lens = jnp.full((8,), 200, jnp.int32)
        rows = []
        for tiles in cands:
            def fn(x, lp, cl, bt, lens, tiles=tiles):
                return fused_layer_decode(x, lp, cl, cfg, kv, bt, lens,
                                          interpret=False, tiles=tiles)

            try:
                dt = _time(jax.jit(fn), x, lp, cl, bt, lens, steps=steps)
            except Exception as e:
                print(f"mega h={hidden} tiles={tiles}  FAILED "
                      f"{type(e).__name__}", flush=True)
                continue
            print(f"mega h={hidden} tiles={tiles}  {dt * 1e6:8.1f} us "
                  f"(live {fused_live_bytes(cfg, kv, tiles)} B)",
                  flush=True)
            rows.append((dt, tiles))
        if not rows:
            continue
        dt_best, best = min(rows)
        dt_greedy = next((d for d, t in rows if t == greedy), None)
        sweeps.append({
            "hidden": hidden, "ffn_mult": ffn_mult, "head_dim": head_dim,
            "best_tiles": list(best),
            "best_us": round(dt_best * 1e6, 1),
            "greedy_tiles": list(greedy) if greedy else None,
            "greedy_us": (round(dt_greedy * 1e6, 1)
                          if dt_greedy is not None else None),
            "live_bytes": fused_live_bytes(cfg, kv, best),
            "candidates_timed": len(rows),
        })
        print(f"BEST mega h={hidden} ffn_mult={ffn_mult} "
              f"hd={head_dim}: tiles={best} ({dt_best * 1e6:.1f} us)")
    line = json_record(metric="megakernel_tile_sweep",
                       ok=bool(sweeps), sweeps=sweeps,
                       vmem_budget_bytes=_VMEM_BUDGET_BYTES,
                       backend=jax.default_backend())
    print(line, flush=True)
    if out:
        with open(out, "w") as fh:
            fh.write(line + "\n")
    return 0 if sweeps else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--out", default=None)
    ap.add_argument("--megakernel-tiles", action="store_true",
                    help="sweep fused-decode weight tilings instead of "
                         "the training-kernel block knobs")
    args = ap.parse_args()

    if jax.default_backend() != "tpu":
        print(f"tune_blocks: backend is {jax.default_backend()}, not tpu; "
              f"refusing to sweep (interpret timings are meaningless)",
              file=sys.stderr)
        return 2
    if args.megakernel_tiles:
        return sweep_megakernel_tiles(args.steps, out=args.out)
    sweep_attention(args.steps)
    sweep_lm_head(args.steps)
    sweep_ln_impl(args.steps)
    sweep_fused_loss(args.steps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
