"""A window layer's flash attention, timed alone on the chip at a few tiles.

At SmallThinker's window cell (2 rows of 16,384 positions, 28 query heads
over 4 K/V heads of 128, bfloat16, a window of 4,096 on the listed schedule)
this times the forward and backward of ``ops.attention.flash_attention``
with ``sliding_window_mask`` at each tile in ``--tiles`` (query and key
blocks alike), and the full layer's causal call at 512 beside them. Each
row: the tile, the live tiles a head the grid visits
(``ops.attention._tile_plan``), and milliseconds a forward + backward (the
median over three batches of ``--reps`` calls queued back to back after a
warm-up).

Run on the chip: ``python benchmarks/flash_window_tpu.py [--tiles 512,1024]
[--out FILE]``. Without a TPU it compiles every candidate for a described
v5e instead and times nothing (exit 2): a rehearsal, never a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from apex_tpu.ops.attention import CAUSAL, _tile_plan, flash_attention, sliding_window_mask

ROWS, HEADS, KV_HEADS, SEQ, D, WINDOW = 2, 28, 4, 16384, 128, 4096


def candidates(tiles):
    """(name, structure, tile) of each call timed."""
    window = sliding_window_mask(WINDOW)
    return ([(f"window{WINDOW}", window, t) for t in tiles]
            + [("causal", CAUSAL, 512)])


def fwd_bwd(structure, tile):
    def loss(q, k, v):
        o = flash_attention(q, k, v, structure=structure, block_q=tile, block_k=tile,
                            scale=D ** -0.5)
        return jnp.sum(o.astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def _time(f, args, reps):
    """Median seconds a call over three batches of ``reps`` calls queued back
    to back, each batch ended by ``block_until_ready``."""
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(reps):
            last = f(*args)
        jax.block_until_ready(last)
        times.append((time.perf_counter() - t) / reps)
    return statistics.median(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="512,1024")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    tiles = [int(t) for t in args.tiles.split(",")]
    shapes = ((ROWS, HEADS, SEQ, D), (ROWS, KV_HEADS, SEQ, D), (ROWS, KV_HEADS, SEQ, D))
    on_chip = jax.default_backend() == "tpu"
    rows = []
    for name, structure, tile in candidates(tiles):
        plan = _tile_plan(SEQ, SEQ, D, jnp.bfloat16, structure, tile, tile,
                          group=HEADS // KV_HEADS)
        row = {"call": name, "tile": tile, "schedule": plan.schedule, "live_tiles": plan.visited}
        f = fwd_bwd(structure, tile)
        if on_chip:
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            args_ = [jax.random.normal(k, s, jnp.bfloat16) for k, s in zip(keys, shapes)]
            row["ms"] = 1e3 * _time(f, args_, args.reps)
        else:
            from apex_tpu.ops._pallas_util import compile_for_tpu
            compile_for_tpu(f, *(jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes))
            row["compiled"] = True
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": str(jax.devices()[0]), "rows": rows}, f, indent=1)
    return 0 if on_chip else 2


if __name__ == "__main__":
    sys.exit(main())
