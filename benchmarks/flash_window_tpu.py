"""The listed flash kernels, timed alone on the chip at several tiles.

Each call is a forward and backward of ``ops.attention.flash_attention`` at
one cell's attention shape, in bfloat16, on the listed schedule:

* ``window4096``: SmallThinker's window layers (2 rows of 16,384 positions,
  28 query heads over 4 K/V heads of 128, a window of 4,096);
* ``causal``: the block-diffusion cell's shape (2 x 16,384, 32 heads over 4
  of 128) under the causal mask;
* ``blockdiff``: the same shape under the block-diffusion mask (block 4);
* ``mla``: the latent-attention cell's (1 x 16,384, 16 heads, keys of 192
  over values of 128, causal).

Each row: the call, the tile (``block_q x block_k``), the live tiles a head
the grid visits (``ops.attention._tile_plan``), the pairs of positions those
tiles compute a head, whether the plan picks the tile when the caller leaves
it (``block_q=None``), and milliseconds a forward + backward (the median over
three batches of ``--reps`` calls queued back to back after a warm-up). On
the chip the last line fits the plan's cost, milliseconds = rows x heads x
steps x (step + tile pairs x pair x (d + d_v) / 256), over every row timed:
the two constants in microseconds and each row's residual.

Run on the chip: ``python benchmarks/flash_window_tpu.py [--calls causal,mla]
[--tiles 512x512,1024x1024] [--out FILE]``. Without a TPU it compiles every
candidate for a described v5e instead and times nothing (exit 2): a
rehearsal, never a measurement.
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
import numpy as np

from apex_tpu.ops.attention import (CAUSAL, _tile_plan, block_diffusion_mask, flash_attention,
                                    sliding_window_mask)

SEQ = 16384

# name -> (rows, heads, K/V heads, key width, value width, structure)
CALLS = {
    "window4096": (2, 28, 4, 128, 128, sliding_window_mask(4096)),
    "causal": (2, 32, 4, 128, 128, CAUSAL),
    "blockdiff": (2, 32, 4, 128, 128, block_diffusion_mask(4)),
    "mla": (1, 16, 16, 192, 128, CAUSAL),
}
TILES = "256x256,512x512,1024x512,512x1024,1024x1024"


def fwd_bwd(structure, bq, bk, d):
    def loss(q, k, v):
        o = flash_attention(q, k, v, structure=structure, block_q=bq, block_k=bk,
                            scale=d ** -0.5)
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


def fit(rows):
    """(step us, pair us at widths 128 / 128, residuals): least squares on
    the relative error of ``ms = head rows x steps x (step + tile pairs x
    pair x width)``."""
    a = np.array([[r["head_rows"] * r["steps"],
                   r["head_rows"] * r["pairs"] * r["width"]] for r in rows], float)
    y = np.array([1e3 * r["ms"] for r in rows])
    (step, pair), *_ = np.linalg.lstsq(a / y[:, None], np.ones(len(y)), rcond=None)
    return step, pair, (a @ [step, pair] - y) / y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", default=",".join(CALLS))
    ap.add_argument("--tiles", default=TILES)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    tiles = [tuple(int(n) for n in t.split("x")) for t in args.tiles.split(",")]
    on_chip = jax.default_backend() == "tpu"
    rows = []
    for name in args.calls.split(","):
        b, h, hk, d, dv, structure = CALLS[name]
        shapes = ((b, h, SEQ, d), (b, hk, SEQ, d), (b, hk, SEQ, dv))
        planned = _tile_plan(SEQ, SEQ, d, jnp.bfloat16, structure, None, None,
                             group=h // hk, whole_rows=True, dv=dv)
        for bq, bk in tiles:
            if name == "window4096" and bq != bk:
                continue
            plan = _tile_plan(SEQ, SEQ, d, jnp.bfloat16, structure, bq, bk,
                              group=h // hk, whole_rows=True, dv=dv)
            row = {"call": name, "tile": f"{bq}x{bk}", "schedule": plan.schedule,
                   "steps": plan.visited, "pairs": plan.visited * bq * bk,
                   "head_rows": b * h, "width": (d + dv) / 256,
                   "planned": (planned.block_q, planned.block_k) == (bq, bk)}
            f = fwd_bwd(structure, bq, bk, d)
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
    out = {"device": str(jax.devices()[0]), "rows": rows}
    if on_chip and len(rows) >= 2:
        step, pair, resid = fit(rows)
        out["fit"] = {"step_us": step, "pair_us": pair,
                      "residuals": [round(float(r), 4) for r in resid]}
        print(json.dumps(out["fit"]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0 if on_chip else 2


if __name__ == "__main__":
    sys.exit(main())
