"""The routed experts' grouped products, timed alone on the chip.

At the shapes of the two routed cells' products (the buffer a pass fills,
the held experts' spans in whole layout tiles, bfloat16 in and out) this
times the forward, dx and dw of each implementation separately:

* ``ragged_dot``: ``lax.ragged_dot`` and its VJP (XLA's grouped product);
* ``ragged_dot_full``: the same with the groups filling the whole buffer,
  which says whether XLA computes the room past the last group;
* ``megablox``: ``jax.experimental.pallas.ops.tpu.megablox``'s ``gmm`` and
  ``tgmm`` at a few tilings;
* ``kernel``: ``apex_tpu.ops.grouped_matmul``'s kernels at a few row tiles.

Each row: milliseconds a call (the median over three batches of ``--reps``
calls queued back to back after a warm-up), TFLOP/s over the rows
the groups fill, and, for the kernels, the largest difference from
``ragged_dot`` over those rows relative to its largest value.

Run on the chip: ``python benchmarks/grouped_matmul_tpu.py [--out FILE]``.
Without a TPU it compiles every candidate for a described v5e instead and
times nothing (exit 2): a rehearsal, never a measurement.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.ops import grouped_matmul as gm

# (cell, buffer rows, groups, mean load, layout tile, (a, b) of each product)
CELLS = [
    ("deepseek-v2-lite.train-s16k", 36864, 16, 1536, 512,
     [(2048, 1408), (1408, 2048)]),
    ("sdar-30b-a3b.train-blockdiff-s8k", 98304, 32, 2048, 1024,
     [(2048, 768), (768, 2048)]),
]
# the kernels' row tiles tried, and megablox's (row tile, widest block)
KERNEL_ROWS = [512, 256, 1024]
MEGABLOX_PLANS = [(512, 1024), (256, 1024), (512, 512)]


def _block(width: int, most: int) -> int:
    """megablox's block of ``width``: the widest multiple of 128 that divides
    it and is at most ``most``; ``width`` whole where that is under a quarter
    of ``most`` (1,408 = 11 x 128 has no divisor between 128 and itself)."""
    best = max((c for c in range(128, min(width, most) + 1, 128)
                if width % c == 0), default=0)
    return best if 4 * best >= most else width


def spans(rows: int, groups: int, mean: int, tile: int, seed: int = 0):
    """Loads about ``mean`` (a standard deviation of 40, as the cells'
    routers give), rounded up to whole tiles and clipped to the buffer."""
    loads = np.random.default_rng(seed).normal(mean, 40, groups).round()
    s = (np.ceil(np.maximum(loads, 0) / tile) * tile).astype(np.int32)
    while s.sum() > rows:
        s[np.argmax(s)] -= tile
    return s


def passes(impl, sizes, a, b, plan=None):
    """{pass: f(xs, w, dy)} for one implementation."""
    sz = jnp.asarray(sizes)
    if impl.startswith("ragged_dot"):
        fwd = lambda xs, w, dy: lax.ragged_dot(xs, w, sz)
        dx = lambda xs, w, dy: jax.vjp(lambda x: lax.ragged_dot(x, w, sz), xs)[1](dy)[0]
        dw = lambda xs, w, dy: jax.vjp(lambda v: lax.ragged_dot(xs, v, sz), w)[1](dy)[0]
        return {"fwd": fwd, "dx": dx, "dw": dw}
    if impl == "megablox":
        # the package's ``gmm`` is the custom_vjp; the kernels' module has both
        mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
        tm, most = plan
        blk = lambda width: _block(width, most)
        bf = jnp.bfloat16
        return {
            "fwd": lambda xs, w, dy: mb.gmm(xs, w, sz, bf, (tm, blk(a), blk(b))),
            "dx": lambda xs, w, dy: mb.gmm(dy, w, sz, bf, (tm, blk(b), blk(a)),
                                           transpose_rhs=True),
            "dw": lambda xs, w, dy: mb.tgmm(xs.swapaxes(0, 1), dy, sz, bf,
                                            (tm, blk(a), blk(b))),
        }
    tm = plan
    return {
        "fwd": lambda xs, w, dy: gm._product(xs, w, sz, tm, False, False, "grouped_fwd"),
        "dx": lambda xs, w, dy: gm._product(dy, w, sz, tm, True, False, "grouped_dx"),
        "dw": lambda xs, w, dy: gm._weight_grad(xs, dy, sz, tm, False, w.dtype),
    }


def candidates():
    """(cell, a, b, impl, plan, sizes, passes) for every row."""
    for cell, rows, groups, mean, tile, products in CELLS:
        sizes = spans(rows, groups, mean, tile)
        full = np.full(groups, rows // groups, np.int32)
        for a, b in products:
            yield cell, rows, a, b, "ragged_dot", None, sizes
            yield cell, rows, a, b, "ragged_dot_full", None, full
            for plan in MEGABLOX_PLANS:
                yield cell, rows, a, b, "megablox", plan, sizes
            for tm in KERNEL_ROWS:
                if tile % tm == 0:
                    yield cell, rows, a, b, "kernel", tm, sizes


def _time(f, args, reps):
    """Milliseconds a call: the median over three batches of ``reps`` calls
    queued back to back, each batch ended by ``block_until_ready``."""
    out = f(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(reps):
            last = f(*args)
        jax.block_until_ready(last)
        times.append((time.perf_counter() - t) / reps)
    return statistics.median(times) * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", help="also write the rows to this file, one JSON line each")
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "device_kind": dev.device_kind,
                      "device_count": jax.device_count()}), flush=True)
    rows_out = []
    refs = {}
    for cell, rows, a, b, impl, plan, sizes in candidates():
        k = jax.random.split(jax.random.PRNGKey(a * 7 + b), 3)
        g = len(sizes)
        xs = jax.random.normal(k[0], (rows, a), jnp.bfloat16)
        w = (jax.random.normal(k[1], (g, a, b), jnp.float32) * a ** -0.5).astype(jnp.bfloat16)
        dy = jax.random.normal(k[2], (rows, b), jnp.bfloat16)
        filled = int(sizes.sum())
        for name, f in passes(impl, sizes, a, b, plan).items():
            row = {"cell": cell, "a": a, "b": b, "impl": impl, "plan": plan,
                   "pass": name, "rows_filled": filled, "rows": rows}
            jf = jax.jit(f)
            try:
                if not on_chip:
                    from apex_tpu.ops._pallas_util import compile_for_tpu
                    compile_for_tpu(jf, xs, w, dy)
                    row["compiled"] = True
                else:
                    ms, out = _time(jf, (xs, w, dy), args.reps)
                    row["ms"] = round(ms, 4)
                    row["tflops"] = round(2 * filled * a * b / ms / 1e9, 2)
                    key = (cell, a, b, name)
                    if impl == "ragged_dot":
                        refs[key] = out
                    elif impl != "ragged_dot_full" and key in refs:
                        ref = refs[key]
                        if name != "dw":
                            ref, out = ref[:filled], out[:filled]
                        err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
                        row["rel_err"] = float(err / jnp.max(jnp.abs(ref.astype(jnp.float32))))
            except Exception as e:  # a tiling Mosaic refuses is a row, not the end
                row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            rows_out.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            for r in rows_out:
                fh.write(json.dumps(r) + "\n")
    return 0 if on_chip else 2


if __name__ == "__main__":
    sys.exit(main())
