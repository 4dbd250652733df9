"""Comm/compute-overlap benchmark: GPTConfig.overlap_comm on vs off.

One ``json_record`` line (the bench.py protocol): tp-parallel GPT train
step time with the monolithic collectives vs the decomposed ppermute rings
(``apex_tpu.comm.overlap``), plus the HLO-measured evidence — total
modeled wire bytes for both programs (per-ring byte-neutral; the full
grad program pays ~10% extra for the dW re-gather under remat, see the
``comm.overlap`` docstring) and the
decomposed program's hidden-vs-exposed collective-permute split from
``comm.accounting.overlap_report`` (hidden = the hop has a ``dot``
scheduled in its async start/done window on TPU, or a data-independent
``dot`` a latency-hiding scheduler may overlap on the CPU sim).

On the CPU sim the time column is NOT the story (collectives are memcpys;
the ring's extra dispatch overhead usually LOSES there) — the byte
neutrality + hidden-fraction columns are; the time column becomes the
headline on a real multi-chip slice (one chip has no ring to overlap and
the record says so honestly).

Run: ``python benchmarks/bench_overlap.py [--out FILE]``.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # a CPU rehearsal (asked for through the environment) runs on the
    # 8-virtual-device sim; the flag must land before the first
    # backend init
    from apex_tpu.utils.platform import pin_cpu_platform

    pin_cpu_platform(virtual_devices=8)

import jax

ON_TPU = jax.default_backend() == "tpu"

import dataclasses  # noqa: E402

import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

# the pinned protocol (canary discipline, see bench_comm.py): one fixed
# model/config so the line is comparable round-over-round
BATCH, SEQ, HIDDEN, LAYERS, HEADS, VOCAB = 2, 256, 128, 2, 8, 512
STEPS = 5


def _build(overlap: bool, tp: int):
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.transformer.pipeline_parallel.schedules.common import (
        replicate_loss,
    )
    from apex_tpu.transformer.testing import (
        GPTConfig,
        gpt_loss,
        gpt_param_specs,
        init_gpt_params,
    )

    cfg = GPTConfig(vocab_size=VOCAB, max_seq=SEQ, hidden=HIDDEN,
                    num_layers=LAYERS, num_heads=HEADS, dtype=jnp.bfloat16,
                    megatron_sp=True, overlap_comm=overlap)
    mesh = build_mesh(tp=tp, pp=1, sp=1)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    specs = gpt_param_specs(cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0, VOCAB)

    def loss(p, t, y):
        def body(p, a, b):
            return replicate_loss(gpt_loss(p, a, b, cfg), mesh,
                                  masked_axis=None)

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(specs, P(), P()), out_specs=P())(
                                 p, t, y)

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        params, tok, tok).compile()
    return compiled, (params, tok, tok)


def _time(compiled, args) -> float:
    out = compiled(*args)  # warmup is the caller's compile; run once more
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = compiled(*args)
    float(out[0])  # value-transfer fence (bench.py protocol)
    return (time.perf_counter() - t0) / STEPS * 1e3


def main() -> int:
    import argparse

    from apex_tpu.comm import collective_report, overlap_report
    from apex_tpu.monitor import json_record
    from apex_tpu.monitor.sink import collect_provenance, set_provenance

    set_provenance(collect_provenance())
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    tp = len(jax.devices())
    name = "gpt_tp_overlap_comm_step"
    if not ON_TPU:
        name += "_CPU_FALLBACK"
    if tp < 2:
        line = json_record(
            metric=name, ok=False, tp=tp,
            reason="single device: no TP ring to decompose; needs a slice")
        print(line, flush=True)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0

    off, off_args = _build(False, tp)
    on, on_args = _build(True, tp)
    off_ms = _time(off, off_args)
    on_ms = _time(on, on_args)
    bytes_off = collective_report(off).wire_bytes
    bytes_on = collective_report(on).wire_bytes
    rep = overlap_report(on)
    rec = {
        "metric": name,
        "tp": tp,
        "megatron_sp": True,
        "overlap_off_ms": round(off_ms, 3),
        "overlap_on_ms": round(on_ms, 3),
        "speedup": round(off_ms / on_ms, 3) if on_ms else None,
        "wire_bytes_off": round(bytes_off),
        "wire_bytes_on": round(bytes_on),
        "permutes": rep.permutes,
        "async_pairs": rep.async_pairs,
        "hidden_bytes": round(rep.hidden_wire_bytes),
        "exposed_bytes": round(rep.exposed_wire_bytes),
        "hidden_fraction": round(rep.hidden_fraction, 4),
        "config": {"batch": BATCH, "seq": SEQ, "hidden": HIDDEN,
                   "layers": LAYERS, "steps": STEPS},
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
