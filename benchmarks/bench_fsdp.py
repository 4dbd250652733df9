"""FSDP (ZeRO-3) vs DDP+ZeRO-1 A/B — step time, HBM and wire bytes.

One ``json_record`` line (the bench.py protocol): the pinned GPT fixture
trained with the ``zero1`` plan (``DistributedFusedAdam``: params
replicated, optimizer state sharded — the repo's pre-FSDP best) and with
the ``fsdp`` plan (``apex_tpu.fsdp``: params sharded too, gather-on-demand
forward, grads reduce-scattered into shard layout), both configured
through ``ParallelismPlan`` presets. Columns:

* ``step_ms_zero1`` / ``step_ms_fsdp`` — compiled train-step wall time;
* ``peak_hbm_bytes_*`` — ``device_memory_stats`` when the backend reports
  it (TPU), else the modeled ``hbm_params_bytes`` accounting
  (``fsdp/accounting.py``) with an honest ``hbm_source`` marker;
* ``hbm_params_bytes_*`` + ``hbm_reduction_vs_zero1``/``_vs_ddp`` — the
  modeled per-chip param+grad+optimizer-state story (the acceptance
  metric: the replicated-params term ZeRO-1 keeps is what FSDP deletes);
* ``wire_bytes_*`` — modeled step wire bytes (same ring models
  ``comm.accounting`` prices off compiled HLO);
* ``ring.hidden_fraction`` — the FSDP-position gather ring
  (``matmul_param_gather`` MLP, fwd+bwd) measured from its compiled HLO
  by ``accounting.overlap_report``: the share of ring bytes that travel
  behind a GEMM.

On the CPU sim the time columns are NOT the story (collectives are
memcpys) — the HBM/wire/hidden-fraction columns are, and the record
carries the ``_CPU_FALLBACK`` suffix. A single chip has no dp axis to shard
(the record says so honestly, like bench_overlap); the time columns need
the four-chip host.

Run: ``python benchmarks/bench_fsdp.py [--plan fsdp|fsdp+tp] [--out F]``.
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
from jax import lax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

# the pinned protocol (canary discipline, see bench_comm.py): one fixed
# model so the line is comparable round-over-round
BATCH_PER_RANK, SEQ, HIDDEN, LAYERS, HEADS, VOCAB = 2, 256, 128, 2, 8, 512
STEPS = 5
LR = 1e-3


def _gpt(plan):
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    cfg = GPTConfig(vocab_size=VOCAB, max_seq=SEQ, hidden=HIDDEN,
                    num_layers=LAYERS, num_heads=HEADS, dtype=jnp.bfloat16,
                    **plan.gpt_overrides())
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _build_zero1(mesh, dp):
    """The baseline: DDP-style replicated params + ZeRO-1 sharded state
    (DistributedFusedAdam — its reduce-scatter/all-gather IS the dp grad
    machinery)."""
    from apex_tpu.parallel import ParallelismPlan
    from apex_tpu.transformer.testing import gpt_loss

    plan = ParallelismPlan.preset("zero1")
    cfg, params = _gpt(plan)
    opt = plan.build_optimizer(lr=LR)

    def init_fn(p):
        return opt.init(p)

    pspecs = jax.tree_util.tree_map(lambda _: P(), params)
    shard = jax.tree_util.tree_map(lambda _: P("dp"), params)
    from apex_tpu.contrib.optimizers.distributed_fused_adam import (
        DistAdamState,
    )

    sspec = DistAdamState(count=P(), master=shard, mu=shard, nu=shard)
    init = jax.jit(jax.shard_map(
        init_fn, mesh=mesh, in_specs=(pspecs,), out_specs=sspec,
        check_vma=False))

    def body(p, st, t):
        l, g = jax.value_and_grad(lambda p: gpt_loss(p, t, t, cfg))(p)
        p, st = opt.step(g, st, p)
        return p, st, lax.pmean(l, "dp")

    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(pspecs, sspec, P("dp")),
        out_specs=(pspecs, sspec, P()), check_vma=False))
    tok = jax.random.randint(jax.random.PRNGKey(1),
                             (dp * BATCH_PER_RANK, SEQ), 0, VOCAB)
    ostate = init(params)
    compiled = step.lower(params, ostate, tok).compile()
    return plan, params, compiled, (params, ostate, tok)


def _local_meta(params, specs, mesh):
    """FSDP LeafMeta of the IN-PROGRAM (tp-local) leaf shapes: each
    sharded dim divided by its mesh axis size."""
    from apex_tpu.fsdp import LeafMeta

    def one(p, spec):
        shape = list(jnp.shape(p))
        for d, axes in enumerate(tuple(spec)):
            if axes is None:
                continue
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                shape[d] //= mesh.shape[a]
        return LeafMeta(tuple(shape), str(jnp.result_type(p)))

    return jax.tree_util.tree_map(one, params, specs)


def _build_fsdp(mesh, dp, preset):
    from apex_tpu.fsdp import FSDPAdamState
    from apex_tpu.parallel import ParallelismPlan
    from apex_tpu.transformer.testing import gpt_loss, gpt_param_specs

    plan = ParallelismPlan.preset(preset)
    cfg, params = _gpt(plan)
    fsdp = plan.fsdp()
    opt = plan.build_optimizer(lr=LR)
    pspecs = (gpt_param_specs(cfg) if plan.tp > 1
              else jax.tree_util.tree_map(lambda _: P(), params))
    # flat master shards: dp-sharded, and under tp ALSO tp-varying (each
    # tp rank shards its own tp-local weights) — stack both axes
    shard_axes = ("dp", "tp") if plan.tp > 1 else ("dp",)
    shard = jax.tree_util.tree_map(lambda _: P(shard_axes), params)
    # meta must describe the TP-LOCAL leaf shapes the gather restores
    meta = _local_meta(params, pspecs, mesh)
    sspec = FSDPAdamState(count=P(), master=shard, mu=shard, nu=shard)
    init = jax.jit(jax.shard_map(
        opt.init, mesh=mesh, in_specs=(pspecs,), out_specs=sspec,
        check_vma=False))

    def body(st, t):
        def loss_fn(master):
            return gpt_loss(fsdp.gather(master, meta), t, t, cfg)

        l, g = jax.value_and_grad(loss_fn)(st.master)
        st = opt.step(g, st)
        return st, lax.pmean(l, "dp")

    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(sspec, P("dp")),
        out_specs=(sspec, P()), check_vma=False))
    tok = jax.random.randint(jax.random.PRNGKey(1),
                             (dp * BATCH_PER_RANK, SEQ), 0, VOCAB)
    state = init(params)
    compiled = step.lower(state, tok).compile()
    return plan, params, meta, fsdp, compiled, (state, tok)


def _time(compiled, args) -> float:
    out = compiled(*args)  # one warm run beyond the AOT compile
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = compiled(*args)
    jax.tree_util.tree_leaves(out)[-1].block_until_ready()
    return (time.perf_counter() - t0) / STEPS * 1e3


def _peak_hbm():
    """(peak bytes, source) — measured when the backend reports it."""
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            return float(stats["peak_bytes_in_use"]), "device_memory_stats"
    except Exception:
        pass
    return None, "modeled"


def _ring_report():
    """Compile the FSDP-position gather-ring MLP (matmul_param_gather,
    fwd+bwd) and measure its hidden/exposed split from the HLO."""
    from apex_tpu.comm import overlap_report
    from apex_tpu.fsdp import FSDP
    from apex_tpu.parallel.mesh import build_mesh

    fsdp = FSDP()
    mesh = build_mesh(tp=1, pp=1, sp=1)
    d_in, d_h = 256, 512
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (len(jax.devices()), 8, d_in), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(3), (d_in, d_h), jnp.float32)
    w2 = jax.random.normal(jax.random.PRNGKey(4), (d_h, d_in), jnp.float32)

    def loss(x, w1, w2):
        def body(x, w1s, w2s):
            h = jax.nn.gelu(fsdp.linear(x[0], w1s))
            y = fsdp.linear(h, w2s)
            return lax.psum(jnp.sum(y * y), "dp")

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("dp"), P(None, "dp"), P(None, "dp")),
            out_specs=P())(x, w1, w2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(1, 2))).lower(
        x, w1, w2).compile()
    rep = overlap_report(compiled.as_text())
    return {"permutes": rep.permutes, "hidden": rep.hidden,
            "hidden_bytes": round(rep.hidden_wire_bytes),
            "exposed_bytes": round(rep.exposed_wire_bytes),
            "hidden_fraction": round(rep.hidden_fraction, 4)}


def main() -> int:
    import argparse

    from apex_tpu.monitor import json_record
    from apex_tpu.monitor.sink import collect_provenance, set_provenance

    set_provenance(collect_provenance())
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", default="fsdp", choices=["fsdp", "fsdp+tp"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    n_dev = len(jax.devices())
    name = "gpt_fsdp_vs_zero1_step"
    if not ON_TPU:
        name += "_CPU_FALLBACK"
    if n_dev < 2:
        line = json_record(
            metric=name, ok=False, n_devices=n_dev,
            reason="single device: no dp axis to shard; needs a slice")
        print(line, flush=True)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 2

    from apex_tpu.contrib.optimizers.distributed_fused_adam import (
        _zero_wire_bytes,
    )
    from apex_tpu.fsdp import fsdp_step_wire_bytes, hbm_params_bytes
    from apex_tpu.parallel import ParallelismPlan
    from apex_tpu.parallel.mesh import build_mesh

    fs_plan = ParallelismPlan.preset(args.plan)
    tp = fs_plan.tp
    dp = n_dev // tp
    mesh_base = build_mesh(tp=1, pp=1, sp=1)
    mesh_fs = fs_plan.mesh()

    # fsdp runs FIRST: ``peak_bytes_in_use`` is a process-lifetime
    # high-water mark, so the side the regress gate watches (fsdp,
    # lower-is-better) must be measured before the bigger zero1 program
    # raises the mark. z_peak is then max(fsdp, zero1) — zero1's own peak
    # whenever the claim under test holds.
    plan_f, f_params, meta, fsdp, f_compiled, f_args = _build_fsdp(
        mesh_fs, dp, args.plan)
    f_ms = _time(f_compiled, f_args)
    f_peak, f_src = _peak_hbm()

    plan_z, params, z_compiled, z_args = _build_zero1(mesh_base, n_dev)
    z_ms = _time(z_compiled, z_args)
    z_peak, _ = _peak_hbm()

    h_ddp = hbm_params_bytes(params, strategy="ddp", world=n_dev)
    h_z = hbm_params_bytes(params, strategy="zero1", world=n_dev)
    # per-chip: the fsdp side shards its TP-LOCAL leaves over dp
    h_f = hbm_params_bytes(meta, strategy="fsdp", world=dp)
    ring = _ring_report()

    record = dict(
        metric=name,
        ok=bool(ring["hidden_fraction"] >= 0.5),
        n_devices=n_dev, dp=dp, tp=tp, plan=args.plan,
        step_ms_zero1=round(z_ms, 3),
        step_ms_fsdp=round(f_ms, 3),
        hbm_source=f_src,
        peak_hbm_bytes_zero1=round(z_peak) if z_peak else round(
            h_z["total"]),
        peak_hbm_bytes_fsdp=round(f_peak) if f_peak else round(
            h_f["total"]),
        hbm_params_bytes_ddp=round(h_ddp["total"]),
        hbm_params_bytes_zero1=round(h_z["total"]),
        hbm_params_bytes_fsdp=round(h_f["total"]),
        hbm_reduction_vs_zero1=round(h_z["total"] / h_f["total"], 3),
        hbm_reduction_vs_ddp=round(h_ddp["total"] / h_f["total"], 3),
        wire_bytes_zero1=round(_zero_wire_bytes(
            jax.tree_util.tree_leaves(params), n_dev, None)),
        wire_bytes_fsdp=round(fsdp_step_wire_bytes(meta, dp)),
        ring=ring,
        config={"batch_per_rank": BATCH_PER_RANK, "seq": SEQ,
                "hidden": HIDDEN, "layers": LAYERS, "heads": HEADS,
                "vocab": VOCAB, "steps": STEPS,
                "zero1": plan_z.describe(), "fsdp": plan_f.describe()},
    )
    line = json_record(**record)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
