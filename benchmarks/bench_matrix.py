"""The full BASELINE.json config matrix — one JSON line per config.

``BASELINE.json`` names five configs (the reference publishes no numbers, so
every figure here is measured by this harness — see BASELINE.md):

1. ResNet-50 ImageNet amp O1, single chip                   -> img/s
2. DCGAN amp (2 models / 3 scalers)                         -> img/s
3. FusedAdam + FusedLayerNorm microbench (BERT-base shapes) -> ms/step
4. ResNet-50 DDP + SyncBatchNorm (8-device scaling shape on the virtual CPU
   mesh; chip img/s on the real chip)                       -> img/s + ratio
5. Megatron GPT-2 TP loss parity vs single-chip (virtual mesh; single-chip
   tokens/s is ../bench.py's headline)                      -> bool

Run: ``python benchmarks/bench_matrix.py [config ...]`` with configs from
{resnet50_o1, dcgan, microbench, ddp_syncbn, gpt_tp_pp}; default all.
Configs that need a multi-device mesh re-exec themselves in a subprocess
whose environment pins it to an 8-device virtual CPU platform (one chip
cannot host them; the child never touches the chip). A config that raises,
an unknown config name or a failed child fails the run.

Timing fence: example trainers host-read the loss every iteration; direct
loops here end with a scalar host-read, which forces the whole dispatched
chain. Steady-state numbers come from a second ``train()`` call that hits
the in-process jit cache.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax
import jax.numpy as jnp


def _emit(metric, value, unit, **extra):
    line = {"metric": metric, "value": round(float(value), 3), "unit": unit}
    line.update(extra)
    print(json.dumps(line), flush=True)


def _on_tpu():
    return jax.default_backend() == "tpu"


def _suffix(name):
    return name if _on_tpu() else name + "_CPU_FALLBACK"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def _imagenet():
    return _load(os.path.join(_ROOT, "examples", "imagenet", "main_amp.py"),
                 "imagenet_main_amp")


# ---------------------------------------------------------------------------
# 1. ResNet-50 amp O1 single chip — drives the example trainer itself

def _timed_train(m, argv, iters):
    """(img_or_tok per sec denominator dt). First train() compiles (the
    example trainer caches its jitted step per config), second is pure
    steady state."""
    m.train(m.parse_args(argv))  # compile
    t0 = time.perf_counter()
    m.train(m.parse_args(argv))  # steady state (jit cache hit)
    return (time.perf_counter() - t0) / iters


def bench_resnet50_o1():
    m = _imagenet()
    # reference operating point: image 224, per-device batch 224 at O1
    # (examples/imagenet/README.md:30-60); walk the batch down on OOM
    batches, size, iters = ([224, 128, 64], 224, 8) if _on_tpu() \
        else ([8], 32, 2)
    for batch in batches:
        argv = ["--arch", "resnet50", "--opt-level", "O1",
                "--batch-size", str(batch), "--image-size", str(size),
                "--iters", str(iters), "--print-freq", "1000"]
        try:
            dt = _timed_train(m, argv, iters)
        except jax.errors.JaxRuntimeError as e:
            # device OOM at this batch — try the next; anything else is a bug
            if "RESOURCE_EXHAUSTED" not in str(e) or batch == batches[-1]:
                raise
            print(f"# resnet50_o1 batch {batch} failed "
                  f"({type(e).__name__}); retrying smaller", flush=True)
            continue
        _emit(_suffix("resnet50_imagenet_ampO1_img_per_sec_chip"),
              batch / dt, "img/s", batch=batch, image_size=size)
        return


# ---------------------------------------------------------------------------
# 2. DCGAN amp

def bench_dcgan():
    dcgan = _load(os.path.join(_ROOT, "examples", "dcgan", "main_amp.py"),
                  "dcgan_main_amp")
    batch, iters = (64, 8) if _on_tpu() else (16, 2)
    argv = ["--iters", str(iters), "--batch-size", str(batch)]
    dcgan.train(dcgan.parse_args(argv))  # compile
    t0 = time.perf_counter()
    dcgan.train(dcgan.parse_args(argv))
    dt = (time.perf_counter() - t0) / iters
    _emit(_suffix("dcgan_ampO1_img_per_sec_chip"), batch / dt, "img/s",
          batch=batch)


# ---------------------------------------------------------------------------
# 3. FusedAdam + FusedLayerNorm microbench (BERT-base shapes)

def bench_microbench():
    from apex_tpu.normalization import FusedLayerNorm
    from apex_tpu.optimizers import FusedAdam

    hidden, tokens = 768, 32 * 512  # BERT-base rows
    iters = 20 if _on_tpu() else 3

    ln = FusedLayerNorm(hidden)
    vs = ln.init(jax.random.PRNGKey(0), jnp.zeros((2, hidden), jnp.bfloat16))
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, hidden)).astype(
        jnp.bfloat16)

    @jax.jit
    def ln_step(x):
        def f(x):
            return jnp.sum(ln.apply(vs, x).astype(jnp.float32) ** 2)
        g = jax.grad(f)(x)
        return x + 0.0 * g.astype(x.dtype)

    x = ln_step(x); float(x[0, 0])
    t0 = time.perf_counter()
    for _ in range(iters):
        x = ln_step(x)
    float(x[0, 0])
    _emit(_suffix("fused_layer_norm_bert_base_fwdbwd_ms"),
          (time.perf_counter() - t0) / iters * 1e3, "ms",
          shape=[tokens, hidden])

    key = jax.random.PRNGKey(2)
    params = {f"l{i}": jax.random.normal(jax.random.fold_in(key, i),
                                         (hidden, 12 * hidden)).astype(
        jnp.bfloat16) for i in range(12)}  # ~85M params
    opt = FusedAdam(lr=1e-4)
    state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def adam_step(p, s):
        g = jax.tree.map(lambda a: a * jnp.bfloat16(1e-4), p)
        u, s = opt.update(g, s, p)
        p = jax.tree.map(lambda a, b: a + b, p, u)
        return p, s

    params, state = adam_step(params, state)
    float(params["l0"][0, 0])
    t0 = time.perf_counter()
    for _ in range(iters):
        params, state = adam_step(params, state)
    float(params["l0"][0, 0])
    n = sum(x.size for x in jax.tree.leaves(params))
    _emit(_suffix("fused_adam_step_ms_per_100M_params"),
          (time.perf_counter() - t0) / iters * 1e3 * (1e8 / n), "ms",
          params_m=round(n / 1e6, 1))


# ---------------------------------------------------------------------------
# 4. ResNet-50 DDP + SyncBatchNorm

def bench_ddp_syncbn():
    """Chip rate on whatever devices exist here, plus 8-way DP scaling shape
    measured on the virtual CPU mesh in a subprocess (dp=8 vs dp=1 on the
    same platform — the scaling ratio the ICI allreduce must beat)."""
    m = _imagenet()
    n_dev = len(jax.devices())
    batches, size, iters = ([128 * n_dev, 64 * n_dev], 224, 6) \
        if _on_tpu() else ([8], 32, 2)
    for batch in batches:
        argv = ["--arch", "resnet50", "--opt-level", "O2", "--sync_bn",
                "--batch-size", str(batch), "--image-size", str(size),
                "--iters", str(iters), "--print-freq", "1000"]
        try:
            dt = _timed_train(m, argv, iters)
        except Exception as e:
            if batch == batches[-1]:
                raise
            print(f"# ddp_syncbn batch {batch} failed "
                  f"({type(e).__name__}); retrying smaller", flush=True)
            continue
        _emit(_suffix("resnet50_ddp_syncbn_img_per_sec"), batch / dt,
              "img/s", devices=n_dev, batch=batch)
        return


def bench_ddp_scaling_virtual():
    """ResNet-50+SyncBN throughput on an 8-device virtual CPU mesh (the dp
    mesh follows the platform's device count). The dp=1 comparison runs in a
    separate 1-device subprocess; the parent computes the scaling ratio."""
    m = _imagenet()
    per, size, iters = 4, 32, 3
    n_dev = len(jax.devices())

    batch = per * n_dev
    argv = ["--arch", "resnet50", "--opt-level", "O2", "--sync_bn",
            "--batch-size", str(batch), "--image-size", str(size),
            "--iters", str(iters), "--print-freq", "1000"]
    m.train(m.parse_args(argv))
    t0 = time.perf_counter()
    m.train(m.parse_args(argv))
    ips = batch * iters / (time.perf_counter() - t0)
    _emit(f"resnet50_ddp_syncbn_{n_dev}dev_virtual", ips, "img/s",
          devices=n_dev)


# ---------------------------------------------------------------------------
# 5. GPT-2 TP loss parity vs single chip (virtual mesh)

def bench_gpt_tp_pp():
    import numpy as np
    from jax.sharding import PartitionSpec as P

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

    n_dev = len(jax.devices())
    if n_dev < 2:
        _emit("gpt2_tp2_loss_parity", float("nan"), "bool",
              note=f"needs >=2 devices, have {n_dev}")
        return
    cfg = GPTConfig(vocab_size=1024, max_seq=128, hidden=128, num_layers=4,
                    num_heads=4, dtype=jnp.float32, remat=False,
                    fused_loss=False)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, 1024)
    tgt = jnp.roll(tok, -1, 1)

    def run(tp):
        mesh = build_mesh(tp=tp, pp=1, sp=1, devices=jax.devices()[:tp])
        specs = gpt_param_specs(cfg)

        def body(p, tok, tgt):
            return replicate_loss(gpt_loss(p, tok, tgt, cfg), mesh,
                                  masked_axis=None)

        return float(jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=P()))(params, tok, tgt))

    single, tp2 = run(1), run(2)
    parity = bool(np.isclose(single, tp2, rtol=1e-4))
    _emit("gpt2_tp2_loss_parity_vs_single_chip", parity, "bool",
          single=round(single, 6), tp2=round(tp2, 6))


CONFIGS = {
    "resnet50_o1": (bench_resnet50_o1, False),
    "dcgan": (bench_dcgan, False),
    "microbench": (bench_microbench, False),
    "ddp_syncbn": (bench_ddp_syncbn, False),
    "ddp_scaling_virtual": (bench_ddp_scaling_virtual, True),
    "gpt_tp_pp": (bench_gpt_tp_pp, True),
}


def _run_virtual(names, n_devices):
    """Re-exec the named configs on an n-device virtual CPU platform and
    forward their JSON lines; returns them parsed."""
    env = dict(os.environ,
               APEX_TPU_BENCH_VIRTUAL="1",
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          f" --xla_force_host_platform_device_count"
                          f"={n_devices}"))
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)] + names,
                          env=env, check=False, capture_output=True, text=True)
    rows = []
    for line in proc.stdout.splitlines():
        try:
            rows.append(json.loads(line))
            print(line, flush=True)
        except json.JSONDecodeError:
            pass
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        raise RuntimeError(f"virtual-mesh child for {names} failed "
                           f"(rc={proc.returncode}); see stderr above")
    return rows


def main(argv=None):
    from apex_tpu.monitor.sink import collect_provenance, set_provenance

    set_provenance(collect_provenance())
    names = list((argv if argv is not None else sys.argv[1:]) or CONFIGS)
    unknown = [n for n in names if n not in CONFIGS]
    if unknown:
        raise SystemExit(f"unknown config(s) {unknown}; choose from "
                         f"{sorted(CONFIGS)}")
    virtual = [n for n in names if CONFIGS[n][1]]
    local = [n for n in names if not CONFIGS[n][1]]
    if os.environ.get("APEX_TPU_BENCH_VIRTUAL"):
        local, virtual = names, []  # we ARE the subprocess

    for n in local:
        CONFIGS[n][0]()

    if virtual:
        rows = _run_virtual(virtual, 8)
        if "ddp_scaling_virtual" in virtual:
            # same program on 1 virtual device -> the DP scaling ratio
            rows1 = _run_virtual(["ddp_scaling_virtual"], 1)
            v8 = next((r["value"] for r in rows
                       if r["metric"].startswith("resnet50_ddp_syncbn_8dev")),
                      None)
            v1 = next((r["value"] for r in rows1
                       if r["metric"].startswith("resnet50_ddp_syncbn_1dev")),
                      None)
            if v8 and v1:
                _emit("resnet50_ddp_syncbn_scaling_ratio_8dev_vs_1dev",
                      v8 / v1, "x", ideal=8.0)


if __name__ == "__main__":
    main()
