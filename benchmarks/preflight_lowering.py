"""Pre-flight: AOT-COMPILE the flagship train path for TPU from a CPU box.

Chip minutes are budgeted, and debugging is the expensive way to spend
them: every Pallas/Mosaic failure found here instead of on the chip is
saved for measurement. This builds the package's train step
(``apex_tpu.train.train_step_fn``, the step the benchmark's cells time) at
GPT-2-124M's widths, 32 x 1024 tokens, for the programs something still
runs: remat off and each remat policy with the fused loss, remat full
without it, on one chip, and remat full on the four-chip meshes
``chip_smoke.py`` runs, plus the ring-attention long-context step, and
compiles each for a v5e topology description: XLA:TPU and Mosaic's own
compiler run in full (VMEM allocation, layout inference, unsupported
vector ops), no chip needed. ``tests/test_tpu_lowering.py`` guards single
kernels and the serve engine's programs; this guards the composed train
programs.

Run: ``JAX_PLATFORMS=cpu python benchmarks/preflight_lowering.py``
Exit 1 if any configuration fails to compile or lost a kernel. A config
XLA reports as over the chip's HBM prints ``NOFIT`` and does not fail:
remat off at this batch is above the fit, and is compiled for its kernels.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from apex_tpu.ops._pallas_util import (
    compile_for_tpu,
    mosaic_calls,
    tpu_topology_devices,
)


def _compile(tag, jitted, *args, min_kernels=1):
    """Compile for the TPU topology and require >= min_kernels Mosaic
    custom calls in the compiled module — a preflight that silently
    compiles the reference fallback (because some dispatch site checks the
    live backend instead of ``compiled_backend()``) would de-risk
    nothing."""
    t0 = time.perf_counter()
    try:
        _, compiled = compile_for_tpu(jitted, *args)
    except Exception as e:  # noqa: BLE001 — report every config, then fail
        if "memory space hbm" in str(e):
            # a config above the HBM fit is no fault of a kernel; a VMEM
            # overflow stays a FAIL
            print(f"NOFIT {tag}: {str(e).split('. ', 1)[-1][:160]}",
                  flush=True)
            return True
        print(f"FAIL {tag}: {type(e).__name__}: {str(e)[:600]}", flush=True)
        return False
    kernels = mosaic_calls(compiled.as_text())
    n = sum(kernels.values())
    if n < min_kernels:
        print(f"FAIL {tag}: only {n} Mosaic call(s) in the compiled module "
              f"(expected >= {min_kernels}) — a kernel dispatch site fell "
              f"back to the reference", flush=True)
        return False
    print(f"OK   {tag}  ({n} kernels, {time.perf_counter() - t0:.1f}s)",
          flush=True)
    return True


def main() -> int:
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.train import abstract_train_args, train_step_fn
    from apex_tpu.transformer.testing import GPTConfig

    ok = True
    devices = tpu_topology_devices()
    batch, seq = 32, 1024

    def train(tag, dp, tp, **cfg_kw):
        mesh = build_mesh(tp=tp, pp=1, sp=1, dp=dp, devices=devices[:dp * tp])
        cfg = GPTConfig(max_seq=seq, **cfg_kw)
        step, opt = train_step_fn(cfg, mesh)
        return _compile(tag, step,
                        *abstract_train_args(cfg, opt, mesh, batch, seq),
                        min_kernels=4)

    # --- the GPT-2-124M train step on one chip ----------------------------
    # at the full batch: VMEM use depends on the row count
    for remat, policy, fused in [
            (False, "full", True), (True, "full", True),
            (True, "dots", True), (True, "dots_attn", True),
            (True, "full", False)]:
        ok &= train(f"train_step remat={remat}/{policy} fused={fused}",
                    1, 1, remat=remat, remat_policy=policy, fused_loss=fused)
    # --- the four-chip meshes chip_smoke.py trains on --------------------
    for dp, tp in ((4, 1), (2, 2)):
        ok &= train(f"train_step dp={dp} tp={tp}", dp, tp,
                    remat=True, remat_policy="full")

    # --- ring attention (long-context SP path), fwd + bwd ---------------
    from apex_tpu.transformer.sequence_parallel import ring_attention

    n = 4
    mesh = build_mesh(tp=1, pp=1, sp=n, devices=devices[:n])
    b, h, s, d = 1, 4, 512 * n, 64
    q = jax.ShapeDtypeStruct(
        (b, h, s, d), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, None, "sp")))

    def ring_loss(q, k, v):
        def body(q, k, v):
            o = ring_attention(q, k, v, axis_name="sp", causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(None, None, "sp"),) * 3,
                          out_specs=P(), check_vma=False)
        return jnp.sum(f(q, k, v))

    ok &= _compile("ring_attention sp fwd+bwd",
                   jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2))), q, q, q,
                   min_kernels=2)

    print("PREFLIGHT", "PASS" if ok else "FAIL", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
