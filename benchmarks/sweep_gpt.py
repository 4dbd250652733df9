"""Config sweep for the GPT train-step bench — measures tokens/s for
combinations of fused_loss / remat / remat_policy to guide tuning.

Run: python benchmarks/sweep_gpt.py
"""

from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

BATCH, SEQ, STEPS = 32, 1024, 10


def measure(remat, remat_policy, fused_loss):
    from apex_tpu.optimizers import FusedAdam
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

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    batch, seq, steps = (BATCH, SEQ, STEPS) if on_tpu else (2, 128, 2)

    cfg = GPTConfig(vocab_size=50304, max_seq=seq, hidden=768, num_layers=12,
                    num_heads=12, dtype=jnp.bfloat16, remat=remat,
                    remat_policy=remat_policy, fused_loss=fused_loss)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    mesh = build_mesh(tp=1, pp=1, sp=1, devices=jax.devices()[:1])
    specs = gpt_param_specs(cfg)
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)

    def loss_fn(p, tok, tgt):
        def body(p, tok, tgt):
            return replicate_loss(gpt_loss(p, tok, tgt, cfg), mesh,
                                  masked_axis=None)

        return jax.shard_map(body, mesh=mesh, in_specs=(specs, P(), P()),
                             out_specs=P())(p, tok, tgt)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tok, tgt):
        loss, grads = jax.value_and_grad(loss_fn)(params, tok, tgt)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    key = jax.random.PRNGKey(1)
    tok = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    tgt = jnp.roll(tok, -1, axis=1)

    try:
        params, opt_state, loss = train_step(params, opt_state, tok, tgt)
        float(loss)  # host-read fence: forces the donated-params chain
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise  # only "does not fit the chip" makes a config unusable
        return None, f"{type(e).__name__}: {str(e)[:120]}"

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state, tok, tgt)
    float(loss)
    dt = (time.perf_counter() - t0) / steps
    return batch * seq / dt, None


def main():
    combos = [
        (False, "full", False),
        (False, "full", True),
        (True, "dots", False),
        (True, "dots", True),
        (True, "full", False),
        (True, "full", True),
    ]
    for remat, pol, fused in combos:
        tps, err = measure(remat, pol, fused)
        tag = f"remat={remat} policy={pol} fused_loss={fused}"
        if tps is None:
            print(f"{tag}: FAILED {err}", flush=True)
        else:
            print(f"{tag}: {tps:,.0f} tokens/s", flush=True)


if __name__ == "__main__":
    main()
