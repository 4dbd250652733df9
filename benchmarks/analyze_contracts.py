"""Compiled-program contract check — the ``apex_tpu.analyze`` bench.

One ``json_record`` line (the bench.py protocol) asserting the repo's
compiled-program contracts on THIS box's toolchain:

* **donation** — the flagship GPT train step's donated params and the
  serve decode step's donated KV pools are ALIASED in the compiled
  executables (``donated_copied`` must stay 0);
* **recompile** — 3 train steps reuse ONE compilation and a warmed serve
  engine runs a fresh mixed-length workload with ZERO new compiles
  (``analyze.recompile_guard``);
* **adapters** — the serve LoRA pool rides every jit site donated AND
  aliased (``analyze.adapters``, ``adapter_donated_copied`` stays 0) and
  an adapter swap on a warm engine compiles NOTHING new;
* **dtype** — the bf16 serve decode program's jaxpr profile:
  ``fp32_dots`` (the two fp32 attention-stability dots are the accepted
  level — regress flags growth) and ``convert_churn_ops`` (must stay 0);
* **host sync** — ``host_syncs`` reachable from the decode step: 0;
* **exposed collectives** — the FSDP-position gather-ring MLP (the
  stage-14 ring, recompiled) split hidden-vs-exposed by
  ``analyze.exposed_report`` over the compiled HLO (needs two devices;
  the record says so honestly otherwise);
* **lint** — ``analyze.lint`` over ``apex_tpu/`` against the checked-in
  baseline (``lint_violations``: NEW violations, must stay 0).

CPU runs carry the ``_CPU_FALLBACK`` metric suffix and never promote
(the watcher rule); a record with ``ok: false`` never promotes either.

Run: ``python benchmarks/analyze_contracts.py [--out FILE]``.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # a CPU rehearsal (asked for through the environment) runs on the
    # 8-virtual-device sim; the flag must land before the first
    # backend init
    from apex_tpu.utils.platform import pin_cpu_platform

    pin_cpu_platform(virtual_devices=8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ON_TPU = jax.default_backend() == "tpu"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gpt_cfg(dtype):
    from apex_tpu.transformer.testing import GPTConfig

    return GPTConfig(vocab_size=97, max_seq=64, hidden=32, num_layers=2,
                     num_heads=4, dtype=dtype, fused_loss=False)


def _serve_fixture(dtype):
    from apex_tpu.serve import KVCacheConfig, init_kv_cache
    from apex_tpu.transformer.testing import init_gpt_params

    cfg = _gpt_cfg(dtype)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    kv = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                       num_blocks=8, block_size=8, dtype=dtype)
    return cfg, params, kv, init_kv_cache(kv)


def gpt_step_contracts() -> dict:
    """Donation + recompile + host-sync on the flagship GPT train step
    (the serve ``gpt_prefill`` forward — tp-optional, stock-safe)."""
    from apex_tpu import analyze
    from apex_tpu.serve.decode import gpt_prefill

    cfg, params, kv, cache = _serve_fixture(jnp.float32)
    toks = jnp.zeros((16,), jnp.int32).at[:9].set(
        jnp.arange(1, 10, dtype=jnp.int32))
    block_row = jnp.arange(2, dtype=jnp.int32)

    def train_step(p, toks, target):
        def loss_fn(p):
            _, logits = gpt_prefill(p, toks, jnp.int32(9), cache,
                                    block_row, cfg, kv)
            return -jax.nn.log_softmax(logits)[target]

        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(
            lambda a, b: a - 0.01 * b, p, g), loss

    rep = analyze.check_donation(train_step, params, toks, jnp.int32(7),
                                 donate_argnums=(0,))
    out = {f"gpt_{k}": v for k, v in rep.as_record().items()}
    step = jax.jit(train_step, donate_argnums=(0,))
    p = jax.tree_util.tree_map(jnp.copy, params)
    try:
        with analyze.recompile_guard(step):
            for _ in range(3):
                p, _loss = step(p, toks, jnp.int32(7))
        out["gpt_recompile_ok"] = True
    except analyze.RecompileError:
        out["gpt_recompile_ok"] = False
    sync = analyze.host_sync_report(train_step, params, toks, jnp.int32(7))
    out["gpt_host_syncs"] = sync.host_syncs
    return out


def serve_contracts() -> dict:
    """Donation + steady-state recompile + dtype/host-sync profile on the
    serve decode path (bf16 pools — the production dtype story)."""
    from apex_tpu import analyze
    from apex_tpu.serve import (
        InferenceEngine, Request, SamplingConfig, ServeConfig,
    )
    from apex_tpu.serve.decode import gpt_decode_step

    cfg, params, kv, cache = _serve_fixture(jnp.bfloat16)
    n = 3
    toks = jnp.zeros((n,), jnp.int32)
    lens = jnp.array([4, 2, 0], jnp.int32)
    active = jnp.array([True, True, False])
    bt = jnp.arange(n * 2, dtype=jnp.int32).reshape(n, 2)

    def decode(cache, toks, lens, active, bt):
        return gpt_decode_step(params, toks, lens, active, cache, bt,
                               cfg, kv, tp_axis=None, use_pallas=False)

    rep = analyze.check_donation(decode, cache, toks, lens, active, bt,
                                 donate_argnums=(0,))
    out = {f"decode_{k}": v for k, v in rep.as_record().items()}
    leak = analyze.dtype_leak_report(decode, cache, toks, lens, active,
                                     bt, policy=jnp.bfloat16)
    out["fp32_dots"] = leak.fp32_dots           # accepted: fp32 attention
    out["convert_churn_ops"] = leak.convert_churn_ops
    out["host_syncs"] = analyze.host_sync_report(
        decode, cache, toks, lens, active, bt).host_syncs

    eng = InferenceEngine(params, cfg, ServeConfig(
        num_slots=3, block_size=8, prefill_chunk=8,
        sampling=SamplingConfig()))
    eng.run([Request("warm1", [1, 2, 3], max_new_tokens=2),
             Request("warm2", list(range(12)), max_new_tokens=2)])
    try:
        with analyze.recompile_guard(eng.programs(), budget=0):
            eng.run([Request("a", [5, 6], max_new_tokens=3),
                     Request("b", list(range(17)), max_new_tokens=2)])
        out["serve_recompile_ok"] = True
    except analyze.RecompileError:
        out["serve_recompile_ok"] = False
    return out


def adapter_contracts() -> dict:
    """The serve LoRA contract (PR-16): the adapter pool rides every jit
    site donated-and-aliased (``analyze.adapters``), and swapping which
    adapters are resident is pure data — zero new compiles."""
    from apex_tpu import analyze
    from apex_tpu.serve import (
        InferenceEngine, Request, SamplingConfig, ServeConfig,
        make_adapter_weights,
    )

    cfg, params, _kv, _cache = _serve_fixture(jnp.float32)
    eng = InferenceEngine(params, cfg, ServeConfig(
        num_slots=3, block_size=8, prefill_chunk=8,
        sampling=SamplingConfig(), lora_rank=4, max_adapters=2))
    eng.load_adapter("t0", make_adapter_weights(
        cfg, 4, jax.random.PRNGKey(11)), scale=0.5)
    eng.run([Request("warm-base", [1, 2, 3], max_new_tokens=2),
             Request("warm-t0", list(range(12)), max_new_tokens=2,
                     adapter="t0")])
    out = analyze.adapter_contract_record(eng)
    try:
        # an adapter SWAP (unload + load into the freed slot) must not
        # retrace — residency is pool data, never a constant
        with analyze.recompile_guard(eng.programs(), budget=0):
            eng.unload_adapter("t0")
            eng.load_adapter("t1", make_adapter_weights(
                cfg, 4, jax.random.PRNGKey(12)), scale=0.5)
            eng.run([Request("a", [5, 6], max_new_tokens=3, adapter="t1"),
                     Request("b", list(range(17)), max_new_tokens=2)])
        out["adapter_recompile_ok"] = True
    except analyze.RecompileError:
        out["adapter_recompile_ok"] = False
    return out


def ring_exposed() -> dict:
    """The stage-14 gather-ring MLP recompiled, hidden/exposed split via
    ``analyze.exposed_report`` on the compiled HLO (all collective
    kinds — the generalized ``overlap_report``)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.analyze import exposed_report
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
    # ALL collective kinds (an exposed all-gather/reduce-scatter from a
    # future ring regression must show up in the banked record, not just
    # permutes); regress gates growth of exposed_bytes, not its absolute
    rep = exposed_report(compiled.as_text())
    return rep.as_record()


def lint_gate() -> dict:
    from apex_tpu.analyze import lint_paths, load_baseline, new_violations

    violations = lint_paths([os.path.join(ROOT, "apex_tpu")], root=ROOT)
    baseline = load_baseline(
        os.path.join(ROOT, "tests", "lint_baseline.json"))
    fresh = new_violations(violations, baseline)
    return {"lint_violations": len(fresh),
            "lint_total": len(violations),
            "lint_baselined": len(violations) - len(fresh)}


def main() -> int:
    import argparse

    from apex_tpu.monitor import json_record

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    name = "analyze_contracts"
    if not ON_TPU:
        name += "_CPU_FALLBACK"

    rec = {"metric": name, "backend": jax.default_backend(),
           "n_devices": len(jax.devices())}
    rec.update(gpt_step_contracts())
    rec.update(serve_contracts())
    rec.update(adapter_contracts())
    rec.update(lint_gate())
    if len(jax.devices()) >= 2:
        rec.update(ring_exposed())
    else:
        rec["ring_exposed"] = "needs a slice"
    rec["ok"] = bool(
        rec.get("gpt_donation_ok") and rec.get("decode_donation_ok")
        and rec.get("gpt_recompile_ok") and rec.get("serve_recompile_ok")
        and rec.get("adapter_donation_ok")
        and rec.get("adapter_recompile_ok")
        and rec.get("convert_churn_ops") == 0
        and rec.get("host_syncs") == 0 and rec.get("gpt_host_syncs") == 0
        and rec.get("lint_violations") == 0)
    line = json_record(**rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
