"""Train-step timing harness — prints ONE JSON line with the headline metric.

Headline: flagship GPT-2 124M-class bf16 **training step** (fwd + bwd +
FusedAdam) tokens/s on one chip. ``vs_baseline`` is measured MFU divided by
the driver-assigned 0.70 MFU target (BASELINE.json: the reference publishes
no numbers — see BASELINE.md — so the target ratio is the honest comparator).

Run: ``python bench.py`` on a machine with a TPU. It measures, so it fails
when the default backend is not ``tpu``; ``build_train_step`` and
``flagship_config`` stay importable anywhere (the lowering preflight and
``chip_smoke.py`` build the same step through them).

Timing protocol: the steps are dispatched asynchronously and the clock
stops only after a scalar host-read of the LAST step's loss — the read
forces the whole donated-params chain, so the window ends when the device
does.
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

BATCH, SEQ = 32, 1024
STEPS = 20


def flagship_config(seq: int = SEQ, **overrides):
    """The benchmark model (GPT-2 124M-class). Shared with
    benchmarks/check_mfu_accounting.py so the cross-check always validates
    the same model bench.py times."""
    from apex_tpu.transformer.testing import GPTConfig

    kw = dict(vocab_size=50304, max_seq=seq, hidden=768, num_layers=12,
              num_heads=12, dtype=jnp.bfloat16)
    kw.update(overrides)
    return GPTConfig(**kw)


def train_step_fn(cfg, mesh):
    """The jitted fwd+bwd+FusedAdam step of ``cfg`` over ``mesh`` (params
    and optimizer state donated), plus the optimizer it steps.

    ``cfg`` is any model's config that gives ``param_specs()`` (a
    ``PartitionSpec`` a leaf), ``init_params(rng)`` and ``loss(params, tokens,
    targets)`` (the local mean loss inside ``shard_map``): ``GPTConfig`` and
    ``transformer.hybrid.HybridConfig`` both do, and both run this one
    step."""
    from apex_tpu.monitor.trace import register_program, span
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.pipeline_parallel.schedules.common import (
        replicate_loss,
    )

    specs = cfg.param_specs()
    opt = FusedAdam(lr=1e-4)

    def loss_fn(p, tok, tgt):
        def body(p, tok, tgt):
            return replicate_loss(cfg.loss(p, tok, tgt), mesh,
                                  masked_axis=None)

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(specs, P("dp"), P("dp")),
                             out_specs=P())(p, tok, tgt)

    def update(grads, opt_state, params):
        # the optimizer steps each device's own shards inside the mesh
        # program: jit's partitioner cannot split FusedAdam's Pallas tail
        state_specs = opt_state._replace(count=P(), mu=specs, nu=specs)
        return jax.shard_map(opt.update, mesh=mesh,
                             in_specs=(specs, state_specs, specs),
                             out_specs=(specs, state_specs))(
                                 grads, opt_state, params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tok, tgt):
        loss, grads = jax.value_and_grad(loss_fn)(params, tok, tgt)
        with span("opt"):
            updates, opt_state = update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    def lower(rows: int, seq: int):
        """The step lowered at the shapes and shardings a job hands it,
        from shapes alone: for ``monitor.trace.scope_table``."""
        return train_step.lower(
            *abstract_train_args(cfg, opt, mesh, rows, seq))

    register_program("jit_train_step", lower)
    return train_step, opt


def abstract_train_args(cfg, opt, mesh, rows: int, seq: int):
    """``(params, opt_state, tok, tgt)`` as ``ShapeDtypeStruct``s placed as
    :func:`build_train_step` places the real ones: no array is made."""
    def placed(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    specs = cfg.param_specs()
    params = jax.tree.map(placed, jax.eval_shape(
        lambda: cfg.init_params(jax.random.PRNGKey(0))), specs)
    state = jax.eval_shape(opt.init, params)
    state = state._replace(count=placed(state.count, P()),
                           mu=jax.tree.map(placed, state.mu, specs),
                           nu=jax.tree.map(placed, state.nu, specs))
    tok = placed(jax.ShapeDtypeStruct((rows, seq), jnp.int32), P("dp"))
    return params, state, tok, tok


_STEP_CACHE: dict = {}


def build_train_step(cfg, batch: int, seq: int, *, dp: int = 1, tp: int = 1):
    """Jitted fwd+bwd+FusedAdam step for ``cfg`` on a dp x tp mesh over the
    first ``dp * tp`` devices JAX reports (one chip when both are 1).
    Returns ``(train_step, params, opt_state, tok, tgt)`` with
    every input placed by the mesh's shardings — params per
    ``gpt_param_specs``, the batch split over ``dp`` — so no device is left
    empty by default placement. The jitted step is cached per
    (cfg, batch, seq, mesh) so re-measuring the auto-tuner's winning config
    reuses its compilation instead of paying another compile."""
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.transformer.testing import gpt_param_specs, init_gpt_params

    key = (cfg, batch, seq, dp, tp)
    if key not in _STEP_CACHE:
        mesh = build_mesh(tp=tp, pp=1, sp=1, dp=dp,
                          devices=jax.devices()[:dp * tp])
        train_step, opt = train_step_fn(cfg, mesh)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 gpt_param_specs(cfg))
        data = NamedSharding(mesh, P("dp"))

        def make_inputs():
            p = jax.device_put(init_gpt_params(jax.random.PRNGKey(0), cfg),
                               shardings)
            k = jax.random.PRNGKey(1)
            tok = jax.random.randint(k, (batch, seq), 0, cfg.vocab_size)
            return (p, opt.init(p), jax.device_put(tok, data),
                    jax.device_put(jnp.roll(tok, -1, axis=1), data))

        _STEP_CACHE[key] = (train_step, make_inputs)
    train_step, make_inputs = _STEP_CACHE[key]
    return (train_step, *make_inputs())


def _measure(remat: bool, remat_policy: str, batch: int, seq: int,
             steps: int, warm_steps: int = 2, unroll: int = 1,
             **cfg_overrides):
    """(tokens/s, n_params, error) of the flagship train step under one
    config; tokens/s is None when the config does not FIT the chip (device
    out-of-memory, e.g. remat off at the full batch) — every other failure
    raises: a kernel that will not compile is a bug, not an unusable config.
    Fresh params each call — donation consumes the previous buffers.
    ``cfg_overrides`` go straight to flagship_config (fused_loss,
    ln_pallas, ...) so A/B sweeps share this one fence/timing protocol."""
    cfg = flagship_config(seq, remat=remat, remat_policy=remat_policy,
                          scan_unroll=unroll, **cfg_overrides)
    train_step, params, opt_state, tok, tgt = build_train_step(
        cfg, batch, seq)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    try:
        # warmup (compile); the float() host-read is the execution fence
        for _ in range(warm_steps):
            params, opt_state, loss = train_step(params, opt_state, tok, tgt)
        float(loss)

        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = train_step(params, opt_state, tok, tgt)
        float(loss)  # forces the whole donated-params chain
        dt = (time.perf_counter() - t0) / steps
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return None, n_params, f"{type(e).__name__}: {str(e)[:200]}"
    return batch * seq / dt, n_params, None


def main() -> None:
    import argparse
    import sys

    from apex_tpu.utils.platform import device_peaks, enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the auto-tune sweep: time the guaranteed-fit "
                         "remat-full config with few steps")
    ap.add_argument("--out", default=None,
                    help="also persist the JSON line to this path")
    args = ap.parse_args()

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py measures the chip; the default backend is "
            f"{jax.default_backend()!r}. Run it on a machine with a TPU.")
    enable_compile_cache()
    dev = jax.devices()[0]
    peak = device_peaks(dev.device_kind).bf16_flops_per_s
    batch, seq, steps = BATCH, SEQ, STEPS

    if args.quick:
        # guaranteed-fit remat-full at the full batch: one compile, short
        # timed run
        candidates = [(batch, True, "full", 1, True)]
        steps = min(steps, 8)
    else:
        # Auto-tune (batch, remat, scan_unroll) jointly: no-remat and
        # selective ("dots") avoid recompute flops the MFU accounting does
        # not credit but may not fit HBM at the full batch; a smaller batch
        # with remat OFF can beat a bigger batch paying recompute (tokens/s
        # is batch-fair); unrolling the layer scan gives XLA straight-line
        # HLO to fuse across layer boundaries at ~12x the layer-compile
        # cost. Measure each briefly and keep the fastest. Ordered
        # most-promising-first so the time budget (below) degrades
        # gracefully; a config that does not fit HBM is skipped, so probing
        # above the estimated fit only costs its compile.
        # the trailing bool is GPTConfig.fused_loss: the Pallas fused
        # LM-head+CE avoids the 3.2 GB logits but its matmul must keep up
        # with XLA's near-peak native head matmul — the sweep answers it
        # empirically rather than assuming the kernel wins
        candidates = [(batch, False, "full", 1, True),
                      (batch, False, "full", 1, False),
                      (batch * 2, False, "full", 1, True),
                      (batch, True, "dots_attn", 1, True),
                      (batch, True, "dots", 1, True),
                      (batch, False, "full", 12, True),
                      (batch * 2, True, "dots_attn", 1, True),
                      (batch, True, "dots", 12, True),
                      (batch, True, "full", 1, False),
                      (batch * 2, True, "dots", 1, True),
                      (batch, True, "full", 1, True),
                      (batch // 2, False, "full", 1, True)]

    def emit(tokens_per_s, batch, remat, policy, unroll, fused,
             provisional):
        from apex_tpu.monitor import gpt_analytic_flops_per_token, json_record

        cfg = flagship_config(seq)
        # the analytic constant is shared with monitor.report so
        # check_mfu_accounting.py always validates the number divided here
        fpt = gpt_analytic_flops_per_token(
            n_params, cfg.num_layers, cfg.hidden, seq)
        mfu = tokens_per_s * fpt / peak
        rec = {
            "metric": "gpt2_124m_bf16_train_tokens_per_sec_chip",
            "value": round(tokens_per_s, 1),
            "unit": "tokens/s",
            "vs_baseline": round(mfu / 0.70, 4),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "tuned_config": {"batch": batch, "remat": remat,
                             "policy": policy, "scan_unroll": unroll,
                             "fused": fused},
        }
        if provisional:
            rec["provisional"] = True  # best-so-far from the short sweep
        line = json_record(**rec)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return line

    # Candidate-phase time budget: the caller may enforce its own timeout —
    # stop trying new candidates past the budget and finalize with the best
    # so far, so the ONE-JSON-line contract survives any cap >= budget +
    # ~3 min.
    budget_s = float(os.environ.get("APEX_TPU_BENCH_BUDGET_S", "600"))
    t_start = time.perf_counter()

    best, best_tps, n_params, last_err = None, 0.0, 0, None
    for cand_batch, remat, policy, unroll, fused in candidates:
        if best is not None and time.perf_counter() - t_start > budget_s:
            print(f"# sweep budget ({budget_s:.0f}s) reached, finalizing "
                  f"with best so far", file=sys.stderr, flush=True)
            break
        tps, n_params, err = _measure(remat, policy, cand_batch, seq,
                                      steps=3, unroll=unroll,
                                      fused_loss=fused)
        # per-candidate line on stderr: one run yields the whole tuning
        # picture even if a later candidate is killed by the caller's cap
        print(f"# candidate batch={cand_batch} remat={remat}/{policy} "
              f"unroll={unroll} fused={fused}: "
              + (f"{tps:.1f} tokens/s" if tps is not None
                 else f"does not fit: {err}"),
              file=sys.stderr, flush=True)
        if err is not None:
            last_err = (f"batch={cand_batch} remat={remat}/{policy} "
                        f"unroll={unroll} fused={fused}: {err}")
        if tps is not None and tps > best_tps:
            best, best_tps = (cand_batch, remat, policy, unroll, fused), tps
            # bank the best-so-far to --out: a timeout mid-sweep still
            # leaves a real number
            emit(best_tps, cand_batch, remat, policy, unroll, fused,
                 provisional=True)

    if best is None:
        raise RuntimeError(f"no bench config fit the chip; last error: "
                           f"{last_err}")
    batch, remat, policy, unroll, fused = best
    tokens_per_s, n_params, err = _measure(remat, policy, batch, seq, steps,
                                           unroll=unroll, fused_loss=fused)
    if tokens_per_s is None:
        raise RuntimeError(f"selected config {best} failed the timed run: "
                           f"{err}")
    # standard MFU accounting: 6N per token (fwd+bwd) + causal attention
    # 6*L*hidden*seq per token; remat recompute is NOT credited. Cross-
    # checked against XLA HLO cost analysis by check_mfu_accounting.py.
    print(emit(tokens_per_s, batch, remat, policy, unroll, fused,
               provisional=False))


if __name__ == "__main__":
    main()
