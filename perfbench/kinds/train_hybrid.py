"""A training cell of the hybrid decoder (linear-attention layers among full
ones): the program's one jitted step (``bench.train_step_fn``: forward,
backward, FusedAdam) driven with a fresh seeded batch every step.

The window, the feed and the comparison are ``kinds/train.py``'s
own, run from a private instance of that module in which the three functions
that know the model are this file's: ``_program`` (the config the program
takes, the benchmark's weights), ``first_steps`` and ``run_reference`` (the
leaves and the plain reference of ``reference_hybrid.py``). So both families
are timed and judged by the same code. ``readings`` is this file's: it adds
the control on the delta rule's own arithmetic and every record's loss gap.
"""

from __future__ import annotations

import gc
import importlib.util
import os
import time
from typing import Any, Dict

import reference_hybrid
import traffic
import weights_hybrid
from harness import Context

_spec = importlib.util.spec_from_file_location(
    "kind_train_for_hybrid", os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)
CHECK_STEPS = train.CHECK_STEPS


def _program(ctx: Context):
    """As ``kinds/train.py``'s: (step, new_state, feed, make_p0, rows, seq)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import bench
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.transformer.hybrid import HybridConfig

    cfg, mix, job = ctx.config, ctx.mix, ctx.config["train"]
    dp, tp = int(mix["mesh"]["dp"]), int(mix["mesh"]["tp"])
    if dp * tp != ctx.chips:
        raise ValueError(f"mesh dp x tp = {dp * tp}, the cell asks for {ctx.chips} chips")
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("the program's linear layers take as many key heads as value heads")
    if job["remat_policy"] != "full":
        raise ValueError(f"the hybrid model recomputes a whole layer or nothing "
                         f"(remat_policy 'full'), not {job['remat_policy']!r}")
    model = HybridConfig(
        vocab_held=cfg["vocab_size"], hidden=cfg["hidden_size"],
        ffn_hidden=cfg["intermediate_size"],
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        num_heads=cfg["num_attention_heads"], head_dim=cfg["assumed"]["head_dim"],
        linear_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        conv_width=cfg["linear_conv_kernel_dim"],
        allow_neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        norm_eps=float(cfg["rms_norm_eps"]), chunk=int(cfg["assumed"]["delta_rule_chunk"]),
        dtype=jnp.dtype(cfg["assumed"]["param_dtype"]), remat=bool(job["remat"]))
    mesh = build_mesh(tp=tp, pp=1, sp=1, dp=dp, devices=ctx.devices)
    step, opt = bench.train_step_fn(model, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), model.param_specs())
    data = NamedSharding(mesh, P("dp"))
    rows, seq = int(mix["rows_per_chip"]) * dp, int(mix["seq"])

    def make_p0():
        return weights_hybrid.make_params(cfg, ctx.seed, shardings)

    def feed(i: int):
        tok, tgt = traffic.train_batch(ctx.seed, i, rows, seq, cfg["vocab_size"])
        return jax.device_put(tok, data), jax.device_put(tgt, data)

    init_state = jax.jit(opt.init)

    def new_state():
        params = make_p0()
        return params, init_state(params)

    return step, new_state, feed, make_p0, rows, seq


def first_steps(ctx: Context, step, params, opt_state, feed, make_p0):
    """The job's first steps through the window's own call and feed, and what
    they left behind (as ``kinds/train.py``'s, by this model's leaves)."""
    import jax

    b1 = float(ctx.config["train"]["betas"][0])
    as_grad = lambda mu: jax.tree.map(lambda m: m / (1.0 - b1), mu)
    grad_norms = jax.jit(lambda mu: reference_hybrid.leaf_norms(as_grad(mu)))
    grad_samples = jax.jit(lambda mu: reference_hybrid.leaf_samples(as_grad(mu)))
    seen: Dict[str, Any] = {"losses": []}
    for i in range(1, CHECK_STEPS + 1):
        params, opt_state, loss = step(params, opt_state, *feed(i))
        seen["losses"].append(float(loss))
        if i == 1:      # Adam's first moment after one step is (1 - b1)·g
            seen["grad_norms"] = jax.device_get(grad_norms(opt_state.mu))
            seen["grad_samples"] = jax.device_get(grad_samples(opt_state.mu))
        if i == 2:      # the change the reference follows (two steps)
            seen["change_norms"] = jax.device_get(
                reference_hybrid._jit_change_norms(params, make_p0()))
    return params, opt_state, seen


def run_reference(ctx: Context, make_p0, n_rows: int, seq: int, **kw) -> Dict:
    import jax.numpy as jnp

    batches = []
    for i in range(1, CHECK_STEPS + 1):
        tok, tgt = traffic.train_batch(ctx.seed, i, n_rows, seq, ctx.config["vocab_size"])
        batches.append((jnp.asarray(tok), jnp.asarray(tgt)))
    return reference_hybrid.train_reference(
        make_p0, batches, ctx.config["train"], reference_hybrid.model_shape(ctx.config),
        int(ctx.mix["check"]["reference_rows_per_block"]), **kw)


def readings(ctx: Context, seeds, control_seeds, fault_seeds) -> list:
    """For the limits, as ``kinds/train.py``'s: what the comparison reads on
    each of ``seeds`` and, on the first few, with a control or the half-batch
    fault put in the program's place. Beside int8's and fp8's grids there is
    the control on the delta rule's own arithmetic (the recurrence on
    bfloat16's grid, every matrix product float32), and each record carries
    its ``loss_gap``, which the comparison does not read."""
    step, new_state, feed, make_p0, rows, seq = _program(ctx)
    loose = {k: float("inf") for k in ("grad_norm_gap", "update_norm_gap",
                                       "grad_error", "grad_error_worst_leaf")}

    def against(ref, got):
        rec = {n["name"]: n for n in compare(got, ref, loose)}
        rec["loss_gap"] = max(abs(a - b) for a, b in zip(got["losses"], ref["losses"]))
        return rec

    out = []
    for seed in seeds:
        ctx.seed = int(seed)
        params, opt_state = new_state()
        params, opt_state, seen = first_steps(ctx, step, params, opt_state, feed, make_p0)
        del params, opt_state
        gc.collect()
        t = time.perf_counter()
        ref = run_reference(ctx, make_p0, rows, seq)
        rec = {"seed": int(seed), "reference_s": time.perf_counter() - t,
               "program": against(ref, seen),
               "losses": {"program": seen["losses"], "reference": ref["losses"]}}
        if seed in control_seeds:
            for grid in ("int8", "fp8", reference_hybrid.CORE_BF16):
                rec["control_" + grid] = against(
                    ref, run_reference(ctx, make_p0, rows, seq, quant=grid))
        if seed in fault_seeds:
            rec["fault_half_batch"] = against(
                ref, run_reference(ctx, make_p0, rows, seq, rows=slice(0, rows // 2)))
        ctx.info(**rec)
        out.append(rec)
    return out


train._program, train.first_steps, train.run_reference = _program, first_steps, run_reference
run, compare = train.run, train.compare
