"""A training cell of the block-diffusion decoder (``transformer/sdar.py``:
grouped-query rotary attention under the block-diffusion mask, routed experts
without drops, this chip's share of the experts): the program's one jitted
step (``bench.train_step_fn``: forward, backward, FusedAdam) driven with a
fresh seeded batch every step, the batch being data ids and the noise
(``reference_sdar.train_batch``: the input pipeline draws each block's rate
and which tokens it masks; the step applies them under its scope ``noise``).

The window, the feed and the comparison are ``kinds/train.py``'s own, run
from a private instance of that module in which the three functions that
know the model are this file's (``_program``, ``first_steps``,
``run_reference``), as ``kinds/train_hybrid.py`` does. ``train_tokens_per_s``
counts DATA tokens (``rows x seq``); the chip computes twice as many
positions. The program's step hands back, fourth, the pairs each held expert
took in each layer (``train_step_fn``, a model with ``loss_and_counters``);
:func:`_program` wraps the step so that the window sees the three results it
expects and keeps the fourth, and :func:`run` turns the window's own steps'
loads into the counters (``routing_facts``), prints them and hands them to
the readers: no pass of their own, no other weights or batch than the timed.
"""

from __future__ import annotations

import gc
import importlib.util
import os
import time
from typing import Any, Dict

import reference_hybrid
import reference_sdar
import weights_sdar
from harness import Context

_spec = importlib.util.spec_from_file_location(
    "kind_train_for_sdar", os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)
CHECK_STEPS = train.CHECK_STEPS


def _model(ctx: Context):
    import jax.numpy as jnp

    from apex_tpu.transformer.sdar import SDARConfig

    cfg, job = ctx.config, ctx.config["train"]
    if job["remat_policy"] not in ("layer", "sublayer"):
        raise ValueError(f"the block-diffusion model replays a layer or a sublayer "
                         f"(remat_policy 'layer' or 'sublayer'), not {job['remat_policy']!r}")
    return SDARConfig(
        vocab_held=cfg["vocab_size"], hidden=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        num_experts=cfg["reduced_from"]["num_experts"],
        experts_held=tuple(cfg["experts_held"]), top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"], block=cfg["assumed"]["block_length"],
        mask_id=cfg["assumed"]["mask_id"], norm_eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.dtype(cfg["assumed"]["param_dtype"]),
        remat=job["remat_policy"] if job["remat"] else "none")


def _batch(ctx: Context, step: int, rows: int, seq: int):
    a = ctx.config["assumed"]
    return reference_sdar.train_batch(ctx.seed, step, rows, seq, a["mask_id"], a["block_length"])


_loads: list = []       # the steps' fourth result since the last _program(), in order


def _program(ctx: Context):
    """As ``kinds/train.py``'s: (step, new_state, feed, make_p0, rows, seq).
    ``step`` is the program's with its fourth result put by (``_loads``: a
    device array of a few hundred bytes a step, read after the window)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import bench
    from apex_tpu.parallel.mesh import build_mesh

    cfg, mix = ctx.config, ctx.mix
    dp, tp = int(mix["mesh"]["dp"]), int(mix["mesh"]["tp"])
    if dp * tp != ctx.chips:
        raise ValueError(f"mesh dp x tp = {dp * tp}, the cell asks for {ctx.chips} chips")
    model = _model(ctx)
    mesh = build_mesh(tp=tp, pp=1, sp=1, dp=dp, devices=ctx.devices)
    program, opt = bench.train_step_fn(model, mesh)
    _loads.clear()

    def step(params, opt_state, tok, noise):
        params, opt_state, loss, counters = program(params, opt_state, tok, noise)
        _loads.append(counters["expert_loads"])
        return params, opt_state, loss

    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), model.param_specs())
    data = NamedSharding(mesh, P("dp"))
    rows, seq = int(mix["rows_per_chip"]) * dp, int(mix["seq"])

    def make_p0():
        return weights_sdar.make_params(cfg, ctx.seed, shardings)

    def feed(i: int):
        tok, noise = _batch(ctx, i, rows, seq)
        return jax.device_put(tok, data), jax.device_put(noise, data)

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

    batches = [tuple(map(jnp.asarray, _batch(ctx, i, n_rows, seq)))
               for i in range(1, CHECK_STEPS + 1)]
    return reference_sdar.train_reference(
        make_p0, batches, ctx.config["train"], reference_sdar.model_shape(ctx.config),
        int(ctx.mix["check"]["reference_rows_per_block"]), **kw)


def routing_counters(ctx: Context, loads, first_step: int) -> Dict[str, float]:
    """The counters of the steps whose ``loads`` these are (an array (dp,
    layers, experts held) a step, the first of them global step
    ``first_step``): ``pairs_held`` and ``pairs_uniform`` a step (summed over
    the layers and the chips), the mean over the steps, with the least and the
    most any step held; ``max_load_over_mean``, ``tiled_rows`` and
    ``padding_rows`` a layer, the mean; ``tiled_rows_most`` and ``passes_run``,
    the most any layer of any step took; the batches' ``masked_positions`` a
    step."""
    import numpy as np

    from apex_tpu.transformer.moe import routing_facts

    model = _model(ctx)
    dp, rows, seq = int(ctx.mix["mesh"]["dp"]), int(ctx.mix["rows_per_chip"]), int(ctx.mix["seq"])
    steps = [[routing_facts(layer, rows * 2 * seq, model.routed) for chip in step for layer in chip]
             for step in np.asarray(loads)]
    held = [sum(f["pairs_held"] for f in step) for step in steps]
    layers = [f for step in steps for f in step]
    mean = lambda key: float(np.mean([f[key] for f in layers]))
    masked = [int((_batch(ctx, first_step + i, rows * dp, seq)[1] & 1).sum())
              for i in range(len(steps))]
    return {"pairs_held": float(np.mean(held)), "pairs_held_least": float(min(held)),
            "pairs_held_most": float(max(held)),
            "pairs_uniform": float(sum(f["pairs_uniform"] for f in steps[0])),
            "max_load_over_mean": mean("max_load_over_mean"),
            "tiled_rows": mean("tiled_rows"),
            "tiled_rows_most": float(max(f["tiled_rows"] for f in layers)),
            "padding_rows": mean("padding_rows"),
            "passes_run": float(max(f["passes_run"] for f in layers)),
            "masked_positions": float(np.mean(masked)), "steps_counted": len(steps)}


def run(ctx: Context, limits) -> Dict[str, Any]:
    import jax

    out = train.run(ctx, limits)
    # the window's own steps: those after the job's first CHECK_STEPS, as
    # many as the window finished (a step dispatched and not waited for is
    # not among ``attempted``)
    window = jax.device_get(_loads[CHECK_STEPS:CHECK_STEPS + out["attempted"]])
    counters = routing_counters(ctx, window, CHECK_STEPS + 1)
    ctx.info(phase="routing counted", **counters)
    out["facts"]["counters"] = counters
    return out


def readings(ctx: Context, seeds, control_seeds, fault_seeds) -> list:
    """For the limits, as ``kinds/train.py``'s: what the comparison reads on
    each of ``seeds`` and, on ``control_seeds``, with the reference on int8's
    and fp8's grid in the program's place, and on ``fault_seeds`` with a
    fault planted there: half of the batch and ``reference_sdar.FAULTS`` (no
    routed experts, the router's weights not renormalised, a causal mask, no
    rotation, the router's product in bfloat16). Each record carries
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
            for grid in ("int8", "fp8"):
                rec["control_" + grid] = against(
                    ref, run_reference(ctx, make_p0, rows, seq, quant=grid))
        if seed in fault_seeds:
            rec["fault_half_batch"] = against(
                ref, run_reference(ctx, make_p0, rows, seq, rows=slice(0, rows // 2)))
            for fault in reference_sdar.FAULTS:
                rec["fault_" + fault] = against(
                    ref, run_reference(ctx, make_p0, rows, seq, fault=fault))
        ctx.info(**rec)
        out.append(rec)
    return out


train._program, train.first_steps, train.run_reference = _program, first_steps, run_reference
compare = train.compare
