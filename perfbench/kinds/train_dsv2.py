"""A training cell of DeepSeek-V2's decoder (``transformer/deepseek.py``:
latent attention with YaRN positions, a leading dense layer, routed experts
without drops beside a shared one, the balance loss, this chip's share of
the routed experts): the program's one jitted step (``bench.train_step_fn``:
forward, backward, FusedAdam) driven with a fresh seeded batch every step
(``traffic.train_batch``: ids uniform over the rows of the vocabulary held,
targets the next token).

The window, the feed and the comparison are ``kinds/train.py``'s own, run
from a private instance of that module in which the three functions that
know the model are this file's (``_program``, ``first_steps``,
``run_reference``), as ``kinds/train_sdar.py`` does, and the comparison
reads one number more (:func:`compare`: the losses' gap, which alone tells a
program that left the balance loss out). The program's step hands
back, fourth, what each expert layer counted (``train_step_fn``, a model with
``loss_and_counters``): the pairs each held expert took, the positions by
places held, and the layer's balance loss. :func:`_program` wraps the step so
that the window sees the three results it expects and keeps the fourth, and
:func:`run` turns the window's own steps' counts into the counters
(``routing_facts``: ``monitor.trace.ROUTING_COUNTERS`` and
``ROUTING_COUNTERS_MORE``), prints them and hands them to the readers: no
pass of their own, no other weights or batch than the timed.
"""

from __future__ import annotations

import gc
import importlib.util
import os
import time
from typing import Any, Dict

import reference_dsv2
import reference_hybrid
import traffic
import weights_dsv2
from harness import Context, compared

_spec = importlib.util.spec_from_file_location(
    "kind_train_for_dsv2", os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)
CHECK_STEPS = train.CHECK_STEPS


def _model(ctx: Context):
    import jax.numpy as jnp

    from apex_tpu.ops.rope import RopeScaling
    from apex_tpu.transformer.deepseek import DeepSeekConfig

    cfg, job = ctx.config, ctx.config["train"]
    if job["remat_policy"] != "sublayer":
        raise ValueError(f"the latent-attention model replays by sublayer "
                         f"(remat_policy 'sublayer'), not {job['remat_policy']!r}")
    if cfg["q_lora_rank"] is not None or cfg["scoring_func"] != "softmax" or cfg["n_group"] != 1:
        raise ValueError("the program has no query latent, no other score than softmax and "
                         "one group of experts")
    return DeepSeekConfig(
        vocab_held=cfg["vocab_size"], hidden=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], first_k_dense=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"], rope_theta=float(cfg["rope_theta"]),
        rope_scaling=RopeScaling.from_config(cfg["rope_scaling"]),
        dense_hidden=cfg["intermediate_size"],
        num_experts=cfg["reduced_from"]["n_routed_experts"],
        experts_held=tuple(cfg["experts_held"]), top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        aux_loss_alpha=float(cfg["assumed"]["aux_loss_alpha"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=jnp.dtype(cfg["assumed"]["param_dtype"]),
        remat=job["remat_policy"] if job["remat"] else "none")


def _batch(ctx: Context, step: int, rows: int, seq: int):
    return traffic.train_batch(ctx.seed, step, rows, seq, ctx.config["vocab_size"])


_counted: list = []     # the steps' fourth result since the last _program(), in order


def _program(ctx: Context):
    """As ``kinds/train.py``'s: (step, new_state, feed, make_p0, rows, seq).
    ``step`` is the program's with its fourth result put by (``_counted``:
    device arrays of a few hundred bytes a step, read after the window)."""
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
    _counted.clear()

    def step(params, opt_state, tok, tgt):
        params, opt_state, loss, counters = program(params, opt_state, tok, tgt)
        _counted.append(counters)
        return params, opt_state, loss

    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), model.param_specs())
    data = NamedSharding(mesh, P("dp"))
    rows, seq = int(mix["rows_per_chip"]) * dp, int(mix["seq"])

    def make_p0():
        return weights_dsv2.make_params(cfg, ctx.seed, shardings)

    def feed(i: int):
        tok, tgt = _batch(ctx, i, rows, seq)
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

    batches = [tuple(map(jnp.asarray, _batch(ctx, i, n_rows, seq)))
               for i in range(1, CHECK_STEPS + 1)]
    return reference_dsv2.train_reference(
        make_p0, batches, ctx.config["train"], reference_dsv2.model_shape(ctx.config),
        int(ctx.mix["check"]["reference_rows_per_block"]), **kw)


def routing_counters(ctx: Context, counted) -> Dict[str, float]:
    """The counters of the steps whose fourth results ``counted`` holds
    (each ``expert_loads`` (dp, expert layers, experts held), ``held_places``
    (dp, expert layers, top_k + 1), ``aux_loss`` (dp, expert layers, 1)):
    ``pairs_held`` and ``pairs_uniform`` a step (summed over the layers and
    the chips), the mean over the steps with the least and the most any step
    held; ``max_load_over_mean``, ``tiled_rows``, ``padding_rows``,
    ``rows_gathered`` and ``rows_gathered_over_held`` a layer, the mean;
    ``tiled_rows_most`` and ``passes_run``, the most any layer of any step
    took; ``aux_loss``, the step's balance loss (summed over its layers, the
    chips' mean), the mean over the steps; ``masked_positions`` nought (no
    position of this model's batch is masked)."""
    import numpy as np

    from apex_tpu.transformer.moe import routing_facts

    model = _model(ctx)
    tokens = int(ctx.mix["rows_per_chip"]) * int(ctx.mix["seq"])
    steps = [[routing_facts(loads, tokens, model.routed, places)
              for chip_loads, chip_places in zip(c["expert_loads"], c["held_places"])
              for loads, places in zip(chip_loads, chip_places)] for c in counted]
    held = [sum(f["pairs_held"] for f in step) for step in steps]
    layers = [f for step in steps for f in step]
    mean = lambda key: float(np.mean([f[key] for f in layers]))
    aux = [float(np.asarray(c["aux_loss"]).sum(axis=(1, 2)).mean()) for c in counted]
    return {"pairs_held": float(np.mean(held)), "pairs_held_least": float(min(held)),
            "pairs_held_most": float(max(held)),
            "pairs_uniform": float(sum(f["pairs_uniform"] for f in steps[0])),
            "max_load_over_mean": mean("max_load_over_mean"),
            "tiled_rows": mean("tiled_rows"),
            "tiled_rows_most": float(max(f["tiled_rows"] for f in layers)),
            "padding_rows": mean("padding_rows"),
            "passes_run": float(max(f["passes_run"] for f in layers)),
            "masked_positions": 0.0,
            "rows_gathered": mean("rows_gathered"),
            "rows_gathered_over_held": mean("rows_gathered_over_held"),
            "aux_loss": float(np.mean(aux)), "steps_counted": len(steps)}


def run(ctx: Context, limits) -> Dict[str, Any]:
    import jax

    out = train.run(ctx, limits)
    # the window's own steps: those after the job's first CHECK_STEPS, as
    # many as the window finished (a step dispatched and not waited for is
    # not among ``attempted``)
    window = jax.device_get(_counted[CHECK_STEPS:CHECK_STEPS + out["attempted"]])
    counters = routing_counters(ctx, window)
    ctx.info(phase="routing counted", **counters)
    out["facts"]["counters"] = counters
    return out


def readings(ctx: Context, seeds, control_seeds, fault_seeds) -> list:
    """For the limits, as ``kinds/train.py``'s: what the comparison reads on
    each of ``seeds`` and, on ``control_seeds``, with the reference on int8's
    and fp8's grid in the program's place, and on ``fault_seeds`` with a
    fault planted there: half of the batch (the one row's first half) and
    ``reference_dsv2.FAULTS``."""
    step, new_state, feed, make_p0, rows, seq = _program(ctx)
    loose = {k: float("inf") for k in ("grad_norm_gap", "update_norm_gap", "grad_error",
                                       "grad_error_worst_leaf", "loss_gap")}

    def against(ref, got):
        return {n["name"]: n for n in compare(got, ref, loose)}

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
                ref, run_reference(ctx, make_p0, rows, seq, cols=slice(0, seq // 2)))
            for fault in reference_dsv2.FAULTS:
                rec["fault_" + fault] = against(
                    ref, run_reference(ctx, make_p0, rows, seq, fault=fault))
        ctx.info(**rec)
        out.append(rec)
    return out


_compare_leaves = train.compare


def compare(seen, ref, limits) -> list:
    """``kinds/train.py``'s four numbers of the leaves, and the largest gap
    of the steps' losses. The balance loss is a thousandth of the loss
    (alpha 0.001 an expert layer) and its gradient reaches the routers alone,
    under what their own flips between bfloat16 and float32 move: a program
    that left it out reads inside every limit on the leaves, and 0.004 under
    the reference's loss, seventy times what a sound run's losses differ by."""
    rows = _compare_leaves(seen, ref, limits)
    gap = max(abs(a - b) for a, b in zip(seen["losses"], ref["losses"]))
    rows.append(compared("loss_gap", gap, limits["loss_gap"]))
    return rows


train._program, train.first_steps, train.run_reference = _program, first_steps, run_reference
train.compare = compare
