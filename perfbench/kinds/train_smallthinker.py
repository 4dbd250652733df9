"""A training cell of SmallThinker's decoder (``transformer/smallthinker.py``:
full causal layers with no positional encoding among windowed rotary ones,
grouped K/V heads, a router that reads the layer's normed input before
attention, ReLU-gated routed experts without drops, this chip's share of
them): the program's one jitted step (``bench.train_step_fn``: forward,
backward, FusedAdam) driven with a fresh seeded batch every step
(``traffic.train_batch``: ids uniform over the rows of the vocabulary held,
targets the next token).

The window and the feed are ``kinds/train.py``'s own, run from a private
instance of that module in which the functions that know the model are this
file's (``_program``, ``first_steps``, ``run_reference``, ``compare``), as
``kinds/train_laguna.py`` does; the comparison reads that module's four
numbers of the leaves and the largest gap of the steps' losses. The
program's step hands back, fourth, what each layer's routed experts
counted: :func:`run` turns the window's own steps' counts into the counters
(``routing counted``: ``monitor.trace.ROUTING_COUNTERS`` and the two gather
counters of ``ROUTING_COUNTERS_MORE``; the model has no balance loss) and
prints, from the program's own tile lists (``ops.attention._listed_tiles``),
what the flash kernels grid on each layer type (``attention planned``).
"""

from __future__ import annotations

import gc
import importlib.util
import os
import time
from typing import Any, Dict

import reference_hybrid
import reference_smallthinker
import traffic
import weights_smallthinker
from harness import Context, compared

_spec = importlib.util.spec_from_file_location(
    "kind_train_for_smallthinker",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)
CHECK_STEPS = train.CHECK_STEPS


def _model(ctx: Context):
    """The program's ``SmallThinkerConfig`` from the configuration's file."""
    import jax.numpy as jnp

    from apex_tpu.transformer.smallthinker import SmallThinkerConfig

    cfg, job = ctx.config, ctx.config["train"]
    if not job["remat"] or job["remat_policy"] != "sublayer":
        raise ValueError(f"SmallThinker's model replays by sublayer (remat_policy 'sublayer'), "
                         f"not {job['remat_policy']!r}")
    if cfg["rope_layout"] != cfg["sliding_window_layout"]:
        raise ValueError("the program rotates the window layers and no others: rope_layout "
                         "must equal sliding_window_layout")
    if (cfg["rope_scaling"] is not None or not cfg["moe_primary_router_apply_softmax"]
            or not cfg["norm_topk_prob"]):
        raise ValueError("the program has plain rotary frequencies and a softmax router "
                         "renormalised over the chosen")
    return SmallThinkerConfig(
        vocab_held=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layer_types=weights_smallthinker.layer_types(cfg),
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window_size"],
        rope_theta=float(cfg["rope_theta"]),
        num_experts=weights_smallthinker.router_width(cfg),
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["moe_num_active_primary_experts"],
        expert_hidden=cfg["moe_ffn_hidden_size"],
        norm_eps=float(cfg["rms_norm_eps"]), dtype=jnp.dtype(cfg["assumed"]["param_dtype"]))


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
        return weights_smallthinker.make_params(cfg, ctx.seed, shardings)

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
    return reference_smallthinker.train_reference(
        make_p0, batches, ctx.config["train"], reference_smallthinker.model_shape(ctx.config),
        int(ctx.mix["check"]["reference_rows_per_block"]), **kw)


def routing_counters(ctx: Context, counted) -> Dict[str, float]:
    """The counters of the steps whose fourth results ``counted`` holds
    (each ``expert_loads`` (dp, layers, experts held) and
    ``held_places`` (dp, layers, top_k + 1)): ``pairs_held`` and
    ``pairs_uniform`` a step (summed over the layers and the chips), the mean
    over the steps with the least and the most any step held;
    ``max_load_over_mean``, ``tiled_rows``, ``padding_rows``,
    ``rows_gathered`` and ``rows_gathered_over_held`` a layer, the mean;
    ``tiled_rows_most`` and ``passes_run``, the most any layer of any step
    took; ``masked_positions`` nought (no position of this model's batch is
    masked)."""
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
            "steps_counted": len(steps)}


def attention_planned(ctx: Context) -> Dict[str, float]:
    """What the flash kernels grid a head on each layer type, from the
    program's own tile lists: live tiles (those the grid visits), the masked
    among them (straddling an edge of the mask), the whole rectangle's tiles
    and the tile's side."""
    from apex_tpu.ops import attention as A
    from apex_tpu.transformer.smallthinker import FULL, WINDOW, WINDOW_BLOCK

    model, seq = _model(ctx), int(ctx.mix["seq"])
    out = {}
    for kind, structure, block in (
            (WINDOW, A.sliding_window_mask(model.sliding_window), WINDOW_BLOCK),
            (FULL, A.CAUSAL, 512)):
        tile = A._pick_block(seq, block)
        by_row, _ = A._listed_tiles(structure, seq, seq, tile, tile)
        out[kind] = {"live_tiles": int(by_row.shape[1]),
                     "masked_tiles": int(((by_row[2] & 4) != 0).sum()),
                     "tiles": (seq // tile) ** 2, "tile": tile,
                     "layers": model.layer_types.count(kind)}
    return out


def run(ctx: Context, limits) -> Dict[str, Any]:
    import jax

    out = train.run(ctx, limits)
    # the window's own steps: those after the job's first CHECK_STEPS, as
    # many as the window finished
    window = jax.device_get(_counted[CHECK_STEPS:CHECK_STEPS + out["attempted"]])
    counters = routing_counters(ctx, window)
    ctx.info(phase="routing counted", **counters)
    planned = attention_planned(ctx)
    ctx.info(phase="attention planned", **planned)
    out["facts"]["counters"] = counters
    out["facts"]["attention_planned"] = planned
    return out


def readings(ctx: Context, seeds, control_seeds, fault_seeds) -> list:
    """For the limits, as ``kinds/train.py``'s: what the comparison reads on
    each of ``seeds`` and, on ``control_seeds``, with the reference on int8's
    and fp8's grid in the program's place, and on ``fault_seeds`` with a
    fault planted there: half of the batch (the first of the two rows) and
    ``reference_smallthinker.FAULTS``."""
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
                ref, run_reference(ctx, make_p0, rows, seq, rows=slice(0, rows // 2)))
            for fault in reference_smallthinker.FAULTS:
                rec["fault_" + fault] = against(
                    ref, run_reference(ctx, make_p0, rows, seq, fault=fault))
        ctx.info(**rec)
        out.append(rec)
    return out


_compare_leaves = train.compare


def compare(seen, ref, limits) -> list:
    """``kinds/train.py``'s four numbers of the leaves, and the largest gap
    of the steps' losses: a program that uses half of the batch reads a
    hundred times the sound runs' gap, the reference on float8's grid ten
    times."""
    rows = _compare_leaves(seen, ref, limits)
    gap = max(abs(a - b) for a, b in zip(seen["losses"], ref["losses"]))
    rows.append(compared("loss_gap", gap, limits["loss_gap"]))
    return rows


train._program, train.first_steps, train.run_reference = _program, first_steps, run_reference
train.compare = compare
