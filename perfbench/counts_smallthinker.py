"""Operations SmallThinker's decoder needs, from shapes alone: the same
whatever implements a layer, with no recomputation and attention counted
over the pairs its masks let through (a window's tiles compute more: a
program that does extra work reads lower, never higher). ``model`` is a
configuration file's dict (the published keys; ``moe_num_primary_experts``
is the count HELD here, ``reduced_from.moe_num_primary_experts`` the
router's width, ``vocab_size`` the rows held, the first
``num_hidden_layers`` of ``sliding_window_layout`` the layers held).
"""

from __future__ import annotations

from typing import Mapping

from counts_laguna import full_pairs, window_pairs
from weights_smallthinker import FULL, WINDOW, layer_types, router_width

BF16 = 2  # bytes


def attn_params(model: Mapping) -> int:
    """q, k, v and o: what meets every position in a multiply-add
    (20,971,520 as published)."""
    h, d = model["hidden_size"], model["head_dim"]
    return 2 * h * model["num_attention_heads"] * d + 2 * h * model["num_key_value_heads"] * d


def router_params(model: Mapping) -> int:
    return model["hidden_size"] * router_width(model)


def expert_params(model: Mapping) -> int:
    """One routed expert: gate, up and down (5,898,240)."""
    return 3 * model["hidden_size"] * model["moe_ffn_hidden_size"]


def n_params(model: Mapping) -> int:
    """Every parameter held: each layer's projections, two norms, router and
    experts held; both tables' rows held; the final norm (656,529,920 at the
    cell's cut)."""
    h = model["hidden_size"]
    layer = (attn_params(model) + 2 * h + router_params(model)
             + model["moe_num_primary_experts"] * expert_params(model))
    return model["num_hidden_layers"] * layer + 2 * model["vocab_size"] * h + h


def attn_ops_per_pair(model: Mapping) -> float:
    """A (query, key) pair a head, forward: the score and the value's
    product over the head's 128, 2 operations a multiply-add: 512."""
    return 4.0 * model["head_dim"]


def attn_flops_per_step(model: Mapping, rows: int, seq: int, kinds=(FULL, WINDOW)) -> float:
    """Attention's core, forward + backward, over the layers held of
    ``kinds``, one step: two products forward, five backward (3.5 times the
    forward's operations) over the pairs each layer's mask lets through."""
    pairs = sum(window_pairs(seq, model["sliding_window_size"]) if kind == WINDOW
                else full_pairs(seq) for kind in layer_types(model) if kind in kinds)
    return 3.5 * attn_ops_per_pair(model) * model["num_attention_heads"] * pairs * rows


def swa_attn_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """:func:`attn_flops_per_step` of the window layers alone."""
    return attn_flops_per_step(model, rows, seq, (WINDOW,))


def nope_attn_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """:func:`attn_flops_per_step` of the full (NoPE) layers alone."""
    return attn_flops_per_step(model, rows, seq, (FULL,))


def experts_flops_per_step(model: Mapping, pairs_held: float) -> float:
    """The held experts' three products, forward + backward: 6 operations a
    weight a (position, expert) pair held, over all layers."""
    return 6.0 * expert_params(model) * pairs_held


def experts_bytes_per_step(model: Mapping, pairs_held: float) -> float:
    """The least the grouped products must move, forward + backward: the
    held experts' weights read twice and their gradients written once, and a
    pair's row in and out (hidden wide, bf16) in both directions."""
    weights = (3 * BF16 * model["num_hidden_layers"] * model["moe_num_primary_experts"]
               * expert_params(model))
    return weights + 4 * BF16 * model["hidden_size"] * pairs_held


def lm_head_loss_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """Fused LM head + cross entropy over the rows of the vocabulary held:
    logits, dx and dw, each 2·T·h·V with T = rows·seq."""
    return 3 * 2.0 * rows * seq * model["hidden_size"] * model["vocab_size"]


def dense_params_per_position(model: Mapping) -> int:
    """The weights every position meets in a multiply-add, all layers: the
    projections and the router."""
    return model["num_hidden_layers"] * (attn_params(model) + router_params(model))


def train_flops_per_step(model: Mapping, rows: int, seq: int, pairs_held: float) -> float:
    """Forward + backward of one step: 6 a weight a position for what every
    position meets, attention over the pairs the masks let through, the held
    experts over the pairs held, the head. Recomputation is not credited."""
    return (6.0 * dense_params_per_position(model) * rows * seq
            + attn_flops_per_step(model, rows, seq)
            + experts_flops_per_step(model, pairs_held)
            + lm_head_loss_flops_per_step(model, rows, seq))
