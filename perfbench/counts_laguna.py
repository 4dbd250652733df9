"""Operations Laguna's decoder needs, from shapes alone: the same whatever
implements a layer, with no recomputation and attention counted over the
pairs its masks let through (a window's tiles compute more: a program that
does extra work reads lower, never higher). ``model`` is a configuration
file's dict (the published keys; ``num_experts`` is the count HELD here,
``reduced_from.num_experts`` the router's width, ``vocab_size`` the rows
held, the first ``num_hidden_layers`` of ``layer_types`` the layers held).
"""

from __future__ import annotations

from typing import List, Mapping

from weights_laguna import FULL, SLIDING, dense_layers
from weights_laguna import heads_of as heads

BF16 = 2  # bytes


def layers_held(model: Mapping) -> List[str]:
    return list(model["layer_types"][:model["num_hidden_layers"]])


def expert_layers(model: Mapping) -> int:
    return model["num_hidden_layers"] - dense_layers(model)


def attn_params(model: Mapping, kind: str) -> int:
    """q, k, v, the per-head gate and o: what meets every position in a
    multiply-add (44,187,648 full, 63,135,744 sliding as published)."""
    h, d, n = model["hidden_size"], model["head_dim"], heads(model, kind)
    return 2 * h * n * d + 2 * h * model["num_key_value_heads"] * d + h * n


def dense_ffn_params(model: Mapping) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def shared_params(model: Mapping) -> int:
    """The shared expert and its gate (9,440,256)."""
    h = model["hidden_size"]
    return 3 * h * model["shared_expert_intermediate_size"] + h


def expert_params(model: Mapping) -> int:
    """One routed expert: gate, up and down (9,437,184)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def router_params(model: Mapping) -> int:
    return model["hidden_size"] * model["reduced_from"]["num_experts"]


def n_params(model: Mapping) -> int:
    """Every parameter held: each layer's projections, gate, QK-norm and two
    norms; the dense layers' FFN; the expert layers' router, shared expert
    and its gate, and experts held; both tables' rows held; the final norm
    (811,030,784 at the cell's cut)."""
    h, d = model["hidden_size"], model["head_dim"]
    norms = 2 * h + 2 * d
    total = 0
    for i, kind in enumerate(layers_held(model)):
        total += attn_params(model, kind) + norms
        total += (dense_ffn_params(model) if i < dense_layers(model) else
                  router_params(model) + shared_params(model)
                  + model["num_experts"] * expert_params(model))
    return total + 2 * model["vocab_size"] * h + h


def full_pairs(seq: int) -> int:
    """(query, key) pairs the causal mask lets through in one row."""
    return seq * (seq + 1) // 2


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a window of ``window`` lets through in one row:
    ``seq x window - window (window - 1) / 2`` (4,063,488 at 8,192 and
    512)."""
    w = min(window, seq)
    return seq * w - w * (w - 1) // 2


def attn_ops_per_pair(model: Mapping) -> float:
    """A (query, key) pair a head, forward: the score and the value's
    product over the head's 128, 2 operations a multiply-add: 512."""
    return 4.0 * model["head_dim"]


def attn_flops_per_step(model: Mapping, rows: int, seq: int, kinds=(FULL, SLIDING)) -> float:
    """Attention's core, forward + backward, over the layers held of
    ``kinds``, one step: two products forward, five backward (3.5 times the
    forward's operations) over the pairs each layer's mask lets through."""
    total = 0.0
    for kind in layers_held(model):
        if kind in kinds:
            pairs = (window_pairs(seq, model["sliding_window"]) if kind == SLIDING
                     else full_pairs(seq))
            total += heads(model, kind) * pairs
    return 3.5 * attn_ops_per_pair(model) * total * rows


def swa_attn_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """:func:`attn_flops_per_step` of the sliding layers alone."""
    return attn_flops_per_step(model, rows, seq, (SLIDING,))


def experts_flops_per_step(model: Mapping, pairs_held: float) -> float:
    """The held experts' three products, forward + backward: 6 operations a
    weight a (position, expert) pair held, over all expert layers."""
    return 6.0 * expert_params(model) * pairs_held


def full_attn_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """:func:`attn_flops_per_step` of the full layers alone."""
    return attn_flops_per_step(model, rows, seq, (FULL,))


def experts_bytes_per_step(model: Mapping, pairs_held: float) -> float:
    """The least the grouped products must move, forward + backward: the
    held experts' weights read twice and their gradients written once, and a
    pair's row in and out (hidden wide, bf16) in both directions."""
    weights = 3 * BF16 * expert_layers(model) * model["num_experts"] * expert_params(model)
    return weights + 4 * BF16 * model["hidden_size"] * pairs_held


def lm_head_loss_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """Fused LM head + cross entropy over the rows of the vocabulary held:
    logits, dx and dw, each 2·T·h·V with T = rows·seq."""
    return 3 * 2.0 * rows * seq * model["hidden_size"] * model["vocab_size"]


def dense_params_per_position(model: Mapping) -> int:
    """The weights every position meets in a multiply-add, all layers:
    projections and gates, the dense layers' FFN, the expert layers' router
    and shared expert."""
    return (sum(attn_params(model, kind) for kind in layers_held(model))
            + dense_layers(model) * dense_ffn_params(model)
            + expert_layers(model) * (router_params(model) + shared_params(model)))


def train_flops_per_step(model: Mapping, rows: int, seq: int, pairs_held: float) -> float:
    """Forward + backward of one step: 6 a weight a position for what every
    position meets, attention over the pairs the masks let through, the held
    experts over the pairs held, the head. Recomputation is not credited."""
    return (6.0 * dense_params_per_position(model) * rows * seq
            + attn_flops_per_step(model, rows, seq)
            + experts_flops_per_step(model, pairs_held)
            + lm_head_loss_flops_per_step(model, rows, seq))
