"""Operations and bytes DeepSeek-V2's decoder needs, from shapes alone: the
same whatever implements a layer, with no recomputation and **no width
padded** (a key is 192 wide and a value 128, as published), so a program that
does extra work reads lower, never higher. ``model`` is a configuration
file's dict (the published keys; ``n_routed_experts`` is the count HELD here,
``reduced_from.n_routed_experts`` the router's width, ``vocab_size`` the rows
held).
"""

from __future__ import annotations

from typing import Mapping

BF16, F32 = 2, 4  # bytes


def router_width(model: Mapping) -> int:
    return model["reduced_from"]["n_routed_experts"]


def expert_layers(model: Mapping) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def attn_params_per_layer(model: Mapping) -> int:
    """q_proj, kv_a_proj_with_mqa, kv_b_proj and o_proj: what meets every
    position in a multiply-add (13,762,560 as published)."""
    h, n, rank = model["hidden_size"], model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    return h * n * (nope + rope) + h * (rank + rope) + rank * n * (nope + dv) + n * dv * h


def dense_ffn_params(model: Mapping) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def shared_params(model: Mapping) -> int:
    return 3 * model["hidden_size"] * model["n_shared_experts"] * model["moe_intermediate_size"]


def expert_params(model: Mapping) -> int:
    """One routed expert: gate, up and down (8,650,752)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def n_params(model: Mapping) -> int:
    """Every parameter held: each layer's projections, kv_a_layernorm and two
    norms; the dense layers' FFN; the expert layers' router, shared expert
    and experts held; both tables' rows held; the final norm (864,313,856 at
    the cell's cut)."""
    h = model["hidden_size"]
    attn = attn_params_per_layer(model) + model["kv_lora_rank"] + 2 * h
    dense = attn + dense_ffn_params(model)
    expert = (attn + h * router_width(model) + shared_params(model)
              + model["n_routed_experts"] * expert_params(model))
    return (model["first_k_dense_replace"] * dense + expert_layers(model) * expert
            + 2 * model["vocab_size"] * h + h)


def causal_pairs(seq: int) -> int:
    """(query, key) pairs the causal mask lets through in one row."""
    return seq * (seq + 1) // 2


def causal_tile_pairs(seq: int, tile: int = 512) -> int:
    """The pairs in the ``tile`` x ``tile`` tiles on or under the diagonal:
    what a tiled kernel computes at the least (528 of 1,024 tiles at seq
    16,384)."""
    tile = min(tile, seq)
    n = seq // tile
    return tile * tile * n * (n + 1) // 2


def attn_ops_per_pair(model: Mapping) -> float:
    """A (query, key) pair a head, forward: the score over the key's 192 and
    the value's 128, 2 operations a multiply-add: 640."""
    return 2.0 * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"] + model["v_head_dim"])


def attn_flops_per_step(model: Mapping, rows: int, seq: int, pairs=None) -> float:
    """Attention's core, forward + backward, every layer, one step: two
    products forward (QK^T over 192, PV over 128), five backward (two over
    128: dV, dP; three over 192: the score again, dQ, dK): 640 x 3.5 = 2,240
    operations a pair a head. ``pairs`` a row; the mask's own where None."""
    pairs = causal_pairs(seq) if pairs is None else pairs
    return (model["num_hidden_layers"] * 3.5 * attn_ops_per_pair(model)
            * model["num_attention_heads"] * pairs * rows)


def attn_tile_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """:func:`attn_flops_per_step` over the visited tiles' pairs: the flash
    kernels' roofline counts what the tiles hold."""
    return attn_flops_per_step(model, rows, seq, causal_tile_pairs(seq))


def experts_flops_per_step(model: Mapping, pairs_held: float) -> float:
    """The held experts' three products, forward + backward, one step: 6
    operations a weight a (position, expert) pair held, ``pairs_held`` the
    pairs over all expert layers."""
    return 6.0 * expert_params(model) * pairs_held


def experts_bytes_per_step(model: Mapping, pairs_held: float) -> float:
    """The least the grouped products must move, forward + backward: the
    held experts' weights read twice and their gradients written once, and a
    pair's row in and out (hidden wide, bf16) in both directions."""
    weights = 3 * BF16 * expert_layers(model) * model["n_routed_experts"] * expert_params(model)
    return weights + 4 * BF16 * model["hidden_size"] * pairs_held


def lm_head_loss_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """Fused LM head + cross entropy over the rows of the vocabulary held:
    logits, dx and dw, each 2·T·h·V with T = rows·seq."""
    return 3 * 2.0 * rows * seq * model["hidden_size"] * model["vocab_size"]


def dense_params_per_position(model: Mapping) -> int:
    """The weights every position meets in a multiply-add, all layers:
    projections, the dense layers' FFN, the expert layers' router and shared
    expert."""
    return (model["num_hidden_layers"] * attn_params_per_layer(model)
            + model["first_k_dense_replace"] * dense_ffn_params(model)
            + expert_layers(model) * (model["hidden_size"] * router_width(model)
                                      + shared_params(model)))


def train_flops_per_step(model: Mapping, rows: int, seq: int, pairs_held: float) -> float:
    """Forward + backward of one step: 6 a weight a position for what every
    position meets, the attention core over the causal pairs, the held
    experts over the pairs held, the head. Recomputation is not credited."""
    return (6.0 * dense_params_per_position(model) * rows * seq
            + attn_flops_per_step(model, rows, seq)
            + experts_flops_per_step(model, pairs_held)
            + lm_head_loss_flops_per_step(model, rows, seq))
