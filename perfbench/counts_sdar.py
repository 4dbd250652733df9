"""Operations and bytes the block-diffusion decoder's algorithm needs, from
shapes alone: the same whatever implements a layer, and with no
recomputation, so a program that does extra work reads lower, never higher.
``model`` is a configuration file's dict (the published keys; ``num_experts``
is the count HELD here, ``reduced_from.num_experts`` the router's width,
``vocab_size`` the rows held). ``seq`` is the DATA tokens of a row, ``L``:
the model computes ``2 L`` positions a row (noised copy, clean copy) and
takes logits on the noised ``L``.
"""

from __future__ import annotations

from typing import Mapping

BF16, F32 = 2, 4  # bytes


def _dims(model: Mapping):
    h, d = model["hidden_size"], model["head_dim"]
    return h, d, model["num_attention_heads"], model["num_key_value_heads"]


def router_width(model: Mapping) -> int:
    return model["reduced_from"]["num_experts"]


def attn_params_per_layer(model: Mapping) -> int:
    """q, k, v and output projections (what meets every position in a
    multiply-add)."""
    h, d, nq, nkv = _dims(model)
    return 2 * h * nq * d + 2 * h * nkv * d


def expert_params(model: Mapping) -> int:
    """One expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def n_params(model: Mapping) -> int:
    """Every parameter held: the layers (projections, two norms, the QK-norm's
    two weights, the router, the experts held), both tables' rows held, the
    final norm."""
    h, d, _, _ = _dims(model)
    layer = (attn_params_per_layer(model) + 2 * h + 2 * d
             + h * router_width(model) + model["num_experts"] * expert_params(model))
    return model["num_hidden_layers"] * layer + 2 * model["vocab_size"] * h + h


def block_mask_pairs(seq: int, block: int) -> int:
    """(query, key) pairs the block-diffusion mask lets through in one row of
    ``2 seq`` positions: a noised query reads its own block (noised) and the
    clean blocks before it; a clean query the clean blocks up to its own."""
    n = seq // block
    noised = seq * block + block * block * n * (n - 1) // 2
    clean = block * block * n * (n + 1) // 2
    return noised + clean


def block_mask_tile_pairs(seq: int, block: int, tile: int = 512) -> int:
    """The pairs in the ``tile`` x ``tile`` tiles of one row's (2 seq)^2
    score rectangle that hold a visible pair: what a tiled kernel computes
    at the least (288 of 1,024 tiles at seq 8,192, block 4)."""
    n = seq // tile                         # tiles a half
    return tile * tile * (n + n * (n + 1))  # noised diagonal; two triangles


def attn_tile_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """:func:`attn_flops_per_step` over the visited tiles' pairs: the flash
    kernels' roofline counts what the tiles hold."""
    pairs = block_mask_pairs(seq, model["assumed"]["block_length"])
    tile = min(512, seq)
    return (attn_flops_per_step(model, rows, seq)
            * block_mask_tile_pairs(seq, model["assumed"]["block_length"], tile) / pairs)


def attn_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """Attention's core, forward + backward, every layer, one step: two
    products (QK^T, PV) of 2 x head_dim operations a visible pair a query
    head forward, five such products against two backward (3.5 x in all)."""
    _, d, nq, _ = _dims(model)
    pairs = block_mask_pairs(seq, model["assumed"]["block_length"])
    return model["num_hidden_layers"] * 3.5 * 2 * 2.0 * d * nq * pairs * rows


def experts_flops_per_step(model: Mapping, pairs_held: float) -> float:
    """The held experts' three products, forward + backward, one step:
    6 operations a weight a (position, expert) pair held, ``pairs_held`` the
    pairs over all layers."""
    return 6.0 * expert_params(model) * pairs_held


def experts_bytes_per_step(model: Mapping, pairs_held: float) -> float:
    """The least the grouped products must move, forward + backward: the
    held experts' weights read twice and their gradients written once, and a
    pair's row in and out (hidden wide, bf16) in both directions."""
    weights = 3 * BF16 * model["num_hidden_layers"] * model["num_experts"] * expert_params(model)
    return weights + 4 * BF16 * model["hidden_size"] * pairs_held


def lm_head_loss_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """Fused LM head + cross entropy over the rows of the vocabulary held,
    on the noised half: logits, dx and dw, each 2·T·h·V with T = rows·L."""
    return 3 * 2.0 * rows * seq * model["hidden_size"] * model["vocab_size"]


def train_flops_per_step(model: Mapping, rows: int, seq: int, pairs_held: float) -> float:
    """Forward + backward of one step: 6 a weight a position for the
    projections and the router (``2 rows seq`` positions), the attention
    core over the visible pairs, the held experts over the pairs held, the
    head over the noised half. Recomputation is not credited."""
    positions = 2 * rows * seq
    dense = attn_params_per_layer(model) + model["hidden_size"] * router_width(model)
    return (6.0 * dense * model["num_hidden_layers"] * positions
            + attn_flops_per_step(model, rows, seq)
            + experts_flops_per_step(model, pairs_held)
            + lm_head_loss_flops_per_step(model, rows, seq))
