"""Operations and bytes the hybrid decoder's algorithm needs, from shapes
alone: the same whatever implements a layer, and with no recomputation, so a
program that does extra work reads lower, never higher. ``model`` is a
configuration file's dict (the published keys; the layers held are the first
``num_hidden_layers`` of ``layer_types``, the vocabulary the ``vocab_size``
rows held).
"""

from __future__ import annotations

from typing import Mapping

BF16, F32 = 2, 4  # bytes
LINEAR, FULL = "linear_attention", "full_attention"


def layers_held(model: Mapping) -> tuple:
    return tuple(model["layer_types"][:model["num_hidden_layers"]])


def period_of(layer_types: tuple) -> tuple:
    """The shortest pattern the layers repeat: the program keeps parameters
    stacked over (periods, a kind's layers in one period)."""
    for n in range(1, len(layer_types) + 1):
        if len(layer_types) % n == 0 and layer_types == layer_types[:n] * (len(layer_types) // n):
            return layer_types[:n]


def _linear_dims(model: Mapping):
    n = model["linear_num_value_heads"]
    return n, model["linear_key_head_dim"], model["linear_value_head_dim"]


def matmul_params_per_layer(model: Mapping, kind: str) -> int:
    """Weights that meet every token in a multiply-add: the projections, the
    gated FFN's three matrices and, in a linear layer, the depthwise
    convolutions (a tap a channel a token). Norm weights and the decay's
    ``A_log`` and ``dt_bias`` are not."""
    h, f = model["hidden_size"], model["intermediate_size"]
    ffn = 3 * h * f
    if kind == FULL:
        d = model["num_attention_heads"] * (h // model["num_attention_heads"])
        return 4 * h * d + ffn
    n, dk, dv = _linear_dims(model)
    proj = h * (2 * n * dk + 2 * n * dv + 2 * n) + n * dv * h
    conv = model["linear_conv_kernel_dim"] * (2 * n * dk + n * dv)
    return proj + conv + ffn


def matmul_params(model: Mapping) -> int:
    """Over the layers held, plus the untied head's rows held (the embedding
    is a lookup)."""
    return (sum(matmul_params_per_layer(model, k) for k in layers_held(model))
            + model["vocab_size"] * model["hidden_size"])


def n_params(model: Mapping) -> int:
    """Every parameter held: the above, the embedding's rows, the norms (two
    a layer, QK-norm's two or the gate norm's one, the final one) and the
    decay's two vectors a linear layer."""
    h = model["hidden_size"]
    n, _, dv = _linear_dims(model)
    extra = {FULL: 2 * h + 2 * h, LINEAR: 2 * h + dv + 2 * n}
    return (matmul_params(model) + model["vocab_size"] * h + h
            + sum(extra[k] for k in layers_held(model)))


def delta_rule_flops_per_token(model: Mapping) -> float:
    """One linear layer, forward + backward, in recurrent form: a head's
    state is decayed (d_k·d_v), read by the key (2), updated by an outer
    product (2) and read by the query (2): 7·d_k·d_v; the backward twice
    that."""
    n, dk, dv = _linear_dims(model)
    return 3.0 * 7.0 * dk * dv * n


def delta_rule_bytes_per_token(model: Mapping) -> float:
    """One linear layer, forward + backward: q, k, v (bf16), g and beta
    (float32) read and o (bf16) written; then all of those and o's cotangent
    read and five cotangents written."""
    n, dk, dv = _linear_dims(model)
    qkv, gb, o = BF16 * n * (2 * dk + dv), F32 * 2 * n, BF16 * n * dv
    return (qkv + gb + o) + (qkv + gb + o) + (qkv + gb)


def full_attn_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """Causal attention, forward + backward, every full layer held, one
    step: 2·rows·s²·h forward over the causal half (QK^T and PV), the
    backward 2.5 x (five such products against two)."""
    width = model["hidden_size"]        # heads x head size
    return layers_held(model).count(FULL) * 3.5 * 2.0 * rows * seq * seq * width


def lm_head_loss_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """Fused LM head + cross entropy over the rows of the vocabulary held:
    logits, dx and dw, each 2·T·h·V."""
    return 3 * 2.0 * rows * seq * model["hidden_size"] * model["vocab_size"]


def train_flops_per_token(model: Mapping, seq: int) -> float:
    """Forward + backward per trained token: 6 a weight in a multiply-add,
    6·h·s a full layer for causal attention, the delta rule's recurrent-form
    operations a linear layer. Recomputation is not credited."""
    kinds = layers_held(model)
    return (6.0 * matmul_params(model)
            + kinds.count(FULL) * 6.0 * model["hidden_size"] * seq
            + kinds.count(LINEAR) * delta_rule_flops_per_token(model))
