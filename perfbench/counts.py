"""Operations and bytes the algorithm needs, from shapes alone.

Every share of a peak that the benchmark reports divides by one of these.
They count what the published model needs (vocabulary 50257, not the padded
table; no recomputation), so a program that does extra work reads lower,
never higher. ``model`` is a configuration file's dict (Hugging Face keys).
"""

from __future__ import annotations

from typing import Iterable, Mapping

BF16 = 2  # bytes


def n_params(model: Mapping) -> int:
    """Published parameter count: token and position tables, ``n_layer``
    blocks of (two LayerNorms, QKV, out-projection, two MLP matrices, all
    with biases), the final LayerNorm; the head is tied to the table."""
    h, L = model["n_embd"], model["n_layer"]
    per_layer = 12 * h * h + 13 * h
    return (model["vocab_size"] * h + model["n_positions"] * h
            + L * per_layer + 2 * h)


def train_flops_per_token(model: Mapping, seq: int) -> float:
    """Forward + backward per trained token: 6N for the matrix products and
    6·L·h·s for causal attention (2·L·h·s forward, twice that backward).
    Recomputation is not credited."""
    return 6.0 * n_params(model) + 6.0 * model["n_layer"] * model["n_embd"] * seq


def serve_flops_per_token(model: Mapping, context: int) -> float:
    """Forward for one token fed (prefill and decode alike) that attends to
    ``context`` positions: 2N + 4·L·h·context."""
    return 2.0 * n_params(model) + 4.0 * model["n_layer"] * model["n_embd"] * context


def serve_flops_span(model: Mapping, first_context: int, last_context: int) -> float:
    """Sum of :func:`serve_flops_per_token` over contexts
    ``first_context..last_context`` inclusive (a request's tokens fed)."""
    n = last_context - first_context + 1
    if n <= 0:
        return 0.0
    ctx_sum = (first_context + last_context) * n / 2.0
    return (2.0 * n_params(model) * n
            + 4.0 * model["n_layer"] * model["n_embd"] * ctx_sum)


def flash_attn_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """Causal flash attention, forward + backward, all layers, one step.
    Forward per layer: QK^T and PV over the causal half, 2·rows·s²·h. The
    backward needs five such products against the forward's two (scores
    again, dP, dQ, dK, dV): 2.5 x. The forward replayed under remat is not
    credited."""
    fwd = 2.0 * rows * seq * seq * model["n_embd"]
    return model["n_layer"] * 3.5 * fwd


def lm_head_loss_flops_per_step(model: Mapping, rows: int, seq: int) -> float:
    """Fused LM head + cross entropy: logits, dx and dw, each 2·T·h·V."""
    return 3 * 2.0 * rows * seq * model["n_embd"] * model["vocab_size"]


def decode_weight_bytes(model: Mapping) -> float:
    """Bytes of bf16 weights one decode step must read once: every block,
    the final LayerNorm and the tied head's table."""
    h, L = model["n_embd"], model["n_layer"]
    return BF16 * (L * (12 * h * h + 13 * h) + 2 * h + model["vocab_size"] * h)


def kv_bytes_per_position(model: Mapping) -> float:
    """Bytes of K and V one cached position holds over all layers (bf16)."""
    return BF16 * 2 * model["n_layer"] * model["n_embd"]


def decode_step_bytes(model: Mapping, contexts: Iterable[int]) -> float:
    """Bytes one decode step must read: the weights once, and K and V of
    every active slot's context."""
    return (decode_weight_bytes(model)
            + kv_bytes_per_position(model) * float(sum(contexts)))
