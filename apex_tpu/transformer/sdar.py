"""A block-diffusion language model trained the SDAR way: a Qwen3-style
decoder block (grouped-query rotary attention, routed experts) fed each
sequence twice, a noised copy then the clean one, under the block-diffusion
attention mask (arXiv:2510.06303, arXiv:2503.09573).

The block (no biases anywhere): ``h = x + Attn(RMSNorm(x))``,
``y = h + MoE(RMSNorm(h))``; a final RMSNorm and an untied head fused into the
loss (``ops/lm_head_loss.py``).

* *Attention*: ``num_heads`` query heads over ``num_kv_heads`` key/value
  heads of ``head_dim``; ``q`` and ``k`` RMS-normalised over each head's
  ``head_dim`` with a learned weight, then rotated (``ops/rope.py``,
  ``rotate_half`` form, both copies carrying positions ``0 .. L - 1``);
  query head ``i`` reads K/V head ``i // group``; the mask is
  ``ops.attention.block_diffusion_mask(block)`` and rides the flash kernels
  as a structure (the listed schedule), K/V never repeated.
* *Routed FFN* (``transformer/moe.py:routed_experts_mlp``): softmax router
  over all ``num_experts`` in float32, ``top_k`` a position, weights
  renormalised over the chosen, SiLU-gated experts, no drops, no auxiliary
  loss. The model is told which experts it holds (``experts_held``): one
  chip's share of an expert-parallel deployment; what the absent experts
  would add is left out and nothing stands in for their chips.
* *Training* (:func:`sdar_loss`): a batch is ``tokens`` (rows, L) and
  ``noise`` (rows, L) int32 from the input pipeline, ``noise = 2 *
  round(t * 2**20) + masked``: the block's rate ``t`` and whether the
  position is replaced by the mask id. The model sees ``[x_t ; x0]`` (2L
  positions), predicts at the masked positions themselves (no shift), and
  the loss is ``sum over masked positions of CE / t`` over ``rows * L``.

This is a sibling of ``transformer/hybrid.py`` and not a configuration of it:
it shares that module's stack (:func:`~apex_tpu.transformer.hybrid.run_stack`:
parameters by layer kind stacked over periods, ``lax.scan``, a checkpoint a
layer) and nothing of its layers (post-norm, dense FFN, delta rule) or of its
loss (next-token cross entropy), so a union config would be two models'
fields side by side. The train step is ``apex_tpu.train.train_step_fn``'s:
``SDARConfig`` meets its three-method protocol, the step's second batch
argument carrying ``noise`` where the other families carry targets.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor.trace import span
from apex_tpu.ops._pallas_util import pvary_like
from apex_tpu.ops.attention import block_diffusion_mask, flash_attention
from apex_tpu.ops.layer_norm import rms_norm
from apex_tpu.ops.lm_head_loss import lm_head_loss
from apex_tpu.ops.rope import apply_rotary
from apex_tpu.transformer.hybrid import refuse_tp, run_stack
from apex_tpu.transformer.moe import (
    RoutedExpertsConfig,
    routed_expert_shapes,
    routed_experts_mlp,
)

Pytree = Any
F32 = jnp.float32
LAYER = "blockdiff_attention_experts"
RATE_BITS = 20                      # a block's rate t is a multiple of 2**-20


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    vocab_held: int = 37984         # rows of the embedding and the head here
    hidden: int = 2048
    num_layers: int = 4
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    num_experts: int = 128          # the router's range
    experts_held: Tuple[int, int] = (0, 32)     # (first, count)
    top_k: int = 8
    expert_hidden: int = 768
    block: int = 4                  # the diffusion block's length
    mask_id: int = 37983            # a row of the vocabulary held
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # what the backward replays: "layer" keeps a layer's input and replays
    # the layer whole; "sublayer" keeps the input of the attention sublayer
    # and of the routed one and replays each by itself, so that the two
    # sublayers' intermediates are never held together; "none" keeps all
    remat: str = "sublayer"

    def __post_init__(self):
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the router's {self.num_experts}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads must divide num_heads")
        if not 0 <= self.mask_id < self.vocab_held:
            raise ValueError("mask_id must be a row of the vocabulary held")
        if self.remat not in ("none", "layer", "sublayer"):
            raise ValueError(f"remat is 'none', 'layer' or 'sublayer', not "
                             f"{self.remat!r}")

    @property
    def routed(self) -> RoutedExpertsConfig:
        return RoutedExpertsConfig(self.num_experts, self.top_k)

    # -- the protocol ``apex_tpu.train.train_step_fn`` takes a model by -------
    def param_specs(self) -> Pytree:
        return {"embed": {"tok": P()},
                "periods": {LAYER: {name: P() for name in layer_shapes(self)}},
                "head": {"norm": P(), "lm": P()}}

    def init_params(self, rng) -> Pytree:
        return init_sdar_params(rng, self)

    def loss(self, params, tokens, noise):
        return sdar_loss(params, tokens, noise, self)[0]

    def loss_and_counters(self, params, tokens, noise):
        """The step's fourth result (``train_step_fn``): the loss and what
        the routed layers counted (``expert_loads``, ``held_places``), stacked
        over ``dp``."""
        loss, counted = sdar_loss(params, tokens, noise, self)
        return loss, jax.tree.map(lambda a: a[None], counted)


# ---------------------------------------------------------------------------
# parameters

def layer_shapes(cfg: SDARConfig) -> Dict[str, Tuple[int, ...]]:
    h, d = cfg.hidden, cfg.head_dim
    return {"norm1": (h,), "wq": (h, cfg.num_heads * d),
            "wk": (h, cfg.num_kv_heads * d), "wv": (h, cfg.num_kv_heads * d),
            "q_norm": (d,), "k_norm": (d,), "wo": (cfg.num_heads * d, h),
            "norm2": (h,),
            **routed_expert_shapes(h, cfg.expert_hidden, cfg.num_experts,
                                   cfg.experts_held[1])}


_NORMS = ("norm1", "norm2", "q_norm", "k_norm")


def init_sdar_params(rng, cfg: SDARConfig) -> Pytree:
    """Normal 0.02, norm weights one; every leaf of a layer stacked over
    (layers, 1): the period is one layer."""
    keys = iter(jax.random.split(rng, 32))
    normal = lambda shape: (0.02 * jax.random.normal(next(keys), shape, F32)
                            ).astype(cfg.dtype)
    lead = (cfg.num_layers, 1)
    layer = {name: (jnp.ones(lead + shape, cfg.dtype) if name in _NORMS
                    else normal(lead + shape))
             for name, shape in layer_shapes(cfg).items()}
    table = (cfg.vocab_held, cfg.hidden)
    return {"embed": {"tok": normal(table)}, "periods": {LAYER: layer},
            "head": {"norm": jnp.ones((cfg.hidden,), cfg.dtype),
                     "lm": normal(table)}}


# ---------------------------------------------------------------------------
# forward (local shards, inside shard_map)

def noised_batch(tokens, noise, cfg: SDARConfig):
    """``([x_t ; x0] (rows, 2L), weight (rows, L))``: the sequence the model
    reads and each noised position's weight in the loss, ``1 / t`` where it
    was masked and 0 elsewhere."""
    masked = (noise & 1) == 1
    t = (noise >> 1).astype(F32) * 2.0 ** -RATE_BITS
    x_t = jnp.where(masked, jnp.int32(cfg.mask_id), tokens)
    weight = jnp.where(masked, 1.0 / t, 0.0)
    return jnp.concatenate([x_t, tokens], axis=1), weight


def _attention(p, x, cfg: SDARConfig):
    b, s, _ = x.shape
    heads = lambda a, n: a.reshape(b, s, n, cfg.head_dim)
    with span("attn/qkv"):
        q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]
    with span("attn/qk_norm"):
        # a head's 128 at a time: XLA's fusion, not the row kernel, whose
        # statistics a row (a million rows of one float32 here) would be
        # kept for the backward in a layout that pads each to 128 lanes
        q = rms_norm(heads(q, cfg.num_heads), p["q_norm"], cfg.norm_eps,
                     use_pallas=False)
        k = rms_norm(heads(k, cfg.num_kv_heads), p["k_norm"], cfg.norm_eps,
                     use_pallas=False)
    with span("attn/rope"):
        # both copies of the sequence carry positions 0 .. L - 1
        positions = jnp.arange(s, dtype=jnp.int32) % (s // 2)
        q = apply_rotary(q.transpose(0, 2, 1, 3), positions, cfg.rope_theta)
        k = apply_rotary(k.transpose(0, 2, 1, 3), positions, cfg.rope_theta)
    with span("attn/core"):
        v = heads(v, cfg.num_kv_heads).transpose(0, 2, 1, 3)
        ctx = flash_attention(q, k, v,
                              structure=block_diffusion_mask(cfg.block))
    with span("attn/out"):
        return ctx.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def _attention_sublayer(p, x, cfg: SDARConfig):
    with span("pre_norm"):
        a = rms_norm(x, p["norm1"], cfg.norm_eps)
    a = _attention(p, a, cfg)
    with span("residual"):
        return x + a


def _experts_sublayer(p, x, cfg: SDARConfig):
    """``(x + MoE(RMSNorm(x)), what the routed layer counted)``."""
    with span("pre_norm"):
        m = rms_norm(x, p["norm2"], cfg.norm_eps)
    m, counted, _ = routed_experts_mlp(p, m, cfg.routed, cfg.experts_held)
    with span("residual"):
        return x + m, counted


def _layer(p, carry, cfg: SDARConfig):
    """One layer over the stack's carry ``(x, counted)``: each array of
    ``counted`` (layers, ...) takes this layer's row at its end and drops its
    first, so that after the last layer its rows are the layers' in order."""
    x, counted = carry
    wrap = jax.checkpoint if cfg.remat == "sublayer" else (lambda f: f)
    x = wrap(lambda p, x: _attention_sublayer(p, x, cfg))(p, x)
    x, here = wrap(lambda p, x: _experts_sublayer(p, x, cfg))(p, x)
    return x, jax.tree.map(lambda all_, row: jnp.concatenate([all_[1:], row[None]]),
                           counted, here)


def sdar_hidden(params, seq2, cfg: SDARConfig):
    """``[x_t ; x0]`` (rows, 2L) -> ``(the last layer's output, counted)``,
    ``counted`` int32 by the layers that ran: ``expert_loads`` (layers,
    experts held), the pairs each held expert took in each layer, and
    ``held_places`` (layers, top_k + 1), the positions that hold 0 .. top_k
    pairs with a row in the layer's first pass."""
    refuse_tp("the block-diffusion model's layers are",
              "grouped heads and the routed experts would each need a split "
              "of their own")
    with span("embed"):
        x = jnp.take(params["embed"]["tok"], seq2, axis=0)
    counted = {name: pvary_like(jnp.zeros((cfg.num_layers, width), jnp.int32), x)
               for name, width in (("expert_loads", cfg.experts_held[1]),
                                   ("held_places", cfg.top_k + 1))}
    return run_stack((x, counted), params["periods"], (LAYER,),
                     lambda kind, lp, carry: _layer(lp, carry, cfg),
                     cfg.remat == "layer")


def sdar_loss(params, tokens, noise, cfg: SDARConfig):
    """``(loss, counted)``: ``sum over masked positions of CE(logits at the
    position, x0) / t`` over ``rows * L``, the logits taken on the noised
    half alone and never materialised; and what :func:`sdar_hidden`'s routed
    layers counted."""
    rows, length = tokens.shape
    with span("noise"):
        seq2, weight = noised_batch(tokens, noise, cfg)
    x, counted = sdar_hidden(params, seq2, cfg)
    x = x[:, :length]
    with span("final_norm"):
        x = rms_norm(x, params["head"]["norm"], cfg.norm_eps)
    with span("lm_head_loss"):
        per = lm_head_loss(x, pvary_like(params["head"]["lm"], x), tokens,
                           weights=weight)
        return jnp.sum(per) / (rows * length), counted


def sdar_logits(params, tokens, noise, cfg: SDARConfig):
    """float32 logits of the noised half over the rows held (tests)."""
    seq2, _ = noised_batch(tokens, noise, cfg)
    x = sdar_hidden(params, seq2, cfg)[0][:, :tokens.shape[1]]
    x = rms_norm(x, params["head"]["norm"], cfg.norm_eps)
    return jnp.einsum("bsh,vh->bsv", x.astype(F32),
                      params["head"]["lm"].astype(F32))
