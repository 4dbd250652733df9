"""Laguna's decoder (``model_type`` ``laguna``; Laguna-S-2.1's published keys
are the defaults): sliding-window attention layers among full ones, more
query heads on the sliding layers than on the full, a sigmoid gate a head on
the attention's output, rotary positions by layer type, a leading dense
layer, then layers of routed experts beside a gated shared one; trained on
next-token cross entropy.

The block (no biases; RMSNorm): ``h = x + Attn(RMSNorm(x))``,
``y = h + FFN(RMSNorm(h))``; a final RMSNorm and an untied head fused into
the loss (``ops/lm_head_loss.py``).

* *Attention*, ``a = RMSNorm(x)``: ``q = a W_q`` (the layer type's query
  heads of ``head_dim``), ``k = a W_k``, ``v = a W_v`` (``num_kv_heads``);
  ``q`` and ``k`` RMS-normalised over each head's width (QK-norm) and
  rotated by the layer type's entry of ``rope_parameters``
  (``ops/rope.py``, ``rotate_half`` form: on full layers YaRN over the first
  ``head_dim x partial_rotary_factor`` elements of a head, the rest passed
  through; on sliding layers plain rotary over the whole head); ``o =
  softmax(q k^T head_dim^-1/2 + mask) v`` on the flash kernels' listed
  schedule, query head ``i`` reading K/V head ``i // group`` (K/V never
  repeated), the mask causal on full layers and
  :func:`~apex_tpu.ops.attention.sliding_window_mask` on sliding ones; each
  head's ``o`` times ``sigmoid(a W_g)`` for that head (``gating``
  ``per-head``); then ``W_o``.
* *FFN*: the first ``first_k_dense`` layers (``mlp_only_layers``) SiLU-gated
  at ``dense_hidden``; the others
  :func:`~apex_tpu.transformer.moe.routed_experts_mlp` (softmax router over
  all ``num_experts`` in float32, ``top_k`` a position, renormalised over
  the chosen, times ``routed_scaling_factor``; no drops) plus ``sigmoid(m
  W_sg) x Shared(m)``, ``m`` the sublayer's normed input, one SiLU-gated FFN
  at ``shared_hidden`` that every position takes. The model is told which
  routed experts it holds (``experts_held``): one chip's share of an
  expert-parallel deployment; what the absent experts would add is left
  out. The shared expert is whole.

A sibling of ``transformer/deepseek.py`` on ``hybrid.run_stack``: the dense
layers are written out before the scan, the expert layers' shortest
repeating pattern of layer types is the scan's period, and each layer
type's leaves are stacked by themselves (the types differ in head count).
The train step is ``apex_tpu.train.train_step_fn``'s: ``LagunaConfig``
meets its protocol, counters fourth.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor.trace import span
from apex_tpu.ops._pallas_util import pvary_like
from apex_tpu.ops.attention import CAUSAL, sliding_window_mask
from apex_tpu.ops.layer_norm import rms_norm
from apex_tpu.ops.lm_head_loss import lm_head_loss
from apex_tpu.ops.rope import RopeScaling
from apex_tpu.transformer.hybrid import (
    ATTN_KEPT,
    append_row,
    gated_ffn,
    grouped_attention,
    refuse_tp,
    run_stack,
    shortest_period,
    sublayer_remat,
)
from apex_tpu.transformer.moe import (
    RoutedExpertsConfig,
    routed_expert_shapes,
    routed_experts_mlp,
)

Pytree = Any
F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
DENSE = "laguna_dense"
# the window's flash tile (query and key blocks alike). At 8,192 positions
# and a window of 512, 512 x 512 tiles visit 31 a head (8.1 M pairs for the
# 4.1 M the window shows), 256 x 256 tiles 93 (6.1 M); on a v5e the window
# layer's forward and backward at 72 heads over 8 take 26.8 ms at 512 and
# 41.3 ms at 256 (PERF.md section 5)
WINDOW_BLOCK = 512

LAGUNA_S_YARN = RopeScaling(factor=128, original_max_position_embeddings=8192,
                            beta_fast=32, beta_slow=1,
                            attention_factor=1.4852030263919618)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_held: int = 12544         # rows of the embedding and the head here
    hidden: int = 3072
    # the layers held, in order (``layer_types[:num_hidden_layers]``)
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING, FULL)
    first_k_dense: int = 1          # mlp_only_layers [0]
    full_heads: int = 48            # num_attention_heads_per_layer, by type
    sliding_heads: int = 72
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    # (rope_theta, scaling, partial_rotary_factor) of each type's entry of
    # ``rope_parameters`` (``ops.rope.rope_for_layer_type``)
    full_rope: Tuple[float, Optional[RopeScaling], float] = (
        500000.0, LAGUNA_S_YARN, 0.5)
    sliding_rope: Tuple[float, Optional[RopeScaling], float] = (
        10000.0, None, 1.0)
    dense_hidden: int = 12288       # intermediate_size
    num_experts: int = 256          # the router's range
    experts_held: Tuple[int, int] = (0, 8)      # (first, count)
    top_k: int = 10
    expert_hidden: int = 1024       # moe_intermediate_size
    shared_hidden: int = 1024       # shared_expert_intermediate_size
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # what the backward replays: "sublayer" the attention sublayer and the
    # FFN one each by itself, the attention one keeping the flash kernel's
    # output and row statistics (``ATTN_KEPT``); "none" keeps all
    remat: str = "sublayer"

    def __post_init__(self):
        for name in ("layer_types", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the router's {self.num_experts}")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}; known: "
                             f"{SLIDING!r}, {FULL!r}")
        if not 0 <= self.first_k_dense < len(self.layer_types):
            raise ValueError("first_k_dense must leave an expert layer")
        if set(self.layer_types[:self.first_k_dense]) - {FULL}:
            raise ValueError("the dense layers are full-attention layers")
        for n in (self.full_heads, self.sliding_heads):
            if n % self.num_kv_heads:
                raise ValueError("num_kv_heads must divide each type's heads")
        if self.remat not in ("none", "sublayer"):
            raise ValueError(f"remat is 'none' or 'sublayer', not {self.remat!r}")

    @property
    def routed(self) -> RoutedExpertsConfig:
        return RoutedExpertsConfig(self.num_experts, self.top_k,
                                   self.norm_topk_prob,
                                   self.routed_scaling_factor)

    @property
    def expert_types(self) -> Tuple[str, ...]:
        return self.layer_types[self.first_k_dense:]

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern the expert layers' types repeat."""
        return shortest_period(self.expert_types)

    def heads(self, kind: str) -> int:
        return self.sliding_heads if kind == SLIDING else self.full_heads

    def rope(self, kind: str):
        return self.sliding_rope if kind == SLIDING else self.full_rope

    # -- the protocol ``apex_tpu.train.train_step_fn`` takes a model by -------
    def param_specs(self) -> Pytree:
        return {"embed": {"tok": P()},
                "periods": {kind: {name: P() for name in layer_shapes(self, kind)}
                            for kind in (DENSE, *dict.fromkeys(self.period))},
                "head": {"norm": P(), "lm": P()}}

    def init_params(self, rng) -> Pytree:
        return init_laguna_params(rng, self)

    def loss(self, params, tokens, targets):
        return laguna_loss(params, tokens, targets, self)[0]

    def loss_and_counters(self, params, tokens, targets):
        """The step's fourth result (``train_step_fn``): the loss and what
        the expert layers counted (``expert_loads``, ``held_places``),
        stacked over ``dp``."""
        loss, counted = laguna_loss(params, tokens, targets, self)
        return loss, jax.tree.map(lambda a: a[None], counted)


# ---------------------------------------------------------------------------
# parameters

def layer_shapes(cfg: LagunaConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    """One layer's leaves by name: the attention's (``wg`` the per-head
    gate), then a dense layer's ``w_gate``, ``w_up``, ``w_down`` or an
    expert layer's router, held experts (``routed_expert_shapes``),
    ``shared_*`` and ``shared_expert_gate``."""
    h, d, kv = cfg.hidden, cfg.head_dim, cfg.num_kv_heads
    n = cfg.heads(FULL if kind == DENSE else kind)
    attn = {"norm1": (h,), "wq": (h, n * d), "wk": (h, kv * d),
            "wv": (h, kv * d), "q_norm": (d,), "k_norm": (d,), "wg": (h, n),
            "wo": (n * d, h), "norm2": (h,)}
    if kind == DENSE:
        f = cfg.dense_hidden
        return {**attn, "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}
    f = cfg.shared_hidden
    return {**attn,
            **routed_expert_shapes(h, cfg.expert_hidden, cfg.num_experts,
                                   cfg.experts_held[1]),
            "shared_gate": (h, f), "shared_up": (h, f), "shared_down": (f, h),
            "shared_expert_gate": (h, 1)}


_NORMS = ("norm1", "norm2", "q_norm", "k_norm")


def init_laguna_params(rng, cfg: LagunaConfig) -> Pytree:
    """Normal 0.02, norm weights one; leaves by kind, each stacked over
    (periods, that kind's layers in a period), the dense layers' over (dense
    layers, 1)."""
    keys = iter(jax.random.split(rng, 96))
    normal = lambda shape: (0.02 * jax.random.normal(next(keys), shape, F32)
                            ).astype(cfg.dtype)

    def stack(kind, lead):
        return {name: (jnp.ones(lead + shape, cfg.dtype) if name in _NORMS
                       else normal(lead + shape))
                for name, shape in layer_shapes(cfg, kind).items()}

    periods = len(cfg.expert_types) // len(cfg.period)
    table = (cfg.vocab_held, cfg.hidden)
    return {"embed": {"tok": normal(table)},
            "periods": {DENSE: stack(DENSE, (cfg.first_k_dense, 1)),
                        **{kind: stack(kind, (periods, cfg.period.count(kind)))
                           for kind in dict.fromkeys(cfg.period)}},
            "head": {"norm": jnp.ones((cfg.hidden,), cfg.dtype),
                     "lm": normal(table)}}


# ---------------------------------------------------------------------------
# forward (local shards, inside shard_map)

def _attention(p, a, cfg: LagunaConfig, kind: str):
    theta, scaling, fraction = cfg.rope(kind)
    rotary_dim = int(cfg.head_dim * fraction)
    window = kind == SLIDING
    return grouped_attention(
        p, a, heads=cfg.heads(kind), kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, scope="swa" if window else "attn",
        structure=sliding_window_mask(cfg.sliding_window) if window else CAUSAL,
        block=WINDOW_BLOCK if window else 512,
        rope=(theta, scaling,
              None if rotary_dim == cfg.head_dim else rotary_dim),
        qk_norm_eps=cfg.norm_eps, gate=True)


def _attention_sublayer(p, x, cfg: LagunaConfig, kind: str):
    with span("pre_norm"):
        a = rms_norm(x, p["norm1"], cfg.norm_eps)
    a = _attention(p, a, cfg, kind)
    with span("residual"):
        return x + a


def _dense_sublayer(p, x, cfg: LagunaConfig):
    with span("pre_norm"):
        m = rms_norm(x, p["norm2"], cfg.norm_eps)
    m = gated_ffn(m, p["w_gate"], p["w_up"], p["w_down"], "mlp")
    with span("residual"):
        return x + m


def _experts_sublayer(p, x, cfg: LagunaConfig):
    """``(x + Routed(m) + sigmoid(m W_sg) Shared(m), counted)``, ``m =
    RMSNorm(x)``; ``counted`` the routed layer's own counts."""
    with span("pre_norm"):
        m = rms_norm(x, p["norm2"], cfg.norm_eps)
    routed, counted, _ = routed_experts_mlp(p, m, cfg.routed, cfg.experts_held)
    shared = gated_ffn(m, p["shared_gate"], p["shared_up"], p["shared_down"],
                       "shared")
    with span("shared/gate"):
        gate = jax.nn.sigmoid((m @ p["shared_expert_gate"]).astype(F32))
        shared = (gate * shared.astype(F32)).astype(x.dtype)
    with span("residual"):
        return x + routed + shared, counted


def _dense_layer(p, x, cfg: LagunaConfig):
    x = sublayer_remat(cfg.remat, ATTN_KEPT)(
        lambda p, x: _attention_sublayer(p, x, cfg, FULL))(p, x)
    return sublayer_remat(cfg.remat)(lambda p, x: _dense_sublayer(p, x, cfg))(p, x)


def _expert_layer(p, carry, cfg: LagunaConfig, kind: str):
    """One expert layer of type ``kind`` over the stack's carry ``(x,
    counted)`` (:func:`~apex_tpu.transformer.hybrid.append_row`)."""
    x, counted = carry
    x = sublayer_remat(cfg.remat, ATTN_KEPT)(
        lambda p, x: _attention_sublayer(p, x, cfg, kind))(p, x)
    x, here = sublayer_remat(cfg.remat)(
        lambda p, x: _experts_sublayer(p, x, cfg))(p, x)
    return x, append_row(counted, here)


def laguna_hidden(params, tokens, cfg: LagunaConfig):
    """tokens (rows, seq) -> ``(the last layer's output, counted)``,
    ``counted`` int32 by the expert layers that ran, in order:
    ``expert_loads`` (expert layers, experts held) and ``held_places``
    (expert layers, top_k + 1) as ``routed_experts_mlp`` counts them."""
    refuse_tp("Laguna's layers are",
              "grouped heads, the per-head gate and the routed experts would "
              "each need a split of their own")
    with span("embed"):
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    for i in range(cfg.first_k_dense):
        with span("layer"):
            x = _dense_layer(jax.tree.map(lambda a: a[i, 0],
                                          params["periods"][DENSE]), x, cfg)
    layers = len(cfg.expert_types)
    counted = {name: pvary_like(jnp.zeros((layers, width), jnp.int32), x)
               for name, width in (("expert_loads", cfg.experts_held[1]),
                                   ("held_places", cfg.top_k + 1))}
    periods = {kind: params["periods"][kind] for kind in dict.fromkeys(cfg.period)}
    return run_stack((x, counted), periods, cfg.period,
                     lambda kind, lp, carry: _expert_layer(lp, carry, cfg, kind),
                     False)


def laguna_loss(params, tokens, targets, cfg: LagunaConfig):
    """``(loss, counted)``: the mean cross entropy of the next token over the
    rows of the vocabulary held (the logits never materialised), and what
    :func:`laguna_hidden`'s expert layers counted."""
    x, counted = laguna_hidden(params, tokens, cfg)
    with span("final_norm"):
        x = rms_norm(x, params["head"]["norm"], cfg.norm_eps)
    with span("lm_head_loss"):
        return jnp.mean(lm_head_loss(x, pvary_like(params["head"]["lm"], x),
                                     targets)), counted
