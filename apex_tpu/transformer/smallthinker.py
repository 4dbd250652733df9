"""SmallThinker's decoder (``model_type`` ``smallthinker``; SmallThinker-21B-A3B's
published keys are the defaults): full causal attention layers with no
positional encoding among windowed rotary ones, grouped K/V heads, and in
every layer routed ReLU-gated experts whose routing is read from the
attention's normed input, before attention; trained on next-token cross
entropy.

One layer, ``x`` its input (no biases; RMSNorm):

* ``a = RMSNorm_1(x)``. **The routing is taken from a**: ``s = softmax(a
  W_r)`` over all ``num_experts`` in float32, the ``top_k`` largest, each
  chosen weight ``s_i / sum of the chosen s`` (``norm_topk_prob``).
* ``h = x + Attn(a)``: ``q = a W_q``, ``k = a W_k``, ``v = a W_v`` (query
  head ``i`` reading K/V head ``i // group``); on window layers q and k
  rotated over the whole head (``rotate_half``, ``rope_theta``) and the mask
  ``i - sliding_window < j <= i``; on full layers no rotation and the causal
  mask; ``softmax(q k^T head_dim^-1/2 + mask) v`` on the flash kernels'
  listed schedule (:func:`~apex_tpu.transformer.hybrid.grouped_attention`),
  then ``W_o``.
* ``y = h + sum over the chosen experts held of w_i (ReLU(m W_gate,i) * (m
  W_up,i)) W_down,i``, ``m = RMSNorm_2(h)``
  (:func:`~apex_tpu.transformer.moe.routed_experts_mlp` given the routing
  above and ``jax.nn.relu``). No shared expert, no dense layer, no balance
  loss. The model is told which routed experts it holds (``experts_held``):
  one chip's share of an expert-parallel deployment; what the absent
  experts would add is left out.

A final RMSNorm and an untied head fused into the loss
(``ops/lm_head_loss.py``). The stack is ``hybrid.run_stack`` over the
shortest repeating pattern of layer types, each type's leaves stacked by
themselves. Under the replay by sublayer the routing, ``(idx, weight)``
(positions x ``top_k``), leaves the attention sublayer and enters the
experts' one, so the router's gradient comes back from the experts through
the attention sublayer's replay into ``a``. The train step is
``apex_tpu.train.train_step_fn``'s: ``SmallThinkerConfig`` meets its
protocol, counters fourth.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor.trace import span
from apex_tpu.ops._pallas_util import pvary_like
from apex_tpu.ops.attention import CAUSAL, sliding_window_mask
from apex_tpu.ops.layer_norm import rms_norm
from apex_tpu.ops.lm_head_loss import lm_head_loss
from apex_tpu.transformer.hybrid import (
    ATTN_KEPT,
    append_row,
    grouped_attention,
    refuse_tp,
    run_stack,
    shortest_period,
    sublayer_remat,
)
from apex_tpu.transformer.moe import (
    RoutedExpertsConfig,
    route_softmax_top_k,
    routed_expert_shapes,
    routed_experts_mlp,
)

Pytree = Any
F32 = jnp.float32
# the layer types: ``sliding_window_layout`` 1 (windowed, rotary) and 0
# (full, no positional encoding); ``rope_layout`` is the same list
WINDOW, FULL = "window", "full"
# the window layers' flash tile (query and key blocks alike). At 16,384
# positions and a window of 4,096, 1,024 x 1,024 tiles visit 70 a head (73.4 M
# pairs for the 58.7 M the window shows), 512 x 512 tiles 252 (66.1 M) and
# 256 x 256 tiles 952; on a v5e a forward and backward of (2, 28, 16,384, 128)
# over 4 K/V heads takes 60.8 ms at 1,024, 76.3 ms at 512 and 157.0 ms at 256
# (PERF.md section 5); 2,048 overflows the kernels' scoped VMEM
WINDOW_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_held: int = 37984         # rows of the embedding and the head here
    hidden: int = 2560
    # the layers held, in order (``sliding_window_layout[:num_hidden_layers]``)
    layer_types: Tuple[str, ...] = (FULL, WINDOW, WINDOW, WINDOW)
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 4096      # sliding_window_size
    rope_theta: float = 1500000.0
    num_experts: int = 64           # the router's range
    experts_held: Tuple[int, int] = (0, 16)     # (first, count)
    top_k: int = 6                  # moe_num_active_primary_experts
    expert_hidden: int = 768        # moe_ffn_hidden_size
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for name in ("layer_types", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the router's {self.num_experts}")
        unknown = set(self.layer_types) - {WINDOW, FULL}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}; known: "
                             f"{WINDOW!r}, {FULL!r}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads must divide num_heads")

    @property
    def routed(self) -> RoutedExpertsConfig:
        # the chosen weights renormalised over the top_k (norm_topk_prob)
        return RoutedExpertsConfig(self.num_experts, self.top_k, True)

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern the layers' types repeat."""
        return shortest_period(self.layer_types)

    # -- the protocol ``apex_tpu.train.train_step_fn`` takes a model by -------
    def param_specs(self) -> Pytree:
        return {"embed": {"tok": P()},
                "periods": {kind: {name: P() for name in layer_shapes(self)}
                            for kind in dict.fromkeys(self.period)},
                "head": {"norm": P(), "lm": P()}}

    def init_params(self, rng) -> Pytree:
        return init_smallthinker_params(rng, self)

    def loss(self, params, tokens, targets):
        return smallthinker_loss(params, tokens, targets, self)[0]

    def loss_and_counters(self, params, tokens, targets):
        """The step's fourth result (``train_step_fn``): the loss and what
        the layers' routed experts counted (``expert_loads``,
        ``held_places``), stacked over ``dp``."""
        loss, counted = smallthinker_loss(params, tokens, targets, self)
        return loss, jax.tree.map(lambda a: a[None], counted)


# ---------------------------------------------------------------------------
# parameters

def layer_shapes(cfg: SmallThinkerConfig) -> Dict[str, Tuple[int, ...]]:
    """One layer's leaves by name (both types alike): the norms, the router
    and the held experts (``routed_expert_shapes``), the attention's
    projections."""
    h, d, n, kv = cfg.hidden, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    return {"norm1": (h,), "wq": (h, n * d), "wk": (h, kv * d),
            "wv": (h, kv * d), "wo": (n * d, h), "norm2": (h,),
            **routed_expert_shapes(h, cfg.expert_hidden, cfg.num_experts,
                                   cfg.experts_held[1])}


_NORMS = ("norm1", "norm2")


def init_smallthinker_params(rng, cfg: SmallThinkerConfig) -> Pytree:
    """Normal 0.02, norm weights one; each type's leaves stacked over
    (periods, that type's layers in a period)."""
    keys = iter(jax.random.split(rng, 64))
    normal = lambda shape: (0.02 * jax.random.normal(next(keys), shape, F32)
                            ).astype(cfg.dtype)
    def stack(lead):
        return {name: (jnp.ones(lead + shape, cfg.dtype) if name in _NORMS
                       else normal(lead + shape))
                for name, shape in layer_shapes(cfg).items()}

    periods = len(cfg.layer_types) // len(cfg.period)
    table = (cfg.vocab_held, cfg.hidden)
    return {"embed": {"tok": normal(table)},
            "periods": {kind: stack((periods, cfg.period.count(kind)))
                        for kind in dict.fromkeys(cfg.period)},
            "head": {"norm": jnp.ones((cfg.hidden,), cfg.dtype),
                     "lm": normal(table)}}


# ---------------------------------------------------------------------------
# forward (local shards, inside shard_map)

def _attention(p, a, cfg: SmallThinkerConfig, kind: str):
    window = kind == WINDOW
    return grouped_attention(
        p, a, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, scope="swa" if window else "attn",
        structure=sliding_window_mask(cfg.sliding_window) if window else CAUSAL,
        block=WINDOW_BLOCK if window else 512,
        rope=(cfg.rope_theta, None, None) if window else None)


def _attention_sublayer(p, x, cfg: SmallThinkerConfig, kind: str):
    """``(x + Attn(a), (idx, weight))``, ``a = RMSNorm_1(x)``: the routing
    read from ``a`` before attention runs."""
    with span("pre_norm"):
        a = rms_norm(x, p["norm1"], cfg.norm_eps)
    with span("moe/route"):
        idx, weight, _ = route_softmax_top_k(a, p["router"], cfg.top_k)
    o = _attention(p, a, cfg, kind)
    with span("residual"):
        return x + o, (idx, weight)


def _experts_sublayer(p, x, routing, cfg: SmallThinkerConfig):
    """``(x + Routed(m), counted)``, ``m = RMSNorm_2(x)``, the experts
    ReLU-gated and chosen by ``routing``."""
    with span("pre_norm"):
        m = rms_norm(x, p["norm2"], cfg.norm_eps)
    y, counted, _ = routed_experts_mlp(p, m, cfg.routed, cfg.experts_held,
                                       routing, jax.nn.relu)
    with span("residual"):
        return x + y, counted


def _layer(p, carry, cfg: SmallThinkerConfig, kind: str):
    """One layer of type ``kind`` over the stack's carry ``(x, counted)``
    (:func:`~apex_tpu.transformer.hybrid.append_row`). The backward replays
    the attention sublayer (with the router) and the experts' each by
    itself, the attention one keeping the flash kernel's output and row
    statistics (``ATTN_KEPT``)."""
    x, counted = carry
    x, routing = sublayer_remat("sublayer", ATTN_KEPT)(
        lambda p, x: _attention_sublayer(p, x, cfg, kind))(p, x)
    x, here = sublayer_remat("sublayer")(
        lambda p, x, r: _experts_sublayer(p, x, r, cfg))(p, x, routing)
    return x, append_row(counted, here)


def smallthinker_hidden(params, tokens, cfg: SmallThinkerConfig):
    """tokens (rows, seq) -> ``(the last layer's output, counted)``,
    ``counted`` int32 by layer, in order: ``expert_loads`` (layers, experts
    held) and ``held_places`` (layers, top_k + 1) as ``routed_experts_mlp``
    counts them."""
    refuse_tp("SmallThinker's layers are",
              "grouped heads and the routed experts would each need a split "
              "of their own")
    with span("embed"):
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    layers = len(cfg.layer_types)
    counted = {name: pvary_like(jnp.zeros((layers, width), jnp.int32), x)
               for name, width in (("expert_loads", cfg.experts_held[1]),
                                   ("held_places", cfg.top_k + 1))}
    periods = {kind: params["periods"][kind] for kind in dict.fromkeys(cfg.period)}
    return run_stack((x, counted), periods, cfg.period,
                     lambda kind, lp, carry: _layer(lp, carry, cfg, kind),
                     False)


def smallthinker_loss(params, tokens, targets, cfg: SmallThinkerConfig):
    """``(loss, counted)``: the mean cross entropy of the next token over the
    rows of the vocabulary held (the logits never materialised), and what
    :func:`smallthinker_hidden`'s layers counted."""
    x, counted = smallthinker_hidden(params, tokens, cfg)
    with span("final_norm"):
        x = rms_norm(x, params["head"]["norm"], cfg.norm_eps)
    with span("lm_head_loss"):
        return jnp.mean(lm_head_loss(x, pvary_like(params["head"]["lm"], x),
                                     targets)), counted
