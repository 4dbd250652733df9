"""DeepSeek-V2's decoder (``model_type`` ``deepseek_v2``; DeepSeek-V2-Lite's
published keys are the defaults): multi-head latent attention with YaRN
positions, a leading dense layer, then layers of routed experts beside a
shared one, trained on next-token cross entropy plus the router's balance
loss.

The block (no biases; RMSNorm): ``a = x + MLA(RMSNorm(x))``,
``y = a + FFN(RMSNorm(a))``; a final RMSNorm and an untied head fused into
the loss (``ops/lm_head_loss.py``).

* *Latent attention* (``q_lora_rank`` null): ``q = x W_q``, a head's ``q =
  [q_nope (qk_nope_head_dim) ; q_pe (qk_rope_head_dim)]``; ``[c
  (kv_lora_rank) ; k_pe (qk_rope_head_dim)] = x W_kva``; ``c = RMSNorm(c)``;
  a head's ``[k_nope ; v (v_head_dim)] = c W_kvb``. ``q_pe`` and the one
  ``k_pe``, which every head shares, are rotated (``ops/rope.py``:
  **interleaved pairs**, the layout the published weights' columns have, and
  YaRN's frequencies from ``rope_scaling``). Training **expands** the
  latent: a head's key is ``[k_nope ; k_pe]``, the rotated part broadcast
  into each head, and ``o = softmax(q k^T scale + causal) v`` runs on the
  flash kernels with keys of 192 over values of 128
  (``ops/attention.py``). ``scale = (nope + rope)^-1/2 * m^2``, ``m`` YaRN's
  ``mscale_all_dim`` term. The latent cache and the absorbed projections are
  a decode path and are not here.
* *FFN*: the first ``first_k_dense`` layers SiLU-gated at ``dense_hidden``;
  the others :func:`~apex_tpu.transformer.moe.routed_experts_mlp` (softmax
  router over all ``num_experts`` in float32, ``top_k`` a position, weights
  **as scored**, ``norm_topk_prob`` false, times ``routed_scaling_factor``;
  no drops) plus one shared SiLU-gated FFN at ``shared_hidden`` that every
  position takes. The model is told which routed experts it holds
  (``experts_held``): one chip's share of an expert-parallel deployment;
  what the absent experts would add is left out. The shared expert is whole.
* *Balance loss* (``seq_aux``): :func:`~apex_tpu.transformer.moe.
  sequence_balance_loss` of each expert layer's scores, ``aux_loss_alpha``
  times, summed over the layers and added to the cross entropy.

A sibling of ``transformer/sdar.py`` on ``hybrid.run_stack``: the dense
layers are written out before the scan (they have other leaves), the expert
layers are the scan's period. The train step is
``apex_tpu.train.train_step_fn``'s: ``DeepSeekConfig`` meets its protocol,
counters fourth.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor.trace import span
from apex_tpu.ops._pallas_util import pvary_like
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.layer_norm import rms_norm
from apex_tpu.ops.lm_head_loss import lm_head_loss
from apex_tpu.ops.rope import RopeScaling, apply_rotary
from apex_tpu.transformer.hybrid import gated_ffn, refuse_tp, run_stack
from apex_tpu.transformer.moe import (
    RoutedExpertsConfig,
    routed_expert_shapes,
    routed_experts_mlp,
    sequence_balance_loss,
)

Pytree = Any
F32 = jnp.float32
DENSE = "mla_dense"
EXPERTS = "mla_experts"

V2_LITE_YARN = RopeScaling(factor=40, original_max_position_embeddings=4096,
                           beta_fast=32, beta_slow=1, mscale=0.707,
                           mscale_all_dim=0.707)


@dataclasses.dataclass(frozen=True)
class DeepSeekConfig:
    vocab_held: int = 25600         # rows of the embedding and the head here
    hidden: int = 2048
    num_layers: int = 5             # dense ones first, then expert layers
    first_k_dense: int = 1          # first_k_dense_replace
    num_heads: int = 16
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = V2_LITE_YARN
    dense_hidden: int = 10944       # intermediate_size
    num_experts: int = 64           # n_routed_experts: the router's range
    experts_held: Tuple[int, int] = (0, 16)     # (first, count)
    top_k: int = 6
    expert_hidden: int = 1408       # moe_intermediate_size
    shared_hidden: int = 2816       # n_shared_experts x moe_intermediate_size
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    aux_loss_alpha: float = 0.001
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # what the backward replays: "sublayer" the attention sublayer and the
    # FFN one each by itself (the attention one keeps the flash kernel's
    # output and row statistics, and does not run the kernel again:
    # ``ATTN_KEPT``), "none" keeps all
    remat: str = "sublayer"

    def __post_init__(self):
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the router's {self.num_experts}")
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError("first_k_dense must leave an expert layer")
        if self.remat not in ("none", "sublayer"):
            raise ValueError(f"remat is 'none' or 'sublayer', not {self.remat!r}")

    @property
    def routed(self) -> RoutedExpertsConfig:
        return RoutedExpertsConfig(self.num_experts, self.top_k,
                                   self.norm_topk_prob,
                                   self.routed_scaling_factor)

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = 1.0 if self.rope_scaling is None else self.rope_scaling.softmax_mscale
        return self.qk_head_dim ** -0.5 * m * m

    # -- the protocol ``apex_tpu.train.train_step_fn`` takes a model by -------
    def param_specs(self) -> Pytree:
        return {"embed": {"tok": P()},
                "periods": {kind: {name: P() for name in layer_shapes(self, kind)}
                            for kind in (DENSE, EXPERTS)},
                "head": {"norm": P(), "lm": P()}}

    def init_params(self, rng) -> Pytree:
        return init_deepseek_params(rng, self)

    def loss(self, params, tokens, targets):
        return deepseek_loss(params, tokens, targets, self)[0]

    def loss_and_counters(self, params, tokens, targets):
        """The step's fourth result (``train_step_fn``): the loss and what
        the expert layers counted (``expert_loads``, ``held_places``,
        ``aux_loss``), stacked over ``dp``."""
        loss, counted = deepseek_loss(params, tokens, targets, self)
        return loss, jax.tree.map(lambda a: a[None], counted)


# ---------------------------------------------------------------------------
# parameters

def layer_shapes(cfg: DeepSeekConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    """One layer's leaves by name, published name in brackets: ``wq``
    [q_proj], ``wkv_a`` [kv_a_proj_with_mqa], ``kv_norm`` [kv_a_layernorm],
    ``wkv_b`` [kv_b_proj], ``wo`` [o_proj]; a dense layer's ``w_gate``,
    ``w_up``, ``w_down``; an expert layer's router, held experts
    (``routed_expert_shapes``) and ``shared_*``."""
    h, n = cfg.hidden, cfg.num_heads
    attn = {"norm1": (h,), "wq": (h, n * cfg.qk_head_dim),
            "wkv_a": (h, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_norm": (cfg.kv_lora_rank,),
            "wkv_b": (cfg.kv_lora_rank,
                      n * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (n * cfg.v_head_dim, h), "norm2": (h,)}
    if kind == DENSE:
        f = cfg.dense_hidden
        return {**attn, "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}
    f = cfg.shared_hidden
    return {**attn,
            **routed_expert_shapes(h, cfg.expert_hidden, cfg.num_experts,
                                   cfg.experts_held[1]),
            "shared_gate": (h, f), "shared_up": (h, f), "shared_down": (f, h)}


_NORMS = ("norm1", "norm2", "kv_norm")


def init_deepseek_params(rng, cfg: DeepSeekConfig) -> Pytree:
    """Normal 0.02, norm weights one. Leaves by layer kind as the other
    stacked models keep them (``params["periods"][kind]``), the dense
    layers' stacked over (dense layers, 1), the expert layers' over (expert
    layers, 1): the scan's period is one expert layer, and the dense layers
    are written out before it."""
    keys = iter(jax.random.split(rng, 64))
    normal = lambda shape: (0.02 * jax.random.normal(next(keys), shape, F32)
                            ).astype(cfg.dtype)

    def stack(kind, lead):
        return {name: (jnp.ones(lead + shape, cfg.dtype) if name in _NORMS
                       else normal(lead + shape))
                for name, shape in layer_shapes(cfg, kind).items()}

    table = (cfg.vocab_held, cfg.hidden)
    return {"embed": {"tok": normal(table)},
            "periods": {DENSE: stack(DENSE, (cfg.first_k_dense, 1)),
                        EXPERTS: stack(EXPERTS, (cfg.expert_layers, 1))},
            "head": {"norm": jnp.ones((cfg.hidden,), cfg.dtype),
                     "lm": normal(table)}}


# ---------------------------------------------------------------------------
# forward (local shards, inside shard_map)

def _attention(p, x, cfg: DeepSeekConfig):
    b, s, _ = x.shape
    n, nope, rope, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    rotate = lambda a: apply_rotary(a, jnp.arange(s, dtype=jnp.int32),
                                    cfg.rope_theta, cfg.rope_scaling, True)
    with span("attn/q_proj"):
        q = (x @ p["wq"]).reshape(b, s, n, nope + rope).transpose(0, 2, 1, 3)
    with span("attn/kv_down"):
        kv = x @ p["wkv_a"]
        c, k_pe = kv[..., :cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
    with span("attn/kv_norm"):
        # 512 wide: XLA's fusion, not the row kernel (sdar.py's QK-norm)
        c = rms_norm(c, p["kv_norm"], cfg.norm_eps, use_pallas=False)
    with span("attn/kv_up"):
        kv = (c @ p["wkv_b"]).reshape(b, s, n, nope + dv).transpose(0, 2, 1, 3)
        k_nope, v = kv[..., :nope], kv[..., nope:]
    with span("attn/rope"):
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
        # the one rotated key, broadcast into every head's
        k_pe = jnp.broadcast_to(rotate(k_pe)[:, None], (b, n, s, rope))
        k = jnp.concatenate([k_nope, k_pe], axis=-1)
    with span("attn/core"):
        ctx = flash_attention(q, k, v, causal=True, scale=cfg.softmax_scale)
    with span("attn/out"):
        return ctx.transpose(0, 2, 1, 3).reshape(b, s, n * dv) @ p["wo"]


def _attention_sublayer(p, x, cfg: DeepSeekConfig):
    with span("pre_norm"):
        a = rms_norm(x, p["norm1"], cfg.norm_eps)
    a = _attention(p, a, cfg)
    with span("residual"):
        return x + a


def _dense_sublayer(p, x, cfg: DeepSeekConfig):
    with span("pre_norm"):
        m = rms_norm(x, p["norm2"], cfg.norm_eps)
    m = gated_ffn(m, p["w_gate"], p["w_up"], p["w_down"], "mlp")
    with span("residual"):
        return x + m


def _experts_sublayer(p, x, cfg: DeepSeekConfig):
    """``(x + Routed(m) + Shared(m), counted)``, ``m = RMSNorm(x)``;
    ``counted`` the routed layer's own counts and this layer's balance
    loss."""
    with span("pre_norm"):
        m = rms_norm(x, p["norm2"], cfg.norm_eps)
    routed, counted, router = routed_experts_mlp(p, m, cfg.routed,
                                                 cfg.experts_held)
    with span("aux_loss"):
        aux = sequence_balance_loss(router["scores"], router["idx"],
                                    x.shape[0], cfg.aux_loss_alpha)
    shared = gated_ffn(m, p["shared_gate"], p["shared_up"], p["shared_down"],
                        "shared")
    with span("residual"):
        return x + routed + shared, {**counted, "aux_loss": aux[None]}


def _wrap(cfg: DeepSeekConfig, keep=()):
    """What a sublayer runs under: with ``remat`` "sublayer" a checkpoint of
    its own that keeps its input and the arrays named ``keep``."""
    if cfg.remat != "sublayer":
        return lambda f: f
    policy = jax.checkpoint_policies.save_only_these_names(*keep)
    return lambda f: jax.checkpoint(f, policy=policy)


# the flash kernel's output and row statistics (``ops/attention.py`` names
# them): kept, the attention sublayer's replay runs its projections and the
# rotation again and not the kernel. 192 MiB a layer at the benchmark's cell
# (o 64, lse 128: a float32 column a row is padded to 128 lanes)
ATTN_KEPT = ("attn_out", "attn_lse")


def _dense_layer(p, x, cfg: DeepSeekConfig):
    x = _wrap(cfg, ATTN_KEPT)(lambda p, x: _attention_sublayer(p, x, cfg))(p, x)
    return _wrap(cfg)(lambda p, x: _dense_sublayer(p, x, cfg))(p, x)


def _expert_layer(p, carry, cfg: DeepSeekConfig):
    """One expert layer over the stack's carry ``(x, counted)``: each array
    of ``counted`` (expert layers, ...) takes this layer's row at its end and
    drops its first (``sdar.py``'s carry)."""
    x, counted = carry
    x = _wrap(cfg, ATTN_KEPT)(lambda p, x: _attention_sublayer(p, x, cfg))(p, x)
    x, here = _wrap(cfg)(lambda p, x: _experts_sublayer(p, x, cfg))(p, x)
    return x, jax.tree.map(
        lambda all_, row: jnp.concatenate([all_[1:], row[None]]), counted, here)


def deepseek_hidden(params, tokens, cfg: DeepSeekConfig):
    """tokens (rows, seq) -> ``(the last layer's output, counted)``,
    ``counted`` by the expert layers that ran: ``expert_loads`` (expert
    layers, experts held) and ``held_places`` (expert layers, top_k + 1)
    int32 as ``routed_experts_mlp`` counts them, ``aux_loss`` (expert layers,
    1) float32."""
    refuse_tp("the latent-attention model's layers are",
              "the heads' expansion from the latent and the routed experts "
              "would each need a split of their own")
    with span("embed"):
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    for i in range(cfg.first_k_dense):
        with span("layer"):
            x = _dense_layer(jax.tree.map(lambda a: a[i, 0],
                                          params["periods"][DENSE]), x, cfg)
    zeros = lambda width, dtype: pvary_like(
        jnp.zeros((cfg.expert_layers, width), dtype), x)
    counted = {"expert_loads": zeros(cfg.experts_held[1], jnp.int32),
               "held_places": zeros(cfg.top_k + 1, jnp.int32),
               "aux_loss": zeros(1, F32)}
    return run_stack((x, counted), {EXPERTS: params["periods"][EXPERTS]},
                     (EXPERTS,),
                     lambda kind, lp, carry: _expert_layer(lp, carry, cfg),
                     False)


def deepseek_loss(params, tokens, targets, cfg: DeepSeekConfig):
    """``(loss, counted)``: the mean cross entropy of the next token over the
    rows of the vocabulary held (the logits never materialised) plus the
    expert layers' balance losses; and what :func:`deepseek_hidden`'s expert
    layers counted."""
    x, counted = deepseek_hidden(params, tokens, cfg)
    with span("final_norm"):
        x = rms_norm(x, params["head"]["norm"], cfg.norm_eps)
    with span("lm_head_loss"):
        ce = jnp.mean(lm_head_loss(x, pvary_like(params["head"]["lm"], x),
                                   targets))
    return ce + jnp.sum(counted["aux_loss"]), counted


def deepseek_logits(params, tokens, cfg: DeepSeekConfig):
    """float32 logits over the rows held (tests; training never forms them)."""
    x = deepseek_hidden(params, tokens, cfg)[0]
    x = rms_norm(x, params["head"]["norm"], cfg.norm_eps)
    return jnp.einsum("bsh,vh->bsv", x.astype(F32),
                      params["head"]["lm"].astype(F32))
