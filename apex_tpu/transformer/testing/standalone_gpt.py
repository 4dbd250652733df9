"""Standalone Megatron-style GPT — the TP+PP-parallel test/flagship model.

Reference: ``apex/transformer/testing/standalone_gpt.py`` — ``GPTModel``
(:1440) over ``ParallelTransformer(Layer)`` (:713,577), ``ParallelAttention``
(:285), ``ParallelMLP`` (:236), vocab-parallel embedding + tied LM head +
``vocab_parallel_cross_entropy`` loss.

TPU re-design: pure functions over an explicit parameter pytree. Parameters
are created at their **global** shapes and laid onto the mesh by
:func:`gpt_param_specs` (GSPMD-style PartitionSpecs); inside ``shard_map``
each function sees its local shard and uses the explicit TP collectives
(``tensor_parallel.layers``) — column-parallel QKV/FC1, row-parallel
out-proj/FC2, vocab-parallel embedding and loss, flash-attention core.
The layer stack is a ``lax.scan`` over stacked layer params (one compiled
layer body regardless of depth), rematerialized per layer — the analogue of
the reference's activation checkpointing (``tensor_parallel/random.py:224``).

Layout contract (local shapes inside shard_map, ``tp`` = TP world size):

==============================  ==========================
``embed.tok``                   (vocab/tp, hidden)
``embed.pos``                   (max_seq, hidden)
``layers.*`` (leading [L])      see ``_init_layer``
``layers.qkv_kernel``           (hidden, 3·hidden/tp)
``layers.out_kernel``           (hidden/tp, hidden)
``layers.fc1_kernel``           (hidden, ffn/tp)
``layers.fc2_kernel``           (ffn/tp, hidden)
``head.ln_w/ln_b``              (hidden,)
``head.lm`` (untied head)       (hidden, vocab/tp)
==============================  ==========================
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor.trace import span, span_function
from apex_tpu.ops._pallas_util import compiled_backend
from apex_tpu.ops.attention import (
    flash_attention,
    flash_attention_packed,
    packed_plan,
    unpack_qkv,
)
from apex_tpu.ops.layer_norm import layer_norm
from apex_tpu.parallel.mesh import SP_AXIS, TP_AXIS
from apex_tpu.transformer.pipeline_parallel.schedules import PipelineSpec
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    column_parallel_linear,
    row_parallel_linear,
    vocab_parallel_embedding,
)

Pytree = Any


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Ref ``testing/arguments.py`` essentials, as one dataclass (SURVEY §5
    config unification)."""

    vocab_size: int = 50304
    max_seq: int = 1024
    hidden: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_mult: int = 4
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = True
    remat: bool = True
    # "full": recompute the whole layer in backward (the reference's
    # activation-checkpointing default, tensor_parallel/random.py:224).
    # "dots": selective policy — save matmul outputs, recompute only
    # elementwise (LN/gelu/adds); ~25% fewer recompute FLOPs for ~5-6 GB
    # of residuals at the 124M bench shape.
    # "dots_attn": dots PLUS the flash-attention custom_vjp residuals
    # (o + lse, named inside the kernels' fwd rules) — backward skips the
    # O(s^2) attention forward replay entirely (dense, ring and varlen)
    # for one extra (b, s, h_local) + lse activation per layer.
    remat_policy: str = "full"
    # Fuse the LM head matmul into the CE loss (ops/lm_head_loss.py) —
    # never materializes the (tokens, vocab) logits.
    fused_loss: bool = True
    # Ref standalone_gpt.py attention-/hidden-dropout sites (:285-735).
    # Active only when the caller passes ``dropout_key`` (training); the
    # attention dropout runs INSIDE the flash kernel with a TP-rank-folded
    # seed (tensor_parallel/random.py stream semantics), hidden/embedding
    # dropout on the replicated activations with the unfolded key (same
    # across the TP group).
    attention_dropout: float = 0.0
    hidden_dropout: float = 0.0
    # Megatron-style sequence parallelism over the tp axis (Korthikanti;
    # NOT in the reference): LN/dropout/residual regions run on (b, s/tp, h)
    # shards, TP blocks all_gather on entry and reduce-scatter on exit,
    # the embedding exit is a reduce-scatter and the LM head entry a
    # gather. Composes with the ring-attention sp axis (the tp split is
    # within each sp shard). Cuts the non-TP activation memory by tp× and
    # shrinks pipeline p2p tensors the same way.
    megatron_sp: bool = False
    # Decompose the layers' TP-boundary collectives into ppermute rings
    # interleaved with partial GEMMs (apex_tpu.comm.overlap): under
    # megatron_sp the QKV/FC1 entry all-gathers become all_gather_matmul
    # and the out-proj/FC2 exit reduce-scatters matmul_reduce_scatter;
    # without it the row-parallel exit psums become matmul_all_reduce.
    # Custom VJPs keep backward overlapped too. XLA cannot hide a
    # DEPENDENT collective→matmul chain on its own — this flag is the
    # reference's async-allreduce capability (tensor_parallel/layers.py:
    # 217-269) rebuilt for the TPU ring. Numerics: all-gather side exact;
    # reduce side equal up to fp addition reorder (ring association).
    # Needs the (sp-local) sequence divisible by tp. The MoE FFN and the
    # LM head keep their monolithic collectives.
    overlap_comm: bool = False
    # num_experts > 0 replaces every layer's MLP with a mixture-of-experts
    # FFN (transformer.moe): top-k capacity routing, experts sharded over
    # the dp(=ep) mesh axis with all_to_all dispatch, expert FFN weights
    # TP-split. The router aux loss is averaged over layers and added to
    # gpt_loss. Composes with megatron_sp (the MoE region gathers the
    # sequence and slices the shard back out) and with the pipeline
    # schedules (PipelineSpec.stage_aux carries the router aux per stage).
    # COST of the default megatron_sp composition: every TP rank gathers
    # the full sequence and runs the whole router+dispatch block
    # redundantly (tp-fold duplicate compute), forfeiting the SP
    # activation saving inside the MoE region. Set ``moe_seq_dispatch``
    # to use the sequence-sharded dispatch instead; see PERF.md
    # "MoE under Megatron-SP".
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # Under megatron_sp, dispatch from the LOCAL sequence shard instead of
    # gathering the full sequence per TP rank: tp-fold less router/dispatch
    # compute, SP activation saving kept. Capacity becomes per-shard, so
    # tight-capacity drop patterns differ from the gathered path (exact
    # match when capacity is ample — see moe_mlp docstring).
    moe_seq_dispatch: bool = False
    # LayerNorm implementation override: None = layer_norm's own auto
    # (Pallas kernel on TPU when shapes allow), True/False forces it.
    # A Pallas call is an XLA fusion barrier, so at small hidden the
    # fused XLA LN can win despite the kernel's fewer HBM passes.
    ln_pallas: Optional[bool] = None

    @property
    def ffn_hidden(self) -> int:
        return self.ffn_mult * self.hidden

    @property
    def head_dim(self) -> int:
        return self.hidden // self.num_heads

    def validate(self, tp: int = 1, sp: int = 1) -> None:
        if self.hidden % self.num_heads:
            raise ValueError("hidden must be divisible by num_heads")
        for name, dim in (("vocab_size", self.vocab_size),
                          ("num_heads", self.num_heads),
                          ("ffn_hidden", self.ffn_hidden)):
            if dim % tp:
                raise ValueError(f"{name} ({dim}) not divisible by tp ({tp})")
        if self.remat_policy not in ("full", "dots", "dots_attn"):
            raise ValueError(
                f"remat_policy must be 'full', 'dots' or 'dots_attn', "
                f"got {self.remat_policy!r}")
        if self.megatron_sp and self.max_seq % tp:
            raise ValueError(
                f"megatron_sp needs max_seq ({self.max_seq}) divisible by "
                f"tp ({tp})")
        if self.overlap_comm and self.max_seq % (tp * sp):
            # the rings shard the SP-LOCAL sequence by tp, so the full
            # sequence must split across both axes (validate(tp) alone
            # cannot see ring-sp; callers composing with sp pass it)
            raise ValueError(
                f"overlap_comm rings shard the sp-local sequence by tp: "
                f"max_seq ({self.max_seq}) must be divisible by "
                f"tp*sp ({tp}*{sp})")
        if self.num_experts:
            self.moe_config  # MoEConfig.__post_init__ owns the MoE checks

    @property
    def moe_config(self):
        from apex_tpu.transformer.moe import MoEConfig

        return MoEConfig(num_experts=self.num_experts, hidden=self.hidden,
                         ffn_hidden=self.ffn_hidden, top_k=self.moe_top_k,
                         capacity_factor=self.moe_capacity_factor,
                         dtype=self.dtype)

    # -- the protocol ``bench.train_step_fn`` takes a model by ----------------
    def param_specs(self) -> Pytree:
        return gpt_param_specs(self)

    def init_params(self, rng) -> Pytree:
        return init_gpt_params(rng, self)

    def loss(self, params, tokens, targets):
        return gpt_loss(params, tokens, targets, self)


# ---------------------------------------------------------------------------
# init (global shapes)

def _init_layer(rng, cfg: GPTConfig) -> Pytree:
    h, f = cfg.hidden, cfg.ffn_hidden
    ks = jax.random.split(rng, 5)
    # Megatron init: normal(0.02) for input projections, output projections
    # scaled by 1/sqrt(2L) (ref standalone_gpt scaled_init_method)
    out_std = 0.02 / math.sqrt(2.0 * cfg.num_layers)
    dt = cfg.dtype
    layer = {
        "ln1_w": jnp.ones((h,), dt), "ln1_b": jnp.zeros((h,), dt),
        "qkv_kernel": (jax.random.normal(ks[0], (h, 3 * h)) * 0.02).astype(dt),
        "qkv_bias": jnp.zeros((3 * h,), dt),
        "out_kernel": (jax.random.normal(ks[1], (h, h)) * out_std).astype(dt),
        "out_bias": jnp.zeros((h,), dt),
        "ln2_w": jnp.ones((h,), dt), "ln2_b": jnp.zeros((h,), dt),
    }
    if cfg.num_experts:
        e = cfg.num_experts
        layer.update({
            "router": jax.random.normal(ks[4], (h, e), jnp.float32) * 0.02,
            "fc1_kernel": (jax.random.normal(ks[2], (e, h, f))
                           * 0.02).astype(dt),
            "fc1_bias": jnp.zeros((e, f), dt),
            "fc2_kernel": (jax.random.normal(ks[3], (e, f, h))
                           * out_std).astype(dt),
            "fc2_bias": jnp.zeros((e, h), dt),
        })
    else:
        layer.update({
            "fc1_kernel": (jax.random.normal(ks[2], (h, f)) * 0.02).astype(dt),
            "fc1_bias": jnp.zeros((f,), dt),
            "fc2_kernel": (jax.random.normal(ks[3], (f, h)) * out_std).astype(dt),
            "fc2_bias": jnp.zeros((h,), dt),
        })
    return layer


def init_gpt_params(rng, cfg: GPTConfig) -> Pytree:
    """Global-shape parameter pytree: ``{"embed", "layers" ([L, ...]), "head"}``."""
    cfg.validate()
    ke, kl, kh = jax.random.split(rng, 3)
    layer_rngs = jax.random.split(kl, cfg.num_layers)
    layers = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[_init_layer(k, cfg) for k in layer_rngs])
    dt = cfg.dtype
    params = {
        "embed": {
            "tok": (jax.random.normal(ke, (cfg.vocab_size, cfg.hidden))
                    * 0.02).astype(dt),
            "pos": (jax.random.normal(jax.random.fold_in(ke, 1),
                                      (cfg.max_seq, cfg.hidden))
                    * 0.02).astype(dt),
        },
        "layers": layers,
        "head": {
            "ln_w": jnp.ones((cfg.hidden,), dt),
            "ln_b": jnp.zeros((cfg.hidden,), dt),
        },
    }
    if not cfg.tie_embeddings:
        params["head"]["lm"] = (
            jax.random.normal(kh, (cfg.hidden, cfg.vocab_size)) * 0.02
        ).astype(dt)
    return params


def gpt_param_specs(cfg: GPTConfig, extra_layer_lead=()) -> Pytree:
    """PartitionSpecs matching :func:`init_gpt_params`: TP sharding on the
    Megatron dims, everything else replicated. ``extra_layer_lead`` prepends
    axes for stacked layer params (e.g. ``("pp",)`` for pipeline stages)."""
    lead = tuple(extra_layer_lead) + (None,)  # [(pp,)] + [L]
    layer = {
        "ln1_w": P(*lead), "ln1_b": P(*lead),
        "qkv_kernel": P(*lead, None, TP_AXIS),
        "qkv_bias": P(*lead, TP_AXIS),
        "out_kernel": P(*lead, TP_AXIS, None),
        "out_bias": P(*lead),
        "ln2_w": P(*lead), "ln2_b": P(*lead),
    }
    if cfg.num_experts:
        from apex_tpu.parallel.mesh import DP_AXIS
        from apex_tpu.transformer.moe import moe_param_specs

        # experts sharded over dp(=ep): each rank OWNS E/dp experts — their
        # grads are per-rank, not dp-reduced (DeepSpeed-MoE layout). The
        # layout is moe_param_specs' — one source of truth — with the
        # stacked-layer lead axes prepended.
        layer.update({k: P(*lead, *s)
                      for k, s in moe_param_specs(DP_AXIS).items()})
    else:
        layer.update({
            "fc1_kernel": P(*lead, None, TP_AXIS),
            "fc1_bias": P(*lead, TP_AXIS),
            "fc2_kernel": P(*lead, TP_AXIS, None),
            "fc2_bias": P(*lead),
        })
    specs = {
        "embed": {"tok": P(TP_AXIS, None), "pos": P()},
        "layers": layer,
        "head": {"ln_w": P(), "ln_b": P()},
    }
    if not cfg.tie_embeddings:
        specs["head"]["lm"] = P(None, TP_AXIS)
    return specs


# ---------------------------------------------------------------------------
# forward (local shards, inside shard_map)

def _hidden_dropout(x, rate: float, key):
    """Dropout on replicated activations (ref hidden-dropout sites): applied
    with the UNFOLDED key so every TP rank drops the same positions — the
    activations are TP-replicated, diverging them would break the region."""
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x * (1.0 / (1.0 - rate)),
                     jnp.zeros_like(x)).astype(x.dtype)


def _attention(p, x, cfg, heads_local: int, causal: bool = True, mask=None,
               dropout_key=None):
    """Ref ParallelAttention (:285): column-parallel fused QKV, flash core
    (with in-kernel probability dropout when training), row-parallel
    out-proj. Under ``cfg.megatron_sp`` ``x`` is the (b, s/tp, h) sequence
    shard: the QKV entry all-gathers seq, the out-proj exit reduce-scatters
    it (attention itself always sees the full sp-local sequence)."""
    b, s, h = x.shape
    if cfg.megatron_sp:
        s = s * lax.axis_size(TP_AXIS)
    with span("attn/qkv"):
        qkv = column_parallel_linear(x, p["qkv_kernel"], p["qkv_bias"],
                                     gather_output=False,
                                     sequence_parallel=cfg.megatron_sp,
                                     overlap_comm=cfg.overlap_comm)
        # per-head interleaved packing — column c of the global qkv kernel
        # is (head, {q,k,v}, head_dim): a contiguous TP column split then
        # assigns whole heads with their q, k, v together, so the computed
        # function is EXACTLY invariant to the TP degree. The flat (3,
        # heads, head_dim) order would make a tp split hand rank 0 "q of
        # heads 0..H/2 but k of heads H/2..H", silently mixing regions
        # across degrees.
        packed = _core_takes_packed(cfg, qkv, heads_local, causal, mask,
                                    dropout_key)
        if not packed:
            q, k, v = unpack_qkv(qkv, heads_local)
    with span("attn/core"):
        if packed:
            ctx = flash_attention_packed(qkv, heads_local, causal=causal)
        else:
            ctx = _attention_core(q, k, v, cfg, causal, mask, dropout_key)
    with span("attn/out"):
        if not packed:
            ctx = ctx.transpose(0, 2, 1, 3).reshape(
                b, s, heads_local * cfg.head_dim)
        # (the dots_attn remat names live INSIDE the flash custom_vjp
        # forward — ops/attention.py tags o and lse, the exact backward
        # residuals; tagging here would save the output without lse and
        # the kernel would replay anyway)
        return row_parallel_linear(ctx, p["out_kernel"], p["out_bias"],
                                   input_is_parallel=True,
                                   sequence_parallel=cfg.megatron_sp,
                                   overlap_comm=cfg.overlap_comm)


def _sp_size() -> int:
    try:
        return lax.axis_size(SP_AXIS)
    except NameError:
        return 1


def _core_takes_packed(cfg, qkv, heads_local, causal, mask, dropout_key):
    """Whether the attention core reads the QKV product as it stands and
    writes o as the output projection reads it
    (``ops.attention.flash_attention_packed``: head size 64, where
    (b, heads, s, head_dim) fills half of every 128-lane tile and cost 17
    layout copies a layer). Decided from what the call can observe: the
    sequence whole on this device, no dense mask, no attention dropout, a
    shape the packed kernels hold (``packed_plan``) and a backend that runs
    kernels; any other call unpacks to (b, heads, s, head_dim) as ever."""
    if mask is not None or _sp_size() > 1:
        return False
    if dropout_key is not None and cfg.attention_dropout > 0.0:
        return False
    return compiled_backend() and packed_plan(
        qkv.shape[1], heads_local, cfg.head_dim, qkv.dtype,
        causal) is not None


def _attention_core(q, k, v, cfg, causal, mask, dropout_key):
    """The attention core on (b, heads, s, head_dim): the flash kernel, or
    the K/V ring where the sequence is sharded over sp."""
    sp = _sp_size()
    rate = cfg.attention_dropout if dropout_key is not None else 0.0
    if sp > 1:
        # sequence sharded over sp: exact attention via the K/V ring
        if mask is not None:
            raise NotImplementedError(
                "explicit attention masks are not supported with sp > 1; "
                "use causal or full attention")
        from apex_tpu.transformer.sequence_parallel import ring_attention

        if rate > 0.0:
            from apex_tpu.transformer.tensor_parallel.random import (
                attention_dropout_seed,
            )

            return ring_attention(
                q, k, v, causal=causal, dropout_rate=rate,
                dropout_seed=attention_dropout_seed(dropout_key))
        return ring_attention(q, k, v, causal=causal)
    if rate > 0.0:
        from apex_tpu.transformer.tensor_parallel.random import (
            attention_dropout_seed,
        )

        seed = attention_dropout_seed(dropout_key)
        return flash_attention(q, k, v, causal=causal, mask=mask,
                               dropout_rate=rate, dropout_seed=seed)
    return flash_attention(q, k, v, causal=causal, mask=mask)


def _mlp(p, x, cfg):
    """Ref ParallelMLP (:236): column-parallel FC1 + gelu, row-parallel FC2.
    Under ``cfg.megatron_sp`` the FC1 entry gathers seq, the FC2 exit
    reduce-scatters it. With ``cfg.num_experts`` the FFN is the MoE layer
    (experts over dp, router aux loss returned alongside)."""
    if cfg.num_experts:
        from apex_tpu.parallel.mesh import DP_AXIS
        from apex_tpu.transformer.moe import moe_mlp

        if cfg.megatron_sp and cfg.moe_seq_dispatch:
            # sequence-sharded dispatch: route only the local s/tp tokens,
            # all-gather the kept expert SLOTS (the TP-split expert FFN
            # still needs replicated inputs for its psum), combine locally.
            # Removes the tp-fold router/dispatch duplication and keeps the
            # SP activation saving; capacity is per shard (see moe_mlp).
            from apex_tpu.parallel.mesh import TP_AXIS

            out, aux = moe_mlp(p, x, cfg.moe_config, ep_axis=DP_AXIS,
                               seq_shard_axis=TP_AXIS)
        elif cfg.megatron_sp:
            # the TP-split expert FFN psums partial outputs over tp, which
            # requires every tp rank to hold the SAME tokens: gather the
            # sequence for the MoE region, then take the own shard back out
            # (the scatter mapping's transpose restores the full per-token
            # cotangent on every rank — see its docstring).
            from apex_tpu.transformer.tensor_parallel.mappings import (
                gather_from_sequence_parallel_region,
                scatter_to_sequence_parallel_region,
            )

            x = gather_from_sequence_parallel_region(x)
            out, aux = moe_mlp(p, x, cfg.moe_config, ep_axis=DP_AXIS)
            out = scatter_to_sequence_parallel_region(out)
        else:
            out, aux = moe_mlp(p, x, cfg.moe_config, ep_axis=DP_AXIS)
        return out, aux["loss"]
    with span("mlp/fc"):
        y = column_parallel_linear(x, p["fc1_kernel"], p["fc1_bias"],
                                   gather_output=False,
                                   sequence_parallel=cfg.megatron_sp,
                                   overlap_comm=cfg.overlap_comm)
    with span("mlp/act"):
        y = jax.nn.gelu(y, approximate=True)
    with span("mlp/proj"):
        out = row_parallel_linear(y, p["fc2_kernel"], p["fc2_bias"],
                                  input_is_parallel=True,
                                  sequence_parallel=cfg.megatron_sp,
                                  overlap_comm=cfg.overlap_comm)
    return out, jnp.zeros((), jnp.float32)


def _hidden_key(key, cfg):
    """Hidden-dropout key policy: replicated activations share the unfolded
    key across the TP group; under megatron_sp each tp rank holds DIFFERENT
    tokens, so the rank must be folded in (tensor_parallel/random.py
    model-parallel stream), and under ring-sp the SP rank likewise — or
    shards would reuse one mask. The folds live HERE, at the hidden-dropout
    sites only: the per-layer base keys stay sp-invariant so the attention
    dropout stream (global-position-keyed in the ring) is identical across
    sharding layouts."""
    if key is None:
        return key
    if _sp_size() > 1:
        key = jax.random.fold_in(key, lax.axis_index(SP_AXIS))
    if not cfg.megatron_sp:
        return key
    from apex_tpu.transformer.tensor_parallel.random import (
        model_parallel_key,
    )

    return model_parallel_key(key)


def _layer(p, x, cfg, heads_local: int, causal: bool = True, mask=None,
           dropout_key=None):
    """Pre-LN transformer layer (ref ParallelTransformerLayer :577):
    attention (+in-kernel attention dropout) -> hidden dropout -> residual;
    MLP -> hidden dropout -> residual."""
    if dropout_key is not None:
        k_attn, k_h1, k_h2 = jax.random.split(dropout_key, 3)
        k_h1, k_h2 = _hidden_key(k_h1, cfg), _hidden_key(k_h2, cfg)
    else:
        k_attn = k_h1 = k_h2 = None
    with span("ln1"):
        h = layer_norm(x, p["ln1_w"], p["ln1_b"], use_pallas=cfg.ln_pallas)
    a = _attention(p, h, cfg, heads_local, causal, mask, dropout_key=k_attn)
    with span("residual"):
        if k_h1 is not None and cfg.hidden_dropout > 0.0:
            a = _hidden_dropout(a, cfg.hidden_dropout, k_h1)
        x = x + a
    with span("ln2"):
        h = layer_norm(x, p["ln2_w"], p["ln2_b"], use_pallas=cfg.ln_pallas)
    m, aux = _mlp(p, h, cfg)
    with span("residual"):
        if k_h2 is not None and cfg.hidden_dropout > 0.0:
            m = _hidden_dropout(m, cfg.hidden_dropout, k_h2)
        return x + m, aux


def dots_attn_policy():
    """The 'dots_attn' remat policy object: dots PLUS the flash-attention
    custom_vjp residuals (o AND lse — named inside the kernels' fwd
    rules; naming the public output alone would still replay the forward
    kernel to rebuild lse). With both saved, backward skips the O(s^2)
    attention forward replay — dense, ring and varlen alike — for one
    extra (b, s, h_local) + (b*h, s, 1) activation per layer."""
    return jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse"))


def _layer_stack(layers, x, cfg, causal: bool = True, mask=None,
                 dropout_key=None):
    """scan the stacked layer params over the hidden state."""
    tp = lax.axis_size(TP_AXIS)
    if cfg.num_heads % tp:
        # init_gpt_params can't see tp (global shapes); check here at trace
        # time instead of failing with a QKV reshape error mid-layer
        raise ValueError(
            f"num_heads ({cfg.num_heads}) not divisible by tp ({tp}); "
            f"see GPTConfig.validate(tp=...)")
    if cfg.overlap_comm and not cfg.megatron_sp and x.shape[1] % tp:
        # validate() only fires when the caller passes tp/sp; the flagship
        # path calls it bare (init_gpt_params) — same trace-time guard as
        # num_heads above, where the mesh is finally visible. (Under
        # megatron_sp the embed exit already enforces divisibility and
        # the exit rings scatter the gathered — always-divisible — seq.)
        raise ValueError(
            f"overlap_comm rings shard the sequence: local sequence "
            f"({x.shape[1]}) not divisible by tp ({tp}); see "
            f"GPTConfig.validate(tp=..., sp=...)")
    heads_local = cfg.num_heads // tp

    def one(lp, h, key):
        return _layer(lp, h, cfg, heads_local, causal, mask,
                      dropout_key=key)

    if cfg.remat:
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif cfg.remat_policy == "dots_attn":
            policy = dots_attn_policy()
        else:
            policy = None
        one = jax.checkpoint(one, policy=policy)

    n_layers = jax.tree_util.tree_leaves(layers)[0].shape[0]
    if dropout_key is not None:
        # per-layer keys; under pipelining each stage holds different layer
        # params but the same local indices — decorrelate by stage rank
        # (folding axis_index makes the keys pp-varying, so the carry must
        # be cast to match or scan rejects the type change)
        try:
            from apex_tpu.parallel.mesh import PP_AXIS

            pp = lax.axis_size(PP_AXIS)
        except NameError:
            pp = 1
        try:
            sp = lax.axis_size(SP_AXIS)
        except NameError:
            sp = 1
        base = dropout_key
        if pp > 1:
            base = jax.random.fold_in(base, lax.axis_index(PP_AXIS))
            if PP_AXIS not in jax.typeof(x).vma:
                x = lax.pcast(x, PP_AXIS, to="varying")
        if sp > 1:
            # the SP-rank fold itself lives in _hidden_key (hidden-dropout
            # sites only — folding it here would leak into the attention
            # seed and break the attention stream's layout invariance);
            # the hidden masks still make the carry sp-varying, so cast it
            if SP_AXIS not in jax.typeof(x).vma:
                x = lax.pcast(x, SP_AXIS, to="varying")
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
            jnp.arange(n_layers))
    else:
        keys = jnp.zeros((n_layers, 2), jnp.uint32)

    if cfg.num_experts:
        # the dp(=ep)-sharded expert weights make the MoE output dp-varying;
        # cast the carry up front so scan's carry types match
        from apex_tpu.parallel.mesh import DP_AXIS

        if DP_AXIS not in jax.typeof(x).vma:
            x = lax.pcast(x, DP_AXIS, to="varying")

    if cfg.overlap_comm and TP_AXIS not in jax.typeof(x).vma:
        # the decomposed row-parallel exit (matmul_all_reduce) returns
        # equal VALUES with tp-varying TYPE, so the scan carry must enter
        # varying; the pcast's transpose is the psum that folds each
        # rank's partial cotangents back together on the residual path —
        # exactly where the monolithic program's invariant-input
        # reduction fires
        x = lax.pcast(x, TP_AXIS, to="varying")

    def body(h, lp_key):
        lp, key = lp_key
        with span("layer"):
            h, aux = one(lp, h, key if dropout_key is not None else None)
        return h, aux

    out, aux_per_layer = lax.scan(body, x, (layers, keys))
    return out, jnp.mean(aux_per_layer)


def embed_tokens(embed, tokens, megatron_sp: bool = False):
    """Token + position embedding (ref GPT Embedding module). ``tokens`` may
    be the sp-local sequence shard; positions are offset by the sp rank.
    With ``megatron_sp`` the embedding's tp-psum becomes a reduce-scatter
    along seq and the result is the (b, s/(sp·tp), h) shard."""
    s_loc = tokens.shape[1]
    if megatron_sp:
        tp_size = lax.axis_size(TP_AXIS)
        if s_loc % tp_size:
            # validate() can only see max_seq; with a ring-sp axis the
            # per-rank requirement is (max_seq/sp) % tp — check the actual
            # shard here where both are known, instead of letting
            # psum_scatter fail deep in the trace
            raise ValueError(
                f"megatron_sp needs the sp-local sequence ({s_loc}) "
                f"divisible by tp ({tp_size})")
    h = vocab_parallel_embedding(tokens, embed["tok"],
                                 sequence_parallel=megatron_sp)
    try:
        sp = lax.axis_size(SP_AXIS)
    except NameError:
        sp = 1
    start = lax.axis_index(SP_AXIS) * s_loc if sp > 1 else 0
    if megatron_sp:
        s_shard = s_loc // lax.axis_size(TP_AXIS)
        start = start + lax.axis_index(TP_AXIS) * s_shard
        s_loc = s_shard
    if sp > 1 or megatron_sp:
        pos = lax.dynamic_slice_in_dim(embed["pos"], start, s_loc, 0)
    else:
        pos = embed["pos"][:s_loc]
    return h + pos[None].astype(h.dtype)


@span_function(name="embed")
def _embed_with_dropout(embed, tokens, cfg: GPTConfig, dropout_key):
    x = embed_tokens(embed, tokens, megatron_sp=cfg.megatron_sp)
    if dropout_key is not None and cfg.hidden_dropout > 0.0:
        try:
            sp = lax.axis_size(SP_AXIS)
        except NameError:
            sp = 1
        # ref GPT embedding dropout: same hidden_dropout rate on the
        # embedding output; distinct stream from the per-layer keys. The
        # SP/TP shard decorrelation is _hidden_key's fold.
        if sp > 1 and SP_AXIS not in jax.typeof(x).vma:
            x = lax.pcast(x, SP_AXIS, to="varying")
        x = _hidden_dropout(x, cfg.hidden_dropout,
                            _hidden_key(jax.random.fold_in(dropout_key,
                                                           0x0E0B), cfg))
    return x


def gpt_forward(params, tokens, cfg: GPTConfig, dropout_key=None):
    """tokens (b, s) -> vocab-sharded logits (b, s, vocab/tp). Call inside a
    mesh program (tp axis bound; tp=1 is the degenerate single-chip case).
    ``dropout_key`` activates cfg's dropout rates (training mode). The MoE
    router aux loss (if any) is dropped here — use :func:`gpt_loss` for
    training."""
    x = _embed_with_dropout(params["embed"], tokens, cfg, dropout_key)
    x, _aux = _layer_stack(params["layers"], x, cfg, dropout_key=dropout_key)
    return gpt_head(params, x, cfg)


def tied_vocab_logits(x, tok_embed, megatron_sp: bool):
    """The tied-embedding LM-head exit shared by GPT and BERT: gather the
    sequence under megatron_sp (the vocab dim is sharded over the same tp
    axis, so the einsum needs the full sequence), mark the TP region, and
    contract against each rank's vocab shard (the reference's
    parallel_output=True path)."""
    from apex_tpu.transformer.tensor_parallel.mappings import (
        copy_to_tensor_model_parallel_region,
        gather_from_sequence_parallel_region,
    )

    if megatron_sp:
        x = gather_from_sequence_parallel_region(x)
    x = copy_to_tensor_model_parallel_region(x)
    return jnp.einsum("bsh,vh->bsv", x, tok_embed)


def gpt_head(params, x, cfg: GPTConfig):
    """Final LN + LM head -> vocab-sharded logits. Tied: logits_i = h @ tok_iᵀ
    (each rank's vocab shard). Under ``cfg.megatron_sp`` the final LN runs
    on the sequence shard; :func:`tied_vocab_logits` gathers at the exit."""
    x = _final_ln(params["head"], x, cfg.ln_pallas)
    with span("lm_head"):
        return _lm_logits(params, x, cfg)


def _final_ln(head, x, ln_use_pallas):
    with span("final_ln"):
        return layer_norm(x, head["ln_w"], head["ln_b"],
                          use_pallas=ln_use_pallas)


def _lm_logits(params, x, cfg: GPTConfig):
    """The LM head on the final LayerNorm's output."""
    if cfg.tie_embeddings:
        return tied_vocab_logits(x, params["embed"]["tok"], cfg.megatron_sp)
    if cfg.megatron_sp:
        from apex_tpu.transformer.tensor_parallel.mappings import (
            gather_from_sequence_parallel_region,
        )

        x = gather_from_sequence_parallel_region(x)
    return column_parallel_linear(x, params["head"]["lm"],
                                  gather_output=False)


def _use_fused_loss(cfg: GPTConfig, n_rows: int) -> bool:
    """Fused path only when the kernel grid actually covers the shapes —
    otherwise the op's shape fallback (dense fp32 logits) would be slower
    than the unfused bf16 logits + CE path."""
    if not cfg.fused_loss:
        return False
    from apex_tpu.ops._pallas_util import compiled_backend
    from apex_tpu.ops.lm_head_loss import pallas_fits

    if compiled_backend():
        return pallas_fits(n_rows, cfg.hidden)
    return True  # CPU/virtual mesh: dense impl, exercised for coverage


def fused_head_loss(head_rows_w, ln_w, ln_b, x, targets,
                    gather_sequence: bool = False, ln_use_pallas=None):
    """Shared fused LM-head + CE block: final LN -> copy-to-TP-region ->
    pvary (so dw reduces over the data axes) -> fused loss kernel.
    ``head_rows_w``: (vocab/tp, hidden) projection rows. With
    ``gather_sequence`` (megatron_sp) the LN runs on the sequence shard
    and seq is gathered before the head."""
    from apex_tpu.ops.lm_head_loss import lm_head_loss
    from apex_tpu.transformer.tensor_parallel.mappings import (
        copy_to_tensor_model_parallel_region,
        gather_from_sequence_parallel_region,
        pvary_like,
    )

    x = _final_ln({"ln_w": ln_w, "ln_b": ln_b}, x, ln_use_pallas)
    with span("lm_head_loss"):
        if gather_sequence:
            x = gather_from_sequence_parallel_region(x)
        x = copy_to_tensor_model_parallel_region(x)
        # the loss kernel's custom_vjp hides w's linearity from shard_map's
        # invariant-input reduction; vary it explicitly over the
        # activations' axes so dw is psum'd over the data axes at the pvary
        # transpose
        w = pvary_like(head_rows_w, x)
        return jnp.mean(lm_head_loss(x, w, targets, axis_name=TP_AXIS))


def gpt_loss(params, tokens, targets, cfg: GPTConfig, dropout_key=None):
    """Mean vocab-parallel cross-entropy (ref vocab_parallel_cross_entropy).

    With ``cfg.fused_loss`` the head matmul is fused into the loss kernel
    (``ops/lm_head_loss.py``) and the logits are never materialized; the
    unfused path is kept for logits-consuming callers and parity tests.
    ``dropout_key`` activates cfg's dropout rates (training mode). With
    ``cfg.num_experts`` the layer-mean MoE router aux loss is added.
    """
    x = _embed_with_dropout(params["embed"], tokens, cfg, dropout_key)
    x, aux = _layer_stack(params["layers"], x, cfg, dropout_key=dropout_key)
    head = params["head"]
    if not _use_fused_loss(cfg, tokens.shape[0] * tokens.shape[1]):
        x = _final_ln(head, x, cfg.ln_pallas)
        with span("lm_head_loss"):
            logits = _lm_logits(params, x, cfg)
            # logits stay in model dtype; CE upcasts internally (fused by
            # XLA)
            return jnp.mean(
                vocab_parallel_cross_entropy(logits, targets)) + aux
    w = (params["embed"]["tok"] if cfg.tie_embeddings
         else head["lm"].T)  # (vocab/tp, hidden) rows
    return fused_head_loss(w, head["ln_w"], head["ln_b"], x, targets,
                           gather_sequence=cfg.megatron_sp,
                           ln_use_pallas=cfg.ln_pallas) + aux


# ---------------------------------------------------------------------------
# pipeline wiring (PipelineSpec contract, schedules/common.py)

def gpt_pipeline_params(rng, cfg: GPTConfig, pp: int,
                        vp: Optional[int] = None) -> Pytree:
    """Re-group :func:`init_gpt_params` into the pipeline driver's
    ``{"embed", "stages" [pp, L/pp, ...], "head"}`` layout — or
    ``[vp, pp, L/(vp·pp), ...]`` for the interleaved schedule (chunk ``v`` on
    stage ``s`` holds depth block ``v·pp + s``, the Megatron interleaved
    assignment). The LM head is untied across stages (ref: the
    embedding-group grad allreduce; see schedules/common.py docstring for why
    tying is a non-issue here only when embed and head share a param — across
    stages they cannot)."""
    chunks = pp * (vp or 1)
    if cfg.num_layers % chunks:
        raise ValueError("num_layers must be divisible by pp * vp")
    cfg_untied = dataclasses.replace(cfg, tie_embeddings=False)
    flat = init_gpt_params(rng, cfg_untied)
    per = cfg.num_layers // chunks
    if vp is None:
        stages = jax.tree.map(
            lambda x: x.reshape((pp, per) + x.shape[1:]), flat["layers"])
    else:
        stages = jax.tree.map(
            lambda x: x.reshape((vp, pp, per) + x.shape[1:]), flat["layers"])
    return {"embed": flat["embed"], "stages": stages, "head": flat["head"]}


def gpt_pipeline_specs_tree(cfg: GPTConfig, interleaved: bool = False
                            ) -> Pytree:
    """PartitionSpecs for :func:`gpt_pipeline_params`."""
    from apex_tpu.parallel.mesh import PP_AXIS

    lead = (None, PP_AXIS) if interleaved else (PP_AXIS,)
    base = gpt_param_specs(
        dataclasses.replace(cfg, tie_embeddings=False),
        extra_layer_lead=lead)
    return {"embed": base["embed"], "stages": base["layers"],
            "head": base["head"]}


def gpt_pipeline_spec(cfg: GPTConfig, dropout: bool = False) -> PipelineSpec:
    """The three pipeline functions (PipelineSpec contract). With
    ``cfg.num_experts`` the stage function also yields its layers' router
    aux loss (``stage_aux=True``) — the schedules accumulate and add it.
    With ``dropout`` the embed/stage functions take the schedules'
    per-microbatch PRNG key (``takes_dropout_key``) and apply cfg's
    dropout rates — the ref ParallelTransformerLayer trains with dropout
    under every schedule; pass ``dropout_key=`` to the schedule driver."""

    if dropout:
        def embed_fn(embed, tokens, key):
            return _embed_with_dropout(embed, tokens, cfg, key)

        def stage_fn(stage_layers, h, key):
            out, aux = _layer_stack(stage_layers, h, cfg, dropout_key=key)
            if cfg.num_experts:
                return out, aux
            return out
    else:
        def embed_fn(embed, tokens):
            return embed_tokens(embed, tokens, megatron_sp=cfg.megatron_sp)

        def stage_fn(stage_layers, h):
            out, aux = _layer_stack(stage_layers, h, cfg)
            if cfg.num_experts:
                return out, aux
            return out

    def loss_fn(head, h, targets):
        # h is the seq shard under megatron_sp; the fused-loss gate needs
        # the gathered row count (what the kernel will actually see)
        rows = h.shape[0] * h.shape[1]
        if cfg.megatron_sp:
            rows *= lax.axis_size(TP_AXIS)
        if _use_fused_loss(cfg, rows):
            return fused_head_loss(head["lm"].T, head["ln_w"], head["ln_b"],
                                   h, targets,
                                   gather_sequence=cfg.megatron_sp,
                                   ln_use_pallas=cfg.ln_pallas)
        logits = gpt_head({"head": head}, h, cfg=dataclasses.replace(
            cfg, tie_embeddings=False))
        return jnp.mean(vocab_parallel_cross_entropy(logits, targets))

    return PipelineSpec(embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
                        stage_aux=bool(cfg.num_experts),
                        takes_dropout_key=dropout)
