"""Standalone T5-style encoder-decoder — the enc-dec pipeline's model family.

Reference: ``ModelType.encoder_and_decoder`` consumers —
``apex/transformer/pipeline_parallel/schedules/common.py:72-103`` builds
encoder blocks before ``pipeline_model_parallel_split_rank`` and decoder
blocks (self-attention + cross-attention + MLP) after it; the reference
ships no standalone T5 *fixture* (its tests stop at GPT/BERT), so this
module supplies the missing consumer the schedules are specified against.

TPU design, same contract as ``standalone_gpt``: pure functions over a
global-shape parameter pytree, Megatron TP layout (column-parallel QKV/FC1
and cross-attention Q/KV, row-parallel out-proj/FC2, vocab-parallel shared
embedding + loss), flash-attention cores (causal for decoder self-attn,
rectangular ``s_dec × s_enc`` for cross-attn), pre-LN residual blocks.
Position scheme: learned absolute positions by default, or T5's real
bucketed relative position biases with ``relative_position_bias=True``
(bias added to the logits inside the flash kernel — encoder bidirectional,
decoder causal, none on cross-attention, per-stack tables; rides ring SP
via per-shard bias strips). ``encoder_final_ln=True`` restores T5's
encoder-exit LayerNorm, applied equivalently at the decoder's memory
consumption so the enc pipeline ring keeps its uniform stage function.
With both flags on the fixture is architecturally T5-the-paper (modulo
LayerNorm-with-bias vs T5's bias-free RMSNorm, a config choice the
normalization module supports either way).

Pipeline wiring: :func:`t5_enc_dec_spec` + :func:`t5_pipeline_params`
feed ``schedules.fwd_bwd_enc_dec`` — encoder ring over all pp stages,
memory broadcast, decoder ring (see that module for why this beats the
reference's split-rank device partition).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.layer_norm import layer_norm
from apex_tpu.parallel.mesh import PP_AXIS, SP_AXIS, TP_AXIS
from apex_tpu.transformer.pipeline_parallel.schedules import EncDecPipelineSpec
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
class T5Config:
    vocab_size: int = 32128
    hidden: int = 512
    num_heads: int = 8
    enc_layers: int = 6
    dec_layers: int = 6
    ffn_mult: int = 4
    max_seq_enc: int = 512
    max_seq_dec: int = 512
    dtype: Any = jnp.bfloat16
    remat: bool = True
    fused_loss: bool = True
    # Ref attention-/hidden-dropout sites, same RNG policy as
    # standalone_gpt: active only when the caller passes ``dropout_key``;
    # attention dropout runs INSIDE the flash kernel with a TP-rank-folded
    # seed (tp ranks drop independent entries of their own heads), hidden/
    # embedding dropout uses the unfolded key (same across the TP group —
    # the activations are TP-replicated).
    attention_dropout: float = 0.0
    hidden_dropout: float = 0.0
    # Megatron-SP over the tp axis (same design as GPTConfig.megatron_sp):
    # LN/residual regions run on (b, s/tp, h) sequence shards, TP blocks
    # gather on entry and reduce-scatter on exit. In the enc-dec pipeline
    # this also shrinks the ring p2p tensors AND the cross-attention
    # memory broadcast by tp.
    megatron_sp: bool = False
    # T5's signature position scheme (opt-in): bucketed relative position
    # biases added to the attention logits INSIDE the flash kernel
    # (ops/attention.py bias path) — bidirectional buckets for encoder
    # self-attention, causal buckets for decoder self-attention, none for
    # cross-attention, one (buckets, heads) table per stack shared across
    # its layers (the T5 layout; heads split over tp). When enabled the
    # learned absolute position tables are skipped (T5 has none).
    relative_position_bias: bool = False
    rel_pos_buckets: int = 32
    rel_pos_max_distance: int = 128
    # T5's encoder-final LayerNorm (opt-in). Applied to the memory at the
    # point of decoder consumption rather than inside the encoder ring:
    # every decoder layer reads the same broadcast memory, so normalizing
    # it once before the decoder stack is EXACTLY the paper's
    # normalize-at-encoder-exit — while the enc pipeline ring keeps its
    # uniform stage function (the reason the trim existed).
    encoder_final_ln: bool = False

    @property
    def ffn_hidden(self) -> int:
        return self.ffn_mult * self.hidden

    @property
    def head_dim(self) -> int:
        return self.hidden // self.num_heads

    def validate(self, tp: int = 1) -> None:
        if self.hidden % self.num_heads:
            raise ValueError("hidden must be divisible by num_heads")
        for name, dim in (("vocab_size", self.vocab_size),
                          ("num_heads", self.num_heads),
                          ("ffn_hidden", self.ffn_hidden)):
            if dim % tp:
                raise ValueError(f"{name} ({dim}) not divisible by tp ({tp})")
        if self.megatron_sp and (self.max_seq_enc % tp
                                 or self.max_seq_dec % tp):
            raise ValueError(
                f"megatron_sp needs max_seq_enc ({self.max_seq_enc}) and "
                f"max_seq_dec ({self.max_seq_dec}) divisible by tp ({tp})")
        if self.relative_position_bias:
            if self.rel_pos_buckets % 2:
                raise ValueError("rel_pos_buckets must be even (half the "
                                 "buckets serve each direction in the "
                                 "bidirectional encoder scheme)")
            if self.rel_pos_max_distance <= self.rel_pos_buckets // 2:
                # the log-spaced range needs max_distance > max_exact for
                # BOTH schemes (decoder max_exact = buckets/2); at or
                # below it the bucket formula divides by log(<=1)
                raise ValueError(
                    f"rel_pos_max_distance ({self.rel_pos_max_distance}) "
                    f"must exceed rel_pos_buckets/2 "
                    f"({self.rel_pos_buckets // 2})")


# ---------------------------------------------------------------------------
# relative position bias (T5 scheme: log-spaced distance buckets)

def _rel_pos_bucket(rel, *, bidirectional: bool, num_buckets: int,
                    max_distance: int):
    """Bucket index for ``rel = k_pos - q_pos`` (int32 array).

    The T5 bucketing (paper §2.1): exact buckets for small distances, one
    log-spaced bucket per range up to ``max_distance``, everything farther
    in the last bucket; bidirectional splits the buckets between the two
    sign halves, unidirectional (decoder) buckets only the past.
    """
    ret = jnp.zeros_like(rel)
    if bidirectional:
        num_buckets //= 2
        ret = ret + (rel > 0).astype(jnp.int32) * num_buckets
        rel = jnp.abs(rel)
    else:
        rel = -jnp.minimum(rel, 0)
    max_exact = num_buckets // 2
    is_small = rel < max_exact
    # log-spaced: position max_exact..max_distance maps onto the remaining
    # buckets; the +1e-6 keeps log finite at rel == 0 (masked by is_small)
    val_large = max_exact + (
        jnp.log(rel.astype(jnp.float32) / max_exact + 1e-6)
        / math.log(max_distance / max_exact)
        * (num_buckets - max_exact)).astype(jnp.int32)
    val_large = jnp.minimum(val_large, num_buckets - 1)
    return ret + jnp.where(is_small, rel, val_large)


def t5_relative_bias(table_local, sq: int | None = None,
                     sk: int | None = None, *, bidirectional: bool,
                     cfg: T5Config, qpos=None, kpos=None):
    """(heads_local, sq, sk) fp32 additive logit bias from the local
    (buckets, heads_local) table shard — feeds ``flash_attention(bias=)``.
    Inside shard_map the table param is already the TP head shard, so each
    rank builds exactly its own heads' bias. Pass explicit ``qpos``/
    ``kpos`` (global position arrays) instead of ``sq``/``sk`` to build a
    ring-SP strip — this device's Q rows against all global key columns."""
    if qpos is None:
        qpos = jnp.arange(sq, dtype=jnp.int32)
    if kpos is None:
        kpos = jnp.arange(sk, dtype=jnp.int32)
    buckets = _rel_pos_bucket(
        kpos[None, :] - qpos[:, None], bidirectional=bidirectional,
        num_buckets=cfg.rel_pos_buckets,
        max_distance=cfg.rel_pos_max_distance)
    return table_local.astype(jnp.float32)[buckets].transpose(2, 0, 1)


def _init_rel_tables(rng, cfg: T5Config) -> Pytree:
    dt = cfg.dtype
    kq, kk = jax.random.split(rng)
    shape = (cfg.rel_pos_buckets, cfg.num_heads)
    return {
        "rel_enc": (jax.random.normal(kq, shape) * 0.02).astype(dt),
        "rel_dec": (jax.random.normal(kk, shape) * 0.02).astype(dt),
    }


# ---------------------------------------------------------------------------
# init (global shapes)

def _mlp_params(ks, cfg: T5Config, out_std: float) -> Pytree:
    h, f, dt = cfg.hidden, cfg.ffn_hidden, cfg.dtype
    return {
        "fc1_kernel": (jax.random.normal(ks[0], (h, f)) * 0.02).astype(dt),
        "fc1_bias": jnp.zeros((f,), dt),
        "fc2_kernel": (jax.random.normal(ks[1], (f, h)) * out_std).astype(dt),
        "fc2_bias": jnp.zeros((h,), dt),
    }


def _init_enc_layer(rng, cfg: T5Config) -> Pytree:
    h, dt = cfg.hidden, cfg.dtype
    ks = jax.random.split(rng, 4)
    out_std = 0.02 / math.sqrt(2.0 * cfg.enc_layers)
    return {
        "ln1_w": jnp.ones((h,), dt), "ln1_b": jnp.zeros((h,), dt),
        "qkv_kernel": (jax.random.normal(ks[0], (h, 3 * h)) * 0.02).astype(dt),
        "qkv_bias": jnp.zeros((3 * h,), dt),
        "out_kernel": (jax.random.normal(ks[1], (h, h)) * out_std).astype(dt),
        "out_bias": jnp.zeros((h,), dt),
        "ln2_w": jnp.ones((h,), dt), "ln2_b": jnp.zeros((h,), dt),
        **_mlp_params(ks[2:], cfg, out_std),
    }


def _init_dec_layer(rng, cfg: T5Config) -> Pytree:
    h, dt = cfg.hidden, cfg.dtype
    ks = jax.random.split(rng, 7)
    out_std = 0.02 / math.sqrt(2.0 * (cfg.enc_layers + cfg.dec_layers))
    return {
        "ln1_w": jnp.ones((h,), dt), "ln1_b": jnp.zeros((h,), dt),
        "qkv_kernel": (jax.random.normal(ks[0], (h, 3 * h)) * 0.02).astype(dt),
        "qkv_bias": jnp.zeros((3 * h,), dt),
        "out_kernel": (jax.random.normal(ks[1], (h, h)) * out_std).astype(dt),
        "out_bias": jnp.zeros((h,), dt),
        "ln2_w": jnp.ones((h,), dt), "ln2_b": jnp.zeros((h,), dt),
        # cross-attention: Q from decoder stream, fused KV from memory
        "q_kernel": (jax.random.normal(ks[2], (h, h)) * 0.02).astype(dt),
        "q_bias": jnp.zeros((h,), dt),
        "kv_kernel": (jax.random.normal(ks[3], (h, 2 * h)) * 0.02).astype(dt),
        "kv_bias": jnp.zeros((2 * h,), dt),
        "xout_kernel": (jax.random.normal(ks[4], (h, h)) * out_std).astype(dt),
        "xout_bias": jnp.zeros((h,), dt),
        "ln3_w": jnp.ones((h,), dt), "ln3_b": jnp.zeros((h,), dt),
        **_mlp_params(ks[5:], cfg, out_std),
    }


def init_t5_params(rng, cfg: T5Config) -> Pytree:
    """Global-shape pytree ``{"embed", "enc_layers" [Le], "dec_layers"
    [Ld], "head"}``; shared token table, tied LM head (the T5 convention)."""
    cfg.validate()
    ke, kenc, kdec = jax.random.split(rng, 3)
    enc = jax.tree.map(lambda *xs: jnp.stack(xs), *[
        _init_enc_layer(k, cfg)
        for k in jax.random.split(kenc, cfg.enc_layers)])
    dec = jax.tree.map(lambda *xs: jnp.stack(xs), *[
        _init_dec_layer(k, cfg)
        for k in jax.random.split(kdec, cfg.dec_layers)])
    dt = cfg.dtype
    embed = {
        "tok": (jax.random.normal(ke, (cfg.vocab_size, cfg.hidden))
                * 0.02).astype(dt),
    }
    if cfg.encoder_final_ln:
        embed["enc_ln_w"] = jnp.ones((cfg.hidden,), dt)
        embed["enc_ln_b"] = jnp.zeros((cfg.hidden,), dt)
    if cfg.relative_position_bias:
        # T5 proper: no absolute positions; one rel-bias table per stack
        embed.update(_init_rel_tables(jax.random.fold_in(ke, 3), cfg))
    else:
        embed["pos_enc"] = (jax.random.normal(
            jax.random.fold_in(ke, 1), (cfg.max_seq_enc, cfg.hidden))
            * 0.02).astype(dt)
        embed["pos_dec"] = (jax.random.normal(
            jax.random.fold_in(ke, 2), (cfg.max_seq_dec, cfg.hidden))
            * 0.02).astype(dt)
    return {
        "embed": embed,
        "enc_layers": enc,
        "dec_layers": dec,
        "head": {
            "ln_w": jnp.ones((cfg.hidden,), dt),
            "ln_b": jnp.zeros((cfg.hidden,), dt),
        },
    }


def _layer_specs(keys, lead) -> Pytree:
    tp_cols = {"qkv_kernel", "fc1_kernel", "q_kernel", "kv_kernel"}
    tp_col_bias = {"qkv_bias", "fc1_bias", "q_bias", "kv_bias"}
    tp_rows = {"out_kernel", "fc2_kernel", "xout_kernel"}
    out = {}
    for k in keys:
        if k in tp_cols:
            out[k] = P(*lead, None, TP_AXIS)
        elif k in tp_col_bias:
            out[k] = P(*lead, TP_AXIS)
        elif k in tp_rows:
            out[k] = P(*lead, TP_AXIS, None)
        else:
            out[k] = P(*lead)
    return out


def t5_param_specs(cfg: T5Config, extra_layer_lead=()) -> Pytree:
    """PartitionSpecs matching :func:`init_t5_params` (Megatron TP layout,
    same dims as ``gpt_param_specs``)."""
    lead = tuple(extra_layer_lead) + (None,)
    enc_keys = ("ln1_w", "ln1_b", "qkv_kernel", "qkv_bias", "out_kernel",
                "out_bias", "ln2_w", "ln2_b", "fc1_kernel", "fc1_bias",
                "fc2_kernel", "fc2_bias")
    dec_keys = enc_keys + ("q_kernel", "q_bias", "kv_kernel", "kv_bias",
                           "xout_kernel", "xout_bias", "ln3_w", "ln3_b")
    embed = {"tok": P(TP_AXIS, None)}
    if cfg.encoder_final_ln:
        embed["enc_ln_w"] = P()
        embed["enc_ln_b"] = P()
    if cfg.relative_position_bias:
        # heads axis TP-split: each rank holds its own heads' bias columns
        embed["rel_enc"] = P(None, TP_AXIS)
        embed["rel_dec"] = P(None, TP_AXIS)
    else:
        embed["pos_enc"] = P()
        embed["pos_dec"] = P()
    return {
        "embed": embed,
        "enc_layers": _layer_specs(enc_keys, lead),
        "dec_layers": _layer_specs(dec_keys, lead),
        "head": {"ln_w": P(), "ln_b": P()},
    }


# ---------------------------------------------------------------------------
# forward (local shards, inside shard_map)

def _heads_local(cfg: T5Config) -> int:
    return cfg.num_heads // lax.axis_size(TP_AXIS)


def _sp_size() -> int:
    try:
        return lax.axis_size(SP_AXIS)
    except NameError:
        return 1


def _bhsd(x, heads_local: int, head_dim: int):
    b, s, _ = x.shape
    return x.reshape(b, s, heads_local, head_dim).transpose(0, 2, 1, 3)


def _attn_core(q, k, v, cfg: T5Config, causal: bool, dropout_key,
               bias=None):
    """Shared attention core: ring over sp shards, flash otherwise,
    with in-kernel probability dropout (TP-folded seed) when training
    and an optional additive logit bias (relative position bias) fed to
    the kernel's bias path.
    """
    rate = cfg.attention_dropout if dropout_key is not None else 0.0
    if _sp_size() > 1:
        from apex_tpu.transformer.sequence_parallel import ring_attention

        # bias here is the ring STRIP (heads_local, s_loc, sp*s_loc) built
        # from global positions by t5_encode/t5_decode; each ring step
        # slices the arriving chunk's columns
        if rate > 0.0:
            from apex_tpu.transformer.tensor_parallel.random import (
                attention_dropout_seed,
            )

            return ring_attention(
                q, k, v, causal=causal, bias_strip=bias,
                dropout_rate=rate,
                dropout_seed=attention_dropout_seed(dropout_key))
        return ring_attention(q, k, v, causal=causal, bias_strip=bias)
    if rate > 0.0:
        from apex_tpu.transformer.tensor_parallel.random import (
            attention_dropout_seed,
        )

        seed = attention_dropout_seed(dropout_key)
        return flash_attention(q, k, v, causal=causal, dropout_rate=rate,
                               dropout_seed=seed, bias=bias)
    return flash_attention(q, k, v, causal=causal, bias=bias)


def _self_attention(p, x, cfg: T5Config, causal: bool, dropout_key=None,
                    rel_bias=None):
    b = x.shape[0]
    hl = _heads_local(cfg)
    qkv = column_parallel_linear(x, p["qkv_kernel"], p["qkv_bias"],
                                 gather_output=False,
                                 sequence_parallel=cfg.megatron_sp)
    s = qkv.shape[1]  # full sequence after the SP gather
    # per-head interleaved packing (head, {q,k,v}, head_dim) — TP-degree
    # invariant under contiguous column splits (see standalone_gpt)
    qkv = qkv.reshape(b, s, hl, 3, cfg.head_dim)
    q, k, v = (qkv[:, :, :, i].transpose(0, 2, 1, 3) for i in range(3))
    ctx = _attn_core(q, k, v, cfg, causal, dropout_key, bias=rel_bias)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, hl * cfg.head_dim)
    return row_parallel_linear(ctx, p["out_kernel"], p["out_bias"],
                               input_is_parallel=True,
                               sequence_parallel=cfg.megatron_sp)


def _cross_attention(p, x, mem, cfg: T5Config, dropout_key=None):
    """Decoder cross-attention: rectangular (s_dec × s_enc) flash core,
    Q column-parallel from the decoder stream, fused KV column-parallel
    from the encoder memory, row-parallel output (ref
    ``ParallelAttention(attention_type=cross_attn)``)."""
    b = x.shape[0]
    hl = _heads_local(cfg)
    q = column_parallel_linear(x, p["q_kernel"], p["q_bias"],
                               gather_output=False,
                               sequence_parallel=cfg.megatron_sp)
    kv = column_parallel_linear(mem, p["kv_kernel"], p["kv_bias"],
                                gather_output=False,
                                sequence_parallel=cfg.megatron_sp)
    s = q.shape[1]  # full decoder sequence after the SP gather
    kv = kv.reshape(b, kv.shape[1], hl, 2, cfg.head_dim)
    k, v = (kv[:, :, :, i].transpose(0, 2, 1, 3) for i in range(2))
    # cross-attention rides the rectangular (s_dec x s_enc) ring under sp
    ctx = _attn_core(_bhsd(q, hl, cfg.head_dim), k, v, cfg, False,
                     dropout_key)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, hl * cfg.head_dim)
    return row_parallel_linear(ctx, p["xout_kernel"], p["xout_bias"],
                               input_is_parallel=True,
                               sequence_parallel=cfg.megatron_sp)


def _mlp(p, x, cfg: T5Config):
    y = column_parallel_linear(x, p["fc1_kernel"], p["fc1_bias"],
                               gather_output=False,
                               sequence_parallel=cfg.megatron_sp)
    y = jax.nn.gelu(y, approximate=True)
    return row_parallel_linear(y, p["fc2_kernel"], p["fc2_bias"],
                               input_is_parallel=True,
                               sequence_parallel=cfg.megatron_sp)


def _maybe_hidden_dropout(x, cfg: T5Config, key, salt: int):
    if key is None or cfg.hidden_dropout <= 0.0:
        return x
    from apex_tpu.transformer.testing.standalone_gpt import (
        _hidden_dropout,
        _hidden_key,
    )

    # _hidden_key is the ONE shard-decorrelation site: it folds the SP
    # rank under ring-sp and the TP rank under megatron_sp — each rank
    # holds a DIFFERENT seq shard, so an unfolded key would repeat one
    # mask across the sequence with period s/sp resp. s/tp
    return _hidden_dropout(x, cfg.hidden_dropout,
                           _hidden_key(jax.random.fold_in(key, salt), cfg))


def enc_layer_fn(p, x, cfg: T5Config, dropout_key=None, rel_bias=None):
    k = dropout_key
    a = _self_attention(p, layer_norm(x, p["ln1_w"], p["ln1_b"]), cfg,
                        causal=False,
                        dropout_key=None if k is None
                        else jax.random.fold_in(k, 0),
                        rel_bias=rel_bias)
    x = x + _maybe_hidden_dropout(a, cfg, k, 1)
    m = _mlp(p, layer_norm(x, p["ln2_w"], p["ln2_b"]), cfg)
    return x + _maybe_hidden_dropout(m, cfg, k, 2)


def dec_layer_fn(p, x, mem, cfg: T5Config, dropout_key=None, rel_bias=None):
    k = dropout_key
    a = _self_attention(p, layer_norm(x, p["ln1_w"], p["ln1_b"]), cfg,
                        causal=True,
                        dropout_key=None if k is None
                        else jax.random.fold_in(k, 0),
                        rel_bias=rel_bias)
    x = x + _maybe_hidden_dropout(a, cfg, k, 1)
    # cross-attention carries NO position bias (the T5 scheme)
    c = _cross_attention(p, layer_norm(x, p["ln2_w"], p["ln2_b"]), mem, cfg,
                         dropout_key=None if k is None
                         else jax.random.fold_in(k, 3))
    x = x + _maybe_hidden_dropout(c, cfg, k, 4)
    m = _mlp(p, layer_norm(x, p["ln3_w"], p["ln3_b"]), cfg)
    return x + _maybe_hidden_dropout(m, cfg, k, 2)


def _scan_layers(layer_fn, layer_params, x, cfg, *extra, dropout_key=None):
    """scan the [L]-stacked layer params (remat per layer, the
    standalone_gpt recipe). ``cfg`` is closed over, NOT passed through the
    checkpoint boundary — jax.checkpoint would flatten it as a traced
    argument. With ``dropout_key``, each layer gets a fold_in-derived key
    (the standalone_gpt per-layer stream)."""
    has_drop = dropout_key is not None

    def apply(lp, h, key, *ex):
        return layer_fn(lp, h, *ex, cfg,
                        dropout_key=key if has_drop else None)

    fn = jax.checkpoint(apply) if cfg.remat else apply

    n_layers = jax.tree.leaves(layer_params)[0].shape[0]
    if has_drop:
        keys = jax.vmap(lambda i: jax.random.fold_in(dropout_key, i))(
            jnp.arange(n_layers))
    else:
        keys = jnp.zeros((n_layers, 2), jnp.uint32)

    def body(h, lp_key):
        lp, key = lp_key
        return fn(lp, h, key, *extra), None

    out, _ = lax.scan(body, x, (layer_params, keys))
    return out


def _embed(embed, tokens, pos_table, megatron_sp: bool = False):
    """Token (+ optional absolute position) embedding. ``pos_table`` is
    None under ``relative_position_bias`` — T5 proper has no absolute
    positions; the layers add bucketed logit biases instead."""
    s_loc = tokens.shape[1]
    if megatron_sp:
        tp_size = lax.axis_size(TP_AXIS)
        if s_loc % tp_size:
            # validate() only sees max_seq; check the actual sequence here
            # instead of letting psum_scatter fail deep in the trace (the
            # standalone_gpt.embed_tokens guard)
            raise ValueError(
                f"megatron_sp needs the sequence length ({s_loc}) "
                f"divisible by tp ({tp_size})")
    h = vocab_parallel_embedding(tokens, embed["tok"],
                                 sequence_parallel=megatron_sp)
    if pos_table is None:
        return h
    sp = _sp_size()
    start = lax.axis_index(SP_AXIS) * s_loc if sp > 1 else 0
    pos = lax.dynamic_slice_in_dim(pos_table, start, s_loc, 0) \
        if sp > 1 else pos_table[:s_loc]
    if megatron_sp:
        from apex_tpu.transformer.tensor_parallel.mappings import (
            scatter_to_sequence_parallel_region,
        )

        pos = scatter_to_sequence_parallel_region(pos, seq_axis=0)
    return h + pos[None, :, :].astype(h.dtype)


def _match_vma(x, ref):
    """pcast ``x`` to also vary over ``ref``'s manual axes — a bias passed
    into the layer scan must start with the varying-axis set its cotangent
    will come back with (dp via the attention inputs), or the transposed
    scan's carry check trips. Thin alias over the ring module's helper so
    the vma-alignment logic lives in one place."""
    from apex_tpu.transformer.sequence_parallel import _vary_like_inputs

    return _vary_like_inputs(x, ref)


def _rel_or_strip(table_local, s_tok: int, *, bidirectional: bool,
                  cfg: T5Config):
    """Build the layer-shared rel bias once per stack: the square
    (hl, s, s) bias at sp == 1 (``s_tok`` is the full sequence there —
    Megatron-SP scatters inside the TP layers), or the ring STRIP
    (hl, s_loc, sp*s_loc) from this shard's global positions at sp > 1
    (``s_tok`` is the local shard)."""
    sp = _sp_size()
    if sp == 1:
        return t5_relative_bias(table_local, s_tok, s_tok,
                                bidirectional=bidirectional, cfg=cfg)
    my = lax.axis_index(SP_AXIS)
    qpos = my * s_tok + jnp.arange(s_tok, dtype=jnp.int32)
    kpos = jnp.arange(sp * s_tok, dtype=jnp.int32)
    return t5_relative_bias(table_local, bidirectional=bidirectional,
                            cfg=cfg, qpos=qpos, kpos=kpos)


def t5_encode(params, enc_tokens, cfg: T5Config, dropout_key=None):
    rel_on = cfg.relative_position_bias
    x = _embed(params["embed"], enc_tokens,
               None if rel_on else params["embed"]["pos_enc"],
               cfg.megatron_sp)
    x = _maybe_hidden_dropout(
        x, cfg, None if dropout_key is None
        else jax.random.fold_in(dropout_key, 100), 0)
    rel = (_match_vma(_rel_or_strip(params["embed"]["rel_enc"],
                                    enc_tokens.shape[1],
                                    bidirectional=True, cfg=cfg), x)
           if rel_on else None)
    return _scan_layers(
        lambda lp, h, rel_bias, c, dropout_key=None: enc_layer_fn(
            lp, h, c, dropout_key=dropout_key, rel_bias=rel_bias),
        params["enc_layers"], x, cfg, rel, dropout_key=dropout_key)


def t5_decode(params, dec_tokens, mem, cfg: T5Config, dropout_key=None):
    rel_on = cfg.relative_position_bias
    if cfg.encoder_final_ln:
        # normalize the memory at the point of consumption — exactly the
        # paper's encoder-exit LayerNorm (see T5Config.encoder_final_ln)
        mem = layer_norm(mem, params["embed"]["enc_ln_w"],
                         params["embed"]["enc_ln_b"])
    x = _embed(params["embed"], dec_tokens,
               None if rel_on else params["embed"]["pos_dec"],
               cfg.megatron_sp)
    x = _maybe_hidden_dropout(
        x, cfg, None if dropout_key is None
        else jax.random.fold_in(dropout_key, 101), 0)
    rel = (_match_vma(_rel_or_strip(params["embed"]["rel_dec"],
                                    dec_tokens.shape[1],
                                    bidirectional=False, cfg=cfg), x)
           if rel_on else None)
    return _scan_layers(
        lambda lp, h, m, rel_bias, c, dropout_key=None: dec_layer_fn(
            lp, h, m, c, dropout_key=dropout_key, rel_bias=rel_bias),
        params["dec_layers"], x, cfg, mem, rel, dropout_key=dropout_key)


def t5_loss(params, enc_tokens, dec_tokens, targets, cfg: T5Config,
            dropout_key=None):
    """Sequential (non-pipelined) enc-dec loss; the ground truth the
    pipeline schedule is tested against, and the TP-only training path.
    ``dropout_key`` activates cfg's dropout rates (training mode), with
    distinct per-side/per-layer streams."""
    ke = kd = None
    if dropout_key is not None:
        ke = jax.random.fold_in(dropout_key, 0)
        kd = jax.random.fold_in(dropout_key, 1)
    mem = t5_encode(params, enc_tokens, cfg, dropout_key=ke)
    x = t5_decode(params, dec_tokens, mem, cfg, dropout_key=kd)
    head = params["head"]
    if cfg.fused_loss:
        from apex_tpu.transformer.testing.standalone_gpt import (
            fused_head_loss,
        )

        return fused_head_loss(params["embed"]["tok"], head["ln_w"],
                               head["ln_b"], x, targets,
                               gather_sequence=cfg.megatron_sp)
    from apex_tpu.transformer.tensor_parallel.mappings import (
        copy_to_tensor_model_parallel_region,
        gather_from_sequence_parallel_region,
    )

    x = layer_norm(x, head["ln_w"], head["ln_b"])
    if cfg.megatron_sp:
        x = gather_from_sequence_parallel_region(x)
    x = copy_to_tensor_model_parallel_region(x)
    logits = jnp.einsum("bsh,vh->bsv", x, params["embed"]["tok"])
    return jnp.mean(vocab_parallel_cross_entropy(logits, targets))


# ---------------------------------------------------------------------------
# pipeline wiring (EncDecPipelineSpec contract)

def t5_pipeline_params(rng, cfg: T5Config, pp: int) -> Pytree:
    """Regroup :func:`init_t5_params` into the enc-dec driver layout
    ``{"embed", "enc_stages" [pp, Le/pp, ...], "dec_stages"
    [pp, Ld/pp, ...], "head"}`` — every stage holds one encoder AND one
    decoder chunk (two-phase ring, ``fwd_bwd_enc_dec.py``)."""
    if cfg.enc_layers % pp or cfg.dec_layers % pp:
        raise ValueError("enc_layers and dec_layers must be divisible by pp")
    p = init_t5_params(rng, cfg)
    regroup = lambda a, n: a.reshape((pp, n // pp) + a.shape[1:])  # noqa: E731
    head = dict(p["head"])
    # the driver's loss head sees only the "head" group, so the pipeline
    # fixture unties the LM projection (initialized from the shared table —
    # the grads then flow separately, as with GPT's untied pipeline head)
    head["lm_rows"] = p["embed"]["tok"]
    enc_stages = jax.tree.map(
        lambda a: regroup(a, cfg.enc_layers), p["enc_layers"])
    dec_stages = jax.tree.map(
        lambda a: regroup(a, cfg.dec_layers), p["dec_layers"])
    embed = p["embed"]
    # stage functions can't reach the embed group, so stage-consumed
    # extras (rel tables, the encoder-final LN) become per-stage copies
    # (initialized equal) — the same untying the pipeline fixture applies
    # to the LM head: exact forward parity with the sequential model,
    # per-stage gradients. The embed copies are dropped (they would sit
    # in optimizer state and checkpoints as frozen dead weights).
    tile = lambda a: jnp.broadcast_to(  # noqa: E731
        a[None], (pp,) + a.shape).copy()
    drop = []
    if cfg.relative_position_bias:
        enc_stages = {"layers": enc_stages, "rel": tile(embed["rel_enc"])}
        dec_stages = {"layers": dec_stages, "rel": tile(embed["rel_dec"])}
        drop += ["rel_enc", "rel_dec"]
    if cfg.encoder_final_ln:
        if not cfg.relative_position_bias:  # not already {"layers", ...}
            dec_stages = {"layers": dec_stages}
        dec_stages["enc_ln_w"] = tile(embed["enc_ln_w"])
        dec_stages["enc_ln_b"] = tile(embed["enc_ln_b"])
        drop += ["enc_ln_w", "enc_ln_b"]
    if drop:
        embed = {k: v for k, v in embed.items() if k not in drop}
    return {
        "embed": embed,
        "enc_stages": enc_stages,
        "dec_stages": dec_stages,
        "head": head,
    }


def t5_pipeline_specs_tree(cfg: T5Config) -> Pytree:
    specs = t5_param_specs(cfg, extra_layer_lead=(PP_AXIS,))
    head = dict(specs["head"])
    head["lm_rows"] = P(TP_AXIS, None)
    enc_stages, dec_stages = specs["enc_layers"], specs["dec_layers"]
    embed = specs["embed"]
    drop = []
    if cfg.relative_position_bias:
        rel_spec = P(PP_AXIS, None, TP_AXIS)
        enc_stages = {"layers": enc_stages, "rel": rel_spec}
        dec_stages = {"layers": dec_stages, "rel": rel_spec}
        drop += ["rel_enc", "rel_dec"]
    if cfg.encoder_final_ln:
        if not cfg.relative_position_bias:  # not already {"layers", ...}
            dec_stages = {"layers": dec_stages}
        dec_stages["enc_ln_w"] = P(PP_AXIS, None)
        dec_stages["enc_ln_b"] = P(PP_AXIS, None)
        drop += ["enc_ln_w", "enc_ln_b"]
    if drop:
        embed = {k: v for k, v in embed.items() if k not in drop}
    return {
        "embed": embed,
        "enc_stages": enc_stages,
        "dec_stages": dec_stages,
        "head": head,
    }


def t5_enc_dec_spec(cfg: T5Config, dropout: bool = False) \
        -> EncDecPipelineSpec:
    """With ``dropout`` the stage functions take the schedule's
    per-microbatch key (``takes_dropout_key``): the side salt (enc 0 /
    dec 1, mirroring ``t5_loss``) and the PP rank are folded here —
    encoder and decoder chunks share a stage's pp rank, and stage-local
    layer indices restart at 0 per stage."""
    rel_on = cfg.relative_position_bias

    def _stage_key(key, side_salt: int):
        key = jax.random.fold_in(key, side_salt)
        return jax.random.fold_in(key, lax.axis_index(PP_AXIS))

    def _enc_embed(embed, enc_tokens, key=None):
        x = _embed(embed, enc_tokens,
                   None if rel_on else embed["pos_enc"], cfg.megatron_sp)
        # same embedding-dropout stream as the sequential path
        # (t5_encode, salt 100)
        return _maybe_hidden_dropout(
            x, cfg, None if key is None
            else jax.random.fold_in(key, 100), 0)

    def _enc_stage(stage_params, h, key=None):
        dk = None if key is None else _stage_key(key, 0)
        if rel_on:
            s = h.shape[1] * (lax.axis_size(TP_AXIS) if cfg.megatron_sp
                              else 1)
            rel = _match_vma(_rel_or_strip(stage_params["rel"], s,
                                           bidirectional=True, cfg=cfg), h)
            return _scan_layers(
                lambda lp, x, rb, c, dropout_key=None: enc_layer_fn(
                    lp, x, c, dropout_key=dropout_key, rel_bias=rb),
                stage_params["layers"], h, cfg, rel, dropout_key=dk)
        return _scan_layers(
            lambda lp, x, c, dropout_key=None: enc_layer_fn(
                lp, x, c, dropout_key=dropout_key),
            stage_params, h, cfg, dropout_key=dk)

    def _dec_embed(embed, dec_tokens, key=None):
        x = _embed(embed, dec_tokens,
                   None if rel_on else embed["pos_dec"], cfg.megatron_sp)
        # t5_decode's embedding-dropout stream (salt 101)
        return _maybe_hidden_dropout(
            x, cfg, None if key is None
            else jax.random.fold_in(key, 101), 0)

    def _dec_stage(stage_params, h, mem, key=None):
        dk = None if key is None else _stage_key(key, 1)
        if cfg.encoder_final_ln:
            # every stage normalizes the same broadcast memory with its
            # copy of the encoder-final LN — identical to normalizing
            # once at encoder exit (see T5Config.encoder_final_ln)
            mem = layer_norm(mem, stage_params["enc_ln_w"],
                             stage_params["enc_ln_b"])
        if rel_on:
            s = h.shape[1] * (lax.axis_size(TP_AXIS) if cfg.megatron_sp
                              else 1)
            rel = _match_vma(_rel_or_strip(stage_params["rel"], s,
                                           bidirectional=False, cfg=cfg), h)
            return _scan_layers(
                lambda lp, x, m, rb, c, dropout_key=None: dec_layer_fn(
                    lp, x, m, c, dropout_key=dropout_key, rel_bias=rb),
                stage_params["layers"], h, cfg, mem, rel, dropout_key=dk)
        layers = (stage_params["layers"] if cfg.encoder_final_ln
                  else stage_params)
        return _scan_layers(
            lambda lp, x, m, c, dropout_key=None: dec_layer_fn(
                lp, x, m, c, dropout_key=dropout_key),
            layers, h, cfg, mem, dropout_key=dk)

    if dropout:
        enc_embed_fn, dec_embed_fn = _enc_embed, _dec_embed
        enc_stage_fn, dec_stage_fn = _enc_stage, _dec_stage
    else:
        def enc_embed_fn(embed, enc_tokens):
            return _enc_embed(embed, enc_tokens)

        def dec_embed_fn(embed, dec_tokens):
            return _dec_embed(embed, dec_tokens)

        def enc_stage_fn(stage_params, h):
            return _enc_stage(stage_params, h)

        def dec_stage_fn(stage_params, h, mem):
            return _dec_stage(stage_params, h, mem)

    def loss_fn(head, h, targets):
        # per-microbatch mean vocab-parallel CE over the untied head rows
        # (see t5_pipeline_params for why the pipeline fixture unties)
        from apex_tpu.transformer.tensor_parallel.mappings import (
            copy_to_tensor_model_parallel_region,
            gather_from_sequence_parallel_region,
        )

        x = layer_norm(h, head["ln_w"], head["ln_b"])
        if cfg.megatron_sp:
            x = gather_from_sequence_parallel_region(x)
        x = copy_to_tensor_model_parallel_region(x)
        logits = jnp.einsum("bsh,vh->bsv", x, head["lm_rows"])
        return jnp.mean(vocab_parallel_cross_entropy(logits, targets))

    return EncDecPipelineSpec(enc_embed_fn, enc_stage_fn, dec_embed_fn,
                              dec_stage_fn, loss_fn,
                              takes_dropout_key=dropout)
