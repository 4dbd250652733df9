"""Decode-path attention against the paged KV cache + the GPT serve programs.

Two halves:

* **paged attention** — attention where K/V live in the block-paged
  pools (``apex_tpu.serve.kv_cache``): a pure-JAX reference (gather
  through the block tables, then exactly the
  ``ops.attention.attention_reference`` math — fp32 accumulation, NEG_INF
  masking) and a Pallas gather-attend kernel that walks each slot's block
  table with scalar-prefetched indices (the ``ops/attention_varlen.py``
  ``PrefetchScalarGridSpec`` idiom) and an online-softmax accumulator (the
  ``ops/attention.py`` forward scheme, no lse output — decode never
  differentiates). The MPK case (arXiv 2512.22219) is why this is one
  kernel and the whole decode step one compiled program: at q_len=1 the
  work per op is tiny and dispatch dominates.

* **serve programs** — one unified :func:`gpt_paged_forward` (q tokens
  per slot against the paged cache, per-row math independent of q) with
  three thin wrappers that are the engine's ONLY compiled programs:
  :func:`gpt_decode_step` (q=1), :func:`gpt_verify_step` (q=k+1 — verify
  k drafted tokens in one call, amortizing the dispatch-bound decode
  step k-fold exactly the way the fused computation-collective ops of
  arXiv 2305.06942 amortize launch overhead), and
  :func:`gpt_prefill_chunk` (one slot, q=chunk — the fixed-size prefill
  chunk that replaced the PR-5 bucket ladder). :func:`gpt_prefill` (the
  full-prompt flash-attention prefill) remains as the COLD-PATH ORACLE
  the chunked/cached/speculative streams are tested against. All are
  built from the SAME ``standalone_gpt`` parameter pytree (tied LM head,
  per-head interleaved QKV packing, ``ops.layer_norm``/``flash_attention``
  cores). TP is axis-optional: with ``tp_axis`` bound (inside a mesh
  program) the projections ride ``tensor_parallel``'s column/row-parallel
  layers — heads sharded, the flash-prefill row-parallel exits honoring
  ``cfg.overlap_comm`` (the decomposed ``comm.overlap`` rings) — and the
  vocab-sharded logits are all-gathered for sampling; with ``tp_axis=None``
  (single device, stock-jax serving) the same math runs as plain dots.
  The q_len=1 decode step's TP exits stay monolithic by design — a
  single-row GEMM has no flops to hide a ring behind — while the
  q_len>1 paths (speculative verify, chunked prefill) honor
  ``cfg.overlap_comm`` exactly like the flash prefill: k+1 or
  chunk-many rows give the ``comm.overlap.matmul_all_reduce`` ring
  partial GEMMs to travel behind (``apex_tpu.serve.sharded`` is the
  plan-driven engine builder that wires this up).

Layers scan over the stacked layer params with the per-layer cache pools
riding the scan's xs/ys — one compiled layer body regardless of depth,
and the updated pools restack for donation.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.monitor.trace import span
from apex_tpu.ops._pallas_util import compiled_backend as _compiled_backend
from apex_tpu.ops._pallas_util import sds as _sds
from apex_tpu.ops.attention import NEG_INF, attention_reference, flash_attention
from apex_tpu.ops.layer_norm import layer_norm
from apex_tpu.parallel.mesh import axis_size as _axis_size
from apex_tpu.serve.kv_cache import KVCacheConfig, gather_kv, paged_write

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Pytree = Any


# ---------------------------------------------------------------------------
# Paged attention — reference


def paged_attention_reference(q, cache_layer, cfg: KVCacheConfig,
                              block_tables, ctx_lens,
                              scale: Optional[float] = None):
    """q (n, H, D) against one layer's paged pools; (n,) ``ctx_lens`` tokens
    of context per slot. Returns (n, H, D) in q.dtype.

    Math is EXACTLY ``attention_reference`` over the gathered K/V with a
    ``kpos >= ctx_len`` mask — the fp32-exact ground truth the Pallas
    kernel and the engine's decode step are tested against. Slots with
    ``ctx_len == 0`` produce a finite junk row (uniform weights over
    NEG_INF-masked scores), never NaN — callers mask by activity.
    """
    k, v = gather_kv(cache_layer, cfg, block_tables)  # (n, H, S, D)
    s_tot = k.shape[2]
    kpos = jnp.arange(s_tot)
    mask = kpos[None, None, None, :] >= ctx_lens[:, None, None, None]
    o = attention_reference(q[:, :, None], k, v, mask=mask, scale=scale)
    return o[:, :, 0]


# ---------------------------------------------------------------------------
# Paged attention — Pallas gather-attend kernel. Grid (slots, blocks); the
# block table rides scalar prefetch so each (slot, j) step DMAs pool block
# ``table[slot, j]`` directly; dead blocks (past the context) clamp their
# fetch to the last live block (Mosaic elides the repeated copy — the
# ops/attention.py causal-clamp trick) and skip compute.


def _nibble_dequant(packed, s, group):
    """In-kernel int4 pool dequant: (.., bs, D/2) packed uint8 codes +
    (.., bs, D/group) bf16 group scales -> (.., bs, D) fp32. Bit-for-bit
    the ``kv_cache._dequant_rows_int4`` math — ``unpack_int4`` is pure
    jnp bit ops, so it traces straight into the Pallas kernel and the
    codes/scales never round-trip through HBM as fp."""
    from apex_tpu.comm.quantize import unpack_int4

    codes = unpack_int4(packed)
    d = codes.shape[-1]
    g = codes.reshape(codes.shape[:-1] + (d // group, group))
    out = g.astype(jnp.float32) * s.astype(jnp.float32)[..., None]
    return out.reshape(codes.shape)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *refs,
                  scale, block_size, nb, quantized, kv_bits=8, kv_group=0):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ctx = len_ref[i]

    @pl.when(j * block_size < ctx)
    def _compute():
        q = q_ref[0]                       # (H, 1, D)
        k = k_ref[:, 0]                    # (H, bs, D) | packed (H, bs, D/2)
        v = v_ref[:, 0]
        if quantized and kv_bits == 4:
            k = _nibble_dequant(k, ks_ref[:, 0], kv_group)
            v = _nibble_dequant(v, vs_ref[:, 0], kv_group)
        elif quantized:
            k = k.astype(jnp.float32) * ks_ref[:, 0, 0][..., None]
            v = v.astype(jnp.float32) * vs_ref[:, 0, 0][..., None]
        dt = jnp.promote_types(q.dtype, k.dtype)  # Mosaic: one dot dtype
        s = lax.dot_general(
            q.astype(dt), k.astype(dt), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (H, 1, bs)
        kpos = j * block_size + lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        s = jnp.where(kpos >= ctx, NEG_INF, s)
        m_prev = m_scr[:, :, :1]
        l_prev = l_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # (H, 1, D)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nb - 1)
    def _finish():
        l = l_scr[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # ctx==0 slot: emit zeros
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def _paged_pallas(q, cache_layer, cfg: KVCacheConfig, block_tables,
                  ctx_lens, scale, interpret):
    n, h, d = q.shape
    nb = block_tables.shape[1]
    bs = cfg.block_size
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    lens = ctx_lens.astype(jnp.int32)

    def blk_index(i, j, bt, ln):
        # clamp dead steps at the last live block: repeated index elides
        # the DMA; max(ctx-1, 0) keeps a ctx==0 slot in range
        jl = jnp.maximum(ln[i] - 1, 0) // bs
        return (0, bt[i * nb + jnp.minimum(j, jl)], 0, 0)

    # the single query row keeps a unit q dim all the way through: Mosaic's
    # batched matmul needs a free (non-contracting) dim on both operands,
    # and a (H, D) <-> (H, 1, D) shape cast inside the kernel does not lower
    row_spec = pl.BlockSpec((1, h, 1, d), lambda i, j, bt, ln: (i, 0, 0, 0))
    dk = d // 2 if cfg.quantized and cfg.bits == 4 else d
    in_specs = [
        row_spec,
        pl.BlockSpec((h, 1, bs, dk), blk_index),
        pl.BlockSpec((h, 1, bs, dk), blk_index),
    ]
    inputs = [q[:, :, None, :], cache_layer["k"], cache_layer["v"]]
    if cfg.quantized and cfg.bits == 4:
        # group scales carry a trailing head_dim/group dim — same 4-d
        # rank as the packed code pools, same block walk
        gdim = d // cfg.kv_group
        in_specs += [pl.BlockSpec((h, 1, bs, gdim), blk_index),
                     pl.BlockSpec((h, 1, bs, gdim), blk_index)]
        inputs += [cache_layer["k_scale"], cache_layer["v_scale"]]
    elif cfg.quantized:
        in_specs += [pl.BlockSpec((h, 1, 1, bs), blk_index),
                     pl.BlockSpec((h, 1, 1, bs), blk_index)]
        inputs += [_scale_rows(cache_layer["k_scale"]),
                   _scale_rows(cache_layer["v_scale"])]
    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=bs, nb=nb,
        quantized=cfg.quantized, kv_bits=cfg.bits if cfg.quantized else 8,
        kv_group=cfg.kv_group if cfg.quantized else 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, nb),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((h, 1, 128), jnp.float32),
            pltpu.VMEM((h, 1, 128), jnp.float32),
            pltpu.VMEM((h, 1, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=_sds((n, h, 1, d), q.dtype, q),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bt_flat, lens, *inputs)[:, :, 0, :]


def _scale_rows(scale_pool):
    """int8 scale pool (H, B, bs) -> (H, B, 1, bs): a free bitcast that
    makes one pool block's scales a tile-legal BlockSpec (a (H, 1, bs)
    block of the 3-d pool has second-to-last dim 1, neither 8-divisible
    nor the full dim — the varlen ``_seg_wide`` class of bug)."""
    return scale_pool[:, :, None, :]


def paged_kernel_refusal(cfg: KVCacheConfig,
                         compiled: bool) -> Optional[str]:
    """Why the Pallas gather-attend kernel cannot serve this pool —
    ``None`` when it can. ``compiled``: whether the kernel would go through
    Mosaic (the interpreter takes everything but a misaligned head_dim)."""
    if cfg.head_dim % 8 != 0:
        return (f"head_dim {cfg.head_dim} % 8 != 0 (sublane alignment; pad "
                f"head_dim to a multiple of 8 to get the kernel)")
    if compiled and cfg.quantized and cfg.bits == 4:
        return ("int4 pools: the in-kernel nibble unpack interleaves lanes "
                "through a shape cast Mosaic refuses (infer-vector-layout: "
                "unsupported shape cast, tpu.reshape vector<HxbsxD/2xi8> -> "
                "vector<HxbsxD/2x1xi8>); the compiled kernel serves fp and "
                "int8 pools")
    return None


# refusals already logged (warn ONCE per reason: a 10x slower serve run
# must be diagnosable from the log, not only from the bench line)
_FALLBACK_WARNED: set = set()


def _warn_reference_fallback(reason: str) -> None:
    if reason in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(reason)
    from apex_tpu._logging import get_logger

    get_logger("apex_tpu.serve").warning(
        "paged_attention: falling back to the pure-JAX gather+reference "
        "path on a compiled TPU backend (expect a much slower decode "
        "step) — %s",
        reason)


def paged_attention(q, cache_layer, cfg: KVCacheConfig, block_tables,
                    ctx_lens, scale: Optional[float] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None):
    """Dispatching front door: Pallas gather-attend on compiled TPU
    backends unless :func:`paged_kernel_refusal` names a reason, the
    gather+reference path elsewhere — the ``flash_attention`` gating
    pattern. Same signature/result as :func:`paged_attention_reference`."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    compiled = _compiled_backend()
    if use_pallas is None:
        reason = paged_kernel_refusal(cfg, compiled)
        use_pallas = compiled and reason is None
        if compiled and reason is not None:
            _warn_reference_fallback(reason)
    if not use_pallas:
        if interpret is not None:
            raise ValueError(
                "interpret= only applies to the Pallas path (pass "
                "use_pallas=True to force the kernel)")
        return paged_attention_reference(q, cache_layer, cfg, block_tables,
                                         ctx_lens, scale=scale)
    if interpret is None:
        interpret = not compiled
    reason = paged_kernel_refusal(cfg, compiled=not interpret)
    if reason is not None:
        raise ValueError(f"pallas paged_attention refused: {reason}")
    return _paged_pallas(q, cache_layer, cfg, block_tables, ctx_lens,
                         scale, interpret)


# ---------------------------------------------------------------------------
# Axis-optional TP plumbing: one code path that runs as plain dots on a
# single device (tp_axis=None — the stock-jax serving case) and as the
# tensor_parallel layers inside a mesh program.


def _tp_size(tp_axis: Optional[str]) -> int:
    if tp_axis is None:
        return 1
    return _axis_size(tp_axis)


def _col(x, kernel, bias, tp_axis: Optional[str]):
    """Column-parallel projection (output-sharded, no gather)."""
    if tp_axis is None:
        y = jnp.dot(x, kernel.astype(x.dtype))
        return y + bias if bias is not None else y
    from apex_tpu.transformer.tensor_parallel.layers import (
        column_parallel_linear,
    )

    return column_parallel_linear(x, kernel, bias, gather_output=False,
                                  axis_name=tp_axis)


def _row(x, kernel, bias, tp_axis: Optional[str], overlap: bool = False):
    """Row-parallel projection (input-sharded, psum exit; ``overlap`` only
    meaningful for 3D (b, s, h) prefill activations)."""
    if tp_axis is None:
        y = jnp.dot(x, kernel.astype(x.dtype))
        return y + bias if bias is not None else y
    from apex_tpu.transformer.tensor_parallel.layers import (
        row_parallel_linear,
    )

    return row_parallel_linear(x, kernel, bias, input_is_parallel=True,
                               axis_name=tp_axis,
                               overlap_comm=overlap and x.ndim == 3)


def _embed(embed, tokens, positions, tp_axis: Optional[str]):
    """Token + position embedding at explicit positions (decode feeds one
    token per slot at its own offset — no implicit arange)."""
    if tp_axis is None:
        x = jnp.take(embed["tok"], tokens, axis=0)
    else:
        from apex_tpu.transformer.tensor_parallel.layers import (
            vocab_parallel_embedding,
        )

        x = vocab_parallel_embedding(tokens, embed["tok"],
                                     axis_name=tp_axis)
    pos = jnp.take(embed["pos"], positions, axis=0)  # OOB clamps (jnp.take)
    return x + pos.astype(x.dtype)


def serve_logits(params, x, cfg, tp_axis: Optional[str] = None):
    """Final LN + LM head -> FULL-vocab fp32 logits (sampling needs the
    global argmax/top-k, so TP-sharded logits are all-gathered here —
    unlike training, where the fused loss never materializes them)."""
    head = params["head"]
    with span("final_ln"):
        x = layer_norm(x, head["ln_w"], head["ln_b"],
                       use_pallas=cfg.ln_pallas)
    with span("lm_head"):
        if cfg.tie_embeddings:
            logits = jnp.einsum("...h,vh->...v", x,
                                params["embed"]["tok"].astype(x.dtype))
        else:
            logits = jnp.dot(x, head["lm"].astype(x.dtype))
        if tp_axis is not None:
            logits = lax.all_gather(logits, tp_axis, axis=logits.ndim - 1,
                                    tiled=True)
        return logits.astype(jnp.float32)


def _split_qkv(qkv, heads_local: int, head_dim: int):
    """Per-head interleaved unpack — the standalone_gpt packing, so serve
    reads the SAME checkpoints at any TP degree."""
    lead = qkv.shape[:-1]
    qkv = qkv.reshape(*lead, heads_local, 3, head_dim)
    return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]


def _serve_heads(cfg, tp_axis: Optional[str]) -> int:
    tp = _tp_size(tp_axis)
    if cfg.num_heads % tp:
        raise ValueError(
            f"num_heads ({cfg.num_heads}) not divisible by tp ({tp})")
    return cfg.num_heads // tp


def ensure_dense_ffn(num_experts: int) -> None:
    """The ONE MoE serving refusal (shared by every serve entry point —
    the paged forward programs and the engine constructor): the decode
    path assumes a dense FFN; routed-expert serving is ROADMAP item 5a."""
    if num_experts:
        raise NotImplementedError(
            "serve does not support MoE layers (num_experts > 0) yet — "
            "the paged decode/prefill programs assume a dense FFN, and "
            "no ServeConfig.plan residency strategy (tp/pp/fsdp, "
            "apex_tpu.serve.sharded) shards experts either: a plan moves "
            "dense weights, it does not route tokens. One refusal for "
            "both stacks; routed-expert serving is ROADMAP item 5a.")


def _check_stack_cfg(cfg, kv_cfg: KVCacheConfig, tp_axis) -> None:
    """The layer-stack-local half of the serve config check (no layer
    COUNT assertion — a PP stage's pools hold its own layer slice)."""
    ensure_dense_ffn(cfg.num_experts)
    heads_local = _serve_heads(cfg, tp_axis)
    if kv_cfg.num_heads != heads_local or kv_cfg.head_dim != cfg.head_dim:
        raise ValueError(
            f"KVCacheConfig ({kv_cfg.num_heads} heads x {kv_cfg.head_dim}) "
            f"does not match the model's local layout ({heads_local} x "
            f"{cfg.head_dim})")


def _check_serve_cfg(cfg, kv_cfg: KVCacheConfig, tp_axis) -> None:
    _check_stack_cfg(cfg, kv_cfg, tp_axis)
    if kv_cfg.num_layers != cfg.num_layers:
        raise ValueError(
            f"KVCacheConfig.num_layers ({kv_cfg.num_layers}) != "
            f"cfg.num_layers ({cfg.num_layers})")


# ---------------------------------------------------------------------------
# Full-prompt prefill: flash attention over the in-flight K/V (the cache
# is write-only here). Since the chunked-prefill engine rewrite this is
# the COLD-PATH ORACLE — the reference the chunked / prefix-cached /
# speculative engine streams are pinned against — and the TP-overlap
# showcase (3D activations give the rings flops to hide behind).


def gpt_prefill(params, tokens, prompt_len, cache, block_row,
                cfg, kv_cfg: KVCacheConfig,
                tp_axis: Optional[str] = None) -> Tuple[Pytree, jnp.ndarray]:
    """Process one prompt into the cache; return the next-token logits.

    ``tokens``: (bucket,) int32, the prompt padded to its compile bucket
    (padding ignored: causal attention means positions < prompt_len never
    see it, and padded K/V writes are dropped). ``prompt_len``: traced
    scalar. ``block_row``: (max_blocks,) int32 blocks owning this slot.
    Returns ``(cache', logits (vocab,))`` — logits at ``prompt_len - 1``,
    fp32, full vocab.
    """
    _check_serve_cfg(cfg, kv_cfg, tp_axis)
    heads_local = _serve_heads(cfg, tp_axis)
    t = tokens.shape[0]
    positions = jnp.arange(t)
    valid = positions < prompt_len
    x = _embed(params["embed"], tokens[None], positions, tp_axis)  # (1,t,h)

    def body(x, xs):
        lp, cl = xs
        h1 = layer_norm(x, lp["ln1_w"], lp["ln1_b"],
                        use_pallas=cfg.ln_pallas)
        qkv = _col(h1, lp["qkv_kernel"], lp["qkv_bias"], tp_axis)
        q, k, v = _split_qkv(qkv, heads_local, cfg.head_dim)  # (1,t,H,D)
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))  # (1,H,t,D)
        ctx = flash_attention(q, k, v, causal=True)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(1, t,
                                                heads_local * cfg.head_dim)
        a = _row(ctx, lp["out_kernel"], lp["out_bias"], tp_axis,
                 overlap=cfg.overlap_comm)
        x = x + a
        h2 = layer_norm(x, lp["ln2_w"], lp["ln2_b"],
                        use_pallas=cfg.ln_pallas)
        y = jax.nn.gelu(_col(h2, lp["fc1_kernel"], lp["fc1_bias"], tp_axis),
                        approximate=True)
        m = _row(y, lp["fc2_kernel"], lp["fc2_bias"], tp_axis,
                 overlap=cfg.overlap_comm)
        x = x + m
        cl = paged_write(cl, kv_cfg, k[0], v[0],
                         jnp.broadcast_to(block_row, (t, block_row.shape[0])),
                         positions, valid)
        return x, cl

    x, cache = lax.scan(body, x, (params["layers"], cache))
    last = jnp.take(x[0], jnp.maximum(prompt_len - 1, 0), axis=0)  # (h,)
    return cache, serve_logits(params, last, cfg, tp_axis)


# ---------------------------------------------------------------------------
# The unified paged forward: q tokens per slot through the whole stack —
# ONE compiled program per (n, q) shape. q=1 is the decode step, q=k+1 the
# speculative verify, (n=1, q=chunk) the chunked prefill. Per-ROW math is
# identical across q (each token row embeds at its own position, writes
# its K/V, then attends through the paged gather masked to its own
# context), which is exactly why speculative verification and chunked
# prefill produce BITWISE the streams sequential decode would — the
# oracle tests in tests/test_serve_prefix.py pin it.


def paged_layer_stack(x, layers, start_lens, n_valid, active, cache,
                      block_tables, cfg, kv_cfg: KVCacheConfig, *,
                      tp_axis: Optional[str] = None,
                      use_pallas: Optional[bool] = None,
                      adapters: Optional[Pytree] = None,
                      adapter_ids=None,
                      gather_layer=None
                      ) -> Tuple[jnp.ndarray, Pytree]:
    """Scan embedded activations ``x`` (n, q, h) through a STACK of
    transformer layers against their paged pools — the body of
    :func:`gpt_paged_forward`, exposed so the PP-staged serving tier
    (``serve.sharded``) can run layer SLICES: stage s streams the x'
    this returns to stage s+1 instead of feeding the LM head, and each
    stage's ``cache`` holds pools for ITS layers only (same block ids,
    shared host allocator).

    ``layers``: stacked (L, ...) layer params — or, with
    ``gather_layer``, whatever per-layer pytree that hook turns into the
    full layer dict. ``gather_layer`` is the FSDP weight-residency hook:
    the scan's xs carry resident block-aligned SHARDS and each layer's
    full weights materialize for exactly one body evaluation
    (gather-on-demand; nothing is restacked, so the gathered copy dies
    with the scan step). Returns ``(x', cache')``.
    """
    _check_stack_cfg(cfg, kv_cfg, tp_axis)
    if adapters is not None:
        if tp_axis is not None:
            raise NotImplementedError(
                "paged LoRA adapters are single-device for now — the pool "
                "is not TP-sharded (pass tp_axis=None)")
        if adapter_ids is None:
            raise ValueError("adapters given without adapter_ids")
        from apex_tpu.serve.adapters import lora_delta
    heads_local = _serve_heads(cfg, tp_axis)
    n, q = x.shape[:2]
    offs = jnp.arange(q)
    positions = start_lens[:, None] + offs[None, :]            # (n, q)
    valid = active[:, None] & (offs[None, :] < n_valid[:, None])
    ctx_lens = jnp.where(valid, positions + 1, 0)
    # flat row views for the paged write/gather (each token is its own
    # "slot" sharing its owner's block-table row)
    bt_rows = jnp.repeat(block_tables, q, axis=0)   # (n*q, max_blocks)
    pos_flat = positions.reshape(-1)
    valid_flat = valid.reshape(-1)
    # q_len>1 row exits honor cfg.overlap_comm: the decomposed ring
    # scatters over the q dim, so it needs q divisible by the axis size;
    # q=1 decode stays monolithic (the PR-5 pin — a single-row GEMM has
    # nothing to hide a hop behind)
    overlap = (tp_axis is not None and cfg.overlap_comm
               and q > 1 and q % _tp_size(tp_axis) == 0)

    def body(x, xs):
        if adapters is None:
            lp, cl = xs
            ad = None
        else:
            lp, cl, ad = xs
        if gather_layer is not None:
            lp = gather_layer(lp)
        with span("layer"):
            return layer(x, lp, cl, ad)

    def layer(x, lp, cl, ad):
        with span("ln1"):
            h1 = layer_norm(x, lp["ln1_w"], lp["ln1_b"],
                            use_pallas=cfg.ln_pallas)
        with span("attn/qkv"):
            qkv = _col(h1, lp["qkv_kernel"], lp["qkv_bias"], tp_axis)
            if ad is not None:
                qkv = qkv + lora_delta(h1, ad["qkv_a"], ad["qkv_b"],
                                       adapter_ids)
            qh, k, v = _split_qkv(qkv, heads_local, cfg.head_dim)  # (n,q,H,D)
        with span("kv_write"):
            k_flat = k.reshape(n * q, heads_local, cfg.head_dim)
            v_flat = v.reshape(n * q, heads_local, cfg.head_dim)
            cl = paged_write(cl, kv_cfg, k_flat.transpose(1, 0, 2),
                             v_flat.transpose(1, 0, 2), bt_rows, pos_flat,
                             valid_flat)
        with span("kv_read"):
            ctx = paged_attention(
                qh.reshape(n * q, heads_local, cfg.head_dim), cl, kv_cfg,
                bt_rows, ctx_lens.reshape(-1), use_pallas=use_pallas)
        with span("attn/out"):
            ctx = ctx.reshape(n, q, heads_local * cfg.head_dim)
            a = _row(ctx, lp["out_kernel"], lp["out_bias"], tp_axis,
                     overlap=overlap)
            if ad is not None:
                a = a + lora_delta(ctx, ad["out_a"], ad["out_b"],
                                   adapter_ids)
        with span("residual"):
            x = x + a
        with span("ln2"):
            h2 = layer_norm(x, lp["ln2_w"], lp["ln2_b"],
                            use_pallas=cfg.ln_pallas)
        with span("mlp/fc"):
            pre = _col(h2, lp["fc1_kernel"], lp["fc1_bias"], tp_axis)
            if ad is not None:
                pre = pre + lora_delta(h2, ad["fc1_a"], ad["fc1_b"],
                                       adapter_ids)
        with span("mlp/act"):
            y = jax.nn.gelu(pre, approximate=True)
        with span("mlp/proj"):
            m = _row(y, lp["fc2_kernel"], lp["fc2_bias"], tp_axis,
                     overlap=overlap)
            if ad is not None:
                m = m + lora_delta(y, ad["fc2_a"], ad["fc2_b"], adapter_ids)
        with span("residual"):
            x = x + m
        return x, cl

    # the adapter pool rides the scan as read-only xs (sliced per layer,
    # never restacked into ys — no per-step pool copy); the caller's jit
    # site donates it and returns it untouched
    xs = ((layers, cache) if adapters is None
          else (layers, cache, adapters))
    return lax.scan(body, x, xs)


def gpt_paged_forward(params, tokens, start_lens, n_valid, active, cache,
                      block_tables, cfg, kv_cfg: KVCacheConfig,
                      tp_axis: Optional[str] = None,
                      use_pallas: Optional[bool] = None,
                      adapters: Optional[Pytree] = None,
                      adapter_ids=None,
                      gather_layer=None
                      ) -> Tuple[Pytree, jnp.ndarray]:
    """Process ``tokens`` (n, q) — per slot, q consecutive tokens starting
    at position ``start_lens[slot]`` — against the paged cache.

    ``n_valid``: (n,) how many of each slot's q tokens are real (the rest
    are padding: K/V writes dropped, logits junk). ``active``: (n,) bool.
    Returns ``(cache', logits (n, q, vocab) fp32)`` — logits[i, j] is the
    next-token distribution after feeding tokens[i, j] at position
    ``start_lens[i] + j``. Inactive slots and invalid positions produce
    finite junk logits the engine ignores.

    ``adapters``: an optional ``serve.adapters`` AdapterPool — per-layer
    LoRA slot stacks riding the layer scan as read-only xs; each row adds
    its adapter's gathered BGMV delta (``lora_delta``) to the four
    adapted projections, with ``adapter_ids`` (n,) int32 selecting the
    pool slot per batch row (id 0 = base = exact zero delta). Per-ROW
    like everything else here, so the same pool serves decode, verify
    and chunked prefill from one compiled program each.

    ``gather_layer``: optional per-layer param materializer — see
    :func:`paged_layer_stack` (``params["layers"]`` then carries FSDP
    shard leaves instead of full stacked weights).
    """
    _check_serve_cfg(cfg, kv_cfg, tp_axis)
    n, q = tokens.shape
    offs = jnp.arange(q)
    positions = start_lens[:, None] + offs[None, :]            # (n, q)
    positions_c = jnp.minimum(positions, cfg.max_seq - 1)
    with span("embed"):
        x = _embed(params["embed"], tokens, positions_c, tp_axis)  # (n, q, h)
    x, cache = paged_layer_stack(
        x, params["layers"], start_lens, n_valid, active, cache,
        block_tables, cfg, kv_cfg, tp_axis=tp_axis, use_pallas=use_pallas,
        adapters=adapters, adapter_ids=adapter_ids,
        gather_layer=gather_layer)
    return cache, serve_logits(params, x, cfg, tp_axis)


def gpt_decode_step(params, last_tokens, seq_lens, active, cache,
                    block_tables, cfg, kv_cfg: KVCacheConfig,
                    tp_axis: Optional[str] = None,
                    use_pallas: Optional[bool] = None,
                    adapters: Optional[Pytree] = None,
                    adapter_ids=None,
                    gather_layer=None
                    ) -> Tuple[Pytree, jnp.ndarray]:
    """Advance every active slot by one token (q=1 paged forward).

    ``last_tokens``: (n,) the token each slot feeds this step (the one
    sampled last step). ``seq_lens``: (n,) tokens already cached — the fed
    token's position. ``active``: (n,) bool. Returns ``(cache', logits
    (n, vocab) fp32)``; inactive slots produce finite junk logits the
    engine ignores. ``adapters``/``adapter_ids``: optional per-slot LoRA
    (see :func:`gpt_paged_forward`).
    """
    n = last_tokens.shape[0]
    cache, logits = gpt_paged_forward(
        params, last_tokens[:, None], seq_lens,
        jnp.ones((n,), jnp.int32), active, cache, block_tables, cfg,
        kv_cfg, tp_axis=tp_axis, use_pallas=use_pallas,
        adapters=adapters, adapter_ids=adapter_ids,
        gather_layer=gather_layer)
    return cache, logits[:, 0]


def gpt_verify_step(params, fed_tokens, seq_lens, n_fed, active, cache,
                    block_tables, cfg, kv_cfg: KVCacheConfig,
                    tp_axis: Optional[str] = None,
                    use_pallas: Optional[bool] = None,
                    adapters: Optional[Pytree] = None,
                    adapter_ids=None,
                    gather_layer=None
                    ) -> Tuple[Pytree, jnp.ndarray]:
    """Speculative verify: feed ``fed_tokens`` (n, k+1) — each slot's last
    sampled token followed by up to k drafted tokens — in ONE paged call
    (the MPK amortization: q_len=k+1 turns k+1 dispatch-bound steps into
    one). Returns ``(cache', logits (n, k+1, vocab))``; logits[i, j]
    scores the token AFTER fed_tokens[i, j], so the engine accepts the
    longest run where the sampled token matches the next draft. Rejected
    drafts' K/V writes need no rollback: the accepted length caps
    ``seq_lens``, the stale positions are masked by every later context
    window and overwritten when real tokens reach them (the same
    ``mode="drop"``/masking contract that drops padded writes).

    :func:`megakernel.gpt_verify_step_fused` is the fused sibling —
    same semantics, one Pallas block per layer — which the engine wires
    in when ``ServeConfig.megakernel`` resolves on; this per-op path is
    the parity oracle the fused one is pinned against."""
    return gpt_paged_forward(params, fed_tokens, seq_lens, n_fed, active,
                             cache, block_tables, cfg, kv_cfg,
                             tp_axis=tp_axis, use_pallas=use_pallas,
                             adapters=adapters, adapter_ids=adapter_ids,
                             gather_layer=gather_layer)


def gpt_prefill_chunk(params, tokens, start, n_valid, cache, block_row,
                      cfg, kv_cfg: KVCacheConfig,
                      tp_axis: Optional[str] = None,
                      use_pallas: Optional[bool] = None,
                      adapters: Optional[Pytree] = None,
                      adapter_id=None,
                      gather_layer=None
                      ) -> Tuple[Pytree, jnp.ndarray]:
    """Process one fixed-size chunk of ONE prompt into the cache.

    ``tokens``: (chunk,) int32, prompt positions ``start .. start+n_valid-1``
    padded to the chunk size (padding writes dropped). ``block_row``:
    (max_blocks,) int32 blocks owning the slot. Returns ``(cache', logits
    (vocab,))`` — the next-token logits after the chunk's LAST valid
    token, meaningful only on the final chunk of a prompt (the engine
    samples the first generated token from it).

    One chunk shape -> ONE compiled prefill program for the engine's
    lifetime, replacing the PR-5 bucket ladder: the chunk interleaves
    into decode steps, so long prompts neither stall running decodes nor
    mint per-bucket compilations.

    ``adapters``/``adapter_id``: optional LoRA — ``adapter_id`` is the
    ONE prefilling slot's pool id (scalar; the prompt's K/V must be
    written with the same adapted projections decode will use).
    """
    aids = (None if adapters is None
            else jnp.reshape(jnp.asarray(adapter_id, jnp.int32), (1,)))
    cache, logits = gpt_paged_forward(
        params, tokens[None, :], jnp.asarray(start)[None],
        jnp.asarray(n_valid)[None], jnp.ones((1,), bool), cache,
        block_row[None, :], cfg, kv_cfg, tp_axis=tp_axis,
        use_pallas=use_pallas, adapters=adapters, adapter_ids=aids,
        gather_layer=gather_layer)
    last = jnp.take(logits[0], jnp.maximum(n_valid - 1, 0), axis=0)
    return cache, last
