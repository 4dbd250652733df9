"""Megakernel decode/verify step — one fused Pallas block per layer,
with the layer's weights STREAMED through VMEM as grid-indexed tiles.

The MPK observation (arXiv 2512.22219) taken past the scheduler: at small
q_len the decode step's per-op work is tiny — a (slots, hidden) GEMM
here, a layer norm there — and the compiled program spends its time
dispatching ~14 XLA ops per layer rather than computing. PR 7 made the
whole step ONE program; PR 8 made each layer's interior ONE kernel but
required the layer's full weight set resident in VMEM, so the 10 MB
budget gated OFF exactly the GPT-2-124M-class models the bench measures
(~14 MB bf16 per layer). This tier lifts that gate:

* **weight-tile streaming** — the four GEMM weights (qkv ``(h, 3h)``,
  out-proj ``(hd, h)``, fc1 ``(h, f)``, fc2 ``(f, h)``) arrive as
  BlockSpec-indexed column/row tiles over a flattened phase grid
  ``j = [qkv tiles | pool-block walk | out tiles | ffn tiles]``. Each
  tile's index map clamps outside its phase, so Mosaic elides the
  repeated fetch and double-buffers the next tile behind the current
  tile's compute. Partial results accumulate in fp32 VMEM scratch
  (gelu applies per fc1 tile — each output column's h-contraction
  completes inside its tile, so the nonlinearity is exact), and the
  single-tile degenerate ``tiles=(1, 1, 1)`` reproduces the PR-8
  resident-weight kernel op for op.
* **tile-budget gating** — :func:`megakernel_ok` now asks whether the
  MAX LIVE TILE SET fits the budget, not the whole layer:
  :func:`default_tiles` greedily splits the largest-tile matrix until
  :func:`fused_live_bytes` (tiles × double-buffering + vectors + pool
  blocks + scratch) fits, and :func:`megakernel_refusal` reports the
  measured bytes vs the budget when nothing fits. GPT-2-124M gates ON.
* :func:`fused_layer_decode` / :func:`fused_layer_verify` — the same
  kernel at q_len=1 and q_len=k+1. The verify variant computes ALL q
  fed rows' K/V in-kernel and folds them with a causal-within-window
  online softmax AFTER the pool walk (position order — row ``w``
  attends the pool's ``start_ctx`` old tokens plus fed rows ``0..w``),
  through the exact codec round-trip, so int8/int4 pool codes stay
  bitwise and logits match the unfused ``gpt_verify_step`` that writes
  first and reads back. The pool write stays the engine's proven
  ``paged_write`` scatter outside the kernel — no in-kernel
  read-after-write hazard, same invalid-row masking contract.
* :func:`gpt_decode_step_fused` / :func:`gpt_verify_step_fused` —
  drop-in replacements for ``decode.gpt_decode_step`` /
  ``decode.gpt_verify_step`` (embed, ``lax.scan`` of the fused block +
  K/V scatter over the stacked layers, final LN + logits), so with
  ``ServeConfig(megakernel=...)`` speculative decoding rides the fused
  path end to end. ``decode.gpt_paged_forward`` remains the parity
  oracle (``tests/test_megakernel.py`` pins fp32 agreement, bitwise
  quantized pool codes, and engine-level stream equality).

Honest gating, unchanged in spirit: MoE layers, TP-sharded programs,
LoRA adapters and lane-hostile head_dims still refuse (the unfused path
provides the collective exits / adapter deltas), and a config whose
FINEST valid tiling still exceeds the budget refuses with the measured
bytes. ``megakernel="auto"`` silently falls back (warn-once, with the
reason); ``"on"`` raises.
"""

from __future__ import annotations

import functools
import math
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.ops._pallas_util import compiled_backend as _compiled_backend
from apex_tpu.ops._pallas_util import sds as _sds
from apex_tpu.ops.attention import NEG_INF
from apex_tpu.serve.kv_cache import KVCacheConfig, paged_write

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Pytree = Any

from apex_tpu.comm.quantize import QMAX as _QMAX  # the codec's code range:
# _codec_roundtrip must track comm.quantize bit-for-bit (parity-pinned)

# VMEM budget for the fused block's live set: the CURRENT weight tiles
# (double-buffered while their phase streams), the resident bias/norm
# vectors, one pool block per pool (double-buffered walk) and the fp32
# activation scratch — a MODEL, kept under XLA's 16 MiB default scoped-VMEM
# limit on v5e (of 128 MiB per core) so Mosaic keeps headroom for its own
# spills. It is an upper bound: for the GPT-2-124M bf16 layer at tiles
# (1, 1, 4) it counts 9.6 MB where the compiler allocates 2.50 MiB of
# scoped VMEM inside the 12-layer decode program (jax 0.9.0, v5e AOT
# compile; fp32 weights at (6, 1, 8) compile too) — XLA stages the weight
# operands itself — so no ``vmem_limit_bytes`` is needed.
_VMEM_BUDGET_BYTES = 10 * 1024 * 1024
_LANE = 128


def layer_weight_bytes(cfg) -> int:
    """FULL-RESIDENCY bytes of one layer's weight set (matrices +
    bias/norm vectors, in the model dtype) — what the PR-8 kernel kept
    live and what ``tiles=(1, 1, 1)`` still keeps live. The gate itself
    compares :func:`fused_live_bytes` at :func:`default_tiles`."""
    h, f = cfg.hidden, cfg.ffn_hidden
    hd = cfg.num_heads * cfg.head_dim
    elems = h * 3 * h + hd * h + h * f + f * h  # the four GEMMs
    # qkv_b (3h) + ln1 w/b (2h) + fc1_b (f) + ln2 w/b (2h) + out_b + fc2_b
    elems += 3 * h + 2 * h + f + 2 * h + h + h
    return elems * jnp.dtype(cfg.dtype).itemsize


def _tiled_dims(cfg) -> Tuple[int, int, int]:
    """The dim each tile count divides: qkv columns (3h), out-proj
    columns (h), and the shared ffn axis f (fc1 columns == fc2 rows)."""
    return 3 * cfg.hidden, cfg.hidden, cfg.ffn_hidden


def _valid_tile_counts(dim: int, compiled: bool = True) -> List[int]:
    """Tile counts that evenly divide ``dim``. Count 1 (full residency —
    the PR-8 path) is always valid; compiled Mosaic additionally needs
    every streamed tile lane-aligned (``dim // t`` a multiple of 128) so
    the BlockSpec slices land on register boundaries. Interpret mode
    (the CPU test rig) accepts any even division."""
    out = [1]
    for t in range(2, dim + 1):
        if dim % t:
            continue
        if compiled and (dim // t) % _LANE:
            continue
        out.append(t)
    return out


def _axis_live_bytes(cfg, axis: int, t: int) -> int:
    """Live VMEM bytes of one tiled matrix group at tile count ``t``:
    the current tile, times two when streaming (Mosaic double-buffers
    the next tile's DMA behind the current tile's compute; at t == 1
    the constant index map means one resident buffer, no prefetch)."""
    h, f = cfg.hidden, cfg.ffn_hidden
    hd = cfg.num_heads * cfg.head_dim
    w = jnp.dtype(cfg.dtype).itemsize
    buf = 2 if t > 1 else 1
    if axis == 0:                       # qkv (h, 3h) column tiles
        return h * (3 * h // t) * w * buf
    if axis == 1:                       # out-proj (hd, h) column tiles
        return hd * (h // t) * w * buf
    # ffn: fc1 (h, f/t) column tile + fc2 (f/t, h) row tile
    return (h * (f // t) + (f // t) * h) * w * buf


def fused_live_bytes(cfg, kv_cfg: KVCacheConfig,
                     tiles: Tuple[int, int, int], q: int = 1) -> int:
    """Peak VMEM bytes of the fused block at weight tiling ``tiles =
    (t_qkv, t_out, t_ffn)`` and ``q`` fed rows per slot: live weight
    tiles (clamped index maps keep ONE tile of every matrix resident
    across the whole grid, double-buffered while streaming), resident
    bias/norm vectors, the double-buffered pool-block pair, the
    activation blocks and the fp32 scratch set."""
    t_qkv, t_out, t_ffn = tiles
    h, f = cfg.hidden, cfg.ffn_hidden
    heads, d = cfg.num_heads, cfg.head_dim
    hd = heads * d
    w = jnp.dtype(cfg.dtype).itemsize
    total = sum(_axis_live_bytes(cfg, a, t)
                for a, t in enumerate((t_qkv, t_out, t_ffn)))
    total += (3 * h + 2 * h + f + 2 * h + h + h) * w  # resident vectors
    bs = kv_cfg.block_size
    if kv_cfg.quantized and kv_cfg.bits == 4:
        # packed uint8 codes + bf16 group scales
        pool = heads * bs * (d // 2) + heads * bs * (d // kv_cfg.kv_group) * 2
    elif kv_cfg.quantized:
        pool = heads * bs * d + heads * bs * 4  # int8 codes + fp32 scales
    else:
        pool = heads * bs * d * jnp.dtype(kv_cfg.dtype).itemsize
    total += 2 * 2 * pool                       # k+v pools, double-buffered
    total += (2 * q * h + 2 * q * hd) * w       # x/x' + emitted K/V blocks
    # fp32 scratch: h1/x1/h2/mlp (q,h) + qkv (q,3h) + ctx (q,hd) +
    # q/kc/vc/acc rows (q,H,D) + online-softmax m/l (q,H,128)
    total += 4 * (4 * q * h + q * 3 * h + q * hd + 4 * q * hd
                  + 2 * q * heads * _LANE)
    return int(total)


def default_tiles(cfg, kv_cfg: KVCacheConfig, q: int = 1,
                  compiled: bool = True
                  ) -> Optional[Tuple[int, int, int]]:
    """Coarsest weight tiling whose live set fits the VMEM budget.

    Greedy: start at full residency ``(1, 1, 1)`` (the PR-8 fast path —
    no streaming DMAs at all) and, while over budget, split whichever
    matrix group currently holds the most live bytes to its next valid
    count that strictly shrinks it (t=1 -> t=2 shrinks nothing: the
    streaming double-buffer cancels the halving). Returns ``None`` when
    even the finest valid tiling does not fit (the refusal path)."""
    dims = _tiled_dims(cfg)
    counts = [_valid_tile_counts(dim, compiled) for dim in dims]
    tiles = [1, 1, 1]
    while fused_live_bytes(cfg, kv_cfg, tuple(tiles), q=q) \
            > _VMEM_BUDGET_BYTES:
        best_axis, best_next = None, None
        best_cur = -1
        for a in range(3):
            cur = _axis_live_bytes(cfg, a, tiles[a])
            nxt = next((t for t in counts[a]
                        if t > tiles[a] and _axis_live_bytes(cfg, a, t) < cur),
                       None)
            if nxt is not None and cur > best_cur:
                best_axis, best_next, best_cur = a, nxt, cur
        if best_axis is None:
            return None
        tiles[best_axis] = best_next
    return tuple(tiles)


def _finest_tiles(cfg, compiled: bool = True) -> Tuple[int, int, int]:
    return tuple(_valid_tile_counts(dim, compiled)[-1]
                 for dim in _tiled_dims(cfg))


def megakernel_refusal(cfg, kv_cfg: KVCacheConfig,
                       allow_interpret: bool = True,
                       q: int = 1) -> Optional[str]:
    """Why the fused block refuses this model/cache shape — ``None``
    when it is supported. Budget refusals report the MEASURED bytes
    (finest-tiling live set vs the budget) so operators see how far
    over a config is, not a bare no."""
    if cfg.num_experts:
        return ("MoE layers (num_experts > 0) — the fused block assumes "
                "a dense FFN (ROADMAP item 5a)")
    if cfg.num_heads * cfg.head_dim != cfg.hidden:
        return (f"num_heads * head_dim ({cfg.num_heads} * {cfg.head_dim} "
                f"= {cfg.num_heads * cfg.head_dim}) != hidden "
                f"({cfg.hidden}) — the residual add needs hd == h")
    if kv_cfg.head_dim != cfg.head_dim or kv_cfg.head_dim % 8 != 0:
        return (f"head_dim {kv_cfg.head_dim} must match the model "
                f"({cfg.head_dim}) and be a multiple of 8 (sublane "
                f"alignment)")
    compiled = _compiled_backend()
    if not allow_interpret and not compiled:
        return ("no compiled Mosaic backend (interpret mode simulates "
                "the kernel — it saves no dispatch)")
    if compiled:
        # the fused block walks the pool with the paged kernel's own
        # dequant: what Mosaic refuses there (int4's nibble unpack) it
        # refuses here
        from apex_tpu.serve.decode import paged_kernel_refusal

        reason = paged_kernel_refusal(kv_cfg, compiled=True)
        if reason is not None:
            return reason
    tiles = default_tiles(cfg, kv_cfg, q=q, compiled=compiled)
    if tiles is None:
        finest = _finest_tiles(cfg, compiled)
        live = fused_live_bytes(cfg, kv_cfg, finest, q=q)
        return (f"per-layer weights {layer_weight_bytes(cfg)} B resident; "
                f"even the finest weight tiling {finest} keeps "
                f"{live} B live, over the {_VMEM_BUDGET_BYTES} B VMEM "
                f"budget")
    return None


def megakernel_ok(cfg, kv_cfg: KVCacheConfig,
                  allow_interpret: bool = True, q: int = 1) -> bool:
    """Whether the fused decode/verify block supports this model/cache
    shape. Static gate, no params needed: pallas importable, no MoE,
    attention heads covering the hidden size (the residual add needs
    hd == h), head_dim lane-friendly, and SOME weight tiling whose live
    tile set fits the VMEM budget (``default_tiles``) — full residency
    is no longer required. ``allow_interpret=False`` additionally
    requires a compiled Mosaic backend (the ``"auto"`` resolution
    off-TPU)."""
    return megakernel_refusal(cfg, kv_cfg,
                              allow_interpret=allow_interpret, q=q) is None


# configs whose silent fused->unfused auto-fallback was already logged
# (warn ONCE per reason — the decode._warn_reference_fallback pattern:
# a slower serve run must be diagnosable from the log, not only from
# the bench line's decode_kernel field)
_FALLBACK_WARNED: set = set()


def warn_megakernel_fallback(reason: str) -> None:
    """Log (once per distinct reason) that ``megakernel="auto"`` fell
    back to the per-op layer body on a compiled backend — with the
    measured-bytes refusal text so operators see how far over budget
    (or which shape rule) the config was."""
    if reason in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(reason)
    from apex_tpu._logging import get_logger

    get_logger("apex_tpu.serve").warning(
        "megakernel='auto': falling back to the unfused per-op decode "
        "path — %s", reason)


def _check_tiles(cfg, tiles: Tuple[int, int, int], compiled: bool) -> None:
    names = ("qkv-column (3*hidden)", "out-proj-column (hidden)",
             "ffn-axis (ffn_hidden)")
    for t, dim, nm in zip(tiles, _tiled_dims(cfg), names):
        if t < 1 or dim % t:
            raise ValueError(
                f"megakernel weight-tile count {t} does not divide the "
                f"{nm} dim {dim}; valid counts: "
                f"{_valid_tile_counts(dim, compiled)}")
        if compiled and t > 1 and (dim // t) % _LANE:
            raise ValueError(
                f"compiled Mosaic needs lane-aligned weight tiles: "
                f"{nm} {dim} / {t} = {dim // t} is not a multiple of "
                f"{_LANE}; valid counts: {_valid_tile_counts(dim, True)}")


# ---------------------------------------------------------------------------
# The fused block kernel. Grid (slots, S) with S = tq + nb + to + tf — a
# single flattened phase axis per slot:
#
#   j in [0, tq)           qkv column tiles (LN1 + per-tile GEMM)
#   j in [tq, tq+nb)       pool-block gather-attend walk (all q rows)
#   j in [b_end, b_end+to) out-proj column tiles -> fp32 residual x1
#   j in [c_end, c_end+tf) ffn tiles: fc1 col + gelu + fc2 row, fp32 acc
#
# Each weight's index map clamps outside its phase, so its current tile
# stays resident (DMA elided) and streams only while its phase runs.
# Tile bodies are STATICALLY UNROLLED Python loops guarded by
# ``pl.when(j == step)`` writing STATIC scratch slices — no dynamic
# lane-dim stores for Mosaic to refuse. Per-row work (q_len rows) is
# likewise unrolled with rows on a LEADING (untiled) scratch dim, so
# every per-row body is byte-identical to the PR-8 q=1 kernel.


def _ln_rows(x, w, b, eps):
    """fp32 layer norm over the last axis — the ``layer_norm_reference``
    math (E[x²]−E[x]² with the cancellation clamp) inlined so the fused
    block and the unfused path normalize identically."""
    n = x.shape[-1]
    mean = jnp.sum(x, axis=-1, keepdims=True) / n
    msq = jnp.sum(x * x, axis=-1, keepdims=True) / n
    var = jnp.maximum(msq - mean * mean, 0.0)
    y = (x - mean) * lax.rsqrt(var + eps)
    return y * w + b


def _codec_roundtrip(x):
    """comm.quantize blockwise codec round-trip at codec-block = head_dim:
    what the unfused path reads back from an int8 pool. (H, D) fp32 in
    and out. The pool write outside re-quantizes the RAW values through
    the same deterministic codec, so the codes it stores match this
    round-trip bit-for-bit."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / _QMAX, 1.0)
    q = jnp.clip(jnp.round(x / scale), -_QMAX, _QMAX)
    return q * scale


def _codec_roundtrip4(x, group):
    """The int4 pool round-trip (``kv_cache._quant_rows_int4`` math):
    per-group absmax/7 scale ROUNDED TO bf16 (the stored scale dtype),
    ±7 round/clip, dequant — bit-for-bit what the unfused path reads
    back from an int4 pool. (H, D) fp32 in and out."""
    from apex_tpu.comm.quantize import QMAX4

    h, d = x.shape
    g = x.reshape(h, d // group, group)
    amax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / QMAX4, 1.0)
    scale = scale.astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.clip(jnp.round(g / scale), -QMAX4, QMAX4)
    return (q * scale).reshape(h, d)


def _fused_block_kernel(bt_ref, len_ref, x_ref, ln1w_ref, ln1b_ref,
                        qkvk_ref, qkvb_ref, outk_ref, outb_ref,
                        ln2w_ref, ln2b_ref, fc1k_ref, fc1b_ref,
                        fc2k_ref, fc2b_ref, k_ref, v_ref, *refs,
                        scale, block_size, nb, heads, head_dim, q_rows,
                        tiles, quantized, pool_dtype, eps,
                        kv_bits=8, kv_group=0):
    tq, to, tf = tiles
    if quantized:
        (ks_ref, vs_ref, xo_ref, ko_ref, vo_ref,
         h1_scr, qkv_scr, q_scr, kc_scr, vc_scr, m_scr, l_scr, acc_scr,
         ctx_scr, x1_scr, h2_scr, mlp_scr) = refs
    else:
        (xo_ref, ko_ref, vo_ref,
         h1_scr, qkv_scr, q_scr, kc_scr, vc_scr, m_scr, l_scr, acc_scr,
         ctx_scr, x1_scr, h2_scr, mlp_scr) = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    ctx = len_ref[i]  # OLD tokens in the pool (fed rows are in-register)
    h = x_ref.shape[-1]
    hd = heads * head_dim
    a_end = tq
    b_end = tq + nb
    c_end = b_end + to
    ct3 = (3 * h) // tq
    co = h // to
    cf = fc1k_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        x = x_ref[0].astype(jnp.float32)                      # (q, h)
        h1_scr[:] = _ln_rows(x, ln1w_ref[:].astype(jnp.float32),
                             ln1b_ref[:].astype(jnp.float32), eps)

    # phase A: qkv column tiles. Each body writes a STATIC column slice
    # of the qkv scratch; the h-contraction is full per tile, so every
    # output column matches the resident-weight dot exactly.
    for t in range(tq):
        @pl.when(j == t)
        def _qkv_tile(t=t):
            h1 = h1_scr[:].astype(x_ref.dtype)
            part = jnp.dot(h1, qkvk_ref[:],
                           preferred_element_type=jnp.float32)  # (q, ct3)
            part = part + qkvb_ref[:, t * ct3:(t + 1) * ct3].astype(
                jnp.float32)
            qkv_scr[:, t * ct3:(t + 1) * ct3] = part

    @pl.when(j == a_end - 1)
    def _emit_qkv():
        # per-head interleaved unpack (the standalone_gpt packing) as
        # STATIC lane slices of the (q, 3h) row, one head at a time: a
        # (1, 3h) -> (H, 3, D) shape cast does not lower in Mosaic. Heads
        # land on a LEADING scratch dim with a unit row dim, so the pool
        # walk's head-batched matmul gets its free dimension.
        def from_pool(x):
            # what the pool hands back for an emitted row: the codec
            # round-trip (int8/int4 cache) or the pool-dtype cast
            if quantized and kv_bits == 4:
                return _codec_roundtrip4(x.astype(jnp.float32), kv_group)
            if quantized:
                return _codec_roundtrip(x.astype(jnp.float32))
            return x.astype(pool_dtype).astype(jnp.float32)

        for w in range(q_rows):
            for hh in range(heads):
                qh, kh, vh = (
                    qkv_scr[w:w + 1, (3 * hh + part) * head_dim:
                            (3 * hh + part + 1) * head_dim]   # (1, D) f32
                    for part in range(3))
                # q rounds through the model dtype like the per-op path's
                # projection output (exact no-op for an fp32 model)
                q_scr[w, hh] = qh.astype(x_ref.dtype).astype(jnp.float32)
                # the EMITTED values (model dtype) are what paged_write
                # consumes — the in-register fold must round-trip through
                # that cast first, or a bf16 model's codec scales/codes
                # diverge from the pool's
                kq = kh.astype(ko_ref.dtype)
                vq = vh.astype(vo_ref.dtype)
                ko_ref[0, w, hh] = kq
                vo_ref[0, w, hh] = vq
                kc_scr[w, hh] = from_pool(kq)
                vc_scr[w, hh] = from_pool(vq)

    @pl.when((j >= a_end) & (j < b_end)
             & ((j - a_end) * block_size < ctx))
    def _attend_block():
        from apex_tpu.serve.decode import _nibble_dequant

        k = k_ref[:, 0]              # (H, bs, D) | packed (H, bs, D/2)
        v = v_ref[:, 0]
        if quantized and kv_bits == 4:
            k = _nibble_dequant(k, ks_ref[:, 0], kv_group)
            v = _nibble_dequant(v, vs_ref[:, 0], kv_group)
        elif quantized:
            k = k.astype(jnp.float32) * ks_ref[:, 0, 0][..., None]
            v = v.astype(jnp.float32) * vs_ref[:, 0, 0][..., None]
        for w in range(q_rows):
            # Mosaic wants one dtype per dot: q joins K's (the pool dtype,
            # or fp32 after the dequant) — exact, q_scr holds model-dtype
            # values
            qw = q_scr[w].astype(k.dtype)                     # (H, 1, D)
            s = lax.dot_general(
                qw, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale   # (H, 1, bs)
            kpos = ((j - a_end) * block_size
                    + lax.broadcasted_iota(jnp.int32, s.shape, 2))
            s = jnp.where(kpos >= ctx, NEG_INF, s)
            m_prev = m_scr[w][:, :, :1]
            l_prev = l_scr[w][:, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = corr * l_prev + jnp.sum(p, axis=2, keepdims=True)
            acc_scr[w] = acc_scr[w] * corr + lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)           # (H, 1, D)
            m_scr[w] = jnp.broadcast_to(m_new, (heads, 1, _LANE))
            l_scr[w] = jnp.broadcast_to(l_new, (heads, 1, _LANE))

    @pl.when(j == b_end - 1)
    def _fold_window():
        # fold the in-register fed rows LAST, in POSITION order — their
        # positions are the end of each row's context, so the online
        # softmax visits scores exactly as the reference does. Row w
        # attends fed rows 0..w (causal within the window); the diagonal
        # is always allowed, so even ctx == 0 slots stay finite.
        for w in range(q_rows):
            qw = q_scr[w]                                     # (H, 1, D)
            m_prev = m_scr[w][:, :, :1]
            l_prev = l_scr[w][:, :, :1]
            acc = acc_scr[w]
            for t in range(w + 1):
                kc = kc_scr[t]
                vc = vc_scr[t]
                s_cur = jnp.sum(qw * kc, axis=2,
                                keepdims=True) * scale        # (H, 1, 1)
                m_new = jnp.maximum(m_prev, s_cur)
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(s_cur - m_new)
                l_new = corr * l_prev + p
                acc = acc * corr + p * vc
                m_prev, l_prev = m_new, l_new
            ctx_vec = acc / l_prev                     # l >= p(diag) > 0
            # heads back onto lanes for the out-proj GEMM, one static
            # lane slice per head (a (H, D) -> (1, hd) cast does not lower)
            for hh in range(heads):
                ctx_scr[w:w + 1,
                        hh * head_dim:(hh + 1) * head_dim] = ctx_vec[hh]

    # phase C: out-proj column tiles -> the fp32 residual x1
    for t in range(to):
        @pl.when(j == b_end + t)
        def _out_tile(t=t):
            ctx_rows = ctx_scr[:].astype(x_ref.dtype)         # (q, hd)
            a = jnp.dot(ctx_rows, outk_ref[:],
                        preferred_element_type=jnp.float32)   # (q, co)
            a = a + outb_ref[:, t * co:(t + 1) * co].astype(jnp.float32)
            x1_scr[:, t * co:(t + 1) * co] = (
                x_ref[0][:, t * co:(t + 1) * co].astype(jnp.float32) + a)

    @pl.when(j == c_end - 1)
    def _ln2():
        h2_scr[:] = _ln_rows(x1_scr[:], ln2w_ref[:].astype(jnp.float32),
                             ln2b_ref[:].astype(jnp.float32), eps)
        mlp_scr[:] = jnp.zeros_like(mlp_scr)

    # phase D: ffn tiles — fc1 column tile (gelu exact: each output
    # column's h-contraction completes inside its tile) + fc2 row tile,
    # partials accumulating in fp32
    for t in range(tf):
        @pl.when(j == c_end + t)
        def _ffn_tile(t=t):
            h2 = h2_scr[:].astype(x_ref.dtype)
            y = jnp.dot(h2, fc1k_ref[:],
                        preferred_element_type=jnp.float32)   # (q, cf)
            y = jax.nn.gelu(
                y + fc1b_ref[:, t * cf:(t + 1) * cf].astype(jnp.float32),
                approximate=True)
            y = y.astype(x_ref.dtype)
            mlp_scr[:] = mlp_scr[:] + jnp.dot(
                y, fc2k_ref[:], preferred_element_type=jnp.float32)

    @pl.when(j == c_end + tf - 1)
    def _emit():
        m_out = mlp_scr[:] + fc2b_ref[:].astype(jnp.float32)
        xo_ref[0] = (x1_scr[:] + m_out).astype(xo_ref.dtype)


def _fused_block(x, layer_params, cache_layer, cfg,
                 kv_cfg: KVCacheConfig, block_tables, ctx_lens,
                 tiles: Optional[Tuple[int, int, int]],
                 interpret: Optional[bool]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The shared pallas_call builder: ``x`` (n, q, h) fed rows ->
    ``(x', k_new (n, q, H, D), v_new)``."""
    n, q, h = x.shape
    heads, d = kv_cfg.num_heads, kv_cfg.head_dim
    nb = block_tables.shape[1]
    bs = kv_cfg.block_size
    f = cfg.ffn_hidden
    if interpret is None:
        interpret = not _compiled_backend()
    if tiles is None:
        tiles = default_tiles(cfg, kv_cfg, q=q, compiled=not interpret)
        if tiles is None:
            raise ValueError(
                megakernel_refusal(cfg, kv_cfg, q=q)
                or "megakernel: no weight tiling fits the VMEM budget")
    _check_tiles(cfg, tiles, compiled=not interpret)
    tq, to, tf = tiles
    a_end, b_end, c_end = tq, tq + nb, tq + nb + to
    steps = c_end + tf
    lp = layer_params
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    lens = ctx_lens.astype(jnp.int32)
    att_scale = 1.0 / math.sqrt(d)

    def row3(i, j, bt, ln):      # per-slot activation rows
        return (i, 0, 0)

    def const2(i, j, bt, ln):    # vectors resident across the whole grid
        return (0, 0)

    # each weight's tile index clamps OUTSIDE its phase: the repeated
    # index elides the DMA, so the tile streams only while its phase runs
    def qkv_tile(i, j, bt, ln):
        return (0, jnp.minimum(j, tq - 1))

    def out_tile(i, j, bt, ln):
        return (0, jnp.clip(j - b_end, 0, to - 1))

    def fc1_tile(i, j, bt, ln):
        return (0, jnp.clip(j - c_end, 0, tf - 1))

    def fc2_tile(i, j, bt, ln):
        return (jnp.clip(j - c_end, 0, tf - 1), 0)

    def blk_index(i, j, bt, ln):
        # dead steps clamp at the last live block — the repeated index
        # elides the DMA (decode._paged_pallas idiom); ctx==0 stays in
        # range via the max(); j < a_end clamps to the walk's first block
        jl = jnp.maximum(ln[i] - 1, 0) // bs
        return (0, bt[i * nb + jnp.clip(j - a_end, 0, jl)], 0, 0)

    dk = d // 2 if kv_cfg.quantized and kv_cfg.bits == 4 else d
    in_specs = [
        pl.BlockSpec((1, q, h), row3),             # x
        pl.BlockSpec((1, h), const2),              # ln1_w
        pl.BlockSpec((1, h), const2),              # ln1_b
        pl.BlockSpec((h, 3 * h // tq), qkv_tile),  # qkv_kernel tile
        pl.BlockSpec((1, 3 * h), const2),          # qkv_bias
        pl.BlockSpec((heads * d, h // to), out_tile),  # out_kernel tile
        pl.BlockSpec((1, h), const2),              # out_bias
        pl.BlockSpec((1, h), const2),              # ln2_w
        pl.BlockSpec((1, h), const2),              # ln2_b
        pl.BlockSpec((h, f // tf), fc1_tile),      # fc1_kernel tile
        pl.BlockSpec((1, f), const2),              # fc1_bias
        pl.BlockSpec((f // tf, h), fc2_tile),      # fc2_kernel tile
        pl.BlockSpec((1, h), const2),              # fc2_bias
        pl.BlockSpec((heads, 1, bs, dk), blk_index),  # k pool
        pl.BlockSpec((heads, 1, bs, dk), blk_index),  # v pool
    ]
    vec = lambda a: a.reshape(1, -1)
    inputs = [
        x,
        vec(lp["ln1_w"]), vec(lp["ln1_b"]),
        lp["qkv_kernel"], vec(lp["qkv_bias"]),
        lp["out_kernel"], vec(lp["out_bias"]),
        vec(lp["ln2_w"]), vec(lp["ln2_b"]),
        lp["fc1_kernel"], vec(lp["fc1_bias"]),
        lp["fc2_kernel"], vec(lp["fc2_bias"]),
        cache_layer["k"], cache_layer["v"],
    ]
    if kv_cfg.quantized and kv_cfg.bits == 4:
        gdim = d // kv_cfg.kv_group
        in_specs += [pl.BlockSpec((heads, 1, bs, gdim), blk_index),
                     pl.BlockSpec((heads, 1, bs, gdim), blk_index)]
        inputs += [cache_layer["k_scale"], cache_layer["v_scale"]]
    elif kv_cfg.quantized:
        from apex_tpu.serve.decode import _scale_rows

        in_specs += [pl.BlockSpec((heads, 1, 1, bs), blk_index),
                     pl.BlockSpec((heads, 1, 1, bs), blk_index)]
        inputs += [_scale_rows(cache_layer["k_scale"]),
                   _scale_rows(cache_layer["v_scale"])]
    kernel = functools.partial(
        _fused_block_kernel, scale=att_scale, block_size=bs, nb=nb,
        heads=heads, head_dim=d, q_rows=q, tiles=tiles,
        quantized=kv_cfg.quantized, pool_dtype=kv_cfg.dtype, eps=1e-5,
        kv_bits=kv_cfg.bits if kv_cfg.quantized else 8,
        kv_group=kv_cfg.kv_group if kv_cfg.quantized else 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, q, h), row3),
            pl.BlockSpec((1, q, heads, 1, d),
                         lambda i, j, bt, ln: (i, 0, 0, 0, 0)),
            pl.BlockSpec((1, q, heads, 1, d),
                         lambda i, j, bt, ln: (i, 0, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((q, h), jnp.float32),          # h1 (LN1 rows)
            pltpu.VMEM((q, 3 * h), jnp.float32),      # qkv accumulator
            # per-head rows keep a unit dim: heads lead (untiled), so the
            # head-batched matmuls read (H, 1, D) without a shape cast
            pltpu.VMEM((q, heads, 1, d), jnp.float32),   # q rows
            pltpu.VMEM((q, heads, 1, d), jnp.float32),   # fed-row K
            pltpu.VMEM((q, heads, 1, d), jnp.float32),   # fed-row V
            pltpu.VMEM((q, heads, 1, _LANE), jnp.float32),  # softmax m
            pltpu.VMEM((q, heads, 1, _LANE), jnp.float32),  # softmax l
            pltpu.VMEM((q, heads, 1, d), jnp.float32),   # softmax acc
            pltpu.VMEM((q, heads * d), jnp.float32),  # attended ctx rows
            pltpu.VMEM((q, h), jnp.float32),          # residual x1
            pltpu.VMEM((q, h), jnp.float32),          # h2 (LN2 rows)
            pltpu.VMEM((q, h), jnp.float32),          # mlp accumulator
        ],
    )
    x_new, k_new, v_new = pl.pallas_call(
        kernel,
        name="fused_decode_block",
        grid_spec=grid_spec,
        out_shape=[
            _sds((n, q, h), x.dtype, x),
            _sds((n, q, heads, 1, d), x.dtype, x),
            _sds((n, q, heads, 1, d), x.dtype, x),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bt_flat, lens, *inputs)
    return x_new, k_new[:, :, :, 0], v_new[:, :, :, 0]


def fused_layer_decode(x, layer_params, cache_layer, cfg,
                       kv_cfg: KVCacheConfig, block_tables, ctx_lens,
                       interpret: Optional[bool] = None,
                       tiles: Optional[Tuple[int, int, int]] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One transformer layer of the decode step as ONE fused Pallas block.

    ``x``: (n, hidden) residual-stream rows, one per slot. ``ctx_lens``:
    (n,) OLD tokens cached per slot (0 for inactive slots — the kernel
    then skips every pool block and produces finite junk from the
    in-register current token alone). ``tiles``: the weight-tile counts
    ``(t_qkv, t_out, t_ffn)``; ``None`` picks :func:`default_tiles`
    (full residency when it fits — the PR-8 path — else the coarsest
    streaming split that fits). Returns ``(x', k_new, v_new)`` with
    ``k_new``/``v_new`` (n, H, D) in the model dtype — the caller
    scatters them via ``paged_write`` (masking invalid slots exactly
    like the unfused path).
    """
    x_new, k_new, v_new = _fused_block(
        x[:, None, :], layer_params, cache_layer, cfg, kv_cfg,
        block_tables, ctx_lens, tiles, interpret)
    return x_new[:, 0], k_new[:, 0], v_new[:, 0]


def fused_layer_verify(x, layer_params, cache_layer, cfg,
                       kv_cfg: KVCacheConfig, block_tables, start_ctx,
                       interpret: Optional[bool] = None,
                       tiles: Optional[Tuple[int, int, int]] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One transformer layer of the VERIFY step (q fed rows per slot) as
    ONE fused Pallas block.

    ``x``: (n, q, hidden) — each slot's last sampled token plus its
    drafted continuation, embedded. ``start_ctx``: (n,) OLD tokens in
    the pool BEFORE the fed window (0 for inactive slots). Row ``w``
    attends the pool's ``start_ctx`` tokens plus fed rows ``0..w``
    (causal within the window), with every in-register contribution
    passed through the exact pool codec round-trip — so logits match the
    unfused ``gpt_verify_step`` (which writes all q rows first, then
    reads them back) on every VALID row. Rows past ``n_fed`` differ only
    in their junk (the unfused path zeroes their context; this kernel
    gives them the causal window) — both are finite and masked by the
    engine's acceptance loop. Returns ``(x', k_new (n, q, H, D), v_new)``
    for the caller's masked ``paged_write``.
    """
    return _fused_block(x, layer_params, cache_layer, cfg, kv_cfg,
                        block_tables, start_ctx, tiles, interpret)


# ---------------------------------------------------------------------------
# The fused serve programs: embed + scan(fused block + K/V scatter) +
# final LN/logits. Signatures mirror decode.gpt_decode_step /
# decode.gpt_verify_step (minus TP/LoRA, which the megakernel refuses)
# so the engine swaps programs freely.


def gpt_decode_step_fused(params, last_tokens, seq_lens, active, cache,
                          block_tables, cfg, kv_cfg: KVCacheConfig,
                          interpret: Optional[bool] = None,
                          tiles: Optional[Tuple[int, int, int]] = None
                          ) -> Tuple[Pytree, jnp.ndarray]:
    """Advance every active slot by one token with the fused per-layer
    block. Bit-compatible contract with ``decode.gpt_decode_step``
    (q=1, ``tp_axis=None``): same cache-write masking, same junk-logits
    behavior for inactive slots; logits agree within fp32 tolerance
    (``tests/test_megakernel.py`` pins it, plus engine-level greedy and
    same-key sampled stream equality)."""
    from apex_tpu.serve.decode import _check_serve_cfg, _embed, serve_logits

    _check_serve_cfg(cfg, kv_cfg, None)
    refusal = megakernel_refusal(cfg, kv_cfg, allow_interpret=True)
    if refusal is not None:
        raise ValueError(f"megakernel unsupported: {refusal} — use "
                         f"decode.gpt_decode_step")
    positions = jnp.minimum(seq_lens, cfg.max_seq - 1)
    x = _embed(params["embed"], last_tokens, positions, None)   # (n, h)
    ctx_old = jnp.where(active, seq_lens, 0).astype(jnp.int32)

    def body(x, xs):
        lp, cl = xs
        x, k_new, v_new = fused_layer_decode(
            x, lp, cl, cfg, kv_cfg, block_tables, ctx_old,
            interpret=interpret, tiles=tiles)
        cl = paged_write(cl, kv_cfg, k_new.transpose(1, 0, 2),
                         v_new.transpose(1, 0, 2), block_tables,
                         seq_lens, active)
        return x, cl

    x, cache = lax.scan(body, x, (params["layers"], cache))
    return cache, serve_logits(params, x, cfg, None)


def gpt_verify_step_fused(params, fed_tokens, seq_lens, n_fed, active,
                          cache, block_tables, cfg,
                          kv_cfg: KVCacheConfig,
                          interpret: Optional[bool] = None,
                          tiles: Optional[Tuple[int, int, int]] = None
                          ) -> Tuple[Pytree, jnp.ndarray]:
    """Speculative verify on the fused path: feed ``fed_tokens``
    (n, k+1) — each slot's last sampled token followed by up to k
    drafted tokens — through the fused per-layer block in ONE call.

    Same caller contract as ``decode.gpt_verify_step``: returns
    ``(cache', logits (n, k+1, vocab) fp32)`` with logits[i, j] scoring
    the token AFTER fed_tokens[i, j]; rejected drafts' K/V writes need
    no rollback (the accepted length caps ``seq_lens``; stale positions
    are masked by every later context window and overwritten when real
    tokens reach them). The fused block computes all q rows' K/V
    in-kernel and folds them causally through the exact pool codec
    round-trip, then the cache write is the same masked ``paged_write``
    scatter the unfused path uses — pool bytes are BITWISE identical,
    and valid-row logits match within fp32 tolerance (engine streams
    bitwise-equal; ``tests/test_megakernel.py`` pins both)."""
    from apex_tpu.serve.decode import _check_serve_cfg, _embed, serve_logits

    _check_serve_cfg(cfg, kv_cfg, None)
    n, q = fed_tokens.shape
    refusal = megakernel_refusal(cfg, kv_cfg, allow_interpret=True, q=q)
    if refusal is not None:
        raise ValueError(f"megakernel unsupported: {refusal} — use "
                         f"decode.gpt_verify_step")
    heads, d = kv_cfg.num_heads, kv_cfg.head_dim
    offs = jnp.arange(q)
    positions = seq_lens[:, None] + offs[None, :]              # (n, q)
    valid = active[:, None] & (offs[None, :] < n_fed[:, None])
    positions_c = jnp.minimum(positions, cfg.max_seq - 1)
    # flat row views for the paged write (each fed row is its own "slot"
    # sharing its owner's block-table row — the gpt_paged_forward idiom)
    bt_rows = jnp.repeat(block_tables, q, axis=0)
    pos_flat = positions.reshape(-1)
    valid_flat = valid.reshape(-1)
    x = _embed(params["embed"], fed_tokens, positions_c, None)  # (n, q, h)
    ctx_old = jnp.where(active, seq_lens, 0).astype(jnp.int32)

    def body(x, xs):
        lp, cl = xs
        x, k_new, v_new = fused_layer_verify(
            x, lp, cl, cfg, kv_cfg, block_tables, ctx_old,
            interpret=interpret, tiles=tiles)
        k_flat = k_new.reshape(n * q, heads, d)
        v_flat = v_new.reshape(n * q, heads, d)
        cl = paged_write(cl, kv_cfg, k_flat.transpose(1, 0, 2),
                         v_flat.transpose(1, 0, 2), bt_rows, pos_flat,
                         valid_flat)
        return x, cl

    x, cache = lax.scan(body, x, (params["layers"], cache))
    return cache, serve_logits(params, x, cfg, None)
