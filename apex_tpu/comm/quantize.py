"""Blockwise int8 quantize/dequantize for gradient wire compression.

Reference context: NVIDIA Apex ships no gradient compression — its DDP
moves fp16/fp32 buckets (``apex/parallel/distributed.py:425-470``) and its
only wire narrowing is the ZeRO ``e5m2_allgather`` param transport. EQuARX
(arxiv 2506.17615) shows blockwise-quantized AllReduce inside XLA recovers
near-full quality at a fraction of the interconnect bytes; this module is
the codec half of that design: flat fp buffers are split into fixed-size
blocks, each block carries one fp32 scale (absmax/127) and int8 mantissas —
4 bytes of scale overhead per ``block_size`` elements, so the wire cost is
``n + 4n/B`` bytes vs ``4n`` for fp32 (≈3.9× at B=256).

Two implementations with identical deterministic math:

* pure JAX (reshape → absmax → round → clip): XLA fuses this into the
  surrounding program; always available, the ground truth for tests;
* a Pallas TPU kernel (``use_pallas``): one VMEM pass producing the int8
  codes and fp32 scales per row-block — selected automatically on compiled
  TPU backends for tile-aligned shapes, opt-in interpret mode elsewhere
  (the ``ops/layer_norm.py`` gating pattern).

Stochastic rounding (``stochastic=True``) draws one uniform per element and
rounds ``floor(x/scale + u)`` — unbiased (E[q·scale] = x), the standard
requirement for quantized *training* signals; the Pallas path uses the
on-core PRNG (``pltpu.prng_random_bits``), the JAX path ``jax.random``.
Both are deterministic given the seed, but their streams differ — parity
tests pin the deterministic mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops._pallas_util import compiled_backend as _compiled_backend
from apex_tpu.ops._pallas_util import sds as _sds

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QMAX = 127.0  # symmetric int8 code range; -128 is never emitted
QMAX4 = 7.0   # symmetric int4 code range; -8 is never emitted


def qmax_for_bits(bits: int) -> float:
    if bits == 8:
        return QMAX
    if bits == 4:
        return QMAX4
    raise ValueError(f"unsupported code width: {bits} bits")


def blocks_for(n: int, block_size: int) -> int:
    """Number of scale blocks covering ``n`` elements."""
    return -(-n // block_size)


def padded_size(n: int, block_size: int) -> int:
    return blocks_for(n, block_size) * block_size


def _block_scales(xb: jnp.ndarray, qmax: float = QMAX) -> jnp.ndarray:
    """(rows, block) fp32 -> (rows,) fp32 scale = absmax/qmax, with all-zero
    blocks mapped to scale 1 so the quotient is well-defined (codes are 0
    there anyway)."""
    amax = jnp.max(jnp.abs(xb), axis=1)
    return jnp.where(amax > 0, amax / qmax, 1.0)


def _uniform_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 -> [0, 1) fp32 using the top 24 bits (exactly representable)."""
    return (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0**-24)


# ---------------------------------------------------------------------------
# int4 nibble packing. Codes live in [-7, 7]; two two's-complement nibbles
# share one byte (even index -> low nibble), so the packed wire/HBM payload
# is exactly 0.5 B per element. Pure elementwise bit ops — XLA fuses the
# pack/unpack into the surrounding program (and the Pallas paged-attention /
# megakernel paths inline the same unpack in-kernel).


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """int8 codes in [-7, 7], even-sized last axis -> uint8 packed pairs
    (last axis halved)."""
    if q.shape[-1] % 2:
        raise ValueError(f"pack_int4 needs an even last axis: {q.shape}")
    lo = q[..., 0::2].astype(jnp.uint8) & jnp.uint8(0xF)
    hi = q[..., 1::2].astype(jnp.uint8) & jnp.uint8(0xF)
    return lo | (hi << jnp.uint8(4))


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """uint8 packed pairs -> int8 codes (last axis doubled); exact inverse
    of :func:`pack_int4` for codes in [-8, 7]."""
    lo = (packed & jnp.uint8(0xF)).astype(jnp.int8)
    hi = (packed >> jnp.uint8(4)).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    return jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1],
                                                2 * packed.shape[-1])


# ---------------------------------------------------------------------------
# Pure-JAX reference path

def _quantize_jax(x_flat, block_size: int, stochastic: bool, seed,
                  qmax: float = QMAX):
    xb = x_flat.astype(jnp.float32).reshape(-1, block_size)
    scales = _block_scales(xb, qmax)
    y = xb / scales[:, None]
    if stochastic:
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        u = _uniform_from_bits(
            jax.random.bits(key, xb.shape, dtype=jnp.uint32))
        q = jnp.floor(y + u)
    else:
        q = jnp.round(y)
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int8)
    return q.reshape(-1), scales


def _dequantize_jax(q_flat, scales, block_size: int):
    qb = q_flat.reshape(-1, block_size).astype(jnp.float32)
    return (qb * scales[:, None]).reshape(-1)


# ---------------------------------------------------------------------------
# Pallas kernels — one pass per row-block of (rows_per_step, block) elements

def _quant_kernel(x_ref, q_ref, s_ref, *, qmax=QMAX):
    x = x_ref[:].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q_ref[:] = q.astype(jnp.int8)
    s_ref[:] = scale


def _quant_kernel_stochastic(x_ref, seed_ref, q_ref, s_ref, *, qmax=QMAX):
    # one PRNG stream per grid step: the per-core PRNG is reseeded with the
    # (seed, program_id) pair so every row-block draws independent bits
    pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    x = x_ref[:].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    y = x / scale
    bits = pltpu.bitcast(pltpu.prng_random_bits(y.shape), jnp.uint32)
    q = jnp.clip(jnp.floor(y + _uniform_from_bits(bits)), -qmax, qmax)
    q_ref[:] = q.astype(jnp.int8)
    s_ref[:] = scale


def _dequant_kernel(q_ref, s_ref, y_ref):
    y_ref[:] = (q_ref[:].astype(jnp.float32) * s_ref[:]).astype(y_ref.dtype)


# int8 VREG tiling wants (32, 128) blocks; a grid step holds a few fp32
# copies of the row block — keep it well under a core's VMEM
_ROWS_PER_STEP = 32


def _pallas_ok(n: int, block_size: int, allow_interpret: bool) -> bool:
    if block_size % 128 != 0:
        return False
    rows = n // block_size
    if n % block_size != 0 or rows % _ROWS_PER_STEP != 0:
        return False
    return allow_interpret or _compiled_backend()


def _interpret_default() -> bool:
    return not _compiled_backend()


def _quantize_pallas(x_flat, block_size: int, stochastic: bool, seed,
                     qmax: float = QMAX):
    rows = x_flat.size // block_size
    x2d = x_flat.reshape(rows, block_size)
    grid = (rows // _ROWS_PER_STEP,)
    out_shape = [
        _sds((rows, block_size), jnp.int8, x_flat),
        _sds((rows, 1), jnp.float32, x_flat),
    ]
    out_specs = [
        pl.BlockSpec((_ROWS_PER_STEP, block_size), lambda i: (i, 0)),
        pl.BlockSpec((_ROWS_PER_STEP, 1), lambda i: (i, 0)),
    ]
    x_spec = pl.BlockSpec((_ROWS_PER_STEP, block_size), lambda i: (i, 0))
    if stochastic:
        q, s = pl.pallas_call(
            functools.partial(_quant_kernel_stochastic, qmax=qmax),
            grid=grid,
            in_specs=[
                x_spec,
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=_interpret_default(),
        )(x2d, jnp.asarray(seed, jnp.int32).reshape((1,)))
    else:
        q, s = pl.pallas_call(
            functools.partial(_quant_kernel, qmax=qmax),
            grid=grid,
            in_specs=[x_spec],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=_interpret_default(),
        )(x2d)
    return q.reshape(-1), s.reshape(-1)


def _dequantize_pallas(q_flat, scales, block_size: int):
    rows = q_flat.size // block_size
    y = pl.pallas_call(
        _dequant_kernel,
        grid=(rows // _ROWS_PER_STEP,),
        in_specs=[
            pl.BlockSpec((_ROWS_PER_STEP, block_size), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS_PER_STEP, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS_PER_STEP, block_size),
                               lambda i: (i, 0)),
        out_shape=_sds((rows, block_size), jnp.float32, q_flat, scales),
        interpret=_interpret_default(),
    )(q_flat.reshape(rows, block_size), scales.reshape(rows, 1))
    return y.reshape(-1)


# ---------------------------------------------------------------------------
# Public API

def quantize_blockwise(
    x_flat: jnp.ndarray,
    block_size: int = 256,
    stochastic: bool = False,
    seed=None,
    use_pallas: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Flat fp buffer -> (int8 codes (n,), fp32 per-block scales (n/B,)).

    ``x_flat.size`` must be a multiple of ``block_size`` (callers pad; see
    :func:`padded_size`). ``seed``: int32 scalar, required when
    ``stochastic`` — the codes are deterministic given it.
    """
    if x_flat.ndim != 1:
        raise ValueError(f"expected flat buffer, got shape {x_flat.shape}")
    if x_flat.size % block_size != 0:
        raise ValueError(
            f"size {x_flat.size} not a multiple of block_size {block_size}")
    if stochastic and seed is None:
        raise ValueError("stochastic quantization needs a seed")
    if use_pallas is None:
        use_pallas = _pallas_ok(x_flat.size, block_size,
                                allow_interpret=False)
    elif use_pallas and not _pallas_ok(x_flat.size, block_size,
                                       allow_interpret=True):
        raise ValueError(
            f"pallas quantize needs block_size % 128 == 0 and "
            f"rows % {_ROWS_PER_STEP} == 0; got n={x_flat.size}, "
            f"block_size={block_size}")
    if stochastic and use_pallas and _interpret_default():
        # pltpu.prng_* has no CPU interpreter lowering — the stochastic
        # kernel is compiled-Mosaic-only; off-TPU the JAX stream stands in
        # (different bits, same distribution — parity tests pin the
        # deterministic mode)
        use_pallas = False
    if use_pallas:
        return _quantize_pallas(x_flat, block_size, stochastic, seed)
    return _quantize_jax(x_flat, block_size, stochastic, seed)


def dequantize_blockwise(
    q_flat: jnp.ndarray,
    scales: jnp.ndarray,
    block_size: int = 256,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """(int8 codes, fp32 scales) -> fp32 flat buffer."""
    if q_flat.size % block_size != 0:
        raise ValueError(
            f"size {q_flat.size} not a multiple of block_size {block_size}")
    if use_pallas is None:
        use_pallas = _pallas_ok(q_flat.size, block_size,
                                allow_interpret=False)
    elif use_pallas and not _pallas_ok(q_flat.size, block_size,
                                       allow_interpret=True):
        raise ValueError(
            f"pallas dequantize needs block_size % 128 == 0 and "
            f"rows % {_ROWS_PER_STEP} == 0; got n={q_flat.size}, "
            f"block_size={block_size}")
    if use_pallas:
        return _dequantize_pallas(q_flat, scales, block_size)
    return _dequantize_jax(q_flat, scales, block_size)


@functools.partial(jax.jit, static_argnums=(1,))
def quantization_error(x_flat, block_size: int = 256):
    """Round-trip error ``x - dq(q(x))`` of the deterministic codec — the
    quantity error feedback re-injects (``error_feedback.py``)."""
    q, s = quantize_blockwise(x_flat, block_size)
    return x_flat.astype(jnp.float32) - dequantize_blockwise(q, s, block_size)


# ---------------------------------------------------------------------------
# 4-bit group-quantized codec. Same scale/rounding machinery at the ±7 code
# range (one fp32 scale per ``group_size`` elements — "group" is the sub-8-
# bit literature's name for the int8 codec's "block"), with the codes
# nibble-packed two per byte: the wire/HBM payload is ``n/2 + 4·n/G`` bytes
# vs ``4n`` fp32 (≈7.5× at G=128). The rounding (incl. the stochastic
# Pallas path — on-core PRNG) happens in the shared kernels; the pack is a
# fused elementwise bit op.


def quantize_blockwise_int4(
    x_flat: jnp.ndarray,
    group_size: int = 128,
    stochastic: bool = False,
    seed=None,
    use_pallas: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Flat fp buffer -> (packed uint8 codes (n/2,), fp32 per-group scales
    (n/G,)). ``x_flat.size`` must be a multiple of the (even) group size;
    ``seed`` as in :func:`quantize_blockwise`."""
    if x_flat.ndim != 1:
        raise ValueError(f"expected flat buffer, got shape {x_flat.shape}")
    if group_size % 2:
        raise ValueError(
            f"int4 group_size must be even (nibble packing): {group_size}")
    if x_flat.size % group_size != 0:
        raise ValueError(
            f"size {x_flat.size} not a multiple of group_size {group_size}")
    if stochastic and seed is None:
        raise ValueError("stochastic quantization needs a seed")
    if use_pallas is None:
        use_pallas = _pallas_ok(x_flat.size, group_size,
                                allow_interpret=False)
    elif use_pallas and not _pallas_ok(x_flat.size, group_size,
                                       allow_interpret=True):
        raise ValueError(
            f"pallas int4 quantize needs group_size % 128 == 0 and "
            f"rows % {_ROWS_PER_STEP} == 0; got n={x_flat.size}, "
            f"group_size={group_size}")
    if stochastic and use_pallas and _interpret_default():
        use_pallas = False  # pltpu.prng_* is compiled-Mosaic-only
    if use_pallas:
        q, s = _quantize_pallas(x_flat, group_size, stochastic, seed,
                                qmax=QMAX4)
    else:
        q, s = _quantize_jax(x_flat, group_size, stochastic, seed,
                             qmax=QMAX4)
    return pack_int4(q), s


def dequantize_blockwise_int4(
    packed: jnp.ndarray,
    scales: jnp.ndarray,
    group_size: int = 128,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """(packed uint8 codes, fp32 group scales) -> fp32 flat buffer."""
    q = unpack_int4(packed)
    return dequantize_blockwise(q, scales, group_size, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnums=(1,))
def quantization_error_int4(x_flat, group_size: int = 128):
    """Round-trip error of the deterministic int4 codec (the EF residual
    quantity for the ``int4_ef`` policy)."""
    q, s = quantize_blockwise_int4(x_flat, group_size)
    return x_flat.astype(jnp.float32) - dequantize_blockwise_int4(
        q, s, group_size)
