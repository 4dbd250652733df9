"""Fused LayerNorm / RMSNorm — Pallas TPU kernels with an XLA fallback.

Reference: ``csrc/layer_norm_cuda_kernel.cu`` — Welford forward
(``cuApplyLayerNorm:411``), two-stage γ/β gradient (``cuComputePartGradGammaBeta:541``)
and dgrad (``:678``); plus the ``fast_layer_norm`` contrib ext
(``apex/contrib/csrc/layer_norm/``) for large hidden sizes. The Python driver
is ``apex/normalization/fused_layer_norm.py``.

TPU re-design: one Pallas kernel per direction. Rows are blocked over the
grid; each block computes row statistics in fp32 on the VPU, normalizes, and
applies the affine. The backward accumulates the γ/β partials across
sequential grid steps into a single output block — the Pallas equivalent of
the reference's two-stage part-grad reduction (TPU grids iterate sequentially,
so accumulation into a shared output block replaces the CUDA inter-block
reduction). Variance uses the E[x²]−E[x]² form so zero-padded lanes (hidden
not a multiple of the 128-lane tile) cannot corrupt the sums; the Pallas path
is gated to tile-aligned shapes anyway, with the XLA path (same math, fused
well by XLA) covering the rest.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops._pallas_util import compiled_backend as _compiled_backend
from apex_tpu.ops._pallas_util import pvary_like as _pvary_like
from apex_tpu.ops._pallas_util import sds as _sds

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Pure-JAX reference implementations (the math XLA fuses on its own; also the
# ground truth the kernels are tested against).

def layer_norm_reference(x, weight=None, bias=None, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    # clamp: E[x²]−E[x]² cancellation can dip negative → nan through rsqrt
    var = jnp.maximum(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) - jnp.square(mean),
        0.0)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_reference(x, weight=None, eps: float = 1e-5):
    """Ref ``apex/normalization/fused_layer_norm.py:16-31`` (manual_rms_norm)."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps, hidden):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.sum(x, axis=1, keepdims=True) / hidden
    msq = jnp.sum(x * x, axis=1, keepdims=True) / hidden
    var = jnp.maximum(msq - mean * mean, 0.0)  # cancellation guard
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _ln_bwd_kernel(
    dy_ref, x_ref, mean_ref, rstd_ref, w_ref, dx_ref, dw_ref, db_ref, *, hidden
):
    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    w = w_ref[:].astype(jnp.float32)
    xhat = (x - mean) * rstd

    # dgrad (ref cuComputeGradInput:678): dx = rstd*(g - mean(g) - xhat*mean(g*xhat))
    g = dy * w
    c1 = jnp.sum(g, axis=1, keepdims=True) / hidden
    c2 = jnp.sum(g * xhat, axis=1, keepdims=True) / hidden
    dx = (g - c1 - xhat * c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)

    # two-stage γ/β grads: partial sums per row-block accumulated across the
    # sequential grid into one (1, hidden) block (ref cuComputePartGradGammaBeta).
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    dw_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


def _rms_fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps, hidden):
    x = x_ref[:].astype(jnp.float32)
    msq = jnp.sum(x * x, axis=1, keepdims=True) / hidden
    rstd = jax.lax.rsqrt(msq + eps)
    y = x * rstd * w_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    rstd_ref[:] = rstd


def _rms_bwd_kernel(dy_ref, x_ref, rstd_ref, w_ref, dx_ref, dw_ref, *, hidden):
    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    w = w_ref[:].astype(jnp.float32)
    xhat = x * rstd
    g = dy * w
    c2 = jnp.sum(g * xhat, axis=1, keepdims=True) / hidden
    dx = (g - xhat * c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    dw_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)


# The backward kernel keeps ~7 block-sized fp32 buffers resident (dy, x,
# xhat, g, dx + weight row + partial-grad row); budget half of a core's
# ~16 MB VMEM. The reference needs a separate ``fast_layer_norm`` extension
# for large hidden (up to 65k); here large hidden shrinks the row block and
# past the budget falls back to the XLA path rather than faulting on VMEM.
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024
_BWD_LIVE_BUFFERS = 7


def _pick_block_rows(rows: int, hidden: int) -> Optional[int]:
    for cand in (256, 128, 64, 32, 16, 8):
        if (rows % cand == 0
                and cand * hidden * 4 * _BWD_LIVE_BUFFERS
                <= _VMEM_BUDGET_BYTES):
            return cand
    return None


def _pallas_ok(rows: int, hidden: int, allow_interpret: bool) -> bool:
    """Shape/platform gate. By default the Pallas path is only *selected* on
    real TPU; off-TPU it runs through the (slow) Pallas interpreter and is
    therefore opt-in via use_pallas=True (tests do this)."""
    if _pick_block_rows(rows, hidden) is None:
        return False
    if hidden % 128 != 0:
        return False
    return allow_interpret or _compiled_backend()


def _interpret_default() -> bool:
    return not _compiled_backend()


# ---------------------------------------------------------------------------
# custom_vjp entry points

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm_affine(x2d, w, b, eps):
    y, _, _ = _ln_fwd(x2d, w, b, eps)
    return y


def _ln_fwd(x2d, w, b, eps):
    rows, hidden = x2d.shape
    block = _pick_block_rows(rows, hidden)
    interpret = _interpret_default()
    kernel = functools.partial(_ln_fwd_kernel, eps=eps, hidden=hidden)
    y, mean, rstd = pl.pallas_call(
        kernel,
        name="layer_norm_fwd",
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            _sds((rows, hidden), x2d.dtype, x2d, w, b),
            _sds((rows, 1), jnp.float32, x2d, w, b),
            _sds((rows, 1), jnp.float32, x2d, w, b),
        ],
        interpret=interpret,
    )(x2d, w.reshape(1, -1), b.reshape(1, -1))
    return y, mean, rstd


def _layer_norm_affine_fwd(x2d, w, b, eps):
    y, mean, rstd = _ln_fwd(x2d, w, b, eps)
    return y, (x2d, w, mean, rstd)


def _layer_norm_affine_bwd(eps, res, dy):
    x2d, w, mean, rstd = res
    rows, hidden = x2d.shape
    block = _pick_block_rows(rows, hidden)
    kernel = functools.partial(_ln_bwd_kernel, hidden=hidden)
    dx, dw, db = pl.pallas_call(
        kernel,
        name="layer_norm_bwd",
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_shape=[
            _sds((rows, hidden), x2d.dtype, x2d, w, dy),
            _sds((1, hidden), jnp.float32, x2d, w, dy),
            _sds((1, hidden), jnp.float32, x2d, w, dy),
        ],
        interpret=_interpret_default(),
    )(dy, x2d, mean, rstd, w.reshape(1, -1))
    return dx, dw.reshape(-1).astype(w.dtype), db.reshape(-1).astype(w.dtype)


_layer_norm_affine.defvjp(_layer_norm_affine_fwd, _layer_norm_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_affine(x2d, w, eps):
    y, _ = _rms_fwd(x2d, w, eps)
    return y


def _rms_fwd(x2d, w, eps):
    rows, hidden = x2d.shape
    block = _pick_block_rows(rows, hidden)
    kernel = functools.partial(_rms_fwd_kernel, eps=eps, hidden=hidden)
    y, rstd = pl.pallas_call(
        kernel,
        name="rms_norm_fwd",
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            _sds((rows, hidden), x2d.dtype, x2d, w),
            _sds((rows, 1), jnp.float32, x2d, w),
        ],
        interpret=_interpret_default(),
    )(x2d, w.reshape(1, -1))
    return y, rstd


def _rms_norm_affine_fwd(x2d, w, eps):
    y, rstd = _rms_fwd(x2d, w, eps)
    return y, (x2d, w, rstd)


def _rms_norm_affine_bwd(eps, res, dy):
    x2d, w, rstd = res
    rows, hidden = x2d.shape
    block = _pick_block_rows(rows, hidden)
    kernel = functools.partial(_rms_bwd_kernel, hidden=hidden)
    dx, dw = pl.pallas_call(
        kernel,
        name="rms_norm_bwd",
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_shape=[
            _sds((rows, hidden), x2d.dtype, x2d, w, dy),
            _sds((1, hidden), jnp.float32, x2d, w, dy),
        ],
        interpret=_interpret_default(),
    )(dy, x2d, rstd, w.reshape(1, -1))
    return dx, dw.reshape(-1).astype(w.dtype)


_rms_norm_affine.defvjp(_rms_norm_affine_fwd, _rms_norm_affine_bwd)


# ---------------------------------------------------------------------------
# Public functional API

def layer_norm(
    x,
    weight=None,
    bias=None,
    eps: float = 1e-5,
    use_pallas: Optional[bool] = None,
):
    """Fused layer norm over the last axis (ref ``fused_layer_norm_cuda``
    forward/backward entry points, ``csrc/layer_norm_cuda.cpp:428-440``).

    Pallas kernel when shapes are tile-aligned on TPU (or interpret mode on
    CPU); identical-math XLA fallback otherwise. ``weight``/``bias`` may be
    None (non-affine variant, ref ``fused_layer_norm.py:32-58``).
    """
    hidden = x.shape[-1]
    rows = math.prod(x.shape[:-1])
    if use_pallas is None:
        use_pallas = _pallas_ok(rows, hidden, allow_interpret=False)
    elif use_pallas and not _pallas_ok(rows, hidden, allow_interpret=True):
        raise ValueError(
            f"pallas layer_norm requires row count divisible by 8, hidden "
            f"% 128 == 0, and a row block fitting VMEM at this hidden size; "
            f"got shape {x.shape}"
        )
    if not use_pallas or weight is None or bias is None:
        return layer_norm_reference(x, weight, bias, eps)
    x2d = x.reshape(rows, hidden)
    # replicated affine params under a data-sharded batch: the custom_vjp
    # hides their linearity from shard_map, so the dw/db reduction over the
    # data axes has to be made explicit (``pvary_like``)
    return _layer_norm_affine(x2d, _pvary_like(weight, x2d),
                              _pvary_like(bias, x2d), eps).reshape(x.shape)


def rms_norm(
    x,
    weight=None,
    eps: float = 1e-5,
    use_pallas: Optional[bool] = None,
):
    """Fused RMS norm (ref RMSNorm variants in ``csrc/layer_norm_cuda.cpp``)."""
    hidden = x.shape[-1]
    rows = math.prod(x.shape[:-1])
    if use_pallas is None:
        use_pallas = _pallas_ok(rows, hidden, allow_interpret=False)
    elif use_pallas and not _pallas_ok(rows, hidden, allow_interpret=True):
        raise ValueError(
            f"pallas rms_norm requires row count divisible by 8, hidden "
            f"% 128 == 0, and a row block fitting VMEM at this hidden size; "
            f"got shape {x.shape}"
        )
    if not use_pallas or weight is None:
        return rms_norm_reference(x, weight, eps)
    x2d = x.reshape(rows, hidden)
    return _rms_norm_affine(x2d, _pvary_like(weight, x2d),
                            eps).reshape(x.shape)


