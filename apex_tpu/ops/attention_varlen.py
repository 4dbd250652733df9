"""Segment-aware (packed varlen) flash attention — Pallas TPU kernels.

Reference: ``apex/contrib/csrc/fmha/`` (7.3k LoC CUDA) — fused attention
over token-packed variable-length batches, driven by
``apex/contrib/fmha/fmha.py:33-76`` with ``cu_seqlens`` prefix sums. The
kernel family exists precisely so packed batches never materialize the
(total, total) score matrix; it is hard-limited to seqlen <= 512.

TPU re-design: the flash scheme of ``ops/attention.py`` extended with
per-token integer segment ids (-1 = padding):

* an in-tile mask ``allowed = (seg_q == seg_k) & (seg_q >= 0)`` — pads
  match nothing, including other pads, and fully-masked query rows emit
  zero output (the reference kernels also zero pad outputs);
* **block-level early exit**: per-block segment [min, max] ranges are
  precomputed on the host side of the launch and passed through scalar
  prefetch; a K/V block whose segment range cannot intersect the Q block's
  is skipped before any MXU work. Packed sequences are contiguous, so for
  a batch of length-L sequences this recovers the O(total x L) work of the
  reference's per-sequence launch without its seqlen limit.

Backward masks ``p`` explicitly (a pad row has lse == NEG_INF and
``exp(s - lse)`` would resurrect as 1), then follows the standard flash
dQ / dK+dV accumulation kernels.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_util import sds as _sds
from apex_tpu.ops._pallas_util import compiled_backend as _compiled_backend
from apex_tpu.ops.attention import NEG_INF, _pick_block


# ---------------------------------------------------------------------------
# Dense reference (ground truth + fallback)

def attention_varlen_reference(q, k, v, seg_q, seg_k=None,
                               causal: bool = False,
                               scale: Optional[float] = None):
    """Dense segment-masked attention; pad (seg < 0) query rows output 0.

    ``q``/``k``/``v``: (b, h, s, d); ``seg_q``/``seg_k``: (b, s) int32.
    """
    if seg_k is None:
        seg_k = seg_q
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    allowed = ((seg_q[:, None, :, None] == seg_k[:, None, None, :])
               & (seg_q[:, None, :, None] >= 0))
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        allowed = allowed & (jnp.arange(sk)[None, None, None, :]
                             <= jnp.arange(sq)[None, None, :, None])
    s = jnp.where(allowed, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(allowed, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    o = o / jnp.where(l == 0.0, 1.0, l)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Kernels. Grid (b, h, nq, nk) — batch and head split so the scalar-prefetch
# block ranges (b, nq)/(b, nk) index directly by the first grid dim.

# Mosaic requires a block's last two dims to be (8k, 128k)-divisible or
# equal to the full array dims; a (1, block) slice of a (b, s) id array is
# neither. Widen host-side instead (the jax.experimental flash kernel's
# scheme): q ids broadcast along a 128-lane axis -> (b, sq, 128) so a
# (1, block_q, 128) block is tile-legal and column 0 is the id column;
# kv ids broadcast along an 8-sublane axis -> (b, 8, sk) so a
# (1, 8, block_k) block is legal and row 0 is the id row.
_SEG_LANES = 128
_SEG_SUBLANES = 8


def _pick_kv_block(sk: int, want: int):
    """KV block size whose seg-id block is Mosaic-legal: the (1, 8, block_k)
    seg_k tile has block_k on the LANE dim, so it must be a multiple of 128
    — or one full-seq block (block == array dim is always legal; sublane
    rules still need sk % 8 == 0). A sub-128 ``want`` is coerced UP to the
    smallest legal size (128) rather than down: 128 divides every seq a
    sub-128 power-of-two block would have divided more often than not, and
    honoring the hint exactly is impossible. Returns None when nothing is
    legal (callers fall back to the dense reference)."""
    for cand in (1024, 512, 256, 128):
        if cand <= max(want, 128) and cand <= sk and sk % cand == 0:
            return cand
    if sk % 8 == 0 and sk <= 2048:  # one block; cap keeps K/V tiles in VMEM
        return sk
    return None


def _seg_wide(seg_q, seg_k):
    """(b, sq)/(b, sk) int32 ids -> tile-legal (b, sq, 128) / (b, 8, sk)."""
    b, sq = seg_q.shape
    sk = seg_k.shape[1]
    segq3 = jax.lax.broadcast_in_dim(seg_q, (b, sq, _SEG_LANES), (0, 1))
    segk3 = jax.lax.broadcast_in_dim(seg_k, (b, _SEG_SUBLANES, sk), (0, 2))
    return segq3, segk3


def _seg_tile(seg_q_ref, seg_k_ref):
    """(1, bq, 128) x (1, 8, bk) segment blocks -> (bq, bk) allowed mask."""
    sq_col = seg_q_ref[0, :, :1]  # (bq, 1)
    sk_row = seg_k_ref[0, :1, :]  # (1, bk)
    return (sq_col == sk_row) & (sq_col >= 0)


def _skip(qmin_ref, qmax_ref, kmin_ref, kmax_ref, b_i, q_i, kv_i,
          causal, block_q, block_k):
    interact = ~((qmin_ref[b_i, q_i] > kmax_ref[b_i, kv_i])
                 | (qmax_ref[b_i, q_i] < kmin_ref[b_i, kv_i]))
    run = interact & (qmax_ref[b_i, q_i] >= 0) & (kmax_ref[b_i, kv_i] >= 0)
    if causal:
        run = run & (kv_i * block_k <= q_i * block_q + block_q - 1)
    return run


def _vl_fwd_kernel(qmin_ref, qmax_ref, kmin_ref, kmax_ref, jlo_ref, jhi_ref,
                   seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref,
                   o_ref, lse_ref, m_scr, l_scr, acc_scr,
                   *, scale, causal, block_q, block_k, nk):
    b_i = pl.program_id(0)
    q_i = pl.program_id(2)
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = _skip(qmin_ref, qmax_ref, kmin_ref, kmax_ref, b_i, q_i, kv_i,
                causal, block_q, block_k)

    @pl.when(run)
    def _compute():
        # model dtype straight into the MXU (fp32 upcast would leave the
        # fast bf16 matmul path); accumulation stays fp32
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        allowed = _seg_tile(seg_q_ref, seg_k_ref)
        if causal:
            qpos = q_i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            allowed = allowed & (kpos <= qpos)
        s = jnp.where(allowed, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(allowed, p, 0.0)  # all-masked rows: m_new = NEG_INF
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l_scr[:, :1] = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kv_i == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l == 0.0, NEG_INF, m_scr[:, :1] + jnp.log(safe_l))


def _vl_bwd_dq_kernel(qmin_ref, qmax_ref, kmin_ref, kmax_ref, jlo_ref,
                      jhi_ref, seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref,
                      do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
                      *, scale, causal, block_q, block_k, nk):
    b_i = pl.program_id(0)
    q_i = pl.program_id(2)
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = _skip(qmin_ref, qmax_ref, kmin_ref, kmax_ref, b_i, q_i, kv_i,
                causal, block_q, block_k)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        allowed = _seg_tile(seg_q_ref, seg_k_ref)
        if causal:
            qpos = q_i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            allowed = allowed & (kpos <= qpos)
        # mask p by value: pad rows have lse == NEG_INF and exp(s - lse)
        # would otherwise resurrect to 1
        p = jnp.where(allowed, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    @pl.when(kv_i == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _vl_bwd_dkv_kernel(qmin_ref, qmax_ref, kmin_ref, kmax_ref, ilo_ref,
                       ihi_ref, seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref,
                       do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr,
                       dv_scr, *, scale, causal, block_q, block_k, nq):
    b_i = pl.program_id(0)
    kv_i = pl.program_id(2)
    q_i = pl.program_id(3)

    @pl.when(q_i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = _skip(qmin_ref, qmax_ref, kmin_ref, kmax_ref, b_i, q_i, kv_i,
                causal, block_q, block_k)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        allowed = _seg_tile(seg_q_ref, seg_k_ref)
        if causal:
            qpos = q_i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            allowed = allowed & (kpos <= qpos)
        p = jnp.where(allowed, jnp.exp(s - lse), 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(q_i == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Launch plumbing

def _block_ranges(seg, block):
    """(b, s) -> per-block (b, s//block) min and max segment ids."""
    b, s = seg.shape
    r = seg.reshape(b, s // block, block)
    return r.min(axis=2), r.max(axis=2)


def _interact_matrix(qmin, qmax, kmin, kmax, causal, block_q, block_k):
    """(b, nq, nk) bool: can q block i and kv block j interact at all?
    Mirrors the kernel-side ``_skip`` predicate exactly."""
    inter = ((qmin[:, :, None] <= kmax[:, None, :])
             & (qmax[:, :, None] >= kmin[:, None, :])
             & (qmax[:, :, None] >= 0) & (kmax[:, None, :] >= 0))
    if causal:
        nq, nk = qmin.shape[1], kmin.shape[1]
        i = jnp.arange(nq)[None, :, None]
        j = jnp.arange(nk)[None, None, :]
        inter = inter & (j * block_k <= i * block_q + block_q - 1)
    return inter


def _live_range(inter, axis):
    """First/last True index along ``axis`` of the interact matrix (0 when
    the row is empty — the clamp target is arbitrary for rows the kernel's
    ``run`` predicate skips entirely)."""
    n = inter.shape[axis]
    any_ = inter.any(axis=axis)
    lo = jnp.where(any_, jnp.argmax(inter, axis=axis), 0)
    hi = jnp.where(any_,
                   n - 1 - jnp.argmax(jnp.flip(inter, axis=axis), axis=axis),
                   0)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _vl_call(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k,
             interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    qmin, qmax = _block_ranges(seg_q, block_q)
    kmin, kmax = _block_ranges(seg_k, block_k)
    # per-q-block live kv range: index maps clamp the kv fetch into it so
    # skipped iterations re-request an edge block (Mosaic elides the
    # repeated copy) instead of streaming dead K/V
    inter = _interact_matrix(qmin, qmax, kmin, kmax, causal,
                             block_q, block_k)
    jlo, jhi = _live_range(inter, axis=2)

    def kv_index(b, h, i, j, qmn, qmx, kmn, kmx, jlo, jhi):
        jc = jnp.clip(j, jlo[b, i], jhi[b, i])
        return (b, h, jc, 0)

    def segk_index(b, h, i, j, qmn, qmx, kmn, kmx, jlo, jhi):
        return (b, 0, jnp.clip(j, jlo[b, i], jhi[b, i]))

    seg_q3, seg_k3 = _seg_wide(seg_q, seg_k)
    kernel = functools.partial(
        _vl_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, _SEG_LANES),
                         lambda b, h, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, _SEG_SUBLANES, block_k), segk_index),
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j, *_: (b, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            _sds((b, h, sq, d), q.dtype, q, k, v),
            _sds((b, h, sq, 1), jnp.float32, q, k, v),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qmin, qmax, kmin, kmax, jlo, jhi, seg_q3, seg_k3, q, k, v)
    return o, lse


def _vl_bwd_call(q, k, v, seg_q, seg_k, o, lse, do, scale, causal,
                 block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    qmin, qmax = _block_ranges(seg_q, block_q)
    kmin, kmax = _block_ranges(seg_k, block_k)
    inter = _interact_matrix(qmin, qmax, kmin, kmax, causal,
                             block_q, block_k)
    jlo, jhi = _live_range(inter, axis=2)  # per q block: live kv range
    ilo, ihi = _live_range(inter, axis=1)  # per kv block: live q range
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def kv_index(b, h, i, j, qmn, qmx, kmn, kmx, jlo, jhi):
        return (b, h, jnp.clip(j, jlo[b, i], jhi[b, i]), 0)

    def segk_index(b, h, i, j, qmn, qmx, kmn, kmx, jlo, jhi):
        return (b, 0, jnp.clip(j, jlo[b, i], jhi[b, i]))

    seg_q3, seg_k3 = _seg_wide(seg_q, seg_k)

    dq = pl.pallas_call(
        functools.partial(_vl_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, _SEG_LANES),
                             lambda b, h, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, _SEG_SUBLANES, block_k), segk_index),
                pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, d), kv_index),
                pl.BlockSpec((1, 1, block_k, d), kv_index),
                pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j, *_: (b, h, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda b, h, i, j, *_: (b, h, i, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=_sds((b, h, sq, d), q.dtype, q, k, v, do),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qmin, qmax, kmin, kmax, jlo, jhi, seg_q3, seg_k3, q, k, v, do, lse, delta)

    def q_index(b, h, j, i, qmn, qmx, kmn, kmx, ilo, ihi):
        return (b, h, jnp.clip(i, ilo[b, j], ihi[b, j]), 0)

    def q1_index(b, h, j, i, qmn, qmx, kmn, kmx, ilo, ihi):
        return (b, h, jnp.clip(i, ilo[b, j], ihi[b, j]), 0)

    def segq_index(b, h, j, i, qmn, qmx, kmn, kmx, ilo, ihi):
        return (b, jnp.clip(i, ilo[b, j], ihi[b, j]), 0)

    dk, dv = pl.pallas_call(
        functools.partial(_vl_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(b, h, nk, nq),
            in_specs=[
                pl.BlockSpec((1, block_q, _SEG_LANES), segq_index),
                pl.BlockSpec((1, _SEG_SUBLANES, block_k),
                             lambda b, h, j, i, *_: (b, 0, j)),
                pl.BlockSpec((1, 1, block_q, d), q_index),
                pl.BlockSpec((1, 1, block_k, d), lambda b, h, j, i, *_: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block_k, d), lambda b, h, j, i, *_: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block_q, d), q_index),
                pl.BlockSpec((1, 1, block_q, 1), q1_index),
                pl.BlockSpec((1, 1, block_q, 1), q1_index),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, j, i, *_: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, j, i, *_: (b, h, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            _sds((b, h, sk, d), k.dtype, q, k, v, do),
            _sds((b, h, sk, d), v.dtype, q, k, v, do),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qmin, qmax, kmin, kmax, ilo, ihi, seg_q3, seg_k3, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp + public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _varlen(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k,
            interpret):
    o, _ = _varlen_fwd(q, k, v, seg_q, seg_k, scale, causal, block_q,
                       block_k, interpret)
    return o


def _varlen_fwd(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k,
                interpret):
    o, lse = _vl_call(q, k, v, seg_q, seg_k, scale, causal, block_q,
                      block_k, interpret)
    # same names as the dense flash residuals: the dots_attn remat policy
    # saves them so backward skips the forward-kernel replay
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, seg_q, seg_k, o, lse)


def _varlen_bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, seg_q, seg_k, o, lse = res
    dq, dk, dv = _vl_bwd_call(q, k, v, seg_q, seg_k, o, lse, do, scale,
                              causal, block_q, block_k, interpret)
    return dq, dk, dv, None, None


_varlen.defvjp(_varlen_fwd, _varlen_bwd)


def flash_attention_varlen(
    q, k, v, seg_q, seg_k=None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
):
    """Packed-varlen attention over (b, h, s, d) with (b, s) segment ids.

    Pads (seg < 0) attend to nothing and output zero. Pallas kernels with
    block-level segment skipping on TPU; dense masked reference elsewhere.
    ``block_k`` is a hint, not a contract: the widened seg-id lane layout
    makes sub-128 kv blocks Mosaic-illegal, so a request that resolves to
    one is coerced to the nearest legal size (a 128-multiple dividing the
    seq, else one full-seq block — which also disables block skipping).
    ``interpret`` selects interpret vs compiled Mosaic execution of the
    Pallas path and therefore only applies when that path is taken; pass
    ``use_pallas=True`` alongside it (``interpret=False`` + the
    ``force_compiled()`` context is how the AOT TPU-lowering guard runs
    Mosaic verification on a CPU box), else ValueError.
    """
    if seg_k is None:
        seg_k = seg_q
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq = _pick_block(sq, block_q)
    bk = _pick_kv_block(sk, block_k)
    if (d % 8 == 0 and (bq is None or bk is None)
            and (use_pallas or (use_pallas is None and _compiled_backend()))):
        # seq lengths with no legal block (e.g. sk = 2056: 8-aligned but
        # not 128-divisible and past the one-block VMEM cap) would
        # otherwise drop to the dense O(s^2) reference exactly at the long
        # seqs where the kernel matters most. Pad to the next 128-multiple
        # with seg = -1 instead: padded keys match nothing, padded query
        # rows output zero and are sliced back off.
        pq = (-sq) % 128 if bq is None else 0
        pk = (-sk) % 128 if bk is None else 0
        if pq or pk:
            out = flash_attention_varlen(
                jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))),
                jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))),
                jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))),
                jnp.pad(seg_q, ((0, 0), (0, pq)), constant_values=-1),
                jnp.pad(seg_k, ((0, 0), (0, pk)), constant_values=-1),
                causal=causal, scale=scale, block_q=block_q,
                block_k=block_k, use_pallas=use_pallas,
                interpret=interpret)
            return out[:, :, :sq]
        # pq == pk == 0: the seq is already aligned and the block pick
        # still failed (a block hint < 8 on an aligned seq) — padding
        # cannot fix that; fall through to the error/fallback below
    fits = bq is not None and bk is not None and d % 8 == 0
    if use_pallas is None:
        use_pallas = fits and _compiled_backend()
    elif use_pallas and not fits:
        raise ValueError(
            f"pallas flash_attention_varlen unavailable for q {q.shape}, "
            f"k {k.shape}, block_q={block_q}, block_k={block_k}: needs "
            f"Pallas importable, head_dim % 8 == 0, and a usable block "
            f"hint (>= 8; misaligned seq lengths are padded "
            f"automatically, a too-small hint on an aligned seq is not)")
    if not use_pallas:
        if interpret is not None:
            raise ValueError(
                "interpret= only applies to the Pallas path; this call "
                "resolved to the dense reference (pass use_pallas=True "
                "to force the kernel, or drop interpret=)")
        return attention_varlen_reference(q, k, v, seg_q, seg_k,
                                          causal=causal, scale=scale)
    if interpret is None:
        interpret = not _compiled_backend()
    return _varlen(q, k, v, seg_q.astype(jnp.int32), seg_k.astype(jnp.int32),
                   scale, causal, bq, bk, interpret)
