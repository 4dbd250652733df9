"""Fused LM-head + softmax cross-entropy — never materializes the logits.

Reference capability: ``apex/contrib/csrc/xentropy`` (fused CE that saves
lse instead of softmax) and the Megatron loss path
``apex/transformer/tensor_parallel/cross_entropy.py`` (vocab-parallel CE over
sharded logits). Both still *receive* a materialized (tokens, vocab) logits
tensor from the LM head matmul. At GPT-2 scale that tensor is the single
largest HBM consumer in the step: (32·1024, 50304) bf16 ≈ 3.3 GB written by
the head matmul, re-read by the CE forward, and re-written as dlogits in
backward — ~10 GB of HBM traffic for ~10% of the model's FLOPs.

TPU re-design: fuse the head matmul INTO the loss, flash-attention style.
A Pallas kernel streams (block_v, hidden) tiles of the projection matrix
through the MXU against (block_n, hidden) tiles of the hidden states,
keeping a running row-max / row-sum (online logsumexp) and the target-column
logit in VMEM scratch. The logits tile lives only in VMEM; HBM sees the
hidden states and the weights, each read O(nN) times. Backward recomputes
the logits tile-wise from the saved (x, w, lse) — two accumulation kernels:

* dX: grid (rows, vocab-blocks), ``dx += ((p - onehot)·g) @ W_blk``
* dW: grid (vocab-blocks, rows), ``dw += ((p - onehot)·g)ᵀ @ X_blk``

where ``p = exp(x·wᵀ − lse)`` is already normalized (the flash backward
identity). Under tensor parallelism the vocab dim is sharded: the kernel
works on the local shard and the wrapper merges per-rank (lse, target-logit)
with a pmax/psum logsumexp merge — the same three collectives as the
reference's vocab-parallel CE, on O(tokens) vectors instead of O(logits).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_util import sds as _sds
from apex_tpu.ops._pallas_util import compiled_backend as _compiled_backend

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Pure-JAX reference (ground truth for tests; fallback for odd shapes).

def lm_head_loss_reference(x2, w, targets, axis_name: Optional[str] = None):
    """Per-position CE of ``logits = x2 @ wᵀ`` vs global target ids, fp32.

    ``x2``: (N, h) hidden states; ``w``: (V_local, h) vocab-sharded
    projection; ``targets``: (N,) global ids. Materializes the logits —
    use only for small shapes / verification.
    """
    logits = jnp.einsum("nh,vh->nv", x2.astype(jnp.float32),
                        w.astype(jnp.float32))
    v_local = w.shape[0]
    if axis_name is None:
        t_local = targets
        lse = jax.nn.logsumexp(logits, axis=-1)
        pred = jnp.take_along_axis(logits, t_local[:, None], axis=1)[:, 0]
        return lse - pred
    rank = lax.axis_index(axis_name)
    t_local = targets - rank * v_local
    in_range = (t_local >= 0) & (t_local < v_local)
    picked = jnp.take_along_axis(
        logits, jnp.where(in_range, t_local, 0)[:, None], axis=1)[:, 0]
    pred = lax.psum(jnp.where(in_range, picked, 0.0), axis_name)
    lse_l = jax.nn.logsumexp(logits, axis=-1)
    m = lax.pmax(lse_l, axis_name)
    lse = m + jnp.log(lax.psum(jnp.exp(lse_l - m), axis_name))
    return lse - pred


# ---------------------------------------------------------------------------
# Pallas kernels. Layouts: x (N, h), w (V, h), t/g/lse as (N, 1) columns
# (last-dim-1 blocks avoid lane<->sublane transposes, like the attention
# kernel's lse). The vocab grid dim is innermost/arbitrary; a ragged final
# vocab block is masked with a column iota (V need not divide block_v).


# Scoped-VMEM ceiling handed to Mosaic for the three kernels. The compiler
# reports the dx kernel's tile set at 14.99 MiB for (block_n 1024, block_v
# 512, hidden 768) and 17.99 MiB once the vocab shard is ragged (tp=2:
# 25152 % 512 != 0 adds the masking temporaries) — over XLA's 16 MiB default
# scoped limit, far inside the 128 MiB a v5e core has.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _col_ids(v_i, block_n, block_v):
    return v_i * block_v + lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)


def _fwd_kernel(t_ref, x_ref, w_ref, lse_ref, pred_ref, m_scr, l_scr, p_scr,
                *, block_n, block_v, nv, v_total):
    v_i = pl.program_id(1)

    @pl.when(v_i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        p_scr[:] = jnp.zeros_like(p_scr)

    # model-dtype inputs straight into the MXU (bf16 x bf16 -> fp32 accum);
    # an fp32 upcast first would land on the much slower fp32 matmul path
    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    col = _col_ids(v_i, block_n, block_v)
    if v_total % block_v:
        s = jnp.where(col >= v_total, NEG_INF, s)
    t = t_ref[...]  # (block_n, 1) int32, local ids (may be out of range)
    hit = col == t
    if v_total % block_v:
        # another vocab shard's target can carry a local id that lands on
        # one of the ragged last block's PADDED columns; its score there is
        # the NEG_INF mask, not a logit — it must not be picked
        hit = hit & (col < v_total)
    p_scr[:, :1] += jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_scr[:, :1] = (l_scr[:, :1] * jnp.exp(m_prev - m_new)
                    + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True))
    m_scr[:, :1] = m_new

    @pl.when(v_i == nv - 1)
    def _finish():
        lse_ref[...] = m_scr[:, :1] + jnp.log(l_scr[:, :1])
        pred_ref[...] = p_scr[:, :1]


def _dx_kernel(t_ref, g_ref, lse_ref, x_ref, w_ref, dx_ref, dx_scr,
               *, block_n, block_v, nv, v_total):
    v_i = pl.program_id(1)

    @pl.when(v_i == 0)
    def _init():
        dx_scr[:] = jnp.zeros_like(dx_scr)

    x = x_ref[...]
    w = w_ref[...]
    col = _col_ids(v_i, block_n, block_v)
    if v_total % block_v:
        # zero padded w rows: dl is 0 there, but 0 x (OOB-pad garbage) = NaN
        row = v_i * block_v + lax.broadcasted_iota(jnp.int32, w.shape, 0)
        w = jnp.where(row < v_total, w, jnp.zeros_like(w))
    s = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if v_total % block_v:
        s = jnp.where(col >= v_total, NEG_INF, s)
    p = jnp.exp(s - lse_ref[...])  # masked cols -> exp(NEG_INF - lse) = 0
    hit = (col == t_ref[...]).astype(jnp.float32)
    dl = (p - hit) * g_ref[...]
    dx_scr[:] += jax.lax.dot(dl.astype(w.dtype), w,
                             preferred_element_type=jnp.float32)

    @pl.when(v_i == nv - 1)
    def _finish():
        dx_ref[...] = dx_scr[:].astype(dx_ref.dtype)


def _dw_kernel(t_ref, g_ref, lse_ref, x_ref, w_ref, dw_ref, dw_scr,
               *, block_n, block_v, nn, v_total):
    v_i = pl.program_id(0)
    n_i = pl.program_id(1)

    @pl.when(n_i == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    col = _col_ids(v_i, block_n, block_v)
    if v_total % block_v:
        s = jnp.where(col >= v_total, NEG_INF, s)
    p = jnp.exp(s - lse_ref[...])
    hit = (col == t_ref[...]).astype(jnp.float32)
    dl = (p - hit) * g_ref[...]
    dw_scr[:] += jax.lax.dot_general(dl.astype(x.dtype), x,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(n_i == nn - 1)
    def _finish():
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)


def _grids(n, v, block_n, block_v):
    return n // block_n, -(-v // block_v)  # nN exact, nV ceil (ragged ok)


def _run_fwd(x2, w, t_local, block_n, block_v, interpret):
    n, h = x2.shape
    v = w.shape[0]
    nn, nv = _grids(n, v, block_n, block_v)
    kernel = functools.partial(_fwd_kernel, block_n=block_n, block_v=block_v,
                               nv=nv, v_total=v)
    lse, pred = pl.pallas_call(
        kernel,
        name="lm_head_fwd",
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, h), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, h), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            _sds((n, 1), jnp.float32, x2, w, t_local),
            _sds((n, 1), jnp.float32, x2, w, t_local),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 128), jnp.float32),
            pltpu.VMEM((block_n, 128), jnp.float32),
            pltpu.VMEM((block_n, 128), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(t_local[:, None], x2, w)
    return lse[:, 0], pred[:, 0]


def _run_bwd(x2, w, t_local, lse, g, block_n, block_v, interpret):
    n, h = x2.shape
    v = w.shape[0]
    nn, nv = _grids(n, v, block_n, block_v)
    t2, g2, lse2 = t_local[:, None], g[:, None], lse[:, None]

    # dw streams X once per vocab block — the opposite trade from dx, which
    # streams W once per row block. Tall vocab blocks and short row blocks
    # minimize dw's X re-reads while the (block_v, h) fp32 accumulator and
    # the (block_n, block_v) score tile stay inside VMEM.
    bn_dw = 512 if block_n > 512 and n % 512 == 0 else block_n
    # only widen the vocab block while the (bv_dw, h) fp32 accumulator stays
    # within a conservative VMEM budget (cf. layer_norm's _VMEM_BUDGET_BYTES);
    # strictly: at hidden 2048 the accumulator is the whole 8 MiB and the
    # kernel's tile set overflows the 32 MiB scoped limit
    bv_dw = block_v
    if block_v < 1024 <= v and 1024 * h * 4 < 8 * 1024 * 1024:
        bv_dw = 1024  # never wider than the vocab shard (caller clamps ≤ v)
    nn_dw, nv_dw = _grids(n, v, bn_dw, bv_dw)

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, block_n=block_n, block_v=block_v,
                          nv=nv, v_total=v),
        name="lm_head_bwd_dx",
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, h), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, h), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, h), lambda i, j: (i, 0)),
        out_shape=_sds((n, h), x2.dtype, x2, w, t_local, g),
        scratch_shapes=[pltpu.VMEM((block_n, h), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(t2, g2, lse2, x2, w)

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, block_n=bn_dw, block_v=bv_dw,
                          nn=nn_dw, v_total=v),
        name="lm_head_bwd_dw",
        grid=(nv_dw, nn_dw),
        in_specs=[
            pl.BlockSpec((bn_dw, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bn_dw, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bn_dw, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bn_dw, h), lambda j, i: (i, 0)),
            pl.BlockSpec((bv_dw, h), lambda j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bv_dw, h), lambda j, i: (j, 0)),
        out_shape=_sds((v, h), w.dtype, x2, w, t_local, g),
        scratch_shapes=[pltpu.VMEM((bv_dw, h), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(t2, g2, lse2, x2, w)
    return dx, dw


# ---------------------------------------------------------------------------
# Dense local impl — same (lse, pred)/(dx, dw) contract as the kernels.
# Exists so the custom_vjp + TP collectives can be exercised under the
# virtual CPU mesh, where pallas interpret mode cannot run inside shard_map
# (its re-evaluated kernel jaxpr mixes mesh-invariant iotas/scratch with
# rank-varying operands, which the VMA checker rejects).

def _dense_fwd(x2, w, t_local):
    logits = jnp.einsum("nh,vh->nv", x2.astype(jnp.float32),
                        w.astype(jnp.float32))
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    v = w.shape[0]
    in_range = (t_local >= 0) & (t_local < v)
    picked = jnp.take_along_axis(
        logits, jnp.where(in_range, t_local, 0)[:, None], axis=1)[:, 0]
    pred = jnp.where(in_range, picked, 0.0)
    return lse, pred


def _dense_bwd(x2, w, t_local, lse, g):
    logits = jnp.einsum("nh,vh->nv", x2.astype(jnp.float32),
                        w.astype(jnp.float32))
    p = jnp.exp(logits - lse[:, None])
    v = w.shape[0]
    iota = lax.broadcasted_iota(jnp.int32, p.shape, 1)
    hit = (iota == t_local[:, None]).astype(jnp.float32)
    dl = (p - hit) * g[:, None]
    dx = (dl @ w.astype(jnp.float32)).astype(x2.dtype)
    dw = jnp.einsum("nv,nh->vh", dl, x2.astype(jnp.float32)).astype(w.dtype)
    return dx, dw


# ---------------------------------------------------------------------------
# custom_vjp over the local shard + TP merge collectives

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _lm_head_loss(x2, w, targets, axis_name, block_n, block_v, impl):
    loss, _ = _lm_fwd(x2, w, targets, axis_name, block_n, block_v, impl)
    return loss


def _localize(targets, v_local, axis_name):
    if axis_name is None:
        return targets.astype(jnp.int32)
    return (targets - lax.axis_index(axis_name) * v_local).astype(jnp.int32)


def _lm_fwd(x2, w, targets, axis_name, block_n, block_v, impl):
    t_local = _localize(targets, w.shape[0], axis_name)
    if impl == "dense":
        lse, pred = _dense_fwd(x2, w, t_local)
    else:
        lse, pred = _run_fwd(x2, w, t_local, block_n, block_v,
                             impl == "pallas_interpret")
    if axis_name is not None:
        # logsumexp merge across vocab shards + sum of the (unique) target
        # logit — the reference's MAX/SUM/SUM collective triple on O(N) data.
        m = lax.pmax(lse, axis_name)
        lse = m + jnp.log(lax.psum(jnp.exp(lse - m), axis_name))
        pred = lax.psum(pred, axis_name)
    loss = lse - pred
    return loss, (x2, w, t_local, lse)


def _lm_bwd(axis_name, block_n, block_v, impl, res, g):
    x2, w, t_local, lse = res
    g = g.astype(jnp.float32)
    if impl == "dense":
        dx, dw = _dense_bwd(x2, w, t_local, lse, g)
    else:
        dx, dw = _run_bwd(x2, w, t_local, lse, g, block_n, block_v,
                          impl == "pallas_interpret")
    # dx is this rank's partial (local vocab shard); the caller's
    # copy_to_tensor_model_parallel_region transpose psums it — same
    # contract as differentiating through a vocab-sharded matmul.
    return dx, dw, None


_lm_head_loss.defvjp(_lm_fwd, _lm_bwd)


# ---------------------------------------------------------------------------
# Public API

DEFAULT_BLOCK_N = 1024
DEFAULT_BLOCK_V = 512
_MIN_BLOCK_N = 128
# What the dx kernel's tile set may take of ``_VMEM_LIMIT_BYTES``, by the
# blocks it keeps: x and dx double-buffered in the model's type and the fp32
# accumulator (12 bytes an element of a (block_n, h) tile), W's tile double
# buffered (4 bytes). 1024 rows hold up to a hidden of about 1,600; at 3,840
# the widest row block is 256.
_TILE_BUDGET_BYTES = 24 * 1024 * 1024


def _widest_block_n(h: int, block_n: int, block_v: int) -> int:
    """The widest row block <= ``block_n`` (halving, not under the 128-row
    floor) whose dx tile set fits the budget at hidden ``h``."""
    while (block_n > _MIN_BLOCK_N
           and 12 * block_n * h + 4 * block_v * h > _TILE_BUDGET_BYTES):
        block_n //= 2
    return block_n


def _resolve_block_n(n: int, block_n: int) -> Optional[int]:
    """Largest block ≤ ``block_n`` that divides ``n`` (halving steps down to
    the 128-row floor, sublane-aligned); None when no grid covers ``n``.
    ``pallas_fits`` and ``lm_head_loss`` both use this, so the gate and the
    op cannot disagree."""
    if n <= 0 or n % 8:
        return None
    b = min(block_n, n)
    while b >= _MIN_BLOCK_N:
        if n % b == 0 and b % 8 == 0:
            return b
        b //= 2
    return n if n < _MIN_BLOCK_N else None


def pallas_fits(n: int, h: int, block_n: int = DEFAULT_BLOCK_N) -> bool:
    """True when the kernel grid covers (n, h) exactly — callers with an
    unfused alternative (e.g. logits+CE) should check this before choosing
    the fused path, because the shape fallback below is a dense fp32
    reference, not a tuned kernel."""
    return _resolve_block_n(n, block_n) is not None and h % 128 == 0


def lm_head_loss(
    x,
    w,
    targets,
    axis_name: Optional[str] = None,
    block_n: int = DEFAULT_BLOCK_N,
    block_v: int = DEFAULT_BLOCK_V,
    use_pallas: Optional[bool] = None,
    weights=None,
):
    """Per-position CE of the projection ``x @ wᵀ`` without materializing it.

    ``x``: (..., h) hidden states; ``w``: (V_local, h); ``targets``: (...)
    int global ids. Returns fp32 loss shaped like ``targets``. Differentiable
    in ``x`` and ``w``; under TP (``axis_name``) ``dx`` is the local partial
    (reduced by the enclosing copy-to-region transpose, Megatron-style).

    ``weights``: (...) a weight a position (0 where a position is not
    scored); the result is ``weights * CE``. The forward is the one pass it
    always was, and the backward kernels take the weight through the
    cotangent a row they already scale ``p - onehot`` by: no second pass
    over the logits, and a position of weight 0 adds nothing to ``dx`` or
    ``dw``.
    """
    h = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, h)
    t1 = targets.reshape(-1)
    n = x2.shape[0]
    bn = _resolve_block_n(n, _widest_block_n(h, block_n, block_v))
    fits = bn is not None and h % 128 == 0
    if use_pallas is None:
        use_pallas = fits and _compiled_backend()
    elif use_pallas and not fits:
        raise ValueError(
            f"pallas lm_head_loss needs a row block dividing rows ({n}) "
            f"and hidden ({h}) divisible by 128")
    if bn is None:
        bn = n  # dense impl ignores the block size
    if use_pallas:
        impl = ("pallas" if _compiled_backend()
                else "pallas_interpret")
    else:
        impl = "dense"
    loss = _lm_head_loss(x2, w, t1, axis_name, bn, min(block_v, w.shape[0]),
                         impl)
    if weights is not None:
        loss = loss * weights.reshape(-1).astype(jnp.float32)
    return loss.reshape(lead)
