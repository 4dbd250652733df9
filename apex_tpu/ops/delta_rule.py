"""Gated delta rule (linear attention with a decaying, error-correcting
state), in chunked form, with the two small ops its layer needs beside it.

Per head, with keys of unit length, a decay ``alpha_t = exp(g_t)`` in (0, 1]
and a write strength ``beta_t`` (arXiv:2412.06464)::

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t                          S in R^{d_v x d_k}, S_0 = 0

Token by token that is 8,192 dependent rank-one updates a row. The chunked
form does the same arithmetic ``chunk`` tokens at a time (the WY / UT
representation of a product of Householder-like factors): write the new value
``n_t = beta_t (v_t - alpha_t S_{t-1} k_t)`` so that ``S_t = alpha_t S_{t-1} +
n_t k_t^T``; inside a chunk that starts from state ``S`` and with ``gamma_t``
the decay accumulated since the chunk's start,

    (I + A) N = beta V - (beta gamma K) S^T,   A_ts = beta_t (k_t.k_s) gamma_t/gamma_s  (s < t)

so ``N = u - w S^T`` with ``u = (I + A)^-1 beta V`` and ``w = (I + A)^-1 beta
gamma K``: one unit-lower-triangular solve a chunk, for all chunks at once.
What is left in sequence is a scan over chunks that carries the state:
``O = (gamma Q) S^T + (Q K^T . L) N`` and ``S'^T = gamma_C S^T + (K
gamma_C/gamma)^T N``. No quotient of decays is formed but as ``exp`` of a
difference that is never positive, so strong decay underflows to nought and
nothing overflows.

The state and every product here are float32 (``_PRECISION``: the MXU's
multi-pass float32); the backward is autodiff through the chunked form, so a
layer under ``jax.checkpoint`` keeps one state a chunk while it is
differentiated and nothing between layers, and a call runs as many rows and
heads at a time as ``_BLOCK_BYTES`` of float32 intermediates hold
(``_block_plan``). There is no Pallas kernel yet: whatever implements the
core sits under the scope ``layer/linattn/core`` (``transformer/hybrid.py``),
which is where its time is read from.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.ops._pallas_util import pvary_like

F32 = jnp.float32
_PRECISION = lax.Precision.HIGHEST


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_PRECISION,
                      preferred_element_type=F32)


# What one block's float32 intermediates may take while it is differentiated:
# a tenth of a v5e's 16 GB. Heads and rows are independent, so a call is cut
# into blocks that run in sequence, each under a checkpoint of its own.
_BLOCK_BYTES = 3 << 29


def _block_plan(b: int, t: int, h: int, dk: int, dv: int, chunk: int):
    """(rows, heads) a block: the most heads of one row (a divisor of ``h``)
    whose float32 intermediates fit ``_BLOCK_BYTES``, and, where a whole row
    fits, the most rows (a divisor of ``b``).

    A head's forward keeps, a token: q, k, v, g, beta in float32 (2 d_k + d_v
    + 2), the chunk's decay, A and attention rows (3 chunk), the solve's
    right side and result (2 (d_k + d_v)), the decayed q and k (2 d_k), the
    scan's new values and outputs (2 d_v) and a state a chunk (d_k d_v /
    chunk); the backward holds a cotangent beside each. At the hybrid cell's
    (8,192 tokens, 96, 192, chunk 64) that is 132 MB a head: ten of 30 heads
    of one row."""
    per_token = 6 * dk + 5 * dv + 3 * chunk + dk * dv // chunk + 2
    fit = max(1, _BLOCK_BYTES // (2 * 4 * t * per_token))
    most = lambda n, cap: max(d for d in range(1, n + 1) if n % d == 0 and d <= cap)
    heads = most(h, fit)
    return (most(b, fit // h) if heads == h else 1), heads


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """``o`` (batch, time, heads, d_v) of the recurrence above, ``S_0 = 0``,
    in ``v``'s type.

    ``q``, ``k``: (batch, time, heads, d_k), already normalised and scaled by
    the caller; ``v``: (batch, time, heads, d_v); ``g`` (log decay, <= 0) and
    ``beta``: (batch, time, heads). ``time`` must be a multiple of ``chunk``.

    The work is done a block of rows and heads at a time (``_block_plan``),
    in sequence, each block under ``jax.checkpoint``: differentiating the
    call keeps its inputs and replays one block at a time, so the float32
    intermediates alive at once are one block's and not the whole call's."""
    b, t, h, dk = q.shape
    if t % chunk:
        raise ValueError(
            f"gated_delta_rule: time ({t}) is not a multiple of the chunk "
            f"({chunk}); pad the sequence or pick a chunk that divides it")
    rows, heads = _block_plan(b, t, h, dk, v.shape[-1], chunk)

    def blocks(x):      # (b, t, h, ...) -> (b/rows · h/heads, rows, t, heads, ...)
        x = x.reshape(b // rows, rows, t, h // heads, heads, *x.shape[3:])
        return jnp.moveaxis(x, 3, 1).reshape(-1, rows, t, heads, *x.shape[5:])

    one = jax.checkpoint(lambda xs: _chunked(*xs, chunk))
    o = lax.map(one, tuple(map(blocks, (q, k, v, g, beta))))
    o = o.reshape(b // rows, h // heads, rows, t, heads, -1)
    return jnp.moveaxis(o, 1, 3).reshape(b, t, h, -1)


def _chunked(q, k, v, g, beta, chunk: int):
    b, t, h, dk = q.shape
    dv, out_dtype = v.shape[-1], v.dtype
    n = t // chunk

    def chunks(x):      # (b, t, h, ...) -> (b, h, n, chunk, ...)
        x = x.astype(F32).reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                     # log gamma_t
    rows = jnp.arange(chunk)
    lower = rows[:, None] >= rows[None, :]
    diff = gc[..., :, None] - gc[..., None, :]
    # gamma_t / gamma_s for s <= t; the upper triangle never reaches exp
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = k * beta[..., None]
    a = jnp.where(rows[:, None] > rows[None, :],
                  _mm("...ik,...jk->...ij", kb, k) * decay, 0.0)
    rhs = jnp.concatenate([kb * jnp.exp(gc)[..., None], v * beta[..., None]],
                          axis=-1)
    wu = lax.linalg.triangular_solve(
        a + jnp.eye(chunk, dtype=F32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    attn = _mm("...ik,...jk->...ij", q, k) * decay  # diagonal included
    q_dec = q * jnp.exp(gc)[..., None]
    g_last = gc[..., -1:]
    k_dec = k * jnp.exp(g_last - gc)[..., None]
    gamma_c = jnp.exp(g_last)[..., None]            # (b, h, n, 1, 1)

    def step(s, xs):    # s: S^T, (b, h, d_k, d_v)
        q_i, w_i, u_i, attn_i, k_i, gam = xs
        new = u_i - _mm("bhck,bhkv->bhcv", w_i, s)
        o = _mm("bhck,bhkv->bhcv", q_i, s) + _mm("bhcj,bhjv->bhcv", attn_i, new)
        s = gam * s + _mm("bhck,bhcv->bhkv", k_i, new)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0)
               for x in (q_dec, w, u, attn, k_dec, gamma_c))
    # under shard_map the carry varies over the axes the inputs vary over
    s0 = pvary_like(jnp.zeros((b, h, dk, dv), F32), q)
    _, o = lax.scan(step, s0, xs)
    # (n, b, h, chunk, d_v) -> (b, t, h, d_v)
    return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, t, h, dv).astype(out_dtype)


def gated_delta_rule_reference(q, k, v, g, beta):
    """The recurrence as written, one token at a time (float32)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):    # s: (b, h, d_v, d_k)
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        err = v_t - _mm("bhvk,bhk->bhv", s, k_t)
        s = s + _mm("bhv,bhk->bhvk", b_t[..., None] * err, k_t)
        return s, _mm("bhvk,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, pvary_like(jnp.zeros((b, h, dv, dk), F32), q), xs)
    return jnp.moveaxis(o, 0, 1)


def causal_conv1d(x, w):
    """Depthwise causal convolution over time, no bias: ``y_t = sum_j w[j]
    x_{t-(W-1)+j}`` (the last tap meets the current token, as
    ``Conv1d(groups=channels, padding=W-1)`` cut to the input's length does).
    ``x``: (batch, time, channels); ``w``: (W, channels)."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + t] * w[j].astype(F32) for j in range(width))
    return y.astype(x.dtype)


def gated_rms_norm(o, gate, weight, eps: float = 1e-6):
    """``RMSNorm(o) * weight * SiLU(gate)`` over the last axis (a head's
    ``d_v``), in float32; returned in ``gate``'s type."""
    o32, g32 = o.astype(F32), gate.astype(F32)
    y = o32 * lax.rsqrt(jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + eps)
    return (y * weight.astype(F32) * jax.nn.silu(g32)).astype(gate.dtype)


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, in float32."""
    x32 = x.astype(F32)
    return x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + eps)
