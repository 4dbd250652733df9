"""Plain reference of the hybrid decoder (``transformer/hybrid.py``): forward,
loss and gradients in straightforward ``jax.numpy``, float32, matmul
precision "highest". No kernels, no chunk algebra, no batching tricks:

* the gated delta rule runs **token by token, as written**,
  ``S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T``,
  ``o_t = S_t q_t``, under ``jax.checkpoint`` over blocks of time so that the
  backward of thousands of steps fits;
* attention is a plain masked softmax, a head at a time for the same reason;
* gradients are ``jax.grad`` of the loss.

It takes the program's parameter tree (``init_hybrid_params``) and a
:class:`HybridConfig` only for its shapes. ``perfbench/reference_hybrid.py``
is the benchmark's own copy, which imports nothing of the program; a test
holds the two equal.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
LINEAR, FULL = "linear_attention", "full_attention"


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def model_shape(cfg) -> tuple:
    """What the functions below need of a ``HybridConfig``, hashable."""
    return tuple(sorted({
        "layer_types": tuple(cfg.layer_types), "heads": cfg.num_heads,
        "lin_heads": cfg.linear_heads, "eps": float(cfg.norm_eps),
        "neg_eigval": bool(cfg.allow_neg_eigval)}.items()))


def period_of(layer_types) -> tuple:
    """The shortest pattern the layers repeat: parameters are stacked over
    (periods, a kind's layers in one period)."""
    for n in range(1, len(layer_types) + 1):
        if len(layer_types) % n == 0 and layer_types == layer_types[:n] * (len(layer_types) // n):
            return layer_types[:n]


# ---------------------------------------------------------------------------
# the model

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _conv(x, w):
    """Depthwise causal convolution over time: ``w[-1]`` meets the current
    token, ``w[0]`` the one ``W - 1`` back. x (rows, seq, c), w (W, c)."""
    width, s = w.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for back in range(width):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
        y = y + shifted * w[width - 1 - back]
    return y


def _unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, block: int = 128):
    """The recurrence, one token at a time. q, k (rows, seq, heads, d_k);
    v (rows, seq, heads, d_v); g, beta (rows, seq, heads) -> o like v."""
    r, s, h, dk = q.shape
    dv = v.shape[-1]

    def token(S, xs):                   # S (rows, heads, d_v, d_k)
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None, None] * S
        err = v_t - jnp.sum(S * k_t[..., None, :], axis=-1)
        S = S + (b_t[..., None] * err)[..., :, None] * k_t[..., None, :]
        return S, jnp.sum(S * q_t[..., None, :], axis=-1)

    @jax.checkpoint
    def time_block(S, xs):
        return jax.lax.scan(token, S, xs)

    block = math.gcd(s, block)
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(s // block, block, *a.shape[:1], *a.shape[2:])
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(time_block, jnp.zeros((r, h, dv, dk), F32), xs)
    return jnp.moveaxis(o.reshape(s, r, h, dv), 0, 1)


def _linear_mixer(x, p, m):
    r, s, _ = x.shape
    n = m["lin_heads"]
    q, k, v = (_silu(_conv(_mm(x, p[w]), p[c]))
               for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    q, k, v = (a.reshape(r, s, n, -1) for a in (q, k, v))
    q = _unit(q) * q.shape[-1] ** -0.5
    k = _unit(k)
    beta = jax.nn.sigmoid(_mm(x, p["wb"])) * (2.0 if m["neg_eigval"] else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(_mm(x, p["wa"]) + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    gate = _mm(x, p["wg"]).reshape(r, s, n, -1)
    y = _rms_norm(o, p["o_norm"], m["eps"]) * _silu(gate)
    return _mm(y.reshape(r, s, -1), p["wo"])


def _full_mixer(x, p, m):
    r, s, _ = x.shape
    n = m["heads"]
    q = _rms_norm(_mm(x, p["wq"]), p["q_norm"], m["eps"])
    k = _rms_norm(_mm(x, p["wk"]), p["k_norm"], m["eps"])
    v = _mm(x, p["wv"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(qkv):                      # one head: (rows, seq, d) each
        q, k, v = qkv
        scores = _einsum("rqd,rkd->rqk", q, k) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _einsum("rqk,rkd->rqd", probs, v)

    by_head = lambda a: jnp.moveaxis(a.reshape(r, s, n, -1), 2, 0)
    ctx = jax.lax.map(head, (by_head(q), by_head(k), by_head(v)))
    return _mm(jnp.moveaxis(ctx, 0, 2).reshape(r, s, -1), p["wo"])


def _block(x, p, kind: str, m):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    mixer = _full_mixer if kind == FULL else _linear_mixer
    x = x + _rms_norm(mixer(x, p, m), p["norm1"], m["eps"])
    f = _mm(_silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"]), p["w_down"])
    return x + _rms_norm(f, p["norm2"], m["eps"])


def hidden_fn(params, tokens, shape, remat: bool = False):
    """tokens (rows, seq) -> the last layer's output, float32."""
    m = dict(shape)
    x = jnp.take(params["embed"]["tok"].astype(F32), tokens, axis=0)
    period = period_of(m["layer_types"])
    seen = dict.fromkeys(period, 0)
    for i, kind in enumerate(m["layer_types"]):
        at = i // len(period), seen[kind] % period.count(kind)
        seen[kind] += 1
        p = jax.tree.map(lambda a: a[at], params["periods"][kind])
        fn = functools.partial(_block, kind=kind, m=m)
        x = (jax.checkpoint(fn) if remat else fn)(x, p)
    return x


def logits_fn(params, tokens, shape, remat: bool = False):
    """tokens (rows, seq) -> float32 logits over the rows of the vocabulary
    held (rows, seq, vocab)."""
    x = hidden_fn(params, tokens, shape, remat)
    x = _rms_norm(x, params["head"]["norm"].astype(F32), dict(shape)["eps"])
    return _einsum("rsh,vh->rsv", x, params["head"]["lm"].astype(F32))


def loss_fn(params, tokens, targets, shape, remat: bool = True):
    """Mean cross entropy of the next token over the rows held."""
    logits = logits_fn(params, tokens, shape, remat)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss_and_grad(params, tokens, targets, shape):
    """(loss, float32 gradients) by ``jax.value_and_grad``."""
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    return jax.value_and_grad(loss_fn)(p32, tokens, targets, shape)
