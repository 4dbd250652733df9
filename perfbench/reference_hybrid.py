"""Plain reference of the hybrid decoder (gated-delta-rule linear-attention
layers among full ones): forward, loss, gradients and Adam.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest". The
delta rule runs **token by token, as written** (no chunk algebra):

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T,   o_t = S_t q_t

under ``jax.checkpoint`` over blocks of time so that the backward of 8,192
steps fits; attention is a plain masked softmax, a head at a time for the
same reason. It imports nothing of the program and takes nothing the program
made: weights come from ``weights_hybrid.py``, the arithmetic of the controls
(``quant``), the leaves' comparison and Adam from ``reference.py``, which
are the benchmark's own. The block, stated in the configuration's file under
``assumed``: ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(FFN(h))``, a
SiLU-gated FFN, QK-norm and no positions in the full layers, parameters
stored in bfloat16 with no float32 master copy, ``vocab_size`` rows of the
vocabulary held (ids and the loss are over those).

``quant`` makes a control. ``"int8"`` and ``"fp8"``: the operands of every
matrix product (the projections, the FFN, attention's two products, the head)
and ``q``, ``k``, ``v`` as the delta rule takes them are rounded first to the
lower type's grid; the recurrence itself stays float32. ``CORE_BF16``: the
other way about, for the arithmetic the configuration states for the delta
rule alone (float32 state, "highest" products): every matrix product stays
float32 and the recurrence runs on bfloat16's grid, its state after every
token's decay and write and the operands of its three products a token, the
sums in float32; the cotangents are rounded where the values are.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp

import reference
from counts_hybrid import FULL, layers_held, period_of
from reference import F32, _einsum, _mm, low_operand


CORE_BF16 = "core-bf16"


def _matmul_grid(quant):
    """The grid of the matrix products' operands under ``quant``: the
    control on the delta rule's arithmetic leaves them float32."""
    return None if quant == CORE_BF16 else quant


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def model_shape(config: Mapping) -> tuple:
    """What the functions below need of a configuration's file, hashable:
    the layers held (the first ``num_hidden_layers`` of ``layer_types``),
    head counts and sizes, the norm's epsilon."""
    return tuple(sorted({
        "layer_types": layers_held(config),
        "heads": config["num_attention_heads"],
        "lin_heads": config["linear_num_value_heads"],
        "eps": float(config["rms_norm_eps"]),
        "neg_eigval": bool(config["linear_allow_neg_eigval"]),
    }.items()))


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


def delta_rule(q, k, v, g, beta, block: int = 128, low: bool = False):
    """The recurrence, one token at a time. q, k (rows, seq, heads, d_k);
    v (rows, seq, heads, d_v); g, beta (rows, seq, heads) -> o like v.
    ``low``: on bfloat16's grid (the ``CORE_BF16`` control)."""
    r, s, h, dk = q.shape
    dv = v.shape[-1]
    grid = _bf16 if low else (lambda x: x)
    q, k, v = grid(q), grid(k), grid(v)

    def token(S, xs):                   # S (rows, heads, d_v, d_k)
        q_t, k_t, v_t, g_t, b_t = xs
        S = grid(jnp.exp(g_t)[..., None, None] * S)
        err = v_t - jnp.sum(S * k_t[..., None, :], axis=-1)
        S = grid(S + grid(b_t[..., None] * err)[..., :, None] * k_t[..., None, :])
        return S, jnp.sum(S * q_t[..., None, :], axis=-1)

    @jax.checkpoint
    def time_block(S, xs):
        return jax.lax.scan(token, S, xs)

    block = math.gcd(s, block)
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(s // block, block, *a.shape[:1], *a.shape[2:])
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(time_block, jnp.zeros((r, h, dv, dk), F32), xs)
    return jnp.moveaxis(o.reshape(s, r, h, dv), 0, 1)


def _linear_mixer(x, p, m, quant, low_core: bool = False):
    r, s, _ = x.shape
    n = m["lin_heads"]
    q, k, v = (_silu(_conv(_mm(x, p[w], quant), p[c]))
               for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    q, k, v = (a.reshape(r, s, n, -1) for a in (q, k, v))
    q = _unit(q) * q.shape[-1] ** -0.5
    k = _unit(k)
    beta = jax.nn.sigmoid(_mm(x, p["wb"], quant)) * (2.0 if m["neg_eigval"] else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(_mm(x, p["wa"], quant) + p["dt_bias"])
    if quant:
        q, k, v = (low_operand(a, -1, quant) for a in (q, k, v))
    o = delta_rule(q, k, v, g, beta, low=low_core)
    gate = _mm(x, p["wg"], quant).reshape(r, s, n, -1)
    y = _rms_norm(o, p["o_norm"], m["eps"]) * _silu(gate)
    return _mm(y.reshape(r, s, -1), p["wo"], quant)


def _full_mixer(x, p, m, quant):
    r, s, _ = x.shape
    n = m["heads"]
    q = _rms_norm(_mm(x, p["wq"], quant), p["q_norm"], m["eps"])
    k = _rms_norm(_mm(x, p["wk"], quant), p["k_norm"], m["eps"])
    v = _mm(x, p["wv"], quant)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(qkv):                      # one head: (rows, seq, d) each
        q, k, v = qkv
        scores = _einsum("rqd,rkd->rqk", q, k, quant, -1, -1) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _einsum("rqk,rkd->rqd", probs, v, quant, -1, 1)

    by_head = lambda a: jnp.moveaxis(a.reshape(r, s, n, -1), 2, 0)
    ctx = jax.lax.map(head, (by_head(q), by_head(k), by_head(v)))
    return _mm(jnp.moveaxis(ctx, 0, 2).reshape(r, s, -1), p["wo"], quant)


def _block(x, p, kind: str, m, quant):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    low_core, quant = quant == CORE_BF16, _matmul_grid(quant)
    mixed = (_full_mixer(x, p, m, quant) if kind == FULL
             else _linear_mixer(x, p, m, quant, low_core))
    x = x + _rms_norm(mixed, p["norm1"], m["eps"])
    f = _mm(_silu(_mm(x, p["w_gate"], quant)) * _mm(x, p["w_up"], quant), p["w_down"], quant)
    return x + _rms_norm(f, p["norm2"], m["eps"])


def hidden_fn(params, tokens, shape, quant=None, remat: bool = False):
    """tokens (rows, seq) -> the last layer's output, float32."""
    m = dict(shape)
    x = jnp.take(params["embed"]["tok"].astype(F32), tokens, axis=0)
    period = period_of(m["layer_types"])
    seen = dict.fromkeys(period, 0)
    for i, kind in enumerate(m["layer_types"]):
        at = i // len(period), seen[kind] % period.count(kind)
        seen[kind] += 1
        p = jax.tree.map(lambda a: a[at], params["periods"][kind])
        fn = functools.partial(_block, kind=kind, m=m, quant=quant)
        x = (jax.checkpoint(fn) if remat else fn)(x, p)
    return x


def logits_fn(params, tokens, shape, quant=None, remat: bool = False):
    """tokens (rows, seq) -> float32 logits over the rows of the vocabulary
    held (rows, seq, vocab)."""
    x = hidden_fn(params, tokens, shape, quant, remat)
    x = _rms_norm(x, params["head"]["norm"].astype(F32), dict(shape)["eps"])
    return _einsum("rsh,vh->rsv", x, params["head"]["lm"].astype(F32),
                   _matmul_grid(quant), -1, -1)


def loss_sum_fn(params, tokens, targets, shape, quant=None):
    """Sum over the block's tokens of the cross entropy (the caller divides
    by the step's token count)."""
    logits = logits_fn(params, tokens, shape, quant, remat=True)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


# ---------------------------------------------------------------------------
# leaves, as the comparison sees them: a stacked leaf counts once a layer

def _flat(tree):
    for name, a in tree["embed"].items():
        yield f"embed.{name}", a[None]
    for name, a in tree["head"].items():
        yield f"head.{name}", a[None]
    for kind, leaves in tree["periods"].items():
        for name, a in leaves.items():
            yield f"{kind}.{name}", a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)).reshape(a.shape[0], -1), axis=1))
            for k, a in _flat(tree)}


def leaf_samples(tree, per_leaf: int = 4096) -> Dict[str, jnp.ndarray]:
    """Evenly spaced elements of every leaf (at most ``per_leaf`` a layer)."""
    out = {}
    for k, a in _flat(tree):
        a2 = a.reshape(a.shape[0], -1)
        stride = max(1, a2.shape[1] // per_leaf)
        out[k] = a2[:, ::stride][:, :per_leaf].astype(F32)
    return out


def change_norms(after, before):
    return leaf_norms(jax.tree.map(lambda a, b: a.astype(F32) - b.astype(F32), after, before))


_jit_leaf_norms = jax.jit(leaf_norms)
_jit_leaf_samples = jax.jit(leaf_samples)
_jit_change_norms = jax.jit(change_norms)


# ---------------------------------------------------------------------------
# training: gradients over blocks of rows, and Adam (reference.py's)

@functools.lru_cache(maxsize=None)
def _grad_block(shape, quant):
    def f(params, acc, loss_acc, tokens, targets, inv_tokens):
        loss, g = jax.value_and_grad(
            lambda p: loss_sum_fn(p, tokens, targets, shape, quant) * inv_tokens)(params)
        g = jax.tree.map(lambda x: x.astype(F32), g)
        return jax.tree.map(jnp.add, acc, g), loss_acc + loss

    return jax.jit(f, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=None)
def _loss_block(shape, quant):
    return jax.jit(lambda p, tok, tgt: loss_sum_fn(p, tok, tgt, shape, quant))


def step_gradient(params, tokens, targets, shape, rows_per_block, quant=None,
                  rows: Optional[slice] = None):
    """(loss, float32 gradients) of the mean cross entropy over ``tokens``
    (or over ``rows`` of them alone: the half-batch fault), accumulated over
    blocks of rows so that it fits."""
    if rows is not None:
        tokens, targets = tokens[rows], targets[rows]
    inv = jnp.asarray(1.0 / tokens.size, F32)
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    loss = jnp.zeros((), F32)
    fn = _grad_block(shape, quant)
    for i in range(0, tokens.shape[0], rows_per_block):
        acc, loss = fn(params, acc, loss, tokens[i:i + rows_per_block],
                       targets[i:i + rows_per_block], inv)
    return loss, acc


def step_loss(params, tokens, targets, shape, rows_per_block, quant=None):
    fn = _loss_block(shape, quant)
    total = 0.0
    for i in range(0, tokens.shape[0], rows_per_block):
        total += float(fn(params, tokens[i:i + rows_per_block], targets[i:i + rows_per_block]))
    return total / tokens.size


def train_reference(make_params: Callable, batches, hp: Mapping, shape,
                    rows_per_block: int, quant=None, rows: Optional[slice] = None) -> Dict:
    """Follow the job's first steps as ``reference.train_reference`` does:
    gradient and Adam for steps 1 and 2, the loss alone for step 3."""
    kw = dict(lr=float(hp["lr"]), b1=float(hp["betas"][0]),
              b2=float(hp["betas"][1]), eps=float(hp["eps"]))
    losses, held = [], []       # the gradients so far, on the host while the next is computed
    p = make_params()
    for step in (1, 2):
        tok, tgt = batches[step - 1]
        loss, g = step_gradient(p, tok, tgt, shape, rows_per_block, quant, rows)
        losses.append(float(loss))
        if step == 1:
            grad_norms = jax.device_get(_jit_leaf_norms(g))
            grad_samples = jax.device_get(_jit_leaf_samples(g))
        p = reference.adam_apply(p, (*held, g), step=step, **kw)
        if step == 1:
            # a float32 gradient is 3.7 GB at the cell's size: beside the second one, the
            # parameters and a row's activations it would not fit the chip
            held.append(jax.device_get(g))
        del g
    del held
    changes = jax.device_get(_jit_change_norms(p, make_params()))
    if len(batches) > 2:
        tok, tgt = batches[2]
        if rows is not None:
            tok, tgt = tok[rows], tgt[rows]
        losses.append(step_loss(p, tok, tgt, shape, rows_per_block, quant))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples, "change_norms": changes}
