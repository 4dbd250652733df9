"""Plain reference of the block-diffusion decoder (SDAR-30B-A3B's block:
grouped-query rotary attention, routed experts) and of its training loss:
forward, loss, gradients and Adam.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest". It
imports nothing of the program and takes nothing the program made: weights
come from ``weights_sdar.py``, batches from :func:`train_batch` below, the
arithmetic of the controls (``quant``), the leaves' comparison and Adam from
``reference.py`` and ``reference_hybrid.py``, which are the benchmark's own.
As the configuration's file states under ``assumed``:

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``, a final
  RMSNorm, an untied head, eps 1e-6;
* attention: ``q = x W_q`` (32 x 128), ``k``, ``v`` (4 x 128); ``q``, ``k``
  RMS-normalised over each head's 128 with a learned weight, then rotated
  (``rotate_half`` form, theta 1e6, positions 0 .. L-1 in both halves); query
  head ``i`` reads key/value head ``i // 8``; softmax of ``q . k / sqrt(128)``
  over the keys the **dense mask built from the three predicates** allows;
* routed FFN: ``s = softmax(x W_r)`` over all 128; the 8 largest;
  ``w_i = s_i / sum of the chosen``; the experts **as a loop over the held
  ones**, each over every position with its weight (0 where the position
  did not choose it): ``y = sum_i w_i E_i(x)``, ``E(x) = (SiLU(x W_gate) *
  (x W_up)) W_down``. What the experts not held would add is left out;
* training: ``[x_t ; x0]``, logits on the noised half, ``loss = sum over
  masked positions of CE / t, over rows x L``.

Departures, for size alone: a step's gradient is worked out **a layer at a
time** (the layers' inputs kept, each layer's vector-Jacobian product its
own call of one compiled function, so four layers compile once) and a row
at a time; attention runs **in blocks of queries** under ``jax.checkpoint``,
a head at a time, so that no more than a block's scores exist at once.

``quant`` (``"int8"``, ``"fp8"``) makes a control: the operands of every
matrix product but the router's (which the configuration states float32)
are rounded first to the lower type's grid. ``fault`` plants what must read
not ``correct``: ``"no-routed"`` (the experts add nothing), ``"no-renorm"``
(``w_i = s_i``, not divided by the chosen's sum), ``"causal-mask"`` (plain
causal over the 2L positions in place of the block mask), ``"no-rope"`` (the
rotation left out), ``"router-bf16"`` (the router's product as a bfloat16
program would leave it: ``x`` and ``W_r`` rounded to bfloat16 and the logits
rounded to bfloat16 before the softmax; the control of the router's own
precision, which ``quant`` leaves alone).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference
from reference import F32, HIGHEST, _einsum, _mm
from reference_hybrid import (_jit_change_norms, _jit_leaf_norms, _jit_leaf_samples)
from weights_sdar import LAYER

RATE_BITS = 20
FAULTS = ("no-routed", "no-renorm", "causal-mask", "no-rope", "router-bf16")


# ---------------------------------------------------------------------------
# the batch: data ids and the noise, from the seed

def train_batch(seed: int, step: int, rows: int, seq: int, mask_id: int, block: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """``(tokens, noise)`` of global step ``step``, both (rows, seq) int32:
    ids uniform below ``mask_id``; a rate ``t = n / 2**20`` a block of
    ``block`` tokens, ``n`` uniform in 1049 .. 2**20 (t in [0.001, 1]); each
    token masked with probability ``t``; ``noise = 2 n + masked``."""
    rng = np.random.default_rng([int(seed), 3, int(step)])
    tokens = rng.integers(0, mask_id, size=(rows, seq), dtype=np.int32)
    n = rng.integers(math.ceil(0.001 * 2 ** RATE_BITS), 2 ** RATE_BITS + 1,
                     size=(rows, seq // block))
    n = np.repeat(n, block, axis=1)
    masked = rng.random((rows, seq)) < n / 2.0 ** RATE_BITS
    return tokens, (2 * n + masked).astype(np.int32)


def model_shape(config: Mapping) -> tuple:
    """What the functions below need of a configuration's file, hashable."""
    return tuple(sorted({
        "layers": config["num_hidden_layers"], "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"], "head_dim": config["head_dim"],
        "theta": float(config["rope_theta"]), "eps": float(config["rms_norm_eps"]),
        "top_k": config["num_experts_per_tok"],
        "experts_held": tuple(config["experts_held"]),
        "block": config["assumed"]["block_length"], "mask_id": config["assumed"]["mask_id"],
    }.items()))


# ---------------------------------------------------------------------------
# the model

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rotate(x, positions, theta):
    """``x`` (..., s, d): the pairs (x[i], x[i + d/2]) turned by the angle
    ``position * theta^(-2i/d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def visible(i, j, length: int, block: int):
    """The three predicates: may query ``i`` read key ``j`` (positions in
    ``[x_t ; x0]``, ``length`` tokens a half)?"""
    i_noised, j_noised = i < length, j < length
    bi, bj = (i % length) // block, (j % length) // block
    return ((i_noised & j_noised & (bj == bi))
            | (i_noised & ~j_noised & (bj < bi))
            | (~i_noised & ~j_noised & (bj <= bi)))


def _attention(x, p, m, quant, fault, q_block: int = 1024):
    r, s, _ = x.shape
    n, nkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    by_head = lambda a, h: jnp.moveaxis(a.reshape(r, s, h, d), 2, 1)     # (r, h, s, d)
    q = _rms_norm(by_head(_mm(x, p["wq"], quant), n), p["q_norm"], m["eps"])
    k = _rms_norm(by_head(_mm(x, p["wk"], quant), nkv), p["k_norm"], m["eps"])
    v = by_head(_mm(x, p["wv"], quant), nkv)
    if fault != "no-rope":
        positions = jnp.arange(s) % (s // 2)
        q, k = _rotate(q, positions, m["theta"]), _rotate(k, positions, m["theta"])
    q_block = math.gcd(s, q_block)
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block_of_queries(args):         # one head, one block of queries
        q, k, v, first = args           # (r, q_block, d), (r, s, d) x 2
        rows = first + jnp.arange(q_block)[:, None]
        seen = (keys <= rows) if fault == "causal-mask" else visible(
            rows, keys, s // 2, m["block"])
        scores = _einsum("rqd,rkd->rqk", q, k, quant, -1, -1) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("rqk,rkd->rqd", probs, v, quant, -1, 1)

    def head(args):
        q, k, v = args                  # (r, s, d) each
        qs = jnp.moveaxis(q.reshape(r, s // q_block, q_block, d), 1, 0)
        firsts = jnp.arange(0, s, q_block)
        out = jax.lax.map(lambda a: block_of_queries((a[0], k, v, a[1])), (qs, firsts))
        return jnp.moveaxis(out, 0, 1).reshape(r, s, d)

    group = n // nkv
    kv_of = jnp.arange(n) // group      # query head i reads key/value head i // group
    ctx = jax.lax.map(lambda a: head((a[0], k[:, a[1]], v[:, a[1]])),
                      (jnp.moveaxis(q, 1, 0), kv_of))
    return _mm(jnp.moveaxis(ctx, 0, 2).reshape(r, s, n * d), p["wo"], quant)


def _routed(x, p, m, quant, fault):
    """The held experts, one at a time, each over every position."""
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    logits = jnp.matmul(xf, p["router"], precision=HIGHEST)
    if fault == "router-bf16":
        low = lambda a: a.astype(jnp.bfloat16).astype(F32)
        logits = low(jnp.matmul(low(xf), low(p["router"]), precision=HIGHEST))
    scores = jax.nn.softmax(logits, axis=-1)
    chosen, idx = jax.lax.top_k(scores, m["top_k"])
    weight = chosen if fault == "no-renorm" else chosen / jnp.sum(chosen, -1, keepdims=True)
    first, count = m["experts_held"]

    @jax.checkpoint
    def expert(e):
        w_gate, w_up, w_down, number = e
        w = jnp.sum(jnp.where(idx == number, weight, 0.0), axis=-1)
        out = _mm(_silu(_mm(xf, w_gate, quant)) * _mm(xf, w_up, quant), w_down, quant)
        return w[:, None] * out

    y, _ = jax.lax.scan(lambda y, e: (y + expert(e), None), jnp.zeros_like(xf),
                        (p["w_gate"], p["w_up"], p["w_down"], first + jnp.arange(count)))
    if fault == "no-routed":
        y = jnp.zeros_like(y)
    return y.reshape(*lead, -1)


def layer_fn(p, x, shape, quant=None, fault=None):
    """One layer: ``p`` its leaves (any float type), ``x`` (rows, 2L, hidden)."""
    m = dict(shape)
    p = jax.tree.map(lambda a: a.astype(F32), p)
    h = x + _attention(_rms_norm(x, p["norm1"], m["eps"]), p, m, quant, fault)
    return h + _routed(_rms_norm(h, p["norm2"], m["eps"]), p, m, quant, fault)


def noised(tokens, noise, shape):
    """``([x_t ; x0], weight)`` as the configuration states them."""
    m = dict(shape)
    masked = (noise % 2) == 1
    t = (noise // 2).astype(F32) / 2.0 ** RATE_BITS
    x_t = jnp.where(masked, m["mask_id"], tokens)
    return jnp.concatenate([x_t, tokens], axis=1), jnp.where(masked, 1.0 / t, 0.0)


def head_loss_sum(head, x, tokens, weight, shape, quant=None):
    """``sum over positions of weight x CE`` on the noised half of ``x``."""
    m = dict(shape)
    x = _rms_norm(x[:, :tokens.shape[1]], head["norm"].astype(F32), m["eps"])
    logits = _einsum("rsh,vh->rsv", x, head["lm"].astype(F32), quant, -1, -1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return jnp.sum(weight * (lse - picked))


def logits_fn(params, tokens, noise, shape, quant=None, fault=None):
    """float32 logits of the noised half (rows, L, vocab): the tests'."""
    m = dict(shape)
    seq2, _ = noised(tokens, noise, shape)
    x = jnp.take(params["embed"]["tok"].astype(F32), seq2, axis=0)
    for i in range(m["layers"]):
        x = layer_fn(jax.tree.map(lambda a: a[i, 0], params["periods"][LAYER]), x,
                     shape, quant, fault)
    x = _rms_norm(x[:, :tokens.shape[1]], params["head"]["norm"].astype(F32), m["eps"])
    return _einsum("rsh,vh->rsv", x, params["head"]["lm"].astype(F32), quant, -1, -1)


# ---------------------------------------------------------------------------
# a step's gradient: a layer at a time, a block of rows at a time

@functools.lru_cache(maxsize=None)
def _jitted(shape, quant, fault):
    layer = functools.partial(layer_fn, shape=shape, quant=quant, fault=fault)

    def layer_vjp(p, x, dy):
        _, pull = jax.vjp(layer, p, x)
        dp, dx = pull(dy)
        return jax.tree.map(lambda a: a.astype(F32), dp), dx

    def head_vjp(head, x, tokens, weight, scale):
        loss, (dhead, dx) = jax.value_and_grad(
            lambda h, x: head_loss_sum(h, x, tokens, weight, shape, quant) * scale,
            argnums=(0, 1))(head, x)
        return loss, jax.tree.map(lambda a: a.astype(F32), dhead), dx

    def embed(table, seq2):
        return jnp.take(table.astype(F32), seq2, axis=0)

    def embed_vjp(table, seq2, dx):
        return jnp.zeros(table.shape, F32).at[seq2].add(dx)

    return {"layer": jax.jit(layer), "layer_vjp": jax.jit(layer_vjp),
            "head_vjp": jax.jit(head_vjp), "embed": jax.jit(embed),
            "embed_vjp": jax.jit(embed_vjp),
            "head_loss": jax.jit(lambda h, x, tok, w: head_loss_sum(h, x, tok, w, shape, quant)),
            "noised": jax.jit(functools.partial(noised, shape=shape))}


def _layer_leaves(params, i):
    return jax.tree.map(lambda a: a[i, 0], params["periods"][LAYER])


def _block_gradient(params, tokens, noise, shape, quant, fault, scale):
    """(loss, gradient tree in float32) of ``scale x sum of weight x CE`` over
    one block of rows."""
    fn, n = _jitted(shape, quant, fault), dict(shape)["layers"]
    seq2, weight = fn["noised"](tokens, noise)
    xs = [fn["embed"](params["embed"]["tok"], seq2)]
    for i in range(n):
        xs.append(fn["layer"](_layer_leaves(params, i), xs[-1]))
    loss, dhead, dx = fn["head_vjp"](params["head"], xs.pop(), tokens, weight, scale)
    by_layer = [None] * n
    for i in reversed(range(n)):
        by_layer[i], dx = fn["layer_vjp"](_layer_leaves(params, i), xs.pop(), dx)
    stacked = jax.tree.map(lambda *a: jnp.stack(a)[:, None], *by_layer)
    return loss, {"embed": {"tok": fn["embed_vjp"](params["embed"]["tok"], seq2, dx)},
                  "periods": {LAYER: stacked}, "head": dhead}


def step_gradient(params, tokens, noise, shape, rows_per_block, quant=None, fault=None,
                  rows: Optional[slice] = None):
    """(loss, float32 gradients) of ``sum over masked positions of CE / t``
    over ``rows x L`` (over ``rows`` of the batch alone: the half-batch
    fault), accumulated over blocks of rows."""
    if rows is not None:
        tokens, noise = tokens[rows], noise[rows]
    scale = jnp.asarray(1.0 / tokens.size, F32)
    loss, acc = jnp.zeros((), F32), None
    for i in range(0, tokens.shape[0], rows_per_block):
        part, g = _block_gradient(params, tokens[i:i + rows_per_block],
                                  noise[i:i + rows_per_block], shape, quant, fault, scale)
        loss = loss + part
        acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
    return loss, acc


def step_loss(params, tokens, noise, shape, rows_per_block, quant=None, fault=None):
    fn, n = _jitted(shape, quant, fault), dict(shape)["layers"]
    total = 0.0
    for i in range(0, tokens.shape[0], rows_per_block):
        tok, nz = tokens[i:i + rows_per_block], noise[i:i + rows_per_block]
        seq2, weight = fn["noised"](tok, nz)
        x = fn["embed"](params["embed"]["tok"], seq2)
        for j in range(n):
            x = fn["layer"](_layer_leaves(params, j), x)
        total += float(fn["head_loss"](params["head"], x, tok, weight))
    return total / tokens.size


def train_reference(make_params: Callable, batches, hp: Mapping, shape,
                    rows_per_block: int, quant=None, fault=None,
                    rows: Optional[slice] = None) -> Dict:
    """Follow the job's first steps as ``reference.train_reference`` does:
    gradient and Adam for steps 1 and 2, the loss alone for step 3."""
    kw = dict(lr=float(hp["lr"]), b1=float(hp["betas"][0]),
              b2=float(hp["betas"][1]), eps=float(hp["eps"]))
    losses, held = [], []       # the gradients so far, on the host while the next is computed
    p = make_params()
    for step in (1, 2):
        tok, noise = batches[step - 1]
        loss, g = step_gradient(p, tok, noise, shape, rows_per_block, quant, fault, rows)
        losses.append(float(loss))
        if step == 1:
            grad_norms = jax.device_get(_jit_leaf_norms(g))
            grad_samples = jax.device_get(_jit_leaf_samples(g))
        p = reference.adam_apply(p, (*held, g), step=step, **kw)
        if step == 1:       # 3.3 GB in float32 at the cell's size
            held.append(jax.device_get(g))
        del g
    del held
    changes = jax.device_get(_jit_change_norms(p, make_params()))
    if len(batches) > 2:
        tok, noise = batches[2]
        if rows is not None:
            tok, noise = tok[rows], noise[rows]
        losses.append(step_loss(p, tok, noise, shape, rows_per_block, quant, fault))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples, "change_norms": changes}
