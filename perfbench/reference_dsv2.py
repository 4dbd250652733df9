"""Plain reference of DeepSeek-V2's decoder (DeepSeek-V2-Lite's block: latent
attention with YaRN positions, a leading dense layer, routed experts beside a
shared one) and of its training loss: forward, loss, gradients and Adam.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (every product also names the
precision itself). It imports nothing of the program and takes nothing the
program made: weights come from ``weights_dsv2.py``, batches from
``traffic.train_batch``, the arithmetic of the controls (``quant``), the
leaves' comparison and Adam from ``reference.py`` and
``reference_hybrid.py``, which are the benchmark's own. As the
configuration's file states (published keys, and ``assumed``):

* block: ``a = x + MLA(RMSNorm(x))``, ``y = a + FFN(RMSNorm(a))``, a final
  RMSNorm, an untied head, eps 1e-6; layer 0's FFN dense
  (``(SiLU(x W_gate) * x W_up) W_down``), the others routed + shared;
* latent attention: ``q = x W_q`` (16 x 192), a head's ``q = [q_nope (128) ;
  q_pe (64)]``; ``[c (512) ; k_pe (64)] = x W_kva``; ``c = RMSNorm(c)``; a
  head's ``[k_nope (128) ; v (128)] = c W_kvb``; ``q_pe`` and the one
  ``k_pe`` rotated: **pairs of neighbours** ``(x[2i], x[2i+1])`` by the
  angle ``position x inv_freq_i``, YaRN's ``inv_freq`` written out from
  ``rope_scaling`` in float64; ``o = softmax(q . [k_nope ; k_pe] x scale)
  v`` under the causal mask, ``scale = 192^-1/2 m^2``;
* expert FFN: ``s = softmax(x W_r)`` over all 64; the 6 largest; ``w_i = s_i
  x routed_scaling_factor``, **not renormalised**; the held experts **as a
  loop**, each over every position with its weight (0 where the position
  did not choose it); plus ``Shared(x)``, one SiLU-gated FFN of width 2,816.
  What the experts not held would add is left out;
* balance loss: a row and an expert layer, ``f_e = (times e is among a
  position's 6) x 64 / (6 L)``, ``P_e = mean of s_e``, ``alpha sum_e f_e
  P_e``; mean over rows, summed over expert layers, added to the mean cross
  entropy of the next token.

Departures, for size alone: a step's gradient is worked out **a layer at a
time** (the layers' inputs kept, each layer's vector-Jacobian product its own
call of a compiled function) and a block of rows at a time; attention runs
**in blocks of query rows** under ``jax.checkpoint``, a head at a time, so
that one row of 16,384 fits beside 864 M float32 weights.

``quant`` (``"int8"``, ``"fp8"``) makes a control: the operands of every
matrix product but the router's are rounded first to the lower type's grid.
``fault`` plants what must read not ``correct`` (:data:`FAULTS`): the
router's weights renormalised, the shared expert left out, no routed
experts, plain frequencies in place of YaRN's, ``scale`` without ``m^2``,
``kv_a_layernorm`` left out, the balance loss left out. ``cols`` keeps a
slice of every row's positions: half of the batch, where the batch is one
row.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

import reference
from reference import F32, HIGHEST, _einsum, _mm
from reference_hybrid import (_jit_change_norms, _jit_leaf_norms, _jit_leaf_samples, _rms_norm,
                              _silu)
from weights_dsv2 import DENSE, EXPERTS

FAULTS = ("renorm", "no-shared", "no-routed", "no-yarn", "no-mscale", "no-kv-norm", "no-aux")


def model_shape(config: Mapping) -> tuple:
    """What the functions below need of a configuration's file, hashable."""
    scaling = config["rope_scaling"]
    return tuple(sorted({
        "layers": config["num_hidden_layers"], "dense_layers": config["first_k_dense_replace"],
        "heads": config["num_attention_heads"], "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "v_dim": config["v_head_dim"],
        "rank": config["kv_lora_rank"], "theta": float(config["rope_theta"]),
        "scaling": None if scaling is None else tuple(sorted(scaling.items())),
        "eps": float(config["rms_norm_eps"]), "top_k": config["num_experts_per_tok"],
        "experts_held": tuple(config["experts_held"]),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "alpha": float(config["assumed"]["aux_loss_alpha"]),
    }.items()))


# ---------------------------------------------------------------------------
# positions

def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, scaling: Optional[Mapping]) -> np.ndarray:
    """The ``d / 2`` frequencies (float64): ``f_i = theta^(-2i/d)``; with YaRN
    ``f_i (1 - ramp_i) + f_i / factor x ramp_i``, the ramp from pair ``low``
    to pair ``high``."""
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    if scaling is None:
        return f
    original = scaling["original_max_position_embeddings"]
    pair_of = lambda turns: d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(pair_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_of(scaling["beta_slow"])), d - 1)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - ramp) + f / scaling["factor"] * ramp


def softmax_scale(m: Mapping, fault=None) -> float:
    scale = (m["nope"] + m["rope"]) ** -0.5
    if m["scaling"] is None or fault == "no-mscale":
        return scale
    s = dict(m["scaling"])
    return scale * _mscale(s["factor"], s["mscale_all_dim"]) ** 2


def _rotate(x, m, fault):
    """``x`` (..., s, d): neighbours (x[2i], x[2i+1]) turned by the angle
    ``position x inv_freq_i``, times YaRN's ratio of mscales."""
    s, d = x.shape[-2], x.shape[-1]
    scaling = None if m["scaling"] is None else dict(m["scaling"])
    inv = yarn_inv_freq(d, m["theta"], None if fault == "no-yarn" else scaling)
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    ratio = 1.0
    if scaling is not None:
        ratio = (_mscale(scaling["factor"], scaling["mscale"])
                 / _mscale(scaling["factor"], scaling["mscale_all_dim"]))
    cos, sin = jnp.cos(ang) * ratio, jnp.sin(ang) * ratio
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


# ---------------------------------------------------------------------------
# the model

def _gated(x, w_gate, w_up, w_down, quant):
    return _mm(_silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant), w_down, quant)


def _attention(x, p, m, quant, fault, q_block: int = 1024):
    r, s, _ = x.shape
    n, nope, rope, dv, rank = m["heads"], m["nope"], m["rope"], m["v_dim"], m["rank"]
    by_head = lambda a, d: jnp.moveaxis(a.reshape(r, s, n, d), 2, 1)     # (r, n, s, d)
    q = by_head(_mm(x, p["wq"], quant), nope + rope)
    kva = _mm(x, p["wkv_a"], quant)
    c, k_pe = kva[..., :rank], kva[..., rank:]
    if fault != "no-kv-norm":
        c = _rms_norm(c, p["kv_norm"], m["eps"])
    kv = by_head(_mm(c, p["wkv_b"], quant), nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], m, fault)], axis=-1)
    k_pe = _rotate(k_pe, m, fault)                                        # (r, s, rope): one for all heads
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None], (r, n, s, rope))], axis=-1)
    scale = softmax_scale(m, fault)
    q_block = math.gcd(s, q_block)
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block_of_queries(args):         # one head, one block of queries
        q, k, v, first = args           # (r, q_block, 192), (r, s, 192), (r, s, 128)
        rows = first + jnp.arange(q_block)[:, None]
        scores = _einsum("rqd,rkd->rqk", q, k, quant, -1, -1) * scale
        probs = jax.nn.softmax(jnp.where(keys <= rows, scores, -jnp.inf), axis=-1)
        return _einsum("rqk,rkd->rqd", probs, v, quant, -1, 1)

    def head(args):
        q, k, v = args
        qs = jnp.moveaxis(q.reshape(r, s // q_block, q_block, nope + rope), 1, 0)
        firsts = jnp.arange(0, s, q_block)
        out = jax.lax.map(lambda a: block_of_queries((a[0], k, v, a[1])), (qs, firsts))
        return jnp.moveaxis(out, 0, 1).reshape(r, s, dv)

    ctx = jax.lax.map(head, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return _mm(jnp.moveaxis(ctx, 0, 2).reshape(r, s, n * dv), p["wo"], quant)


def _routed(x, p, m, quant, fault):
    """``(the held experts' sum, one at a time and each over every position;
    alpha x sum over rows of sum_e f_e P_e)``."""
    r, s, h = x.shape
    xf = x.reshape(-1, h)
    scores = jax.nn.softmax(jnp.matmul(xf, p["router"], precision=HIGHEST), axis=-1)
    chosen, idx = jax.lax.top_k(scores, m["top_k"])
    if m["norm_topk_prob"] or fault == "renorm":
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    weight = chosen * m["routed_scaling_factor"]
    first, count = m["experts_held"]

    @jax.checkpoint
    def expert(e):
        w_gate, w_up, w_down, number = e
        w = jnp.sum(jnp.where(idx == number, weight, 0.0), axis=-1)
        return w[:, None] * _gated(xf, w_gate, w_up, w_down, quant)

    y, _ = jax.lax.scan(lambda y, e: (y + expert(e), None), jnp.zeros_like(xf),
                        (p["w_gate"], p["w_up"], p["w_down"], first + jnp.arange(count)))
    if fault == "no-routed":
        y = jnp.zeros_like(y)
    e = scores.shape[-1]
    times = jnp.sum(jax.nn.one_hot(idx.reshape(r, s * m["top_k"]), e, dtype=F32), axis=1)
    f = jax.lax.stop_gradient(times) * e / (m["top_k"] * s)
    aux = m["alpha"] * jnp.sum(f * jnp.mean(scores.reshape(r, s, e), axis=1))
    if fault == "no-aux":
        aux = 0.0 * aux
    return y.reshape(r, s, h), aux


def layer_fn(p, x, shape, kind, quant=None, fault=None):
    """One layer: ``p`` its leaves (any float type), ``x`` (rows, seq,
    hidden). Returns ``(y, aux)``, ``aux`` the rows' balance losses summed
    (nought for a dense layer)."""
    m = dict(shape)
    p = jax.tree.map(lambda a: a.astype(F32), p)
    a = x + _attention(_rms_norm(x, p["norm1"], m["eps"]), p, m, quant, fault)
    f = _rms_norm(a, p["norm2"], m["eps"])
    if kind == DENSE:
        return a + _gated(f, p["w_gate"], p["w_up"], p["w_down"], quant), jnp.zeros((), F32)
    y, aux = _routed(f, p, m, quant, fault)
    if fault != "no-shared":
        y = y + _gated(f, p["shared_gate"], p["shared_up"], p["shared_down"], quant)
    return a + y, aux


def head_loss_sum(head, x, targets, shape, quant=None):
    """The cross entropies of the next token, summed."""
    m = dict(shape)
    x = _rms_norm(x, head["norm"].astype(F32), m["eps"])
    logits = _einsum("rsh,vh->rsv", x, head["lm"].astype(F32), quant, -1, -1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def layers_of(params, shape):
    """``[(kind, that layer's leaves)]`` in the model's order."""
    m = dict(shape)
    out = [(DENSE, jax.tree.map(lambda a: a[i, 0], params["periods"][DENSE]))
           for i in range(m["dense_layers"])]
    return out + [(EXPERTS, jax.tree.map(lambda a: a[i, 0], params["periods"][EXPERTS]))
                  for i in range(m["layers"] - m["dense_layers"])]


def loss_fn(params, tokens, targets, shape, quant=None, fault=None):
    """The whole loss in one differentiable piece (the tests' sizes)."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["tok"].astype(F32), tokens, axis=0)
        aux = 0.0
        for kind, lp in layers_of(params, shape):
            x, a = layer_fn(lp, x, shape, kind, quant, fault)
            aux = aux + a
        return (head_loss_sum(params["head"], x, targets, shape, quant) / tokens.size
                + aux / tokens.shape[0])


# ---------------------------------------------------------------------------
# a step's gradient: a layer at a time, a block of rows at a time

@functools.lru_cache(maxsize=None)
def _jitted(shape, quant, fault):
    def layer(kind):
        return functools.partial(layer_fn, shape=shape, kind=kind, quant=quant, fault=fault)

    def layer_vjp(kind):
        def f(p, x, dy, daux):
            _, pull = jax.vjp(layer(kind), p, x)
            dp, dx = pull((dy, daux))
            return jax.tree.map(lambda a: a.astype(F32), dp), dx
        return f

    def head_vjp(head, x, targets, scale):
        loss, (dhead, dx) = jax.value_and_grad(
            lambda h, x: head_loss_sum(h, x, targets, shape, quant) * scale,
            argnums=(0, 1))(head, x)
        return loss, jax.tree.map(lambda a: a.astype(F32), dhead), dx

    return {"layer": {k: jax.jit(layer(k)) for k in (DENSE, EXPERTS)},
            "layer_vjp": {k: jax.jit(layer_vjp(k)) for k in (DENSE, EXPERTS)},
            "head_vjp": jax.jit(head_vjp),
            "embed": jax.jit(lambda table, tok: jnp.take(table.astype(F32), tok, axis=0)),
            "embed_vjp": jax.jit(lambda table, tok, dx: jnp.zeros(table.shape, F32).at[tok].add(dx)),
            "head_loss": jax.jit(lambda h, x, tgt: head_loss_sum(h, x, tgt, shape, quant))}


def _stack(by_layer):
    return jax.tree.map(lambda *a: jnp.stack(a)[:, None], *by_layer)


def _block_gradient(params, tokens, targets, shape, quant, fault, scale, aux_scale):
    """(loss, gradient tree in float32) of ``scale x sum of CE + aux_scale x
    the layers' balance sums`` over one block of rows."""
    fn, layers = _jitted(shape, quant, fault), layers_of(params, shape)
    xs, loss = [fn["embed"](params["embed"]["tok"], tokens)], 0.0
    for kind, lp in layers:
        y, aux = fn["layer"][kind](lp, xs[-1])
        xs.append(y)
        loss = loss + aux_scale * aux
    ce, dhead, dx = fn["head_vjp"](params["head"], xs.pop(), targets, scale)
    grads = {DENSE: [], EXPERTS: []}
    for kind, lp in reversed(layers):
        g, dx = fn["layer_vjp"][kind](lp, xs.pop(), dx, aux_scale)
        grads[kind].insert(0, g)
    return loss + ce, {"embed": {"tok": fn["embed_vjp"](params["embed"]["tok"], tokens, dx)},
                       "periods": {k: _stack(v) for k, v in grads.items()}, "head": dhead}


def _cut(tokens, targets, rows, cols):
    if rows is not None:
        tokens, targets = tokens[rows], targets[rows]
    if cols is not None:
        tokens, targets = tokens[:, cols], targets[:, cols]
    return tokens, targets


def step_gradient(params, tokens, targets, shape, rows_per_block, quant=None, fault=None,
                  rows: Optional[slice] = None, cols: Optional[slice] = None):
    """(loss, float32 gradients) of the mean cross entropy plus the balance
    losses, accumulated over blocks of rows."""
    with jax.default_matmul_precision("highest"):
        tokens, targets = _cut(tokens, targets, rows, cols)
        scale = jnp.asarray(1.0 / tokens.size, F32)
        aux_scale = jnp.asarray(1.0 / tokens.shape[0], F32)
        loss, acc = jnp.zeros((), F32), None
        for i in range(0, tokens.shape[0], rows_per_block):
            part, g = _block_gradient(params, tokens[i:i + rows_per_block],
                                      targets[i:i + rows_per_block], shape, quant, fault,
                                      scale, aux_scale)
            loss = loss + part
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
        return loss, acc


def step_loss(params, tokens, targets, shape, rows_per_block, quant=None, fault=None):
    with jax.default_matmul_precision("highest"):
        fn, total = _jitted(shape, quant, fault), 0.0
        for i in range(0, tokens.shape[0], rows_per_block):
            tok, tgt = tokens[i:i + rows_per_block], targets[i:i + rows_per_block]
            x = fn["embed"](params["embed"]["tok"], tok)
            for kind, lp in layers_of(params, shape):
                x, aux = fn["layer"][kind](lp, x)
                total += float(aux) / tokens.shape[0]
            total += float(fn["head_loss"](params["head"], x, tgt)) / tokens.size
        return total


def train_reference(make_params: Callable, batches, hp: Mapping, shape,
                    rows_per_block: int, quant=None, fault=None,
                    rows: Optional[slice] = None, cols: Optional[slice] = None) -> Dict:
    """Follow the job's first steps as ``reference.train_reference`` does:
    gradient and Adam for steps 1 and 2, the loss alone for step 3."""
    kw = dict(lr=float(hp["lr"]), b1=float(hp["betas"][0]),
              b2=float(hp["betas"][1]), eps=float(hp["eps"]))
    losses, held = [], []       # the gradients so far, on the host while the next is computed
    p = make_params()
    for step in (1, 2):
        tok, tgt = batches[step - 1]
        loss, g = step_gradient(p, tok, tgt, shape, rows_per_block, quant, fault, rows, cols)
        losses.append(float(loss))
        if step == 1:
            grad_norms = jax.device_get(_jit_leaf_norms(g))
            grad_samples = jax.device_get(_jit_leaf_samples(g))
        p = reference.adam_apply(p, (*held, g), step=step, **kw)
        if step == 1:       # 3.5 GB in float32 at the cell's size
            held.append(jax.device_get(g))
        del g
    del held
    changes = jax.device_get(_jit_change_norms(p, make_params()))
    if len(batches) > 2:
        tok, tgt = _cut(*batches[2], rows, cols)
        losses.append(step_loss(p, tok, tgt, shape, rows_per_block, quant, fault))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples, "change_norms": changes}
