"""Plain reference of SmallThinker's decoder (SmallThinker-21B-A3B's block:
full causal attention layers with no positional encoding among windowed
rotary ones, grouped K/V heads, a router that reads the layer's normed input
before attention, ReLU-gated routed experts) and of its training loss:
forward, loss, gradients and Adam.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (every product also names the
precision itself). It imports nothing of the program and takes nothing the
program made: weights come from ``weights_smallthinker.py``, batches from
``traffic.train_batch``, the controls' arithmetic (``quant``), the leaves'
comparison and Adam from ``reference.py``, ``reference_hybrid.py`` and
``reference_dsv2.py``, which are the benchmark's own. As the configuration's
file states (published keys, and ``assumed``), one layer, ``x`` its input:

* ``a = RMSNorm_1(x)``; **the routing from a**: ``s = softmax(a W_r)`` over
  all 64 in float32, the 6 largest, ``w_i = s_i / sum of the chosen s``;
* ``q = a W_q`` (28 heads), ``k = a W_k``, ``v = a W_v`` (4 heads; query
  head ``i`` reads K/V head ``i // 7``); on window layers q and k rotated in
  ``rotate_half`` form over all 128 (pairs ``(i, i + 64)``, theta
  1,500,000, the angle ``position x inv_freq_i`` with the frequencies
  written out in float64), on full layers not at all; ``o = softmax(q k^T
  128^-1/2 + mask) v``, the mask **explicit**: causal on full layers, ``i -
  4096 < j <= i`` on window ones; ``h = x + o W_o``;
* ``m = RMSNorm_2(h)``; ``y = h + sum over the chosen experts held of w_i
  (ReLU(m W_gate) * (m W_up)) W_down``, the held experts **as a loop**, each
  over every position with its weight (0 where the position did not choose
  it). What the experts not held would add is left out; no balance loss;
* a final RMSNorm, an untied head, eps 1e-6.

Departures, for size alone: a step's gradient is worked out **a layer at a
time** (the layers' inputs kept, each layer's vector-Jacobian product its
own call of a compiled function) and a row at a time; attention runs **in
blocks of query rows** under ``jax.checkpoint``, a K/V head's group of query
heads at a time, over every key with the mask applied.

``quant`` (``"int8"``, ``"fp8"``) makes a control: the operands of every
matrix product but the router's are rounded first to the lower type's grid.
``fault`` plants what must read not ``correct`` (:data:`FAULTS`): the router
reading ``m``, the experts' input after attention, in place of ``a``; SiLU in
place of ReLU in the experts; the full layers rotated as the window layers
are; the window left out (window layers causal over the whole row); the
chosen weights not renormalised. Each fault is a number the compiled layers
read (``knobs``), so every fault runs the sound reference's programs.
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
from reference_dsv2 import _cut, head_loss_sum
from reference_hybrid import _jit_change_norms, _jit_leaf_norms, _jit_leaf_samples, _rms_norm
from reference_hybrid import _silu
from weights_smallthinker import FULL, WINDOW, layer_types, period_of, stacked

FAULTS = ("router-reads-m", "silu", "full-rotation", "no-window", "no-renorm")


def knobs(fault: Optional[str] = None):
    """The faults as the compiled layers read them: one where planted."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    return jnp.asarray([float(f == fault) for f in FAULTS], F32)


def _on(k, fault: str):
    return k[FAULTS.index(fault)] > 0.5


def model_shape(config: Mapping) -> tuple:
    """What the functions below need of a configuration's file, hashable."""
    return tuple(sorted({
        "layers": layer_types(config), "period": period_of(config),
        "heads": config["num_attention_heads"], "kv": config["num_key_value_heads"],
        "d": config["head_dim"], "window": config["sliding_window_size"],
        "theta": float(config["rope_theta"]), "eps": float(config["rms_norm_eps"]),
        "top_k": config["moe_num_active_primary_experts"],
        "experts_held": tuple(config["experts_held"]),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "stacked": tuple(sorted(stacked(config).items())),
    }.items()))


# ---------------------------------------------------------------------------
# the model

def _rotate(x, theta: float):
    """``x`` (..., s, d) rotated pair ``(i, i + d/2)`` by ``position x
    theta^(-2i/d)``."""
    s, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(freq, F32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(a, p, m, kind, quant, k, q_block: int = 512):
    r, s, _ = a.shape
    n, kv, d = m["heads"], m["kv"], m["d"]
    g = n // kv
    by_head = lambda t, heads: jnp.moveaxis(t.reshape(r, s, heads, d), 2, 1)  # (r, heads, s, d)
    q = by_head(_mm(a, p["wq"], quant), n)
    kk = by_head(_mm(a, p["wk"], quant), kv)
    v = by_head(_mm(a, p["wv"], quant), kv)
    if kind == WINDOW:
        q, kk = _rotate(q, m["theta"]), _rotate(kk, m["theta"])
        window = jnp.where(_on(k, "no-window"), s, m["window"])
    else:
        turned = _on(k, "full-rotation")
        q = jnp.where(turned, _rotate(q, m["theta"]), q)
        kk = jnp.where(turned, _rotate(kk, m["theta"]), kk)
        window = s
    scale = d ** -0.5
    q_block = math.gcd(s, q_block)
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block_of_queries(args):         # one K/V head's group, one block of queries
        q, kk, v, first = args          # (r, g, q_block, d), (r, s, d), (r, s, d)
        rows = first + jnp.arange(q_block)[:, None]
        seen = (keys <= rows) & (rows - keys < window)
        scores = _einsum("rgqd,rkd->rgqk", q, kk, quant, -1, -1) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("rgqk,rkd->rgqd", probs, v, quant, -1, 1)

    def group(args):
        q, kk, v = args                 # (r, g, s, d), (r, s, d), (r, s, d)
        qs = jnp.moveaxis(q.reshape(r, g, s // q_block, q_block, d), 2, 0)
        out = jax.lax.map(lambda t: block_of_queries((t[0], kk, v, t[1])),
                          (qs, jnp.arange(0, s, q_block)))
        return jnp.moveaxis(out, 0, 2).reshape(r, g, s, d)

    ctx = jax.lax.map(group, (jnp.moveaxis(q.reshape(r, kv, g, s, d), 1, 0),
                              jnp.moveaxis(kk, 1, 0), jnp.moveaxis(v, 1, 0)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(r, n, s, d)            # head i = group i // g
    return _mm(jnp.moveaxis(ctx, 1, 2).reshape(r, s, n * d), p["wo"], quant)


def route(a, router, m, k):
    """``(idx, weight)`` of each position (positions, top_k): softmax over
    every expert in float32, the largest ``top_k``, renormalised over
    them."""
    scores = jax.nn.softmax(jnp.matmul(a, router, precision=HIGHEST), axis=-1)
    chosen, idx = jax.lax.top_k(scores, m["top_k"])
    if m["norm_topk_prob"]:
        chosen = jnp.where(_on(k, "no-renorm"), chosen,
                           chosen / jnp.sum(chosen, -1, keepdims=True))
    return idx, chosen


def _routed(f, idx, weight, p, m, quant, k):
    """The held experts' sum, one at a time and each over every position:
    ``(ReLU(f W_gate) * f W_up) W_down``."""
    first, count = m["experts_held"]
    act = lambda t: jnp.where(_on(k, "silu"), _silu(t), jnp.maximum(t, 0.0))

    @jax.checkpoint
    def expert(e):
        w_gate, w_up, w_down, number = e
        w = jnp.sum(jnp.where(idx == number, weight, 0.0), axis=-1)
        y = _mm(act(_mm(f, w_gate, quant)) * _mm(f, w_up, quant), w_down, quant)
        return w[:, None] * y

    y, _ = jax.lax.scan(lambda y, e: (y + expert(e), None), jnp.zeros_like(f),
                        (p["w_gate"], p["w_up"], p["w_down"], first + jnp.arange(count)))
    return y


def layer_fn(p, x, k, shape, kind, quant=None):
    """One layer: ``p`` its leaves (any float type), ``x`` (rows, seq,
    hidden), ``k`` the faults' :func:`knobs`, ``kind`` ``FULL`` or
    ``WINDOW``."""
    m = dict(shape)
    p = jax.tree.map(lambda t: t.astype(F32), p)
    r, s, hidden = x.shape
    a = _rms_norm(x, p["norm1"], m["eps"])
    h = x + _attention(a, p, m, kind, quant, k)
    f = _rms_norm(h, p["norm2"], m["eps"])
    reads = jnp.where(_on(k, "router-reads-m"), f, a).reshape(-1, hidden)
    idx, weight = route(reads, p["router"], m, k)
    return h + _routed(f.reshape(-1, hidden), idx, weight, p, m, quant, k).reshape(r, s, hidden)


def layers_of(params, shape):
    """``[(kind, that layer's leaves)]`` in the model's order."""
    m = dict(shape)
    out = []
    periods = dict(m["stacked"])[m["period"][0]][0]
    for i in range(periods):
        seen = dict.fromkeys(m["period"], 0)
        for kind in m["period"]:
            out.append((kind, jax.tree.map(lambda a: a[i, seen[kind]], params["periods"][kind])))
            seen[kind] += 1
    return out


def loss_fn(params, tokens, targets, shape, quant=None, fault=None):
    """The whole loss in one differentiable piece (the tests' sizes)."""
    return knobbed_loss(params, tokens, targets, shape, knobs(fault), quant)


def knobbed_loss(params, tokens, targets, shape, k, quant=None):
    """:func:`loss_fn` with the faults given as :func:`knobs` ``k``: one
    compiled program for the sound model and every fault."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["tok"].astype(F32), tokens, axis=0)
        for kind, lp in layers_of(params, shape):
            x = layer_fn(lp, x, k, shape, kind, quant)
        return head_loss_sum(params["head"], x, targets, shape, quant) / tokens.size


# ---------------------------------------------------------------------------
# a step's gradient: a layer at a time, a block of rows at a time

@functools.lru_cache(maxsize=None)
def _jitted(shape, quant):
    kinds = tuple(dict.fromkeys(dict(shape)["period"]))

    def layer(kind):
        return functools.partial(layer_fn, shape=shape, kind=kind, quant=quant)

    def layer_vjp(kind):
        def f(p, x, k, dy):
            _, pull = jax.vjp(lambda p, x: layer(kind)(p, x, k), p, x)
            dp, dx = pull(dy)
            return jax.tree.map(lambda a: a.astype(F32), dp), dx
        return f

    def head_vjp(head, x, targets, scale):
        loss, (dhead, dx) = jax.value_and_grad(
            lambda h, x: head_loss_sum(h, x, targets, shape, quant) * scale,
            argnums=(0, 1))(head, x)
        return loss, jax.tree.map(lambda a: a.astype(F32), dhead), dx

    return {"layer": {kind: jax.jit(layer(kind)) for kind in kinds},
            "layer_vjp": {kind: jax.jit(layer_vjp(kind)) for kind in kinds},
            "head_vjp": jax.jit(head_vjp),
            "embed": jax.jit(lambda table, tok: jnp.take(table.astype(F32), tok, axis=0)),
            "embed_vjp": jax.jit(lambda table, tok, dx: jnp.zeros(table.shape, F32).at[tok].add(dx)),
            "head_loss": jax.jit(lambda h, x, tgt: head_loss_sum(h, x, tgt, shape, quant))}


def _block_gradient(params, tokens, targets, shape, quant, k, scale):
    """(loss, gradient tree in float32) of ``scale x sum of CE`` over one
    block of rows."""
    fn, layers = _jitted(shape, quant), layers_of(params, shape)
    xs = [fn["embed"](params["embed"]["tok"], tokens)]
    for kind, lp in layers:
        xs.append(fn["layer"][kind](lp, xs[-1], k))
    loss, dhead, dx = fn["head_vjp"](params["head"], xs.pop(), targets, scale)
    grads = {kind: [] for kind in params["periods"]}
    for kind, lp in reversed(layers):
        g, dx = fn["layer_vjp"][kind](lp, xs.pop(), k, dx)
        grads[kind].insert(0, g)
    lead = dict(dict(shape)["stacked"])
    periods = {kind: jax.tree.map(lambda *a: jnp.stack(a).reshape(lead[kind] + a[0].shape), *v)
               for kind, v in grads.items()}
    return loss, {"embed": {"tok": fn["embed_vjp"](params["embed"]["tok"], tokens, dx)},
                  "periods": periods, "head": dhead}


def step_gradient(params, tokens, targets, shape, rows_per_block, quant=None, fault=None,
                  rows: Optional[slice] = None, cols: Optional[slice] = None):
    """(loss, float32 gradients) of the mean cross entropy, accumulated over
    blocks of rows."""
    with jax.default_matmul_precision("highest"):
        tokens, targets = _cut(tokens, targets, rows, cols)
        scale = jnp.asarray(1.0 / tokens.size, F32)
        k = knobs(fault)
        loss, acc = jnp.zeros((), F32), None
        for i in range(0, tokens.shape[0], rows_per_block):
            part, g = _block_gradient(params, tokens[i:i + rows_per_block],
                                      targets[i:i + rows_per_block], shape, quant, k, scale)
            loss = loss + part
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
        return loss, acc


def step_loss(params, tokens, targets, shape, rows_per_block, quant=None, fault=None):
    with jax.default_matmul_precision("highest"):
        fn, total, k = _jitted(shape, quant), 0.0, knobs(fault)
        for i in range(0, tokens.shape[0], rows_per_block):
            tok, tgt = tokens[i:i + rows_per_block], targets[i:i + rows_per_block]
            x = fn["embed"](params["embed"]["tok"], tok)
            for kind, lp in layers_of(params, shape):
                x = fn["layer"][kind](lp, x, k)
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
        if step == 1:       # 2.6 GB in float32 at the cell's size
            held.append(jax.device_get(g))
        del g
    del held
    changes = jax.device_get(_jit_change_norms(p, make_params()))
    if len(batches) > 2:
        tok, tgt = _cut(*batches[2], rows, cols)
        losses.append(step_loss(p, tok, tgt, shape, rows_per_block, quant, fault))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples, "change_norms": changes}
