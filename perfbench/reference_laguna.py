"""Plain reference of Laguna's decoder (Laguna-S-2.1's block: sliding-window
and full attention layers with grouped K/V heads, QK-norm, rotary positions
by layer type and a sigmoid gate a head, a leading dense layer, routed
experts beside a gated shared one) and of its training loss: forward, loss,
gradients and Adam.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (every product also names the
precision itself). It imports nothing of the program and takes nothing the
program made: weights come from ``weights_laguna.py``, batches from
``traffic.train_batch``, the controls' arithmetic (``quant``), the leaves'
comparison and Adam from ``reference.py``, ``reference_hybrid.py`` and
``reference_dsv2.py``, which are the benchmark's own. As the configuration's
file states (published keys, and ``assumed``):

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, a final
  RMSNorm, an untied head, eps 1e-6; layer 0's FFN dense (``(SiLU(x W_gate)
  * x W_up) W_down``), the others routed + gated shared;
* attention, ``a = RMSNorm(x)``: ``q = a W_q`` (48 heads on full layers, 72
  on sliding ones), ``k = a W_k``, ``v = a W_v`` (8 heads; query head ``i``
  reads K/V head ``i // group``); q and k RMS-normalised over each head's 128
  with a learned weight; rotated in ``rotate_half`` form, the angle
  ``position x inv_freq_i`` written out in float64: on full layers the first
  64 of a head's 128 (pairs ``(i, i + 32)``), YaRN's frequencies over those
  64 (ramp from pair 9 to pair 18) and cos and sin times
  ``attention_factor``, the other 64 as they are; on sliding layers all 128
  (pairs ``(i, i + 64)``) at theta 10,000; ``o = softmax(q k^T 128^-1/2 +
  mask) v``, the mask **explicit**: causal on full layers, ``i - 512 < j <=
  i`` on sliding ones; each head's ``o`` times ``sigmoid(a W_g)`` for that
  head; then ``W_o``;
* expert FFN, ``m = RMSNorm(h)``: ``s = softmax(m W_r)`` over all 256; the
  10 largest; ``w_i = s_i / sum of the chosen s x 2.5``; the held experts
  **as a loop**, each over every position with its weight (0 where the
  position did not choose it); plus ``sigmoid(m W_sg) x Shared(m)``. What
  the experts not held would add is left out; no balance loss.

Departures, for size alone: a step's gradient is worked out **a layer at a
time** (the layers' inputs kept, each layer's vector-Jacobian product its own
call of a compiled function) and a row at a time; attention runs **in
blocks of query rows** under ``jax.checkpoint``, a K/V head's group of query
heads at a time, so that 72 heads at 8,192 fit beside 811 M float32 weights.

``quant`` (``"int8"``, ``"fp8"``) makes a control: the operands of every
matrix product but the router's are rounded first to the lower type's grid.
``fault`` plants what must read not ``correct`` (:data:`FAULTS`): the window
left out (sliding layers causal over the whole row), the per-head gate left
out, all 128 of a full layer's head rotated (YaRN over 128), plain
frequencies in place of YaRN's, the routed weights without the 2.5, the
shared expert without its gate. Each fault is a number the compiled layers
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
from reference_dsv2 import _cut, _gated, head_loss_sum
from reference_hybrid import _jit_change_norms, _jit_leaf_norms, _jit_leaf_samples, _rms_norm
from weights_laguna import DENSE, FULL, SLIDING, dense_layers, heads_of, period_of, stacked

FAULTS = ("no-window", "no-gate", "full-rotation", "no-yarn", "no-scale", "no-shared-gate")


def knobs(fault: Optional[str] = None):
    """The faults as the compiled layers read them: one where planted."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    return jnp.asarray([float(f == fault) for f in FAULTS], F32)


def _on(k, fault: str):
    return k[FAULTS.index(fault)] > 0.5


def model_shape(config: Mapping) -> tuple:
    """What the functions below need of a configuration's file, hashable."""
    rope = {}
    for kind in (FULL, SLIDING):
        e = config["rope_parameters"][kind]
        rope[kind] = tuple(sorted(e.items()))
    return tuple(sorted({
        "layers": tuple(config["layer_types"][:config["num_hidden_layers"]]),
        "dense_layers": dense_layers(config), "period": period_of(config),
        "heads": ((FULL, heads_of(config, FULL)), (SLIDING, heads_of(config, SLIDING))),
        "kv": config["num_key_value_heads"], "d": config["head_dim"],
        "window": config["sliding_window"], "rope": tuple(sorted(rope.items())),
        "eps": float(config["rms_norm_eps"]), "top_k": config["num_experts_per_tok"],
        "experts_held": tuple(config["experts_held"]),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "scale": float(config["moe_routed_scaling_factor"]),
        "stacked": tuple(sorted(stacked(config).items())),
    }.items()))


# ---------------------------------------------------------------------------
# positions

def inv_freq(d: int, theta: float, entry: Optional[Mapping]) -> np.ndarray:
    """The ``d / 2`` frequencies (float64): ``f_i = theta^(-2i/d)``; with a
    YaRN ``entry`` ``f_i (1 - ramp_i) + f_i / factor x ramp_i``, the ramp from
    pair ``low`` to pair ``high`` over these ``d``."""
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    if entry is None:
        return f
    original = entry["original_max_position_embeddings"]
    pair_of = lambda turns: d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(pair_of(entry["beta_fast"])), 0)
    high = min(math.ceil(pair_of(entry["beta_slow"])), d - 1)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - ramp) + f / entry["factor"] * ramp


def _turn(x, freq, factor: float):
    """``x`` (..., s, w) rotated pair ``(i, i + w/2)`` by ``position x
    freq_i``, cos and sin times ``factor``."""
    s, w = x.shape[-2], x.shape[-1]
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(freq, F32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :w // 2], x[..., w // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rotate(x, m, kind, k):
    """``x`` (..., s, d) by the layer type's ``rope_parameters``."""
    entry = dict(dict(m["rope"])[kind])
    d, theta = m["d"], float(entry["rope_theta"])
    yarn = entry if entry.get("rope_type", "default") == "yarn" else None
    factor = float(entry.get("attention_factor", 1.0)) if yarn else 1.0
    r = int(d * float(entry.get("partial_rotary_factor", 1.0)))
    if yarn is None:
        return jnp.concatenate([_turn(x[..., :r], inv_freq(r, theta, None), 1.0),
                                x[..., r:]], axis=-1)
    freq = jnp.where(_on(k, "no-yarn"), jnp.asarray(inv_freq(r, theta, None), F32),
                     jnp.asarray(inv_freq(r, theta, yarn), F32))
    partial = jnp.concatenate([_turn(x[..., :r], freq, factor), x[..., r:]], axis=-1)
    whole = _turn(x, inv_freq(d, theta, yarn), factor)
    return jnp.where(_on(k, "full-rotation"), whole, partial)


# ---------------------------------------------------------------------------
# the model

def _attention(a, p, m, kind, quant, k, q_block: int = 512):
    r, s, _ = a.shape
    n, kv, d = dict(m["heads"])[kind], m["kv"], m["d"]
    g = n // kv
    by_head = lambda t, heads: jnp.moveaxis(t.reshape(r, s, heads, d), 2, 1)  # (r, heads, s, d)
    q = _rms_norm(by_head(_mm(a, p["wq"], quant), n), p["q_norm"], m["eps"])
    kk = _rms_norm(by_head(_mm(a, p["wk"], quant), kv), p["k_norm"], m["eps"])
    v = by_head(_mm(a, p["wv"], quant), kv)
    q, kk = _rotate(q, m, kind, k), _rotate(kk, m, kind, k)
    scale = d ** -0.5
    q_block = math.gcd(s, q_block)
    keys = jnp.arange(s)[None, :]
    window = jnp.where(_on(k, "no-window"), s, m["window"]) if kind == SLIDING else s

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
    gate = jax.nn.sigmoid(_mm(a, p["wg"], quant))                # (r, s, n)
    gate = jnp.where(_on(k, "no-gate"), 1.0, gate)
    ctx = jnp.moveaxis(ctx, 1, 2) * gate[..., None]              # (r, s, n, d)
    return _mm(ctx.reshape(r, s, n * d), p["wo"], quant)


def _routed(x, p, m, quant, k):
    """The held experts' sum, one at a time and each over every position."""
    r, s, h = x.shape
    xf = x.reshape(-1, h)
    scores = jax.nn.softmax(jnp.matmul(xf, p["router"], precision=HIGHEST), axis=-1)
    chosen, idx = jax.lax.top_k(scores, m["top_k"])
    if m["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    weight = chosen * jnp.where(_on(k, "no-scale"), 1.0, m["scale"])
    first, count = m["experts_held"]

    @jax.checkpoint
    def expert(e):
        w_gate, w_up, w_down, number = e
        w = jnp.sum(jnp.where(idx == number, weight, 0.0), axis=-1)
        return w[:, None] * _gated(xf, w_gate, w_up, w_down, quant)

    y, _ = jax.lax.scan(lambda y, e: (y + expert(e), None), jnp.zeros_like(xf),
                        (p["w_gate"], p["w_up"], p["w_down"], first + jnp.arange(count)))
    return y.reshape(r, s, h)


def shared_fn(f, p, quant, k):
    """The shared expert times its gate a position."""
    gate = jax.nn.sigmoid(_mm(f, p["shared_expert_gate"], quant))
    gate = jnp.where(_on(k, "no-shared-gate"), 1.0, gate)
    return gate * _gated(f, p["shared_gate"], p["shared_up"], p["shared_down"], quant)


def layer_fn(p, x, k, shape, kind, quant=None):
    """One layer: ``p`` its leaves (any float type), ``x`` (rows, seq,
    hidden), ``k`` the faults' :func:`knobs`, ``kind`` ``DENSE`` or an expert
    layer's type."""
    m = dict(shape)
    p = jax.tree.map(lambda a: a.astype(F32), p)
    h = x + _attention(_rms_norm(x, p["norm1"], m["eps"]), p, m,
                       FULL if kind == DENSE else kind, quant, k)
    f = _rms_norm(h, p["norm2"], m["eps"])
    if kind == DENSE:
        return h + _gated(f, p["w_gate"], p["w_up"], p["w_down"], quant)
    return h + _routed(f, p, m, quant, k) + shared_fn(f, p, quant, k)


def layers_of(params, shape):
    """``[(kind, that layer's leaves)]`` in the model's order."""
    m = dict(shape)
    out = [(DENSE, jax.tree.map(lambda a: a[i, 0], params["periods"][DENSE]))
           for i in range(m["dense_layers"])]
    periods = dict(m["stacked"])[m["period"][0]][0]
    for i in range(periods):
        seen = dict.fromkeys(m["period"], 0)
        for kind in m["period"]:
            out.append((kind, jax.tree.map(lambda a: a[i, seen[kind]], params["periods"][kind])))
            seen[kind] += 1
    return out


def loss_fn(params, tokens, targets, shape, quant=None, fault=None):
    """The whole loss in one differentiable piece (the tests' sizes)."""
    with jax.default_matmul_precision("highest"):
        k = knobs(fault)
        x = jnp.take(params["embed"]["tok"].astype(F32), tokens, axis=0)
        for kind, lp in layers_of(params, shape):
            x = layer_fn(lp, x, k, shape, kind, quant)
        return head_loss_sum(params["head"], x, targets, shape, quant) / tokens.size


# ---------------------------------------------------------------------------
# a step's gradient: a layer at a time, a block of rows at a time

@functools.lru_cache(maxsize=None)
def _jitted(shape, quant):
    kinds = (DENSE, *dict.fromkeys(dict(shape)["period"]))

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
        if step == 1:       # 3.2 GB in float32 at the cell's size
            held.append(jax.device_get(g))
        del g
    del held
    changes = jax.device_get(_jit_change_norms(p, make_params()))
    if len(batches) > 2:
        tok, tgt = _cut(*batches[2], rows, cols)
        losses.append(step_loss(p, tok, tgt, shape, rows_per_block, quant, fault))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples, "change_norms": changes}
