"""Plain reference of the GPT-2 block: forward, loss, gradients and Adam.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest":
no kernels, no cache, no batching tricks. It imports nothing of the program
and takes nothing the program made; weights come from ``weights.py`` (the
benchmark's own, from the seed). Departures from the published model, both
stated in the configuration files: the embedding table is padded to
``assumed.padded_vocab_size`` rows, and parameters are *stored* in bfloat16
with no float32 master copy, so an Adam step ends in a rounding of the
parameter to bfloat16.

``quant`` turns the same code into the control: every matrix product's two
operands are rounded first to the grid of a type one step below bfloat16,
scaled by the absmax along the contracted dimension (the most careful of the
usual recipes): ``"int8"`` (127 levels a side) or ``"fp8"`` (float8 e4m3, three
mantissa bits). PERF.md says which of the two the limits were set from.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# the arithmetics: float32 "highest", and the controls' lower grids

def _grid(x, axis, quant: str):
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        return jnp.round(x / scale) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


@jax.custom_vjp
def _ste(x, q):
    return q


_ste.defvjp(lambda x, q: (q, None), lambda _, g: (g, jnp.zeros_like(g)))


def low_operand(x, axis, quant: str):
    """``x`` on the lower type's grid along ``axis``; straight-through
    gradient."""
    return _ste(x, _grid(x, axis, quant))


def _mm(a, b, quant):
    """(..., k) @ (k, n)."""
    if quant:
        a = low_operand(a, -1, quant)
        b = low_operand(b, 0, quant)
    return jnp.matmul(a, b, precision=HIGHEST)


def _einsum(spec, a, b, quant, a_axis: int, b_axis: int):
    if quant:
        a = low_operand(a, a_axis, quant)
        b = low_operand(b, b_axis, quant)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# the model

def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lp, n_head: int, eps: float, quant):
    """One pre-LN block on (rows, seq, h). QKV columns are packed
    (head, {q, k, v}, head_dim), the layout the weights are made in."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    r, s, h = x.shape
    d = h // n_head
    y = _layer_norm(x, lp["ln1_w"], lp["ln1_b"], eps)
    qkv = _mm(y, lp["qkv_kernel"], quant) + lp["qkv_bias"]
    qkv = qkv.reshape(r, s, n_head, 3, d)
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    scores = _einsum("rqhd,rkhd->rhqk", q, k, quant, -1, -1) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _einsum("rhqk,rkhd->rqhd", probs, v, quant, -1, 1)
    x = x + _mm(ctx.reshape(r, s, h), lp["out_kernel"], quant) + lp["out_bias"]
    y = _layer_norm(x, lp["ln2_w"], lp["ln2_b"], eps)
    m = _gelu_new(_mm(y, lp["fc1_kernel"], quant) + lp["fc1_bias"])
    return x + _mm(m, lp["fc2_kernel"], quant) + lp["fc2_bias"]


def logits_fn(params, tokens, n_head: int, eps: float, quant=None,
              remat: bool = False):
    """tokens (rows, seq) -> float32 logits (rows, seq, padded vocab)."""
    tok = params["embed"]["tok"].astype(F32)
    pos = params["embed"]["pos"].astype(F32)
    x = jnp.take(tok, tokens, axis=0) + pos[None, :tokens.shape[1]]

    def body(x, lp):
        return _block(x, lp, n_head, eps, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _layer_norm(x, params["head"]["ln_w"].astype(F32),
                    params["head"]["ln_b"].astype(F32), eps)
    return _einsum("rsh,vh->rsv", x, tok, quant, -1, -1)


def loss_sum_fn(params, tokens, targets, n_head, eps, quant=None):
    """Sum over the block's tokens of the cross entropy (the caller divides
    by the step's token count, so blocks of rows add up to the step's mean)."""
    logits = logits_fn(params, tokens, n_head, eps, quant, remat=True)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


# ---------------------------------------------------------------------------
# leaves, as the comparison sees them

def leaf_norms(tree, n_head: int) -> Dict[str, jnp.ndarray]:
    """L2 norm of every leaf the comparison counts. A stacked layer leaf
    counts once per layer, and the packed QKV leaves once per layer for each
    of q, k and v (a key's bias has no gradient under softmax: it has to be a
    leaf of its own for the rule that leaves such leaves out)."""
    out = {}
    sq = lambda a, axes: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)), axis=axes))
    for name, a in tree["embed"].items():
        out[f"embed.{name}"] = sq(a, None)[None]
    for name, a in tree["head"].items():
        out[f"head.{name}"] = sq(a, None)[None]
    for name, a in tree["layers"].items():
        if name in ("qkv_kernel", "qkv_bias"):
            L = a.shape[0]
            d = a.shape[-1] // (3 * n_head)
            parts = a.reshape(L, -1, n_head, 3, d)
            per = sq(parts, (1, 2, 4))                      # (L, 3)
            for i, part in enumerate("qkv"):
                out[f"layers.{name}.{part}"] = per[:, i]
        else:
            out[f"layers.{name}"] = sq(a, tuple(range(1, a.ndim)))
    return out


def leaf_samples(tree, n_head: int, per_leaf: int = 4096) -> Dict[str, jnp.ndarray]:
    """Evenly spaced elements of every leaf :func:`leaf_norms` counts (at most
    ``per_leaf`` of each), as (leaves, samples) arrays: enough to read a
    leaf's relative error to a percent or two without keeping the leaf."""
    def take(a2d):                       # (L, n) -> (L, <= per_leaf)
        stride = max(1, a2d.shape[1] // per_leaf)
        return a2d[:, ::stride][:, :per_leaf].astype(F32)

    out = {}
    for group in ("embed", "head"):
        for name, a in tree[group].items():
            out[f"{group}.{name}"] = take(a.reshape(1, -1))
    for name, a in tree["layers"].items():
        L = a.shape[0]
        if name in ("qkv_kernel", "qkv_bias"):
            d = a.shape[-1] // (3 * n_head)
            parts = a.reshape(L, -1, n_head, 3, d)
            for i, part in enumerate("qkv"):
                out[f"layers.{name}.{part}"] = take(parts[:, :, :, i].reshape(L, -1))
        else:
            out[f"layers.{name}"] = take(a.reshape(L, -1))
    return out


def sampled_error(prog: Mapping, ref: Mapping) -> Tuple[float, str, float]:
    """From :func:`leaf_samples` of the program's gradient and of the
    reference's: the relative error of the worst leaf (the norm of the
    difference over the reference's norm of that leaf or of the median leaf,
    whichever is larger), that leaf's name, and the relative error of all
    sampled elements together."""
    names, errs, norms = [], [], []
    sq_diff = sq_ref = 0.0
    for k in sorted(ref):
        p, q = np.asarray(prog[k], np.float64), np.asarray(ref[k], np.float64)
        d2, r2 = np.sum((p - q) ** 2, axis=1), np.sum(q ** 2, axis=1)
        sq_diff += float(d2.sum())
        sq_ref += float(r2.sum())
        names += [f"{k}[{i}]" for i in range(len(d2))]
        errs.append(np.sqrt(d2))
        norms.append(np.sqrt(r2))
    errs, norms = np.concatenate(errs), np.concatenate(norms)
    rel = errs / np.maximum(norms, float(np.median(norms)) or 1.0)
    i = int(np.argmax(rel))
    return float(rel[i]), names[i], float(np.sqrt(sq_diff / sq_ref))


def flatten_norms(norms: Mapping[str, np.ndarray]) -> Tuple[Sequence[str], np.ndarray]:
    names, vals = [], []
    for k in sorted(norms):
        v = np.asarray(norms[k], np.float64).reshape(-1)
        names += [f"{k}[{i}]" for i in range(v.size)]
        vals.append(v)
    return names, np.concatenate(vals)


def worst_leaf_gap(prog: Mapping, ref: Mapping, keep: Optional[np.ndarray] = None
                   ) -> Tuple[float, str]:
    """The gap between the program's norm and the reference's, by the worst
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger. ``keep`` masks the leaves that count."""
    names, p = flatten_norms(prog)
    _, r = flatten_norms(ref)
    floor = float(np.median(r))
    gap = np.abs(p - r) / np.maximum(r, floor if floor > 0 else 1.0)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), names[i]


# ---------------------------------------------------------------------------
# training: gradients over blocks of rows, and Adam

def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.lru_cache(maxsize=None)
def _grad_block(n_head, eps, quant):
    def f(params, acc, loss_acc, tokens, targets, inv_tokens):
        loss, g = jax.value_and_grad(
            lambda p: loss_sum_fn(p, tokens, targets, n_head, eps, quant)
            * inv_tokens)(params)
        g = jax.tree.map(lambda x: x.astype(F32), g)
        return _tree_add(acc, g), loss_acc + loss

    return jax.jit(f, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=None)
def _loss_block(n_head, eps, quant):
    return jax.jit(lambda p, tok, tgt: loss_sum_fn(p, tok, tgt, n_head, eps, quant))


def step_gradient(params, tokens, targets, n_head, eps, rows_per_block,
                  quant=None, rows: Optional[slice] = None):
    """(loss, float32 gradients) of the mean cross entropy over ``tokens``
    (or over ``rows`` of them alone, the mean taken over those: the
    half-batch fault), accumulated over blocks of rows so that it fits."""
    if rows is not None:
        tokens, targets = tokens[rows], targets[rows]
    n = tokens.shape[0]
    inv = jnp.asarray(1.0 / tokens.size, F32)
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    loss = jnp.zeros((), F32)
    fn = _grad_block(n_head, eps, quant)
    for i in range(0, n, rows_per_block):
        acc, loss = fn(params, acc, loss, tokens[i:i + rows_per_block],
                       targets[i:i + rows_per_block], inv)
    return loss, acc


def step_loss(params, tokens, targets, n_head, eps, rows_per_block, quant=None):
    fn = _loss_block(n_head, eps, quant)
    total = 0.0
    for i in range(0, tokens.shape[0], rows_per_block):
        total += float(fn(params, tokens[i:i + rows_per_block],
                          targets[i:i + rows_per_block]))
    return total / tokens.size


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "step"),
                   donate_argnums=(0,))
def adam_apply(params, grads_so_far, *, lr, b1, b2, eps, step):
    """Parameters after Adam's step number ``step`` (1-based), the moments
    worked out from all gradients so far (zero initial moments, so they need
    not be stored): m_t = (1-b1)·Σ b1^(t-i)·g_i, likewise v_t. The parameter
    is stored in its own type (bfloat16), so the step ends in a rounding."""
    def leaf(p, *gs):
        m = sum((1 - b1) * b1 ** (step - i) * g for i, g in enumerate(gs, 1))
        v = sum((1 - b2) * b2 ** (step - i) * g * g for i, g in enumerate(gs, 1))
        mhat = m / (1 - b1 ** step)
        vhat = v / (1 - b2 ** step)
        upd = -lr * mhat / (jnp.sqrt(vhat) + eps)
        return (p.astype(F32) + upd).astype(p.dtype)

    return jax.tree.map(leaf, params, *grads_so_far)


def train_reference(make_params: Callable, batches, hp: Mapping, n_head: int,
                    ln_eps: float, rows_per_block: int, quant=None,
                    rows: Optional[slice] = None) -> Dict:
    """Follow the job's first steps: gradient and Adam for steps 1 and 2,
    the loss alone for step 3 (a third gradient would not fit beside the
    first two). Returns losses, the first gradient's leaf norms and the leaf
    norms of the parameters' change after two steps. ``make_params()`` gives
    the initial parameters, anew each time it is called."""
    kw = dict(lr=float(hp["lr"]), b1=float(hp["betas"][0]),
              b2=float(hp["betas"][1]), eps=float(hp["eps"]))
    losses, grads = [], []
    grad_norms = None
    p = make_params()
    for step in (1, 2):
        tok, tgt = batches[step - 1]
        loss, g = step_gradient(p, tok, tgt, n_head, ln_eps, rows_per_block,
                                quant, rows)
        losses.append(float(loss))
        if step == 1:
            grad_norms = jax.device_get(_jit_leaf_norms(n_head)(g))
            grad_samples = jax.device_get(_jit_leaf_samples(n_head)(g))
        grads.append(g)
        p = adam_apply(p, tuple(grads), step=step, **kw)
    del grads, g
    change_norms = jax.device_get(_jit_change_norms(n_head)(p, make_params()))
    if len(batches) > 2:
        tok, tgt = batches[2]
        if rows is not None:
            tok, tgt = tok[rows], tgt[rows]
        losses.append(step_loss(p, tok, tgt, n_head, ln_eps, rows_per_block, quant))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples, "change_norms": change_norms}


@functools.lru_cache(maxsize=None)
def _jit_leaf_norms(n_head):
    return jax.jit(lambda t: leaf_norms(t, n_head))


@functools.lru_cache(maxsize=None)
def _jit_leaf_samples(n_head):
    return jax.jit(lambda t: leaf_samples(t, n_head))


def change_norms(after, before, n_head):
    """Leaf norms of ``after - before``, the difference taken in float32."""
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(F32) - b.astype(F32), after, before), n_head)


@functools.lru_cache(maxsize=None)
def _jit_change_norms(n_head):
    return jax.jit(lambda a, b: change_norms(a, b, n_head))


# ---------------------------------------------------------------------------
# serving: the gap by which a served token lies below the reference's best

@functools.lru_cache(maxsize=None)
def _jit_logits(n_head, eps, quant):
    return jax.jit(lambda p, t: logits_fn(p, t, n_head, eps, quant))


def served_token_gaps(params, sequences: Sequence[Tuple[Sequence[int], Sequence[int]]],
                      n_head: int, ln_eps: float, max_context: int,
                      control: Optional[str] = None, block: int = 8) -> np.ndarray:
    """For every served token of every (prompt, served) pair: how far its
    float32 reference logit lies below the reference's best at that
    position, the reference run once over prompt + served tokens (in blocks
    of ``block`` sequences, padded to the longest rounded up to 128). With
    ``control`` (``"int8"`` or ``"fp8"``) the token judged is not the served
    one but the one that arithmetic puts first at that position (the control
    need not decode)."""
    if not sequences:
        return np.zeros((0,))
    longest = max(len(p) + len(s) for p, s in sequences)
    pad_to = min(max_context, -(-longest // 128) * 128)
    fn = _jit_logits(n_head, ln_eps, None)
    low_fn = _jit_logits(n_head, ln_eps, control) if control else None
    gaps = []
    for at in range(0, len(sequences), block):
        chunk = list(sequences[at:at + block])
        toks = np.zeros((block, pad_to), np.int32)
        for i, (prompt, served) in enumerate(chunk):
            seq = list(prompt) + list(served)
            toks[i, :len(seq)] = seq
        toks = jnp.asarray(toks)
        logits = fn(params, toks)
        low = low_fn(params, toks) if low_fn else None
        for i, (prompt, served) in enumerate(chunk):
            p, n = len(prompt), len(served)
            rows = logits[i, p - 1:p - 1 + n]                     # (n, V)
            if low is not None:
                judged = jnp.argmax(low[i, p - 1:p - 1 + n], axis=-1)
            else:
                judged = jnp.asarray(np.asarray(served, np.int32))
            got = jnp.take_along_axis(rows, judged[:, None], axis=-1)[:, 0]
            gaps.append(np.asarray(jnp.max(rows, axis=-1) - got, np.float64))
    return np.concatenate(gaps)
