"""A decoder whose layers are of several kinds: gated-delta-rule linear
attention among full softmax attention, in a repeating period.

The block is the modern dense one (no biases anywhere): the norm sits on each
sublayer's *output*, ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(FFN(h))``
with a SiLU-gated FFN; a final RMSNorm and an untied head fused into the loss
(``ops/lm_head_loss.py``). The mixer is one of

* ``full_attention``: QK-norm (RMSNorm over the whole projection), causal
  softmax attention through ``ops.flash_attention``, no positional encoding;
* ``linear_attention``: per head ``q, k`` (``d_k``) and ``v`` (``d_v``)
  through a short causal convolution and SiLU, ``q, k`` normalised to unit
  length, a write strength ``beta`` (up to 2 with ``allow_neg_eigval``) and a
  decay ``alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))``, the gated
  delta rule (``ops/delta_rule.py``), then ``RMSNorm(o) * SiLU(x W_g)`` a head
  and the output projection.

Parameters are kept **by layer kind**, each leaf stacked over (periods, that
kind's layers in a period): ``layer_types`` is cut into its shortest period,
the stack is a ``lax.scan`` over periods whose body writes the period's layers
out in order, each under ``jax.checkpoint``. The model holds
``vocab_held`` rows of the embedding and of the head (a chip's share of a
vocabulary divided by rows over several chips is a smaller vocabulary: ids
and the loss are over the rows held).

``transformer/sdar.py`` (block-diffusion training through grouped-query rotary
attention and routed experts) is a sibling that runs the same stack
(:func:`run_stack`) with a layer and a loss of its own.

Data parallelism works as for GPT (replicated parameters, the batch over
``dp``). Tensor parallelism is not written for these mixers and ``tp > 1`` is
refused. The train step is ``bench.train_step_fn``'s: this config meets the
same three-method protocol as ``GPTConfig`` (``param_specs``, ``init_params``,
``loss``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor.trace import span
from apex_tpu.ops._pallas_util import pvary_like
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.delta_rule import (
    causal_conv1d,
    gated_delta_rule,
    gated_rms_norm,
    l2_normalize,
)
from apex_tpu.ops.layer_norm import rms_norm
from apex_tpu.ops.lm_head_loss import lm_head_loss
from apex_tpu.ops.rope import apply_rotary
from apex_tpu.parallel.mesh import TP_AXIS

Pytree = Any
F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_held: int = 12544         # rows of the embedding and the head here
    hidden: int = 3840
    ffn_hidden: int = 11008
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    num_heads: int = 30             # full layers
    head_dim: int = 128
    linear_heads: int = 30          # key heads = value heads
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_width: int = 4
    allow_neg_eigval: bool = True   # beta in (0, 2)
    norm_eps: float = 1e-6
    chunk: int = 64                 # the delta rule's chunk
    dtype: Any = jnp.bfloat16
    # a layer keeps its input and replays itself in the backward
    remat: bool = True

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - {LINEAR, FULL}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(unknown)}; known: "
                             f"{LINEAR!r}, {FULL!r}")

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern that ``layer_types`` repeats."""
        return shortest_period(self.layer_types)

    @property
    def num_periods(self) -> int:
        return len(self.layer_types) // len(self.period)

    # -- the protocol ``bench.train_step_fn`` takes a model by ----------------
    def param_specs(self) -> Pytree:
        return hybrid_param_specs(self)

    def init_params(self, rng) -> Pytree:
        return init_hybrid_params(rng, self)

    def loss(self, params, tokens, targets):
        return hybrid_loss(params, tokens, targets, self)


# ---------------------------------------------------------------------------
# parameters, by layer kind

def _mixer_shapes(cfg: HybridConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    h = cfg.hidden
    if kind == FULL:
        d = cfg.num_heads * cfg.head_dim
        return {"wq": (h, d), "wk": (h, d), "wv": (h, d), "wo": (d, h),
                "q_norm": (d,), "k_norm": (d,)}
    dk = cfg.linear_heads * cfg.linear_key_dim
    dv = cfg.linear_heads * cfg.linear_value_dim
    return {"wq": (h, dk), "wk": (h, dk), "wv": (h, dv), "wg": (h, dv),
            "wb": (h, cfg.linear_heads), "wa": (h, cfg.linear_heads),
            "conv_q": (cfg.conv_width, dk), "conv_k": (cfg.conv_width, dk),
            "conv_v": (cfg.conv_width, dv),
            "A_log": (cfg.linear_heads,), "dt_bias": (cfg.linear_heads,),
            "o_norm": (cfg.linear_value_dim,), "wo": (dv, h)}


def layer_shapes(cfg: HybridConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    """One layer's leaves: the mixer's, the gated FFN's, the two norms'."""
    h, f = cfg.hidden, cfg.ffn_hidden
    return {**_mixer_shapes(cfg, kind), "norm1": (h,),
            "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h), "norm2": (h,)}


_OUT_PROJECTIONS = ("wo", "w_down")
_NORMS = ("norm1", "norm2", "q_norm", "k_norm", "o_norm")


def init_hybrid_params(rng, cfg: HybridConfig) -> Pytree:
    """Normal 0.02, output projections scaled by (2·depth)^-1/2, norm
    weights one, convolutions uniform in ±W^-1/2; the decay's ``A_log`` and
    ``dt_bias`` drawn so that ``alpha`` at a zero input is log-uniform in
    (0.9, 0.999)."""
    out_std = 0.02 / math.sqrt(2.0 * len(cfg.layer_types))
    keys = iter(jax.random.split(rng, 64))

    def leaf(name, shape, layer):
        if name in _NORMS:
            return jnp.ones(shape, cfg.dtype)
        if name.startswith("conv_"):
            bound = cfg.conv_width ** -0.5
            return jax.random.uniform(next(keys), shape, F32, -bound, bound
                                      ).astype(cfg.dtype)
        if name == "A_log":
            return jax.random.uniform(next(keys), shape, F32,
                                      math.log(0.5), math.log(2.0))
        if name == "dt_bias":   # -log(alpha) = exp(A_log) · softplus(dt_bias)
            neg_log_alpha = jnp.exp(jax.random.uniform(
                next(keys), shape, F32,
                math.log(-math.log(0.999)), math.log(-math.log(0.9))))
            sp = neg_log_alpha / jnp.exp(layer["A_log"])
            return sp + jnp.log(-jnp.expm1(-sp))               # softplus^-1
        std = out_std if name in _OUT_PROJECTIONS else 0.02
        return (std * jax.random.normal(next(keys), shape, F32)).astype(cfg.dtype)

    periods = {}
    for kind in dict.fromkeys(cfg.period):
        lead = (cfg.num_periods, cfg.period.count(kind))
        layer = periods[kind] = {}
        for name, shape in layer_shapes(cfg, kind).items():
            layer[name] = leaf(name, lead + shape, layer)
    table = lambda: (0.02 * jax.random.normal(
        next(keys), (cfg.vocab_held, cfg.hidden), F32)).astype(cfg.dtype)
    return {"embed": {"tok": table()}, "periods": periods,
            "head": {"norm": jnp.ones((cfg.hidden,), cfg.dtype), "lm": table()}}


def hybrid_param_specs(cfg: HybridConfig) -> Pytree:
    """Every leaf replicated: ``dp`` splits the batch, and ``tp`` is refused
    (:func:`hybrid_loss`)."""
    return {"embed": {"tok": P()},
            "periods": {kind: {name: P() for name in layer_shapes(cfg, kind)}
                        for kind in dict.fromkeys(cfg.period)},
            "head": {"norm": P(), "lm": P()}}


# ---------------------------------------------------------------------------
# forward (local shards, inside shard_map)

def _heads(x, n: int):
    return x.reshape(*x.shape[:-1], n, x.shape[-1] // n)


def _full_attention(p, x, cfg: HybridConfig):
    b, s, _ = x.shape
    with span("attn/qkv"):
        q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]
    with span("attn/qk_norm"):
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    with span("attn/core"):
        q, k, v = (_heads(a, cfg.num_heads).transpose(0, 2, 1, 3)
                   for a in (q, k, v))
        ctx = flash_attention(q, k, v, causal=True)
    with span("attn/out"):
        return ctx.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def _linear_attention(p, x, cfg: HybridConfig):
    b, s, _ = x.shape
    n, dk = cfg.linear_heads, cfg.linear_key_dim
    with span("linattn/proj"):
        q, k, v, gate = x @ p["wq"], x @ p["wk"], x @ p["wv"], x @ p["wg"]
        beta = jax.nn.sigmoid((x @ p["wb"]).astype(F32))
        if cfg.allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            (x @ p["wa"]).astype(F32) + p["dt_bias"])
    with span("linattn/conv"):
        q, k, v = (jax.nn.silu(causal_conv1d(a, w).astype(F32))
                   for a, w in ((q, p["conv_q"]), (k, p["conv_k"]),
                                (v, p["conv_v"])))
        q = (l2_normalize(_heads(q, n)) * dk ** -0.5).astype(x.dtype)
        k = l2_normalize(_heads(k, n)).astype(x.dtype)
        v = _heads(v, n).astype(x.dtype)
    with span("linattn/core"):
        o = gated_delta_rule(q, k, v, g, beta, chunk=cfg.chunk)
    with span("linattn/gate_norm"):
        y = gated_rms_norm(o, _heads(gate, n), p["o_norm"], cfg.norm_eps)
    with span("linattn/out"):
        return y.reshape(b, s, -1) @ p["wo"]


def gated_ffn(x, w_gate, w_up, w_down, scope: str = "mlp"):
    """``(SiLU(x W_gate) * x W_up) W_down``, the gate in float32, under the
    scopes ``<scope>/gate_up``, ``<scope>/act`` and ``<scope>/down``."""
    with span(scope + "/gate_up"):
        gate, up = x @ w_gate, x @ w_up
    with span(scope + "/act"):
        y = (jax.nn.silu(gate.astype(F32)) * up.astype(F32)).astype(x.dtype)
    with span(scope + "/down"):
        return y @ w_down


def grouped_attention(p, a, *, heads: int, kv_heads: int, head_dim: int,
                      scope: str, structure, block: int = 512, rope=None,
                      qk_norm_eps: Optional[float] = None, gate: bool = False):
    """Softmax attention over ``a`` (rows, seq, hidden), a sublayer's normed
    input: ``q = a W_q`` (``heads`` of ``head_dim``), ``k = a W_k``, ``v = a
    W_v`` (``kv_heads``); with ``qk_norm_eps`` q and k RMS-normalised over
    each head (``q_norm``, ``k_norm``); with ``rope = (theta, scaling,
    rotary_dim)`` both rotated in ``rotate_half`` form (``ops/rope.py``;
    ``rotary_dim`` None for the whole head), without it no positional
    encoding; ``o = softmax(q k^T head_dim^-1/2 + mask) v`` on the flash
    kernels, ``structure`` the mask (``ops.attention.CAUSAL``,
    ``sliding_window_mask``) on tiles of ``block``, query head ``i`` reading
    K/V head ``i // group`` (never repeated); with ``gate`` each head's
    ``o`` times ``sigmoid(a W_g)``; then ``W_o``. Under the scopes
    ``<scope>/{qkv, qk_norm, rope, core, gate, out}`` that run."""
    b, s, _ = a.shape
    n, kv, d = heads, kv_heads, head_dim
    by_head = lambda t, m: t.reshape(b, s, m, d)
    with span(scope + "/qkv"):
        q, k, v = a @ p["wq"], a @ p["wk"], a @ p["wv"]
        if qk_norm_eps is None:
            q, k = by_head(q, n), by_head(k, kv)
    if qk_norm_eps is not None:
        with span(scope + "/qk_norm"):
            # a head's width at a time: XLA's fusion, not the row kernel
            q = rms_norm(by_head(q, n), p["q_norm"], qk_norm_eps,
                         use_pallas=False)
            k = rms_norm(by_head(k, kv), p["k_norm"], qk_norm_eps,
                         use_pallas=False)
    if rope is not None:
        with span(scope + "/rope"):
            theta, scaling, rotary_dim = rope
            positions = jnp.arange(s, dtype=jnp.int32)
            turn = lambda t: apply_rotary(t.transpose(0, 2, 1, 3), positions,
                                          theta, scaling, False, rotary_dim)
            q, k = turn(q), turn(k)
    with span(scope + "/core"):
        if rope is None:
            q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
        v = by_head(v, kv).transpose(0, 2, 1, 3)
        ctx = flash_attention(q, k, v, scale=d ** -0.5, block_q=block,
                              block_k=block, structure=structure)
    if gate:
        with span(scope + "/gate"):
            g = jax.nn.sigmoid((a @ p["wg"]).astype(F32))
            ctx = (ctx.transpose(0, 2, 1, 3).astype(F32) * g[..., None]
                   ).astype(a.dtype)
    else:
        ctx = ctx.transpose(0, 2, 1, 3)
    with span(scope + "/out"):
        return ctx.reshape(b, s, n * d) @ p["wo"]


def _mlp(p, x):
    return gated_ffn(x, p["w_gate"], p["w_up"], p["w_down"])


def _layer(p, x, cfg: HybridConfig, kind: str):
    mixer = _full_attention if kind == FULL else _linear_attention
    m = mixer(p, x, cfg)
    with span("post_norm"):
        m = rms_norm(m, p["norm1"], cfg.norm_eps)
    with span("residual"):
        x = x + m
    m = _mlp(p, x)
    with span("post_norm"):
        m = rms_norm(m, p["norm2"], cfg.norm_eps)
    with span("residual"):
        return x + m


def shortest_period(types: Tuple[str, ...]) -> Tuple[str, ...]:
    """The shortest pattern that ``types`` repeats whole."""
    for n in range(1, len(types) + 1):
        if len(types) % n == 0 and types == types[:n] * (len(types) // n):
            return types[:n]


def _refuse_tp():
    refuse_tp("the hybrid model's mixers are",
              "the delta rule's heads, its convolutions and the QK-norm over "
              "the whole projection would each need a split of their own")


def run_stack(x, periods, period: Tuple[str, ...], layer, remat: bool):
    """The stack every model kept by layer kind shares: a ``lax.scan`` over
    ``periods`` (``{kind: {leaf: (periods, that kind's layers in a period,
    ...)}}``) whose body writes one period's layers out in the order of
    ``period``, each ``layer(kind, leaves, h) -> h`` under the scope
    ``layer`` and, with ``remat``, under ``jax.checkpoint``."""
    def layer_fn(kind):
        fn = lambda lp, h: layer(kind, lp, h)
        return jax.checkpoint(fn) if remat else fn

    fns = {kind: layer_fn(kind) for kind in dict.fromkeys(period)}

    def body(h, leaves):
        seen = dict.fromkeys(fns, 0)
        for kind in period:
            lp = jax.tree.map(lambda a: a[seen[kind]], leaves[kind])
            seen[kind] += 1
            with span("layer"):
                h = fns[kind](lp, h)
        return h, None

    x, _ = lax.scan(body, x, periods)
    return x


# the flash kernel's output and row statistics (``ops/attention.py`` names
# them): kept, an attention sublayer's replay runs its projections and the
# rotation again and not the kernel
ATTN_KEPT = ("attn_out", "attn_lse")


def sublayer_remat(remat: str, keep=()):
    """What a sublayer runs under: with ``remat`` "sublayer" a checkpoint of
    its own that keeps its input and the arrays named ``keep``."""
    if remat != "sublayer":
        return lambda f: f
    policy = jax.checkpoint_policies.save_only_these_names(*keep)
    return lambda f: jax.checkpoint(f, policy=policy)


def append_row(counted, row):
    """The stack's counters over a layer: each array of ``counted`` (layers,
    ...) takes this layer's ``row`` at its end and drops its first, so that
    after the last layer its rows are the layers' in order."""
    return jax.tree.map(lambda all_, r: jnp.concatenate([all_[1:], r[None]]),
                        counted, row)


def refuse_tp(what: str, why: str):
    try:
        tp = lax.axis_size(TP_AXIS)
    except NameError:
        return
    if tp > 1:
        raise NotImplementedError(
            f"{what} not written for tensor parallelism (tp = {tp}): "
            f"{why}. Use dp, or tp = 1.")


def hybrid_hidden(params, tokens, cfg: HybridConfig):
    """tokens (rows, seq) -> the last layer's output (rows, seq, hidden)."""
    _refuse_tp()
    with span("embed"):
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    return run_stack(x, params["periods"], cfg.period,
                     lambda kind, lp, h: _layer(lp, h, cfg, kind), cfg.remat)


def hybrid_loss(params, tokens, targets, cfg: HybridConfig):
    """Mean cross entropy of the next token over the rows of the vocabulary
    held; the logits are never materialised."""
    x = hybrid_hidden(params, tokens, cfg)
    with span("final_norm"):
        x = rms_norm(x, params["head"]["norm"], cfg.norm_eps)
    with span("lm_head_loss"):
        return jnp.mean(lm_head_loss(x, pvary_like(params["head"]["lm"], x),
                                     targets))


def hybrid_logits(params, tokens, cfg: HybridConfig):
    """float32 logits over the rows held (tests; training never forms them)."""
    x = hybrid_hidden(params, tokens, cfg)
    x = rms_norm(x, params["head"]["norm"], cfg.norm_eps)
    return jnp.einsum("bsh,vh->bsv", x.astype(F32),
                      params["head"]["lm"].astype(F32))
