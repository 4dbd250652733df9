"""The benchmark's own weights for the hybrid decoder, made on the device from
the seed.

One jitted call makes the whole parameter tree in the type it is trained in
and in the layout the program's entry point takes: ``{"embed": {"tok"},
"periods": {<layer kind>: {... each leaf stacked over (periods, that kind's
layers in a period) ...}}, "head": {"norm", "lm"}}``. Matrices are normal
0.02, output projections scaled by (2 x the published depth)^-1/2; the
convolutions are uniform in +-W^-1/2; norm weights get small noise round one
so that a fault in how they are used shows; the decay's ``A_log`` is uniform
in (log 1/2, log 2) and ``dt_bias`` is set beside it so that ``alpha`` at a
zero input is log-uniform in (0.9, 0.999) (both float32). The seed is a traced
argument: every seed runs the same compiled program.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Mapping

import jax
import jax.numpy as jnp

from counts_hybrid import FULL, layers_held, period_of
from weights import _Hashable, seed_words

F32 = jnp.float32


def layer_shapes(config: Mapping, kind: str) -> dict:
    """One layer's leaves by name, from the configuration's file."""
    h, f = config["hidden_size"], config["intermediate_size"]
    if kind == FULL:
        d = config["num_attention_heads"] * config["assumed"]["head_dim"]
        mixer = {"wq": (h, d), "wk": (h, d), "wv": (h, d), "wo": (d, h),
                 "q_norm": (d,), "k_norm": (d,)}
    else:
        n, w = config["linear_num_value_heads"], config["linear_conv_kernel_dim"]
        dk, dv = n * config["linear_key_head_dim"], n * config["linear_value_head_dim"]
        mixer = {"wq": (h, dk), "wk": (h, dk), "wv": (h, dv), "wg": (h, dv),
                 "wb": (h, n), "wa": (h, n), "conv_q": (w, dk), "conv_k": (w, dk),
                 "conv_v": (w, dv), "A_log": (n,), "dt_bias": (n,),
                 "o_norm": (config["linear_value_head_dim"],), "wo": (dv, h)}
    return {**mixer, "norm1": (h,), "w_gate": (h, f), "w_up": (h, f),
            "w_down": (f, h), "norm2": (h,)}


def _make(lo, hi, *, config):
    dtype = jnp.dtype(config["assumed"]["param_dtype"])
    out_std = 0.02 / math.sqrt(2.0 * config["reduced_from"]["num_hidden_layers"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    ks = iter(jax.random.split(key, 64))
    normal = lambda shape, std, mean=0.0: (
        mean + std * jax.random.normal(next(ks), shape, F32)).astype(dtype)
    uniform = lambda shape, lo, hi: jax.random.uniform(next(ks), shape, F32, lo, hi)

    period = period_of(layers_held(config))
    periods = {}
    for kind in dict.fromkeys(period):
        lead = (config["num_hidden_layers"] // len(period), period.count(kind))
        tree = {}
        for name, shape in layer_shapes(config, kind).items():
            shape = lead + shape
            if "norm" in name:
                tree[name] = normal(shape, 0.02, 1.0)
            elif name.startswith("conv_"):
                bound = shape[-2] ** -0.5
                tree[name] = uniform(shape, -bound, bound).astype(dtype)
            elif name == "A_log":
                tree[name] = uniform(shape, math.log(0.5), math.log(2.0))
            elif name == "dt_bias":
                # -log(alpha) = exp(A_log) · softplus(dt_bias)
                neg_log_alpha = jnp.exp(uniform(shape, math.log(-math.log(0.999)),
                                                math.log(-math.log(0.9))))
                sp = neg_log_alpha / jnp.exp(tree["A_log"])
                tree[name] = sp + jnp.log(-jnp.expm1(-sp))      # softplus^-1
            else:
                tree[name] = normal(shape, out_std if name in ("wo", "w_down") else 0.02)
        periods[kind] = tree
    table = (config["vocab_size"], config["hidden_size"])
    return {"embed": {"tok": normal(table, 0.02)}, "periods": periods,
            "head": {"norm": normal(table[1:], 0.02, 1.0), "lm": normal(table, 0.02)}}


@functools.lru_cache(maxsize=None)
def _jitted(config_json: str, shardings):
    fn = functools.partial(_make, config=json.loads(config_json))
    if shardings is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=shardings.tree)


_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers", "vocab_size",
         "num_attention_heads", "linear_num_value_heads", "linear_key_head_dim",
         "linear_value_head_dim", "linear_conv_kernel_dim", "layer_types",
         "assumed", "reduced_from")


def make_params(config: Mapping, seed: int, shardings=None, _cache={}):
    """The parameter tree of ``config`` (a configuration file's dict) from
    ``seed``. ``shardings`` (a tree of ``NamedSharding``) places each leaf."""
    key = None
    if shardings is not None:
        key = _cache.setdefault(id(shardings), _Hashable(shardings))
    as_key = json.dumps({k: config[k] for k in _KEYS}, sort_keys=True)
    return _jitted(as_key, key)(*seed_words(seed))
