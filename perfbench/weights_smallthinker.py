"""The benchmark's own weights for SmallThinker's decoder, made on the device
from the seed.

One jitted call makes the whole parameter tree in the type it is trained in
and in the layout the program's entry point takes: ``{"embed": {"tok"},
"periods": {<type>: {... each leaf stacked over (periods, that type's layers
in a period) ...}}, "head": {"norm", "lm"}}``, the types being ``full``
(``sliding_window_layout`` 0) and ``window`` (1). Matrices are normal 0.02,
output projections (``wo``, ``w_down``) scaled by (2 x the published
depth)^-1/2, norm weights get small noise round one so that a fault in how
they are used shows, and the embedding's rows are normal
``assumed.embedding_std`` (1.0), as ``weights_laguna.py`` makes them and for
the same reason: the routers' loads stay near a uniform router's. The seed
is a traced argument: every seed runs the same compiled program.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from weights import _Hashable, seed_words

F32 = jnp.float32
WINDOW, FULL = "window", "full"
OUTPUTS = ("wo", "w_down")


def layer_types(config: Mapping) -> Tuple[str, ...]:
    """The layers held, in order, from ``sliding_window_layout``."""
    layout = config["sliding_window_layout"][:config["num_hidden_layers"]]
    return tuple(WINDOW if w else FULL for w in layout)


def period_of(config: Mapping) -> Tuple[str, ...]:
    """The shortest pattern the held layers' types repeat."""
    types = layer_types(config)
    for n in range(1, len(types) + 1):
        if len(types) % n == 0 and types == types[:n] * (len(types) // n):
            return types[:n]


def router_width(config: Mapping) -> int:
    """The router's outputs: the published expert count."""
    return config["reduced_from"]["moe_num_primary_experts"]


def layer_shapes(config: Mapping) -> dict:
    """One layer's leaves by name (both types alike), from the file."""
    h, d = config["hidden_size"], config["head_dim"]
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    f, held = config["moe_ffn_hidden_size"], config["moe_num_primary_experts"]
    return {"norm1": (h,), "wq": (h, n * d), "wk": (h, kv * d), "wv": (h, kv * d),
            "wo": (n * d, h), "norm2": (h,), "router": (h, router_width(config)),
            "w_gate": (held, h, f), "w_up": (held, h, f), "w_down": (held, f, h)}


def stacked(config: Mapping) -> Dict[str, Tuple[int, int]]:
    """Each type's leading axes: (periods, that type's layers in a period)."""
    period = period_of(config)
    periods = config["num_hidden_layers"] // len(period)
    return {kind: (periods, period.count(kind)) for kind in dict.fromkeys(period)}


def _make(lo, hi, *, config):
    dtype = jnp.dtype(config["assumed"]["param_dtype"])
    out_std = 0.02 / math.sqrt(2.0 * config["reduced_from"]["num_hidden_layers"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    ks = iter(jax.random.split(key, 64))
    normal = lambda shape, std, mean=0.0: (
        mean + std * jax.random.normal(next(ks), shape, F32)).astype(dtype)
    periods = {}
    for kind, lead in stacked(config).items():
        periods[kind] = {
            name: (normal(lead + shape, 0.02, 1.0) if "norm" in name else
                   normal(lead + shape, out_std if name in OUTPUTS else 0.02))
            for name, shape in layer_shapes(config).items()}
    table = (config["vocab_size"], config["hidden_size"])
    return {"embed": {"tok": normal(table, float(config["assumed"]["embedding_std"]))},
            "periods": periods,
            "head": {"norm": normal(table[1:], 0.02, 1.0), "lm": normal(table, 0.02)}}


@functools.lru_cache(maxsize=None)
def _jitted(config_json: str, shardings):
    fn = functools.partial(_make, config=json.loads(config_json))
    if shardings is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=shardings.tree)


_KEYS = ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
         "sliding_window_layout", "moe_ffn_hidden_size", "moe_num_primary_experts",
         "num_hidden_layers", "vocab_size", "assumed", "reduced_from")


def make_params(config: Mapping, seed: int, shardings=None, _cache={}):
    """The parameter tree of ``config`` (a configuration file's dict) from
    ``seed``. ``shardings`` (a tree of ``NamedSharding``) places each leaf."""
    key = None
    if shardings is not None:
        key = _cache.setdefault(id(shardings), _Hashable(shardings))
    as_key = json.dumps({k: config[k] for k in _KEYS}, sort_keys=True)
    return _jitted(as_key, key)(*seed_words(seed))
