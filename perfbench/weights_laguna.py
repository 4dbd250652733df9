"""The benchmark's own weights for Laguna's decoder, made on the device from
the seed.

One jitted call makes the whole parameter tree in the type it is trained in
and in the layout the program's entry point takes: ``{"embed": {"tok"},
"periods": {<kind>: {... each leaf stacked over (periods, that kind's layers
in a period) ...}}, "head": {"norm", "lm"}}``, the kinds being the leading
dense layers (stacked over (dense layers, 1)) and each layer type of the
expert layers' period. Matrices are normal 0.02, output projections (``wo``,
``w_down``, ``shared_down``) scaled by (2 x the published depth)^-1/2, norm
weights get small noise round one so that a fault in how they are used
shows, and the embedding's rows are normal ``assumed.embedding_std`` (1.0),
as ``weights_dsv2.py`` makes them and for the same reason: the routers' loads
stay near a uniform router's. The seed is a traced argument: every seed runs
the same compiled program.
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
DENSE = "laguna_dense"
SLIDING, FULL = "sliding_attention", "full_attention"
OUTPUTS = ("wo", "w_down", "shared_down")


def dense_layers(config: Mapping) -> int:
    return len(config["mlp_only_layers"])


def period_of(config: Mapping) -> Tuple[str, ...]:
    """The shortest pattern the held expert layers' types repeat."""
    types = tuple(config["layer_types"][dense_layers(config):config["num_hidden_layers"]])
    for n in range(1, len(types) + 1):
        if len(types) % n == 0 and types == types[:n] * (len(types) // n):
            return types[:n]


def heads_of(config: Mapping, kind: str) -> int:
    """The query heads of a layer type (``num_attention_heads_per_layer``)."""
    kind = FULL if kind == DENSE else kind
    types, heads = config["layer_types"], config["num_attention_heads_per_layer"]
    return next(n for t, n in zip(types, heads) if t == kind)


def layer_shapes(config: Mapping, kind: str) -> dict:
    """One layer's leaves by name, from the configuration's file."""
    h, d, kv = config["hidden_size"], config["head_dim"], config["num_key_value_heads"]
    n = heads_of(config, kind)
    attn = {"norm1": (h,), "wq": (h, n * d), "wk": (h, kv * d), "wv": (h, kv * d),
            "q_norm": (d,), "k_norm": (d,), "wg": (h, n), "wo": (n * d, h), "norm2": (h,)}
    if kind == DENSE:
        f = config["intermediate_size"]
        return {**attn, "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}
    f, held, shared = (config["moe_intermediate_size"], config["num_experts"],
                       config["shared_expert_intermediate_size"])
    return {**attn, "router": (h, config["reduced_from"]["num_experts"]),
            "w_gate": (held, h, f), "w_up": (held, h, f), "w_down": (held, f, h),
            "shared_gate": (h, shared), "shared_up": (h, shared), "shared_down": (shared, h),
            "shared_expert_gate": (h, 1)}


def stacked(config: Mapping) -> Dict[str, Tuple[int, int]]:
    """Each kind's leading axes: (dense layers, 1), and (periods, that type's
    layers in a period)."""
    period = period_of(config)
    periods = (config["num_hidden_layers"] - dense_layers(config)) // len(period)
    return {DENSE: (dense_layers(config), 1),
            **{kind: (periods, period.count(kind)) for kind in dict.fromkeys(period)}}


def _make(lo, hi, *, config):
    dtype = jnp.dtype(config["assumed"]["param_dtype"])
    out_std = 0.02 / math.sqrt(2.0 * config["reduced_from"]["num_hidden_layers"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    ks = iter(jax.random.split(key, 96))
    normal = lambda shape, std, mean=0.0: (
        mean + std * jax.random.normal(next(ks), shape, F32)).astype(dtype)
    periods = {}
    for kind, lead in stacked(config).items():
        periods[kind] = {
            name: (normal(lead + shape, 0.02, 1.0) if "norm" in name else
                   normal(lead + shape, out_std if name in OUTPUTS else 0.02))
            for name, shape in layer_shapes(config, kind).items()}
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


_KEYS = ("hidden_size", "head_dim", "num_key_value_heads", "num_attention_heads_per_layer",
         "layer_types", "mlp_only_layers", "intermediate_size", "moe_intermediate_size",
         "shared_expert_intermediate_size", "num_experts", "num_hidden_layers", "vocab_size",
         "assumed", "reduced_from")


def make_params(config: Mapping, seed: int, shardings=None, _cache={}):
    """The parameter tree of ``config`` (a configuration file's dict) from
    ``seed``. ``shardings`` (a tree of ``NamedSharding``) places each leaf."""
    key = None
    if shardings is not None:
        key = _cache.setdefault(id(shardings), _Hashable(shardings))
    as_key = json.dumps({k: config[k] for k in _KEYS}, sort_keys=True)
    return _jitted(as_key, key)(*seed_words(seed))
