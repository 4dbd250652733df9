"""The benchmark's own weights for DeepSeek-V2's decoder, made on the device
from the seed.

One jitted call makes the whole parameter tree in the type it is trained in
and in the layout the program's entry point takes: ``{"embed": {"tok"},
"periods": {<layer kind>: {... each leaf stacked over (that kind's layers, 1)
...}}, "head": {"norm", "lm"}}``, the kinds being the leading dense layers and
the expert layers. Matrices are normal 0.02, output projections (``wo``,
``w_down``, ``shared_down``) scaled by (2 x the published depth)^-1/2, norm
weights get small noise round one so that a fault in how they are used
shows, and **the embedding's rows are normal ``assumed.embedding_std``
(1.0)**: with the table at 0.02 the residual after the first mixer is one
direction common to all tokens, a random router collapses onto a few experts
and the held experts' load swings from seed to seed (PERF.md §6, PR 33 (a)).
At 1.0 a position's own embedding dominates its residual; the batch's ids are
uniform over the rows held and no token is special, so the loads stay near
uniform with no placement or pin (``sdar-30b-a3b``'s mask token needed both).
The seed is a traced argument: every seed runs the same compiled program.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Mapping

import jax
import jax.numpy as jnp

from weights import _Hashable, seed_words

F32 = jnp.float32
DENSE = "mla_dense"
EXPERTS = "mla_experts"
OUTPUTS = ("wo", "w_down", "shared_down")


def layer_shapes(config: Mapping, kind: str) -> dict:
    """One layer's leaves by name, from the configuration's file."""
    h, n = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    rank = config["kv_lora_rank"]
    attn = {"norm1": (h,), "wq": (h, n * (nope + rope)), "wkv_a": (h, rank + rope),
            "kv_norm": (rank,), "wkv_b": (rank, n * (nope + dv)), "wo": (n * dv, h),
            "norm2": (h,)}
    if kind == DENSE:
        f = config["intermediate_size"]
        return {**attn, "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}
    f, held = config["moe_intermediate_size"], config["n_routed_experts"]
    shared = config["n_shared_experts"] * f
    return {**attn, "router": (h, config["reduced_from"]["n_routed_experts"]),
            "w_gate": (held, h, f), "w_up": (held, h, f), "w_down": (held, f, h),
            "shared_gate": (h, shared), "shared_up": (h, shared), "shared_down": (shared, h)}


def layers_of(config: Mapping) -> dict:
    dense = config["first_k_dense_replace"]
    return {DENSE: dense, EXPERTS: config["num_hidden_layers"] - dense}


def _make(lo, hi, *, config):
    dtype = jnp.dtype(config["assumed"]["param_dtype"])
    out_std = 0.02 / math.sqrt(2.0 * config["reduced_from"]["num_hidden_layers"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    ks = iter(jax.random.split(key, 64))
    normal = lambda shape, std, mean=0.0: (
        mean + std * jax.random.normal(next(ks), shape, F32)).astype(dtype)
    periods = {}
    for kind, n in layers_of(config).items():
        periods[kind] = {
            name: (normal((n, 1) + shape, 0.02, 1.0) if "norm" in name else
                   normal((n, 1) + shape, out_std if name in OUTPUTS else 0.02))
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


_KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "kv_lora_rank", "intermediate_size", "moe_intermediate_size",
         "n_routed_experts", "n_shared_experts", "first_k_dense_replace", "num_hidden_layers",
         "vocab_size", "assumed", "reduced_from")


def make_params(config: Mapping, seed: int, shardings=None, _cache={}):
    """The parameter tree of ``config`` (a configuration file's dict) from
    ``seed``. ``shardings`` (a tree of ``NamedSharding``) places each leaf."""
    key = None
    if shardings is not None:
        key = _cache.setdefault(id(shardings), _Hashable(shardings))
    as_key = json.dumps({k: config[k] for k in _KEYS}, sort_keys=True)
    return _jitted(as_key, key)(*seed_words(seed))
