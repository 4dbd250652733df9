"""The benchmark's own weights, made on the device from the seed.

One jitted call makes the whole parameter tree in the type it is trained and
served in (bfloat16), in the layout the program's entry points take:
``{"embed": {"tok", "pos"}, "layers": {... stacked over n_layer ...},
"head": {"ln_w", "ln_b"}}``, QKV columns packed (head, {q, k, v}, head_dim).
Matrices follow GPT-2's and Megatron's rule (normal 0.02, output projections
scaled by 1/sqrt(2·n_layer)); biases and LayerNorm parameters get small noise
too, so that a fault in how they are used shows in the comparison. The seed is
a traced argument: every seed runs the same compiled program.
"""

from __future__ import annotations

import functools
import math
from typing import Mapping

import jax
import jax.numpy as jnp


def seed_words(seed: int):
    """A seed of any size as two uint32 words."""
    seed = int(seed)
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def _make(lo, hi, *, vocab, n_pos, h, L, dtype):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    ks = iter(jax.random.split(key, 24))
    out_std = 0.02 / math.sqrt(2.0 * L)

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    layers = {
        "ln1_w": normal((L, h), 0.02, 1.0), "ln1_b": normal((L, h), 0.02),
        "qkv_kernel": normal((L, h, 3 * h), 0.02),
        "qkv_bias": normal((L, 3 * h), 0.02),
        "out_kernel": normal((L, h, h), out_std),
        "out_bias": normal((L, h), 0.02),
        "ln2_w": normal((L, h), 0.02, 1.0), "ln2_b": normal((L, h), 0.02),
        "fc1_kernel": normal((L, h, 4 * h), 0.02),
        "fc1_bias": normal((L, 4 * h), 0.02),
        "fc2_kernel": normal((L, 4 * h, h), out_std),
        "fc2_bias": normal((L, h), 0.02),
    }
    return {
        "embed": {"tok": normal((vocab, h), 0.02), "pos": normal((n_pos, h), 0.02)},
        "layers": layers,
        "head": {"ln_w": normal((h,), 0.02, 1.0), "ln_b": normal((h,), 0.02)},
    }


@functools.lru_cache(maxsize=None)
def _jitted(vocab, n_pos, h, L, dtype_name, shardings):
    fn = functools.partial(_make, vocab=vocab, n_pos=n_pos, h=h, L=L,
                           dtype=jnp.dtype(dtype_name))
    if shardings is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=shardings.tree)


class _Hashable:
    """A pytree of shardings as a cache key (by identity)."""

    def __init__(self, tree):
        self.tree = tree


def make_params(config: Mapping, seed: int, shardings=None, _cache={}):
    """The parameter tree of ``config`` (a configuration file's dict) from
    ``seed``. ``shardings`` (a tree of ``NamedSharding``) places each leaf."""
    key = None
    if shardings is not None:
        key = _cache.setdefault(id(shardings), _Hashable(shardings))
    fn = _jitted(config["assumed"]["padded_vocab_size"], config["n_positions"],
                 config["n_embd"], config["n_layer"],
                 config["assumed"]["param_dtype"], key)
    return fn(*seed_words(seed))
