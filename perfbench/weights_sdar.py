"""The benchmark's own weights for the block-diffusion decoder, made on the
device from the seed.

One jitted call makes the whole parameter tree in the type it is trained in
and in the layout the program's entry point takes: ``{"embed": {"tok"},
"periods": {<layer kind>: {... each leaf stacked over (layers, 1) ...}},
"head": {"norm", "lm"}}``. Matrices are normal 0.02, output projections
(``wo``, ``w_down``) scaled by (2 x the published depth)^-1/2, norm weights
get small noise round one so that a fault in how they are used shows, and
**the embedding's rows are normal ``assumed.embedding_std`` (1.0)**: with the
table at 0.02 the residual after the first mixer is one direction common to
all tokens, a random router collapses onto a few experts and the held
experts' load swings from seed to seed.

**Expert placement** (``assumed.expert_placement``): half of the noised copy's
positions hold the one mask token, so a quarter of all positions carry the
same residual into every layer's router and choose the same eight experts.
How many of those eight lie among the 32 held here would be the draw's luck
(none to five), and with it the held pairs a step (53 k to 90 k a layer) and
the step's time. A deployment places experts on chips to even out such load;
the benchmark's weights do it at the source: each layer's experts are
numbered over the four chips in turn by their affinity to the mask token
(the router's columns sorted by ``RMSNorm(embedding[mask_id]) . W_r`` and
dealt out there and back: a renumbering of experts, which the model is
symmetric under), so two of the mask token's eight experts are held here, on
every seed. And the mask token's choice is **pinned for the run**
(``MASK_GAP``): its eight experts' logits are lifted together until the eighth
leads the ninth by eight logits (each of those columns moved along the one
direction the mask token's residual has, by about a fifth of its length).
Without it the run measures a tie and its drift: (1) a quarter of all
positions sit on whatever tie the draw left between the eighth and the ninth
expert, and the context's few percent, or bfloat16 against float32, tips
thousands of positions at once from one expert to another, so the comparison
with the reference reads the tie, not the program; (2) Adam moves every
router weight by ``lr`` a step with a sign that the 8,200 masked positions
agree on, which is 0.16 logits a step for each chosen expert, and a random
router's order (logits of standard deviation 0.9) is reshuffled within five
steps: measured on the chip, the mask token's experts held here went from two
to five by the job's sixth step and the step from 1.383 to 1.456 s as a
second pass over the buffer began, and six seeds' ``train_tokens_per_s``
spread by 3.0% (PERF.md §6). Eight logits outlast the 35 steps of a run.
**What the pin hides**: the cell then reads a load that does not drift (one
pass over the buffer in every layer of every step), so what an imbalance
costs, the second pass's 5% of the step, is not in its number; the run
prints ``passes_run`` and ``tiled_rows_most`` of its own steps, so a run in
which the load did move says so. And the first step's gradients no longer
tell a router whose weights are not renormalised (the chosen scores of the
masked positions sum to 0.999): two Adam steps do (``update_norm_gap``).
The seed is a traced argument: every seed runs the same compiled
program.
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
LAYER = "blockdiff_attention_experts"


def layer_shapes(config: Mapping) -> dict:
    """One layer's leaves by name, from the configuration's file."""
    h, d, f = config["hidden_size"], config["head_dim"], config["moe_intermediate_size"]
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    held, routed = config["num_experts"], config["reduced_from"]["num_experts"]
    return {"norm1": (h,), "wq": (h, nq * d), "wk": (h, nkv * d), "wv": (h, nkv * d),
            "q_norm": (d,), "k_norm": (d,), "wo": (nq * d, h), "norm2": (h,),
            "router": (h, routed), "w_gate": (held, h, f), "w_up": (held, h, f),
            "w_down": (held, f, h)}


def _make(lo, hi, *, config):
    dtype = jnp.dtype(config["assumed"]["param_dtype"])
    out_std = 0.02 / math.sqrt(2.0 * config["reduced_from"]["num_hidden_layers"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    ks = iter(jax.random.split(key, 32))
    normal = lambda shape, std, mean=0.0: (
        mean + std * jax.random.normal(next(ks), shape, F32)).astype(dtype)
    lead = (config["num_hidden_layers"], 1)
    held, routed = config["num_experts"], config["reduced_from"]["num_experts"]
    layer = {}
    for name, shape in layer_shapes(config).items():
        if "norm" in name:
            layer[name] = normal(lead + shape, 0.02, 1.0)
        else:
            layer[name] = normal(lead + shape, out_std if name in ("wo", "w_down") else 0.02)
    table = (config["vocab_size"], config["hidden_size"])
    embed = normal(table, float(config["assumed"]["embedding_std"]))
    layer["router"] = _place_experts(layer["router"], layer["norm2"],
                                     embed[config["assumed"]["mask_id"]],
                                     routed // held, float(config["rms_norm_eps"]),
                                     config["num_experts_per_tok"])
    return {"embed": {"tok": embed}, "periods": {LAYER: layer},
            "head": {"norm": normal(table[1:], 0.02, 1.0), "lm": normal(table, 0.02)}}


MASK_GAP = 8.0      # in logits; theirs have a standard deviation of about 0.9


def _place_experts(router, norm2, mask_row, chips: int, eps: float, top_k: int):
    """Each layer's router with its columns (experts) renumbered: the expert
    of rank ``j`` in affinity to the mask token becomes number
    ``chip(j) * (experts // chips) + j // chips``, the ranks dealt over the
    chips' ranges there and back. router (layers, 1, hidden, experts)."""
    u = mask_row.astype(F32)
    u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u)) + eps)
    seen = u * norm2[:, 0].astype(F32)                       # what the router reads: (layers, hidden)
    logits = jnp.einsum("lh,lhe->le", seen, router[:, 0].astype(F32),
                        precision=jax.lax.Precision.HIGHEST)
    by_rank = jnp.argsort(-logits, axis=-1)                  # rank j -> old number
    # the mask token's choice is pinned (the module's docstring): its top_k
    # experts' logits are lifted together until the last of them leads the
    # next expert by MASK_GAP, each column moved along ``seen`` alone
    ranked = jnp.take_along_axis(logits, by_rank, axis=-1)
    lift = jnp.maximum(0.0, MASK_GAP - (ranked[:, top_k - 1] - ranked[:, top_k]))
    chosen = jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], by_rank[:, :top_k]].set(1.0)
    step = (lift / jnp.sum(jnp.square(seen), axis=-1))[:, None, None] * seen[:, :, None]
    router = (router.astype(F32) + (step * chosen[:, None, :])[:, None]).astype(router.dtype)
    n = router.shape[-1]
    rank = jnp.arange(n)
    # dealt out there and back (0 1 2 3 3 2 1 0 0 1 ...): the eighth and the
    # ninth in rank lie on one chip, so which of the two a position's top 8
    # ends on moves no chip's load
    chip = jnp.where((rank // chips) % 2 == 0, rank % chips, chips - 1 - rank % chips)
    new_number = chip * (n // chips) + rank // chips
    old_of_new = jnp.zeros_like(by_rank).at[:, new_number].set(by_rank)
    return jnp.take_along_axis(router, old_of_new[:, None, None, :], axis=-1)


@functools.lru_cache(maxsize=None)
def _jitted(config_json: str, shardings):
    fn = functools.partial(_make, config=json.loads(config_json))
    if shardings is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=shardings.tree)


_KEYS = ("hidden_size", "head_dim", "moe_intermediate_size", "num_attention_heads",
         "num_key_value_heads", "num_experts", "num_hidden_layers", "vocab_size", "rms_norm_eps",
         "num_experts_per_tok",
         "assumed", "reduced_from")


def make_params(config: Mapping, seed: int, shardings=None, _cache={}):
    """The parameter tree of ``config`` (a configuration file's dict) from
    ``seed``. ``shardings`` (a tree of ``NamedSharding``) places each leaf."""
    key = None
    if shardings is not None:
        key = _cache.setdefault(id(shardings), _Hashable(shardings))
    as_key = json.dumps({k: config[k] for k in _KEYS}, sort_keys=True)
    return _jitted(as_key, key)(*seed_words(seed))
