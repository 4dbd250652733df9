"""Mixture-of-Experts layer with expert parallelism (EP) — TPU-native.

The reference has no MoE/expert parallelism (SURVEY §2.3 marks EP "not
present"); this module is the north-star extension that completes the
parallelism checklist alongside ring/Ulysses sequence parallelism. The
design follows the GShard/Switch capacity-factor formulation, built the
TPU way:

* **Static shapes everywhere.** Token→expert assignment uses a fixed
  per-expert capacity ``C``; overflowing tokens are dropped from the expert
  path (their output is the zero vector, so the surrounding residual
  connection passes them through unchanged). No dynamic shapes, no host
  round-trips — the whole layer is one traced program.
* **EP rides the data-parallel axis.** Experts are sharded over ``ep``
  (default: the ``dp`` mesh axis — the standard ep ⊆ dp layout): each rank
  holds ``E / ep`` experts and routes its local tokens to *global* experts
  with one ``lax.all_to_all`` each way. On TPU the all-to-all maps onto the
  ICI torus natively.
* **TP composes inside the expert.** Expert FFN weights carry the usual
  Megatron column/row split on the hidden dim; the TP collectives are the
  same copy/reduce pair as ``tensor_parallel.layers`` (identity-fwd/psum-bwd
  on entry, psum-fwd/identity-bwd on exit).

Routing math (fp32, regardless of model dtype): top-k gates, normalized
over the selected k (GShard top-2 convention), position-in-expert by
priority cumsum (all ranks' top-1 choices outrank top-2), load-balance
auxiliary loss ``E · Σ_e f_e · p̄_e`` (Switch eq. 4) and router z-loss.

That is :func:`moe_mlp` (top-2, GELU experts, a capacity
(``MoEConfig.capacity``) and drops in ``_route``), which
``standalone_gpt.py`` runs. Beside it :func:`routed_experts_mlp` is the layer
of the softmax-routed models with many small experts
(``transformer/sdar.py``, ``transformer/deepseek.py``,
``transformer/laguna.py``, ``transformer/smallthinker.py``): **no capacity
and no drops**, gated experts (SiLU, or ReLU where the caller says so), and
the layer told which of the router's experts it holds (``experts_held``):
one chip's share of an expert-parallel deployment, computed without the
exchange. The routing is the layer's own, from its input, or the caller's,
from another tensor (SmallThinker routes the attention's normed input). Two
fields of :class:`RoutedExpertsConfig` say what a chosen expert's weight is,
each what the published key of its name says: ``norm_topk_prob`` (true: the chosen
scores divided by their sum, the block-diffusion decoder's Qwen3-style
router; false: the scores as they are, DeepSeek-V2) and
``routed_scaling_factor`` (the weights times it; DeepSeek-V2-Lite publishes
1). The layer also hands back the router's float32 scores and choices, which
:func:`sequence_balance_loss` (DeepSeek-V2's ``seq_aux``) reads. Every gather
of rows the layer makes, in both directions, is of about a row a pair held
here (``_to_rows`` by the buffer's rows, ``_sum_rows`` by the places a
position holds), never of ``positions x top_k``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.monitor.trace import span
from apex_tpu.ops._pallas_util import pvary_like
from apex_tpu.ops.grouped_matmul import grouped_matmul
from apex_tpu.parallel.mesh import DP_AXIS, TP_AXIS
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
)

Pytree = Any


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Static MoE hyper-parameters (one dataclass, SURVEY §5 config style)."""

    num_experts: int
    hidden: int
    ffn_hidden: int
    top_k: int = 2
    # capacity per expert = ceil(top_k * tokens / num_experts) * factor
    capacity_factor: float = 1.25
    # weight of the load-balance aux loss in `moe_mlp`'s returned aux dict
    lb_loss_weight: float = 1e-2
    z_loss_weight: float = 1e-3
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.top_k > self.num_experts:
            raise ValueError(
                f"top_k ({self.top_k}) cannot exceed num_experts "
                f"({self.num_experts})")

    def capacity(self, tokens_per_rank: int) -> int:
        per = self.top_k * tokens_per_rank / self.num_experts
        cap = int(per * self.capacity_factor) + 1
        # keep the lane dim friendly: round up to 8 (sublane) when roomy
        return max(8, -(-cap // 8) * 8) if cap > 8 else max(1, cap)


def init_moe_params(rng, cfg: MoEConfig, ep: int = 1, tp: int = 1) -> Pytree:
    """Global-shape parameter pytree. Expert weights lead with the GLOBAL
    expert dim [E]; :func:`moe_param_specs` shards it over ``ep`` and the
    ffn dim over ``tp``."""
    if cfg.num_experts % ep:
        raise ValueError(
            f"num_experts ({cfg.num_experts}) not divisible by ep ({ep})")
    if cfg.ffn_hidden % tp:
        raise ValueError(
            f"ffn_hidden ({cfg.ffn_hidden}) not divisible by tp ({tp})")
    kr, k1, k2 = jax.random.split(rng, 3)
    e, h, f = cfg.num_experts, cfg.hidden, cfg.ffn_hidden
    dt = cfg.dtype
    return {
        # router stays fp32: its output feeds softmax/top-k decisions
        "router": jax.random.normal(kr, (h, e), jnp.float32) * 0.02,
        "fc1_kernel": (jax.random.normal(k1, (e, h, f)) * 0.02).astype(dt),
        "fc1_bias": jnp.zeros((e, f), dt),
        "fc2_kernel": (jax.random.normal(k2, (e, f, h)) * 0.02).astype(dt),
        "fc2_bias": jnp.zeros((e, h), dt),
    }


def moe_param_specs(ep_axis: Optional[str] = DP_AXIS) -> Pytree:
    """PartitionSpecs for :func:`init_moe_params`: experts over ``ep_axis``,
    expert FFN dim over tp (Megatron column/row split)."""
    from jax.sharding import PartitionSpec as P

    return {
        "router": P(),
        "fc1_kernel": P(ep_axis, None, TP_AXIS),
        "fc1_bias": P(ep_axis, TP_AXIS),
        "fc2_kernel": P(ep_axis, TP_AXIS, None),
        "fc2_bias": P(ep_axis, None),
    }


# ---------------------------------------------------------------------------
# routing


def _route(logits32, top_k: int, capacity: int):
    """Token-choice top-k routing with per-expert capacity.

    ``logits32``: (T, E) fp32. Returns ``(dispatch, combine, aux)`` where
    ``dispatch`` is a boolean (T, E, C) assignment, ``combine`` the fp32
    gate-weighted version, and ``aux`` carries the load stats.
    """
    t, e = logits32.shape
    probs = jax.nn.softmax(logits32, axis=-1)
    gate, idx = lax.top_k(probs, top_k)  # (T, k)
    # GShard: renormalize the selected gates over the k choices
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (T, k, E)
    # priority: every token's slot-0 choice outranks any slot-1 choice —
    # order the cumsum (k, T, E) so rank-0 rows come first
    sel_kt = onehot.transpose(1, 0, 2).reshape(top_k * t, e)
    pos_kt = jnp.cumsum(sel_kt, axis=0) - sel_kt  # 0-based slot in expert
    pos = pos_kt.reshape(top_k, t, e).transpose(1, 0, 2)  # (T, k, E)
    keep = onehot * (pos < capacity)
    slot = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)  # (T, k) slot id

    # (T, k, E, C) -> reduce k: a token occupies ≤1 slot per expert
    slot_oh = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)  # (T, k, C)
    dispatch = jnp.einsum("tke,tkc->tec", keep, slot_oh)
    combine = jnp.einsum("tke,tkc,tk->tec", keep, slot_oh, gate)

    # Switch aux loss: E * sum_e (fraction routed to e) * (mean prob of e).
    # "routed" counts the top-1 assignment before capacity (standard form).
    frac = jnp.mean(onehot[:, 0, :], axis=0)
    lb_loss = e * jnp.sum(frac * jnp.mean(probs, axis=0))
    z = jax.nn.logsumexp(logits32, axis=-1)
    z_loss = jnp.mean(z * z)
    kept = jnp.sum(keep) / jnp.maximum(jnp.sum(onehot), 1.0)
    aux = {"lb_loss": lb_loss, "z_loss": z_loss, "fraction_kept": kept}
    return dispatch, combine, aux


# ---------------------------------------------------------------------------
# expert compute (local experts, TP-sharded FFN)


def _expert_ffn(p, x):
    """``x``: (E_local, N, h) TP-replicated -> (E_local, N, h). Megatron
    split on the ffn dim: fc1 column-parallel, gelu, fc2 row-parallel."""
    x = copy_to_tensor_model_parallel_region(x)
    # input-dtype einsum: keeps backward cotangents bf16 (see
    # tensor_parallel/layers.py) — fp32 MXU accumulation either way
    y = jnp.einsum("enh,ehf->enf", x,
                   p["fc1_kernel"].astype(x.dtype))
    y = y + p["fc1_bias"][:, None, :]
    y = jax.nn.gelu(y, approximate=True)
    y = jnp.einsum("enf,efh->enh", y,
                   p["fc2_kernel"].astype(x.dtype))
    y = reduce_from_tensor_model_parallel_region(y)
    return y + p["fc2_bias"][:, None, :]


# ---------------------------------------------------------------------------
# the layer


def moe_mlp(params, x, cfg: MoEConfig, ep_axis: Optional[str] = DP_AXIS,
            seq_shard_axis: Optional[str] = None) -> Tuple[jax.Array, dict]:
    """MoE FFN over ``x`` (..., h). Call inside a mesh program; tokens are
    this rank's local shard, experts are sharded over ``ep_axis`` (pass
    ``None`` for a single-rank/no-EP layer). Returns ``(out, aux)``;
    ``aux['loss']`` is the weighted router auxiliary loss (psum-mean it over
    the data axis alongside the main loss).

    ``seq_shard_axis`` enables the sequence-sharded dispatch for callers
    whose tokens are sharded over that axis (Megatron-SP regions, sharded
    over tp): each rank routes only its LOCAL tokens with a per-shard
    capacity ``C/axis_size``, the kept expert slots — not the raw sequence
    — are all-gathered along the capacity dim (the expert FFN's TP split
    needs replicated inputs for its row-parallel psum), and each rank
    combines only its own slot block back out. Versus gathering the full
    sequence first, router/dispatch/combine einsum FLOPs drop by the axis
    size, the all_to_all bytes are unchanged, and the output STAYS
    sequence-sharded (the SP activation saving is kept). Semantics note:
    capacity is enforced per sequence shard, so under skewed load the drop
    pattern differs from the full-sequence path; with ample capacity the
    outputs are bitwise the gathered path's (tested).
    """
    lead = x.shape[:-1]
    h = x.shape[-1]
    xf = x.reshape(-1, h)
    t = xf.shape[0]
    e = cfg.num_experts
    cap = cfg.capacity(t)

    logits = jnp.dot(xf.astype(jnp.float32), params["router"])
    dispatch, combine, aux = _route(logits, cfg.top_k, cap)

    # (T, h) -> (E, C, h): zero rows where a slot is unfilled
    exp_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xf)

    if seq_shard_axis is not None:
        # kept slots from every sequence shard, stacked on the capacity dim
        exp_in = lax.all_gather(exp_in, seq_shard_axis, axis=1, tiled=True)

    if ep_axis is not None:
        ep = lax.axis_size(ep_axis)
    else:
        ep = 1
    if ep > 1:
        e_local = e // ep
        # exchange: split global experts over ranks, gather every rank's
        # contribution for the local experts along the token dim
        exp_in = lax.all_to_all(exp_in, ep_axis, split_axis=0, concat_axis=1,
                                tiled=True)  # (E/ep, ep*C, h)
        exp_out = _expert_ffn(_local_experts(params, ep_axis, e_local),
                              exp_in)
        exp_out = lax.all_to_all(exp_out, ep_axis, split_axis=1,
                                 concat_axis=0, tiled=True)  # (E, C, h)
    else:
        exp_out = _expert_ffn(params, exp_in)

    if seq_shard_axis is not None:
        # this rank's slot block back out of the gathered capacity dim
        exp_out = lax.dynamic_slice_in_dim(
            exp_out, lax.axis_index(seq_shard_axis) * cap, cap, axis=1)

    out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), exp_out)
    aux = dict(aux)
    aux["loss"] = (cfg.lb_loss_weight * aux["lb_loss"]
                   + cfg.z_loss_weight * aux["z_loss"])
    return out.reshape(*lead, h), aux


def _local_experts(params, ep_axis: str, e_local: int) -> Pytree:
    """Slice this rank's expert shard out of params that arrived replicated
    (inside shard_map the spec normally delivers them pre-sliced; this
    handles the replicated-params case, e.g. pure-pjit callers)."""
    fc1 = params["fc1_kernel"]
    if fc1.shape[0] == e_local:
        return params  # already the local shard (shard_map + specs)
    start = lax.axis_index(ep_axis) * e_local
    return {
        k: lax.dynamic_slice_in_dim(params[k], start, e_local, 0)
        for k in ("fc1_kernel", "fc1_bias", "fc2_kernel", "fc2_bias")
    }


# ---------------------------------------------------------------------------
# routed experts without drops, for the experts held here

F32 = jnp.float32


# A held expert's rows start on a multiple of the tile, so that no tile of
# the grouped product holds two experts and the product's work changes only
# when a load crosses a tile's edge. The tile is the largest power of two
# within half an expert's mean load (the tiles' tails then pad the buffer by
# a quarter of the pairs on average), and no more than TILE_ROWS: at the
# benchmark's cell a data token's expert sees 1,533 +- 39 pairs a step, three
# 512-row tiles to within a standard deviation and two of 1,024 on every seed
# (my chip runs, PR 33).
TILE_ROWS = 1024
# The buffer a pass fills, over what a uniform router would send here.
BUFFER_OVER_MEAN = 1.5
# The most positions `_sum_rows` gathers a row each for at a time. At the
# benchmark's cell the layer's forward and backward take 55.4-55.8 ms at 512,
# 1,024, 2,048, 4,096 and 8,192 (a gather costs nothing by itself; the smaller
# chunk reads 5% fewer rows and takes four times the loop's turns): any of
# them (my chip runs, PR 34).
CHUNK_ROWS = 2048
# The passes over the buffer written out one after another; any past them
# run as turns of one loop behind one conditional, so the step compiles the
# pass four times and not once a pass: a layer that holds 8 experts of 256
# with top-10 has 18 passes in its worst case, and written out they took
# 351 s of the step's cold compile on a TPU v5e, where the layer's forward and
# backward take 22.1 ms either way. Every pass after the first is a
# conditional that a router near uniform never enters.
PASSES_WRITTEN_OUT = 3


@dataclasses.dataclass(frozen=True)
class RoutedExpertsConfig:
    """What :func:`routed_experts_mlp` needs beside its leaves.

    The (position, expert) pairs that land on a held expert are laid out by
    expert in a buffer of ``rows_per_pass`` rows, **each expert's rows
    starting on a multiple of ``tile_rows``** (the grouped kernels' row tile,
    ``ops.grouped_matmul``: no tile holds two experts). The buffer is
    ``BUFFER_OVER_MEAN`` times what a uniform router would send, so the
    products and the elementwise passes follow the pairs held and not the
    worst case, and so does every gather: the rows into the buffer and their
    cotangent out of it by the buffer's rows, the sums back onto the
    positions (the combine, the dispatch's backward) by the places each
    position holds in the pass
    (:func:`_sum_rows`). Pairs beyond the buffer (a router far from uniform)
    are run by further passes over it, each skipped by a conditional while
    there is nothing left: exact at any imbalance."""
    num_experts: int = 128          # the router's range
    top_k: int = 8
    # the published keys of these names (:func:`route_softmax_top_k`)
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0

    def tile_rows(self, tokens: int) -> int:
        half = tokens * self.top_k // (2 * self.num_experts)
        return min(TILE_ROWS, max(8, 1 << max(half, 1).bit_length() - 1))

    def rows_per_pass(self, tokens: int, count: int) -> int:
        tile = self.tile_rows(tokens)
        mean = tokens * self.top_k * count / self.num_experts
        want = -(-int(BUFFER_OVER_MEAN * mean) // tile) * tile
        return max(tile, min(want, self.worst_rows(tokens, count)))

    def worst_rows(self, tokens: int, count: int) -> int:
        """Every position choosing as many held experts as it can, every
        expert's last tile holding one pair."""
        tile = self.tile_rows(tokens)
        rows = tokens * min(self.top_k, count) + count * (tile - 1)
        return -(-rows // tile) * tile

    def passes(self, tokens: int, count: int) -> int:
        return -(-self.worst_rows(tokens, count)
                 // self.rows_per_pass(tokens, count))


def routed_expert_shapes(hidden: int, width: int, num_experts: int,
                         count: int) -> dict:
    """The leaves of :func:`routed_experts_mlp`: the router over all
    ``num_experts``, the ``count`` experts held, stacked."""
    return {"router": (hidden, num_experts),
            "w_gate": (count, hidden, width), "w_up": (count, hidden, width),
            "w_down": (count, width, hidden)}


def route_softmax_top_k(x, router, top_k: int, norm_topk_prob: bool = True,
                        routed_scaling_factor: float = 1.0):
    """``(idx, weight, scores)``: ``scores = softmax(x W_r)`` (tokens,
    experts) over every expert in float32; ``idx`` (tokens, top_k) its
    ``top_k`` largest; ``weight`` (tokens, top_k) what each chosen expert's
    result is multiplied by: with ``norm_topk_prob`` ``s_i / sum of the
    chosen s`` (the block-diffusion decoder), without it ``s_i`` as scored
    (DeepSeek-V2: the chosen weights then sum to less than one), either
    times ``routed_scaling_factor``."""
    logits = jnp.dot(x.astype(F32), router.astype(F32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    chosen, idx = lax.top_k(scores, top_k)
    if norm_topk_prob:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    if routed_scaling_factor != 1.0:
        chosen = chosen * routed_scaling_factor
    return idx, chosen, scores


def sequence_balance_loss(scores, idx, rows: int, alpha: float):
    """DeepSeek-V2's expert-level balance loss a sequence (``seq_aux``), one
    layer's: ``scores`` (rows x length, experts) float32 and ``idx`` (rows x
    length, top_k) as :func:`route_softmax_top_k` hands them out, the
    positions of a row together. A row: ``f_e = (times e is among a
    position's top_k) x experts / (top_k x length)``, ``P_e = mean over the
    row of s_e``, ``alpha x sum_e f_e P_e``; the mean over rows. ``f`` is a
    count, so the gradient goes through ``P`` alone."""
    e, k = scores.shape[-1], idx.shape[-1]
    length = scores.shape[0] // rows
    picked = jnp.sum(idx.reshape(rows, length * k)[:, :, None]
                     == jnp.arange(e)[None, None, :], axis=1, dtype=F32)
    f = picked * (e / (k * length))
    p = jnp.mean(scores.reshape(rows, length, e), axis=1)
    return alpha * jnp.mean(jnp.sum(f * p, axis=-1))


def _has_row(rank, n: int):
    """Whether a pair's ``rank`` is a row of a pass's ``n`` (a pair of an
    expert not held, or of another pass, has none)."""
    return (rank >= 0) & (rank < n)


def _rows_at(a, rank):
    """``a[rank]`` where the pair has a row of ``a``, nought elsewhere."""
    n = a.shape[0]
    ok = _has_row(rank, n)
    got = jnp.take(a, jnp.clip(rank, 0, n - 1), axis=0)
    return jnp.where(ok.reshape(ok.shape + (1,) * (a.ndim - 1)), got,
                     jnp.zeros((), a.dtype))


def _chunk_rows(tokens: int) -> int:
    """The positions :func:`_sum_rows` gathers a row each for at a time: a
    sixteenth of them (a power of two, at least 8), so that the staircase of
    places held is followed to within a chunk a place, and no more than
    ``CHUNK_ROWS``."""
    return min(CHUNK_ROWS, max(8, 1 << max(tokens // 16, 1).bit_length() - 1))


def _sum_rows(a, rank, weight=None):
    """``out[t] = sum_j weight[t, j] a[rank[t, j]]`` (``weight`` one where
    None) over the places ``j`` with ``0 <= rank[t, j] < rows of a``, summed
    in float32 in the places' order and returned in ``a``'s type: what a
    gather of every position's row a place and a sum over the places gives,
    bit for bit, **for about a gathered row a place held**.

    Within a position the places that have a row come first, in their order
    (``x + 0.0`` is exact, so the sum is the same terms in the same order);
    the positions are taken by how many they hold, most first, so that the
    positions whose ``j``-th place has a row are the first ``m_j``. A chunk
    of ``_chunk_rows`` positions then needs as many gathers as its first
    position holds places: a loop whose trip count is read from the data.
    One more gather puts the positions back in order. Nothing is scattered,
    and one chunk's rows exist at a time."""
    t, k = rank.shape
    n, h = a.shape
    chunk = _chunk_rows(t)
    ok = _has_row(rank, n)
    held = jnp.sum(ok, axis=1, dtype=jnp.int32)
    # the places that have a row first, in their order
    slot = jnp.cumsum(ok, axis=1, dtype=jnp.int32) - 1
    hit = ok[:, :, None] & (slot[:, :, None] == jnp.arange(k))
    first = jnp.argsort(-held, stable=True).astype(jnp.int32)
    pad = -t % chunk

    def by_place(v):
        """``v`` (tokens, k) as (k, positions by places held, padded to
        whole chunks): the ``j``-th place that has a row, nought past the
        last."""
        v = jnp.sum(jnp.where(hit, v[:, :, None], jnp.zeros((), v.dtype)),
                    axis=1)
        return jnp.pad(jnp.take(v, first, axis=0), ((0, pad), (0, 0))).T

    rows = by_place(rank)
    scale = None if weight is None else by_place(weight.astype(F32))
    held = jnp.pad(jnp.take(held, first), (0, pad))

    def one_chunk(q, out):
        at = q * chunk
        here = lax.dynamic_slice(held, (at,), (chunk,))

        def one_place(j, acc):
            r = lax.dynamic_slice(rows, (j, at), (1, chunk))[0]
            got = jnp.where((j < here)[:, None], jnp.take(a, r, axis=0),
                            jnp.zeros((), a.dtype)).astype(F32)
            if scale is not None:
                got = lax.dynamic_slice(scale, (j, at), (1, chunk))[0][:, None] * got
            return acc + got

        acc = lax.fori_loop(0, here[0], one_place,
                            pvary_like(jnp.zeros((chunk, h), F32), a))
        return lax.dynamic_update_slice(out, acc.astype(a.dtype), (at, 0))

    out = lax.fori_loop(0, (t + pad) // chunk, one_chunk,
                        pvary_like(jnp.zeros((t + pad, h), a.dtype), a))
    return jnp.take(out, jnp.argsort(first), axis=0)


@jax.custom_vjp
def _to_rows(x, pair, rank):
    """``xs[r] = x[pair[r] // k]``: row ``r`` gets the position of the pair
    it holds (a row that holds none, ``pair`` -1, gets position 0: computed,
    never used). ``rank`` (tokens, k) is the row each pair has in this pass,
    so the cotangent is gathers too (:func:`_sum_rows`) and nothing is
    scattered."""
    return jnp.take(x, jnp.maximum(pair, 0) // rank.shape[1], axis=0)


def _to_rows_fwd(x, pair, rank):
    return _to_rows(x, pair, rank), rank


def _to_rows_bwd(rank, dxs):
    return _sum_rows(dxs, rank), None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _from_rows(ys, weight, pair, rank):
    """``y[t] = sum_j weight[t, j] ys[rank[t, j]]``, summed in float32 and
    returned in ``ys``' type, over the pairs that have a row in this pass
    (:func:`_sum_rows`). A row that holds no pair takes no cotangent."""
    return _sum_rows(ys, rank, weight)


def _from_rows_fwd(ys, weight, pair, rank):
    return _from_rows(ys, weight, pair, rank), (ys, weight, pair, rank)


def _from_rows_bwd(res, dy):
    """``dys`` by row, and the weights' cotangent from the same rows:
    ``dw[t, j] = <dy[t], ys[rank[t, j]]>`` is row ``rank[t, j]``'s ``<dy of
    its position, ys>``, a pass over the two arrays by row and a gather of
    scalars."""
    ys, weight, pair, rank = res
    k = rank.shape[1]
    at = jnp.maximum(pair, 0)
    w = jnp.where(pair >= 0, jnp.take(weight.reshape(-1), at), 0.0)
    dy_rows = jnp.take(dy, at // k, axis=0).astype(F32)
    dys = (w[:, None] * dy_rows).astype(ys.dtype)
    dw = _rows_at(jnp.sum(dy_rows * ys.astype(F32), axis=-1), rank)
    return dys, dw, None, None


_from_rows.defvjp(_from_rows_fwd, _from_rows_bwd)


def _layout(key, count: int, tile: int):
    """Where the (position, place) pairs lie. ``key`` (pairs,) is each
    pair's held expert, ``count`` for an expert not held. Returns ``(order,
    rank, sizes, first_place, first_row, spans)``: the pairs sorted by
    expert (stable), the row each pair has in the tiled layout (a large
    number for a pair not held), each expert's load, where its pairs start
    among the sorted ones, the row its span starts on and the span's length
    (the load rounded up to whole tiles)."""
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    spans = -(-sizes // tile) * tile
    first_place = jnp.cumsum(sizes) - sizes
    first_row = jnp.cumsum(spans) - spans
    shift = jnp.take(first_row - first_place, jnp.minimum(key, count - 1))
    rank = jnp.where(key < count, place + shift, jnp.iinfo(jnp.int32).max // 2)
    return order, rank, sizes, first_place, first_row, spans


def routed_experts_mlp(p, x, cfg: RoutedExpertsConfig,
                       experts_held: Tuple[int, int], routing=None,
                       activation=jax.nn.silu):
    """``sum over the chosen experts held here of w_i E_i(x)`` over ``x``
    (..., hidden), ``E(x) = (act(x W_gate) * (x W_up)) W_down``, ``act``
    the ``activation`` (SiLU; ``jax.nn.relu`` for ReGLU experts).

    ``routing``: ``None`` routes ``x`` itself (:func:`route_softmax_top_k`
    over ``p["router"]``); else ``(idx, weight)`` (..., top_k) as that
    function hands them out for the same positions from another tensor,
    decided by the caller (a router that reads the layer's input before
    attention). The weights' cotangent then leaves through ``routing``.

    ``p``: :func:`routed_expert_shapes`' leaves. ``experts_held = (first,
    count)``: the experts ``first .. first + count - 1`` of the router's
    range are ``p["w_gate"][0 .. count - 1]`` and so on. Every position is
    routed over all ``cfg.num_experts`` and its weights are those of all
    ``cfg.top_k`` it chose (normalised over them or as scored, by
    ``cfg.norm_topk_prob``; times ``cfg.routed_scaling_factor``); what the
    experts not held would add is left out, and no code stands in for the
    chips that hold them.

    **No position is dropped, at any imbalance**, shapes are static, and the
    work follows the pairs held (:class:`RoutedExpertsConfig`): the first
    pass runs outright; each pass after it is a conditional (the pass under
    ``jax.checkpoint``, so that it keeps nothing) that runs only while pairs
    are left, those past ``PASSES_WRITTEN_OUT`` as turns of one loop inside
    one more conditional.

    Returns ``(y, counted, routed)``. ``counted`` is what the layout itself
    counted, int32 (:func:`routing_facts` reads a step's counters from
    them): ``counted["expert_loads"]`` (count,), the pairs each held expert
    took, and ``counted["held_places"]`` (top_k + 1,), how many positions
    hold exactly 0 .. top_k pairs with a row in the first pass: what the
    gathers of :func:`_sum_rows` follow. ``routed`` is the router's own
    result over the flattened positions: ``routed["scores"]`` (tokens,
    num_experts) float32 (``None`` where ``routing`` was given) and
    ``routed["idx"]`` (tokens, top_k), for a balance loss
    (:func:`sequence_balance_loss`)."""
    lead, h = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, h)
    t, k = xf.shape[0], cfg.top_k
    first, count = experts_held
    tile = cfg.tile_rows(t)
    n = cfg.rows_per_pass(t, count)
    with span("moe/route"):
        # the cotangents of the leaves and of the input leave the layer
        # together (the barrier's transpose is a barrier): left free, XLA
        # puts the weight gradients' products off past the sublayer that
        # follows in the backward and holds the buffer's rows, their
        # cotangents and the later passes' results (1.9 GB at the benchmark's
        # cell) across it, then rematerialises that sublayer to fit
        if routing is None:
            p, xf = lax.optimization_barrier((
                {name: p[name] for name in ("router", "w_gate", "w_up",
                                            "w_down")}, xf))
            idx, weight, scores = route_softmax_top_k(
                xf, p["router"], k, cfg.norm_topk_prob,
                cfg.routed_scaling_factor)
        else:
            idx, weight = (r.reshape(t, k) for r in routing)
            p, xf, weight = lax.optimization_barrier((
                {name: p[name] for name in ("w_gate", "w_up", "w_down")}, xf,
                weight))
            scores = None
    with span("moe/dispatch"):
        local = idx - first
        key = jnp.where((local >= 0) & (local < count), local, count
                        ).reshape(-1)
        order, rank, sizes, first_place, first_row, spans = _layout(
            key, count, tile)
        rank = rank.reshape(t, k)
        total = jnp.sum(spans)
        held = jnp.sum(_has_row(rank, n), axis=1)
        held_places = jnp.sum(held[:, None] == jnp.arange(k + 1)[None, :],
                              axis=0, dtype=jnp.int32)

    def one_pass(c):
        lo = c * n
        with span("moe/dispatch"):
            r = lo + jnp.arange(n, dtype=jnp.int32)
            g = jnp.minimum(jnp.sum(r[:, None] >= (first_row + spans)[None, :],
                                    axis=1), count - 1)
            at = r - jnp.take(first_row, g)
            pair = jnp.where(
                at < jnp.take(sizes, g),
                jnp.take(order, jnp.minimum(jnp.take(first_place, g) + at,
                                            t * k - 1)), -1)
            rows = (jnp.clip(first_row + spans, lo, lo + n)
                    - jnp.clip(first_row, lo, lo + n))
            here = rank - lo
            xs = _to_rows(xf, pair, here)
        with span("moe/experts"):
            gate = grouped_matmul(xs, p["w_gate"], rows, tile)
            up = grouped_matmul(xs, p["w_up"], rows, tile)
            act = (activation(gate.astype(F32)) * up.astype(F32)
                   ).astype(x.dtype)
            ys = grouped_matmul(act, p["w_down"], rows, tile)
        with span("moe/combine"):
            return _from_rows(ys, weight, pair, here)

    y = one_pass(jnp.int32(0))
    later = jax.checkpoint(one_pass)
    passes = cfg.passes(t, count)
    for c in range(1, min(passes, PASSES_WRITTEN_OUT)):
        with span("moe/combine"):
            y = y + lax.cond(c * n < total, later,
                             lambda c: jnp.zeros_like(y), jnp.int32(c))

    if passes > PASSES_WRITTEN_OUT:
        def turn(c, y):
            # the checkpoint around the conditional: a turn keeps its index
            # alone (inside it, the pass's inputs would leave the conditional
            # as copies that the loop stacks a turn: 16.7 of 15.75 GiB at
            # hidden 3,072 and 15 turns)
            return y + jax.checkpoint(lambda c: lax.cond(
                c * n < total, one_pass, lambda c: jnp.zeros_like(y), c))(c)

        def the_rest(y):
            # bounds known when traced: a scan, so the backward runs it too
            return lax.fori_loop(PASSES_WRITTEN_OUT, passes, turn, y)

        # one conditional round the loop: a layer with no pairs past the
        # passes written out skips it whole, in the backward too (a turn's
        # gradient adds the zeros of every leaf of the pass: 85 ms a step
        # at the window cell when every turn ran it)
        with span("moe/combine"):
            y = y + jax.checkpoint(lambda y: lax.cond(
                PASSES_WRITTEN_OUT * n < total, the_rest, jnp.zeros_like, y))(
                    jnp.zeros_like(y))
    return (y.reshape(*lead, h),
            {"expert_loads": sizes, "held_places": held_places},
            {"scores": scores, "idx": idx})


def routing_facts(loads, tokens: int, cfg: RoutedExpertsConfig,
                  held_places=None) -> dict:
    """What a layer's routing did in one step, from the held experts' loads
    (:func:`routed_experts_mlp`'s ``expert_loads``) over ``tokens``
    positions, as plain numbers: ``pairs_held`` (position, place) pairs that
    landed on an expert held here and ``pairs_uniform``, what a uniform
    router would send; ``max_load_over_mean`` among the held experts;
    ``tiled_rows``, the rows the experts' spans take (each load rounded up to
    whole tiles); ``passes_run``, the passes over the buffer the layer ran;
    and ``padding_rows``, the rows of those passes that hold no pair (the
    tiles' tails and the room past the last expert).

    With the layer's ``held_places`` also ``rows_gathered``, the rows one
    :func:`_sum_rows` over the first pass gathers (the combine's forward; the
    dispatch's backward gathers as many): each chunk of positions as many
    times as its first position holds places, and every position once to
    put them back in order; and ``rows_gathered_over_held``, that over the
    pairs that have a row in the first pass (``positions x top_k`` over them
    is what a gather a place would read)."""
    loads = np.asarray(loads)
    tile = cfg.tile_rows(tokens)
    n = cfg.rows_per_pass(tokens, len(loads))
    tiled = int((-(-loads // tile) * tile).sum())
    passes = max(1, -(-tiled // n))
    facts = {"pairs_held": int(loads.sum()),
             "pairs_uniform": tokens * cfg.top_k * len(loads) / cfg.num_experts,
             "max_load_over_mean": float(loads.max() / max(loads.mean(), 1e-9)),
             "tiled_rows": tiled,
             "padding_rows": n * passes - int(loads.sum()),
             "passes_run": passes}
    if held_places is not None:
        held_places = np.asarray(held_places)
        chunk = _chunk_rows(tokens)
        # positions that hold at least 1, 2, .. places: a chunk that starts
        # before the i-th of these ends is gathered for its i-th place
        at_least = np.cumsum(held_places[::-1])[::-1][1:]
        gathered = int((-(-at_least // chunk) * chunk).sum()) + tokens
        facts["rows_gathered"] = gathered
        facts["rows_gathered_over_held"] = gathered / max(
            int((np.arange(len(held_places)) * held_places).sum()), 1)
    return facts
