"""Flash attention — Pallas TPU kernels with online softmax, plus reference.

Reference: ``apex/contrib/csrc/fmha/`` (``fmhalib`` — fused MHA for packed
varlen sequences ≤512, driver ``apex/contrib/fmha/fmha.py:33-76``) and
``apex/contrib/csrc/multihead_attn/`` (``fast_multihead_attn`` — fused
QKV+softmax+dropout+out-proj, drivers ``apex/contrib/multihead_attn/``).
Those CUDA kernels exist because eager attention materializes the (sq, sk)
score matrix in HBM; they are hard-limited to seqlen ≤ 512.

TPU re-design: the flash-attention scheme — scores are computed a
(block_q, block_k) tile at a time with a running row-max and denominator
(online softmax) and never materialized. This removes the reference's
sequence-length limit entirely and is the building block for ring attention
(``apex_tpu/transformer/sequence_parallel.py``). Backward recomputes scores
tile by tile from the saved output and row log-sum-exp (the standard flash
backward), as two accumulation kernels (dQ, and dK/dV).

Who loops over the tiles is the *tile schedule*, chosen per shape by
``_tile_plan`` and by nothing else:

* **streamed** — q tiles and K/V tiles both on the grid, the statistics in
  VMEM scratch across the innermost grid dim, dead causal steps skipped by
  predicate. What the other schedules do not carry: the additive bias (its
  tile rides the grid), attention dropout, and ring attention's partial
  blocks (a row that sees no key, lse as a column to merge).
* **resident** — one head a grid step, its Q, K, V (and dO, lse, delta)
  whole in VMEM, the loops over q tiles and K/V tiles unrolled inside the
  kernel with static indices. A causal call computes only the tiles on or
  under the diagonal and builds the mask only on those that straddle it;
  m, l and the accumulators are values, stored once a row (or K/V) block.
  Every unrolled tile body is compiled, lowered and hashed at each set-up,
  so a kernel holds at most ``_RESIDENT_MAX_BODIES`` of them (a 2 x 2
  rectangle): a call over that, or over the VMEM budget, is listed.
* **listed** — the grid walks a *list* of the live tiles (scalar-prefetched
  tables of q tile, K/V tile and flags), so a tile the mask hides costs no
  grid step and no DMA, a whole tile pays no mask, and a tile that straddles
  an edge builds its mask from positions; lse and delta are lane-dense
  rows. Every call the other two do not take: causal or unmasked, any
  :class:`MaskStructure` (the block-diffusion mask, the window), and grouped
  heads (K/V with fewer heads than Q: the K/V block's index map reads head
  ``i // group``, dK and dV are summed over the group inside the kernel,
  nothing is repeated in HBM).

Layout: ``flash_attention`` takes (batch, heads, seq, head_dim); at head_dim 64
(half a lane tile) ``flash_attention_packed`` takes the QKV product: file's end.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite stand-in for -inf: keeps exp() exact zero without nan from (-inf) - (-inf).
NEG_INF = -1e30


from apex_tpu.ops._pallas_util import sds as _sds  # noqa: E402
from apex_tpu.ops._pallas_util import compiled_backend as _compiled_backend


# ---------------------------------------------------------------------------
# The mask as a structure: which (query, key) pairs see each other, stated so
# that a tile schedule can tell from the tiles' corners alone which tiles to
# skip, which are whole and which straddle an edge and build a mask.

@dataclasses.dataclass(frozen=True)
class MaskStructure:
    """A static rule over (query position, key position), ``sq == sk``.

    ``causal``: key ``j`` is seen by query ``i`` iff ``j <= i``.

    ``block_diffusion`` (block length ``block``): the sequence is a noised
    copy followed by the clean copy of ``L = sq // 2`` tokens. With
    ``blk(i) = (i mod L) // block``, query ``i`` sees key ``j`` iff both are
    noised and ``blk(j) == blk(i)``; or ``i`` is noised, ``j`` clean and
    ``blk(j) < blk(i)``; or both are clean and ``blk(j) <= blk(i)``. A clean
    query sees no noised key.

    ``window`` (``block`` the window's length ``W``): key ``j`` is seen by
    query ``i`` iff ``i - W < j <= i``, the query's own position and the
    ``W - 1`` before it.

    :meth:`hidden` is the rule element by element (arrays of positions: the
    kernels' straddling tiles and the dense reference); :meth:`tile_kinds`
    is the same rule over whole tiles, from their corners."""
    kind: str = "causal"
    block: int = 1

    def __post_init__(self):
        if self.kind not in ("causal", "block_diffusion", "window"):
            raise ValueError(f"unknown mask structure {self.kind!r}")
        if self.block < 1:
            raise ValueError(f"block must be >= 1, got {self.block}")

    def hidden(self, qpos, kpos, sq: int):
        """True where query ``qpos`` does NOT see key ``kpos`` (arrays that
        broadcast against each other: numpy or traced)."""
        if self.kind == "causal":
            return kpos > qpos
        if self.kind == "window":
            return (kpos > qpos) | (qpos - kpos >= self.block)
        half = sq // 2
        q_noised, k_noised = qpos < half, kpos < half
        # positions are never negative, so // and % are shifts and masks
        # where ``block`` and ``half`` are powers of two
        qb = (qpos - (qpos >= half) * half) // self.block
        kb = (kpos - (kpos >= half) * half) // self.block
        seen = ((q_noised & k_noised & (kb == qb))
                | (q_noised & ~k_noised & (kb < qb))
                | (~q_noised & ~k_noised & (kb <= qb)))
        return ~seen

    def tiles(self, sq: int, sk: int, block_q: int, block_k: int) -> bool:
        """Whether (block_q, block_k) tiles can carry the rule: a tile
        must lie within one quadrant of (noised | clean) x (noised | clean)."""
        if sq != sk:
            return False
        if self.kind in ("causal", "window"):
            return True
        half = sq // 2
        return sq % 2 == 0 and half % block_q == 0 and half % block_k == 0

    def tile_kinds(self, sq: int, block_q: int, block_k: int):
        """``(live, interior)``: boolean (q tiles, K/V tiles) arrays. A live
        tile holds a pair that is seen; an interior tile holds only such."""
        i = np.arange(sq // block_q)[:, None]
        j = np.arange(sq // block_k)[None, :]
        q0, q1 = i * block_q, i * block_q + block_q - 1
        k0, k1 = j * block_k, j * block_k + block_k - 1
        if self.kind == "causal":
            return k0 <= q1, k1 <= q0
        if self.kind == "window":
            # q - k over the tile runs from q0 - k1 to q1 - k0; a pair is
            # seen where it lies in [0, W - 1]
            w = self.block
            return ((q1 - k0 >= 0) & (q0 - k1 <= w - 1),
                    (q0 - k1 >= 0) & (q1 - k0 <= w - 1))
        half = sq // 2
        qn, kn = q0 < half, k0 < half
        qb0, qb1 = (q0 % half) // self.block, (q1 % half) // self.block
        kb0, kb1 = (k0 % half) // self.block, (k1 % half) // self.block
        live = np.where(qn, np.where(kn, (kb0 <= qb1) & (qb0 <= kb1), kb0 < qb1),
                        ~kn & (kb0 <= qb1))
        interior = np.where(
            qn, np.where(kn, (qb0 == qb1) & (kb0 == kb1) & (qb0 == kb0),
                         kb1 < qb0),
            ~kn & (kb1 <= qb0))
        return live, interior


CAUSAL = MaskStructure("causal")


def block_diffusion_mask(block: int) -> MaskStructure:
    """The block-diffusion training mask over ``[noised copy ; clean copy]``
    (:class:`MaskStructure`)."""
    return MaskStructure("block_diffusion", int(block))


def sliding_window_mask(window: int) -> MaskStructure:
    """Causal attention over the last ``window`` positions, the query's own
    among them (:class:`MaskStructure`)."""
    return MaskStructure("window", int(window))


def _as_structure(causal) -> Optional[MaskStructure]:
    """``causal`` as callers give it (a bool, or a structure) -> a structure
    or None."""
    if isinstance(causal, MaskStructure):
        return causal
    return CAUSAL if causal else None


def _repeat_kv(q, k, v):
    """K and V with each head repeated for the query heads that read it
    (head ``i`` reads K/V head ``i // group``): the reference path only."""
    group = q.shape[-3] // k.shape[-3]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=-3), jnp.repeat(v, group, axis=-3)


# ---------------------------------------------------------------------------
# Pure-JAX reference (ground truth for kernel tests; also the fallback path
# for arbitrary masks / unaligned shapes — XLA fuses it into a few loops).

def attention_reference(q, k, v, mask=None, scale: Optional[float] = None,
                        causal: bool = False, dropout_rate: float = 0.0,
                        dropout_key=None, bias=None, dropout_keep=None,
                        structure: Optional[MaskStructure] = None):
    """Plain softmax(QKᵀ·scale + bias)V in fp32 accumulation.

    ``structure``: a :class:`MaskStructure`, built here as the dense mask it
    states. K and V may have fewer heads than Q (a divisor): query head
    ``i`` reads K/V head ``i // group``.

    ``mask``: broadcastable boolean over (..., sq, sk), True = masked OUT
    (the reference convention, ``apex/contrib/fmha/fmha.py`` cu_seqlens
    padding → masked). ``bias``: additive logit bias broadcastable over
    (..., sq, sk) — e.g. T5 relative position bias (heads, sq, sk).
    Optional probability dropout on the softmax (the reference kernels'
    fused dropout, here materialized); ``dropout_keep`` supplies an
    explicit keep mask instead of the ``dropout_key`` draw (how
    ``flash_attention``'s fallback stays on the kernels' counter-hash
    stream). Returns q.dtype.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if q.ndim >= 3 and k.shape[-3] != q.shape[-3]:
        k, v = _repeat_kv(q, k, v)
    q32 = q.astype(jnp.float32)
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    s = jnp.einsum("...qd,...kd->...qk", q32, k32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if structure is not None:
        sq, sk = s.shape[-2], s.shape[-1]
        qpos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(structure.hidden(qpos, kpos, sq), NEG_INF, s)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qpos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(kpos > qpos + (sk - sq), NEG_INF, s)
    if mask is not None:
        s = jnp.where(mask, NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        if dropout_keep is not None and dropout_key is not None:
            raise ValueError(
                "pass either dropout_key (draw a mask) or dropout_keep "
                "(explicit mask), not both — the key would be silently "
                "ignored")
        if dropout_keep is None:
            if dropout_key is None:
                raise ValueError("dropout_rate > 0 needs dropout_key")
            dropout_keep = jax.random.bernoulli(dropout_key,
                                                1.0 - dropout_rate, p.shape)
        p = jnp.where(dropout_keep, p / (1.0 - dropout_rate), 0.0)
    o = jnp.einsum("...qk,...kd->...qd", p, v32)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Dropout: the counter-hash keep mask the kernels and the dense / ring
# einsum paths share

def _dropout_keep(seed_ref, rate, block_q, block_k, q_i, kv_i, bh_i):
    """Deterministic keep mask from a counter-based hash of (seed, batch*head,
    GLOBAL q position, GLOBAL k position) — the philox-counter scheme of
    the reference's fmhalib dropout. Position-keyed (not block-keyed), so the
    identical mask regenerates in forward and both backward kernels even at
    different block sizes, and plain integer ops keep it portable to pallas
    interpret mode (pltpu's hardware PRNG is TPU-only). ``bh_i`` must be read
    at kernel top level (program_id inside a pl.when body does not lower in
    interpret mode).

    ``seed_ref`` is the SMEM operand ``[seed, q_off, k_off]``: the offsets
    translate kernel-local positions to global sequence positions, so a
    seq-sharded call (ring attention's per-chunk kernels) regenerates
    EXACTLY the corresponding slice of the dense global mask — sharding is
    invisible to the dropout stream."""
    # all-uint32 arithmetic: mixing a signed scalar into the uint32 iota
    # would promote/wrap and skew the keep probability
    qpos = (seed_ref[1].astype(jnp.uint32)
            + jnp.asarray(q_i * block_q).astype(jnp.uint32)
            + jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 0))
    kpos = (seed_ref[2].astype(jnp.uint32)
            + jnp.asarray(kv_i * block_k).astype(jnp.uint32)
            + jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 1))
    return _hash_keep(qpos, kpos, seed_ref[0].astype(jnp.uint32),
                      bh_i.astype(jnp.uint32), rate)


def _hash_keep(qpos, kpos, seed_u32, bh_u32, rate: float):
    """The ONE mask derivation both the Pallas kernels and the dense/ring
    einsum paths share — any drift between copies would silently break the
    ring-equals-dense dropout invariant. All operands uint32."""
    x = (qpos * jnp.uint32(0x9E3779B1)
         + kpos * jnp.uint32(0x85EBCA77)
         + seed_u32 * jnp.uint32(0xC2B2AE3D)
         + bh_u32 * jnp.uint32(0x27D4EB2F))
    # murmur3 fmix32 finalizer: full-avalanche 32-bit mixing
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    thresh = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return x >= thresh


def _seed3(seed):
    """Normalize the dropout SMEM operand to ``[seed, q_off, k_off]``;
    scalar/(1,) legacy callers get zero offsets."""
    if seed is None:
        return jnp.zeros((3,), jnp.int32)
    seed = jnp.asarray(seed, jnp.int32).reshape(-1)
    if seed.shape[0] == 1:
        return jnp.concatenate([seed, jnp.zeros((2,), jnp.int32)])
    if seed.shape[0] != 3:
        raise ValueError(f"dropout seed operand must be scalar, (1,) or "
                         f"(3,) [seed, q_off, k_off]; got {seed.shape}")
    return seed


def attention_dropout_mask(seed, rate: float, bh: int, sq: int, sk: int,
                           q_off=0, k_off=0):
    """(bh, sq, sk) keep mask — bit-identical to what the Pallas kernels
    regenerate from ``(seed, batch*head, global positions)``. Used by the
    ring-SP einsum chunk path and parity tests: with the right offsets a
    seq shard's mask IS the corresponding slice of the dense mask."""
    qpos = (jnp.asarray(q_off).astype(jnp.uint32)
            + jnp.arange(sq, dtype=jnp.uint32))[None, :, None]
    kpos = (jnp.asarray(k_off).astype(jnp.uint32)
            + jnp.arange(sk, dtype=jnp.uint32))[None, None, :]
    bh_i = jnp.arange(bh, dtype=jnp.uint32)[:, None, None]
    return _hash_keep(qpos, kpos, jnp.asarray(seed).astype(jnp.uint32),
                      bh_i, rate)


# ---------------------------------------------------------------------------
# The tile schedule: which (q tile, K/V tile) pairs a call visits, at what
# size, and whether the loop over them is the kernel's own or the grid's.

# Unrolled tile bodies a resident kernel may hold: a 2 x 2 rectangle, of which
# a causal call visits 3 (s 1024 at 512 x 512, the benchmark cells' call).
# The v5e's kernel time barely moves with more of them (3 -> 20 bodies: 4.14
# -> 3.95 ms a layer at the cells' shape), while every body is traced,
# lowered, hashed into the compile-cache key and loaded at each warm set-up
# and compiled at each cold one: the three kernels' first call takes 1.0-1.6
# s streamed, 2.0 s at 3 bodies, 2.1 s at 4 and 5.1 s at 20, which cost PR 26
# 12% of gpt2-medium's setup_s (PERF.md section 6, PR 26 and PR 27).
_RESIDENT_MAX_BODIES = 4

# VMEM a resident grid step may plan for: the widest kernel's operands and
# results (Q, dO, K, V, dK, dV, lse, delta in flash_bwd_dkv), double-buffered
# and counted as Mosaic lays them out (the minor dim padded to 128 lanes, so
# an (s, 1) fp32 column costs s * 512 bytes), plus six fp32 score-sized
# temporaries of one tile. 11 MiB of it at the cells' shape; XLA's default
# scoped limit is 16 MiB.
_RESIDENT_VMEM_BYTES = 14 * 1024 * 1024

# The listed kernels' time a head, forward and backward, as two terms: a grid
# step of the three kernels, and a pair of positions a tile computes at keys
# and values of 128 (scaled by (d + d_v) / 256). Fitted on the v5e to a
# forward and backward timed alone at each tile below and 256 x 256, at the
# window (4,096), causal, block-diffusion and latent-attention cells' shapes
# (``benchmarks/flash_window_tpu.py``, 18 timings, each within 21%; PERF.md
# section 6). Of those tiles the least cost is the fastest timed in each call.
_STEP_US = 1.977
_PAIR_US = 1.453e-5

# The tiles a listed call weighs when its caller leaves the tile to the plan.
_LISTED_TILES = ((512, 512), (1024, 512), (512, 1024), (1024, 1024))

# VMEM the listed kernels may plan for: XLA's default scoped limit, which
# ``_listed_params`` leaves in place.
_LISTED_VMEM_BYTES = 16 * 1024 * 1024


class TilePlan(NamedTuple):
    """What a call's kernels run, static per shape. ``visited`` / ``masked``
    / ``rectangle`` count (q tile, K/V tile) pairs per head: computed, built
    with the causal mask, and in the whole (sq, sk) rectangle."""
    schedule: str  # "resident" | "streamed" | "listed"
    block_q: int
    block_k: int
    visited: int
    masked: int
    rectangle: int

    @property
    def bodies(self) -> int:
        """Tile bodies one kernel's program holds: every visited tile when
        the loops are unrolled in the kernel, one when the grid loops."""
        return self.visited if self.schedule == "resident" else 1


def _tile_kind(causal, q_i, kv_i, block_q, block_k):
    """Tile (q_i, kv_i) -> (live: some score is visible, interior: every
    score is, so the tile needs no mask)."""
    if not causal:
        return True, True
    return (kv_i * block_k <= q_i * block_q + block_q - 1,
            kv_i * block_k + block_k - 1 <= q_i * block_q)


@functools.lru_cache(maxsize=None)
def _listed_tiles(structure: Optional[MaskStructure], sq, sk, bq, bk):
    """The live tiles of a listed call as two int32 tables of rows (q tile,
    K/V tile, flags): in the order the forward and dQ walk them (a q tile's
    K/V tiles together, ascending) and in the order dK/dV does (a K/V tile's
    q tiles together). Flags: 1 the first tile of its row (or column), 2 the
    last, 4 the tile straddles an edge of the mask and builds it."""
    nq, nk = sq // bq, sk // bk
    if structure is None:
        live = np.ones((nq, nk), bool)
        interior = live
    else:
        live, interior = structure.tile_kinds(sq, bq, bk)
        live, interior = (np.broadcast_to(a, (nq, nk)) for a in (live, interior))
    if not (live.any(axis=1).all() and live.any(axis=0).all()):
        raise ValueError(f"mask structure {structure} leaves a whole tile row "
                         f"or column of ({sq}, {sk}) unseen")

    def table(by_column: bool):
        grid = live.T if by_column else live
        outer, inner = np.nonzero(grid)
        first = np.r_[True, outer[1:] != outer[:-1]]
        last = np.r_[outer[1:] != outer[:-1], True]
        qi, kj = (inner, outer) if by_column else (outer, inner)
        flags = first * 1 + last * 2 + ~interior[qi, kj] * 4
        return np.stack([qi, kj, flags]).astype(np.int32)

    return table(False), table(True)


def _listed_plan(structure, sq, sk, bq, bk) -> TilePlan:
    """``visited``: the live tiles, each kernel's grid steps a head."""
    by_row, _ = _listed_tiles(structure, sq, sk, bq, bk)
    return TilePlan("listed", bq, bk, by_row.shape[1],
                    int(np.sum(by_row[2] & 4 != 0)), (sq // bq) * (sk // bk))


def _listed_vmem(bq, bk, d, dv, itemsize) -> int:
    """VMEM the widest listed kernel (dK/dV) plans for, an upper bound: its
    q, dO, K and V blocks and dK, dV results at 128-lane widths, its fp32
    accumulators, and two fp32 score-sized temporaries of one tile.
    Compiled for a described v5e the three kernels need, in MiB (this count
    in brackets): 3.2 (3.25) at 512 x 512 and widths of 128, 9.9 (10.5) at
    1,024 x 1,024, 9.6 (11.75) there at keys of 192 over 128, 15.5 (19.25)
    at 576 over 512, 18.0 (19) at 2,048 x 1,024 and 128."""
    width = -(-d // 128) * 128 + -(-dv // 128) * 128
    return ((bq + 2 * bk) * width * itemsize + bk * width * 4
            + 2 * bq * bk * 4)


def _listed_tile(structure, sq, sk, d, dv, dtype, block_q, block_k):
    """The listed schedule's tile. A number bounds it from above (the widest
    divisor under it); ``None`` for both leaves it to the fitted cost: of
    ``_LISTED_TILES``, those that divide the sequence, that the structure
    can carry and whose kernels fit ``_LISTED_VMEM_BYTES``, the one of least
    grid steps x ``_STEP_US`` + pairs computed x ``_PAIR_US``; 512 x 512's
    divisors where none qualifies."""
    if block_q is not None or block_k is not None:
        return (_pick_block(sq, 512 if block_q is None else block_q),
                _pick_block(sk, 512 if block_k is None else block_k))
    itemsize = jnp.dtype(dtype).itemsize
    pair = _PAIR_US * (d + dv) / 256
    costs = []
    for bq, bk in _LISTED_TILES:
        if (sq % bq or sk % bk
                or (structure is not None and not structure.tiles(sq, sk, bq, bk))
                or _listed_vmem(bq, bk, d, dv, itemsize) > _LISTED_VMEM_BYTES):
            continue
        steps = _listed_tiles(structure, sq, sk, bq, bk)[0].shape[1]
        costs.append((steps * (_STEP_US + bq * bk * pair), bq, bk))
    if not costs:
        return _pick_block(sq, 512), _pick_block(sk, 512)
    return min(costs)[1:]


def _tile_plan(sq, sk, d, dtype, causal, block_q=512, block_k=512,
               has_bias=False, group=1, dropout=False,
               whole_rows=False, dv=None) -> TilePlan:
    """The one place the schedule and the tile are chosen, from what the call
    can observe. ``causal`` is the mask's structure: a bool as ever, or a
    :class:`MaskStructure` (``True`` is ``CAUSAL``). ``group``: query heads a
    K/V head. ``dropout``: the call drops attention weights.
    ``whole_rows``: the caller owns each row whole (``flash_attention``);
    left False, the call may be a block of ring attention's, whose rows may
    see no key and whose lse is merged as a column, as ``_fa_fwd`` and
    ``_fa_bwd`` serve. ``dv``: the value width (``d`` when None).
    ``block_q`` / ``block_k`` bound the tile from above; the widest divisor
    under them is taken on every schedule. ``None`` leaves the tile to the
    plan: 512 on the resident and streamed schedules, the listed one's
    fitted cost on the listed (``_listed_tile``)."""
    structure = _as_structure(causal)
    dv = d if dv is None else dv
    bq = _pick_block(sq, 512 if block_q is None else block_q)
    bk = _pick_block(sk, 512 if block_k is None else block_k)
    nq, nk = sq // bq, sk // bk
    listed = lambda: _listed_plan(structure, sq, sk, *_listed_tile(
        structure, sq, sk, d, dv, dtype, block_q, block_k))
    if group > 1 or structure not in (None, CAUSAL):
        return listed()
    causal = structure is not None
    kinds = [_tile_kind(causal, i, j, bq, bk)
             for i in range(nq) for j in range(nk)]
    visited = sum(live for live, _ in kinds)
    interior = sum(full for _, full in kinds)
    itemsize = jnp.dtype(dtype).itemsize
    lanes = -(-d // 128) * 128
    vmem = (2 * (3 * (sq + sk) * lanes * itemsize + 2 * sq * 128 * 4)
            + 6 * bq * bk * 4)
    resident = (
        visited <= _RESIDENT_MAX_BODIES and vmem <= _RESIDENT_VMEM_BYTES
        # the bias tile rides the grid; a causal rectangle (no caller makes
        # one) keeps the grid's own bounds
        and not has_bias and (not causal or sq == sk)
        # the kernel slices whole operands at multiples of the tile: they
        # must sit on the dtype's sublane tiling (8 rows fp32, 16 bf16)
        and bq % (32 // itemsize) == 0 and bk % (32 // itemsize) == 0)
    if resident:
        return TilePlan("resident", bq, bk, visited, visited - interior,
                        nq * nk)
    streamed = (
        # what only the streamed kernels carry: the bias tile, the dropout
        # mask, the l == 0 guard and lse as a column for the ring's merge,
        # and a causal rectangle's own bounds (no caller makes one)
        has_bias or dropout or not whole_rows or (causal and sq != sk)
        # the listed kernels keep lse and delta as rows of the q tile: a
        # block of whole 128 lanes, or the whole sequence
        or (bq % 128 and bq != sq))
    if not streamed:
        return listed()
    # the streamed kernels build the causal mask on every tile they compute
    return TilePlan("streamed", bq, bk, visited, visited if causal else 0,
                    nq * nk)


def _scale_folds(scale) -> bool:
    """A power-of-two ``scale`` (1/8 at head size 64) multiplies a bf16 or
    fp32 operand exactly, so the resident kernels apply it to the (rows, d)
    operand tile once instead of to every score."""
    return math.frexp(scale)[0] == 0.5


# ---------------------------------------------------------------------------
# Tile bodies of the resident schedule

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _tile_scores(q, k, scale, mask_at):
    """fp32 score tile q @ k.T. ``scale`` is None when an operand carries it
    (``_scale_folds``). ``mask_at`` = (first q row, first k column), static,
    builds the causal mask: tiles that straddle the diagonal only; interior
    tiles pass None and pay no iota, compare or select."""
    # inputs stay in model dtype: MXU runs bf16 x bf16 -> fp32 natively
    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    if scale is not None:
        s = s * scale
    if mask_at is not None:
        q0, k0 = mask_at
        ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                 - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        s = jnp.where(ahead > q0 - k0, NEG_INF, s)
    return s


def _tile_extras(interior, seed_ref, dropout_rate, block_q, block_k, q_i,
                 kv_i, bh_i):
    """(mask_at, keep) of tile (q_i, kv_i): the mask's origin if the tile
    straddles the diagonal, the dropout keep mask if there is dropout."""
    mask_at = None if interior else (q_i * block_q, kv_i * block_k)
    keep = None
    if dropout_rate > 0.0:
        keep = _dropout_keep(seed_ref, dropout_rate, block_q, block_k, q_i,
                             kv_i, bh_i)
    return mask_at, keep


def _fwd_tile(carry, q, k, v, scale, mask_at, keep, dropout_rate):
    """One online-softmax step: (m, l, acc) after this K/V tile. ``carry``
    None = the row block's first tile, with nothing to rescale."""
    s = _tile_scores(q, k, scale, mask_at)
    m_new = jnp.max(s, axis=1, keepdims=True)
    if carry is not None:
        m_prev, l_prev, acc = carry
        m_new = jnp.maximum(m_prev, m_new)
        corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    # l accumulates the UNdropped p: normalization precedes dropout,
    # so the final divide yields dropout(softmax(s)) @ v exactly
    l_new = jnp.sum(p, axis=1, keepdims=True)
    if keep is not None:
        p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
    pv = jax.lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    if carry is None:
        return m_new, l_new, pv
    return m_new, corr * l_prev + l_new, acc * corr + pv


def _bwd_tile(q, k, v, do, lse, delta, scale, mask_at, keep, dropout_rate):
    """Scores recomputed from the saved lse -> (p as dV sees it, dL/ds
    without the q·kᵀ ``scale``, which the caller applies to its sum)."""
    s = _tile_scores(q, k, scale, mask_at)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    p_v = p
    if keep is not None:
        inv = 1.0 / (1.0 - dropout_rate)
        p_v = jnp.where(keep, p * inv, 0.0)
        dp = jnp.where(keep, dp * inv, 0.0)
    return p_v, p * (dp - delta)


def _rows(i, block):
    """Tile ``i`` of the one head a resident block holds."""
    return 0, slice(i * block, (i + 1) * block)


# ---------------------------------------------------------------------------
# Pallas forward

def _fa_fwd_resident_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                            scale, causal, block_q, block_k, dropout_rate):
    """Resident schedule: Q, K and V of one head whole in VMEM, the loops
    over q tiles and K/V tiles unrolled here with every index static."""
    bh_i = pl.program_id(0)
    fold = _scale_folds(scale)
    for q_i in range(q_ref.shape[1] // block_q):
        q_at = _rows(q_i, block_q)
        q = q_ref[q_at] * scale if fold else q_ref[q_at]
        carry = None
        for kv_i in range(k_ref.shape[1] // block_k):
            live, interior = _tile_kind(causal, q_i, kv_i, block_q, block_k)
            if not live:
                continue
            kv_at = _rows(kv_i, block_k)
            carry = _fwd_tile(
                carry, q, k_ref[kv_at], v_ref[kv_at], None if fold else scale,
                *_tile_extras(interior, seed_ref, dropout_rate, block_q,
                              block_k, q_i, kv_i, bh_i), dropout_rate)
        # no l == 0 guard as in the streamed _finish: a resident row sees its
        # own diagonal (or, not causal, every key), so its max gives l >= 1
        m, l, acc = carry
        o_ref[q_at] = (acc / l).astype(o_ref.dtype)
        lse_ref[q_at] = m + jnp.log(l)


def _fa_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *refs,
                   scale, causal, block_q, block_k, nk, dropout_rate,
                   has_bias=False):
    if has_bias:
        bias_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        bias_ref = None
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    bh_i = pl.program_id(0)
    q_i = pl.program_id(1)
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: skip K/V blocks entirely above the diagonal.
    run = (kv_i * block_k <= q_i * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        # inputs stay in model dtype: MXU runs bf16 x bf16 -> fp32 natively;
        # upcasting first would push the matmul onto the (8x slower) fp32 path
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            qpos = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos > qpos, NEG_INF, s)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # l accumulates the UNdropped p: normalization precedes dropout,
        # so the final divide yields dropout(softmax(s)) @ v exactly
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, dropout_rate, block_q, block_k,
                                 q_i, kv_i, bh_i)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kv_i == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        # Fully-masked rows (possible under ring-attention partial blocks)
        # produce l == 0; emit 0 output and lse = NEG_INF for the merge.
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # lse is laid out (bh, sq, 1): a (block_q, 1) block writes/reads with
        # no lane↔sublane transpose (TPU block rules need the last dim to be
        # 128-divisible or equal to the full array dim — here it's 1 == 1).
        lse_ref[0] = jnp.where(l == 0.0, NEG_INF, m_scr[:, :1] + jnp.log(safe_l))


def _kv_lim(i, block_q, block_k):
    """Last K/V block index the causal mask leaves live for q block ``i``."""
    return (i * block_q + block_q - 1) // block_k


def _bias_spec(num_heads, block_q, block_k, causal=False):
    """BlockSpec for a batch-shared (heads, sq, sk) bias: grid dim 0 is the
    flattened b*h (b-major), so the head index is bh mod heads. Under
    ``causal`` the kv coordinate is clamped at the diagonal (see
    ``_fa_fwd``)."""

    def index(b, i, j):
        if causal:
            j = jnp.minimum(j, _kv_lim(i, block_q, block_k))
        return (jax.lax.rem(b, num_heads), i, j)

    return pl.BlockSpec((1, block_q, block_k), index)


def _head_spec(rows, width):
    """A resident kernel's operand: one whole head a grid step."""
    return pl.BlockSpec((1, rows, width), lambda b: (b, 0, 0))


def _fa_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret,
            dropout_rate=0.0, seed=None, bias=None):
    """(o, lse (bh, sq, 1)) of one attention call on (bh, seq, d) arrays,
    resident or streamed: the kernels of a bias, dropout and ring
    attention's partial rows. ``block_q`` / ``block_k`` bound the tile;
    ``_tile_plan`` picks it and the schedule."""
    bh, sq, d = q3.shape
    sk, d_v = k3.shape[1], v3.shape[2]
    seed = _seed3(seed)
    has_bias = bias is not None
    plan = _tile_plan(sq, sk, d, q3.dtype, causal, block_q, block_k, has_bias)
    block_q, block_k = plan.block_q, plan.block_k
    nq = sq // block_q
    nk = sk // block_k
    out_shape = [
        _sds((bh, sq, d_v), q3.dtype, q3, k3, v3),
        _sds((bh, sq, 1), jnp.float32, q3, k3, v3),
    ]
    if plan.schedule == "resident":
        return pl.pallas_call(
            functools.partial(
                _fa_fwd_resident_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, dropout_rate=dropout_rate),
            name="flash_fwd",
            grid=(bh,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      _head_spec(sq, d), _head_spec(sk, d), _head_spec(sk, d_v)],
            out_specs=[_head_spec(sq, d_v), _head_spec(sq, 1)],
            out_shape=out_shape,
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(seed, q3, k3, v3)

    kernel = functools.partial(
        _fa_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk, dropout_rate=dropout_rate,
        has_bias=has_bias)

    # Causal: clamp the K/V fetch at the diagonal. The ``run`` predicate
    # already skips the compute for blocks above it; clamping the index map
    # makes those iterations re-request the diagonal block, and Mosaic
    # elides a copy whose block index matches the previous iteration —
    # halving K/V HBM traffic instead of fetching masked-out blocks.
    def kv_index(b, i, j):
        if causal:
            j = jnp.minimum(j, _kv_lim(i, block_q, block_k))
        return (b, j, 0)

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d_v), kv_index),
    ]
    inputs = [seed, q3, k3, v3]
    if has_bias:
        in_specs.append(_bias_spec(bias.shape[0], block_q, block_k, causal))
        inputs.append(bias)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*inputs)
    return o, lse


# ---------------------------------------------------------------------------
# Pallas backward: dQ kernel (loops over K/V blocks) and dK/dV kernel (loops
# over Q blocks), each on the forward's schedule: the grid's innermost dim
# when streamed, unrolled when resident. Scores are recomputed from q, k and the
# saved lse — p = exp(s - lse) is already normalized, so no second pass over
# the row is needed (the flash-attention backward identity).

def _fa_bwd_dq_resident_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref,
                               lse_ref, delta_ref, dq_ref, *,
                               scale, causal, block_q, block_k, dropout_rate):
    """Resident schedule (see ``_fa_fwd_resident_kernel``): dq summed over a
    q tile's live K/V tiles in a value, scaled and written once."""
    bh_i = pl.program_id(0)
    fold = _scale_folds(scale)
    for q_i in range(q_ref.shape[1] // block_q):
        q_at = _rows(q_i, block_q)
        q = q_ref[q_at] * scale if fold else q_ref[q_at]
        do, lse, delta = do_ref[q_at], lse_ref[q_at], delta_ref[q_at]
        dq = None
        for kv_i in range(k_ref.shape[1] // block_k):
            live, interior = _tile_kind(causal, q_i, kv_i, block_q, block_k)
            if not live:
                continue
            kv_at = _rows(kv_i, block_k)
            k = k_ref[kv_at]
            _, ds = _bwd_tile(
                q, k, v_ref[kv_at], do, lse, delta, None if fold else scale,
                *_tile_extras(interior, seed_ref, dropout_rate, block_q,
                              block_k, q_i, kv_i, bh_i), dropout_rate)
            part = jax.lax.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)
            dq = part if dq is None else dq + part
        dq_ref[q_at] = (dq * scale).astype(dq_ref.dtype)


def _fa_bwd_dkv_resident_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref,
                                lse_ref, delta_ref, dk_ref, dv_ref, *,
                                scale, causal, block_q, block_k,
                                dropout_rate):
    """Resident schedule, mirrored: for each K/V tile the q tiles from the
    diagonal down, dk and dv summed in values and written once."""
    bh_i = pl.program_id(0)
    fold = _scale_folds(scale)
    for kv_i in range(k_ref.shape[1] // block_k):
        kv_at = _rows(kv_i, block_k)
        k = k_ref[kv_at] * scale if fold else k_ref[kv_at]
        v = v_ref[kv_at]
        dk = dv = None
        for q_i in range(q_ref.shape[1] // block_q):
            live, interior = _tile_kind(causal, q_i, kv_i, block_q, block_k)
            if not live:
                continue
            q_at = _rows(q_i, block_q)
            q, do = q_ref[q_at], do_ref[q_at]
            p_v, ds = _bwd_tile(
                q, k, v, do, lse_ref[q_at], delta_ref[q_at],
                None if fold else scale,
                *_tile_extras(interior, seed_ref, dropout_rate, block_q,
                              block_k, q_i, kv_i, bh_i), dropout_rate)
            dv_part = jax.lax.dot_general(
                p_v.astype(do.dtype), do, _TN,
                preferred_element_type=jnp.float32)
            dk_part = jax.lax.dot_general(
                ds.astype(q.dtype), q, _TN,
                preferred_element_type=jnp.float32)
            dv = dv_part if dv is None else dv + dv_part
            dk = dk_part if dk is None else dk + dk_part
        dk_ref[kv_at] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[kv_at] = dv.astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, *refs,
                      scale, causal, block_q, block_k, nk, dropout_rate,
                      has_bias=False):
    if has_bias:
        bias_ref, dq_ref, dq_scr = refs
    else:
        bias_ref = None
        dq_ref, dq_scr = refs
    bh_i = pl.program_id(0)
    q_i = pl.program_id(1)
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = (kv_i * block_k <= q_i * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            qpos = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos > qpos, NEG_INF, s)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, dropout_rate, block_q, block_k,
                                 q_i, kv_i, bh_i)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    @pl.when(kv_i == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, *refs,
                       scale, causal, block_q, block_k, nq, dropout_rate,
                       has_bias=False):
    if has_bias:
        bias_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        bias_ref = None
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    bh_i = pl.program_id(0)
    kv_i = pl.program_id(1)
    q_i = pl.program_id(2)

    @pl.when(q_i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = (q_i * block_q + block_q - 1 >= kv_i * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            qpos = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos > qpos, NEG_INF, s)
        p = jnp.exp(s - lse)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, dropout_rate, block_q, block_k,
                                 q_i, kv_i, bh_i)
            inv = 1.0 / (1.0 - dropout_rate)
            p_v = jnp.where(keep, p * inv, 0.0)
        else:
            p_v = p
        dv_scr[:] += jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(q_i == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _fa_bwd_dbias_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, bias_ref, db_ref, db_scr,
                         *, scale, causal, block_q, block_k, nb, num_heads,
                         dropout_rate):
    """dL/dbias for a batch-shared (heads, sq, sk) bias: recompute ds
    blockwise (the flash backward identity) and accumulate over the batch
    (innermost grid dim). dL/ds excludes the q·kᵀ ``scale`` — bias enters
    the logits after scaling."""
    h_i = pl.program_id(0)
    q_i = pl.program_id(1)
    kv_i = pl.program_id(2)
    b_i = pl.program_id(3)
    bh_i = b_i * num_heads + h_i

    @pl.when(b_i == 0)
    def _init():
        db_scr[:] = jnp.zeros_like(db_scr)

    run = (kv_i * block_k <= q_i * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            qpos = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos > qpos, NEG_INF, s)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, dropout_rate, block_q, block_k,
                                 q_i, kv_i, bh_i)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        db_scr[:] += p * (dp - delta)

    @pl.when(b_i == nb - 1)
    def _finish():
        db_ref[0] = db_scr[:].astype(db_ref.dtype)


def _fa_bwd(q3, k3, v3, o3, lse, do3, scale, causal, block_q, block_k,
            interpret, dropout_rate=0.0, seed=None, bias=None):
    """(dq, dk, dv, dbias or None) on the schedule ``_tile_plan`` gives the
    shape — the forward's."""
    bh, sq, d = q3.shape
    sk, d_v = k3.shape[1], v3.shape[2]
    seed = _seed3(seed)
    has_bias = bias is not None
    plan = _tile_plan(sq, sk, d, q3.dtype, causal, block_q, block_k, has_bias)
    block_q, block_k = plan.block_q, plan.block_k
    nq = sq // block_q
    nk = sk // block_k
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dq_shape = _sds((bh, sq, d), q3.dtype, q3, k3, v3, do3)
    dkv_shape = [
        _sds((bh, sk, d), k3.dtype, q3, k3, v3, do3),
        _sds((bh, sk, d_v), v3.dtype, q3, k3, v3, do3),
    ]

    if plan.schedule == "resident":
        static = dict(scale=scale, causal=causal, block_q=block_q,
                      block_k=block_k, dropout_rate=dropout_rate)
        call = dict(
            grid=(bh,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      _head_spec(sq, d), _head_spec(sk, d), _head_spec(sk, d_v),
                      _head_spec(sq, d_v), _head_spec(sq, 1), _head_spec(sq, 1)],
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret)
        inputs = (seed, q3, k3, v3, do3, lse, delta)
        dq = pl.pallas_call(
            functools.partial(_fa_bwd_dq_resident_kernel, **static),
            name="flash_bwd_dq", out_specs=_head_spec(sq, d),
            out_shape=dq_shape, **call)(*inputs)
        dk, dv = pl.pallas_call(
            functools.partial(_fa_bwd_dkv_resident_kernel, **static),
            name="flash_bwd_dkv",
            out_specs=[_head_spec(sk, d), _head_spec(sk, d_v)],
            out_shape=dkv_shape, **call)(*inputs)
        return dq, dk, dv, None

    dq_kernel = functools.partial(
        _fa_bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk, dropout_rate=dropout_rate,
        has_bias=has_bias)
    # same causal diagonal clamp as the forward (elide masked-block DMA)
    def kv_index(b, i, j):
        if causal:
            j = jnp.minimum(j, _kv_lim(i, block_q, block_k))
        return (b, j, 0)

    dq_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d_v), kv_index),
        pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    dq_inputs = [seed, q3, k3, v3, do3, lse, delta]
    if has_bias:
        dq_specs.append(_bias_spec(bias.shape[0], block_q, block_k, causal))
        dq_inputs.append(bias)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*dq_inputs)

    dkv_kernel = functools.partial(
        _fa_bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nq=nq, dropout_rate=dropout_rate,
        has_bias=has_bias)
    # dK/dV mirror clamp: for kv block j the first live q block is
    # (j*block_k)//block_q; earlier (masked-out) iterations re-request it,
    # eliding their q/do/lse/delta DMA
    def q_clamp(i, j):
        return jnp.maximum(i, (j * block_k) // block_q) if causal else i

    dkv_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, q_clamp(i, j), 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d_v), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d_v), lambda b, j, i: (b, q_clamp(i, j), 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, q_clamp(i, j), 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, q_clamp(i, j), 0)),
    ]
    dkv_inputs = [seed, q3, k3, v3, do3, lse, delta]
    if has_bias:
        num_heads = bias.shape[0]
        dkv_specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, j, i: (jax.lax.rem(b, num_heads), q_clamp(i, j), j)))
        dkv_inputs.append(bias)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(bh, nk, nq),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=dkv_shape,
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*dkv_inputs)

    if not has_bias:
        return dq, dk, dv, None

    num_heads = bias.shape[0]
    nb = bh // num_heads
    dbias_kernel = functools.partial(
        _fa_bwd_dbias_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nb=nb, num_heads=num_heads,
        dropout_rate=dropout_rate)
    def b_live(i, j, b):
        # tiles above the causal diagonal never compute: pin their batch
        # fetch to item 0 so the repeated index elides the per-b DMA
        if not causal:
            return b
        return jnp.where(j * block_k <= i * block_q + block_q - 1, b, 0)

    db = pl.pallas_call(
        dbias_kernel,
        name="flash_bwd_dbias",
        # batch innermost ("arbitrary"): the (h, q, k) tile accumulates
        # its batch sum in scratch and writes once at the last batch item
        grid=(num_heads, nq, nk, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d),
                         lambda h, i, j, b: (b_live(i, j, b) * num_heads + h,
                                             i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda h, i, j, b: (b_live(i, j, b) * num_heads + h,
                                             j, 0)),
            pl.BlockSpec((1, block_k, d_v),
                         lambda h, i, j, b: (b_live(i, j, b) * num_heads + h,
                                             j, 0)),
            pl.BlockSpec((1, block_q, d_v),
                         lambda h, i, j, b: (b_live(i, j, b) * num_heads + h,
                                             i, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda h, i, j, b: (b_live(i, j, b) * num_heads + h,
                                             i, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda h, i, j, b: (b_live(i, j, b) * num_heads + h,
                                             i, 0)),
            pl.BlockSpec((1, block_q, block_k), lambda h, i, j, b: (h, i, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda h, i, j, b: (h, i, j)),
        out_shape=_sds((num_heads, sq, sk), jnp.float32, q3, k3, v3, do3),
        scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(seed, q3, k3, v3, do3, lse, delta, bias)
    return dq, dk, dv, db


# ---------------------------------------------------------------------------
# The listed schedule: the grid walks the live tiles (``_listed_tiles``).
# Scalar-prefetched ``tiles`` is (3, n): q tile, K/V tile, flags.

def _listed_scores(tiles_ref, t, q, k, scale, structure, block_q, block_k,
                   sq, masked):
    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32
                            ) * scale
    if masked:
        qpos = tiles_ref[0, t] * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = tiles_ref[1, t] * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(structure.hidden(qpos, kpos, sq), NEG_INF, s)
    return s


def _on_tile_kind(flags, structure, body):
    """Run ``body(masked)`` once: with the mask on a tile that straddles an
    edge, without on a whole one."""
    if structure is None:
        body(False)
        return
    pl.when(flags & 4 != 0)(functools.partial(body, True))
    pl.when(flags & 4 == 0)(functools.partial(body, False))


def _fa_fwd_listed_kernel(tiles_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                          m_scr, l_scr, acc_scr, *, scale, structure,
                          block_q, block_k, sq):
    t = pl.program_id(1)
    flags = tiles_ref[2, t]

    @pl.when(flags & 1 != 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def body(masked):
        v = v_ref[0]
        s = _listed_scores(tiles_ref, t, q_ref[0], k_ref[0], scale,
                           structure, block_q, block_k, sq, masked)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _on_tile_kind(flags, structure, body)

    @pl.when(flags & 2 != 0)
    def _finish():
        # a row's live tiles show every query a key, so l >= 1 here: no guard
        # as in the streamed _finish (a query that saw none in an earlier
        # tile, at a window's edge, is zeroed by the first tile that shows one)
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)
        # lse leaves lane-dense, a row of block_q (the scratch holds it across
        # its 128 lanes, so its transpose holds it in every row): a column
        # (sq, 1) in HBM is padded to 128 lanes, 512 MB at 64 x 16,384
        lse_ref[0] = jnp.transpose(m_scr[:] + jnp.log(l_scr[:]))[:1]


def _listed_ds(tiles_ref, t, q, k, v, do, stats, scale, structure,
               block_q, block_k, sq, masked):
    """(p, dL/ds x scale) of one tile, scores recomputed from the saved lse.
    ``stats`` (2, block_q) holds lse and delta as lane-dense rows; one
    transpose turns both into the columns the scores need."""
    rows = jnp.concatenate([jnp.broadcast_to(stats[:1], (8, block_q)),
                            jnp.broadcast_to(stats[1:], (120, block_q))])
    cols = jnp.transpose(rows)
    lse, delta = cols[:, :1], cols[:, 8:9]
    s = _listed_scores(tiles_ref, t, q, k, scale, structure, block_q,
                       block_k, sq, masked)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * scale


def _fa_bwd_dq_listed_kernel(tiles_ref, q_ref, k_ref, v_ref, do_ref,
                             stats_ref, dq_ref, dq_scr, *, scale, structure,
                             block_q, block_k, sq):
    t = pl.program_id(1)
    flags = tiles_ref[2, t]

    @pl.when(flags & 1 != 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def body(masked):
        k = k_ref[0]
        _, ds = _listed_ds(tiles_ref, t, q_ref[0], k, v_ref[0], do_ref[0],
                           stats_ref[0], scale, structure, block_q, block_k,
                           sq, masked)
        dq_scr[:] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    _on_tile_kind(flags, structure, body)

    @pl.when(flags & 2 != 0)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_listed_kernel(tiles_ref, q_ref, k_ref, v_ref, do_ref,
                              stats_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                              scale, structure, block_q, block_k, sq, group):
    """One K/V head a grid row; a K/V tile's live q tiles, and for each the
    ``group`` query heads that read this K/V head (innermost), all summed
    into dK and dV before they are written."""
    t = pl.program_id(1)
    g = pl.program_id(2)
    flags = tiles_ref[2, t]

    @pl.when((flags & 1 != 0) & (g == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def body(masked):
        q, do = q_ref[0], do_ref[0]
        p, ds = _listed_ds(tiles_ref, t, q, k_ref[0], v_ref[0], do,
                           stats_ref[0], scale, structure, block_q, block_k,
                           sq, masked)
        dv_scr[:] += jax.lax.dot_general(p.astype(do.dtype), do, _TN,
                                         preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(ds.astype(q.dtype), q, _TN,
                                         preferred_element_type=jnp.float32)

    _on_tile_kind(flags, structure, body)

    @pl.when((flags & 2 != 0) & (g == group - 1))
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _listed_params(interpret, semantics):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=semantics)


def _fa_fwd_listed(q3, k3, v3, scale, structure, block_q, block_k, interpret):
    """(o, lse (bh, 1, sq)); ``k3``, ``v3`` hold ``bh // group`` heads."""
    bh, sq, d = q3.shape
    sk, d_v = k3.shape[1], v3.shape[2]
    group = bh // k3.shape[0]
    by_row, _ = _listed_tiles(structure, sq, sk, block_q, block_k)
    row = lambda b, t, tiles: (b, tiles[0, t], 0)
    kv = lambda b, t, tiles: (b // group, tiles[1, t], 0)
    return pl.pallas_call(
        functools.partial(_fa_fwd_listed_kernel, scale=scale,
                          structure=structure, block_q=block_q,
                          block_k=block_k, sq=sq),
        name="flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, by_row.shape[1]),
            in_specs=[pl.BlockSpec((1, block_q, d), row),
                      pl.BlockSpec((1, block_k, d), kv),
                      pl.BlockSpec((1, block_k, d_v), kv)],
            out_specs=[pl.BlockSpec((1, block_q, d_v), row),
                       pl.BlockSpec((1, 1, block_q),
                                    lambda b, t, tiles: (b, 0, tiles[0, t]))],
            scratch_shapes=[pltpu.VMEM((block_q, 128), jnp.float32),
                            pltpu.VMEM((block_q, 128), jnp.float32),
                            pltpu.VMEM((block_q, d_v), jnp.float32)]),
        out_shape=[_sds((bh, sq, d_v), q3.dtype, q3, k3, v3),
                   _sds((bh, 1, sq), jnp.float32, q3, k3, v3)],
        compiler_params=_listed_params(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(by_row), q3, k3, v3)


def _fa_bwd_listed(q3, k3, v3, o3, lse, do3, scale, structure, block_q,
                   block_k, interpret):
    """(dq, dk, dv) on the listed schedule; dk, dv in K/V's own head count."""
    bh, sq, d = q3.shape
    bkv, sk, _ = k3.shape
    d_v = v3.shape[2]
    group = bh // bkv
    by_row, by_col = _listed_tiles(structure, sq, sk, block_q, block_k)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)
    stats = jnp.concatenate([lse, delta[:, None]], axis=1)     # (bh, 2, sq)
    static = dict(scale=scale, structure=structure, block_q=block_q,
                  block_k=block_k, sq=sq)
    row = lambda b, t, tiles: (b, tiles[0, t], 0)
    kv = lambda b, t, tiles: (b // group, tiles[1, t], 0)
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_listed_kernel, **static),
        name="flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, by_row.shape[1]),
            in_specs=[pl.BlockSpec((1, block_q, d), row),
                      pl.BlockSpec((1, block_k, d), kv),
                      pl.BlockSpec((1, block_k, d_v), kv),
                      pl.BlockSpec((1, block_q, d_v), row),
                      pl.BlockSpec((1, 2, block_q),
                                   lambda b, t, tiles: (b, 0, tiles[0, t]))],
            out_specs=pl.BlockSpec((1, block_q, d), row),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=_sds((bh, sq, d), q3.dtype, q3, k3, v3, do3),
        compiler_params=_listed_params(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(by_row), q3, k3, v3, do3, stats)

    head = lambda c, t, g, tiles: (c * group + g, tiles[0, t], 0)
    col = lambda c, t, g, tiles: (c, tiles[1, t], 0)
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_listed_kernel, group=group, **static),
        name="flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bkv, by_col.shape[1], group),
            in_specs=[pl.BlockSpec((1, block_q, d), head),
                      pl.BlockSpec((1, block_k, d), col),
                      pl.BlockSpec((1, block_k, d_v), col),
                      pl.BlockSpec((1, block_q, d_v), head),
                      pl.BlockSpec((1, 2, block_q),
                                   lambda c, t, g, tiles:
                                   (c * group + g, 0, tiles[0, t]))],
            out_specs=[pl.BlockSpec((1, block_k, d), col),
                       pl.BlockSpec((1, block_k, d_v), col)],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d_v), jnp.float32)]),
        out_shape=[_sds((bkv, sk, d), k3.dtype, q3, k3, v3, do3),
                   _sds((bkv, sk, d_v), v3.dtype, q3, k3, v3, do3)],
        compiler_params=_listed_params(
            interpret, ("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(by_col), q3, k3, v3, do3, stats)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash3_listed(q3, k3, v3, scale, structure, block_q, block_k, interpret):
    o, _ = _fa_fwd_listed(q3, k3, v3, scale, structure, block_q, block_k,
                          interpret)
    return o


def _flash3_listed_fwd(q3, k3, v3, scale, structure, block_q, block_k,
                       interpret):
    o, lse = _fa_fwd_listed(q3, k3, v3, scale, structure, block_q, block_k,
                            interpret)
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q3, k3, v3, o, lse)


def _flash3_listed_bwd(scale, structure, block_q, block_k, interpret, res,
                       do3):
    q3, k3, v3, o3, lse = res
    return _fa_bwd_listed(q3, k3, v3, o3, lse, do3, scale, structure,
                          block_q, block_k, interpret)


_flash3_listed.defvjp(_flash3_listed_fwd, _flash3_listed_bwd)


# ---------------------------------------------------------------------------
# custom_vjp plumbing over (bh, seq, d) arrays

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash3(q3, k3, v3, seed, scale, causal, block_q, block_k, interpret,
            dropout_rate):
    o, _ = _fa_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret,
                   dropout_rate, seed)
    return o


def _flash3_fwd(q3, k3, v3, seed, scale, causal, block_q, block_k, interpret,
                dropout_rate):
    o, lse = _fa_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret,
                     dropout_rate, seed)
    # named so a remat policy can save EXACTLY the backward's residuals
    # (q/k/v/seed are region inputs; o + lse are the only computed ones) —
    # naming just the public output would still replay the forward kernel
    # to rebuild lse (reviewer-verified). See GPTConfig.remat_policy
    # 'dots_attn'.
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q3, k3, v3, seed, o, lse)


def _flash3_bwd(scale, causal, block_q, block_k, interpret, dropout_rate,
                res, do3):
    q3, k3, v3, seed, o3, lse = res
    dq, dk, dv, _ = _fa_bwd(q3, k3, v3, o3, lse, do3, scale, causal, block_q,
                            block_k, interpret, dropout_rate, seed)
    return dq, dk, dv, None


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


# Bias-carrying variant: same kernels with the additive (heads, sq, sk)
# logit bias (T5 relative position bias) threaded through forward and all
# three backward kernels; the extra dbias kernel batch-reduces dL/ds.

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash3_bias(q3, k3, v3, bias, seed, scale, causal, block_q, block_k,
                 interpret, dropout_rate):
    o, _ = _fa_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret,
                   dropout_rate, seed, bias=bias)
    return o


def _flash3_bias_fwd(q3, k3, v3, bias, seed, scale, causal, block_q, block_k,
                     interpret, dropout_rate):
    o, lse = _fa_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret,
                     dropout_rate, seed, bias=bias)
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q3, k3, v3, bias, seed, o, lse)


def _flash3_bias_bwd(scale, causal, block_q, block_k, interpret, dropout_rate,
                     res, do3):
    q3, k3, v3, bias, seed, o3, lse = res
    dq, dk, dv, db = _fa_bwd(q3, k3, v3, o3, lse, do3, scale, causal,
                             block_q, block_k, interpret, dropout_rate, seed,
                             bias=bias)
    return dq, dk, dv, db.astype(bias.dtype), None


_flash3_bias.defvjp(_flash3_bias_fwd, _flash3_bias_bwd)


def flash_attention_with_lse(q3, k3, v3, scale, causal, block_q, block_k,
                             interpret):
    """Forward-only variant returning (o, lse) with lse (bh, sq) — the
    ring-attention building block (merging partial results needs the
    log-sum-exp). Not differentiable; ring attention differentiates through
    its own recompute."""
    o, lse = _fa_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# Public API

def _flash_listed(q, k, v, mask, structure, scale, block_q, block_k,
                  use_pallas, dropout_rate, bias, interpret):
    """``flash_attention`` for a structure other than causal, or grouped
    heads: the listed kernels where the shapes tile, the reference (dense
    mask, K/V repeated) elsewhere."""
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    if dropout_rate > 0.0 or bias is not None:
        raise NotImplementedError(
            "attention dropout and the additive bias are not written for "
            "the listed schedule (a MaskStructure other than causal, or "
            "K/V with fewer heads than Q)")
    dv = v.shape[3]
    bq = bk = None
    tiles = (mask is None and _pick_block(sq, 128) is not None
             and _pick_block(sk, 128) is not None and d % 8 == 0
             and dv % 8 == 0)
    if tiles:
        bq, bk = _listed_tile(structure, sq, sk, d, dv, q.dtype, block_q,
                              block_k)
        tiles = structure is None or structure.tiles(sq, sk, bq, bk)
    if use_pallas is None:
        use_pallas = tiles and _compiled_backend()
    elif use_pallas and not tiles:
        raise ValueError(
            f"pallas flash_attention with structure={structure} needs no "
            f"dense mask, seq divisible by a block size, head_dim % 8 == 0 "
            f"and tiles that lie within the structure's quadrants "
            f"(got q {q.shape}, k {k.shape}, tile {bq} x {bk})")
    if not use_pallas:
        if interpret is not None:
            raise ValueError(
                "interpret= only applies to the Pallas path; this call "
                "resolved to the reference")
        return attention_reference(q, k, v, mask=mask, scale=scale,
                                   structure=structure)
    if interpret is None:
        interpret = not _compiled_backend()
    o3 = _flash3_listed(q.reshape(b * h, sq, d), k.reshape(b * hk, sk, d),
                        v.reshape(b * hk, sk, dv), scale, structure, bq, bk,
                        interpret)
    return o3.reshape(b, h, sq, dv)


def _pick_block(seq: int, want: int) -> Optional[int]:
    for cand in (want, 512, 256, 128, 64, 32, 16, 8):
        if cand <= want and seq % cand == 0:
            return cand
    return None


def _pallas_ok(sq, sk, d, causal, allow_interpret, dv=None):
    if _pick_block(sq, 128) is None or _pick_block(sk, 128) is None:
        return False
    if d % 8 != 0 or (dv or d) % 8 != 0:
        return False
    if causal and sq != sk:
        return False
    return allow_interpret or _compiled_backend()


def flash_attention(
    q, k, v,
    mask=None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    dropout_rate: float = 0.0,
    dropout_seed=None,
    bias=None,
    interpret: Optional[bool] = None,
    structure: Optional[MaskStructure] = None,
):
    """Memory-efficient attention over (batch, heads, seq, head_dim).

    ``v`` may have another width than ``q`` and ``k`` (keys of 192 over
    values of 128: latent attention's expanded heads); the output has
    ``v``'s. Every schedule takes it: the score's products run over
    ``q``'s width, ``p @ v`` and ``do @ v.T`` over ``v``'s, and nothing is
    padded to make them equal.

    Pallas flash kernels on aligned shapes (ref capability: ``fmhalib`` +
    ``fast_multihead_attn``, without their seqlen ≤ 512 limit) for no mask
    and for a mask given as a **structure** (:class:`MaskStructure`):
    ``causal=True`` is ``structure=CAUSAL``. A head the resident schedule
    does not hold runs the listed one, which skips the tiles the structure
    hides and builds a mask only on the tiles that straddle one of its
    edges; a bias or dropout keeps such a call streamed. An arbitrary
    dense ``mask`` (True = masked out) or an odd shape takes the XLA
    reference path, which materialises the scores.

    ``block_q`` / ``block_k``: numbers bound the tile from above; ``None``
    leaves it to the plan (``_tile_plan``): 512 on the resident and
    streamed schedules, on the listed one the tile of least fitted cost.

    ``k`` and ``v`` may have fewer heads than ``q`` (a divisor of its head
    count): query head ``i`` reads K/V head ``i // group``. On the kernels
    that is the K/V block's index map (the listed schedule); nothing is
    repeated in HBM and ``dk``, ``dv`` come back in K/V's own head count.

    ``bias``: optional batch-shared additive logit bias of shape
    (heads, sq, sk) — the T5 relative-position-bias contract. It rides the
    Pallas path (added to the score tile inside all kernels; its gradient
    comes from a dedicated batch-reducing kernel) and is differentiable.
    Note the compiled TPU path tiles the bias (block_q, block_k), so sk
    must be a multiple of 128 or fit one block; the reference fallback has
    no such limit.

    ``dropout_rate`` > 0 applies probability dropout to the (normalized)
    attention weights *inside* the kernel — the counter-based keep mask is
    regenerated identically in forward and backward from ``dropout_seed``
    (an int32 scalar/array; required when the rate is nonzero), so training
    configs with attention dropout stay on the Pallas path. The non-pallas
    fallback materializes the SAME counter-hash mask, so the result does
    not depend on which dispatch path ran.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    hk = k.shape[1]
    dv = v.shape[3]
    if k.shape[3] != d:
        raise ValueError(f"q and k must have one width ({d}, {k.shape[3]}); "
                         f"v may have its own")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 needs dropout_seed")
    if structure is not None and causal and structure != CAUSAL:
        raise ValueError(f"causal=True and structure={structure} are two "
                         f"masks; give one")
    if hk != v.shape[1] or h % hk:
        raise ValueError(f"K/V heads ({hk}, {v.shape[1]}) must be equal and "
                         f"divide the query heads ({h})")
    if structure == CAUSAL:
        structure, causal = None, True      # one spelling: the plan decides
    if structure is not None or hk != h:
        return _flash_listed(q, k, v, mask, CAUSAL if causal else structure,
                             scale, block_q, block_k, use_pallas,
                             dropout_rate, bias, interpret)
    if bias is not None and bias.shape != (h, sq, sk):
        raise ValueError(
            f"bias must be batch-shared (heads, sq, sk) = {(h, sq, sk)}, "
            f"got {bias.shape}")
    pallas_possible = mask is None and _pallas_ok(
        sq, sk, d, causal, allow_interpret=True, dv=dv)
    if use_pallas is None:
        use_pallas = mask is None and _pallas_ok(
            sq, sk, d, causal, allow_interpret=False, dv=dv)
    elif use_pallas and not pallas_possible:
        raise ValueError(
            f"pallas flash_attention needs no dense mask (a mask rides the "
            f"kernels as a MaskStructure: causal, block_diffusion_mask), "
            f"seq divisible by a block size, head_dim % 8 == 0, and "
            f"sq == sk when causal "
            f"(got q {q.shape}, k {k.shape}, causal={causal}, "
            f"mask={'set' if mask is not None else None})")
    if not use_pallas:
        if interpret is not None:
            raise ValueError(
                "interpret= only applies to the Pallas path; this call "
                "resolved to the reference (pass use_pallas=True to force "
                "the kernel, or drop interpret=)")
        keep = None
        if dropout_rate > 0.0:
            # the kernels' counter-hash stream, NOT a jax.random draw: the
            # fallback must drop the same entries as the compiled kernel
            # (and the ring's chunks) for the same seed, or results change
            # with the dispatch path
            keep = attention_dropout_mask(
                jnp.asarray(dropout_seed).reshape(()), float(dropout_rate),
                b * h, sq, sk).reshape(b, h, sq, sk)
        return attention_reference(q, k, v, mask=mask, scale=scale,
                                   causal=causal, dropout_rate=dropout_rate,
                                   dropout_keep=keep, bias=bias)
    plan = _tile_plan(sq, sk, d, q.dtype, causal, block_q, block_k,
                      has_bias=bias is not None, dropout=dropout_rate > 0.0,
                      whole_rows=True, dv=dv)
    bq, bk = plan.block_q, plan.block_k
    if interpret is None:
        interpret = not _compiled_backend()
    if plan.schedule == "listed":
        o3 = _flash3_listed(
            q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
            v.reshape(b * h, sk, dv), scale, CAUSAL if causal else None, bq,
            bk, interpret)
        return o3.reshape(b, h, sq, dv)
    seed = (jnp.zeros((1,), jnp.int32) if dropout_seed is None
            else jnp.asarray(dropout_seed, jnp.int32).reshape((1,)))
    if bias is not None:
        o3 = _flash3_bias(
            q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
            v.reshape(b * h, sk, dv), bias, seed, scale, causal, bq, bk,
            interpret, float(dropout_rate))
        return o3.reshape(b, h, sq, dv)
    o3 = _flash3(
        q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
        v.reshape(b * h, sk, dv), seed, scale, causal, bq, bk, interpret,
        float(dropout_rate))
    return o3.reshape(b, h, sq, dv)


# ---------------------------------------------------------------------------
# The packed entry: head size 64, half of the 128 lanes.
#
# (batch, heads, seq, head_dim) at head_dim 64 fills half of each 128-lane
# tile: in HBM every operand is padded to twice its bytes, and XLA keeps the
# projections on either side in other layouts and copies between them (17
# copies a GPT-2 layer, forward, replay and backward; PERF.md section 6, PR
# 36). Here the operands keep the layout the projections give and take: the
# QKV product as it stands, (batch, seq, heads x [q k v] x 64), and o as
# (batch, seq, heads x 64). A pair of heads fills whole lanes: 384 of the
# product, three blocks of 128, [q0 k0] [v0 q1] [k1 v1], and 128 of o,
# [o0 o1]. A grid step runs one head of a pair on the resident schedule's
# kernel bodies as they are, on 128-lane operands: the head's q with its
# neighbour's half zeroed and the halves swapped (one lane rotation), so it
# meets k in k's half and the other half adds exact zeros to every score;
# k's and v's blocks whole. The MXU contracts over 128 lanes at head size 64
# either way, and its results are 128 lanes wide either way: the half of
# p @ [v0 q1] that is o0 lands in o0's lanes, and so do dk and dv in theirs
# (dq lands in k's and is rotated back), so nothing is sliced or stored by
# halves: a result is written over its half of the pair's block, the other
# half kept. lse is lane-dense, (batch, pairs, 2, seq); delta is summed in
# the kernels from do and o. dq leaves its kernel in o's layout and the
# dK/dV kernel writes the three gradients as [dq0 dk0] [dv0 dq1] [dk1 dv1]:
# the product's cotangent, as the weight-gradient product reads it. What it
# costs in the kernels: an ``a @ b.T`` product's right operand (k, v) is
# turned at 128 lanes where (rows, 64) turned at 64 (PERF.md section 6).

_PACKED_D = 64


def packed_plan(seq, heads, d, dtype, causal, block_q=512,
                block_k=512) -> Optional[TilePlan]:
    """The plan of a packed call, or None where the shape is not one the
    packed kernels take: head size 64 (half the lanes: a smaller divisor of
    128 would want a rotation that differs by head), whole pairs of heads,
    and a call the plan holds resident."""
    if d != _PACKED_D or heads % 2 or _pick_block(seq, 128) is None:
        return None
    plan = _tile_plan(seq, seq, d, dtype, causal, block_q, block_k)
    return plan if plan.schedule == "resident" else None


def _half_of_head(p, rows):
    """(rows, 128) mask of the lanes that are head ``p``'s (the head of the
    pair, traced or not) in a block that holds a pair's operand side by
    side."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
    return (lane >= _PACKED_D) ^ (p == 0)


def _pick_half(half, x, other=None):
    """``x`` on the lanes of ``half`` and ``other`` (zeros if None) on the
    rest."""
    return jax.lax.select(half, x, jnp.zeros_like(x) if other is None
                          else other)


def _swap_halves(x):
    """The two 64-lane halves of a (rows, 128) tile swapped: one lane
    rotation, on 32-bit words (Mosaic rotates nothing narrower; a bfloat16
    tile is rotated as the words that hold two of its rows, and a lane is a
    lane either way)."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, _PACKED_D, 1)
    words = pltpu.roll(pltpu.bitcast(x, jnp.uint32), _PACKED_D, 1)
    return pltpu.bitcast(words, x.dtype)


class _HeadOf:
    """A head of a (1, rows, 128) block that holds a pair's operand, read
    as the resident kernels read a head's block: the other head's half
    zeroed (``half``: the head's lanes), so a product over the 128 lanes is
    the head's own. ``swap``: the halves swapped, which brings q into k's
    half."""

    def __init__(self, ref, half, swap=False):
        self.ref, self.half, self.swap = ref, half, swap
        self.shape = ref.shape

    def __getitem__(self, at):
        x = _pick_half(self.half, self.ref[at])
        return _swap_halves(x) if self.swap else x


class _IntoHalf:
    """A pair's result block (1, rows, 128), written as the resident
    kernels write a head's: the 128-lane result's half ``half`` is the
    head's, the other is left as it is. ``swap``: the result's halves
    swapped first (dq comes out in k's half)."""

    def __init__(self, ref, half, swap=False):
        self.ref, self.half, self.swap = ref, half, swap
        self.shape, self.dtype = ref.shape, ref.dtype

    def __setitem__(self, at, value):
        if self.swap:
            value = _swap_halves(value)
        self.ref[at] = _pick_half(self.half, value, self.ref[at])


class _Head:
    """Head ``p`` (traced) of a scratch ref (2, rows, 128), written as the
    one-head block (1, rows, 128) the resident kernels write."""

    def __init__(self, ref, p):
        self.ref, self.p = ref, p
        self.shape, self.dtype = (1,) + ref.shape[1:], ref.dtype

    def __setitem__(self, at, value):
        self.ref[(self.p,) + at[1:]] = value


class _HeadStats:
    """Row ``p`` (traced) of a lane-dense statistics block (1, 1, 2, seq),
    indexed as the (1, seq, 1) column the resident kernels take: a tile's
    rows fill a (128, rows) tile's sublanes and one transpose turns them
    (the listed schedule's way)."""

    def __init__(self, ref, p):
        self.ref, self.p = ref, p

    def __getitem__(self, at):
        row = self.ref[0, 0, pl.ds(self.p, 1), at[1]]
        return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, :1]

    def __setitem__(self, at, col):
        tile = jnp.broadcast_to(col, (col.shape[0], 128))
        self.ref[0, 0, pl.ds(self.p, 1), at[1]] = jnp.transpose(tile)[:1]


class _HeadDelta:
    """delta = rowsum(do x o) over a head's half of the pair's blocks, as
    the (1, seq, 1) column the resident kernels take. ``do``: the head's
    ``_HeadOf``, the other half zeroed."""

    def __init__(self, do, o_ref):
        self.do, self.o_ref = do, o_ref

    def __getitem__(self, at):
        return jnp.sum(self.do[at].astype(jnp.float32)
                       * self.o_ref[at].astype(jnp.float32),
                       axis=1, keepdims=True)


def _head_and_half(block_q):
    """(head of the pair this grid step runs, the mask of its lanes over a
    q tile)."""
    p = pl.program_id(2)
    return p, _half_of_head(p, block_q)


def _fa_fwd_packed_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                          **static):
    p, half = _head_and_half(static["block_q"])
    _fa_fwd_resident_kernel(
        seed_ref, _HeadOf(q_ref, half, swap=True), k_ref, v_ref,
        _IntoHalf(o_ref, half), _HeadStats(lse_ref, p), **static)


def _fa_bwd_dq_packed_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                             lse_ref, dq_ref, **static):
    p, half = _head_and_half(static["block_q"])
    do = _HeadOf(do_ref, half)
    _fa_bwd_dq_resident_kernel(
        seed_ref, _HeadOf(q_ref, half, swap=True), k_ref, v_ref, do,
        _HeadStats(lse_ref, p), _HeadDelta(do, o_ref),
        _IntoHalf(dq_ref, half, swap=True), **static)


def _fa_bwd_dkv_packed_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                              lse_ref, dq_ref, dqkv_ref, dk_scr, dv_scr,
                              **static):
    """dk and dv of the pair's heads (each in its own half: dk in k's, where
    the swapped q put it, dv in v's), written with the dq kernel's result as
    the packed product's cotangent."""
    p, half = _head_and_half(static["block_q"])
    do = _HeadOf(do_ref, half)
    _fa_bwd_dkv_resident_kernel(
        seed_ref, _HeadOf(q_ref, half, swap=True), k_ref, v_ref, do,
        _HeadStats(lse_ref, p), _HeadDelta(do, o_ref), _Head(dk_scr, p),
        _Head(dv_scr, p), **static)

    @pl.when(p == 1)
    def _pack():
        dq = dq_ref[0]
        low = _half_of_head(0, dq.shape[0])
        dqkv_ref[0] = jnp.concatenate(
            [_pick_half(low, dq, dk_scr[0]), _pick_half(low, dv_scr[0], dq),
             _pick_half(low, dk_scr[1], dv_scr[1])], axis=-1)


def _fa_packed_specs(b, seq, heads, dtype, like):
    """What the three packed kernels share: the grid (a step a row, a pair
    of heads and a head of the pair; over the last no result's block moves
    and Mosaic elides the repeated DMA), the specs of the three 128-lane
    blocks of the product that hold the head's q, k and v, of a pair's
    block of o (do, dq), of the product's whole 384 lanes and of the
    lane-dense statistics."""
    pairs, d = heads // 2, _PACKED_D
    lanes = lambda index: pl.BlockSpec((1, seq, 128), index)
    return dict(
        grid=(b, pairs, 2),
        q=lanes(lambda i, g, p: (i, 0, 3 * g + p)),
        k=lanes(lambda i, g, p: (i, 0, 3 * g + 2 * p)),
        v=lanes(lambda i, g, p: (i, 0, 3 * g + 1 + p)),
        o=lanes(lambda i, g, p: (i, 0, g)),
        qkv=pl.BlockSpec((1, seq, 384), lambda i, g, p: (i, 0, g)),
        stats=pl.BlockSpec((1, 1, 2, seq), lambda i, g, p: (i, g, 0, 0)),
        smem=pl.BlockSpec(memory_space=pltpu.SMEM),
        params=("parallel", "parallel", "arbitrary"),
        o_shape=_sds((b, seq, heads * d), dtype, *like),
        stats_shape=_sds((b, pairs, 2, seq), jnp.float32, *like))


def _fa_fwd_packed(qkv, heads, scale, causal, block_q, block_k, interpret):
    """(o (b, s, heads x 64), lse (b, pairs, 2, s)) of the packed product
    (b, s, heads x 3 x 64) on the resident schedule."""
    b, seq, _ = qkv.shape
    at = _fa_packed_specs(b, seq, heads, qkv.dtype, (qkv,))
    return pl.pallas_call(
        functools.partial(
            _fa_fwd_packed_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, dropout_rate=0.0),
        name="flash_fwd",
        grid=at["grid"],
        in_specs=[at["smem"], at["q"], at["k"], at["v"]],
        out_specs=[at["o"], at["stats"]],
        out_shape=[at["o_shape"], at["stats_shape"]],
        compiler_params=_listed_params(interpret, at["params"]),
        interpret=interpret,
    )(_seed3(None), qkv, qkv, qkv)


def _fa_bwd_packed(qkv, o, lse, do, heads, scale, causal, block_q, block_k,
                   interpret):
    """The packed product's cotangent (b, s, heads x 3 x 64)."""
    b, seq, _ = qkv.shape
    at = _fa_packed_specs(b, seq, heads, qkv.dtype, (qkv, do))
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, dropout_rate=0.0)
    call = dict(grid=at["grid"], interpret=interpret,
                compiler_params=_listed_params(interpret, at["params"]))
    inputs = (_seed3(None), qkv, qkv, qkv, do, o, lse)
    in_specs = [at["smem"], at["q"], at["k"], at["v"], at["o"], at["o"],
                at["stats"]]
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_packed_kernel, **static),
        name="flash_bwd_dq", in_specs=in_specs, out_specs=at["o"],
        out_shape=at["o_shape"], **call)(*inputs)
    return pl.pallas_call(
        functools.partial(_fa_bwd_dkv_packed_kernel, **static),
        name="flash_bwd_dkv", in_specs=in_specs + [at["o"]],
        out_specs=at["qkv"], out_shape=_sds(qkv.shape, qkv.dtype, qkv, do),
        scratch_shapes=[pltpu.VMEM((2, seq, 128), qkv.dtype)] * 2,
        **call)(*inputs, dq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _flash_packed(qkv, heads, scale, causal, block_q, block_k, interpret):
    return _fa_fwd_packed(qkv, heads, scale, causal, block_q, block_k,
                          interpret)[0]


def _flash_packed_fwd(qkv, heads, scale, causal, block_q, block_k, interpret):
    o, lse = _fa_fwd_packed(qkv, heads, scale, causal, block_q, block_k,
                            interpret)
    # the backward's residuals by name, as in ``_flash3_fwd``
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (qkv, o, lse)


def _flash_packed_bwd(heads, scale, causal, block_q, block_k, interpret, res,
                      do):
    qkv, o, lse = res
    return (_fa_bwd_packed(qkv, o, lse, do, heads, scale, causal, block_q,
                           block_k, interpret),)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def unpack_qkv(qkv, heads):
    """The packed product (b, s, heads x [q k v] x d) -> q, k, v in
    :func:`flash_attention`'s layout, (b, heads, s, d)."""
    b, seq, width = qkv.shape
    qkv = qkv.reshape(b, seq, heads, 3, width // (3 * heads))
    return tuple(qkv[:, :, :, i].transpose(0, 2, 1, 3) for i in range(3))


def flash_attention_packed(qkv, heads: int, causal: bool = False,
                           scale: Optional[float] = None, block_q: int = 512,
                           block_k: int = 512,
                           use_pallas: Optional[bool] = None,
                           interpret: Optional[bool] = None):
    """Attention over the QKV product as the projection leaves it:
    ``qkv`` (batch, seq, heads x [q k v] x head_dim), a head's q, k and v
    side by side; returns o as (batch, seq, heads x head_dim), which the
    output projection reads as it stands. The same function as
    :func:`flash_attention` on :func:`unpack_qkv`'s operands, bit for bit
    where both run the kernels: the same tiles and tile bodies on the
    resident schedule, only the operands' indexing differs.

    The packed kernels run where :func:`packed_plan` gives a plan (head
    size 64, whole pairs of heads, a call the resident schedule holds) on a
    compiled backend; any other call goes through :func:`flash_attention`
    and its layout. Dropout, a dense mask and the bias are
    :func:`flash_attention`'s."""
    b, seq, width = qkv.shape
    if width % (3 * heads):
        raise ValueError(f"qkv's width {width} is not heads ({heads}) x 3 x "
                         f"head_dim")
    d = width // (3 * heads)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    plan = packed_plan(seq, heads, d, qkv.dtype, causal, block_q, block_k)
    if use_pallas is None:
        use_pallas = plan is not None and _compiled_backend()
    elif use_pallas and plan is None:
        raise ValueError(
            f"the packed flash kernels need head size {_PACKED_D}, whole "
            f"pairs of heads and a call the resident schedule holds (got "
            f"qkv {qkv.shape}, heads {heads}, causal={causal})")
    if not use_pallas:
        o = flash_attention(*unpack_qkv(qkv, heads), causal=causal,
                            scale=scale, block_q=block_q, block_k=block_k,
                            interpret=interpret)
        return o.transpose(0, 2, 1, 3).reshape(b, seq, heads * d)
    if interpret is None:
        interpret = not _compiled_backend()
    return _flash_packed(qkv, heads, scale, causal, plan.block_q,
                         plan.block_k, interpret)
