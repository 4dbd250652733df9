"""Block-paged KV cache — the inference engine's device memory manager.

Reference context: NVIDIA Apex has no serving story at all — its only
inference artifact is ``amp.initialize(..., opt_level)`` eval-mode half
precision over a stateless module. A TPU decode path lives or dies on KV
memory management: a contiguous per-request cache fragments HBM the moment
requests have different lengths, and re-allocating on every admission
retraces the step. The paged design (vLLM's PagedAttention, here rebuilt
for donated JAX pytrees) splits every sequence's K/V into fixed-size
**blocks** drawn from one shared pool:

* the pool is a single statically-shaped pytree — ``(L, H, num_blocks,
  block_size, head_dim)`` per K and V — threaded through the jitted
  prefill/decode programs with ``donate_argnums``, so the engine never
  re-allocates or retraces as requests come and go;
* a host-side :class:`BlockAllocator` free-list hands block ids to new
  requests and reclaims them at retirement — admission is pure bookkeeping,
  zero device work;
* per-slot **block tables** (``(slots, max_blocks)`` int32) map logical
  token positions to pool blocks; the decode attention gathers through
  them (``apex_tpu.serve.decode``);
* **prefix caching** (``BlockAllocator(prefix_cache=True)``) adds
  content-addressed reuse: full prompt blocks get a chained
  hash-of-token-prefix address (:func:`prefix_block_hashes`), freed
  cached blocks park in an evictable LRU at refcount 0 instead of being
  recycled, and a later request sharing the prefix re-acquires them via
  :meth:`BlockAllocator.lookup` — a shared system prompt costs zero
  prefill flops after its first admission. :func:`copy_block` is the
  copy-on-write escape hatch for the one case where a request must write
  inside a shared block.

Optional int8 KV quantization (``quantized=True``) stores the pools as
int8 codes plus one fp32 scale per (token, head) vector — the
``comm.quantize`` blockwise codec applied at codec-block = ``head_dim``,
so KV HBM traffic drops ~3.6× (``1 + 4/head_dim`` bytes per bf16 element's
2) and the same deterministic round-trip error bounds proven for the
gradient wire apply to the cache.

Byte accounting (:func:`kv_write_bytes_per_token`, :func:`kv_read_bytes`)
uses the same modeled-bytes convention as ``comm.accounting`` — the
engine reports both through the ``monitor`` pipeline.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

Pytree = Any


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Static shape/layout of the paged pools.

    ``num_heads`` is the LOCAL head count (``cfg.num_heads // tp`` inside a
    TP mesh program; the global count on a single device). ``num_blocks``
    is the POOL size shared by every slot — the unit of HBM budgeting:
    ``num_blocks * block_size`` total cacheable tokens.

    Quantized modes: ``quantized=True, bits=8`` is the PR-5 layout (int8
    codes + one fp32 scale per (token, head) head_dim vector);
    ``bits=4`` drops to the sub-8-bit tier — codes nibble-packed two per
    byte (pool leaf last dim = ``head_dim // 2``) and GROUP-quantized
    along head_dim: one **bf16** scale per ``group_size`` consecutive
    channel values (default group = the whole vector, so the pool is
    exactly HALF the int8 pool's bytes at every head_dim — a bf16 scale's
    8-bit mantissa costs ~0.4% relative scale error, an order below the
    4-bit codes' half-step; smaller groups trade scale bytes back for
    code resolution). Scale pools grow a trailing
    ``head_dim // group_size`` dim.
    """

    num_layers: int
    num_heads: int
    head_dim: int
    num_blocks: int
    block_size: int = 16
    dtype: Any = jnp.bfloat16
    # quantized codes + scales via the comm.quantize codec
    quantized: bool = False
    bits: int = 8
    # int4 scale-group length along head_dim; None -> head_dim (one scale
    # per vector, the exact-2x-vs-int8 default)
    group_size: Optional[int] = None

    @property
    def tokens_capacity(self) -> int:
        return self.num_blocks * self.block_size

    @property
    def kv_group(self) -> int:
        """Effective scale-group length along head_dim (the full vector
        unless int4 ``group_size`` narrows it)."""
        if self.bits == 8 or self.group_size is None:
            return self.head_dim
        return self.group_size

    def blocks_for_tokens(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` (ceil)."""
        return -(-n_tokens // self.block_size)

    def validate(self) -> None:
        for name in ("num_layers", "num_heads", "head_dim", "num_blocks",
                     "block_size"):
            if getattr(self, name) <= 0:
                raise ValueError(f"{name} must be positive")
        if self.bits not in (4, 8):
            raise ValueError(f"bits must be 4 or 8, got {self.bits}")
        if self.group_size is not None and self.bits == 8:
            raise ValueError("group_size only applies to the int4 mode "
                             "(int8 scales one full head_dim vector)")
        if self.quantized and self.bits == 4:
            g = self.kv_group
            if self.head_dim % 2:
                raise ValueError(
                    f"int4 KV needs an even head_dim (nibble packing): "
                    f"{self.head_dim}")
            if g % 2 or g <= 0 or self.head_dim % g:
                raise ValueError(
                    f"int4 KV group_size must be even and divide head_dim "
                    f"({self.head_dim}): got {g}")


def init_kv_cache(cfg: KVCacheConfig) -> Dict[str, jnp.ndarray]:
    """Zeroed pool pytree: ``{"k", "v"}`` (+ ``{"k_scale", "v_scale"}`` when
    quantized). One allocation for the engine's whole lifetime; every
    prefill/decode step donates it back in. int4 pools store nibble-packed
    uint8 codes (last dim halved) + per-group scales (trailing
    ``head_dim // group`` dim)."""
    cfg.validate()
    shape = (cfg.num_layers, cfg.num_heads, cfg.num_blocks, cfg.block_size,
             cfg.head_dim)
    if cfg.quantized and cfg.bits == 4:
        code_shape = shape[:-1] + (cfg.head_dim // 2,)
        cache = {"k": jnp.zeros(code_shape, jnp.uint8),
                 "v": jnp.zeros(code_shape, jnp.uint8)}
        sshape = shape[:-1] + (cfg.head_dim // cfg.kv_group,)
        # bf16 scales: half the int8 layout's scale bytes (see the config
        # docstring); scale 1 keeps dequantize(0-codes) well-defined
        cache["k_scale"] = jnp.ones(sshape, jnp.bfloat16)
        cache["v_scale"] = jnp.ones(sshape, jnp.bfloat16)
        return cache
    dt = jnp.int8 if cfg.quantized else cfg.dtype
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if cfg.quantized:
        sshape = shape[:-1]
        # scale 1 keeps dequantize(0-codes) well-defined (codec convention)
        cache["k_scale"] = jnp.ones(sshape, jnp.float32)
        cache["v_scale"] = jnp.ones(sshape, jnp.float32)
    return cache


def _quant_rows(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize (..., head_dim) vectors with the comm.quantize blockwise
    codec at codec-block = head_dim: int8 codes same shape + fp32 scale per
    vector. Deterministic (round-to-nearest) — KV is an activation signal
    read many times, so the unbiased-stochastic mode's extra noise per read
    buys nothing here."""
    from apex_tpu.comm.quantize import quantize_blockwise

    d = x.shape[-1]
    q, s = quantize_blockwise(x.astype(jnp.float32).reshape(-1), d,
                              use_pallas=False)
    return q.reshape(x.shape), s.reshape(x.shape[:-1])


def _dequant_rows(q: jnp.ndarray, s: jnp.ndarray,
                  dtype: Any) -> jnp.ndarray:
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)


def _quant_rows_int4(x: jnp.ndarray, group: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(..., head_dim) vectors -> (packed uint8 codes (..., head_dim/2),
    bf16 scales (..., head_dim/group)) — the comm.quantize int4 math
    (absmax/7 per group, round-to-nearest, ±7 clip, nibble pack) with the
    scale ROUNDED TO bf16 FIRST and the codes computed against that
    stored value, so the half-step bound holds against exactly what the
    pool holds."""
    from apex_tpu.comm.quantize import QMAX4, pack_int4

    d = x.shape[-1]
    g = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // group, group))
    amax = jnp.max(jnp.abs(g), axis=-1)
    scale = jnp.where(amax > 0, amax / QMAX4, 1.0).astype(jnp.bfloat16)
    q = jnp.clip(jnp.round(g / scale.astype(jnp.float32)[..., None]),
                 -QMAX4, QMAX4).astype(jnp.int8)
    return pack_int4(q.reshape(x.shape)), scale


def _dequant_rows_int4(q: jnp.ndarray, s: jnp.ndarray, group: int,
                       dtype: Any) -> jnp.ndarray:
    """Inverse of :func:`_quant_rows_int4`: unpack nibbles, scale per
    group, restore (..., head_dim)."""
    from apex_tpu.comm.quantize import unpack_int4

    codes = unpack_int4(q)                                # (..., D)
    d = codes.shape[-1]
    g = codes.reshape(codes.shape[:-1] + (d // group, group))
    out = g.astype(jnp.float32) * s.astype(jnp.float32)[..., None]
    return out.reshape(codes.shape).astype(dtype)


# ---------------------------------------------------------------------------
# In-graph paged writes/reads. These operate on ONE layer's pools — the
# natural view inside the model's lax.scan over layers (the stacked (L, ...)
# pools ride the scan's xs/ys). Positions map to (block, offset) through the
# slot's block-table row; invalid writes (inactive slot, padded prefill
# position) are routed to an out-of-range pool index and dropped by scatter
# mode="drop" — no branch, no extra compilation.


def _pool_write(pool, values, block_ids, offsets, valid):
    """Scatter ``values`` (H, n, ...) into ``pool`` (H, B, bs, ...) at
    ``(block_ids[i], offsets[i])``; entries with ``valid[i] == False`` are
    dropped (routed out of bounds). Works for both the code pools
    ((H, B, bs, D) <- (H, n, D)) and the scale pools ((H, B, bs) <-
    (H, n)) — indexing touches only dims 1-2."""
    num_blocks = pool.shape[1]
    idx = jnp.where(valid, block_ids, num_blocks)  # OOB -> dropped
    return pool.at[:, idx, offsets].set(values.astype(pool.dtype),
                                        mode="drop")


def paged_write(
    cache_layer: Dict[str, jnp.ndarray],
    cfg: KVCacheConfig,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    block_rows: jnp.ndarray,
    positions: jnp.ndarray,
    valid: jnp.ndarray,
) -> Dict[str, jnp.ndarray]:
    """Write per-token K/V into one layer's pools.

    ``cache_layer``: ``{"k": (H, B, bs, D), "v": ...}`` (+ scales when
    quantized). ``k_new``/``v_new``: (H, n, head_dim) — n tokens (one per
    decode slot, or the prompt positions of one prefill). ``block_rows``:
    (n, max_blocks) int32 block-table rows owning each token.
    ``positions``: (n,) int32 logical token positions. ``valid``: (n,) bool
    — False entries (inactive slots, bucket padding past the prompt) are
    dropped.
    """
    bs = cfg.block_size
    mb = block_rows.shape[1]
    block_ids = jnp.take_along_axis(
        block_rows, jnp.minimum(positions[:, None] // bs, mb - 1), axis=1
    )[:, 0]
    offsets = positions % bs
    valid = valid & (positions < mb * bs)
    out = dict(cache_layer)
    if cfg.quantized:
        if cfg.bits == 4:
            kq, ks = _quant_rows_int4(k_new, cfg.kv_group)
            vq, vs = _quant_rows_int4(v_new, cfg.kv_group)
        else:
            kq, ks = _quant_rows(k_new)
            vq, vs = _quant_rows(v_new)
        out["k"] = _pool_write(cache_layer["k"], kq, block_ids, offsets,
                               valid)
        out["v"] = _pool_write(cache_layer["v"], vq, block_ids, offsets,
                               valid)
        out["k_scale"] = _pool_write(cache_layer["k_scale"], ks, block_ids,
                                     offsets, valid)
        out["v_scale"] = _pool_write(cache_layer["v_scale"], vs, block_ids,
                                     offsets, valid)
    else:
        out["k"] = _pool_write(cache_layer["k"], k_new, block_ids, offsets,
                               valid)
        out["v"] = _pool_write(cache_layer["v"], v_new, block_ids, offsets,
                               valid)
    return out


def gather_kv(
    cache_layer: Dict[str, jnp.ndarray],
    cfg: KVCacheConfig,
    block_tables: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Assemble contiguous K/V from one layer's pools through the block
    tables.

    ``block_tables``: (n, max_blocks) int32. Returns ``(k, v)`` of shape
    (n, H, max_blocks*block_size, head_dim) in ``cfg.dtype`` — dequantized
    when the cache is int8. The gather is exact: positions never written
    come back as whatever the pool holds and MUST be masked by the caller's
    context lengths.
    """
    def grab(pool):
        g = pool[:, block_tables]  # (H, n, mb, bs[, D])
        h, n, mb, bs = g.shape[:4]
        tail = g.shape[4:]
        perm = (1, 0, 2, 3) + tuple(range(4, g.ndim))
        return g.transpose(perm).reshape((n, h, mb * bs) + tail)

    k, v = grab(cache_layer["k"]), grab(cache_layer["v"])
    if cfg.quantized and cfg.bits == 4:
        ks, vs = grab(cache_layer["k_scale"]), grab(cache_layer["v_scale"])
        k = _dequant_rows_int4(k, ks, cfg.kv_group, cfg.dtype)
        v = _dequant_rows_int4(v, vs, cfg.kv_group, cfg.dtype)
    elif cfg.quantized:
        k = _dequant_rows(k, grab(cache_layer["k_scale"]), cfg.dtype)
        v = _dequant_rows(v, grab(cache_layer["v_scale"]), cfg.dtype)
    else:
        k, v = k.astype(cfg.dtype), v.astype(cfg.dtype)
    return k, v


def copy_block(cache: Dict[str, jnp.ndarray], src, dst
               ) -> Dict[str, jnp.ndarray]:
    """Copy pool block ``src`` -> ``dst`` across every layer and pool leaf
    (K, V, and the int8 scales when present) — the device half of
    copy-on-write: when a request must write into a SHARED cached block
    (recomputing the last prompt position of a fully-cached prompt), the
    engine allocates a private block, copies the shared content here, and
    rewrites its block table; the sharing requests' block is never
    mutated. ``src``/``dst`` are traced scalars, so the jitted copy is ONE
    compiled program for the engine's lifetime."""
    return {k: v.at[:, :, dst].set(v[:, :, src]) for k, v in cache.items()}


# ---------------------------------------------------------------------------
# Prefix hashing — the content address of a FULL block of prompt tokens.
# Chained (each block's hash folds its predecessor's), so a hash names the
# whole token prefix ending at that block, not just the block's own span:
# matching block j implies the entire prefix [0, (j+1)*block_size) matches.
# Ints only (python salts str hashing per process; int hashing is stable
# within one process, which is all a per-engine cache needs).


def hash_block_tokens(prev_hash: int, tokens: Sequence[int]) -> int:
    """Chained content hash of one full block: ``h_j = H(h_{j-1}, tokens)``."""
    return hash((prev_hash,) + tuple(int(t) for t in tokens))


def prefix_block_hashes(tokens: Sequence[int],
                        block_size: int) -> List[int]:
    """Chain hashes of every FULL block of ``tokens`` (the partial tail
    block has no content address — it is never shared)."""
    out: List[int] = []
    h = hash(("apex_tpu.serve.prefix", block_size))
    for j in range(len(tokens) // block_size):
        h = hash_block_tokens(h, tokens[j * block_size:(j + 1) * block_size])
        out.append(h)
    return out


# ---------------------------------------------------------------------------
# Host-side block allocator. Admission happens between steps on the host,
# so this needs no device work and no locking (the engine is
# single-threaded by construction). Two modes:
#
# * plain (``prefix_cache=False``) — a LIFO free-list, every block owned by
#   exactly one request (the PR-5 behavior);
# * prefix-caching (``prefix_cache=True``) — content-addressed reuse: a
#   hash-of-token-prefix -> block-id map at block granularity with
#   per-block refcounts. Freed blocks that carry a content address are
#   PARKED in an LRU of evictable cached blocks instead of returning to
#   the free list — a later request whose prompt shares the prefix
#   re-acquires them via :meth:`lookup` and pays ZERO prefill flops for
#   those tokens; ``alloc`` evicts least-recently-used refcount-0 cached
#   blocks only when the free list runs dry.


class BlockAllocator:
    """Refcounted free-list (+ optional content-addressed prefix cache)
    over the pool's ``num_blocks`` block ids.

    Invariants (``assert_consistent`` checks them; the chaos test in
    ``tests/test_serve_prefix.py`` hammers them under random admit/retire/
    evict interleavings):

    * every block is in exactly ONE of: free list, evictable LRU
      (cached, refcount 0), or allocated (refcount >= 1);
    * a block is evictable iff its refcount is 0 and it holds a content
      hash; eviction drops the hash and returns it to the free list;
    * ``free`` of a block whose refcount is already 0 raises (double
      free), as does an out-of-range id.
    """

    def __init__(self, num_blocks: int, prefix_cache: bool = False):
        if num_blocks <= 0:
            raise ValueError("num_blocks must be positive")
        self.num_blocks = num_blocks
        self.prefix_cache = prefix_cache
        # LIFO: recently freed blocks are re-used first (still warm in any
        # cache hierarchy; also makes tests deterministic).
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._refcount: Dict[int, int] = {}
        self._hash_to_block: Dict[int, int] = {}
        self._block_hash: Dict[int, int] = {}
        # refcount-0 cached blocks, least-recently-used first
        self._lru: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        # lifetime counters (the engine's prefix-cache stats read these)
        self.blocks_reused_total = 0
        self.blocks_evicted_total = 0

    @property
    def free_count(self) -> int:
        """Allocatable blocks: truly free + evictable cached."""
        return len(self._free) + len(self._lru)

    @property
    def cached_count(self) -> int:
        """Blocks holding a content address (shared or parked)."""
        return len(self._block_hash)

    def refcount(self, block: int) -> int:
        return self._refcount.get(block, 0)

    def _evict_one(self) -> None:
        b, _ = self._lru.popitem(last=False)  # least recently used
        h = self._block_hash.pop(b)
        del self._hash_to_block[h]
        self._free.append(b)
        self.blocks_evicted_total += 1

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh block ids at refcount 1, or None when the pool
        cannot satisfy the request even after evicting every refcount-0
        cached block (caller defers admission — never a partial grant)."""
        if n < 0:
            raise ValueError("n must be >= 0")
        if n > self.free_count:
            return None
        while len(self._free) < n:
            self._evict_one()
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refcount[b] = 1
        return out

    def free(self, ids: Sequence[int]) -> None:
        """Drop one reference per id. A cached block reaching refcount 0
        parks in the evictable LRU (its content stays addressable); an
        uncached block returns to the free list."""
        for b in ids:
            if not 0 <= b < self.num_blocks:
                raise ValueError(f"block id {b} out of range")
            rc = self._refcount.get(b, 0)
            if rc <= 0:
                raise ValueError(f"double free of block {b}")
            if rc > 1:
                self._refcount[b] = rc - 1
                continue
            del self._refcount[b]
            if b in self._block_hash:
                self._lru[b] = None          # most-recently-used end
            else:
                self._free.append(b)

    # -- content-addressed reuse ------------------------------------------
    def lookup(self, hashes: Sequence[int]) -> List[int]:
        """Longest cached prefix of the chained ``hashes``: acquires (one
        reference each) and returns the matched block ids in prefix order.
        A parked block leaves the LRU; a shared one just gains a holder.
        Always misses when the allocator was built plain
        (``prefix_cache=False``)."""
        if not self.prefix_cache:
            return []
        out: List[int] = []
        for h in hashes:
            b = self._hash_to_block.get(h)
            if b is None:
                break
            out.append(b)
        for b in out:
            rc = self._refcount.get(b, 0)
            if rc == 0:
                self._lru.pop(b, None)
            self._refcount[b] = rc + 1
            self.blocks_reused_total += 1
        return out

    def commit(self, block: int, h: int) -> bool:
        """Register an allocated, fully-written block under its content
        hash. No-op (False) when the allocator is plain
        (``prefix_cache=False``), when the hash is already mapped (a
        concurrent identical prompt won the race — this copy stays
        private), or when the block already carries an address."""
        if self._refcount.get(block, 0) <= 0:
            raise ValueError(f"commit of unallocated block {block}")
        if not self.prefix_cache:
            return False
        if h in self._hash_to_block or block in self._block_hash:
            return False
        self._hash_to_block[h] = block
        self._block_hash[block] = h
        return True

    def assert_consistent(self) -> None:
        """Every-block-in-exactly-one-place conservation check (cheap; the
        chaos test calls it after every random operation)."""
        free = set(self._free)
        lru = set(self._lru)
        alloc = set(self._refcount)
        assert not (free & lru) and not (free & alloc) and not (lru & alloc)
        assert len(free) + len(lru) + len(alloc) == self.num_blocks
        assert all(rc >= 1 for rc in self._refcount.values())
        for b in lru:
            assert b in self._block_hash, f"evictable block {b} uncached"
        for h, b in self._hash_to_block.items():
            assert self._block_hash.get(b) == h


# ---------------------------------------------------------------------------
# Byte accounting — modeled HBM traffic of the paged cache, the serving
# analogue of comm.accounting's modeled wire bytes.


def _elem_bytes(cfg: KVCacheConfig) -> float:
    """Bytes per cached K or V element, scale overhead amortized in."""
    if cfg.quantized and cfg.bits == 4:
        # nibble-packed code + bf16 scale per group along head_dim:
        # exactly half the int8 layout at group = head_dim
        return 0.5 + 2.0 / cfg.kv_group
    if cfg.quantized:
        return 1.0 + 4.0 / cfg.head_dim  # int8 code + fp32 scale per vector
    return float(jnp.dtype(cfg.dtype).itemsize)


def kv_cache_bytes(cfg: KVCacheConfig) -> int:
    """Total HBM held by the pools (the engine's fixed KV budget)."""
    n = (cfg.num_layers * cfg.num_heads * cfg.num_blocks * cfg.block_size
         * cfg.head_dim)
    return int(2 * n * _elem_bytes(cfg))


def kv_write_bytes_per_token(cfg: KVCacheConfig) -> float:
    """Bytes written to the pools per cached token (all layers, K+V)."""
    return 2 * cfg.num_layers * cfg.num_heads * cfg.head_dim * _elem_bytes(cfg)


def kv_read_bytes(cfg: KVCacheConfig, seq_lens: Sequence[int]) -> float:
    """Modeled bytes read by ONE decode step over the given active context
    lengths: each slot streams its live blocks (whole blocks — the paged
    gather fetches block granules, like the wire models price whole
    transfers) through every layer's attention."""
    toks = sum(cfg.blocks_for_tokens(int(s)) * cfg.block_size
               for s in seq_lens if int(s) > 0)
    return (2 * cfg.num_layers * cfg.num_heads * cfg.head_dim
            * _elem_bytes(cfg) * toks)
