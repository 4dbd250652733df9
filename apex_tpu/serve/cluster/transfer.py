"""KV-block transfer between serving hosts — pack, ship, unpack, account.

Disaggregated prefill/decode serving (the ROADMAP item-2 split) moves a
request's cached K/V from the prefill host's staging pool into the decode
host's paged pool exactly once, at the prefill→decode handoff. This module
is that wire:

* **pack/unpack** — :func:`extract_blocks` slices whole pool blocks out of
  a :func:`~apex_tpu.serve.kv_cache.init_kv_cache` pytree (every layer,
  K+V, + the int8 scales when the pool is quantized) and
  :func:`insert_blocks` lands them in the destination pool with the same
  ``.at[].set(mode="drop")`` indexing :func:`~apex_tpu.serve.kv_cache.
  copy_block` uses — padded destination ids route out of bounds and drop,
  so both programs compile ONCE per worker for a fixed padded block count.
* **wire modes** — ``"raw"`` ships the pool representation verbatim; on an
  int8 pool that is ALREADY codes+scales, so the two modes coincide and a
  transferred block lands **bitwise identical** in the decode pool
  (dequant→requant never happens — the property
  ``tests/test_serve_cluster.py`` pins). ``"int8"`` on an fp16/fp32 pool
  quantizes each ``(token, head)`` ``head_dim`` vector through the
  ``comm.quantize`` blockwise codec (codec block = head_dim, the
  ``kv_cache`` int8-pool layout) before shipping — ~3.6× fewer wire bytes
  at fp32, within the codec's proven round-trip tolerance.
* **accounting** — :func:`transfer_wire_bytes` models bytes-on-wire per
  handoff with the ``comm.accounting`` convention (whole transfers priced
  from shapes, scale overhead amortized per element exactly like
  ``kv_cache._elem_bytes``); the packed payload's measured ``nbytes``
  agrees with the model to the byte (``tests/test_serve_cluster.py``).
* **transports** — :class:`SimTransport` is the host-simulated in-process
  link (modeled latency = fixed + bytes/bandwidth against the cluster's
  one monotonic clock) that lets the whole multi-"host" cluster run on a
  single CPU/chip for tests and rehearsals. :func:`ppermute_blocks` is the
  real-mesh hop for when prefill and decode live on different slices of
  one ICI ring: a ``lax.ppermute`` over the payload pytree, the same
  primitive ``comm.overlap`` builds its decomposed rings from — decode
  compute the scheduler can slide into the permute window hides the hop,
  and its wire cost is exactly :func:`transfer_wire_bytes`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.serve.kv_cache import KVCacheConfig

Pytree = Any

WIRE_MODES = ("raw", "int8")

# payload leaves per wire format (scales present iff codes ship)
_POOL_KEYS = ("k", "v")
_SCALE_KEYS = ("k_scale", "v_scale")


def validate_wire_mode(wire_mode: str) -> None:
    if wire_mode not in WIRE_MODES:
        raise ValueError(
            f"wire_mode must be one of {WIRE_MODES}, got {wire_mode!r}")


def payload_is_quantized(cfg: KVCacheConfig, wire_mode: str) -> bool:
    """Whether the wire carries int8 codes + fp32 scales. True for an int8
    pool under EITHER mode (the pool representation IS the wire format —
    shipping it raw is already quantized) and for ``wire_mode="int8"`` on
    a float pool."""
    validate_wire_mode(wire_mode)
    return cfg.quantized or wire_mode == "int8"


# ---------------------------------------------------------------------------
# Wire-byte model — the comm.accounting convention: whole transfers priced
# from static shapes, one number the measured payload must agree with.


def transfer_wire_bytes(cfg: KVCacheConfig, n_blocks: int,
                        wire_mode: str = "raw") -> int:
    """Modeled bytes-on-wire to hand off ``n_blocks`` pool blocks (all
    layers, K+V, scales included when the wire is quantized). Matches the
    packed payload's ``nbytes`` exactly: a quantized POOL ships its own
    representation (the ``kv_cache._elem_bytes`` amortization — int8
    codes + fp32 per-vector scales at ``1 + 4/head_dim`` B/element, int4
    nibble pairs + bf16 group scales at ``0.5 + 2/group`` — half the int8
    wire again); ``wire_mode="int8"`` on a float pool is the codec-side
    int8 layout; a raw float wire is the pool dtype's itemsize."""
    from apex_tpu.serve.kv_cache import _elem_bytes

    validate_wire_mode(wire_mode)
    elems = (cfg.num_layers * cfg.num_heads * n_blocks * cfg.block_size
             * cfg.head_dim)
    if cfg.quantized:
        return int(round(2 * elems * _elem_bytes(cfg)))
    if payload_is_quantized(cfg, wire_mode):
        vectors = elems // cfg.head_dim
        return 2 * (elems + 4 * vectors)
    return 2 * elems * int(jnp.dtype(cfg.dtype).itemsize)


# ---------------------------------------------------------------------------
# Pack / unpack — device-side block slicing. Both take a PADDED id vector
# of fixed length so each worker compiles exactly one extract and one
# insert program: extract pads by repeating a live block (junk content the
# insert drops), insert pads with an out-of-range id (mode="drop").


def extract_blocks(cache: Dict[str, jnp.ndarray],
                   ids: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Slice blocks ``ids`` ((nb_pad,) int32) out of every pool leaf:
    ``(L, H, B, bs[, D])`` → ``(L, H, nb_pad, bs[, D])``."""
    return {name: arr[:, :, ids] for name, arr in cache.items()}


def _quantize_payload(payload: Dict[str, jnp.ndarray]
                      ) -> Dict[str, jnp.ndarray]:
    """Float block payload → int8 codes + fp32 scales per (L, H, block,
    token) head_dim vector — the exact ``kv_cache._quant_rows`` codec, so
    an int8 wire on a float pool shares the int8 pool's layout and error
    bounds."""
    from apex_tpu.serve.kv_cache import _quant_rows

    out = {}
    for name in _POOL_KEYS:
        q, s = _quant_rows(payload[name])
        out[name] = q
        out[name + "_scale"] = s
    return out


def _dequantize_payload(payload: Dict[str, jnp.ndarray],
                        dtype) -> Dict[str, jnp.ndarray]:
    from apex_tpu.serve.kv_cache import _dequant_rows

    return {name: _dequant_rows(payload[name], payload[name + "_scale"],
                                dtype)
            for name in _POOL_KEYS}


def pack_blocks(cache: Dict[str, jnp.ndarray], cfg: KVCacheConfig,
                ids: jnp.ndarray, wire_mode: str = "raw"
                ) -> Dict[str, jnp.ndarray]:
    """Extract blocks ``ids`` and encode them for the wire. An int8 pool
    ships its codes+scales verbatim under BOTH modes (no dequant-requant);
    a float pool ships raw arrays or codec-quantized codes+scales."""
    validate_wire_mode(wire_mode)
    payload = extract_blocks(cache, ids)
    if cfg.quantized or wire_mode == "raw":
        return payload
    return _quantize_payload(payload)


def insert_blocks(cache: Dict[str, jnp.ndarray], cfg: KVCacheConfig,
                  payload: Dict[str, jnp.ndarray], dst_ids: jnp.ndarray,
                  wire_mode: str = "raw") -> Dict[str, jnp.ndarray]:
    """Land a packed payload at pool blocks ``dst_ids`` ((nb_pad,) int32;
    out-of-range entries drop — the padding convention). The indexing is
    :func:`~apex_tpu.serve.kv_cache.copy_block`'s ``.at[:, :, dst]`` set,
    one whole block per id across every leaf."""
    validate_wire_mode(wire_mode)
    if not cfg.quantized and wire_mode == "int8":
        payload = _dequantize_payload(payload, cfg.dtype)
    out = dict(cache)
    for name, arr in cache.items():
        out[name] = arr.at[:, :, dst_ids].set(
            payload[name].astype(arr.dtype), mode="drop")
    return out


def payload_nbytes(payload: Dict[str, Any], n_blocks: int) -> int:
    """Measured wire bytes of a (host-side) payload trimmed to its
    ``n_blocks`` valid blocks — the number that must agree with
    :func:`transfer_wire_bytes`."""
    total = 0
    for arr in payload.values():
        a = np.asarray(arr)
        total += a[:, :, :n_blocks].nbytes
    return total


def payload_crc32(payload: Dict[str, Any]) -> int:
    """crc32 over a packed payload's bytes (leaves in sorted-name order)
    — stamped on every :class:`~apex_tpu.serve.cluster.workers.KVHandoff`
    at pack time and re-checked at delivery, so a corrupted transfer is
    DETECTED and re-requested instead of silently diverging the stream
    (the ``resilience.checkpoint`` per-leaf-crc discipline applied to
    the wire)."""
    import zlib

    crc = 0
    for name in sorted(payload):
        a = np.ascontiguousarray(np.asarray(payload[name]))
        crc = zlib.crc32(a.tobytes(), crc)
    return crc


def corrupt_payload(payload: Dict[str, Any]) -> Dict[str, Any]:
    """Bit-rot a COPY of a payload (first leaf, middle bytes XOR-flipped
    — the ``resilience.chaos.corrupt_file`` "flip" mode applied to a
    wire payload). The original is untouched: the sender's retry copy
    must survive the corruption of the bytes on the wire."""
    out = {k: np.array(np.asarray(v), copy=True)
           for k, v in payload.items()}
    name = sorted(out)[0]
    flat = out[name].reshape(-1).view(np.uint8)
    off = flat.size // 2
    n = min(64, flat.size - off)
    flat[off:off + n] ^= 0xFF
    return out


# ---------------------------------------------------------------------------
# Real-mesh hop: the ppermute primitive the decomposed comm.overlap rings
# are built from, applied to a whole block payload. Runs inside a
# shard_map/mesh program whose axis spans the prefill+decode slices; the
# scheduler overlaps decode-side compute with the permute window exactly
# as accounting.overlap_report proves for the collective matmuls.


def ppermute_blocks(payload: Pytree, axis_name: str,
                    perm: Sequence[Tuple[int, int]]) -> Pytree:
    """One ICI hop of the payload pytree: ``lax.ppermute`` every leaf over
    ``perm`` (``[(src, dst), ...]``). Wire cost per hop =
    :func:`transfer_wire_bytes` of the payload's blocks."""
    return jax.tree_util.tree_map(
        lambda x: lax.ppermute(x, axis_name, perm), payload)


# ---------------------------------------------------------------------------
# Host-simulated transport — the in-process link that runs the whole
# multi-"host" cluster on one CPU/chip. Deterministic: delivery time is
# send time + a modeled latency (fixed + bytes/bandwidth), measured on the
# cluster's one monotonic clock.


@dataclasses.dataclass
class Delivery:
    """One in-flight handoff: the opaque item plus its wire accounting.
    ``corrupted`` marks a fault-injected delivery whose payload bytes
    must be treated as rotted at the receiver (the receiver's CRC check
    is what must catch it); ``dropped`` marks a send the link ate."""

    item: Any
    wire_bytes: int
    t_send_ms: float
    t_deliver_ms: float
    corrupted: bool = False
    dropped: bool = False

    @property
    def transfer_ms(self) -> float:
        return self.t_deliver_ms - self.t_send_ms


# deterministic link fault modes (serve.cluster.chaos injects these):
# drop — the send never arrives; stall — delivery delayed stall_ms;
# corrupt — the payload bytes rot on the wire (CRC must catch it)
FAULT_MODES = ("drop", "stall", "corrupt")


class SimTransport:
    """In-process prefill→decode link with modeled latency.

    ``fixed_ms`` is the per-transfer setup cost; ``gib_per_s`` the modeled
    link bandwidth (0 disables the byte term — instant delivery, the
    deterministic test default). Totals (``wire_bytes_total``,
    ``transfer_ms_total``, ``transfers_total``) feed the cluster's
    transfer telemetry.

    **Fault injection** (the chaos harness's link half):
    :meth:`inject_fault` queues deterministic faults consumed by the
    NEXT sends, in order — ``drop`` (the delivery never happens),
    ``stall`` (delivery delayed ``stall_ms``) and ``corrupt`` (delivery
    arrives flagged ``corrupted`` — the receiver's CRC validation, not
    the transport, is what must notice). Fault counters
    (``drops_total`` / ``stalls_total`` / ``corrupts_total``) feed the
    chaos record."""

    def __init__(self, fixed_ms: float = 0.0, gib_per_s: float = 0.0):
        if fixed_ms < 0 or gib_per_s < 0:
            raise ValueError("fixed_ms and gib_per_s must be >= 0")
        self.fixed_ms = float(fixed_ms)
        self.gib_per_s = float(gib_per_s)
        self._inflight: List[Delivery] = []
        self._faults: List[Tuple[str, float]] = []
        self.wire_bytes_total = 0
        self.transfer_ms_total = 0.0
        self.transfers_total = 0
        self.drops_total = 0
        self.stalls_total = 0
        self.corrupts_total = 0

    def inject_fault(self, mode: str, count: int = 1,
                     stall_ms: float = 0.0) -> None:
        """Queue ``count`` link faults for the next sends (FIFO)."""
        if mode not in FAULT_MODES:
            raise ValueError(
                f"fault mode must be one of {FAULT_MODES}, got {mode!r}")
        if count < 1:
            raise ValueError("count must be >= 1")
        if mode == "stall" and stall_ms <= 0:
            raise ValueError("stall fault needs stall_ms > 0")
        self._faults.extend([(mode, float(stall_ms))] * int(count))

    @property
    def pending_faults(self) -> int:
        return len(self._faults)

    def modeled_ms(self, wire_bytes: int) -> float:
        ms = self.fixed_ms
        if self.gib_per_s > 0:
            ms += wire_bytes / (self.gib_per_s * (1 << 30)) * 1e3
        return ms

    def send(self, item: Any, wire_bytes: int, t_ms: float) -> Delivery:
        d = Delivery(item=item, wire_bytes=int(wire_bytes),
                     t_send_ms=float(t_ms),
                     t_deliver_ms=float(t_ms) + self.modeled_ms(wire_bytes))
        if self._faults:
            mode, stall_ms = self._faults.pop(0)
            if mode == "drop":
                # the bytes transited the wire, but no transfer
                # completed: count the bytes and the drop, not a
                # delivery — transfers_total must not overstate link
                # health under the exact chaos plans the gate compares
                d.dropped = True
                self.drops_total += 1
                self.wire_bytes_total += d.wire_bytes
                return d  # eaten: never enters the in-flight set
            if mode == "stall":
                d.t_deliver_ms += stall_ms
                self.stalls_total += 1
            elif mode == "corrupt":
                d.corrupted = True
                self.corrupts_total += 1
        # totals AFTER fault application: a stalled delivery's extra
        # latency belongs in transfer_ms_total (it agrees with the
        # per-delivery transfer_ms the receiver histograms)
        self.wire_bytes_total += d.wire_bytes
        self.transfer_ms_total += d.transfer_ms
        self.transfers_total += 1
        self._inflight.append(d)
        return d

    def poll(self, t_ms: float) -> List[Delivery]:
        """Deliveries whose modeled arrival time has passed, in send
        order."""
        ready = [d for d in self._inflight if d.t_deliver_ms <= t_ms]
        if ready:
            self._inflight = [d for d in self._inflight
                              if d.t_deliver_ms > t_ms]
        return ready

    @property
    def in_flight(self) -> int:
        return len(self._inflight)
