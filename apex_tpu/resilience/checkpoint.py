"""Production checkpointing — atomic, manifested, async, self-verifying.

Reference context: the reference delegates checkpointing to ``torch.save``
(``examples/imagenet/main_amp.py`` writes one file in-place). At pod scale
that contract is not survivable: a preemption mid-``torch.save`` leaves a
torn file that unpickles halfway or not at all, and with ZeRO-sharded
optimizer state (``contrib/optimizers``) a half-written blob silently
mis-binds shards. This module layers the missing durability on
:mod:`apex_tpu.utils.checkpoint` (which supplies the serialization backend
— orbax when present, atomic pickle otherwise):

* **atomic write** — everything lands in a ``.tmp-*`` staging dir, then one
  ``os.replace`` publishes it; a crash at any point leaves either the old
  checkpoint set or the new one, never a torn member (a same-step re-save
  parks the old copy under ``.trash-*`` between the two renames, so even
  that crash window loses no bytes).
* **versioned manifest** — ``manifest.json`` carries a schema version, the
  step, a treedef+shape/dtype fingerprint of the saved state (the
  ``--resume`` fingerprint contract from the imagenet trainer, now shared),
  and a per-leaf crc32 so corruption is *detected*, not just hoped against.
* **async save** — ``device_get`` happens on the caller (the only part that
  must see the live arrays); serialization + fsync + publish run on a
  single worker thread off the step critical path.
* **retention GC** — keep-last-N plus keep-every-K milestones.
* **latest_valid() discovery** — scan, verify manifests + checksums, and
  skip torn/corrupt checkpoints, so auto-resume always lands on a good one.
* **per-shard manifests** — FSDP/ZeRO pytrees whose leaves are sharded
  ACROSS processes are not refused: each process saves its local shards
  under ``shard-p{K}/`` with its own fingerprinted manifest (leaf index +
  shard placement + crc32), the main manifest records the dp degree, and
  restore validates dp-degree + shard-shape/placement against the live
  sharding before rebinding — skew is refused exactly like a revision
  mismatch. The loud ``CheckpointError`` remains only for leaves with no
  addressable replica-0 shard (genuinely non-addressable).

Telemetry: each save records ``ckpt_save_ms`` / ``ckpt_bytes`` (readable on
:attr:`CheckpointManager.last_save_ms`; pass ``sink=`` to append a
``monitor`` JSONL record per save), and the blocking host section traces
under the ``ckpt`` monitor span.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
import zlib
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

MANIFEST_NAME = "manifest.json"
MANIFEST_SCHEMA = 1
# sharded checkpoints (FSDP/ZeRO leaves in per-process shard-p{K} payloads,
# absent from the main payload) are a different on-disk format: they carry
# schema 2 so a pre-sharding reader refuses with a loud schema mismatch
# instead of a misleading "payload is missing leaf K" corruption error.
# Plain checkpoints keep schema 1 (bidirectionally compatible).
MANIFEST_SCHEMA_SHARDED = 2
_PREFIX = "ckpt_"
_TMP_PREFIX = ".tmp-"
_TRASH_PREFIX = ".trash-"


class CheckpointError(RuntimeError):
    """A checkpoint could not be written, verified, or restored."""


def fingerprint(state: Pytree) -> str:
    """Structure fingerprint: treedef + per-leaf shape/dtype. Leaves are
    checkpointed by flat positional index and re-hung on the LIVE treedef,
    so a same-leaf-count checkpoint from another code revision would
    otherwise silently mis-bind optimizer/amp/guard state. Shape/dtype come
    from the avals — no device-to-host copies."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    per_leaf = ";".join(
        f"{tuple(jnp.shape(x))}:{jnp.result_type(x)}" for x in leaves)
    return f"{treedef}|{per_leaf}"


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes())


def _is_cross_process(x) -> bool:
    """A leaf this process cannot materialize whole — an FSDP/ZeRO shard
    pytree under multi-process SPMD. Module-level so tests can exercise
    the per-shard path on a single-process mesh."""
    return (hasattr(x, "is_fully_addressable")
            and not x.is_fully_addressable
            and not getattr(x, "is_fully_replicated", False))


def _index_key(index, shape) -> str:
    """Serializable key for a shard's position: 'start:stop' per dim.
    Pins the shard SHAPE and placement, so a checkpoint written at a
    different dp degree (different slicing) is refused at restore."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append(f"{start}:{stop}")
    return ",".join(out)


def _local_shards(x):
    """This process's unique (replica-0) shards of a cross-process-sharded
    leaf: ``[(index_key, np.ndarray)]``. A leaf with NO addressable
    replica-0 shard is genuinely non-addressable here — the loud refusal
    stays for that case only."""
    shards = [s for s in x.addressable_shards
              if getattr(s, "replica_id", 0) == 0]
    if not shards:
        raise CheckpointError(
            "state contains an array with no addressable replica-0 shard "
            f"on this process (shape {getattr(x, 'shape', '?')}) — "
            "genuinely non-addressable; all-gather it first or use an "
            "orbax multihost checkpointer")
    return [(_index_key(s.index, x.shape), np.asarray(s.data))
            for s in shards]


def _process_info():
    try:
        return jax.process_index(), jax.process_count()
    except Exception:  # jax not initialized — single-process tooling
        return 0, 1


def state_dict(state: Pytree, elastic: Optional[Any] = None
               ) -> Dict[str, Any]:
    """Pytree → flat fingerprinted dict (the manifest path's in-memory
    form): leaves keyed by flat index plus the structure fingerprint, so a
    restore against different code fails loudly instead of mis-binding.

    FSDP/ZeRO shard pytrees ride the same path: a leaf SHARDED across
    processes is stored as this process's local shards (``{"__sharded__":
    ..., "shards": {index_key: array}}``) stamped with the process
    index/count — :func:`load_state_dict` validates the dp degree and
    every shard's placement before rebinding. Only a leaf with no
    addressable replica-0 shard is refused.

    ``elastic``: an optional per-leaf ``reshard.LeafSpec`` tree (or
    pre-flattened mapping) stamped into the dict, so a later
    ``load_state_dict(..., allow_reshard=True)`` at a different dp degree
    can redo the shard arithmetic instead of refusing."""
    leaves = jax.tree_util.tree_leaves(state)
    pidx, pcount = _process_info()
    out: Dict[str, Any] = {"fingerprint": fingerprint(state), "leaves": {}}
    if elastic is not None:
        from apex_tpu.resilience.reshard import elastic_manifest

        out["elastic"] = elastic_manifest(state, elastic)
    host_idx = [i for i, x in enumerate(leaves) if not _is_cross_process(x)]
    fetched = jax.device_get([leaves[i] for i in host_idx])
    for i, h in zip(host_idx, fetched):
        out["leaves"][str(i)] = np.asarray(h)
    for i, x in enumerate(leaves):
        if _is_cross_process(x):
            out["leaves"][str(i)] = {
                "__sharded__": True,
                "global_shape": list(jnp.shape(x)),
                "dtype": str(jnp.result_type(x)),
                "process_index": pidx,
                "process_count": pcount,
                "shards": dict(_local_shards(x)),
            }
    return out


def _manifest_ident(path: str):
    """Filesystem identity (inode+mtime+size) of a published dir's
    manifest — lets a peer distinguish a stale same-step dir (left by a
    crashed previous run, possibly with a colliding ``save_seq``) from
    process 0's fresh publish, whose manifest is always a new file."""
    try:
        st = os.stat(os.path.join(path, MANIFEST_NAME))
    except OSError:
        return None
    return (st.st_ino, st.st_mtime_ns, st.st_size)


def _restore_sharded_leaf(template_leaf, entry: Dict[str, Any], i: int):
    """Rebind one per-shard entry onto the LIVE template leaf's sharding,
    refusing dp-degree or shard-shape/placement skew — the failure mode
    parameter sharding adds over replicated state."""
    pidx, pcount = _process_info()
    if entry["process_count"] != pcount:
        raise CheckpointError(
            f"leaf {i}: checkpoint shards were written at dp degree "
            f"{entry['process_count']} processes, live mesh has {pcount} "
            "— refusing to mis-bind shards (restore on the original "
            "topology or all-gather + reshard explicitly)")
    if list(jnp.shape(template_leaf)) != list(entry["global_shape"]):
        raise CheckpointError(
            f"leaf {i}: checkpoint global shape {entry['global_shape']} "
            f"!= live {list(jnp.shape(template_leaf))}")
    saved = entry["shards"]
    live_shards = [s for s in template_leaf.addressable_shards
                   if getattr(s, "replica_id", 0) == 0]
    live_keys = {_index_key(s.index, template_leaf.shape)
                 for s in live_shards}
    if set(saved) != live_keys:
        raise CheckpointError(
            f"leaf {i}: shard layout skew — checkpoint holds shards "
            f"{sorted(saved)}, live sharding expects {sorted(live_keys)} "
            "(different dp degree or shard alignment)")
    arrays = []
    for s in template_leaf.addressable_shards:
        key = _index_key(s.index, template_leaf.shape)
        if key not in saved:
            # an addressable replica>0 copy whose replica-0 home lives on
            # another process: its bytes are in that process's shard
            # payload, not ours — refuse loudly rather than KeyError
            raise CheckpointError(
                f"leaf {i}: live sharding places a replica copy of shard "
                f"{key} on this process but its replica-0 home is on "
                "another process — per-process shard payloads cannot "
                "rebuild it; restore on the original topology")
        arr = np.asarray(saved[key]).astype(
            jnp.result_type(template_leaf), copy=False)
        arrays.append(jax.device_put(arr, s.device))
    return jax.make_array_from_single_device_arrays(
        template_leaf.shape, template_leaf.sharding, arrays)


def _treedef_compatible(saved_fp: Optional[str], template: Pytree) -> bool:
    """True iff ``saved_fp`` names the same tree STRUCTURE as ``template``
    (the treedef prefix of the fingerprint — per-leaf shapes may differ,
    which is exactly what an elastic reshard changes)."""
    if saved_fp is None:
        return True
    _, treedef = jax.tree_util.tree_flatten(template)
    return str(saved_fp).startswith(f"{treedef}|")


def _rebind_global(leaf, i: int, full: np.ndarray):
    """Bind one assembled-and-retargeted GLOBAL array onto the live leaf:
    slice per live placement + device_put for a cross-process-sharded
    target, a plain asarray otherwise."""
    if not _is_cross_process(leaf):
        return jnp.asarray(full, jnp.result_type(leaf))
    if tuple(full.shape) != tuple(leaf.shape):
        raise CheckpointError(
            f"leaf {i}: resharded global shape {tuple(full.shape)} != "
            f"live {tuple(leaf.shape)}")
    arrays = []
    for s in leaf.addressable_shards:
        piece = np.ascontiguousarray(full[s.index]).astype(
            jnp.result_type(leaf), copy=False)
        arrays.append(jax.device_put(piece, s.device))
    return jax.make_array_from_single_device_arrays(
        leaf.shape, leaf.sharding, arrays)


def _sharded_layout_skew(leaf, entry: Dict[str, Any]) -> bool:
    """True iff a ``__sharded__`` entry cannot rebind exactly onto the
    live leaf: different process count, global shape, or shard placement
    set. (The fingerprint misses the mesh-slicing case — a (64,) leaf
    sharded 8-ways and 2-ways fingerprints identically.)"""
    pidx, pcount = _process_info()
    if entry.get("process_count") != pcount:
        return True
    if list(jnp.shape(leaf)) != list(entry["global_shape"]):
        return True
    live_keys = {_index_key(s.index, leaf.shape)
                 for s in leaf.addressable_shards
                 if getattr(s, "replica_id", 0) == 0}
    return set(entry["shards"]) != live_keys


def _reshard_entry_leaf(leaf, entry: Dict[str, Any], i: int,
                        espec: Optional[Dict[str, Any]]):
    """Elastic restore of one ``__sharded__`` entry onto a live leaf whose
    layout differs: reassemble the logical leaf from its placements,
    retarget via the elastic spec when the global shape changed, re-slice
    to the live placements. Needs the FULL placement set — a
    multi-process state_dict holds only the local shards, in which case
    :func:`assemble_leaf`'s coverage check refuses loudly."""
    from apex_tpu.resilience import reshard as _rs

    full = _rs.assemble_leaf(entry["global_shape"], entry["dtype"],
                             entry["shards"])
    if tuple(full.shape) != tuple(jnp.shape(leaf)):
        if espec is None:
            raise CheckpointError(
                f"leaf {i}: saved global shape {entry['global_shape']} != "
                f"live {list(jnp.shape(leaf))} and the checkpoint carries "
                "no elastic spec for it — re-save with elastic= (the "
                "optimizers' elastic_spec()) or restore on the original "
                "topology")
        full = _rs.retarget_leaf(full, espec, jnp.shape(leaf))
    return _rebind_global(leaf, i, full)


def load_state_dict(template: Pytree, d: Dict[str, Any],
                    allow_reshard: bool = False) -> Pytree:
    """Restore a :func:`state_dict` blob onto ``template``'s structure,
    refusing a fingerprint mismatch (and, for per-shard entries, any
    dp-degree or shard-shape skew against the live sharding).

    ``allow_reshard=True`` relaxes the refusal for TOPOLOGY skew only:
    the treedef and leaf count must still match, but leaves whose
    shard layout (or dp-flat size) changed are reassembled and re-sliced
    via the dict's ``elastic`` specs (see
    :mod:`apex_tpu.resilience.reshard`). Without the flag, behavior is
    byte-for-byte the old refusal."""
    live = fingerprint(template)
    saved = d.get("fingerprint")
    reshard_mode = False
    if saved is not None and saved != live:
        if not allow_reshard:
            raise CheckpointError(
                "state_dict was written by a different state revision — "
                f"refusing to mis-bind.\n   saved: {str(saved)[:200]}\n"
                f"   live:  {live[:200]}")
        if not _treedef_compatible(saved, template):
            raise CheckpointError(
                "allow_reshard only relaxes per-leaf shard layouts; this "
                "state_dict has a different tree STRUCTURE — revision "
                "skew, not topology skew")
        reshard_mode = True
    leaves, treedef = jax.tree_util.tree_flatten(template)
    if len(d["leaves"]) != len(leaves):
        raise CheckpointError(
            f"state_dict has {len(d['leaves'])} leaves, live structure "
            f"has {len(leaves)}")
    elastic = d.get("elastic") or {}
    out = []
    for i, leaf in enumerate(leaves):
        entry = d["leaves"][str(i)]
        if isinstance(entry, dict) and entry.get("__sharded__"):
            if allow_reshard and (
                    not _is_cross_process(leaf)
                    or _sharded_layout_skew(leaf, entry)):
                out.append(_reshard_entry_leaf(leaf, entry, i,
                                               elastic.get(str(i))))
                continue
            if not _is_cross_process(leaf):
                raise CheckpointError(
                    f"leaf {i} was checkpointed as per-process shards but "
                    "the live template is fully addressable — dp-degree "
                    "skew; restore on the original topology")
            out.append(_restore_sharded_leaf(leaf, entry, i))
        elif reshard_mode and (
                tuple(np.shape(entry)) != tuple(jnp.shape(leaf))):
            from apex_tpu.resilience import reshard as _rs

            espec = elastic.get(str(i))
            if espec is None:
                raise CheckpointError(
                    f"leaf {i}: shape changed "
                    f"{tuple(np.shape(entry))} -> "
                    f"{tuple(jnp.shape(leaf))} and the state_dict carries "
                    "no elastic spec for it — save with elastic= or "
                    "restore on the original topology")
            full = _rs.retarget_leaf(np.asarray(entry), espec,
                                     jnp.shape(leaf))
            out.append(_rebind_global(leaf, i, full))
        else:
            out.append(jnp.asarray(entry, jnp.result_type(leaf)))
    return jax.tree_util.tree_unflatten(treedef, out)


def _step_of(name: str) -> Optional[int]:
    if not name.startswith(_PREFIX):
        return None
    try:
        return int(name[len(_PREFIX):])
    except ValueError:
        return None


def _is_process_zero() -> bool:
    try:
        return jax.process_index() == 0
    except Exception:  # jax not initialized — single-process tooling
        return True


class CheckpointManager:
    """Atomic, manifested checkpoint directory. Typical loop::

        mgr = CheckpointManager(ckpt_dir, keep_last_n=3, async_save=True)
        found = mgr.latest_valid()
        if found:
            state, start = mgr.restore(target=state)
        for step in range(start, n):
            state = train_step(state, ...)
            if step % save_freq == 0:
                mgr.save(state, step)
        mgr.close()                        # drains the async worker

    ``state`` is any pytree (amp state, optimizer state incl. ZeRO shards,
    batch stats, DDP error-feedback residuals, guard state, ...).
    """

    def __init__(
        self,
        directory: str,
        keep_last_n: int = 3,
        keep_every_k: int = 0,
        async_save: bool = False,
        fsync: bool = True,
        sink: Optional[Any] = None,
        process0_only: bool = True,
        shard_publish_timeout_s: float = 60.0,
        allow_reshard: bool = False,
    ):
        self.directory = os.path.abspath(directory)
        # default for restore(): opt into topology-elastic restores (a
        # per-call allow_reshard= overrides)
        self.allow_reshard = bool(allow_reshard)
        self.keep_last_n = max(1, int(keep_last_n))
        self.keep_every_k = max(0, int(keep_every_k))
        self.async_save = async_save
        self.fsync = fsync
        self.sink = sink
        # multi-process SPMD (the preemption barrier's world): every
        # process calls save() at the agreed step, but only process 0
        # touches the shared directory — the JsonlSink gating pattern.
        # Reads (latest_valid/restore) stay ungated: they are idempotent.
        self.write_enabled = _is_process_zero() if process0_only else True
        self._process0_only = bool(process0_only)
        # how long a non-zero process waits for process 0's publish before
        # declaring the sharded save failed (slow shared filesystems need
        # more than the default)
        self.shard_publish_timeout_s = float(shard_publish_timeout_s)
        # save-call counter, advanced in lockstep on EVERY process (save()
        # is SPMD): stamps the manifest so peers publishing shards can tell
        # THIS save's dir from an older same-step dir (re-save)
        self._save_seq = 0
        self.last_save_ms: Optional[float] = None
        self.last_save_bytes: Optional[int] = None
        # host ms spent in the reshard arithmetic of the last elastic
        # restore (0.0 when the last restore bound exactly)
        self.last_reshard_ms: float = 0.0
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pending: List[Future] = []
        self._lock = threading.Lock()

    # -- paths -------------------------------------------------------------
    def step_path(self, step: int) -> str:
        return os.path.join(self.directory, f"{_PREFIX}{int(step):08d}")

    def all_steps(self) -> List[int]:
        """Published checkpoint steps, ascending (no validity check)."""
        if not os.path.isdir(self.directory):
            return []
        steps = [_step_of(n) for n in os.listdir(self.directory)]
        return sorted(s for s in steps if s is not None)

    # -- save --------------------------------------------------------------
    def save(self, state: Pytree, step: int, block: Optional[bool] = None,
             elastic: Optional[Any] = None) -> str:
        """Write ``state`` at ``step``; returns the (future) final path.

        ``block=None`` follows the manager's ``async_save`` setting. Only
        the device→host transfer (plus, for async, one private host copy —
        donation safety) runs on the caller; checksums, serialization and
        the atomic publish run on the worker thread. Errors from an async
        save surface on the next :meth:`save` / :meth:`wait` /
        :meth:`close`.

        ``elastic``: optional per-leaf ``reshard.LeafSpec`` tree (or
        pre-flattened mapping) stamped into the manifest so
        :meth:`restore` with ``allow_reshard=True`` can rebuild the state
        at a DIFFERENT dp degree (see
        :mod:`apex_tpu.resilience.reshard`; the ZeRO-1/FSDP optimizers
        build it via ``elastic_spec()``).
        """
        from apex_tpu.monitor.trace import span

        final = self.step_path(step)
        # advanced on every process, even ones that end up writing nothing
        # — the counters must stay in lockstep for the publish handshake
        save_seq = self._save_seq
        self._save_seq += 1
        # captured NOW, before process 0 can have started this save's
        # write: whatever dir currently sits at `final` is stale (an older
        # save of this step) and must never receive this save's shards
        stale_ident = None if self.write_enabled else _manifest_ident(final)
        leaves, _ = jax.tree_util.tree_flatten(state)
        pidx, pcount = _process_info()
        # FSDP/ZeRO shard pytrees: leaves sharded ACROSS processes ride the
        # per-process shard-payload path (each process saves its local
        # shards; _local_shards raises the loud refusal for the genuinely
        # non-addressable case). Everything else is process-0's payload.
        shard_entries: List[Tuple[int, str, np.ndarray]] = []
        host_idx = []
        for i, x in enumerate(leaves):
            if _is_cross_process(x):
                for key, arr in _local_shards(x):
                    shard_entries.append((i, key, arr))
            else:
                host_idx.append(i)
        if not self.write_enabled and not shard_entries:
            return final  # non-zero process, nothing sharded: no write
        self._raise_pending()
        t0 = time.perf_counter()
        sync = not self.async_save if block is None else block
        if shard_entries and pcount > 1:
            if not self._process0_only:
                # with every process a full writer there is no single
                # manifest owner: each would publish its own step dir
                # holding only its own shard-p{K} and the last os.replace
                # wins — every save would verify as torn
                raise CheckpointError(
                    "multi-process sharded saves need process0_only=True: "
                    "the per-shard publish protocol has process 0 own the "
                    "manifest and peers rename their shard dirs in")
            # multi-process sharded saves publish in two phases (shard
            # subdirs land after process 0's manifest) — keep the whole
            # sequence on the caller so the preemption barrier that agreed
            # on the step also brackets the write
            sync = True
        if not sync:
            # backpressure: at most ONE in-flight async save — a second
            # submit would pin a second full host snapshot of the state
            # (unbounded RAM when serialization is slower than the save
            # cadence); blocking here degrades to sync-save pacing instead
            self.wait()
        with span("ckpt"):
            if self.write_enabled:
                fetched = jax.device_get([leaves[i] for i in host_idx])
                host = list(zip(host_idx,
                                [np.asarray(h) for h in fetched]))
            else:
                # non-writer process: _write ignores the replicated
                # payload — don't pay a full device→host transfer on the
                # forced-sync critical path for bytes never written
                host = []
            if not sync:
                # donation safety: on the CPU backend device_get can alias
                # the live buffer, which a donating train step may overwrite
                # while the worker is still serializing — snapshot it. (The
                # checksum/serialize work itself runs on the worker.)
                host = [(i, np.array(h, copy=True)) for i, h in host]
                shard_entries = [(i, k, np.array(a, copy=True))
                                 for i, k, a in shard_entries]
        meta = {
            "schema": (MANIFEST_SCHEMA_SHARDED if shard_entries
                       else MANIFEST_SCHEMA),
            "step": int(step),
            "save_seq": save_seq,
            "fingerprint": fingerprint(state),
            "num_leaves": len(leaves),
        }
        if elastic is not None:
            from apex_tpu.resilience.reshard import elastic_manifest

            meta["elastic"] = elastic_manifest(state, elastic)
        if shard_entries:
            sharded = {}
            for i, _, _ in shard_entries:
                sharded[str(i)] = {
                    "global_shape": list(jnp.shape(leaves[i])),
                    "dtype": str(jnp.result_type(leaves[i])),
                    "dp_degree": pcount,
                }
            meta["sharded"] = sharded
        if sync:
            self.wait()  # a sync save must not interleave with the worker
            self._write(host, shard_entries, meta, final, t0, stale_ident)
        else:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="apex-tpu-ckpt")
            with self._lock:
                self._pending.append(self._pool.submit(
                    self._write, host, shard_entries, meta, final, t0,
                    stale_ident))
        return final

    def _write_shard_subdir(self, parent: str,
                            shard_entries: List[Tuple[int, str, np.ndarray]],
                            meta: Dict[str, Any]) -> int:
        """This process's shard payload + fingerprinted shard manifest
        under ``parent/shard-p{K}``; returns the shard bytes."""
        from apex_tpu.utils.checkpoint import save_checkpoint

        pidx, pcount = _process_info()
        sub = os.path.join(parent, f"shard-p{pidx}")
        os.makedirs(sub, exist_ok=True)
        payload = save_checkpoint(
            os.path.join(sub, "payload"),
            {f"{i}|{key}": arr for i, key, arr in shard_entries})
        manifest = {
            "schema": MANIFEST_SCHEMA,
            "step": meta["step"],
            "process_index": pidx,
            "process_count": pcount,
            "payload": os.path.basename(payload),
            "shards": [{"leaf": i, "index": key, "shape": list(a.shape),
                        "dtype": str(a.dtype), "crc32": _crc(a)}
                       for i, key, a in shard_entries],
        }
        with open(os.path.join(sub, MANIFEST_NAME), "w") as f:
            json.dump(manifest, f)
            if self.fsync:
                f.flush()
                os.fsync(f.fileno())
        return int(sum(a.nbytes for _, _, a in shard_entries))

    def _publish_shard_subdir(self, shard_entries, meta, final,
                              stale_ident=None) -> None:
        """Non-zero process under multi-process SPMD: stage this process's
        shards, wait for process 0 to publish THIS save's checkpoint dir,
        then rename the staging in. A crash before the rename leaves a
        manifest whose expected shard dir is missing — verify() reports
        the checkpoint torn, exactly like a torn payload.

        The wait must not match an OLDER dir for the same step (re-save:
        process 0 parks the old copy and publishes a fresh dir — renaming
        into the old one would land the shard in the copy about to be
        trashed). The fresh dir is recognized by its manifest carrying
        this save's ``save_seq``, not being the dir captured as stale at
        save() entry (``stale_ident`` closes the restart case where a
        crashed previous run left a torn dir whose save_seq collides),
        and not yet holding this process's shard subdir (a completed
        older save always holds one)."""
        pidx, _ = _process_info()
        staging = os.path.join(
            self.directory,
            f"{_TMP_PREFIX}shard-{os.path.basename(final)}-p{pidx}")
        if os.path.isdir(staging):
            shutil.rmtree(staging)
        os.makedirs(staging)

        def _fresh_dir_published() -> bool:
            if os.path.exists(os.path.join(final, f"shard-p{pidx}")):
                return False  # an older, completed copy of this step
            ident = _manifest_ident(final)
            if ident is None or ident == stale_ident:
                return False  # absent, or the stale copy seen at entry
            try:
                with open(os.path.join(final, MANIFEST_NAME)) as f:
                    m = json.load(f)
            except (OSError, ValueError):
                return False
            return m.get("save_seq") == meta["save_seq"]

        try:
            self._write_shard_subdir(staging, shard_entries, meta)
            deadline = time.monotonic() + self.shard_publish_timeout_s
            while not _fresh_dir_published():
                if time.monotonic() > deadline:
                    raise CheckpointError(
                        f"process {pidx}: {final} (save_seq "
                        f"{meta['save_seq']}) was never published by "
                        "process 0 — this save is lost on this process "
                        "(its staged shards are discarded)")
                time.sleep(0.05)
            # _write_shard_subdir staged under staging/shard-p{K}
            os.replace(os.path.join(staging, f"shard-p{pidx}"),
                       os.path.join(final, f"shard-p{pidx}"))
        finally:
            shutil.rmtree(staging, ignore_errors=True)

    def _write(self, host: List[Tuple[int, np.ndarray]],
               shard_entries: List[Tuple[int, str, np.ndarray]],
               meta: Dict[str, Any], final: str, t0: float,
               stale_ident=None) -> None:
        from apex_tpu.utils.checkpoint import save_checkpoint

        if not self.write_enabled:
            # non-zero process: only its shard subdir (sharded saves only
            # reach here with shard entries)
            self._publish_shard_subdir(shard_entries, meta, final,
                                       stale_ident)
            ms = (time.perf_counter() - t0) * 1000.0
            self.last_save_ms = ms
            self.last_save_bytes = int(
                sum(a.nbytes for _, _, a in shard_entries))
            return
        # checksum + manifest assembly on the worker: the host list is a
        # private snapshot, so only the device transfer had to stay on the
        # caller (the async save's critical-path cost)
        manifest = dict(
            meta,
            leaves=[{"leaf_index": i, "shape": list(h.shape),
                     "dtype": str(h.dtype), "crc32": _crc(h)}
                    for i, h in host],
            bytes=int(sum(h.nbytes for _, h in host)))
        os.makedirs(self.directory, exist_ok=True)
        tmp = os.path.join(
            self.directory,
            f"{_TMP_PREFIX}{os.path.basename(final)}-{os.getpid()}")
        if os.path.isdir(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        try:
            payload = save_checkpoint(
                os.path.join(tmp, "payload"),
                {str(i): h for i, h in host})
            manifest = dict(manifest, payload=os.path.basename(payload))
            if shard_entries:
                # process 0's own shards land INSIDE the staging dir, so
                # the atomic publish below covers them too
                manifest["bytes"] += self._write_shard_subdir(
                    tmp, shard_entries, meta)
            mpath = os.path.join(tmp, MANIFEST_NAME)
            with open(mpath, "w") as f:
                json.dump(manifest, f)
                if self.fsync:
                    f.flush()
                    os.fsync(f.fileno())
            trash = None
            if os.path.isdir(final):
                # re-save of the same step: POSIX cannot atomically swap a
                # non-empty dir, so park the old copy under a hidden name
                # first — a crash between the two renames leaves this step
                # missing but the old bytes intact (and recoverable),
                # never a torn mixture
                trash = os.path.join(
                    self.directory,
                    f"{_TRASH_PREFIX}{os.path.basename(final)}-"
                    f"{os.getpid()}")
                if os.path.isdir(trash):
                    shutil.rmtree(trash)
                os.replace(final, trash)
            os.replace(tmp, final)  # the publish — atomic on POSIX
            if trash is not None:
                shutil.rmtree(trash, ignore_errors=True)
            if self.fsync:
                dirfd = os.open(self.directory, os.O_RDONLY)
                try:
                    os.fsync(dirfd)
                finally:
                    os.close(dirfd)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self._gc()
        ms = (time.perf_counter() - t0) * 1000.0
        self.last_save_ms = ms
        self.last_save_bytes = manifest["bytes"]
        if self.sink is not None:
            self.sink.write(step=manifest["step"], ckpt_save_ms=round(ms, 3),
                            ckpt_bytes=manifest["bytes"], ckpt_path=final)

    # -- async bookkeeping -------------------------------------------------
    def _raise_pending(self) -> None:
        with self._lock:
            done = [f for f in self._pending if f.done()]
            self._pending = [f for f in self._pending if not f.done()]
        for f in done:
            f.result()  # re-raise the worker's exception, if any

    def wait(self) -> None:
        """Drain in-flight async saves; re-raise their errors."""
        while True:
            with self._lock:
                if not self._pending:
                    return
                f = self._pending.pop(0)
            f.result()

    def close(self) -> None:
        self.wait()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- verify / discover -------------------------------------------------
    def read_manifest(self, path: str) -> Dict[str, Any]:
        with open(os.path.join(path, MANIFEST_NAME)) as f:
            m = json.load(f)
        if m.get("schema") not in (MANIFEST_SCHEMA, MANIFEST_SCHEMA_SHARDED):
            raise CheckpointError(
                f"{path}: manifest schema {m.get('schema')!r} not in "
                f"{(MANIFEST_SCHEMA, MANIFEST_SCHEMA_SHARDED)}")
        return m

    def _load_leaves(self, path: str, manifest: Dict[str, Any]
                     ) -> List[np.ndarray]:
        from apex_tpu.utils.checkpoint import load_checkpoint

        blob = load_checkpoint(os.path.join(path, manifest["payload"]))
        entries = manifest["leaves"]
        try:
            # keys are original flat leaf indices (sharded leaves are
            # absent — they live in the per-process shard payloads); old
            # manifests without leaf_index are positional
            return [np.asarray(blob[str(e.get("leaf_index", j))])
                    for j, e in enumerate(entries)]
        except KeyError as e:
            raise CheckpointError(
                f"{path}: payload is missing leaf {e} of "
                f"{len(entries)}") from e

    def _load_shard_dir(self, path: str, manifest: Dict[str, Any],
                        pidx: Optional[int] = None):
        """One process's shard payload of a sharded checkpoint (default:
        this process's): ``{leaf_index: {index_key: np.ndarray}}`` after
        verifying the shard manifest + per-shard crc32s; raises
        CheckpointError on a missing/torn shard dir (a crash between
        process 0's publish and this process's shard rename)."""
        from apex_tpu.utils.checkpoint import load_checkpoint

        if pidx is None:
            pidx, _ = _process_info()
        sub = os.path.join(path, f"shard-p{pidx}")
        try:
            with open(os.path.join(sub, MANIFEST_NAME)) as f:
                sm = json.load(f)
        except OSError as e:
            raise CheckpointError(
                f"{path}: missing shard dir for process {pidx} "
                "(torn sharded save)") from e
        if sm.get("schema") != MANIFEST_SCHEMA:
            raise CheckpointError(
                f"{sub}: shard manifest schema {sm.get('schema')!r} != "
                f"{MANIFEST_SCHEMA}")
        blob = load_checkpoint(os.path.join(sub, sm["payload"]))
        out: Dict[int, Dict[str, np.ndarray]] = {}
        for spec in sm["shards"]:
            key = f"{spec['leaf']}|{spec['index']}"
            try:
                arr = np.asarray(blob[key])
            except KeyError as e:
                raise CheckpointError(
                    f"{sub}: shard payload is missing {key}") from e
            if (list(arr.shape) != spec["shape"]
                    or str(arr.dtype) != spec["dtype"]
                    or _crc(arr) != spec["crc32"]):
                raise CheckpointError(
                    f"{sub}: shard {key} fails its manifest "
                    "shape/dtype/crc32 — corrupt shard payload")
            out.setdefault(int(spec["leaf"]), {})[spec["index"]] = arr
        expected = set(manifest.get("sharded", {}))
        if {str(i) for i in out} != expected:
            raise CheckpointError(
                f"{sub}: shard payload covers leaves {sorted(out)}, "
                f"manifest expects {sorted(expected)}")
        return out, sm

    def verify(self, path: str) -> bool:
        """True iff ``path`` holds a complete, uncorrupted checkpoint:
        manifest parses, payload loads, every leaf matches its manifest
        shape/dtype/crc32."""
        try:
            self._verify_or_raise(path)
            return True
        except Exception:
            return False

    def _verify_or_raise(self, path: str):
        manifest = self.read_manifest(path)
        host = self._load_leaves(path, manifest)
        for i, (h, spec) in enumerate(zip(host, manifest["leaves"])):
            if list(h.shape) != spec["shape"] or str(h.dtype) != spec["dtype"]:
                raise CheckpointError(
                    f"{path}: leaf {i} is {h.shape}:{h.dtype}, manifest "
                    f"says {spec['shape']}:{spec['dtype']}")
            if _crc(h) != spec["crc32"]:
                raise CheckpointError(
                    f"{path}: leaf {i} fails its crc32 — corrupt payload")
        by_proc = None
        if manifest.get("sharded"):
            by_proc = self._check_all_shard_dirs(path, manifest)
        return manifest, host, by_proc

    def _check_all_shard_dirs(self, path: str, manifest: Dict[str, Any]
                              ) -> Dict[int, Dict[int, Dict[str, Any]]]:
        """EVERY process's shard dir must be present, step-consistent AND
        pass its own manifest's per-shard crc32s. Checked by every process
        (not just for its own shard) so all ranks reach the same
        verify()/latest_valid() verdict — a torn or bit-rotted shard dir
        (even another rank's) makes the whole job fall back to the
        previous checkpoint instead of rank K alone restoring older state
        and diverging from its peers. Returns the verified payloads keyed
        by process index — restore's exact path uses its own, the elastic
        reshard path assembles from all of them."""
        degree = max(int(s["dp_degree"])
                     for s in manifest["sharded"].values())
        by_proc: Dict[int, Dict[int, Dict[str, Any]]] = {}
        for p in range(degree):
            sub = os.path.join(path, f"shard-p{p}")
            try:
                with open(os.path.join(sub, MANIFEST_NAME)) as f:
                    sm = json.load(f)
            except OSError as e:
                raise CheckpointError(
                    f"{path}: records dp degree {degree} but the shard dir "
                    f"for process {p} is missing — torn sharded save or "
                    "dp-degree skew") from e
            if sm.get("step") != manifest["step"]:
                raise CheckpointError(
                    f"{sub}: shard dir step {sm.get('step')} != manifest "
                    f"step {manifest['step']} — stale shard dir")
            by_proc[p], _ = self._load_shard_dir(path, manifest, pidx=p)
        return by_proc

    def latest_valid(self) -> Optional[str]:
        """Path of the newest checkpoint that verifies; torn or corrupt
        ones (crashed save, truncated payload, flipped bits) are skipped
        with a warning. ``None`` when no valid checkpoint exists."""
        from apex_tpu._logging import get_logger

        for step in reversed(self.all_steps()):
            p = self.step_path(step)
            if self.verify(p):
                return p
            get_logger("apex_tpu.resilience").warning(
                "skipping invalid checkpoint %s (torn or corrupt)", p)
        return None

    # -- restore -----------------------------------------------------------
    @staticmethod
    def _merged_shards(by_proc, leaf_idx: int) -> Dict[str, Any]:
        """Every process's placements of one leaf, merged (the elastic
        assembly input — replica-0 placements are disjoint by
        construction; overlap is caught downstream by assemble_leaf)."""
        merged: Dict[str, Any] = {}
        for shards in (by_proc or {}).values():
            merged.update(shards.get(leaf_idx, {}))
        return merged

    def restore(self, target: Pytree, path: Optional[str] = None,
                allow_reshard: Optional[bool] = None) -> Tuple[Pytree, int]:
        """Load a checkpoint onto ``target``'s structure; returns
        ``(state, step)``. ``path=None`` discovers :meth:`latest_valid`.
        The manifest fingerprint must match ``target``'s — a checkpoint
        from a different train-state revision is refused, not mis-bound.

        ``allow_reshard`` (default: the manager's constructor setting)
        relaxes the refusal for TOPOLOGY skew only: the treedef and leaf
        count must still match, but leaves whose dp shard layout changed
        are reassembled from EVERY process's crc-verified shard dir and
        re-sliced onto the live layout via the manifest's ``elastic``
        specs (:mod:`apex_tpu.resilience.reshard`) — save at dp=N,
        resume at dp=M. The host ms spent resharding lands on
        :attr:`last_reshard_ms`. Without the flag the old loud refusal is
        unchanged."""
        allow = (self.allow_reshard if allow_reshard is None
                 else bool(allow_reshard))
        if path is None:
            path = self.latest_valid()
            if path is None:
                raise CheckpointError(
                    f"no valid checkpoint under {self.directory}")
        try:
            manifest, host, by_proc = self._verify_or_raise(path)
        except CheckpointError:
            raise
        except Exception as e:
            # missing dir, a path to a pre-manager-format file, damaged
            # JSON, ... — one error type for callers to catch
            raise CheckpointError(
                f"'{path}' is not a readable checkpoint "
                f"({type(e).__name__}: {e})") from e
        self.last_reshard_ms = 0.0
        live = fingerprint(target)
        reshard_mode = manifest["fingerprint"] != live
        if reshard_mode:
            if not allow:
                raise CheckpointError(
                    f"checkpoint '{path}' was written by a different "
                    "train-state revision — refusing to mis-bind state.\n"
                    f"   saved: {manifest['fingerprint'][:200]}...\n"
                    f"   live:  {live[:200]}...")
            if not _treedef_compatible(manifest["fingerprint"], target):
                raise CheckpointError(
                    f"checkpoint '{path}': allow_reshard only relaxes "
                    "per-leaf shard layouts; this checkpoint has a "
                    "different tree STRUCTURE — revision skew, not "
                    "topology skew")
        leaves, treedef = jax.tree_util.tree_flatten(target)
        if reshard_mode and manifest.get("num_leaves") != len(leaves):
            raise CheckpointError(
                f"checkpoint '{path}' has {manifest.get('num_leaves')} "
                f"leaves, live structure has {len(leaves)}")
        sharded = manifest.get("sharded", {})
        if not sharded and not reshard_mode:
            state = jax.tree_util.tree_unflatten(
                treedef, [jnp.asarray(h) for h in host])
            return state, int(manifest["step"])
        pidx, _ = _process_info()
        shards = (by_proc or {}).get(pidx) or {}
        elastic = manifest.get("elastic") or {}
        by_idx = {e.get("leaf_index", j): h
                  for j, (e, h) in enumerate(zip(manifest["leaves"], host))}
        from apex_tpu.resilience import reshard as _rs

        out = []
        for i, leaf in enumerate(leaves):
            if str(i) in sharded:
                spec = sharded[str(i)]
                entry = {
                    "__sharded__": True,
                    "global_shape": spec["global_shape"],
                    "dtype": spec["dtype"],
                    "process_count": spec["dp_degree"],
                    "shards": shards.get(i, {}),
                }
                if allow and (not _is_cross_process(leaf)
                              or _sharded_layout_skew(leaf, entry)):
                    t0 = time.perf_counter()
                    out.append(_reshard_entry_leaf(
                        leaf,
                        dict(entry, shards=self._merged_shards(by_proc, i)),
                        i, elastic.get(str(i))))
                    self.last_reshard_ms += (
                        time.perf_counter() - t0) * 1000.0
                    continue
                if not _is_cross_process(leaf):
                    raise CheckpointError(
                        f"{path}: leaf {i} was saved as per-process shards "
                        "(dp degree "
                        f"{spec['dp_degree']}) but the live target is "
                        "fully addressable — dp-degree skew; restore on "
                        "the original topology")
                out.append(_restore_sharded_leaf(leaf, entry, i))
            else:
                h = by_idx[i]
                shape_skew = tuple(h.shape) != tuple(jnp.shape(leaf))
                if reshard_mode and shape_skew:
                    espec = elastic.get(str(i))
                    if espec is None:
                        raise CheckpointError(
                            f"{path}: leaf {i} shape changed "
                            f"{tuple(h.shape)} -> "
                            f"{tuple(jnp.shape(leaf))} and the checkpoint "
                            "carries no elastic spec for it — re-save "
                            "with elastic= (the optimizers' "
                            "elastic_spec()) or restore on the original "
                            "topology")
                    t0 = time.perf_counter()
                    full = _rs.retarget_leaf(h, espec, jnp.shape(leaf))
                    self.last_reshard_ms += (
                        time.perf_counter() - t0) * 1000.0
                    out.append(_rebind_global(leaf, i, full))
                elif reshard_mode and _is_cross_process(leaf):
                    # plain-saved leaf binding onto a sharded live layout
                    # (e.g. a replicated leaf the new topology shards):
                    # pure placement retarget, no arithmetic needed
                    out.append(_rebind_global(leaf, i, np.asarray(h)))
                else:
                    out.append(jnp.asarray(h))
        return (jax.tree_util.tree_unflatten(treedef, out),
                int(manifest["step"]))

    # -- retention ---------------------------------------------------------
    def _gc(self) -> None:
        """keep-last-N + keep-every-K milestone retention, plus a sweep of
        staging/trash dirs orphaned by a crashed writer — a relaunch-heavy
        spot job must not leak one checkpoint-sized dir per kill."""
        pid_suffix = f"-{os.getpid()}"
        for name in os.listdir(self.directory):
            if name.endswith(pid_suffix):
                continue  # this writer's own live staging
            if name.startswith(f"{_TMP_PREFIX}shard-"):
                # another process's shard staging. A LIVE peer mid-publish
                # (its step's dir exists but its shard is not yet renamed
                # in) must not be torn — but a dead peer's staging would
                # otherwise leak one shard-sized dir per crash. Dead means
                # the publish can no longer complete: the step dir is gone
                # (GC'd / never published before the job died) or already
                # holds this process's shard (rename done, cleanup lost).
                rest = name[len(f"{_TMP_PREFIX}shard-"):]
                target, _, pname = rest.rpartition("-")
                tdir = os.path.join(self.directory, target)
                if (not os.path.isdir(tdir)
                        or os.path.exists(os.path.join(
                            tdir, f"shard-{pname}"))):
                    shutil.rmtree(os.path.join(self.directory, name),
                                  ignore_errors=True)
                continue
            p = os.path.join(self.directory, name)
            if name.startswith(_TMP_PREFIX):
                # a dead writer's staging dir: never completed, delete
                shutil.rmtree(p, ignore_errors=True)
            elif name.startswith(_TRASH_PREFIX):
                # a dead writer's parked old copy (same-step re-save). If
                # the crash hit between the two renames, this trash is the
                # ONLY copy of that step — restore it, don't delete it.
                orig = name[len(_TRASH_PREFIX):].rsplit("-", 1)[0]
                dest = os.path.join(self.directory, orig)
                if _step_of(orig) is not None and not os.path.isdir(dest):
                    try:
                        os.replace(p, dest)
                        continue
                    except OSError:
                        pass
                shutil.rmtree(p, ignore_errors=True)
        steps = self.all_steps()
        if len(steps) <= self.keep_last_n:
            return
        keep = set(steps[-self.keep_last_n:])
        if self.keep_every_k:
            keep.update(s for s in steps if s % self.keep_every_k == 0)
        for s in steps:
            if s not in keep:
                shutil.rmtree(self.step_path(s), ignore_errors=True)
