"""Data-parallel gradient synchronization — the DDP capability as a mesh
program.

Reference: ``apex/parallel/distributed.py:129-639`` — bucketed, comm/compute-
overlapped NCCL allreduce driven by per-param grad hooks: first backward
records arrival order, buckets are flattened (``apex_C.flatten``), optionally
cast fp32, pre-divided, allreduced on side streams, averaged and unflattened
back (``allreduce_bucket:425-470``), with options ``message_size``,
``allreduce_always_fp32``, ``gradient_average``, ``gradient_predivide_factor``,
``delay_allreduce``, ``num_allreduce_streams``.

TPU re-design: grads come out of ``jax.grad`` as one pytree, so "hook-driven
readiness" disappears; the capability that remains is (a) the collective
itself (``lax.psum`` over the ``dp`` mesh axis), (b) dtype policy, (c)
pre/post scaling, and (d) **bucketing** — concatenating many small grads into
a few flat buffers so the ICI sees large transfers (the reference's
``message_size`` batching; XLA also combines small all-reduces itself, this
makes the batching explicit and deterministic).

Comm/compute overlap: the per-bucket collectives are emitted inside the
jitted step so XLA's latency-hiding scheduler interleaves them with
independent work, replacing the reference's manual side streams + events
(``distributed.py:411-470``) — but a ``lax.scan`` is a scheduling barrier:
accumulate microbatch grads in a scan and every bucket's reduce waits for
the whole loop. :meth:`DistributedDataParallel.accumulate_and_average`
restores the reference's hook-driven overlap shape (``overlap_reductions``,
``delay_allreduce=False``): it scans all-but-the-last microbatch, runs the
LAST microbatch's backward unrolled outside the scan, and emits the bucket
reduces in **reverse production order** — each bucket's collective depends
only on its own leaves' final contributions, so the late-layer buckets
(whose grads finalize first in backward) launch while the front of the
backward is still computing. :meth:`average_gradients` emits the same
reverse order on the barriered path, where it is a free scheduler hint.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.comm.collectives import (
    CompressionConfig,
    allreduce_wire_bytes,
    compressed_allreduce,
    fold_seed,
)
from apex_tpu.comm.error_feedback import init_error_feedback
from apex_tpu.parallel.mesh import DP_AXIS, vma_tracked


def _flatten_buckets(leaves: List[jnp.ndarray], message_size: int):
    """Group leaf indices into buckets of ~message_size elements per dtype
    (ref bucket construction, ``distributed.py:283-318`` + ``message_size``
    default 10M elements)."""
    buckets = []  # list of (dtype, [leaf_idx...])
    current = {}
    counts = {}
    for i, g in enumerate(leaves):
        dt = g.dtype
        current.setdefault(dt, []).append(i)
        counts[dt] = counts.get(dt, 0) + g.size
        if counts[dt] >= message_size:
            buckets.append((dt, current.pop(dt)))
            counts[dt] = 0
    for dt, idxs in current.items():
        if idxs:
            buckets.append((dt, idxs))
    return buckets


def _rebuild(comm_state, new_leaves):
    """Re-hang updated residual leaves on the comm_state structure."""
    if comm_state is None or new_leaves is None:
        return comm_state
    treedef = jax.tree_util.tree_structure(comm_state)
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def _record_comm_metrics(metrics, bucket_bytes, baseline_bytes):
    """Record per-bucket + total modeled wire bytes and the compression
    ratio into a monitor ``Metrics`` (all trace-time constants).
    ``bucket_bytes``/``baseline_bytes`` are keyed by BUCKET INDEX (tree
    order), so the ``comm_bucket{i}_bytes`` labels are stable however the
    reduction emission order is scheduled."""
    total = float(sum(bucket_bytes.values()))
    base = float(sum(baseline_bytes.values()))
    entries = {f"comm_bucket{i}_bytes": bucket_bytes[i]
               for i in sorted(bucket_bytes)}
    entries["comm_wire_bytes"] = total
    entries["comm_compression_ratio"] = base / total if total else 1.0
    return metrics.record(**entries)


class DistributedDataParallel:
    """Functional DDP: ``grads = ddp.average_gradients(grads)`` inside the
    mesh program (shard_map/pjit body). Mirrors the reference constructor
    options (``distributed.py:162-253``) that still have meaning under XLA.
    """

    def __init__(
        self,
        axis: str = DP_AXIS,
        message_size: int = 10_000_000,
        gradient_average: bool = True,
        gradient_predivide_factor: float = 1.0,
        allreduce_always_fp32: bool = False,
        flat_buckets: bool = True,
        compression: Optional[CompressionConfig] = None,
    ):
        self.axis = axis
        self.message_size = message_size
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.flat_buckets = flat_buckets
        self.compression = compression

    def _world(self):
        # inside a mesh program the axis size is static
        return lax.axis_size(self.axis)

    def init_comm_state(self, grads_template: Any) -> Optional[Any]:
        """Error-feedback residuals for ``compression='int8_ef'`` — one fp32
        leaf per grad leaf, carried through the step like the loss-scaler
        state and threaded back into :meth:`average_gradients` via
        ``comm_state``. ``None`` for policies with no step-to-step state."""
        if self.compression is not None and self.compression.error_feedback:
            return init_error_feedback(grads_template)
        return None

    def comm_state_dict(self, comm_state: Any) -> Optional[dict]:
        """Serialize the error-feedback comm state for a checkpoint
        (``None`` stays ``None``) — the resilience manifest path: include
        the returned dict in the pytree handed to
        :class:`apex_tpu.resilience.CheckpointManager` (or any
        ``state_dict`` blob) so a resumed run keeps its residuals instead
        of silently restarting EF from zero."""
        from apex_tpu.comm import error_feedback as ef

        return None if comm_state is None else ef.state_dict(comm_state)

    def load_comm_state_dict(self, comm_state_template: Any,
                             d: Optional[dict]) -> Optional[Any]:
        """Inverse of :meth:`comm_state_dict`; validates the stored
        structure against the live one (from :meth:`init_comm_state`)."""
        from apex_tpu.comm import error_feedback as ef

        return None if d is None else ef.load_state_dict(
            comm_state_template, d)

    def replicate(self, params: Any) -> Any:
        """Mark params as per-replica (device-varying) inside the mesh
        program — the analogue of each DDP rank holding its own module copy.

        This matters for AD semantics: JAX's shard_map auto-inserts a psum
        when differentiating w.r.t. *replicated* values (the transpose of the
        implicit broadcast), which would double-count with
        :meth:`average_gradients`. Differentiate w.r.t.
        ``ddp.replicate(params)`` and the gradients come back per-replica,
        exactly like the reference's per-process ``.grad`` buffers, ready for
        the explicit allreduce. Under ``check_vma=False`` nothing is
        tracked and nothing auto-inserted, so the params pass through."""
        if not vma_tracked(self.axis):
            return params
        return jax.tree_util.tree_map(
            lambda p: lax.pcast(p, self.axis, to="varying"), params
        )

    def average_gradients(self, grads: Any, enabled: bool = True,
                          comm_state: Optional[Any] = None, seed=None,
                          metrics: Optional[Any] = None) -> Any:
        """The allreduce_bucket pipeline (ref ``distributed.py:425-470``):
        [flatten] → [fp32 cast] → predivide → psum → postdivide → unflatten.
        Must be called inside a mesh program with ``self.axis`` bound.

        ``enabled``: static python bool — the functional form of the ref's
        ``disable_allreduce``/torch-DDP ``no_sync``. There is deliberately no
        stateful context-manager variant: under ``jit`` a mutable flag is
        frozen at trace time, so an accumulate-then-sync loop must instead
        trace two specializations (``enabled=False`` for accumulation
        microbatches, ``enabled=True`` for the boundary step) or accumulate
        on device and allreduce once — see
        ``pipeline_parallel/schedules/fwd_bwd_no_pipelining.py``.

        With a :class:`~apex_tpu.comm.CompressionConfig` the psum is the
        quantized two-pass allreduce (``comm/collectives.py``) — int8 codes
        + fp32 block scales on the wire. Policy ``int8_ef`` additionally
        threads the error-feedback residual: pass ``comm_state`` (from
        :meth:`init_comm_state`) and the return becomes ``(grads,
        new_comm_state)``; the residual lives in the same predivided units
        the wire carries, so ``gradient_predivide_factor`` composes. Under
        AMP those units include the loss scale: non-finite compression
        errors (overflow steps) are dropped rather than carried, and a
        dynamic-scale change mis-scales one step's correction by the
        ratio before EF re-absorbs it (the ZeRO optimizers, which see the
        scale, carry their residual unscaled instead).
        ``seed``: int32 scalar for ``stochastic_rounding`` (fold the step
        count in for fresh streams). Compressed results come off a final
        all-gather — replicated by construction, so programs that assert
        value-movement types need ``check_vma=False`` (the pattern
        ``tests/test_distributed_optimizers.py`` already uses for the ZeRO
        all-gathers).

        ``metrics``: an :class:`apex_tpu.monitor.Metrics` to record comm
        telemetry into — per-bucket modeled bytes-on-wire
        (``comm_bucket{i}_bytes``, ring model, identical to what
        ``comm.accounting`` prices off the compiled HLO), the
        ``comm_wire_bytes`` total, and ``comm_compression_ratio``
        (uncompressed-wire / actual-wire; 1.0 without compression). The
        values are trace-time constants — recording them never adds device
        work. When passed, the updated Metrics is appended to the return:
        ``grads`` → ``(grads, metrics)``; ``(grads, comm_state)`` →
        ``(grads, comm_state, metrics)``.
        """
        if not isinstance(enabled, bool):
            raise TypeError(
                f"enabled must be a static python bool, got {enabled!r}")
        cfg = self.compression
        compressing = cfg is not None and cfg.enabled
        if compressing and cfg.error_feedback and comm_state is None:
            raise ValueError(
                "compression policy 'int8_ef' carries state: pass comm_state="
                "ddp.init_comm_state(grads) and thread the returned state")
        # per-bucket modeled (actual, uncompressed-baseline) wire bytes —
        # python floats from static shapes, keyed by bucket index (tree
        # order) so the labels are emission-order-independent
        bucket_bytes: dict = {}
        baseline_bytes: dict = {}

        # uniform calling convention: state appended iff passed in, then
        # metrics iff passed in
        def wrap(g, s):
            out = (g,)
            if comm_state is not None:
                out += (s,)
            if metrics is not None:
                out += (_record_comm_metrics(metrics, bucket_bytes,
                                             baseline_bytes),)
            return out[0] if len(out) == 1 else out

        if not enabled:
            return wrap(grads, comm_state)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if not leaves:
            return wrap(grads, comm_state)
        world = self._world()

        def _account(bi: int, n: int, dtype) -> None:
            base_item = 4 if self.allreduce_always_fp32 else dtype.itemsize
            bucket_bytes[bi] = allreduce_wire_bytes(n, base_item, world, cfg)
            baseline_bytes[bi] = allreduce_wire_bytes(n, base_item, world,
                                                     None)

        # Predivide is applied unconditionally before the allreduce — it is
        # the fp16/bf16 overflow guard; only the post-multiply is gated on
        # gradient_average (ref distributed.py:445-454).
        pre = 1.0 / self.gradient_predivide_factor
        post = self.gradient_predivide_factor / world if self.gradient_average else 1.0

        res_leaves = (jax.tree_util.tree_flatten(comm_state)[0]
                      if comm_state is not None else None)
        new_res = list(res_leaves) if res_leaves is not None else None

        def _reduce_flat(flat, residual=None, bucket_seed=None):
            """-> (reduced flat, new residual or None). Traced under the
            canonical ``comm`` monitor span so the allreduce shows up as
            its own phase in trace/pyprof reports."""
            from apex_tpu.monitor.trace import span

            with span("comm"):
                if compressing:
                    comm = flat.astype(jnp.float32)
                    if pre != 1.0:
                        comm = comm * pre
                    comm, residual = compressed_allreduce(
                        comm, self.axis, cfg, residual=residual,
                        seed=bucket_seed)
                else:
                    comm = (flat.astype(jnp.float32)
                            if self.allreduce_always_fp32 else flat)
                    if pre != 1.0:
                        comm = comm * pre
                    comm = lax.psum(comm, self.axis)
                if post != 1.0:
                    comm = comm * post
            return comm, residual

        def _bucket_seed(i):
            # hash-combined, not seed+i: a step-counter seed must not make
            # bucket i at step s replay bucket i+1 at step s-1
            return None if seed is None else fold_seed(seed, i)

        # Reverse production order (satellite of the overlap work): the
        # backward emits the LAST layers' grads first, so the highest-index
        # buckets/leaves (tree order tracks forward order) finalize
        # earliest — emitting their reduces first is the reference's
        # arrival-order trick (``distributed.py:283-318``): the scheduler
        # sees launchable collectives while the front of the backward is
        # still computing. Pure emission-order change: bucket contents,
        # seeds and metric labels stay keyed by bucket index.
        if not self.flat_buckets:
            out = [None] * len(leaves)
            for i in reversed(range(len(leaves))):
                g = leaves[i]
                r = res_leaves[i].reshape(-1) if res_leaves is not None \
                    else None
                _account(i, g.size, g.dtype)
                red, r_new = _reduce_flat(g.reshape(-1), r, _bucket_seed(i))
                out[i] = red.reshape(g.shape).astype(g.dtype)
                if new_res is not None and r_new is not None:
                    new_res[i] = r_new.reshape(res_leaves[i].shape)
            return wrap(jax.tree_util.tree_unflatten(treedef, out),
                        _rebuild(comm_state, new_res))

        out = [None] * len(leaves)
        buckets = _flatten_buckets(leaves, self.message_size)
        for bi in reversed(range(len(buckets))):
            _dt, idxs = buckets[bi]
            flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
            _account(bi, flat.size, flat.dtype)
            residual = None
            if res_leaves is not None:
                residual = jnp.concatenate(
                    [res_leaves[i].reshape(-1) for i in idxs])
            red, r_new = _reduce_flat(flat, residual, _bucket_seed(bi))
            offset = 0
            for i in idxs:
                n = leaves[i].size
                out[i] = red[offset : offset + n].reshape(leaves[i].shape).astype(
                    leaves[i].dtype
                )
                if new_res is not None and r_new is not None:
                    new_res[i] = r_new[offset : offset + n].reshape(
                        res_leaves[i].shape)
                offset += n
        return wrap(jax.tree_util.tree_unflatten(treedef, out),
                    _rebuild(comm_state, new_res))

    def accumulate_and_average(
        self,
        value_and_grad_fn,
        params: Any,
        microbatches: Any,
        *,
        microbatch_keys: Optional[Any] = None,
        unroll: int = 1,
        enabled: bool = True,
        comm_state: Optional[Any] = None,
        seed=None,
        metrics: Optional[Any] = None,
    ):
        """Grad accumulation with overlap-scheduled reduction — the
        reference's ``overlap_reductions`` (``delay_allreduce=False``)
        rebuilt for XLA scheduling.

        The barriered recipe (``forward_backward_no_pipelining`` + one
        :meth:`average_gradients` after it) hides nothing: a ``lax.scan``
        releases ALL its outputs at once, so every bucket's collective
        waits for the full backward. This method restructures the same
        math — scan the first ``M-1`` microbatches, run the LAST
        microbatch's backward **unrolled outside the scan**, and emit the
        bucket reduces (via :meth:`average_gradients`, reverse production
        order) against it: each bucket's collective depends only on its
        own leaves' final-microbatch contributions, which materialize
        progressively through the unrolled backward, so the late-layer
        buckets launch while the early layers' dX/dW GEMMs are still
        running — grad-hook arrival-order overlap, from dataflow alone.

        ``value_and_grad_fn(params, microbatch[, key]) -> (loss, grads)``
        (close over ``ddp.replicate`` / loss scaling as needed);
        ``microbatches``: pytree with leading dim ``M``;
        ``microbatch_keys``: optional ``[M, ...]`` per-microbatch PRNG
        keys. Remaining kwargs go to :meth:`average_gradients`.

        Returns ``(mean_loss, grads[, comm_state][, metrics])`` —
        **loss-curve-identical** to the barriered path: the scan
        accumulates ``(((g₁+g₂)+…)+g_{M-1})`` and the peeled step adds
        ``g_M`` last, the exact association the full scan performs, and
        the reduction math is shared — only the schedule changes
        (``tests/test_overlap.py`` pins the equality, int8+EF included).
        """
        leaves = jax.tree_util.tree_leaves(microbatches)
        if not leaves:
            raise ValueError("microbatches is an empty pytree")
        m = leaves[0].shape[0]

        def call(mb, key):
            from apex_tpu.monitor.trace import span

            with span("fwd_bwd"):
                return (value_and_grad_fn(params, mb) if key is None
                        else value_and_grad_fn(params, mb, key))

        def take(i):
            return jax.tree_util.tree_map(lambda x: x[i], microbatches)
        last_key = (None if microbatch_keys is None
                    else microbatch_keys[m - 1])
        if m > 1:
            head = jax.tree_util.tree_map(lambda x: x[: m - 1], microbatches)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

            def body(acc, mk):
                mb, key = mk
                loss_sum, gacc = acc
                l, g = call(mb, key)
                return (loss_sum + l,
                        jax.tree_util.tree_map(jnp.add, gacc, g)), None

            if microbatch_keys is not None:
                (loss_sum, gacc), _ = lax.scan(
                    body, (jnp.zeros(()), zeros),
                    (head, microbatch_keys[: m - 1]), unroll=unroll)
            else:
                (loss_sum, gacc), _ = lax.scan(
                    lambda acc, mb: body(acc, (mb, None)),
                    (jnp.zeros(()), zeros), head, unroll=unroll)
            l_last, g_last = call(take(m - 1), last_key)
            loss_sum = loss_sum + l_last
            grads = jax.tree_util.tree_map(jnp.add, gacc, g_last)
        else:
            loss_sum, grads = call(take(0), last_key)
        red = self.average_gradients(grads, enabled=enabled,
                                     comm_state=comm_state, seed=seed,
                                     metrics=metrics)
        red = red if isinstance(red, tuple) else (red,)
        return (loss_sum / m,) + red

    def broadcast_params(self, params: Any) -> Any:
        """Make all ranks along the axis agree on rank-0's values (ref param
        broadcast at DDP init, ``distributed.py:254``). Implemented as a
        masked psum — same result as gathering and taking index 0, but 1x
        memory and ordinary allreduce traffic instead of a world-times-size
        gather."""
        # is_zero is device-varying; mixing it in makes the select varying
        # regardless of whether params came in replicated or per-replica.
        is_zero = lax.axis_index(self.axis) == 0
        return jax.tree_util.tree_map(
            lambda p: lax.psum(
                jnp.where(is_zero, p, jnp.zeros_like(p)), self.axis
            ),
            params,
        )


class Reducer:
    """Manual-sync variant (ref ``apex/parallel/distributed.py:89-128``):
    broadcast once, then ``reduce`` when the user says so — no averaging
    options, raw sum like the reference."""

    def __init__(self, axis: str = DP_AXIS):
        self.axis = axis

    def reduce(self, tree: Any) -> Any:
        return jax.tree_util.tree_map(lambda g: lax.psum(g, self.axis), tree)

    def broadcast_params(self, params: Any) -> Any:
        return DistributedDataParallel(axis=self.axis).broadcast_params(params)
