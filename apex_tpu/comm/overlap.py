"""Decomposed collective matmuls — comm/compute overlap by construction.

Reference: the reference Apex hides its tensor-parallel collective latency
by hand: ``LinearWithGradAccumulationAndAsyncAllreduce`` launches the
input-grad all-reduce on a side stream and overlaps it with the dW GEMM
(``apex/transformer/tensor_parallel/layers.py:217-269``). Our rebuild's
layers note (``tensor_parallel/layers.py``) punted that job to XLA's
latency-hiding scheduler — which works for *independent* ops but cannot
overlap a **dependent** collective→matmul chain: ``all_gather(x) @ w`` is
one all-gather every FLOP waits on. The fix (Wang et al., "Overlap
Communication with Dependent Computation via Decomposition",
arXiv:2305.06942 — productionized as XLA:TPU's collective-matmul pass —
and the MLPerf TPU-pod playbook, arXiv:1909.09756) is to decompose the
collective into a ``ppermute`` ring and interleave one partial GEMM with
each hop, so every hop travels behind a matmul that does not depend on it.

Three ops, each a ``custom_vjp`` whose backward rides decomposed rings too:

``all_gather_matmul(x, w)``
    ``all_gather(x, gather_axis) @ w`` — the Megatron-SP entry ``g``
    fused with the column-parallel GEMM. Ring all-gather: at step ``t``
    the shard from rank ``idx+t`` arrives and its partial GEMM lands in
    the output slice while the next hop is already in flight.
    Unidirectional (W-1 sequential hops) or bidirectional (two
    counter-rotating streams, ⌈(W-1)/2⌉ sequential hops — both ICI
    directions busy). Exact: the gathered dim is non-contracting, so the
    decomposition reorders no floating-point reduction.

``matmul_reduce_scatter(x, w)``
    ``reduce_scatter(x @ w, scatter_axis)`` — the Megatron-SP exit ``ḡ``
    fused with the row-parallel GEMM. The accumulator for output shard
    ``d`` starts at rank ``d+1`` and rides the ring once; each rank adds
    its partial GEMM for the resident shard, so the hop carrying the
    previous accumulator overlaps the next partial GEMM. Matches the
    monolithic path to fp addition-reorder tolerance (the per-shard sum
    is associated in ring order instead of XLA's).

``matmul_all_reduce(x, w)``
    ``psum(x @ w)`` — the plain (non-SP) row-parallel exit: the
    reduce-scatter ring above followed by a ppermute ring broadcast.
    Backward is purely local (the psum transpose), exactly like the
    monolithic path.

Backward overlap: ``all_gather_matmul``'s dX is a ``matmul_reduce_scatter``
ring and its dW re-gathers ``x`` through a second ring with one partial dW
GEMM per hop (the reference's async-allreduce trick, generalized);
``matmul_reduce_scatter``'s backward runs ONE ring over the output
cotangent computing both dX slices and dW partials per hop.

Overlap here is *provable from the compiled HLO* on a box with no
accelerator, before any profile is taken:
:func:`apex_tpu.comm.accounting.overlap_report` checks async
``collective-permute-start``/``-done`` pairs with ``dot``\\ s scheduled
inside the window (TPU) or ring hops with data-independent ``dot``\\ s a
latency-hiding scheduler may overlap (pre-schedule/CPU HLO), and the
``*_wire_bytes`` models below agree op-for-op with what
``accounting.collective_report`` prices on the same program. Each ring is
wire-byte-neutral — ``(W-1)`` hops of one shard equal the monolithic
collective's ring cost exactly. One deliberate exception program-wide:
``all_gather_matmul``'s backward re-gathers its input for dW (the
Megatron-SP backward recipe — shard-sized residuals instead of storing
the gathered activation), so under full-remat training, which ALSO
replays the forward ring, the program pays one extra input gather per
column layer (~10% more wire bytes on the flagship) — bytes traded for
activation memory, and hops that all travel behind GEMMs regardless.

``matmul_param_gather(x, w_shard)``
    ``x @ all_gather(w_shard, axis=-1)`` — the same decomposition in **FSDP
    position** (arXiv:2004.13336's weight-update sharding taken to ZeRO-3):
    the *weight* is what is sharded (each dp rank owns a column shard), the
    activation is resident, and the gather ring hops weight shards while
    each hop's partial GEMM lands in an output column slice. Backward is
    the classic FSDP pair: dX **re-gathers** the weight through a second
    ring (re-materialize — the shard is the residual, the full weight is
    never saved: reshard-after-forward by construction) while dW rides a
    travelling-accumulator ring that reduce-scatters the dp-summed weight
    gradient straight into shard layout. The two backward rings rotate in
    opposite directions, so both ICI directions carry payload.

Wired in via ``ColumnParallelLinear``/``RowParallelLinear``/
``column_parallel_linear``/``row_parallel_linear`` ``overlap_comm=`` and
``GPTConfig.overlap_comm`` (``transformer/testing/standalone_gpt.py``);
``matmul_param_gather`` via ``apex_tpu.fsdp.FSDP.linear`` and the
``ParallelismPlan`` fsdp presets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.parallel.mesh import DP_AXIS, TP_AXIS
from apex_tpu.parallel.mesh import axis_size as _axis_size

__all__ = [
    "all_gather_matmul",
    "matmul_param_gather",
    "matmul_reduce_scatter",
    "matmul_all_reduce",
    "all_gather_matmul_wire_bytes",
    "matmul_param_gather_wire_bytes",
    "matmul_reduce_scatter_wire_bytes",
    "matmul_all_reduce_wire_bytes",
]


# ---------------------------------------------------------------------------
# wire-byte models (the accounting.collective_report agreement contract)


def all_gather_matmul_wire_bytes(shard_elems: int, itemsize: int,
                                 world: int) -> float:
    """Modeled bytes-on-wire per device of one ring all-gather-matmul whose
    INPUT shard has ``shard_elems`` elements: ``(W-1)`` collective-permute
    hops of the shard — identical to the monolithic all-gather's
    ``b_full·(W-1)/W``. Bidirectional moves the same bytes in fewer
    sequential steps."""
    if world <= 1:
        return 0.0
    return float(shard_elems) * itemsize * (world - 1)


def matmul_param_gather_wire_bytes(shard_elems: int, itemsize: int,
                                   world: int, backward: bool = False
                                   ) -> float:
    """Modeled wire bytes of one FSDP-position gather-matmul ring whose
    WEIGHT shard has ``shard_elems`` elements: ``(W-1)`` hops of the shard
    forward — identical to the monolithic tiled all-gather of the full
    weight. ``backward=True`` prices the backward pair instead: the dX
    re-gather ring (shard bytes again) plus the dW travelling accumulator
    (fp32, shard-shaped) — identical to the monolithic all-gather +
    fp32 reduce-scatter the unfused FSDP backward pays."""
    if world <= 1:
        return 0.0
    fwd = float(shard_elems) * itemsize * (world - 1)
    if not backward:
        return fwd
    return fwd + float(shard_elems) * 4 * (world - 1)


def matmul_reduce_scatter_wire_bytes(shard_elems: int, itemsize: int,
                                     world: int) -> float:
    """Modeled wire bytes of one matmul-reduce-scatter ring whose OUTPUT
    shard has ``shard_elems`` elements: ``(W-1)`` hops of the travelling
    accumulator — identical to the monolithic reduce-scatter's
    ``b_shard·(W-1)``."""
    if world <= 1:
        return 0.0
    return float(shard_elems) * itemsize * (world - 1)


def matmul_all_reduce_wire_bytes(shard_elems: int, itemsize: int,
                                 world: int) -> float:
    """Reduce-scatter ring + broadcast ring over the result's 1/W shard:
    ``2·b_shard·(W-1)`` — identical to the monolithic all-reduce's
    ``2·b_full·(W-1)/W``."""
    if world <= 1:
        return 0.0
    return 2.0 * float(shard_elems) * itemsize * (world - 1)


# ---------------------------------------------------------------------------
# ring plumbing


def _span_comm():
    """The canonical ``comm`` monitor span — ring hops carry the same HLO
    op-metadata phase tag as the DDP/ZeRO collectives, so
    ``monitor.report.phase_breakdown`` attributes hop time to ``comm``
    while the interleaved partial GEMMs stay in their fwd/bwd phase."""
    from apex_tpu.monitor.trace import span

    return span("comm")


def _pvary_like(x, ref):
    """Promote ``x`` to the value-movement type of ``ref`` (identity
    value-wise; no-op when vma tracking is off). Fresh buffers
    (``jnp.zeros``) are axis-invariant; mixing them with ring chunks needs
    the explicit cast under ``check_vma=True``."""
    from apex_tpu.transformer.tensor_parallel.mappings import pvary_like

    return pvary_like(x, ref)


def _gather_ring(x, axis_name: str, bidirectional: bool):
    """Yield ``(chunk, src_rank)`` for every rank's shard of ``x``, hopping
    between yields. The next hop's ``ppermute`` is issued BEFORE the chunk
    is yielded, so the caller's per-chunk GEMM is data-independent of the
    in-flight hop — the decomposition's whole point. Unidirectional: one
    stream, ``W-1`` hops deep; bidirectional: two counter-rotating
    streams, ``⌈(W-1)/2⌉`` hops deep, same total bytes."""
    world = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    if world == 1:
        yield x, idx
        return
    fwd = [(j, (j - 1) % world) for j in range(world)]  # recv from right
    if not bidirectional:
        chunk = x
        for t in range(world):
            if t < world - 1:
                with _span_comm():
                    nxt = lax.ppermute(chunk, axis_name, fwd)
            else:
                nxt = None
            yield chunk, (idx + t) % world
            chunk = nxt
        return
    bwd = [(j, (j + 1) % world) for j in range(world)]  # recv from left
    k_plus = (world - 1 + 1) // 2  # hops on the + stream (ceil)
    k_minus = (world - 1) // 2  # hops on the − stream (floor)
    yield x, idx
    plus = minus = x
    for t in range(1, max(k_plus, k_minus) + 1):
        with _span_comm():
            if t <= k_plus:
                plus = lax.ppermute(plus, axis_name, fwd)
            if t <= k_minus:
                minus = lax.ppermute(minus, axis_name, bwd)
        if t <= k_plus:
            yield plus, (idx + t) % world
        if t <= k_minus:
            yield minus, (idx - t + world) % world


def _chunk_slice(x, src, size: int, axis: int):
    return lax.dynamic_slice_in_dim(x, src * size, size, axis=axis)


def _place(out, part, src, size: int, axis: int):
    return lax.dynamic_update_slice_in_dim(out, part, src * size, axis=axis)


def _contract_leading(a, b):
    """dW partial: contract every leading (batch/seq) dim of ``a`` against
    ``b`` → ``(a.shape[-1], b.shape[-1])``, accumulated fp32. The
    monolithic dW is ONE dot with an fp32 MXU accumulator; summing W
    model-dtype partials would add W-1 roundings it never takes, so the
    ring keeps its running dW in fp32 and rounds once at the end."""
    n = a.ndim - 1
    return lax.dot_general(
        a, b, (((tuple(range(n)), tuple(range(n)))), ((), ())),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward rings (shared by the primals and the VJP rules)


def _ag_matmul_impl(x, kernel, axis_name, gather_axis, bidirectional):
    """all_gather(x, gather_axis) @ kernel, as a ppermute ring of partial
    GEMMs landing in the output slices."""
    world = _axis_size(axis_name)
    s_loc = x.shape[gather_axis]
    if world == 1:
        return jnp.dot(x, kernel)
    out_shape = list(x.shape[:-1]) + [kernel.shape[-1]]
    out_shape[gather_axis] = s_loc * world
    out = _pvary_like(
        jnp.zeros(tuple(out_shape), jnp.result_type(x.dtype, kernel.dtype)),
        x)
    for chunk, src in _gather_ring(x, axis_name, bidirectional):
        out = _place(out, jnp.dot(chunk, kernel), src, s_loc, gather_axis)
    return out


def _matmul_rs_impl(x, kernel, axis_name, scatter_axis):
    """reduce_scatter(x @ kernel, scatter_axis) as a shifting-accumulator
    ring: the accumulator for shard ``d`` starts at rank ``d+1``, visits
    every rank once collecting its partial GEMM, and arrives home after
    ``W-1`` hops — each hop independent of the partial GEMM the receiving
    rank computes next."""
    world = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    s = x.shape[scatter_axis]
    if s % world:
        raise ValueError(
            f"matmul_reduce_scatter needs dim {scatter_axis} ({s}) "
            f"divisible by the axis size ({world})")
    s_shard = s // world
    if world == 1:
        return jnp.dot(x, kernel)
    perm = [(j, (j + 1) % world) for j in range(world)]  # acc moves right
    acc = None
    for t in range(world):
        d = lax.rem(idx - 1 - t + 2 * world, world)
        part = jnp.dot(_chunk_slice(x, d, s_shard, scatter_axis), kernel)
        acc = part if acc is None else acc + part
        if t < world - 1:
            with _span_comm():
                acc = lax.ppermute(acc, axis_name, perm)
    return acc


def _ring_broadcast(shard, axis_name, gather_axis):
    """all_gather as a ppermute ring (the broadcast leg of
    matmul_all_reduce): every hop's payload is placed as it arrives, so
    trailing consumers of early slices can start before the ring drains."""
    world = _axis_size(axis_name)
    if world == 1:
        return shard
    s_loc = shard.shape[gather_axis]
    out_shape = list(shard.shape)
    out_shape[gather_axis] = s_loc * world
    out = _pvary_like(jnp.zeros(tuple(out_shape), shard.dtype), shard)
    for chunk, src in _gather_ring(shard, axis_name, False):
        out = _place(out, chunk, src, s_loc, gather_axis)
    return out


# ---------------------------------------------------------------------------
# public ops (custom VJPs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _all_gather_matmul(x, kernel, axis_name, gather_axis, bidirectional):
    return _ag_matmul_impl(x, kernel, axis_name, gather_axis, bidirectional)


def _ag_mm_fwd(x, kernel, axis_name, gather_axis, bidirectional):
    return (_ag_matmul_impl(x, kernel, axis_name, gather_axis,
                            bidirectional), (x, kernel))


def _ag_mm_bwd(axis_name, gather_axis, bidirectional, res, dy):
    x, kernel = res
    # dX: reduce_scatter(dy @ Wᵀ) — itself a decomposed overlap ring
    dx = _matmul_rs_impl(dy, kernel.T, axis_name, gather_axis)
    # dW: re-gather x through a second ring, one partial dW GEMM per hop
    # (the reference's input-grad-comm/dW-GEMM overlap, ring-shaped)
    s_loc = x.shape[gather_axis]
    dw = None
    for chunk, src in _gather_ring(x, axis_name, bidirectional):
        part = _contract_leading(
            chunk, _chunk_slice(dy, src, s_loc, gather_axis))
        dw = part if dw is None else dw + part
    return dx.astype(x.dtype), dw.astype(kernel.dtype)


_all_gather_matmul.defvjp(_ag_mm_fwd, _ag_mm_bwd)


def all_gather_matmul(x, kernel, *, axis_name: str = TP_AXIS,
                      gather_axis: int = 1, bidirectional: bool = False):
    """``all_gather(x, gather_axis) @ kernel`` with the gather decomposed
    into a ppermute ring interleaved with partial GEMMs.

    ``x``: the local shard, gathered along ``gather_axis`` (a
    non-contracting dim — seq for the Megatron-SP entry). ``kernel``:
    ``(in, out)``, contracted against ``x``'s last dim. Exact parity with
    the monolithic path (no reduction is reordered). ``bidirectional``
    splits the ring into two counter-rotating streams — same bytes, half
    the sequential hop depth (use on meshes whose both ICI directions are
    otherwise idle). Backward: dX rides a matmul_reduce_scatter ring, dW a
    second gather ring. Must run inside a mesh program; under
    ``check_vma=True`` pass a ``kernel`` already varying on every axis the
    activations vary on (``mappings.pvary_like``) so the dW reduction over
    the data axes lands on the pvary transpose."""
    return _all_gather_matmul(x, kernel, axis_name, gather_axis,
                              bool(bidirectional))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul_reduce_scatter(x, kernel, axis_name, scatter_axis):
    return _matmul_rs_impl(x, kernel, axis_name, scatter_axis)


def _mm_rs_fwd(x, kernel, axis_name, scatter_axis):
    return _matmul_rs_impl(x, kernel, axis_name, scatter_axis), (x, kernel)


def _mm_rs_bwd(axis_name, scatter_axis, res, dy):
    x, kernel = res
    # ONE ring over the cotangent shard computes both grads per hop:
    # dX slice = dy_src @ Wᵀ placed at src, dW += x[src]ᵀ dy_src — two
    # independent GEMMs behind every in-flight hop
    world = _axis_size(axis_name)
    s_loc = dy.shape[scatter_axis]
    shape = list(dy.shape[:-1]) + [kernel.shape[0]]
    shape[scatter_axis] = s_loc * world
    dx = _pvary_like(
        jnp.zeros(tuple(shape), jnp.result_type(dy.dtype, kernel.dtype)),
        dy)
    dw = None
    for chunk, src in _gather_ring(dy, axis_name, False):
        dx = _place(dx, jnp.dot(chunk, kernel.T), src, s_loc, scatter_axis)
        part = _contract_leading(
            _chunk_slice(x, src, s_loc, scatter_axis), chunk)
        dw = part if dw is None else dw + part
    return dx.astype(x.dtype), dw.astype(kernel.dtype)


_matmul_reduce_scatter.defvjp(_mm_rs_fwd, _mm_rs_bwd)


def matmul_reduce_scatter(x, kernel, *, axis_name: str = TP_AXIS,
                          scatter_axis: int = 1):
    """``reduce_scatter(x @ kernel, scatter_axis)`` with the scatter
    decomposed into a shifting-accumulator ppermute ring (Megatron-SP exit
    ``ḡ`` fused with the row-parallel GEMM).

    ``x``: ``(..., s, ..., in_local)`` full-length along ``scatter_axis``
    (divisible by the axis size); returns the local ``s/W`` shard of the
    summed product. Parity with ``psum_scatter(x @ kernel)`` up to fp
    addition reorder (ring association). Backward: one gather ring over
    the cotangent computing dX slices and dW partials per hop. Same
    ``pvary_like`` contract as :func:`all_gather_matmul`."""
    return _matmul_reduce_scatter(x, kernel, axis_name, scatter_axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul_all_reduce(x, kernel, axis_name, scatter_axis):
    return _ring_broadcast(
        _matmul_rs_impl(x, kernel, axis_name, scatter_axis),
        axis_name, scatter_axis)


def _mm_ar_fwd(x, kernel, axis_name, scatter_axis):
    y = _ring_broadcast(
        _matmul_rs_impl(x, kernel, axis_name, scatter_axis),
        axis_name, scatter_axis)
    return y, (x, kernel)


def _mm_ar_bwd(axis_name, scatter_axis, res, dy):
    # The ring output is rank-VARYING (equal values, per-rank type), so
    # downstream cotangents arrive as partials of the true dL/dy; sum them
    # once — the monolithic path pays the identical psum at its
    # invariant-output pvary transpose, so backward bytes match. After the
    # sum both grads are local GEMMs (ref row-parallel backward).
    x, kernel = res
    dy = lax.psum(dy, axis_name)
    dx = jnp.dot(dy, kernel.T).astype(x.dtype)
    dw = _contract_leading(x, dy).astype(kernel.dtype)
    return dx, dw


_matmul_all_reduce.defvjp(_mm_ar_fwd, _mm_ar_bwd)


def matmul_all_reduce(x, kernel, *, axis_name: str = TP_AXIS,
                      scatter_axis: int = 1):
    """``psum(x @ kernel)`` decomposed: the matmul_reduce_scatter ring
    followed by a ppermute broadcast ring — the plain row-parallel exit
    with the reduce half hidden behind the partial GEMMs.

    Needs ``x``'s ``scatter_axis`` dim divisible by the axis size (the
    internal shard). The result is value-identical on every rank but
    TYPE-varying under ``check_vma`` (it comes off a ring, not a psum) —
    downstream mappings (``copy_to_...`` etc.) treat varying input as a
    no-op, and the GPT ``_layer_stack`` casts its scan carry to match.
    Backward is purely local (the psum transpose). Same ``pvary_like``
    contract as :func:`all_gather_matmul`."""
    return _matmul_all_reduce(x, kernel, axis_name, scatter_axis)


# ---------------------------------------------------------------------------
# FSDP position: the WEIGHT is the sharded operand


def _mm_pg_impl(x, w_shard, axis_name, bidirectional):
    """x @ all_gather(w_shard, axis=-1): ring-gather the weight shards,
    one partial GEMM per hop landing in the output COLUMN slice. Exact —
    the gathered dim is non-contracting, no reduction is reordered."""
    world = _axis_size(axis_name)
    if world == 1:
        return jnp.dot(x, w_shard)
    n_loc = w_shard.shape[-1]
    out_shape = list(x.shape[:-1]) + [n_loc * world]
    out = _pvary_like(
        jnp.zeros(tuple(out_shape), jnp.result_type(x.dtype, w_shard.dtype)),
        x)
    axis = len(out_shape) - 1
    for chunk, src in _gather_ring(w_shard, axis_name, bidirectional):
        out = _place(out, jnp.dot(x, chunk), src, n_loc, axis)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul_param_gather(x, w_shard, axis_name, bidirectional):
    return _mm_pg_impl(x, w_shard, axis_name, bidirectional)


def _mm_pg_fwd(x, w_shard, axis_name, bidirectional):
    # residuals are (x, SHARD): the gathered full weight is never saved —
    # reshard-after-forward is structural, not a hook
    return _mm_pg_impl(x, w_shard, axis_name, bidirectional), (x, w_shard)


def _mm_pg_bwd(axis_name, bidirectional, res, dy):
    x, w_shard = res
    world = _axis_size(axis_name)
    if world == 1:
        dx = jnp.dot(dy, w_shard.T).astype(x.dtype)
        dw = _contract_leading(x, dy).astype(w_shard.dtype)
        return dx, dw
    idx = lax.axis_index(axis_name)
    n_loc = w_shard.shape[-1]
    col = dy.ndim - 1
    # ONE loop, two counter-rotating rings: the weight re-gather ring
    # (recv-from-right — the classic FSDP backward re-materialize; the
    # full weight was never a residual) feeds the dX partial sums, while
    # the dW travelling accumulator (moving right) reduce-scatters the
    # dp-summed weight grad straight into shard layout. Each hop of both
    # rings travels behind the two partial GEMMs of the next iteration.
    perm_w = [(j, (j - 1) % world) for j in range(world)]
    perm_acc = [(j, (j + 1) % world) for j in range(world)]
    chunk = w_shard
    dx = None
    acc = None
    for t in range(world):
        src = lax.rem(idx + t, jnp.int32(world))  # which w shard we hold
        # dX partial: dy's src column block against the resident shard.
        # fp32 accumulator — the monolithic dX is ONE dot with an fp32 MXU
        # accumulator; summing W model-dtype partials would add roundings
        p_dx = lax.dot_general(
            _chunk_slice(dy, src, n_loc, col), chunk,
            (((col,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        dx = p_dx if dx is None else dx + p_dx
        # dW partial for the accumulator currently resident (starts at the
        # left neighbour's shard and arrives home after W-1 hops — the
        # _matmul_rs_impl shifting-accumulator recipe)
        d = lax.rem(idx - 1 - t + 2 * world, world)
        p_dw = _contract_leading(x, _chunk_slice(dy, d, n_loc, col))
        acc = p_dw if acc is None else acc + p_dw
        if t < world - 1:
            with _span_comm():
                chunk = lax.ppermute(chunk, axis_name, perm_w)
                acc = lax.ppermute(acc, axis_name, perm_acc)
    return dx.astype(x.dtype), acc.astype(w_shard.dtype)


_matmul_param_gather.defvjp(_mm_pg_fwd, _mm_pg_bwd)


def matmul_param_gather(x, w_shard, *, axis_name: str = DP_AXIS,
                        bidirectional: bool = False):
    """``x @ all_gather(w_shard, axis=-1)`` with the WEIGHT gather
    decomposed into a ppermute ring interleaved with partial GEMMs — the
    collective-matmul decomposition in FSDP (ZeRO-3) position.

    ``x``: the rank-resident activation ``(..., in)`` (each dp rank holds
    its own batch shard). ``w_shard``: this rank's column shard ``(in,
    out/W)`` of the full ``(in, out)`` weight. Forward is EXACT vs the
    monolithic ``x @ all_gather(w)`` (the gathered dim is
    non-contracting). Backward: dX re-gathers the weight through a second
    ring (fp-reorder tolerance — W partials vs one fused dot) and dW
    arrives as this rank's ``(in, out/W)`` shard of the dp-SUMMED weight
    gradient (the FSDP grad reduce-scatter, fused into the same loop);
    divide by the axis size for the data-parallel mean. Wire-byte-neutral
    vs the monolithic gather + reduce-scatter pair
    (:func:`matmul_param_gather_wire_bytes`). Same ``pvary_like``/mesh
    contract as :func:`all_gather_matmul`."""
    # a weight split over more axes than the activations vary on (its
    # columns over tp, x replicated there): dX is then the sum of every
    # such rank's partial, which is this cast's transpose
    x = _pvary_like(x, w_shard)
    return _matmul_param_gather(x, w_shard, axis_name, bool(bidirectional))
