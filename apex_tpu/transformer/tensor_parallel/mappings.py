"""The four tensor-parallel collective mappings, as differentiable functions.

Reference: ``apex/transformer/tensor_parallel/mappings.py:23-157`` — four
``torch.autograd.Function``s pairing a forward collective with its transpose
in backward:

====================  =============================  =======================
mapping               forward                        backward
====================  =============================  =======================
copy_to_...           identity                       all-reduce
reduce_from_...       all-reduce                     identity
scatter_to_...        split last dim (keep my slice) all-gather (concat)
gather_from_...       all-gather (concat last dim)   split (keep my slice)
====================  =============================  =======================

TPU re-design: in a ``shard_map`` body JAX tracks which values vary across
each mesh axis (the VMA system) and *derives* the transpose collectives, so
three of the four mappings are raw primitives whose autodiff rules already
match the reference's backward table:

* copy      = ``pcast(to='varying')`` — identity whose transpose is ``psum``
  (the reference's bwd all-reduce, ``mappings.py:77-92``); crucially the psum
  is inserted exactly once, where a hand-written custom-VJP psum would
  double-count against shard_map's own invariant-input reduction.
* reduce    = ``lax.psum`` — its transpose is the identity cast (:95-107).
* scatter   = ``axis_index``-based slice — its transpose (scatter-add + the
  invariant-input psum) reassembles the full gradient = the reference's bwd
  all-gather (:110-121).
* gather    = ``lax.all_gather(tiled)`` — this one DOES need a custom VJP:
  the built-in transpose is ``psum_scatter``, which double-counts when the
  downstream loss is computed redundantly per TP rank (the Megatron pattern:
  every rank holds the gathered activations and computes the same loss). The
  reference's bwd is *split, not reduce-scatter* (:124-135) for exactly this
  reason.

These functions therefore require ``check_vma=True`` (the shard_map default)
— with ``check_vma=False`` JAX cannot insert the copy/scatter transposes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.ops._pallas_util import pvary_like  # noqa: F401 (re-export)
from apex_tpu.parallel.mesh import TP_AXIS
from apex_tpu.parallel.mesh import axis_size as _axis_size
from apex_tpu.parallel.mesh import vma_tracked
from apex_tpu.transformer.tensor_parallel.utils import divide


def _is_varying(x, axis_name: str) -> bool:
    if not vma_tracked(axis_name):
        return True  # no vma tracking (check_vma=False) — treat as varying
    return axis_name in jax.typeof(x).vma


def _pvary(x, axis_name: str):
    """Mark x as device-varying over axis (identity value-wise); transpose is
    psum. No-op if already varying."""
    if _is_varying(x, axis_name):
        return x
    return lax.pcast(x, axis_name, to="varying")


def _split(x, axis_name: str):
    """Keep this rank's slice of the last dim (ref mappings.py:36-52)."""
    world = _axis_size(axis_name)
    chunk = divide(x.shape[-1], world)
    rank = lax.axis_index(axis_name)
    return lax.dynamic_slice_in_dim(x, rank * chunk, chunk, axis=x.ndim - 1)


def copy_to_tensor_model_parallel_region(x, axis_name: str = TP_AXIS):
    """Identity fwd / all-reduce bwd (ref _CopyToModelParallelRegion,
    mappings.py:77-92). Feeds activations into a column-parallel matmul."""
    return _pvary(x, axis_name)


def reduce_from_tensor_model_parallel_region(x, axis_name: str = TP_AXIS):
    """All-reduce fwd / identity bwd (ref _ReduceFromModelParallelRegion,
    mappings.py:95-107). Collects partial sums out of a row-parallel matmul."""
    return lax.psum(_pvary(x, axis_name), axis_name)


def scatter_to_tensor_model_parallel_region(x, axis_name: str = TP_AXIS):
    """Split-last-dim fwd / all-gather bwd (ref _ScatterToModelParallelRegion,
    mappings.py:110-121)."""
    return _split(_pvary(x, axis_name), axis_name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def gather_from_tensor_model_parallel_region(x, axis_name: str = TP_AXIS):
    """All-gather-concat fwd / split bwd (ref _GatherFromModelParallelRegion,
    mappings.py:124-135)."""
    return lax.all_gather(x, axis_name, axis=x.ndim - 1, tiled=True)


def _gather_fwd(x, axis_name):
    return gather_from_tensor_model_parallel_region(x, axis_name), None


def _gather_bwd(axis_name, _res, g):
    return (_split(g, axis_name),)


gather_from_tensor_model_parallel_region.defvjp(_gather_fwd, _gather_bwd)


# ---------------------------------------------------------------------------
# Megatron-style sequence-parallel region boundaries (Korthikanti et al.,
# "Reducing Activation Recomputation"; NOT in the reference snapshot — its
# only SP artifact is activation-shard checkpointing, random.py:244-263).
# Activations in the LN/dropout/residual regions are sharded along the
# SEQUENCE dim over the same tp ranks; entering a TP block all-gathers the
# sequence ("g"), leaving one reduce-scatters it ("ḡ") — the psum a plain
# row-parallel exit would do, split across ranks. Unlike the replicated
# copy/gather mappings above, the input here is genuinely rank-varying, so
# JAX AD's built-in transposes (all_gather ⇄ psum_scatter) are exactly the
# Megatron backward pair and no custom_vjp is needed.


def gather_from_sequence_parallel_region(x, axis_name: str = TP_AXIS,
                                         seq_axis: int = 1):
    """Sequence all-gather entering a column-parallel block (fwd ``g``:
    all_gather; bwd: reduce-scatter). ``x``: the local (b, s/tp, h) shard."""
    return lax.all_gather(
        _pvary(x, axis_name), axis_name, axis=seq_axis, tiled=True)


def reduce_scatter_to_sequence_parallel_region(x, axis_name: str = TP_AXIS,
                                               seq_axis: int = 1):
    """Sequence reduce-scatter leaving a row-parallel block (fwd ``ḡ``:
    psum_scatter; bwd: all_gather). Returns the local (b, s/tp, h) shard."""
    return lax.psum_scatter(
        _pvary(x, axis_name), axis_name, scatter_dimension=seq_axis,
        tiled=True)


def scatter_to_sequence_parallel_region(x, axis_name: str = TP_AXIS,
                                        seq_axis: int = 1):
    """Rank-indexed sequence slice of an axis-invariant (fully reduced)
    tensor — the no-reduction exit from a region where every rank computed
    the full sequence (e.g. the MoE block under Megatron-SP). Backward is
    exact by transposition: slicing an invariant tensor at the rank index
    transposes to a psum of zero-padded shard cotangents, so every rank
    recovers the FULL per-token cotangent. Use
    :func:`reduce_scatter_to_sequence_parallel_region` instead when the
    input still carries per-rank partial sums."""
    world = _axis_size(axis_name)
    chunk = divide(x.shape[seq_axis], world)
    rank = lax.axis_index(axis_name)
    return lax.dynamic_slice_in_dim(x, rank * chunk, chunk, axis=seq_axis)
