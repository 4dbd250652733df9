"""Device-mesh construction — the TPU-native replacement for process groups.

Reference analogue: ``apex/transformer/parallel_state.py:57-185`` builds four
families of ``torch.distributed`` process groups (data-parallel, tensor-MP,
pipeline-MP, model-parallel) by slicing the flat rank list. On TPU the single
source of truth is one ``jax.sharding.Mesh`` with named axes; every "process
group" becomes a named axis (or tuple of axes) passed to ``lax.psum`` /
``all_gather`` / ``ppermute``, and "grouped" collectives (e.g. SyncBN process
groups, ``apex/parallel/__init__.py:58-95``) become collectives over a subset
of axes.

Axis order is chosen for the hardware, innermost-last so the highest-traffic
axis gets the fastest-varying device placement (contiguous ICI neighbours):
``("dp", "pp", "sp", "tp")``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax import lax
from jax.sharding import Mesh

# Canonical axis names, outermost → innermost.
DP_AXIS = "dp"
PP_AXIS = "pp"
SP_AXIS = "sp"
TP_AXIS = "tp"
AXIS_ORDER: Tuple[str, ...] = (DP_AXIS, PP_AXIS, SP_AXIS, TP_AXIS)


def axis_size(axis_name, mesh: Optional[Mesh] = None) -> int:
    """Size of a mesh axis.

    Two calling conventions share this door:

    * ``axis_size(name)`` — the bound size from inside a mesh program
      (``lax.axis_size``).
    * ``axis_size(mesh, name)`` — static lookup outside any trace,
      ``mesh.shape[name]``.
    """
    if isinstance(axis_name, Mesh):  # legacy (mesh, axis) argument order
        return axis_name.shape[mesh]
    if mesh is not None:
        return mesh.shape[axis_name]
    return lax.axis_size(axis_name)


def vma_tracked(axis_name: str) -> bool:
    """False inside ``shard_map(check_vma=False)``: every value's ``vma`` is
    then empty (``axis_index`` included, which always varies when tracked),
    and a ``pcast`` there cannot be transposed (its transpose is a psum over
    an axis the cotangent does not vary on)."""
    return axis_name in jax.typeof(lax.axis_index(axis_name)).vma


def build_mesh(
    tp: int = 1,
    pp: int = 1,
    sp: int = 1,
    dp: int = -1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the global 4-axis mesh.

    ``dp=-1`` means "all remaining devices". Raises if the requested product
    does not divide the device count (mirrors the divisibility assertions in
    ``apex/transformer/parallel_state.py:80-90``).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    model = tp * pp * sp
    if dp == -1:
        if n % model != 0:
            raise ValueError(
                f"device count {n} is not divisible by tp*pp*sp = {model}"
            )
        dp = n // model
    if dp * model != n:
        raise ValueError(
            f"mesh shape dp={dp} pp={pp} sp={sp} tp={tp} requires {dp * model} "
            f"devices, have {n}"
        )
    shape = (dp, pp, sp, tp)
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    except (ImportError, ValueError, NotImplementedError) as e:
        # create_device_mesh optimizes placement for the physical ICI topology;
        # when it can't handle the shape, fall back to flat order but say so —
        # TP neighbours may no longer be contiguous ICI rings.
        from apex_tpu._logging import get_logger

        get_logger(__name__).warning(
            "mesh_utils.create_device_mesh failed (%s); falling back to flat "
            "device order — collective bandwidth may be degraded", e
        )
        dev_array = np.asarray(list(devices)).reshape(shape)
    return Mesh(dev_array, axis_names=AXIS_ORDER)


def build_hybrid_mesh(
    tp: int = 1,
    pp: int = 1,
    sp: int = 1,
    dp_per_slice: int = -1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """DCN×ICI hybrid mesh for multi-slice / multi-host pods.

    Layout follows the standard scaling recipe: data parallelism is the
    ONLY axis that crosses the slice (DCN) boundary — its collectives are
    one bandwidth-tolerant psum per step — while tp/pp/sp stay inside a
    slice riding ICI. The reference reaches the same goal with NCCL
    process groups laid out host-major (``parallel_state.py:76-90``'s
    "adjacent ranks on the same DGX box" note); here
    ``mesh_utils.create_hybrid_device_mesh`` encodes it against the real
    slice topology (``device.slice_index``).

    ``dp_per_slice=-1`` means all remaining devices within each slice. On
    a single slice (or a simulation whose devices carry no slice index)
    this degrades to :func:`build_mesh` — same axes, ICI-only placement.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    slice_ids = sorted({getattr(d, "slice_index", 0) for d in devices})
    num_slices = len(slice_ids)
    if num_slices <= 1:
        return build_mesh(tp=tp, pp=pp, sp=sp, dp=dp_per_slice,
                          devices=devices)
    per_slice = len(devices) // num_slices
    model = tp * pp * sp
    if dp_per_slice == -1:
        if per_slice % model:
            raise ValueError(
                f"devices per slice ({per_slice}) not divisible by "
                f"tp*pp*sp = {model}")
        dp_per_slice = per_slice // model
    if dp_per_slice * model != per_slice:
        raise ValueError(
            f"dp_per_slice={dp_per_slice} x tp*pp*sp={model} != devices "
            f"per slice ({per_slice})")
    from jax.experimental import mesh_utils

    dev_array = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(dp_per_slice, pp, sp, tp),
        dcn_mesh_shape=(num_slices, 1, 1, 1),
        devices=devices)
    return Mesh(dev_array, axis_names=AXIS_ORDER)


def model_parallel_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes forming the "model-parallel group" (ref parallel_state.py:110-120):
    everything except data parallel."""
    return tuple(a for a in mesh.axis_names if a != DP_AXIS)
