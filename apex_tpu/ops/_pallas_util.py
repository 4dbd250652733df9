"""Shared Pallas plumbing for the kernel layer."""

from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import re

import jax
from jax import lax

_FORCE_COMPILED = contextvars.ContextVar("apex_tpu_force_compiled",
                                         default=False)


@contextlib.contextmanager
def force_compiled():
    """Treat the current backend as TPU for kernel dispatch: every Pallas
    entry point selects its compiled (non-interpret) Mosaic path.

    Exists for the AOT TPU-lowering regression guard
    (``tests/test_tpu_lowering.py``): ``jit(f).trace(args).lower(
    lowering_platforms=("tpu",))`` runs Mosaic's block-shape/layout
    verification on a CPU-only box — interpret mode skips exactly those
    checks, which is how a kernel that lowers nowhere can pass the whole
    CPU suite (the varlen seg-block bug, round 4).

    AOT-lowering-only: wrap ``.trace(...).lower(...)`` calls, never code
    that EXECUTES on CPU — jit would cache the trace with
    ``interpret=False`` baked in and later executions of that cached
    callable off-chip would fail. The flag is a ``contextvars.ContextVar``
    so concurrent threads/tasks see independent values."""
    token = _FORCE_COMPILED.set(True)
    try:
        yield
    finally:
        _FORCE_COMPILED.reset(token)


@functools.cache
def tpu_topology_devices():
    """The devices of a TPU topology DESCRIPTION — one four-chip v5e host
    ("v5e:2x2"); no chip needed, only the installed libtpu. Programs whose
    arguments (or mesh) sit on these devices compile for the real target:
    XLA:TPU and Mosaic's own compiler run in full, so VMEM overflows,
    unsupported vector ops and layout refusals surface on a CPU box (AOT
    *lowering* alone stops at the Pallas->Mosaic MLIR checks). A one-chip
    program uses the first device."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2").devices


def compile_for_tpu(jitted, *args):
    """AOT-compile ``jitted(*args)`` for the TPU topology under
    :func:`force_compiled`; returns ``(lowered, compiled)``. ``args`` may be
    arrays or ``ShapeDtypeStruct``s; a leaf without a sharding is placed on
    the topology's first device, one that carries a sharding (a
    ``NamedSharding`` over a topology mesh) keeps it."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(tpu_topology_devices()[0])

    def abstract(a):
        if isinstance(a, jax.ShapeDtypeStruct) and a.sharding is not None:
            return a
        if not isinstance(a, jax.ShapeDtypeStruct):
            a = jax.numpy.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    with force_compiled():
        lowered = jitted.trace(*jax.tree.map(abstract, args)).lower(
            lowering_platforms=("tpu",))
    return lowered, lowered.compile()


def compiled_backend() -> bool:
    """True when kernel dispatch should pick the compiled Mosaic path."""
    return _FORCE_COMPILED.get() or jax.default_backend() == "tpu"


def mosaic_placeable() -> bool:
    """Whether a Mosaic kernel traced HERE can be placed without help: the
    SPMD partitioner cannot split one ("Mosaic kernels cannot be
    automatically partitioned"), so it runs where the trace is already
    per-device — inside a fully-manual ``shard_map`` body — or where there
    is one device and nothing to partition. Elsewhere (plain ``jit`` on a
    multi-device host) an ``auto`` dispatch keeps the XLA op chain."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return set(mesh.manual_axes) == set(mesh.axis_names)
    return jax.device_count() == 1


_MOSAIC_CALL = re.compile(
    r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"')


def mosaic_calls(compiled_hlo: str) -> collections.Counter:
    """Mosaic custom calls in a COMPILED module's text
    (``jit(f).lower(...).compile().as_text()``), counted by kernel name —
    the ``name=`` every ``pallas_call`` in this package passes, which XLA
    keeps as the path segment before ``/pallas_call`` in the instruction's
    ``op_name``. A call site inside a scanned layer body counts once. This
    is how a caller proves which dispatch path a program took: the
    reference paths leave no ``tpu_custom_call`` behind."""
    out: collections.Counter = collections.Counter()
    for op_name in _MOSAIC_CALL.findall(compiled_hlo):
        parts = op_name.split("/")
        at = parts.index("pallas_call") if "pallas_call" in parts else 0
        out[parts[at - 1] if at else "unnamed"] += 1
    return out


def sds(shape, dtype, *like):
    """ShapeDtypeStruct whose varying-mesh-axes set is the union of the
    inputs' — pallas_call outputs inside shard_map (check_vma=True) must
    declare how they vary across mesh axes."""
    vma = set()
    tracked = False
    for x in like:
        try:
            vma |= set(jax.typeof(x).vma)
            tracked = True
        except (AttributeError, TypeError):
            pass
    if tracked:
        # under shard_map the vma set must be explicit even when empty
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def pvary_like(w, ref):
    """Mark ``w`` varying over every mesh axis ``ref`` varies on (identity
    value-wise; transpose = psum over those axes). Required before feeding a
    replicated parameter together with sharded activations into a
    ``custom_vjp`` op: the opaque vjp rule hides the linearity, so
    shard_map's automatic invariant-input reduction cannot fire — this makes
    the reduction explicit at the pvary transpose, over exactly the axes the
    cotangent (which inherits the activations' vma) will carry."""
    try:
        want = set(jax.typeof(ref).vma)
        have = set(jax.typeof(w).vma)
    except (AttributeError, TypeError):
        return w
    missing = tuple(sorted(want - have))
    if missing:
        w = lax.pcast(w, missing, to="varying")
    return w
