"""Fused optimizer update tail — one Pallas kernel per flat shard leaf.

For the ZeRO optimizers and FSDP, whose state is one-dimensional by nature
(each leaf flattened and cut into 1/dp shards): after the gradient
reduce-scatter, the optimizer "tail" — moment updates, bias correction,
weight decay, the update direction — is a chain of ~10 tiny elementwise
XLA ops **per leaf**. This module fuses the whole chain into ONE kernel
per leaf:

* :func:`fused_adam_tail` — ``m' = β₁m + (1-β₁)g``, ``v' = β₂v +
  (1-β₂)g²``, ``u = (m'/c₁)/(√(v'/c₂)+ε)`` with either decay mode
  (ADAM_MODE_0 decoupled / ADAM_MODE_1 L2 — the ``multi_tensor_adam.cu``
  split), emitted as ``(u, m', v')``. The caller applies ``p - lr·u`` to
  its float32 master shard — the one op deliberately left outside, since
  LAMB must scale ``u`` by the trust ratio first.
* :func:`fused_lamb_tail` — the same kernel with two extra ``(8, 128)``
  per-lane partial-sum tiles accumulated across the sequential grid (folded
  by the wrapper): the LOCAL sq-sums
  ``Σp²`` and ``Σu²`` that LAMB's trust ratio needs (the Pallas analogue
  of the reference's two-stage ``multi_tensor_l2norm``); the caller
  psums them over the dp axis and applies ``p - lr·trust·u``.

Leaves are flattened, zero-padded to the fp32 tile (rows of 128 lanes,
row count a multiple of 8) and processed in row blocks; padding lanes
compute ``u = 0`` and contribute nothing to the norm accumulators, so
results are exact after the final slice. Deliberately per-leaf — fusing
across leaves would need a concat/split round-trip of the whole optimizer
state through HBM every step, trading real bandwidth for saved dispatch.

Wired behind ``fused_update=`` on the ZeRO
``DistributedFusedAdam``/``DistributedFusedLAMB`` and FSDP's optimizer
("auto" picks the kernel only on a compiled Mosaic backend). ``*_reference``
twins carry the identical math for parity tests and the off-TPU fallback.

Not for a leaf in its own shape: on the chip's (8, 128) tiling the flatten,
pad and reshape to ``(rows, 128)`` of a stacked matrix are physical copies,
and they cost the train step three times what the kernel did (PERF.md §6,
PR 31). ``optimizers.FusedAdam`` therefore leaves its tail to XLA, one
fusion a leaf over donated buffers, and no benchmark cell runs this module.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops._pallas_util import compiled_backend as _compiled_backend
from apex_tpu.ops._pallas_util import mosaic_placeable as _mosaic_placeable
from apex_tpu.ops._pallas_util import sds as _sds

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_TILE = 8 * _LANES        # fp32 min tile: small leaves pad to a multiple
_BLOCK_ROWS = 512         # row block per grid step for large leaves
_TILE_BIG = _BLOCK_ROWS * _LANES  # large leaves pad to whole row blocks


# ---------------------------------------------------------------------------
# references — the exact math the ZeRO ``upd`` closures ran before fusion
# (and still run when the kernel is off)


def adam_tail_reference(g, m, v, p, c1, c2, *, betas, eps,
                        weight_decay=0.0, adam_w_mode=True):
    """Elementwise Adam tail on fp32 leaves -> ``(u, m', v')``."""
    b1, b2 = betas
    if not adam_w_mode and weight_decay:
        g = g + weight_decay * p
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    u = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
    if adam_w_mode and weight_decay:
        u = u + weight_decay * p
    return u, m_new, v_new


def lamb_tail_reference(g, m, v, p, c1, c2, *, betas, eps,
                        weight_decay=0.0):
    """LAMB tail -> ``(u, m', v', Σp², Σu²)`` (sums LOCAL — LAMB psums
    them over the dp axis before the trust ratio). LAMB's decay is always
    the decoupled ``u + wd·p`` form."""
    u, m_new, v_new = adam_tail_reference(
        g, m, v, p, c1, c2, betas=betas, eps=eps,
        weight_decay=weight_decay, adam_w_mode=True)
    return u, m_new, v_new, jnp.sum(p * p), jnp.sum(u * u)


# ---------------------------------------------------------------------------
# kernel


def _tail_kernel(c_ref, g_ref, m_ref, v_ref, p_ref, *refs,
                 b1, b2, eps, wd, adam_w, with_norms):
    if with_norms:
        u_ref, m_out, v_out, wsq_ref, usq_ref = refs
    else:
        u_ref, m_out, v_out = refs
    c1 = c_ref[0, 0]
    c2 = c_ref[0, 1]
    g = g_ref[:]
    p = p_ref[:]
    if not adam_w and wd:
        g = g + wd * p
    m_new = b1 * m_ref[:] + (1.0 - b1) * g
    v_new = b2 * v_ref[:] + (1.0 - b2) * g * g
    u = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
    if adam_w and wd:
        u = u + wd * p
    u_ref[:] = u
    m_out[:] = m_new
    v_out[:] = v_new
    if with_norms:
        # sequential-grid accumulation into one resident (8, 128) tile per
        # norm: per-lane partial sums, added as whole vregs (Mosaic cannot
        # store a scalar to VMEM); the caller folds the tile. Zero padding
        # adds 0.
        @pl.when(pl.program_id(0) == 0)
        def _init():
            wsq_ref[:] = jnp.zeros_like(wsq_ref)
            usq_ref[:] = jnp.zeros_like(usq_ref)

        def lane_sums(x):
            return jnp.sum(x.reshape(-1, 8, _LANES), axis=0)

        wsq_ref[:] += lane_sums(p * p)
        usq_ref[:] += lane_sums(u * u)


def _tail_pallas(g, m, v, p, c1, c2, *, betas, eps, weight_decay,
                 adam_w_mode, with_norms, interpret):
    shape = g.shape
    n = g.size
    # one grid step for small leaves; fixed 512-row blocks for large ones
    # (padding a leaf out to whole blocks costs < 256 KiB fp32 and keeps
    # the grid short — grid steps are pure overhead for elementwise work)
    pad = (-n) % (_TILE if n <= _TILE_BIG else _TILE_BIG)
    flat = [jnp.pad(a.reshape(-1).astype(jnp.float32), (0, pad))
            for a in (g, m, v, p)]
    rows = (n + pad) // _LANES
    block = min(rows, _BLOCK_ROWS)
    mats = [a.reshape(rows, _LANES) for a in flat]
    c = jnp.stack([jnp.asarray(c1, jnp.float32),
                   jnp.asarray(c2, jnp.float32)]).reshape(1, 2)
    b1, b2 = betas
    kernel = functools.partial(
        _tail_kernel, b1=b1, b2=b2, eps=eps, wd=weight_decay,
        adam_w=adam_w_mode, with_norms=with_norms)
    row_spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    out_specs = [row_spec, row_spec, row_spec]
    out_shape = [_sds((rows, _LANES), jnp.float32, g, m, v, p)] * 3
    if with_norms:
        out_specs += [pl.BlockSpec((8, _LANES), lambda i: (0, 0))] * 2
        out_shape += [_sds((8, _LANES), jnp.float32, g, m, v, p)] * 2
    out = pl.pallas_call(
        kernel,
        name="lamb_tail" if with_norms else "adam_tail",
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (1, 2) c1/c2
            row_spec, row_spec, row_spec, row_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        # the norm tiles accumulate across the grid: it must run in order
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(c, *mats)

    def unpad(a):
        return a.reshape(-1)[:n].reshape(shape)

    u, m_new, v_new = (unpad(a) for a in out[:3])
    if with_norms:
        return u, m_new, v_new, jnp.sum(out[3]), jnp.sum(out[4])
    return u, m_new, v_new


def fused_adam_tail(g, m, v, p, c1, c2, *, betas, eps,
                    weight_decay=0.0, adam_w_mode=True,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None) -> Tuple:
    """Dispatching front door: ONE fused kernel for the whole Adam tail of
    one (shard) leaf, reference math elsewhere. ``c1``/``c2`` are the
    (traced) bias corrections ``1 - βᵗ``. Inputs any shape/dtype; results
    fp32 in the input shape. Returns ``(u, m', v')`` — apply with
    ``p - lr·u``."""
    if use_pallas is None:
        use_pallas = _compiled_backend()
    if not use_pallas:
        if interpret is not None:
            raise ValueError("interpret= only applies to the Pallas path")
        return adam_tail_reference(
            g.astype(jnp.float32), m, v, p, c1, c2, betas=betas, eps=eps,
            weight_decay=weight_decay, adam_w_mode=adam_w_mode)
    if interpret is None:
        interpret = not _compiled_backend()
    return _tail_pallas(g, m, v, p, c1, c2, betas=betas, eps=eps,
                        weight_decay=weight_decay, adam_w_mode=adam_w_mode,
                        with_norms=False, interpret=interpret)


def fused_lamb_tail(g, m, v, p, c1, c2, *, betas, eps,
                    weight_decay=0.0,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None) -> Tuple:
    """LAMB variant: ``(u, m', v', Σp², Σu²)`` with the trust-ratio
    sq-sums accumulated in-kernel (LOCAL — psum them over dp, then
    ``p - lr·trust·u``)."""
    if use_pallas is None:
        use_pallas = _compiled_backend()
    if not use_pallas:
        if interpret is not None:
            raise ValueError("interpret= only applies to the Pallas path")
        return lamb_tail_reference(
            g.astype(jnp.float32), m, v, p, c1, c2, betas=betas, eps=eps,
            weight_decay=weight_decay)
    if interpret is None:
        interpret = not _compiled_backend()
    return _tail_pallas(g, m, v, p, c1, c2, betas=betas, eps=eps,
                        weight_decay=weight_decay, adam_w_mode=True,
                        with_norms=True, interpret=interpret)


def resolve_fused(mode: str) -> bool:
    """``"auto" | "on" | "off"`` -> whether to run the fused kernels.
    ``auto`` picks them only where they are a win AND can be placed — a
    compiled Mosaic backend, traced inside a ``shard_map`` body or on a
    one-device host (an optimizer stepping sharded leaves under plain
    ``jit`` would hand the partitioner a kernel it cannot split); off-TPU
    the interpreter just re-expands the kernel body into the same XLA ops,
    saving no dispatch (``"on"`` forces exactly that, which is how the
    parity tests run). Call at trace time, where the step is traced."""
    if mode == "off":
        return False
    if mode == "on":
        return True
    if mode == "auto":
        return _compiled_backend() and _mosaic_placeable()
    raise ValueError(
        f"fused_update must be 'auto', 'on' or 'off', got {mode!r}")
