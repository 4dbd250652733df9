"""Grouped products over a buffer laid out in whole row tiles.

``grouped_matmul(xs, w, sizes, tile)``: the rows of ``xs`` (n, a) in groups
of ``sizes`` (one a group, in order, from row 0 on), each group's rows times
its ``w[g]`` (a, b): what ``lax.ragged_dot(xs, w, sizes)`` gives, for a
buffer whose every group starts on a multiple of ``tile`` and is whole tiles
long (``transformer.moe._layout``: a held expert's span). That layout is what
the kernels use:

* **a table of the row tiles the groups fill**, scalar-prefetched: each row
  tile's group, and how many tiles the groups fill. The grid walks those
  tiles and no others: no tile holds two groups, no row is masked, and the
  room past the last group is never visited (its output rows are left
  unwritten; a caller reads only the groups' rows).
* ``grouped_fwd``: a grid step is a row tile times its group's whole
  weight; the weight's block stays put while the tiles of one group pass,
  so the pipeline fetches each group's weight once.
* ``grouped_dx``: the same kernel with the weight read transposed by its
  block's index map and the product's dimension numbers (no transposed copy
  in HBM): ``dxs = dys @ w[g].T``.
* ``grouped_dw``: ``dw[g] = xs[g's rows].T @ dys[g's rows]``, a sum over
  the group's row tiles accumulated in float32 in VMEM and written once a
  group; a group with no rows gets one visit that writes zeros.

Every block holds its operands' widths whole (up to ``MAX_WIDTH``), so a
width needs no tile that divides it: 1,408 is 128 x 11, 768 is 128 x 6.

bfloat16 operands, float32 accumulation and a result in the operands' type,
as ``ragged_dot`` gives. On a compiled backend where the shapes tile the
kernels run; elsewhere ``lax.ragged_dot`` does (the kernels run in interpret
mode when asked for off the chip, as ``ops/attention.py``'s do).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_util import (
    compiled_backend,
    mosaic_placeable,
    pvary_like,
    sds,
)

F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
# The kernels' row tile, at most: the layout's tile where it is no larger. At
# the block-diffusion cell's widths a tile of 1,024 rows runs 1.0-2.6% faster
# than one of 512, and one of 256 7-9% slower (TPU v5e,
# benchmarks/grouped_matmul_tpu.py).
ROW_TILE = 1024
# The widest operand the kernels take whole (both widths of every block): at
# the cells' widths the blocks take up to about 30 MiB of VMEM (grouped_dw's
# float32 block of 2,048 x 1,408 beside its double-buffered output and row
# tiles), past XLA's 16 MiB default scoped limit; a v5e core has 128.
MAX_WIDTH = 2048
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def row_tiles(sizes, rows: int, tm: int):
    """``(group, count)``: the group of each of the buffer's ``rows // tm``
    row tiles (the last group past the groups' end) and how many tiles the
    groups fill, the grid ``grouped_fwd`` and ``grouped_dx`` walk."""
    ends = jnp.cumsum(sizes.astype(jnp.int32)) // tm
    t = jnp.arange(rows // tm, dtype=jnp.int32)
    group = jnp.sum(t[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    return jnp.minimum(group, sizes.shape[0] - 1), ends[-1]


def _visits(sizes, rows: int, tm: int):
    """``(table (3, rows // tm + groups + 1), count)`` for ``grouped_dw``:
    a visit's group, its row tile and whether it computes; a group visits
    each of its tiles, one with none visits once and computes nothing."""
    g = sizes.shape[0]
    tiles = sizes.astype(jnp.int32) // tm
    visits = jnp.maximum(tiles, 1)
    n = rows // tm + g + 1
    group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), visits,
                       total_repeat_length=n)
    first_visit = jnp.cumsum(visits) - visits
    first_tile = jnp.cumsum(tiles) - tiles
    v = jnp.arange(n, dtype=jnp.int32)
    tile = jnp.take(first_tile, group) + v - jnp.take(first_visit, group)
    real = jnp.take(tiles, group) > 0
    # an empty group reads the tile before it (no fetch: the block is held)
    tile = jnp.clip(jnp.where(real, tile, tile - 1), 0, rows // tm - 1)
    return jnp.stack([group, tile, real.astype(jnp.int32)]), jnp.sum(visits)


def _params(interpret):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _product_kernel(group_ref, x_ref, w_ref, o_ref, *, dims):
    del group_ref
    o_ref[...] = lax.dot_general(x_ref[...], w_ref[...], dims,
                                 preferred_element_type=F32
                                 ).astype(o_ref.dtype)


def _product(xs, w, sizes, tm, transposed, interpret, name):
    """``xs @ w[g]`` (``w[g].T`` when ``transposed``) a row tile, over the
    tiles the groups fill; the weight's block changes only with the group."""
    n, a = xs.shape
    width = w.shape[1] if transposed else w.shape[2]
    group, count = row_tiles(sizes, n, tm)
    return pl.pallas_call(
        functools.partial(_product_kernel, dims=_NT if transposed else _NN),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(count,),
            in_specs=[pl.BlockSpec((tm, a), lambda i, grp: (i, 0)),
                      pl.BlockSpec((None,) + w.shape[1:],
                                   lambda i, grp: (grp[i], 0, 0))],
            out_specs=pl.BlockSpec((tm, width), lambda i, grp: (i, 0))),
        out_shape=sds((n, width), xs.dtype, xs, w),
        compiler_params=_params(interpret),
        interpret=interpret,
    )(group, xs, w)


def _dw_kernel(table_ref, x_ref, dy_ref, dw_ref, acc_ref):
    v = pl.program_id(0)
    g = table_ref[0, v]

    @pl.when((v == 0) | (table_ref[0, jnp.maximum(v - 1, 0)] != g))
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(table_ref[2, v] != 0)
    def _add():
        acc_ref[...] += lax.dot_general(x_ref[...], dy_ref[...], _TN,
                                        preferred_element_type=F32)

    @pl.when((v == pl.num_programs(0) - 1) | (table_ref[0, v + 1] != g))
    def _finish():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _weight_grad(xs, dys, sizes, tm, interpret, dtype):
    """``dw[g] = xs[g's rows].T @ dys[g's rows]`` (groups, a, b)."""
    n, a = xs.shape
    b = dys.shape[1]
    table, count = _visits(sizes, n, tm)
    return pl.pallas_call(
        _dw_kernel,
        name="grouped_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(count,),
            in_specs=[pl.BlockSpec((tm, a), lambda v, t: (t[1, v], 0)),
                      pl.BlockSpec((tm, b), lambda v, t: (t[1, v], 0))],
            out_specs=pl.BlockSpec((None, a, b), lambda v, t: (t[0, v], 0, 0)),
            scratch_shapes=[pltpu.VMEM((a, b), F32)]),
        out_shape=sds((sizes.shape[0], a, b), dtype, xs, dys),
        compiler_params=_params(interpret),
        interpret=interpret,
    )(table, xs, dys)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(xs, w, sizes, tm, interpret):
    return _product(xs, w, sizes, tm, False, interpret, "grouped_fwd")


def _grouped_fwd(xs, w, sizes, tm, interpret):
    return _grouped(xs, w, sizes, tm, interpret), (xs, w, sizes)


def _grouped_bwd(tm, interpret, res, dys):
    xs, w, sizes = res
    dxs = _product(dys, w, sizes, tm, True, interpret, "grouped_dx")
    dw = _weight_grad(xs, dys, sizes, tm, interpret, w.dtype)
    return dxs, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def kernel_row_tile(xs, w, tile: int) -> Optional[int]:
    """The kernels' row tile for ``xs`` (n, a) times ``w`` (groups, a, b)
    laid out in tiles of ``tile`` rows: the largest divisor of ``tile`` up to
    ``ROW_TILE`` that is a multiple of 16 (a bfloat16 tile's rows) and
    divides ``n``; None where the kernels do not take the shapes (2-D and 3-D
    operands of one type, ``a`` and ``b`` multiples of 128 and at most
    ``MAX_WIDTH``)."""
    if (xs.ndim != 2 or w.ndim != 3 or xs.dtype != w.dtype
            or xs.shape[1] != w.shape[1]
            or any(d % 128 or d > MAX_WIDTH for d in w.shape[1:])):
        return None
    tm = min(tile, ROW_TILE)
    while tm >= 16 and (tile % tm or tm % 16):
        tm //= 2
    return tm if tm >= 16 and xs.shape[0] % tm == 0 else None


def grouped_matmul(xs, w, sizes, tile: int, *, use_pallas=None):
    """``xs`` (n, a) in groups of ``sizes`` (groups,) int32, each group's
    rows times ``w[g]`` (a, b): (n, b) in ``xs``' type.

    ``tile``: every group starts on a multiple of it and is whole tiles long
    (the layout's guarantee; the kernels rely on it). Rows past the groups'
    end are not read, and the kernels leave them unwritten.

    ``use_pallas``: None picks the kernels on a compiled backend where a
    Mosaic kernel can be placed and :func:`kernel_row_tile` takes the
    shapes, ``lax.ragged_dot`` elsewhere; True asks for the kernels
    (interpreted off the chip)."""
    tm = kernel_row_tile(xs, w, tile)
    if use_pallas is None:
        use_pallas = (tm is not None and compiled_backend()
                      and mosaic_placeable())
    elif use_pallas and tm is None:
        raise ValueError(
            f"the grouped kernels need xs (n, a) and w (groups, a, b) of one "
            f"type, a and b multiples of 128 and n a multiple of a row tile "
            f"that divides {tile} (got xs {xs.shape} {xs.dtype}, w {w.shape} "
            f"{w.dtype})")
    if not use_pallas:
        return lax.ragged_dot(xs, w, sizes)
    return _grouped(xs, pvary_like(w, xs), sizes, tm, not compiled_backend())
