"""Gated delta rule (linear attention with a decaying, error-correcting
state), in chunked form, with the two small ops its layer needs beside it.

Per head, with keys of unit length, a decay ``alpha_t = exp(g_t)`` in (0, 1]
and a write strength ``beta_t`` (arXiv:2412.06464)::

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t                          S in R^{d_v x d_k}, S_0 = 0

Token by token that is 8,192 dependent rank-one updates a row. The chunked
form does the same arithmetic ``chunk`` tokens at a time (the WY / UT
representation of a product of Householder-like factors): write the new value
``n_t = beta_t (v_t - alpha_t S_{t-1} k_t)`` so that ``S_t = alpha_t S_{t-1} +
n_t k_t^T``; inside a chunk that starts from state ``S`` and with ``gamma_t``
the decay accumulated since the chunk's start,

    (I + A) N = beta V - (beta gamma K) S^T,   A_ts = beta_t (k_t.k_s) gamma_t/gamma_s  (s < t)

so ``N = u - w S^T`` with ``u = (I + A)^-1 beta V`` and ``w = (I + A)^-1 beta
gamma K``: one unit-lower-triangular solve a chunk, for all chunks at once.
What is left in sequence is a scan over chunks that carries the state:
``O = (gamma Q) S^T + (Q K^T . L) N`` and ``S'^T = gamma_C S^T + (K
gamma_C/gamma)^T N``. No quotient of decays is formed but as ``exp`` of a
difference that is never positive, so strong decay underflows to nought and
nothing overflows.

The state and every product here are float32 (``_PRECISION``: the MXU's
multi-pass float32), whichever of two forms runs, chosen by what the call can
see (``_kernels_take``), never by an argument:

* **Pallas kernels** on a compiled backend. ``delta_rule_fwd`` walks (row x
  head) in parallel and the chunks in order with a head's state (d_k x d_v
  float32) in VMEM from the first chunk to the last; a chunk's ``k k^T``, ``q
  k^T``, decay masks, ``(I + A)^-1``, ``w``, ``u``, the new values, the output
  and the state's update never leave the chip. As many chunks go together
  as fill the MXU's 128 rows (``_plan``: two of 64): what is local to a chunk
  is done for all on one tile with the others' blocks masked to nought, the
  products with the state a chunk at a time. The operands arrive with time as
  their last axis, as XLA keeps the layer's tensors anyway, and a span's tile
  is turned in the kernel (``_kernels``).
  ``(I + A)^-1`` is block forward substitution with the blocks doubling,
  ``[[T1, 0], [-T2 A21 T1, T2]]`` from single rows up (no power of ``A`` is
  formed: every intermediate is a block of the inverse itself). The op is a
  ``jax.custom_vjp``: differentiated, the forward also leaves the state each
  grid step (1,024 tokens) starts from and each chunk's ``(I + A)^-1`` (alive
  while the layer is differentiated), and ``delta_rule_bwd`` walks the grid
  steps in reverse with the state's cotangent in VMEM: it runs a step's
  forward again into VMEM (the chunks' states, ``w``, ``u``, the new values),
  then its chunks last to first, solving nothing: for ``W = T R``, ``dR = T^T
  dW`` and ``dA = -strict_lower(dR W^T)``. Every length that is a multiple of
  the chunk is taken: what does not fill the last tile or the last grid step
  is padded with tokens that write nothing.
* **XLA's chunked form** elsewhere (the CPU tests; a chunk that does not go
  into 128 or a head size that is no whole tile, which the kernels refuse):
  one triangular solve for all chunks, a scan over chunks, autodiff.

Whatever implements the core sits under the scope ``layer/linattn/core``
(``transformer/hybrid.py``), which is where its time is read from; the kernels
add their names below it.
"""

from __future__ import annotations

import functools

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
_PRECISION = lax.Precision.HIGHEST


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_PRECISION,
                      preferred_element_type=F32)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """``o`` (batch, time, heads, d_v) of the recurrence above, ``S_0 = 0``,
    in ``v``'s type.

    ``q``, ``k``: (batch, time, heads, d_k), already normalised and scaled by
    the caller; ``v``: (batch, time, heads, d_v); ``g`` (log decay, <= 0) and
    ``beta``: (batch, time, heads). ``time`` must be a multiple of ``chunk``.

    On a compiled backend, at the chunks and head sizes the kernels tile
    (``_kernels_take``), the Pallas kernels run (a backward of their own:
    differentiating keeps the operands in the kernels' layout, a state a grid
    step and an inverse a chunk); elsewhere XLA's chunked form does."""
    t = q.shape[1]
    if t % chunk:
        raise ValueError(
            f"gated_delta_rule: time ({t}) is not a multiple of the chunk "
            f"({chunk}); pad the sequence or pick a chunk that divides it")
    if (compiled_backend() and mosaic_placeable()
            and _kernels_take(q, k, v, chunk)):
        return _kernels(q, k, v, g, beta, chunk)
    return _chunked(q, k, v, g, beta, chunk)


# ---------------------------------------------------------------------------
# the Pallas kernels

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
# tokens a grid step at most (a loop over spans inside the kernel): what a
# step keeps in VMEM for the backward grows with them
_TOKENS_A_STEP = 1024


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, precision=_PRECISION,
                           preferred_element_type=F32)


def _plan(t: int, chunk: int):
    """(chunks a span, spans a grid step, grid steps) for ``t`` tokens.

    A span is what the kernels take together: as many chunks as fill the
    MXU's 128 rows (two of 64), which are also the 128 lanes a tile of the
    time-minor operands has. What is local to a chunk (``k k^T``, the
    inverse, ``w``, ``u``, the cotangents' products) is then done for all of
    them on one tile, the other chunks' blocks masked to nought, and only the
    products with the state go a chunk at a time: fewer dependent products a
    chunk, on taller tiles. The spans are dealt evenly over the fewest grid
    steps of at most ``_TOKENS_A_STEP``; where they do not fill the last one
    the sequence is padded with tokens that write nothing (``_kernels``)."""
    span = 128 // chunk
    width = span * chunk
    spans = -(-t // width)
    steps = -(-spans // (_TOKENS_A_STEP // width))
    return span, -(-spans // steps), steps


def _kernels_take(q, k, v, chunk: int) -> bool:
    """Whether the kernels tile this call: a chunk that goes into the 128
    lanes of a tile, head sizes of whole sublane tiles of the operands' type
    (8 rows of float32, 16 of bfloat16), q, k, v of one type."""
    packed = 8 * 4 // jnp.dtype(q.dtype).itemsize
    return (q.dtype == k.dtype == v.dtype and q.dtype in (jnp.float32, jnp.bfloat16)
            and chunk in (16, 32, 64, 128)
            and q.shape[-1] % packed == 0 and v.shape[-1] % packed == 0)


def _kernels(q, k, v, g, beta, chunk: int, interpret: bool = False):
    """The kernels' path: q, k, v with time as their last axis ((batch, time,
    heads, d) -> (batch x heads, d, time)), the decay summed over a chunk's
    tokens, the core, and ``o`` back from (batch x heads, d_v, time). XLA
    differentiates the layout changes and the sum; the core is its own.

    Time last because that is how XLA keeps what a convolution over time
    makes and what a norm over a head reads: in the hybrid cell's step the
    kernels' operands are then the producers' own buffers and no copy is left
    round the core; the kernels turn a span's tile themselves. And because 96
    or 192 rows are whole sublane tiles, where 96 or 192 lanes are padded to
    128 and 256 in HBM (a third more bytes alive through a layer's backward).

    The barriers keep each tensor on its 16 bits: left to itself XLA widens
    ``o`` and the cotangents of q, k, v to float32 for their float32
    consumers first and moves twice the bytes (0.5% of the hybrid cell's
    step)."""
    b, t, h, _ = q.shape
    span, spans, steps = _plan(t, chunk)
    width = span * chunk
    pad = steps * spans * width - t
    if pad:     # k = 0, beta = 0, g = 0: a token that leaves the state as it is
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    rows = lambda x: jnp.transpose(x, (0, 2, 3, 1)).reshape(b * h, x.shape[-1], t + pad)
    per_chunk = lambda x: jnp.swapaxes(x.astype(F32), 1, 2).reshape(
        b * h, (t + pad) // chunk, chunk)
    per_span = lambda x: x.reshape(b * h, steps, spans, width)
    gc = per_span(jnp.cumsum(per_chunk(g), axis=-1))    # log gamma_t
    q, k, v = lax.optimization_barrier((q, k, v))
    o = _core(rows(q), rows(k), rows(v), gc, per_span(per_chunk(beta)), chunk, interpret)
    o = jnp.transpose(o.reshape(b, h, -1, t + pad), (0, 3, 1, 2))
    return lax.optimization_barrier(o[:, :t] if pad else o)


def _decays(gc, beta, width: int, chunk: int):
    """From a span's (1, width) rows of log gamma and beta. The index grids
    and which entries lie in one chunk; log gamma, beta and the log gamma_C of
    a token's own chunk as (width, 1) columns; gamma_t / gamma_s for s <= t in
    one chunk (0 elsewhere; what is masked never reaches exp)."""
    rows = lax.broadcasted_iota(jnp.int32, (width, width), 0)
    cols = lax.broadcasted_iota(jnp.int32, (width, width), 1)
    col = lambda x, at: jnp.sum(jnp.where(at, x, 0.0), axis=1, keepdims=True)
    same = rows // chunk == cols // chunk if width > chunk else True
    gc_col = col(gc, rows == cols)
    lower = same & (rows >= cols)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gc_col - gc, 0.0)), 0.0)
    g_last = col(gc, same & (cols % chunk == chunk - 1))
    return rows, cols, same, gc_col, col(beta, rows == cols), g_last, decay


def _lane(x, cols, at: int):
    """Entry ``at`` of a (1, width) row, as (1, 1)."""
    return jnp.sum(jnp.where(cols[:1] == at, x, 0.0), axis=1, keepdims=True)


def _unit_lower_inverse(a, rows, cols, chunk: int):
    """``(I + a)^-1`` for a strictly lower triangular ``a``, a chunk a
    diagonal block: block forward substitution with the blocks doubling. With
    T1, T2 the inverses of two neighbouring diagonal blocks of size m, the
    inverse of the block of size 2m is ``[[T1, 0], [-T2 A21 T1, T2]]``: as
    whole matrices, ``T - T (A . siblings) T``. Single rows need no product."""
    t = jnp.where(rows == cols, 1.0, 0.0) - jnp.where((rows ^ cols) == 1, a, 0.0)
    m = 2
    while m < chunk:    # a power of two: every chunk is a block of its own
        siblings = ((rows // m) ^ (cols // m)) == 1
        t = t - _dot(t, _dot(jnp.where(siblings, a, 0.0), t))
        m *= 2
    return t


def _pack_inverse(t, chunk: int, span: int):
    """A span's inverse as its chunks' diagonal blocks side by side (chunk,
    span x chunk): the rest of it is nought."""
    return sum(t[p * chunk:(p + 1) * chunk] for p in range(span))


def _unpack_inverse(packed, same, span: int):
    return jnp.where(same, jnp.concatenate([packed] * span, axis=0), 0.0)


def _tokens(ref, at):
    """A span's tile of a time-minor operand, turned: (tokens, d) float32."""
    return ref[:, at].astype(F32).T


def _fwd_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, o_ref, *rest,
                chunk: int, span: int, spans: int, save: bool):
    if save:
        s_ref, t_ref, state = rest
    else:
        state, = rest
    width = span * chunk

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if save:
        s_ref[...] = state[...]

    def one(i, carry):
        at = pl.ds(pl.multiple_of(i * width, width), width)
        q, k, v = (_tokens(r, at) for r in (q_ref, k_ref, v_ref))
        gc = gc_ref[pl.ds(i, 1), :]
        rows, cols, same, gc_col, beta_col, g_last, decay = _decays(
            gc, beta_ref[pl.ds(i, 1), :], width, chunk)
        a = jnp.where(same & (rows > cols), beta_col * _dot(k, k, _NT) * decay, 0.0)
        t = _unit_lower_inverse(a, rows, cols, chunk)
        gamma = jnp.exp(gc_col)
        w = _dot(t, (beta_col * gamma) * k)
        u = _dot(t, beta_col * v)
        k_dec = jnp.exp(g_last - gc_col) * k
        # what meets the state, a chunk at a time
        s, new, read = state[...], [], []
        for p in range(span):
            c = slice(p * chunk, (p + 1) * chunk)
            new.append(u[c] - _dot(w[c], s))
            # before the state's update: emitted after it, or before the new
            # values, the forward is 2% slower on the v5e
            read.append(_dot(q[c], s))
            s = (jnp.exp(_lane(gc, cols, c.stop - 1)) * s
                 + _dot(k_dec[c], new[p], _TN))
        state[...] = s
        if save:
            t_ref[i] = _pack_inverse(t, chunk, span)
        o = (gamma * jnp.concatenate(read, axis=0)
             + _dot(_dot(q, k, _NT) * decay, jnp.concatenate(new, axis=0)))
        o_ref[:, at] = o.T.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, spans, one, None)


def _bwd_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, do_ref, s_ref, t_ref,
                dq_ref, dk_ref, dv_ref, dgc_ref, dbeta_ref,
                dstate, states, q_scr, k_scr, v_scr, w_scr, u_scr, new_scr,
                *, chunk: int, span: int, spans: int):
    width = span * chunk
    lanes = lambda x: jnp.sum(x, axis=1, keepdims=True)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    def at(i):
        return pl.ds(pl.multiple_of(i * width, width), width)

    states[0] = s_ref[...]

    def replay(i, carry):
        """The block's forward again from the state it started with and its
        spans' inverses: each chunk's starting state, ``w``, ``u`` and the
        new values, left in VMEM for the sweep back beside q, k, v turned."""
        q, k, v = (_tokens(x, at(i)) for x in (q_ref, k_ref, v_ref))
        q_scr[at(i), :], k_scr[at(i), :], v_scr[at(i), :] = q, k, v
        gc = gc_ref[pl.ds(i, 1), :]
        rows, cols, same, gc_col, beta_col, g_last, _ = _decays(
            gc, beta_ref[pl.ds(i, 1), :], width, chunk)
        t = _unpack_inverse(t_ref[i], same, span)
        w = _dot(t, (beta_col * jnp.exp(gc_col)) * k)
        u = _dot(t, beta_col * v)
        k_dec = jnp.exp(g_last - gc_col) * k
        new = []
        for p in range(span):
            c = slice(p * chunk, (p + 1) * chunk)
            s = states[i * span + p]
            new.append(u[c] - _dot(w[c], s))
            states[i * span + p + 1] = (jnp.exp(_lane(gc, cols, c.stop - 1)) * s
                                        + _dot(k_dec[c], new[p], _TN))
        w_scr[at(i), :], u_scr[at(i), :] = w, u
        new_scr[at(i), :] = jnp.concatenate(new, axis=0)
        return carry

    lax.fori_loop(0, spans, replay, None)

    def back(r, carry):
        i = spans - 1 - r
        q, k, v = (x[at(i), :] for x in (q_scr, k_scr, v_scr))
        do = _tokens(do_ref, at(i))
        w, u, new = w_scr[at(i), :], u_scr[at(i), :], new_scr[at(i), :]
        gc = gc_ref[pl.ds(i, 1), :]
        rows, cols, same, gc_col, beta_col, g_last, decay = _decays(
            gc, beta_ref[pl.ds(i, 1), :], width, chunk)
        t = _unpack_inverse(t_ref[i], same, span)
        gamma, to_end = jnp.exp(gc_col), jnp.exp(g_last - gc_col)
        kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
        q_dec, k_dec = gamma * q, to_end * k
        # what meets the state and its cotangent, a chunk at a time, last first
        from_out = _dot(qk * decay, do, _TN)
        ds, to_last = dstate[...], 0.0
        dnew, dw, dq_dec, dk_dec = ([None] * span for _ in range(4))
        for p in reversed(range(span)):
            c = slice(p * chunk, (p + 1) * chunk)
            s = states[i * span + p]
            dnew[p] = from_out[c] + _dot(k_dec[c], ds)
            dw[p] = -_dot(dnew[p], s, _NT)          # N = u - w S
            dq_dec[p], dk_dec[p] = _dot(do[c], s, _NT), _dot(new[c], ds, _NT)
            gamma_c = jnp.exp(_lane(gc, cols, c.stop - 1))
            to_last += jnp.where(cols[:1] == c.stop - 1, gamma_c * jnp.sum(
                lanes(s * ds), axis=0, keepdims=True), 0.0)
            ds = gamma_c * ds + _dot(q_dec[c], do[c], _TN) - _dot(w[c], dnew[p], _TN)
        dstate[...] = ds
        dnew, dw, dq_dec, dk_dec = (
            jnp.concatenate(x, axis=0) for x in (dnew, dw, dq_dec, dk_dec))
        # W = T R: dR = T^T dW, dA = -strict_lower(dR W^T)
        dru, drw = _dot(t, dnew, _TN), _dot(t, dw, _TN)
        da = -jnp.where(same & (rows > cols),
                        _dot(drw, w, _NT) + _dot(dru, u, _NT), 0.0)
        da_kk = da * decay                      # over beta: d(k k^T) = beta . this
        dkk = beta_col * da_kk
        dqk = jnp.where(same & (rows >= cols), _dot(do, new, _NT), 0.0) * decay
        dq_ref[:, at(i)] = (gamma * dq_dec + _dot(dqk, k)).T.astype(dq_ref.dtype)
        dk_ref[:, at(i)] = ((beta_col * gamma) * drw + to_end * dk_dec
                            + _dot(dkk, k) + _dot(dkk, k, _TN) + _dot(dqk, q, _TN)
                            ).T.astype(dk_ref.dtype)
        dv_ref[:, at(i)] = (beta_col * dru).T.astype(dv_ref.dtype)
        to_row = lambda x, where: jnp.sum(jnp.where(where, x, 0.0), axis=0, keepdims=True)
        dbeta_ref[pl.ds(i, 1), :] = to_row(
            lanes(drw * (gamma * k)) + lanes(dru * v) + lanes(da_kk * kk), rows == cols)
        # every decay is exp of a difference of log gammas: d log gamma_t is
        # what t's row of (cotangent . value) holds less what its column does;
        # a chunk's last token also carries what decays to the chunk's end
        m = dkk * kk + dqk * qk
        through_end = lanes(dk_dec * k_dec)
        dgc_ref[pl.ds(i, 1), :] = (
            to_row(lanes(drw * (beta_col * gamma) * k + dq_dec * q_dec) - through_end
                   + lanes(m), rows == cols)
            - jnp.sum(m, axis=0, keepdims=True)
            + to_row(through_end, same & (cols % chunk == chunk - 1)) + to_last)
        return carry

    lax.fori_loop(0, spans, back, None)


def _grid(q, v, gc, chunk: int):
    """(grid, chunks a span, spans a step, the five operands' block specs):
    rows x heads in parallel, steps of spans in order."""
    (bh, dk, _), (_, steps, spans, width) = q.shape, gc.shape
    tokens = lambda d: pl.BlockSpec((None, d, spans * width), lambda j, c: (j, 0, c))
    scalars = pl.BlockSpec((None, None, spans, width), lambda j, c: (j, c, 0, 0))
    return ((bh, steps), width // chunk, spans,
            [tokens(dk), tokens(dk), tokens(v.shape[1]), scalars, scalars])


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _saved_shapes(q, v, gc, chunk: int):
    """What the forward leaves for the backward, each with its block: the
    state each grid step starts from, and the spans' packed inverses."""
    (bh, dk, _), dv, (_, steps, spans, width) = q.shape, v.shape[1], gc.shape
    return (((bh, steps, dk, dv), (None, None, dk, dv)),
            ((bh, steps * spans, chunk, width), (None, spans, chunk, width)))


def _forward(q, k, v, gc, beta, chunk: int, interpret: bool, save: bool):
    """``o``, and with ``save`` what the backward starts from
    (``_saved_shapes``)."""
    (bh, dk, t), dv = q.shape, v.shape[1]
    grid, span, spans, in_specs = _grid(q, v, gc, chunk)
    like = (q, k, v, gc, beta)
    saved = _saved_shapes(q, v, gc, chunk) if save else ()
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, span=span, spans=spans, save=save),
        name="delta_rule_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[in_specs[2]] + [
            pl.BlockSpec(block, lambda j, c: (j, c, 0, 0)) for _, block in saved],
        out_shape=[sds((bh, dv, t), v.dtype, *like)] + [
            sds(shape, F32, *like) for shape, _ in saved],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, gc, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q, k, v, gc, beta, chunk, interpret):
    """q, k: (rows x heads, d_k, time); v and ``o``: (.., d_v, time); gc (log
    gamma_t, summed from each chunk's start) and beta: (rows x heads, grid
    steps, spans a step, a span's tokens) float32 (``_plan``)."""
    return _forward(q, k, v, gc, beta, chunk, interpret, save=False)[0]


def _core_fwd(q, k, v, gc, beta, chunk, interpret):
    o, starts, inverses = _forward(q, k, v, gc, beta, chunk, interpret, save=True)
    return o, (q, k, v, gc, beta, starts, inverses)


def _core_bwd(chunk, interpret, res, do):
    q, k, v, gc, beta, starts, inverses = res
    dk, dv = q.shape[1], v.shape[1]
    grid, span, spans, in_specs = _grid(q, v, gc, chunk)
    last = grid[1] - 1
    # the chunks in reverse: the grid's second axis counts from the last block
    back = lambda block: pl.BlockSpec(
        block, lambda j, c: (j, last - c) + (0,) * (len(block) - 2))
    tokens = lambda spec: pl.BlockSpec(spec.block_shape, lambda j, c: (j, 0, last - c))
    in_specs = [tokens(s) for s in in_specs[:3]] + [back(s.block_shape) for s in in_specs[3:]]
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, span=span, spans=spans),
        name="delta_rule_bwd",
        grid=grid,
        in_specs=in_specs + [in_specs[2]]
        + [back(block) for _, block in _saved_shapes(q, v, gc, chunk)],
        out_specs=in_specs,
        out_shape=[sds(x.shape, x.dtype, *res, do) for x in (q, k, v, gc, beta)],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32),
                        pltpu.VMEM((spans * span + 1, dk, dv), F32)]
        + [pltpu.VMEM((spans * span * chunk, d), F32) for d in (dk, dk, dv, dk, dv, dv)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, gc, beta, do, starts, inverses))


_core.defvjp(_core_fwd, _core_bwd)


# ---------------------------------------------------------------------------
# the chunked form in XLA

def _chunked(q, k, v, g, beta, chunk: int):
    b, t, h, dk = q.shape
    dv, out_dtype = v.shape[-1], v.dtype
    n = t // chunk

    def chunks(x):      # (b, t, h, ...) -> (b, h, n, chunk, ...)
        x = x.astype(F32).reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                     # log gamma_t
    rows = jnp.arange(chunk)
    lower = rows[:, None] >= rows[None, :]
    diff = gc[..., :, None] - gc[..., None, :]
    # gamma_t / gamma_s for s <= t; the upper triangle never reaches exp
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = k * beta[..., None]
    a = jnp.where(rows[:, None] > rows[None, :],
                  _mm("...ik,...jk->...ij", kb, k) * decay, 0.0)
    rhs = jnp.concatenate([kb * jnp.exp(gc)[..., None], v * beta[..., None]],
                          axis=-1)
    wu = lax.linalg.triangular_solve(
        a + jnp.eye(chunk, dtype=F32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    attn = _mm("...ik,...jk->...ij", q, k) * decay  # diagonal included
    q_dec = q * jnp.exp(gc)[..., None]
    g_last = gc[..., -1:]
    k_dec = k * jnp.exp(g_last - gc)[..., None]
    gamma_c = jnp.exp(g_last)[..., None]            # (b, h, n, 1, 1)

    def step(s, xs):    # s: S^T, (b, h, d_k, d_v)
        q_i, w_i, u_i, attn_i, k_i, gam = xs
        new = u_i - _mm("bhck,bhkv->bhcv", w_i, s)
        o = _mm("bhck,bhkv->bhcv", q_i, s) + _mm("bhcj,bhjv->bhcv", attn_i, new)
        s = gam * s + _mm("bhck,bhcv->bhkv", k_i, new)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0)
               for x in (q_dec, w, u, attn, k_dec, gamma_c))
    # under shard_map the carry varies over the axes the inputs vary over
    s0 = pvary_like(jnp.zeros((b, h, dk, dv), F32), q)
    _, o = lax.scan(step, s0, xs)
    # (n, b, h, chunk, d_v) -> (b, t, h, d_v)
    return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, t, h, dv).astype(out_dtype)


def gated_delta_rule_reference(q, k, v, g, beta):
    """The recurrence as written, one token at a time (float32)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):    # s: (b, h, d_v, d_k)
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        err = v_t - _mm("bhvk,bhk->bhv", s, k_t)
        s = s + _mm("bhv,bhk->bhvk", b_t[..., None] * err, k_t)
        return s, _mm("bhvk,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, pvary_like(jnp.zeros((b, h, dv, dk), F32), q), xs)
    return jnp.moveaxis(o, 0, 1)


def causal_conv1d(x, w):
    """Depthwise causal convolution over time, no bias: ``y_t = sum_j w[j]
    x_{t-(W-1)+j}`` (the last tap meets the current token, as
    ``Conv1d(groups=channels, padding=W-1)`` cut to the input's length does).
    ``x``: (batch, time, channels); ``w``: (W, channels)."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + t] * w[j].astype(F32) for j in range(width))
    return y.astype(x.dtype)


def gated_rms_norm(o, gate, weight, eps: float = 1e-6):
    """``RMSNorm(o) * weight * SiLU(gate)`` over the last axis (a head's
    ``d_v``), in float32; returned in ``gate``'s type."""
    o32, g32 = o.astype(F32), gate.astype(F32)
    y = o32 * lax.rsqrt(jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + eps)
    return (y * weight.astype(F32) * jax.nn.silu(g32)).astype(gate.dtype)


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, in float32."""
    x32 = x.astype(F32)
    return x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + eps)
