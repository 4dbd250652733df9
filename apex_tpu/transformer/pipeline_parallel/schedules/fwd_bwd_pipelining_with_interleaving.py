"""Interleaved (virtual-pipeline) schedule: the circular ring.

Reference: ``fwd_bwd_pipelining_with_interleaving.py:25-300`` — each stage
holds ``vp`` model chunks; chunk ``v`` on stage ``s`` owns layer block
``v * pp + s``; microbatches visit stage 0..pp-1 for chunk 0, wrap back to
stage 0 for chunk 1, etc. The interleaving shrinks the pipeline bubble by
``~vp``× at the cost of ``vp``× more p2p traffic.

TPU re-design: the wrap-around IS the ``ppermute`` ring: the non-interleaved
schedule already shifts stage pp-1 → stage 0; here that wrapped value becomes
the input of the next chunk instead of being discarded. Microbatches are
processed in groups of ``pp`` (the reference asserts
``num_microbatches % pp == 0``); within a group the pp in-flight microbatches
circle the ring ``vp`` times, and groups follow each other with zero bubble
(the ring is saturated except for the single global fill/drain of pp-1
ticks — total bubble (pp-1)/(M·vp + pp-1) vs the non-interleaved
(pp-1)/(M + pp-1)).

Tick → work-item map (u = t - rank):
    g = u // (pp·vp)   — microbatch group
    r = (u mod pp·vp) // pp  — chunk (virtual stage) index
    i = u mod pp       — index within group → microbatch m = g·pp + i
Chunk params are gathered per tick with a dynamic index into the local
``[vp, ...]`` chunk stack.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor.trace import span
from apex_tpu.parallel.mesh import DP_AXIS, PP_AXIS
from apex_tpu.transformer.pipeline_parallel.schedules.common import (
    PipelineSpec,
    append_dropout_operand,
    check_dropout_spec,
    derive_microbatch_keys,
    embed_microbatches,
    replicate_loss,
    split_microbatches,
    stage_params_spec,
)
from apex_tpu.transformer.pipeline_parallel.schedules.fwd_bwd_pipelining_without_interleaving import (
    _mesh_axis_names,
    _pvary_all,
    _ring_shift,
    _tree_index,
    _tree_where,
)

Pytree = Any


def pipeline_ring_interleaved(
    stage_fn: Callable[[Pytree, Pytree], Pytree],
    chunk_params: Pytree,
    h_mb: Pytree,
    *,
    num_microbatches: int,
    virtual_pipeline_size: int,
    axis_name: str = PP_AXIS,
    remat: bool = True,
    returns_aux: bool = False,
    keys_mb: Optional[jax.Array] = None,
) -> Pytree:
    """Circular ring inside a mesh program. ``chunk_params`` is this stage's
    ``[vp, ...]`` chunk stack (pp axis already squeezed). Returns ``[M, ...]``
    final-chunk outputs, valid on the last stage. With ``returns_aux`` the
    stage function yields ``(h, aux_scalar)`` and the result is
    ``(outputs, aux_mean)``: the stage's aux averaged over its real
    (microbatch, chunk) ticks.

    ``keys_mb`` ([M]-stacked PRNG keys) activates dropout routing: the
    stage function is called ``stage_fn(params, h, key)`` with the
    microbatch's key folded by the CHUNK index — chunks on one stage share
    its pp rank, so without the fold chunk r and r' would reuse the same
    per-layer mask streams."""
    pp = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    M, vp = num_microbatches, virtual_pipeline_size
    if M % pp != 0:
        raise ValueError(
            f"interleaved schedule requires num_microbatches ({M}) divisible "
            f"by pipeline size ({pp})"  # ref interleaving.py assert
        )
    G = M // pp
    work = G * pp * vp
    T = work + pp - 1
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    axes = _mesh_axis_names()

    def tick(carry, t):
        h, aux_sum = carry
        u = jnp.clip(t - rank, 0, work - 1)
        g = u // (pp * vp)
        w = u % (pp * vp)
        r = w // pp
        i = w % pp
        m = jnp.clip(g * pp + i, 0, M - 1)
        x0 = _tree_index(h_mb, m)
        take_new = (rank == 0) & (r == 0)
        inp = _tree_where(take_new, x0, h)
        p_r = _tree_index(chunk_params, r)
        args = (p_r, inp)
        if keys_mb is not None:
            key_m = lax.dynamic_index_in_dim(keys_mb, m, 0, keepdims=False)
            args += (jax.random.fold_in(key_m, r),)
        # monitor spans: stage compute vs ring p2p as distinct layer paths
        # (same names as the non-interleaved schedule for uniform reports)
        if returns_aux:
            with span("pp_stage"):
                out, aux = fn(*args)
            valid = (t >= rank) & (t - rank <= work - 1)
            aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
        else:
            with span("pp_stage"):
                out = fn(*args)
        with span("pp_ring_shift"):
            shifted = _ring_shift(out, axis_name)
        return (_pvary_all(shifted, axes),
                _pvary_all(aux_sum, axes)), out

    init = (
        _pvary_all(jax.tree.map(lambda a: jnp.zeros_like(a[0]), h_mb), axes),
        _pvary_all(jnp.zeros((), jnp.float32), axes),
    )
    (_, aux_sum), ys = lax.scan(tick, init, jnp.arange(T))
    # microbatch m = g*pp+i finishes chunk vp-1 on the last stage at tick
    # g*pp*vp + (vp-1)*pp + i + (pp-1)
    idx = np.asarray(
        [g * pp * vp + (vp - 1) * pp + i + pp - 1
         for g in range(G) for i in range(pp)],
        dtype=np.int32,
    )
    outs = jax.tree.map(lambda a: a[idx], ys)
    if returns_aux:
        return outs, aux_sum / work
    return outs


def _pipeline_body(
    params: Pytree,
    inputs_mb: Pytree,
    targets_mb: Pytree,
    keys_mb: Optional[jax.Array] = None,
    *,
    spec: PipelineSpec,
    num_microbatches: int,
    virtual_pipeline_size: int,
    mesh,
    remat: bool,
):
    # stages leaves are [vp, 1, ...] locally (pp axis sharded at dim 1)
    chunk_local = jax.tree.map(lambda a: a[:, 0], params["stages"])
    h_mb = embed_microbatches(spec.embed_fn, params["embed"], inputs_mb,
                              keys_mb)
    ys = pipeline_ring_interleaved(
        spec.stage_fn,
        chunk_local,
        h_mb,
        num_microbatches=num_microbatches,
        virtual_pipeline_size=virtual_pipeline_size,
        remat=remat,
        returns_aux=spec.stage_aux,
        keys_mb=keys_mb,
    )
    aux = None
    if spec.stage_aux:
        ys, aux = ys
    losses = jax.vmap(spec.loss_fn, in_axes=(None, 0, 0))(
        params["head"], ys, targets_mb
    )
    pp = lax.axis_size(PP_AXIS)
    is_last = lax.axis_index(PP_AXIS) == pp - 1
    local = jnp.where(is_last, jnp.mean(losses), 0.0)
    total = replicate_loss(local, mesh)
    if aux is not None:
        # per-stage (chunk-mean) aux -> model-wide layer mean (psum/pp)
        total = total + replicate_loss(aux, mesh, masked_axis=None)
    return total


def forward_backward_pipelining_with_interleaving(
    spec: PipelineSpec,
    params: Pytree,
    batch: Tuple[Pytree, Pytree],
    *,
    num_microbatches: int,
    virtual_pipeline_size: int,
    mesh=None,
    params_specs: Optional[Pytree] = None,
    data_spec: P = P(None, DP_AXIS),
    loss_scale: Optional[jnp.ndarray] = None,
    remat: bool = True,
    dropout_key: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, Pytree]:
    """Driver (ref :25). Same contract as the non-interleaved driver except
    ``params["stages"]`` carries leading ``[vp, pp]`` axes (see
    ``common.build_model``). ``dropout_key`` as in the non-interleaved
    driver, with the chunk index additionally folded per tick."""
    from apex_tpu.transformer.pipeline_parallel.schedules.fwd_bwd_enc_dec import (
        EncDecPipelineSpec,
    )

    if isinstance(spec, EncDecPipelineSpec):
        # Matches the reference: the interleaved schedule rejects
        # ModelType.encoder_and_decoder (ref schedules/__init__.py guard).
        raise ValueError(
            "the interleaved schedule supports encoder-or-decoder models "
            "only; use forward_backward_pipelining_without_interleaving for "
            "encoder-decoder specs"
        )
    if mesh is None:
        from apex_tpu.transformer import parallel_state

        mesh = parallel_state.get_mesh()
    if params_specs is None:
        params_specs = {
            "embed": jax.tree.map(lambda _: P(), params["embed"]),
            "stages": stage_params_spec(params["stages"], interleaved=True),
            "head": jax.tree.map(lambda _: P(), params["head"]),
        }
    inputs, targets = batch
    inputs_mb = split_microbatches(inputs, num_microbatches)
    targets_mb = split_microbatches(targets, num_microbatches)
    check_dropout_spec(spec, dropout_key)
    keys_mb = derive_microbatch_keys(dropout_key, num_microbatches)

    body = functools.partial(
        _pipeline_body,
        spec=spec,
        num_microbatches=num_microbatches,
        virtual_pipeline_size=virtual_pipeline_size,
        mesh=mesh,
        remat=remat,
    )
    in_specs = [
        params_specs,
        jax.tree.map(lambda _: data_spec, inputs_mb),
        jax.tree.map(lambda _: data_spec, targets_mb),
    ]
    args = [inputs_mb, targets_mb]
    append_dropout_operand(in_specs, args, keys_mb)
    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(),
    )

    scale = 1.0 if loss_scale is None else loss_scale

    def scaled(p):
        loss = sharded(p, *args)
        return loss * scale, loss

    (_, loss), grads = jax.value_and_grad(scaled, has_aux=True)(params)
    return loss, grads
