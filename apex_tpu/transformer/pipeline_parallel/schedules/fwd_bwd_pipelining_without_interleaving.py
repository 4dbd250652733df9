"""1F1B pipeline schedule over a ppermute ring.

Reference: ``fwd_bwd_pipelining_without_interleaving.py:155-345`` — warmup
forwards (pp - rank - 1), steady-state one-forward-one-backward with fused
``send_forward_recv_backward`` p2p, cooldown backwards; activation/cotangent
tensors move between stage processes with batched isend/irecv.

TPU re-design: the whole schedule is ONE shard_map program containing a
``lax.scan`` over ``M + pp - 1`` ticks. Each tick every stage applies its
layer block and the ring shifts activations one stage forward
(``lax.ppermute`` — collective permute is the ICI-native neighbor exchange).
Differentiating the program yields the backward pipeline automatically: the
transpose of the scan is the reverse-tick scan and the transpose of the
ppermute is the reverse shift, i.e. exactly the reference's cooldown/steady
backward traffic, scheduled by XLA instead of by hand. The 1F1B memory
property (≤ pp microbatches of activations live per stage) is approximated
with ``jax.checkpoint`` on the stage function: only the stage-boundary
activations of each tick are saved (one microbatch-sized tensor per tick);
interior activations are rematerialized in the backward sweep.

Fill/drain ticks compute on zero-initialized garbage that is masked out of
the loss; with finite stage math (any standard transformer block) those paths
contribute exactly-zero cotangents.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

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

Pytree = Any


def _tree_index(tree: Pytree, i) -> Pytree:
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree
    )


def _tree_where(cond, a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(lambda x, y: jnp.where(cond, x, y), a, b)


def _ring_shift(x: Pytree, axis_name: str) -> Pytree:
    from apex_tpu.transformer.pipeline_parallel import p2p_communication

    return p2p_communication.send_forward_recv_forward(x, axis_name)


def _pvary_all(x: Pytree, axis_names) -> Pytree:
    """Mark every leaf varying over all given axes (identity value-wise) so
    the scan carry has a fixed VMA type regardless of what collectives the
    stage function ends with."""

    def one(a):
        for name in axis_names:
            try:
                if name in jax.typeof(a).vma:
                    continue
            except (AttributeError, TypeError):
                return a  # no vma tracking
            a = lax.pcast(a, name, to="varying")
        return a

    return jax.tree.map(one, x)


def _mesh_axis_names():
    from apex_tpu.parallel.mesh import AXIS_ORDER

    return AXIS_ORDER


def pipeline_ring(
    stage_fn: Callable[..., Pytree],
    stage_params: Pytree,
    h_mb: Pytree,
    *,
    num_microbatches: int,
    axis_name: str = PP_AXIS,
    remat: bool = True,
    returns_aux: bool = False,
    extra_mb: Optional[Pytree] = None,
) -> Pytree:
    """Run ``num_microbatches`` activations through the pp-stage ring.

    Must be called inside a mesh program. ``stage_params`` is this stage's
    local params (stage axis already squeezed); ``h_mb`` is ``[M, ...]``
    stage-0 inputs (present on every device, consumed at stage 0). Returns
    ``[M, ...]`` outputs, valid on the LAST stage (garbage elsewhere — mask
    before use). With ``returns_aux`` the stage function yields
    ``(h, aux_scalar)`` and the result is ``(outputs, aux_mean)`` where
    ``aux_mean`` averages the stage's aux over its real microbatch ticks
    (fill/drain garbage is masked out).

    ``extra_mb`` is an optional ``[M, ...]`` per-microbatch side operand
    valid on EVERY device (e.g. encoder memory for a decoder ring); when
    given, the stage function is called ``stage_fn(params, h, extra_t)``
    with ``extra_t`` the entry for the microbatch this stage processes at
    this tick (``t - rank``, clipped on fill/drain ticks whose outputs are
    masked downstream).
    """
    pp = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    M = num_microbatches
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    axes = _mesh_axis_names()

    def tick(carry, t):
        h, aux_sum = carry
        x0 = _tree_index(h_mb, jnp.clip(t, 0, M - 1))
        inp = _tree_where(rank == 0, x0, h)
        args = (stage_params, inp)
        if extra_mb is not None:
            # stage `rank` holds microbatch t-rank at tick t
            args += (_tree_index(extra_mb, jnp.clip(t - rank, 0, M - 1)),)
        # monitor spans: per-tick stage compute vs ring p2p show up as
        # distinct layer paths in the trace/measured tables — with the
        # analytic bubble share from monitor.pipeline_bubble_fraction this
        # is the schedule's bubble attribution
        if returns_aux:
            with span("pp_stage"):
                out, aux = fn(*args)
            valid = (t >= rank) & (t - rank <= M - 1)
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
    (_, aux_sum), ys = lax.scan(tick, init, jnp.arange(M + pp - 1))
    # tick pp-1+i holds microbatch i's final output on the last stage
    outs = jax.tree.map(lambda a: a[pp - 1:], ys)
    if returns_aux:
        return outs, aux_sum / M
    return outs


def _pipeline_body(
    params: Pytree,
    inputs_mb: Pytree,
    targets_mb: Pytree,
    keys_mb: Optional[Pytree] = None,
    *,
    spec: PipelineSpec,
    num_microbatches: int,
    mesh,
    remat: bool,
):
    stage_local = jax.tree.map(lambda a: a[0], params["stages"])
    h_mb = embed_microbatches(spec.embed_fn, params["embed"], inputs_mb,
                              keys_mb)
    ys = pipeline_ring(
        spec.stage_fn,
        stage_local,
        h_mb,
        num_microbatches=num_microbatches,
        remat=remat,
        returns_aux=spec.stage_aux,
        extra_mb=keys_mb,
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
        # per-stage layer-mean aux -> model-wide layer mean (psum/pp), same
        # dp averaging as the main loss
        total = total + replicate_loss(aux, mesh, masked_axis=None)
    return total


def forward_backward_pipelining_without_interleaving(
    spec: PipelineSpec,
    params: Pytree,
    batch: Tuple[Pytree, Pytree],
    *,
    num_microbatches: int,
    mesh=None,
    params_specs: Optional[Pytree] = None,
    data_spec: P = P(None, DP_AXIS),
    loss_scale: Optional[jnp.ndarray] = None,
    remat: bool = True,
    dropout_key: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, Pytree]:
    """The driver (ref :155). ``batch = (inputs, targets)`` pytrees with a
    leading global-batch dim. Returns ``(mean_unscaled_loss, grads)``; grads
    are w.r.t. ``loss * loss_scale``.

    ``params = {"embed": ..., "stages": <leading [pp] axis>, "head": ...}``.
    ``params_specs`` mirrors ``params`` with PartitionSpecs (default:
    embed/head replicated, stages ``P("pp")`` — supply your own to lay TP
    shards onto the mesh). ``data_spec`` shards the microbatched data
    ``[M, B, ...]``; the default splits the per-microbatch batch dim over dp.

    ``dropout_key`` (training mode; requires a spec built with
    ``takes_dropout_key``) derives one key per microbatch and routes it to
    the embed/stage functions through the ring's per-microbatch side
    channel, so microbatches drop independent positions; stage/sp
    decorrelation is the model's own axis-fold (ref ParallelTransformer
    trains with dropout under every schedule).
    """
    from apex_tpu.transformer.pipeline_parallel.schedules.fwd_bwd_enc_dec import (
        EncDecPipelineSpec,
        forward_backward_pipelining_enc_dec,
    )

    if isinstance(spec, EncDecPipelineSpec):
        # ModelType.encoder_and_decoder routing (ref common.py:80-103): the
        # same driver name serves both model types, as in the reference.
        return forward_backward_pipelining_enc_dec(
            spec,
            params,
            batch,
            num_microbatches=num_microbatches,
            mesh=mesh,
            params_specs=params_specs,
            data_spec=data_spec,
            loss_scale=loss_scale,
            remat=remat,
            dropout_key=dropout_key,
        )
    if mesh is None:
        from apex_tpu.transformer import parallel_state

        mesh = parallel_state.get_mesh()
    if params_specs is None:
        params_specs = {
            "embed": jax.tree.map(lambda _: P(), params["embed"]),
            "stages": stage_params_spec(params["stages"]),
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
