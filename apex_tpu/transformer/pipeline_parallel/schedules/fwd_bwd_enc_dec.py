"""Encoder-decoder (T5-style) pipeline schedule.

Reference: ``apex/transformer/pipeline_parallel/schedules/common.py:72-96``
(``ModelType.encoder_and_decoder`` build: ranks before
``pipeline_model_parallel_split_rank`` hold encoder blocks, ranks at/after it
hold decoder blocks) and the double-tensor plumbing in
``fwd_bwd_pipelining_without_interleaving.py`` (decoder stages forward BOTH
the decoder hidden state and the encoder output between stages, and the
backward pass returns two cotangents).

TPU re-design — two pipelined phases over the SAME pp ring instead of a
static device split:

* The reference must partition devices at ``split_rank`` because each process
  is bound to either encoder or decoder layers for the whole run; whichever
  side has fewer layers idles while the other works. Under SPMD one device
  can hold one encoder chunk AND one decoder chunk, so here ALL ``pp`` stages
  pipeline the encoder (ring #1), the encoder outputs are broadcast from the
  last stage, then ALL ``pp`` stages pipeline the decoder (ring #2) — full
  utilization in both phases, and no split-rank balance problem to tune.
  ``parallel_state`` still exposes the split-rank accessors for API parity.
* Cross-attention memory: every decoder stage needs the encoder output of
  the microbatch it is currently processing. After ring #1 the per-microbatch
  encoder outputs ``[M, ...]`` are made pp-invariant with one masked ``psum``
  (the last stage holds the valid values); ring #2's tick ``t`` on stage
  ``r`` then indexes microbatch ``t - r``. This replaces the reference's
  per-hop "send encoder output along with hidden" p2p chain with one
  collective, and holds ``M`` microbatches of encoder output per device —
  the same budget as the ``[M, ...]`` stage-0 inputs the uniform rings
  already keep resident.
* The backward "double grad" path (ref ``backward_step``'s two-cotangent
  handling) is autodiff: the decoder ring consumes ``mem`` at every tick, so
  its cotangent accumulates across ticks and flows through the broadcast
  transpose into ring #1's scan transpose — exactly the encoder-side gradient
  traffic the reference hand-schedules.

The interleaved (virtual-pipeline) schedule does not support
encoder-decoder models, matching the reference's restriction.
"""

from __future__ import annotations

import dataclasses
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
    _pvary,
    append_dropout_operand,
    check_dropout_spec,
    derive_microbatch_keys,
    embed_microbatches,
    replicate_loss,
    split_microbatches,
    stage_params_spec,
)
from apex_tpu.transformer.pipeline_parallel.schedules.fwd_bwd_pipelining_without_interleaving import (
    pipeline_ring,
)

Pytree = Any


@dataclasses.dataclass(frozen=True)
class EncDecPipelineSpec:
    """The encoder-decoder pipelined model contract
    (``ModelType.encoder_and_decoder``'s ``model_provider_func`` analogue,
    ref common.py:80-103 ``add_encoder``/``add_decoder``).

    enc_embed_fn(embed_params, enc_inputs_mb) -> enc_hidden
        Encoder-side ``pre_process`` for ONE microbatch.
    enc_stage_fn(enc_stage_params, h) -> h
        One encoder pipeline stage (``num_enc_layers / pp`` layers),
        shape-preserving.
    dec_embed_fn(embed_params, dec_inputs_mb) -> dec_hidden
        Decoder-side ``pre_process`` (the reference's second ``pre_process``
        at ``rank == split_rank``, common.py:93).
    dec_stage_fn(dec_stage_params, h, memory) -> h
        One decoder pipeline stage: self-attention + cross-attention over
        ``memory`` (the encoder output for the SAME microbatch) + MLP.
        Shape-preserving in ``h``; ``memory`` may have a different sequence
        length.
    loss_fn(head_params, h, targets_mb) -> scalar
        Decoder-side ``post_process``, averaged over the microbatch.
    """

    enc_embed_fn: Callable[[Pytree, Pytree], Pytree]
    enc_stage_fn: Callable[[Pytree, Pytree], Pytree]
    dec_embed_fn: Callable[[Pytree, Pytree], Pytree]
    dec_stage_fn: Callable[[Pytree, Pytree, Pytree], Pytree]
    loss_fn: Callable[[Pytree, Pytree, Pytree], jnp.ndarray]
    # True: embed and stage functions take a trailing per-microbatch PRNG
    # key — ``enc_embed_fn(p, tok, key)`` / ``enc_stage_fn(p, h, key)`` /
    # ``dec_stage_fn(p, h, memory, key)`` — so embedding dropout matches
    # the sequential path (t5_encode/t5_decode apply it, salts 100/101).
    # Per-side / per-stage decorrelation is the model's job.
    takes_dropout_key: bool = False


def broadcast_from_last_stage(x: Pytree, axis_name: str = PP_AXIS) -> Pytree:
    """Replicate the last pipeline stage's values over the pp axis.

    The fill/drain garbage on earlier stages is finite (zero-init through
    finite stage math), so a masked psum both discards it and broadcasts in
    one collective.
    """
    pp = lax.axis_size(axis_name)
    is_last = lax.axis_index(axis_name) == pp - 1

    def one(a):
        masked = jnp.where(is_last, a, jnp.zeros_like(a))
        return lax.psum(_pvary(masked, axis_name), axis_name)

    return jax.tree.map(one, x)


def decoder_ring(
    dec_fn: Callable[[Pytree, Pytree, Pytree], Pytree],
    stage_params: Pytree,
    h_mb: Pytree,
    mem_mb: Pytree,
    *,
    num_microbatches: int,
    axis_name: str = PP_AXIS,
    remat: bool = True,
    keys_mb: Optional[jax.Array] = None,
) -> Pytree:
    """``pipeline_ring`` with a per-tick cross-attention memory operand.

    ``mem_mb`` is ``[M, ...]`` encoder outputs, valid on EVERY device (run
    :func:`broadcast_from_last_stage` first). At tick ``t`` stage ``r``
    processes microbatch ``t - r``, so it cross-attends to
    ``mem_mb[t - r]``; fill/drain ticks index a clipped microbatch and are
    masked out of the loss downstream, contributing exactly-zero cotangents
    to ``mem_mb`` through the finite stage math.

    ``keys_mb`` ([M]-stacked PRNG keys) rides the same per-microbatch side
    channel, arriving as ``dec_fn(params, h, memory, key)``.
    """
    fn = dec_fn
    extra = mem_mb
    if keys_mb is not None:
        extra = (mem_mb, keys_mb)
        fn = lambda p, h, mem_key: dec_fn(p, h, *mem_key)  # noqa: E731
    return pipeline_ring(
        fn,
        stage_params,
        h_mb,
        num_microbatches=num_microbatches,
        axis_name=axis_name,
        remat=remat,
        extra_mb=extra,
    )


def _enc_dec_body(
    params: Pytree,
    enc_inputs_mb: Pytree,
    dec_inputs_mb: Pytree,
    targets_mb: Pytree,
    keys_mb: Optional[jax.Array] = None,
    *,
    spec: EncDecPipelineSpec,
    num_microbatches: int,
    mesh,
    remat: bool,
):
    enc_local = jax.tree.map(lambda a: a[0], params["enc_stages"])
    dec_local = jax.tree.map(lambda a: a[0], params["dec_stages"])

    # Phase 1: encoder ring over all pp stages. The monitor spans nest the
    # ring's own pp_stage/pp_ring_shift ranges under a per-phase name, so
    # trace/pyprof reports split enc vs dec vs memory-broadcast time.
    with span("pp_encode"):
        h_enc_mb = embed_microbatches(spec.enc_embed_fn, params["embed"],
                                      enc_inputs_mb, keys_mb)
        enc_out_mb = pipeline_ring(
            spec.enc_stage_fn,
            enc_local,
            h_enc_mb,
            num_microbatches=num_microbatches,
            remat=remat,
            extra_mb=keys_mb,
        )
    with span("pp_memory_broadcast"):
        mem_mb = broadcast_from_last_stage(enc_out_mb)

    # Phase 2: decoder ring, cross-attending to the broadcast memory.
    with span("pp_decode"):
        h_dec_mb = embed_microbatches(spec.dec_embed_fn, params["embed"],
                                      dec_inputs_mb, keys_mb)
        ys = decoder_ring(
            spec.dec_stage_fn,
            dec_local,
            h_dec_mb,
            mem_mb,
            num_microbatches=num_microbatches,
            remat=remat,
            keys_mb=keys_mb,
        )
    losses = jax.vmap(spec.loss_fn, in_axes=(None, 0, 0))(
        params["head"], ys, targets_mb
    )
    pp = lax.axis_size(PP_AXIS)
    is_last = lax.axis_index(PP_AXIS) == pp - 1
    local = jnp.where(is_last, jnp.mean(losses), 0.0)
    return replicate_loss(local, mesh)


def forward_backward_pipelining_enc_dec(
    spec: EncDecPipelineSpec,
    params: Pytree,
    batch: Tuple[Pytree, Pytree, Pytree],
    *,
    num_microbatches: int,
    mesh=None,
    params_specs: Optional[Pytree] = None,
    data_spec: P = P(None, DP_AXIS),
    loss_scale: Optional[jnp.ndarray] = None,
    remat: bool = True,
    dropout_key: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, Pytree]:
    """Encoder-decoder 1F1B driver. ``batch = (enc_inputs, dec_inputs,
    targets)`` pytrees with a leading global-batch dim. Returns
    ``(mean_unscaled_loss, grads)``; grads are w.r.t. ``loss * loss_scale``.

    ``params = {"embed": ..., "enc_stages": <[pp] axis>, "dec_stages":
    <[pp] axis>, "head": ...}`` — each device holds one encoder AND one
    decoder chunk (see module docstring for why this beats the reference's
    split-rank device partition on TPU).

    ``dropout_key`` (requires ``spec.takes_dropout_key``) derives one key
    per microbatch, delivered to BOTH rings' stage functions through the
    per-microbatch side channel (``enc_stage_fn(p, h, key)`` /
    ``dec_stage_fn(p, h, mem, key)``); per-side and per-stage
    decorrelation is the model's fold (see ``t5_enc_dec_spec``).
    """
    if mesh is None:
        from apex_tpu.transformer import parallel_state

        mesh = parallel_state.get_mesh()
    if params_specs is None:
        params_specs = {
            "embed": jax.tree.map(lambda _: P(), params["embed"]),
            "enc_stages": stage_params_spec(params["enc_stages"]),
            "dec_stages": stage_params_spec(params["dec_stages"]),
            "head": jax.tree.map(lambda _: P(), params["head"]),
        }
    enc_inputs, dec_inputs, targets = batch
    enc_mb = split_microbatches(enc_inputs, num_microbatches)
    dec_mb = split_microbatches(dec_inputs, num_microbatches)
    tgt_mb = split_microbatches(targets, num_microbatches)
    check_dropout_spec(spec, dropout_key)
    keys_mb = derive_microbatch_keys(dropout_key, num_microbatches)

    body = functools.partial(
        _enc_dec_body,
        spec=spec,
        num_microbatches=num_microbatches,
        mesh=mesh,
        remat=remat,
    )
    in_specs = [
        params_specs,
        jax.tree.map(lambda _: data_spec, enc_mb),
        jax.tree.map(lambda _: data_spec, dec_mb),
        jax.tree.map(lambda _: data_spec, tgt_mb),
    ]
    args = [enc_mb, dec_mb, tgt_mb]
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
