"""Async-dispatch + buffer-donation stress tests.

Ref analogue: ``tests/distributed/DDP/ddp_race_condition_test.py:28-50``
backs the reference's overlap engine with a dedicated race test (mutate a
param mid-flight, assert the all-reduced grads still come out right). The
XLA design dissolves stream races, but this repo's own hazard class —
donated buffers reused across asynchronously-dispatched steps and host
reads interleaved with in-flight work — had no dedicated test until this
one.

Strategy: run the donated flagship-style train step (the same
donate_argnums=(0,1) shape ``apex_tpu.train`` and the EP dryrun use) many steps with
host reads interleaved at different cadences; every cadence must produce
the bitwise-identical loss trajectory. If XLA ever handed a donated buffer
to a new step while a prior consumer was still in flight — or a host read
raced the write — the trajectories would diverge.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import (
    GPTConfig,
    gpt_loss,
    gpt_param_specs,
    init_gpt_params,
)

STEPS = 6


def _make_step(mesh, cfg, donate):
    specs = gpt_param_specs(cfg)
    opt = FusedAdam(lr=1e-2)

    def loss_fn(p, tok, tgt):
        def body(p, tok, tgt):
            from apex_tpu.transformer.pipeline_parallel.schedules.common import (
                replicate_loss,
            )

            return replicate_loss(gpt_loss(p, tok, tgt, cfg), mesh,
                                  masked_axis=None)

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(specs, P(), P()), out_specs=P())(
                                 p, tok, tgt)

    def train_step(params, opt_state, tok, tgt):
        loss, grads = jax.value_and_grad(loss_fn)(params, tok, tgt)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    step = (jax.jit(train_step, donate_argnums=(0, 1)) if donate
            else jax.jit(train_step))

    def init():
        p = init_gpt_params(jax.random.PRNGKey(0), cfg)
        s = opt.init(p)
        k = jax.random.PRNGKey(1)
        tok = jax.random.randint(k, (4, cfg.max_seq), 0, cfg.vocab_size)
        return p, s, tok, jnp.roll(tok, -1, axis=1)

    return step, init


@pytest.fixture(scope="module")
def small_cfg():
    return GPTConfig(vocab_size=64, max_seq=32, hidden=32, num_layers=2,
                     num_heads=4, dtype=jnp.float32, tie_embeddings=False)


def _run_trajectory(step, init, read_every):
    """Drive STEPS donated steps, host-reading the loss every
    ``read_every`` steps (1 = fence each step; STEPS = let the whole
    donated chain queue up async before the single final read)."""
    params, opt_state, tok, tgt = init()
    losses = []
    for i in range(STEPS):
        params, opt_state, loss = step(params, opt_state, tok, tgt)
        losses.append(loss)
        if (i + 1) % read_every == 0:
            losses[-1] = float(losses[-1])
    return [float(x) for x in losses]


def test_donated_chain_value_stability(small_cfg):
    """The same donated-step chain must be bitwise identical whether the
    host fences every step or lets the async queue run ahead."""
    mesh = parallel_state.initialize_model_parallel()  # dp=8 mesh
    step, init = _make_step(mesh, small_cfg, donate=True)
    fenced = _run_trajectory(step, init, read_every=1)
    queued = _run_trajectory(step, init, read_every=STEPS)
    assert fenced == queued, (fenced, queued)
    assert fenced[-1] < fenced[0]  # and it actually trains


def test_donation_matches_undonated(small_cfg):
    """Donation is an aliasing optimization — it must not change values
    vs the undonated step (the reference's race test asserts the overlap
    engine is value-neutral the same way)."""
    mesh = parallel_state.initialize_model_parallel()
    donated_step, init = _make_step(mesh, small_cfg, donate=True)
    plain_step, _ = _make_step(mesh, small_cfg, donate=False)
    donated = _run_trajectory(donated_step, init, read_every=2)
    plain = _run_trajectory(plain_step, init, read_every=1)
    assert donated == plain, (donated, plain)


def test_interleaved_param_reads_see_consistent_state(small_cfg):
    """Host-reading a param leaf between queued donated steps must see
    that step's committed value (never a torn/reused buffer): the read-back
    norms must match the fenced trajectory's norms exactly."""
    mesh = parallel_state.initialize_model_parallel()
    step, init = _make_step(mesh, small_cfg, donate=True)

    def norms(read_back):
        params, opt_state, tok, tgt = init()
        out = []
        for i in range(STEPS):
            params, opt_state, loss = step(params, opt_state, tok, tgt)
            if read_back:
                # immediate host read of a mid-pytree leaf, racing the
                # async dispatch of the NEXT iteration's donation
                leaf = jax.tree.leaves(params)[3]
                out.append(float(jnp.vdot(leaf, leaf)))
        if not read_back:
            leaf = jax.tree.leaves(params)[3]
            out.append(float(jnp.vdot(leaf, leaf)))
        return out

    interleaved = norms(read_back=True)
    final_only = norms(read_back=False)
    np.testing.assert_array_equal(interleaved[-1], final_only[-1])


def test_donated_input_is_consumed(small_cfg):
    """Reading a donated argument AFTER the step must raise — the buffer
    belongs to the new state. Pins the deletion semantics the donated
    entry points (``apex_tpu.train``, the EP dryrun) rely on."""
    mesh = parallel_state.initialize_model_parallel()
    step, init = _make_step(mesh, small_cfg, donate=True)
    params, opt_state, tok, tgt = init()
    new_params, new_opt_state, loss = step(params, opt_state, tok, tgt)
    float(loss)
    with pytest.raises(RuntimeError):
        np.asarray(jax.tree.leaves(params)[0])
    # the NEW state is alive and readable
    assert np.isfinite(np.asarray(jax.tree.leaves(new_params)[0])).all()
