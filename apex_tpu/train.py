"""The train step: forward, backward and FusedAdam as one jitted program
over a mesh. Every model the package trains runs this one step, and it is
the step the benchmark's cells time (``perfbench/kinds/``).

A model is any object with three methods:

* ``param_specs()``: the parameters' pytree with a ``PartitionSpec`` a leaf;
* ``init_params(rng)``: the parameters, in that pytree;
* ``loss(params, tokens, targets)``: the local mean loss, called inside
  ``shard_map`` over the mesh (parameters per ``param_specs()``, the batch
  split over ``dp``).

``transformer.testing.GPTConfig``, ``transformer.hybrid.HybridConfig``,
``transformer.sdar.SDARConfig``, ``transformer.deepseek.DeepSeekConfig``,
``transformer.laguna.LagunaConfig`` and
``transformer.smallthinker.SmallThinkerConfig`` are. A model that counts
what its step did (a routed layer's loads, its balance loss) has a fourth,
``loss_and_counters(params, tokens, targets)``: the loss and a pytree of
arrays whose leading axis stacks over ``dp`` (every routed decoder has). Its step returns
them fourth, from the step itself and at no pass of their own.

Each call of the step is a host span ``train_step`` carrying its call index
(``monitor.trace.host_log``), so that a compilation is a record that names
the call it held up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


class _Dispatched:
    """A jitted step whose every call is a host span named after it, with
    the call's index from 1; ``lower``, ``trace`` and the rest are the jitted
    function's own."""

    def __init__(self, jitted, name: str):
        from apex_tpu.monitor.trace import span

        self._jitted = jitted
        self._name = name
        self._span = span
        self.calls = 0

    def __call__(self, *args):
        self.calls += 1
        with self._span(self._name, call=self.calls):
            return self._jitted(*args)

    def __getattr__(self, attr):
        return getattr(self._jitted, attr)


def train_step_fn(model, mesh):
    """The jitted fwd+bwd+FusedAdam step of ``model`` over ``mesh`` (params
    and optimizer state donated), each call a host span ``train_step``
    (:class:`_Dispatched`), plus the optimizer it steps."""
    from apex_tpu.monitor.trace import register_program, span
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.pipeline_parallel.schedules.common import (
        replicate_loss,
    )

    specs = model.param_specs()
    opt = FusedAdam(lr=1e-4)
    counted = hasattr(model, "loss_and_counters")

    def loss_fn(p, tok, tgt):
        def body(p, tok, tgt):
            if counted:
                loss, counters = model.loss_and_counters(p, tok, tgt)
                return replicate_loss(loss, mesh, masked_axis=None), counters
            return replicate_loss(model.loss(p, tok, tgt), mesh,
                                  masked_axis=None)

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(specs, P("dp"), P("dp")),
                             out_specs=(P(), P("dp")) if counted else P())(
                                 p, tok, tgt)

    def update(grads, opt_state, params):
        # the optimizer steps each device's own shards inside the mesh
        # program, in the shapes and types they have: one fusion a leaf
        # that writes m, v and p where the donated ones were
        state_specs = opt_state._replace(count=P(), mu=specs, nu=specs)
        return jax.shard_map(opt.step, mesh=mesh,
                             in_specs=(specs, state_specs, specs),
                             out_specs=(specs, state_specs))(
                                 grads, opt_state, params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tok, tgt):
        # ``out`` is the loss, or (loss, counters) of a model that counts
        out, grads = jax.value_and_grad(loss_fn, has_aux=counted)(
            params, tok, tgt)
        # the optimizer's pass starts from gradients in memory: without the
        # barrier XLA fuses some leaves' tails into the products that make
        # their gradients, under those products' scopes, and what is read
        # under ``opt`` is no longer the optimizer's whole cost
        grads = jax.tree.map(jax.lax.optimization_barrier, grads)
        with span("opt"):
            params, opt_state = update(grads, opt_state, params)
        if counted:
            return (params, opt_state, *out)
        return params, opt_state, out

    def lower(rows: int, seq: int):
        """The step lowered at the shapes and shardings a job hands it,
        from shapes alone: for ``monitor.trace.scope_table``."""
        return train_step.lower(
            *abstract_train_args(model, opt, mesh, rows, seq))

    register_program("jit_train_step", lower)
    return _Dispatched(train_step, "train_step"), opt


def abstract_train_args(model, opt, mesh, rows: int, seq: int):
    """``(params, opt_state, tok, tgt)`` as ``ShapeDtypeStruct``s placed as
    a job places the real ones (parameters and Adam's moments per
    ``param_specs()``, the batch over ``dp``): no array is made."""
    def placed(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    specs = model.param_specs()
    params = jax.tree.map(placed, jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0))), specs)
    state = jax.eval_shape(opt.init, params)
    state = state._replace(count=placed(state.count, P()),
                           mu=jax.tree.map(placed, state.mu, specs),
                           nu=jax.tree.map(placed, state.nu, specs))
    tok = placed(jax.ShapeDtypeStruct((rows, seq), jnp.int32), P("dp"))
    return params, state, tok, tok
