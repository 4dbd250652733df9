"""``FusedAdam(...).step``: the one-pass form the train step runs.

``step(grads, state, params) -> (params, state)`` is ``update`` with the
update applied: the same moments to float32 reassociation, and the parameter
``p32 - lr*u`` rounded once to the leaf's type where ``update`` rounds ``u``
and then the sum. Checked over three steps from one trajectory, for the leaf
shapes of the benchmark's cells in small. The contract on the compiled train
step (no copy of a leaf under ``opt``, every leaf written where it was) is in
``tests/test_tpu_lowering.py``, with the other compiles for a described chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_tpu.optimizers import FusedAdam

# the cells' leaves in small: GPT-2's stacked matrices and vectors and its
# tables, the hybrid's 4-D stacks with trailing 30 and 2,880-like widths
# (22.5 x 128), its convolutions, its float32 decay vectors, a scalar
SHAPES = {
    "stacked_matrix": (3, 16, 48),
    "table": (64, 16),
    "stacked_vector": (3, 16),
    "vector": (16,),
    "stack_4d_trailing_30": (1, 3, 16, 30),
    "stack_4d_not_128s": (1, 3, 8, 360),
    "convolution": (1, 3, 4, 36),
    "decay_vector": (1, 3, 30),
    "scalar": (),
}


def _ulp(x, dtype):
    """The spacing of ``dtype``'s grid at ``|x|``, elementwise."""
    x = np.abs(np.asarray(x, np.float32))
    bits = jnp.finfo(dtype).nmant
    tiny = float(jnp.finfo(dtype).tiny)
    return 2.0 ** (np.floor(np.log2(np.maximum(x, tiny))) - bits)


def _tree(shapes, dtype, seed, scale=1.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {name: (scale * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
            for k, (name, shape) in zip(keys, shapes.items())}


def _three_steps(opt, params, dtype):
    """Both forms from the same state, three times along ``step``'s own
    trajectory: yields (by ``step``, by ``update``, the parameters before)."""
    step, update = jax.jit(opt.step), jax.jit(opt.update)
    state = opt.init(params)
    for i in range(3):
        grads = _tree({k: v.shape for k, v in params.items()}, dtype, 100 + i, 0.1)
        updates, state_u = update(grads, state, params)
        by_update = optax.apply_updates(params, updates)
        by_step, state_s = step(grads, state, params)
        yield (by_step, state_s), (by_update, state_u), params
        params, state = by_step, state_s


def _assert_one_rounding_apart(by_step, by_update, before):
    (p_s, st_s), (p_u, st_u) = by_step, by_update
    assert int(st_s.count) == int(st_u.count)
    for name in before:
        assert p_s[name].dtype == p_u[name].dtype == before[name].dtype
        assert p_s[name].shape == before[name].shape
        assert st_s.mu[name].dtype == st_s.nu[name].dtype == jnp.float32
        np.testing.assert_allclose(st_s.mu[name], st_u.mu[name], rtol=1e-6, atol=0)
        np.testing.assert_allclose(st_s.nu[name], st_u.nu[name], rtol=1e-6, atol=0)
        got, other, was = (np.asarray(a[name], np.float32) for a in (p_s, p_u, before))
        # one rounding of the sum, and the rounding of ``u`` that ``update`` makes
        dtype = before[name].dtype
        room = _ulp(np.maximum(np.abs(got), np.abs(other)), dtype) + _ulp(got - was, dtype)
        assert (np.abs(got - other) <= room).all(), (name, float(np.abs(got - other).max()))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_step_is_update_applied_for_a_leaf_of_the_cells(shape, dtype):
    opt = FusedAdam(lr=1e-2, weight_decay=0.01)
    params = _tree({shape: SHAPES[shape]}, dtype, 0)
    for by_step, by_update, before in _three_steps(opt, params, dtype):
        _assert_one_rounding_apart(by_step, by_update, before)


@pytest.mark.parametrize("lr", [1e-2, optax.linear_schedule(1e-2, 1e-3, 3)],
                         ids=["constant", "scheduled"])
@pytest.mark.parametrize("weight_decay", [0.0, 0.05])
@pytest.mark.parametrize("adam_w_mode", [True, False], ids=["adamw", "l2"])
@pytest.mark.parametrize("bias_correction", [True, False], ids=["corrected", "raw"])
def test_step_is_update_applied_in_every_mode(bias_correction, adam_w_mode, weight_decay, lr):
    """A tree as a cell holds it: bf16 leaves with the float32 decay vectors
    among them."""
    opt = FusedAdam(lr=lr, bias_correction=bias_correction, adam_w_mode=adam_w_mode,
                    weight_decay=weight_decay)
    params = _tree(SHAPES, jnp.bfloat16, 1)
    params["decay_vector"] = params["decay_vector"].astype(jnp.float32)
    for by_step, by_update, before in _three_steps(opt, params, jnp.bfloat16):
        _assert_one_rounding_apart(by_step, by_update, before)


@pytest.mark.parametrize("adam_w_mode", [True, False], ids=["adamw", "l2"])
def test_step_rounds_the_parameter_once(adam_w_mode):
    """Against the tail in float64: the bf16 parameter lies within half a
    spacing of ``p - lr*u`` (two roundings may lie a whole one away)."""
    lr, wd, b1, b2, eps = 1e-2, 0.05, 0.9, 0.999, 1e-8
    opt = FusedAdam(lr=lr, weight_decay=wd, betas=(b1, b2), eps=eps, adam_w_mode=adam_w_mode)
    p = _tree({"w": (64, 48)}, jnp.bfloat16, 2)
    g = _tree({"w": (64, 48)}, jnp.bfloat16, 3, 0.1)
    new_p, state = jax.jit(opt.step)(g, opt.init(p), p)
    p64, g64 = np.asarray(p["w"], np.float64), np.asarray(g["w"], np.float64)
    if not adam_w_mode:
        g64 = g64 + wd * p64
    m = (1 - b1) * g64
    v = (1 - b2) * g64 * g64
    u = (m / (1 - b1)) / (np.sqrt(v / (1 - b2)) + eps)
    if adam_w_mode:
        u = u + wd * p64
    want = p64 - lr * u
    np.testing.assert_allclose(state.mu["w"], m, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(state.nu["w"], v, rtol=1e-5, atol=1e-10)
    gap = np.abs(np.asarray(new_p["w"], np.float64) - want)
    assert (gap <= 0.5 * _ulp(want, jnp.bfloat16) * (1 + 1e-3)).all()


def test_the_transformation_is_optax_s_with_a_step_beside_it():
    opt = FusedAdam(lr=1e-3)
    init, update = opt
    assert isinstance(opt, optax.GradientTransformation)
    assert (init, update) == (opt.init, opt.update) and callable(opt.step)
    p = {"w": jnp.ones((4, 3), jnp.bfloat16)}
    g = {"w": jnp.full((4, 3), 0.5, jnp.bfloat16)}
    chained = optax.chain(optax.clip_by_global_norm(1.0), opt)
    updates, _ = chained.update(g, chained.init(p), p)
    assert updates["w"].dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="requires params"):
        opt.step(g, opt.init(p), None)
