"""``ops.lm_head_loss(..., weights=)``: a weight a row rides the cotangent the
backward kernels already take, so the weighted loss and its gradients equal
the dense weighted cross entropy, and a row of weight 0 adds nothing."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops.lm_head_loss import lm_head_loss, lm_head_loss_reference

N, H, V = 256, 128, 384


def _inputs(dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (2, N // 2, H), jnp.float32).astype(dtype)
    w = (0.1 * jax.random.normal(k[1], (V, H), jnp.float32)).astype(dtype)
    t = jax.random.randint(k[2], (2, N // 2), 0, V)
    weights = jnp.where(jax.random.uniform(k[3], (2, N // 2)) < 0.4,
                        1.0 / jax.random.uniform(k[3], (2, N // 2), minval=0.1), 0.0)
    return x, w, t, weights


@pytest.mark.parametrize("use_pallas", [False, True], ids=["dense", "kernels"])
def test_weighted_loss_and_gradients_equal_the_dense_weighted_cross_entropy(use_pallas):
    x, w, t, weights = _inputs()
    got = lm_head_loss(x, w, t, weights=weights, use_pallas=use_pallas)
    want = weights * lm_head_loss_reference(x.reshape(-1, H), w, t.reshape(-1)).reshape(t.shape)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    f = lambda x, w: jnp.sum(lm_head_loss(x, w, t, weights=weights, use_pallas=use_pallas))
    g = lambda x, w: jnp.sum(weights * lm_head_loss_reference(
        x.reshape(-1, H), w, t.reshape(-1)).reshape(t.shape))
    for a, b in zip(jax.grad(f, (0, 1))(x, w), jax.grad(g, (0, 1))(x, w)):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_a_row_of_weight_zero_adds_nothing_to_either_gradient():
    x, w, t, weights = _inputs()
    f = lambda x, w, t: jnp.sum(lm_head_loss(x, w, t, weights=weights, use_pallas=True))
    dx, dw = jax.grad(f, (0, 1))(x, w, t)
    assert float(jnp.abs(dx[weights == 0]).max()) == 0.0
    # the unscored rows' targets and states can be anything
    t2 = jnp.where(weights == 0, (t + 7) % V, t)
    x2 = jnp.where((weights == 0)[..., None], x * 3.0, x)
    _, dw2 = jax.grad(f, (0, 1))(x2, w, t2)
    np.testing.assert_allclose(dw, dw2, atol=1e-5)


def test_no_weights_is_the_op_as_it_was():
    x, w, t, _ = _inputs()
    np.testing.assert_array_equal(lm_head_loss(x, w, t, use_pallas=True),
                                  lm_head_loss(x, w, t, use_pallas=True,
                                               weights=jnp.ones(t.shape)))


def test_hidden_2048_keeps_the_dw_kernels_tile_set_inside_its_limit():
    """At hidden 2048 a (1024, hidden) float32 accumulator is the whole 8 MiB
    allowance: the vocabulary block stays 512 (the 1024 block's tile set
    overflowed the kernel's 32 MiB scoped VMEM on a v5e)."""
    from apex_tpu.ops import lm_head_loss as m
    seen = {}
    real = m.pl.pallas_call

    def spy(kernel, **kw):
        if kw.get("name") == "lm_head_bwd_dw":
            seen["grid"] = kw["grid"]
        return real(kernel, **kw)

    m.pl.pallas_call = spy
    try:
        x = jnp.zeros((512, 2048), jnp.float32)
        w = jnp.zeros((2048, 2048), jnp.float32)
        jax.grad(lambda w: jnp.sum(lm_head_loss(x, w, jnp.zeros((512,), jnp.int32),
                                                use_pallas=True)))(w)
    finally:
        m.pl.pallas_call = real
    assert seen["grid"][0] == 2048 // 512
