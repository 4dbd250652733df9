"""Fused LM-head CE vs dense logits + vocab-parallel CE (ground truth)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.lm_head_loss import (
    _lm_head_loss,
    lm_head_loss,
    lm_head_loss_reference,
)
from apex_tpu.parallel.mesh import TP_AXIS, build_mesh


def _dense_loss(x2, w, t):
    logits = jnp.einsum("nh,vh->nv", x2.astype(jnp.float32),
                        w.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, t[:, None], axis=1)[:, 0]


@pytest.mark.parametrize("n,v,h,bn,bv", [
    (16, 64, 128, 8, 16),     # aligned vocab
    (16, 37, 128, 8, 16),     # ragged final vocab block
    (32, 100, 256, 16, 32),   # ragged, larger
])
def test_fused_matches_dense_and_grads(n, v, h, bn, bv):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x2 = jax.random.normal(ks[0], (n, h), jnp.float32) * 0.5
    w = jax.random.normal(ks[1], (v, h), jnp.float32) * 0.1
    t = jax.random.randint(ks[2], (n,), 0, v)

    def fused(x2, w):
        return jnp.mean(_lm_head_loss(x2, w, t, None, bn, bv,
                                      "pallas_interpret"))

    def dense(x2, w):
        return jnp.mean(_dense_loss(x2, w, t))

    lf, (dxf, dwf) = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(x2, w)
    ld, (dxd, dwd) = jax.jit(jax.value_and_grad(dense, argnums=(0, 1)))(x2, w)
    np.testing.assert_allclose(lf, ld, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dxf, dxd, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dwf, dwd, rtol=1e-4, atol=1e-5)


def test_reference_unsharded_matches_dense():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x2 = jax.random.normal(ks[0], (8, 32))
    w = jax.random.normal(ks[1], (20, 32)) * 0.2
    t = jax.random.randint(ks[2], (8,), 0, 20)
    np.testing.assert_allclose(lm_head_loss_reference(x2, w, t),
                               _dense_loss(x2, w, t), rtol=1e-5, atol=1e-6)


def test_vocab_parallel_fused_matches_dense():
    """tp=8 sharded vocab: loss and grads match the unsharded dense CE."""
    tp = 8
    n, v, h = 16, 8 * 16, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (n, h), jnp.float32) * 0.5
    w = jax.random.normal(ks[1], (v, h), jnp.float32) * 0.1
    t = jax.random.randint(ks[2], (n,), 0, v)
    mesh = build_mesh(tp=tp, pp=1, sp=1)

    from apex_tpu.transformer.tensor_parallel.mappings import (
        copy_to_tensor_model_parallel_region,
    )

    def sharded(x, w):
        def body(x, w):
            xr = copy_to_tensor_model_parallel_region(x)
            # dense local impl: pallas interpret cannot run inside shard_map
            # (VMA strictness); the custom_vjp + collectives are shared, the
            # kernel math is covered by the unsharded tests above.
            loss = jnp.mean(
                _lm_head_loss(xr, w, t, TP_AXIS, 8, 8, "dense"))
            return jax.lax.psum(loss, TP_AXIS) / tp

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(), P(TP_AXIS, None)),
                             out_specs=P())(x, w)

    def dense(x, w):
        return jnp.mean(_dense_loss(x, w, t))

    lf, (dxf, dwf) = jax.jit(jax.value_and_grad(sharded, argnums=(0, 1)))(x, w)
    ld, (dxd, dwd) = jax.jit(jax.value_and_grad(dense, argnums=(0, 1)))(x, w)
    np.testing.assert_allclose(lf, ld, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dxf, dxd, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dwf, dwd, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t_max", [37, 48])
def test_dense_impl_matches_pallas_interpret_unsharded(t_max):
    """The dense local impl and the kernel impl are interchangeable —
    also for targets that belong to ANOTHER vocab shard (``t_max`` 48: local
    ids 37..47 land on the ragged last block's padded columns, whose masked
    score must not be picked; on the chip at tp=2 this made the loss 1e28)."""
    n, v, h, bn, bv = 16, 37, 128, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x2 = jax.random.normal(ks[0], (n, h), jnp.float32) * 0.5
    w = jax.random.normal(ks[1], (v, h), jnp.float32) * 0.1
    t = jax.random.randint(ks[2], (n,), 0, t_max)
    if t_max > v:
        t = t.at[:4].set(jnp.arange(v, v + 4))  # surely some out of shard

    def f(impl):
        def loss(x2, w):
            return jnp.mean(_lm_head_loss(x2, w, t, None, bn, bv, impl))
        l, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x2, w)
        return l, grads

    lp, (dxp, dwp) = f("pallas_interpret")
    ld, (dxd, dwd) = f("dense")
    np.testing.assert_allclose(lp, ld, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dxp, dxd, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(dwp, dwd, rtol=1e-4, atol=1e-6)


def test_public_wrapper_fallback_shapes():
    """(b, s, h) wrapper reshapes and falls back off-TPU."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (2, 8, 32))
    w = jax.random.normal(ks[1], (20, 32)) * 0.2
    t = jax.random.randint(ks[2], (2, 8), 0, 20)
    loss = lm_head_loss(x, w, t)
    assert loss.shape == (2, 8)
    np.testing.assert_allclose(
        loss.reshape(-1), _dense_loss(x.reshape(-1, 32), w, t.reshape(-1)),
        rtol=1e-5, atol=1e-6)
