"""Rotary position embedding (``ops/rope.py``): the ``rotate_half`` form
against the complex product it writes out, its backward (a ``custom_vjp``:
the rotation by the opposite angle) against autodiff of the plain form."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops.rope import apply_rotary, rotary_angles, rotate_half


def _complex_form(x, positions, theta):
    """(x1 + i x2) exp(i position theta^(-2j/d)), the halves as real and
    imaginary parts."""
    d = x.shape[-1]
    z = np.asarray(x[..., :d // 2], np.float64) + 1j * np.asarray(x[..., d // 2:], np.float64)
    freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    out = z * np.exp(1j * np.asarray(positions, np.float64)[..., None] * freq)
    return np.concatenate([out.real, out.imag], axis=-1)


@pytest.mark.parametrize("theta", [1e4, 1e6])
@pytest.mark.parametrize("d", [8, 64, 128])
def test_rotation_equals_the_complex_form(theta, d):
    x = jax.random.normal(jax.random.PRNGKey(d), (2, 3, 40, d), jnp.float32)
    positions = jnp.arange(40) % 20          # both halves of a doubled sequence
    got = apply_rotary(x, positions, theta)
    np.testing.assert_allclose(got, _complex_form(x, positions, theta), atol=2e-5)


def test_rotation_keeps_each_pairs_length_and_position_zero_is_identity():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 32), jnp.float32)
    y = apply_rotary(x, jnp.arange(16), 1e6)
    pair = lambda a: a[..., :16] ** 2 + a[..., 16:] ** 2
    np.testing.assert_allclose(pair(y), pair(x), rtol=1e-5)
    np.testing.assert_array_equal(y[:, 0], x[:, 0])


def test_scores_depend_on_the_distance_alone():
    """q_m . k_n after the rotation is a function of m - n."""
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 64), jnp.float32)
    dot = lambda m, n: float(jnp.sum(apply_rotary(q, jnp.array([m]), 1e4)
                                     * apply_rotary(k, jnp.array([n]), 1e4)))
    assert abs(dot(5, 2) - dot(103, 100)) < 1e-3
    assert abs(dot(5, 2) - dot(2, 5)) > 1e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_backward_is_the_opposite_rotation(dtype):
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 2, 24, 16), jnp.float32).astype(dtype)
    positions = jnp.arange(24)

    def plain(x):
        cos, sin = rotary_angles(positions, 16, 1e6)
        x32 = x.astype(jnp.float32)
        return (x32 * cos + rotate_half(x32) * sin).astype(x.dtype)

    loss = lambda f: lambda x: jnp.sum(jnp.sin(f(x).astype(jnp.float32)))
    got = jax.grad(loss(lambda x: apply_rotary(x, positions, 1e6)))(x)
    want = jax.grad(loss(plain))(x)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=1e-6 if dtype == jnp.float32 else 2e-2)


def test_odd_width_is_refused():
    with pytest.raises(ValueError, match="even width"):
        apply_rotary(jnp.zeros((4, 7)), jnp.arange(4))
