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


# ---------------------------------------------------------------------------
# rope_scaling (YaRN) and interleaved pairs: DeepSeek-V2-Lite's published keys

from apex_tpu.ops.rope import RopeScaling, rotary_inv_freq, rotate_pairs

V2_LITE = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
           "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "type": "yarn"}


def test_yarn_at_the_published_numbers():
    """low 10, high 23, m 1.26080, scale 0.114721, cos and sin times 1.0."""
    scaling = RopeScaling.from_config(V2_LITE)
    assert scaling.ramp_bounds(64, 10000.0) == (10, 23)
    assert scaling.softmax_mscale == pytest.approx(1.26080, abs=5e-6)
    assert 192 ** -0.5 * scaling.softmax_mscale ** 2 == pytest.approx(0.114721, abs=5e-7)
    assert scaling.cos_sin_scale == 1.0
    got = np.asarray(rotary_inv_freq(64, 10000.0, scaling), np.float64)
    i = np.arange(32, dtype=np.float64)
    f = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    np.testing.assert_allclose(got, f * (1 - ramp) + f / 40 * ramp, rtol=2e-6)
    np.testing.assert_allclose(got[:11], f[:11], rtol=2e-6)          # fast pairs: as they were
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=2e-6)     # slow pairs: stretched 40 times


def test_no_rope_scaling_is_bit_for_bit_as_before():
    """The frequencies, the angles and the rotation with no ``rope_scaling``
    are today's expressions, evaluated in today's order."""
    for d, theta in ((64, 1e4), (128, 1e6)):
        want = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        np.testing.assert_array_equal(rotary_inv_freq(d, theta), want)
        np.testing.assert_array_equal(rotary_inv_freq(d, theta, None), want)
        x = jax.random.normal(jax.random.PRNGKey(d), (2, 3, 24, d), jnp.float32)
        positions = jnp.arange(24)
        ang = positions.astype(jnp.float32)[:, None] * want
        ang = jnp.concatenate([ang, ang], axis=-1)
        plain = x * jnp.cos(ang) + rotate_half(x) * (1.0 * jnp.sin(ang))
        np.testing.assert_array_equal(apply_rotary(x, positions, theta), plain)
        np.testing.assert_array_equal(apply_rotary(x, positions, theta, None, False), plain)
    assert RopeScaling.from_config(None) is None
    with pytest.raises(NotImplementedError, match="linear"):
        RopeScaling.from_config({"type": "linear", "factor": 2})


def _complex_pairs(x, positions, inv_freq):
    """(x[2i] + i x[2i+1]) exp(i position inv_freq_i), neighbours as real and
    imaginary parts, the layout kept."""
    x = np.asarray(x, np.float64)
    z = x[..., 0::2] + 1j * x[..., 1::2]
    # the angle as the op forms it, a float32 product (at 16 k positions its
    # rounding is 1e-3 rad); its sine and cosine exactly
    ang = (np.asarray(positions, np.float32)[..., None] * np.asarray(inv_freq, np.float32))
    out = z * np.exp(1j * ang.astype(np.float64))
    return np.stack([out.real, out.imag], axis=-1).reshape(x.shape)


@pytest.mark.parametrize("scaled", [False, True])
def test_interleaved_pairs_equal_the_complex_form(scaled):
    scaling = RopeScaling.from_config(V2_LITE) if scaled else None
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 40, 64), jnp.float32)
    positions = jnp.arange(40) * 400            # up to 16 k: where YaRN's frequencies differ
    inv = np.asarray(rotary_inv_freq(64, 1e4, scaling))
    got = apply_rotary(x, positions, 1e4, scaling, True)
    np.testing.assert_allclose(got, _complex_pairs(x, positions, inv), atol=2e-4)
    if scaled:      # and they do differ from the plain ones there
        plain = apply_rotary(x, positions, 1e4, None, True)
        assert float(jnp.abs(got - plain).max()) > 0.5


def test_interleaved_is_the_half_form_on_permuted_columns():
    """The published code moves a pair's halves apart and applies
    ``rotate_half``: the same rotation, the columns permuted."""
    x = jax.random.normal(jax.random.PRNGKey(10), (5, 24, 16), jnp.float32)
    positions = jnp.arange(24)
    apart = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = apply_rotary(apart, positions, 1e4)
    back = jnp.stack([half[..., :8], half[..., 8:]], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(apply_rotary(x, positions, 1e4, None, True), back, atol=1e-6)
    np.testing.assert_array_equal(rotate_pairs(jnp.arange(6.0)), [-1, 0, -3, 2, -5, 4])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_backward_of_the_scaled_interleaved_rotation_is_the_opposite_one(dtype):
    scaling = RopeScaling(factor=40, original_max_position_embeddings=32, mscale=1.0,
                          mscale_all_dim=0.5)          # cos and sin times 1.156: not orthogonal
    assert scaling.cos_sin_scale != 1.0
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 2, 24, 16), jnp.float32).astype(dtype)
    positions = jnp.arange(24) * 7

    def plain(x):
        cos, sin = rotary_angles(positions, 16, 1e4, scaling, True)
        x32 = x.astype(jnp.float32)
        return (x32 * cos + rotate_pairs(x32) * sin).astype(x.dtype)

    loss = lambda f: lambda x: jnp.sum(jnp.sin(f(x).astype(jnp.float32)))
    got = jax.grad(loss(lambda x: apply_rotary(x, positions, 1e4, scaling, True)))(x)
    want = jax.grad(loss(plain))(x)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=1e-6 if dtype == jnp.float32 else 2e-2)
