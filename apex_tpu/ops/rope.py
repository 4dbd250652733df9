"""Rotary position embedding, ``rotate_half`` form.

``x`` (..., seq, d) with ``d`` even is rotated pair by pair, the pair being
(``x[..., i]``, ``x[..., i + d/2]``), by the angle ``position * theta^(-2i/d)``:

    out = x * cos + rotate_half(x) * sin,   rotate_half(x) = [-x2, x1]

which is the product of the complex number ``x1 + i x2`` with ``exp(i angle)``
written out over the two halves. Angles, sines and cosines are float32
whatever ``x`` is; the result is ``x``'s type. The rotation is linear in
``x`` and orthogonal, so its backward is the rotation by the opposite angle
of the cotangent: a ``custom_vjp`` says so, and the backward is the same one
fused pass as the forward (autodiff of the split and the concatenation
materialises float32 halves instead). Positions take no gradient. No kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def rotary_angles(positions, dim: int, theta: float = 10000.0):
    """``(cos, sin)`` of shape ``positions.shape + (dim,)``, float32, each
    frequency twice (once for either half)."""
    if dim % 2:
        raise ValueError(f"rotary embedding needs an even width, got {dim}")
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotate(x, positions, theta, sign):
    cos, sin = rotary_angles(positions, x.shape[-1], theta)
    x32 = x.astype(jnp.float32)
    return (x32 * cos + rotate_half(x32) * (sign * sin)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def apply_rotary(x, positions, theta: float = 10000.0):
    """``x`` (..., seq, d) rotated by ``positions`` (seq,), or any shape that
    broadcasts against ``x.shape[:-1]``."""
    return _rotate(x, positions, theta, 1.0)


def _apply_rotary_fwd(x, positions, theta):
    return _rotate(x, positions, theta, 1.0), positions


def _apply_rotary_bwd(theta, positions, dy):
    return _rotate(dy, positions, theta, -1.0), None


apply_rotary.defvjp(_apply_rotary_fwd, _apply_rotary_bwd)
