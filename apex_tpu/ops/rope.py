"""Rotary position embedding.

``x`` (..., seq, d) with ``d`` even is rotated pair by pair by the angle
``position * inv_freq_i``, the product of the complex number ``x1 + i x2``
with ``exp(i angle)``. Two things a model's published config decides:

* **which elements make a pair.** ``rotate_half`` form (the default; Llama,
  Qwen, the block-diffusion decoder): the pair is (``x[..., i]``,
  ``x[..., i + d/2]``)::

      out = x * cos + rotate_half(x) * sin,   rotate_half(x) = [-x2, x1]

  ``interleaved=True`` (DeepSeek-V2's latent attention): the pair is
  (``x[..., 2i]``, ``x[..., 2i + 1]``), which is how that family's weights'
  columns are laid out. The published code moves each pair's halves apart
  (``view(d/2, 2).transpose``) and then applies ``rotate_half``, leaving the
  result in the half layout; here the pairs are turned where they lie and
  the layout is kept. Queries and keys go through the same permutation
  there and none here, so every score ``q . k`` is the same.
* **the frequencies.** ``inv_freq_i = theta^(-2i/d)`` with no
  ``rope_scaling``; :class:`RopeScaling` (YaRN, as ``rope_scaling`` of type
  ``yarn`` publishes it) blends each with ``inv_freq_i / factor`` by a ramp
  over the pair's index and multiplies cos and sin by a ratio of two
  ``mscale`` terms, or by ``attention_factor`` where the config gives one
  (:func:`rotary_inv_freq`).
* **how much of a head turns.** ``rotary_dim`` (``d x
  partial_rotary_factor``): the first ``rotary_dim`` elements are rotated as
  a head of that width (pairs, frequencies and YaRN's ramp over it), the
  rest pass through. A config whose ``rope_parameters`` differ by layer type
  gives each type's entry (:func:`rope_for_layer_type`).

Angles, sines and cosines are float32 whatever ``x`` is; the result is
``x``'s type. The rotation is linear in ``x`` and orthogonal (times the
``mscale`` ratio), so its backward is the rotation by the opposite angle of
the cotangent: a ``custom_vjp`` says so, and the backward is the same one
fused pass as the forward (autodiff of the split and the concatenation
materialises float32 halves instead). Positions take no gradient. No kernel.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """A config's ``rope_scaling`` of type ``yarn``, key for key."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    # what cos and sin are multiplied by, where the config states it
    attention_factor: Optional[float] = None

    @classmethod
    def from_config(cls, rope_scaling: Optional[Mapping]) -> Optional["RopeScaling"]:
        """``None`` for no ``rope_scaling``; another type than ``yarn`` is
        refused. The type is ``type`` or ``rope_type``."""
        if rope_scaling is None:
            return None
        kind = rope_scaling.get("type", rope_scaling.get("rope_type"))
        if kind != "yarn":
            raise NotImplementedError(
                f"rope_scaling of type {kind!r} is not written (yarn is)")
        names = [f.name for f in dataclasses.fields(cls)]
        return cls(**{k: rope_scaling[k] for k in names if k in rope_scaling})

    def ramp_bounds(self, dim: int, theta: float):
        """``(low, high)``: the pairs below ``low`` keep their frequency,
        those from ``high`` on take ``1 / factor`` of it."""
        def pair_of(turns):     # the pair that turns this often in the original length
            return (dim * math.log(self.original_max_position_embeddings
                                   / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))
        return (max(math.floor(pair_of(self.beta_fast)), 0),
                min(math.ceil(pair_of(self.beta_slow)), dim - 1))

    @property
    def cos_sin_scale(self) -> float:
        """What cos and sin are multiplied by."""
        if self.attention_factor is not None:
            return self.attention_factor
        return (_yarn_mscale(self.factor, self.mscale)
                / _yarn_mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_mscale(self) -> float:
        """``m``: a model whose config has ``mscale_all_dim`` multiplies its
        softmax scale ``d^-1/2`` by ``m * m``."""
        return _yarn_mscale(self.factor, self.mscale_all_dim)


def rope_for_layer_type(rope_parameters: Mapping, layer_type: str
                        ) -> Tuple[float, Optional[RopeScaling], float]:
    """``(theta, scaling, partial_rotary_factor)`` of one layer type's entry
    of a config's ``rope_parameters`` (``{"full_attention": {...},
    "sliding_attention": {...}}``): ``rope_type`` ``default`` is no
    scaling, ``yarn`` a :class:`RopeScaling`."""
    entry = rope_parameters[layer_type]
    kind = entry.get("rope_type", entry.get("type", "default"))
    scaling = None if kind == "default" else RopeScaling.from_config(entry)
    return (float(entry["rope_theta"]), scaling,
            float(entry.get("partial_rotary_factor", 1.0)))


def rotary_inv_freq(dim: int, theta: float = 10000.0,
                    scaling: Optional[RopeScaling] = None):
    """The ``dim / 2`` frequencies, float32. YaRN: ``f_i (1 - ramp_i) +
    (f_i / factor) ramp_i``, ``ramp_i = clip((i - low) / (high - low), 0,
    1)``."""
    if dim % 2:
        raise ValueError(f"rotary embedding needs an even width, got {dim}")
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if scaling is None:
        return inv_freq
    low, high = scaling.ramp_bounds(dim, theta)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv_freq * (1.0 - ramp) + (inv_freq / scaling.factor) * ramp


def rotary_angles(positions, dim: int, theta: float = 10000.0,
                  scaling: Optional[RopeScaling] = None,
                  interleaved: bool = False):
    """``(cos, sin)`` of shape ``positions.shape + (dim,)``, float32, each
    frequency twice: once for either half, or (``interleaved``) for the two
    neighbours of a pair."""
    inv_freq = rotary_inv_freq(dim, theta, scaling)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    ang = (jnp.repeat(ang, 2, axis=-1) if interleaved
           else jnp.concatenate([ang, ang], axis=-1))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None and scaling.cos_sin_scale != 1.0:
        cos, sin = cos * scaling.cos_sin_scale, sin * scaling.cos_sin_scale
    return cos, sin


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rotate_pairs(x):
    """``rotate_half`` for neighbours: ``out[2i] = -x[2i + 1]``,
    ``out[2i + 1] = x[2i]``, as two shifts along the width and a select (no
    reshape to a minor dimension of two)."""
    even = jnp.arange(x.shape[-1]) % 2 == 0
    return jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))


def _rotate(x, positions, theta, scaling, interleaved, sign, rotary_dim=None):
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        turned = _rotate(x[..., :rotary_dim], positions, theta, scaling,
                         interleaved, sign)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    cos, sin = rotary_angles(positions, x.shape[-1], theta, scaling,
                             interleaved)
    x32 = x.astype(jnp.float32)
    turn = rotate_pairs if interleaved else rotate_half
    return (x32 * cos + turn(x32) * (sign * sin)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def apply_rotary(x, positions, theta: float = 10000.0,
                 scaling: Optional[RopeScaling] = None,
                 interleaved: bool = False, rotary_dim: Optional[int] = None):
    """``x`` (..., seq, d) rotated by ``positions`` (seq,), or any shape that
    broadcasts against ``x.shape[:-1]``; only its first ``rotary_dim``
    elements where given, the rest passed through."""
    return _rotate(x, positions, theta, scaling, interleaved, 1.0, rotary_dim)


def _apply_rotary_fwd(x, positions, theta, scaling, interleaved, rotary_dim):
    return (_rotate(x, positions, theta, scaling, interleaved, 1.0, rotary_dim),
            positions)


def _apply_rotary_bwd(theta, scaling, interleaved, rotary_dim, positions, dy):
    return (_rotate(dy, positions, theta, scaling, interleaved, -1.0,
                    rotary_dim), None)


apply_rotary.defvjp(_apply_rotary_fwd, _apply_rotary_bwd)
