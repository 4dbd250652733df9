"""FusedAdam — Adam/AdamW with the reference's exact update math.

Reference: ``apex/optimizers/fused_adam.py:4-165`` (python driver grouping
params by dtype and launching ``multi_tensor_adam``) and the kernel math in
``csrc/multi_tensor_adam.cu:24-140``:

ADAM_MODE_0 (adamw / decoupled decay)::

    m = b1*m + (1-b1)*g
    v = b2*v + (1-b2)*g*g
    mhat = m / (1 - b1^t)        (when bias_correction)
    vhat = v / (1 - b2^t)
    p  -= lr * (mhat / (sqrt(vhat) + eps) + weight_decay * p)

ADAM_MODE_1 (classic adam / L2 regularization)::

    g  += weight_decay * p       (before the moments)
    ... same moment update, no decay term in the step

On TPU the whole pytree update is one jitted program — the equivalent of the
single chunked CUDA launch. State (m, v, step) is an explicit pytree and is
kept in fp32 regardless of param dtype (the kernel stores fp32 moments too).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

from apex_tpu.optimizers._common import Schedule, tree_map, value_at


class FusedAdamState(NamedTuple):
    count: jnp.ndarray  # i32 step counter ("step" in the reference state)
    mu: Any  # first moments, fp32
    nu: Any  # second moments, fp32


class FusedAdamTransformation(optax.GradientTransformation):
    """``(init, update)`` as optax has them, and beside them ``step(grads,
    state, params) -> (params, state)``: the same update applied, each of g,
    m, v and p read once and m, v and p written once."""

    def __new__(cls, init, update, step):
        self = super().__new__(cls, init, update)
        self.step = step
        return self


def FusedAdam(
    lr: Schedule = 1e-3,
    bias_correction: bool = True,
    betas: Tuple[float, float] = (0.9, 0.999),
    eps: float = 1e-8,
    adam_w_mode: bool = True,
    weight_decay: float = 0.0,
    amsgrad: bool = False,
    capturable: bool = True,  # always "capturable": everything lives on device
) -> FusedAdamTransformation:
    """Build the transform (ref ``fused_adam.py:4`` constructor signature;
    ``step`` at ``:92``). ``amsgrad`` is unsupported, as in the reference
    (``fused_adam.py:77-78`` raises).

    ``update`` keeps optax's contract and returns the updates. ``step`` is
    the reference's own form, parameters in and parameters out: a leaf's
    whole tail and ``p - lr*u`` are one elementwise function of g, m, v and
    p in the shapes and types they have, so that under ``jit`` with params
    and state donated XLA emits one fusion a leaf that writes m, v and p
    where they were. The parameter is rounded once, from float32."""
    if amsgrad:
        raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
    b1, b2 = betas

    def init(params):
        # zeros_like keeps each leaf's sharding: moments live where their
        # parameter does, not on the default device
        zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return FusedAdamState(
            count=jnp.zeros((), jnp.int32),
            mu=tree_map(zeros, params),
            nu=tree_map(zeros, params),
        )

    def tails(grads, state, params, applied: bool):
        """Every leaf's tail: ``-lr*u``, or ``p - lr*u`` where ``applied``,
        in the leaf's type, and the state with the new moments."""
        if params is None:
            raise ValueError("FusedAdam requires params in update()")
        count = state.count + 1
        step_lr = value_at(lr, count)
        t = count.astype(jnp.float32)
        # bias corrections computed once per step, scalar (ref fused_adam.py:106-112)
        c1 = 1.0 - jnp.power(b1, t) if bias_correction else jnp.asarray(1.0)
        c2 = 1.0 - jnp.power(b2, t) if bias_correction else jnp.asarray(1.0)

        def leaf(g, p, m, v):
            g = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            if not adam_w_mode and weight_decay != 0.0:
                g = g + weight_decay * p32  # ADAM_MODE_1 (multi_tensor_adam.cu:60)
            m_new = b1 * m + (1.0 - b1) * g
            v_new = b2 * v + (1.0 - b2) * g * g
            mhat = m_new / c1
            vhat = v_new / c2
            upd = mhat / (jnp.sqrt(vhat) + eps)
            if adam_w_mode and weight_decay != 0.0:
                upd = upd + weight_decay * p32  # ADAM_MODE_0 decoupled decay
            out = -step_lr * upd
            if applied:
                out = p32 + out
            return out.astype(p.dtype), m_new, v_new

        flat = tree_map(leaf, grads, params, state.mu, state.nu)
        out, mu, nu = [
            tree_map(lambda t3: t3[i], flat, is_leaf=lambda x: isinstance(x, tuple))
            for i in range(3)]
        return out, FusedAdamState(count, mu, nu)

    def update(grads, state, params):
        return tails(grads, state, params, applied=False)

    def step(grads, state, params):
        return tails(grads, state, params, applied=True)

    return FusedAdamTransformation(init, update, step)
