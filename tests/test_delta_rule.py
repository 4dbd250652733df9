"""The gated delta rule in chunked form (``ops/delta_rule.py``) against the
recurrence token by token, forward and gradients, where the write strength
passes 1 and where the decay is strong; and the two small ops beside it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import delta_rule
from apex_tpu.ops.delta_rule import (
    causal_conv1d,
    gated_delta_rule,
    gated_delta_rule_reference,
    gated_rms_norm,
    l2_normalize,
)

B, T, H, DK, DV = 2, 128, 2, 16, 32


def _inputs(regime: str, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = l2_normalize(jax.random.normal(ks[0], (B, T, H, DK))) * DK ** -0.5
    k = l2_normalize(jax.random.normal(ks[1], (B, T, H, DK)))
    v = jax.random.normal(ks[2], (B, T, H, DV))
    # beta_above_1: most write strengths in (1, 2), decay mild;
    # strong_decay: alpha about e^-4 a token, so a chunk's decay underflows
    shift = {"beta_above_1": (2.0, -3.0), "strong_decay": (0.0, 4.0)}[regime]
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H)) + shift[0])
    g = -jax.nn.softplus(jax.random.normal(ks[4], (B, T, H)) + shift[1])
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("regime", ["beta_above_1", "strong_decay"])
def test_chunked_forward_equals_the_recurrence(regime, chunk):
    args = _inputs(regime)
    if regime == "beta_above_1":
        assert float(jnp.mean(args[4] > 1.0)) > 0.8
    else:
        assert float(jnp.sum(args[3].reshape(B, T // chunk, chunk, H), axis=2).max()) < -40
    want = gated_delta_rule_reference(*args)
    got = gated_delta_rule(*args, chunk=chunk)
    assert got.shape == (B, T, H, DV) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-5)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("regime", ["beta_above_1", "strong_decay"])
def test_chunked_gradients_equal_the_recurrences(regime, chunk):
    args = _inputs(regime, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, DV))
    loss = lambda fn: lambda *a: jnp.sum(w * fn(*a))
    want = jax.grad(loss(gated_delta_rule_reference), argnums=range(5))(*args)
    got = jax.grad(loss(lambda *a: gated_delta_rule(*a, chunk=chunk)), argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, rtol=1e-4, err_msg=name)


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused():
    q, k, v, g, beta = (a[:, :100] for a in _inputs("beta_above_1"))
    with pytest.raises(ValueError, match=r"time \(100\) is not a multiple of the chunk \(64\)"):
        gated_delta_rule(q, k, v, g, beta, chunk=64)


def test_causal_conv_meets_the_current_token_with_its_last_tap():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    want = np.zeros((2, 9, 3), np.float32)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(w[j]) * np.asarray(x[:, t - 3 + j])
    np.testing.assert_allclose(causal_conv1d(x, w), want, atol=1e-6)
    # causal: a later token changes no earlier output
    y2 = causal_conv1d(x.at[:, 5].add(1.0), w)
    np.testing.assert_array_equal(y2[:, :5], causal_conv1d(x, w)[:, :5])


def test_gated_rms_norm_is_the_norm_times_the_weight_times_silu_of_the_gate():
    o = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 32))
    gate = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3, 32)).astype(jnp.bfloat16)
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    o64, g64 = np.asarray(o, np.float64), np.asarray(gate, np.float64)
    want = (o64 / np.sqrt((o64 ** 2).mean(-1, keepdims=True) + 1e-6) * np.asarray(w, np.float64)
            * g64 / (1 + np.exp(-g64)))
    got = gated_rms_norm(o, gate, w, 1e-6)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=0.02, rtol=0.01)


_PER_HEAD = 8 * T * (6 * DK + 5 * DV + 3 * 16 + DK * DV // 16 + 2)    # bytes, chunk 16


@pytest.mark.parametrize("budget, plan", [(1 << 40, (B, H)), (3 * _PER_HEAD, (1, H)),
                                          (1, (1, 1))])
def test_a_block_of_rows_and_heads_at_a_time_gives_the_same_output_and_gradients(
        monkeypatch, budget, plan):
    """Whatever the byte budget cuts the call into, the result is that of all
    rows and heads at once (``_chunked``)."""
    args = _inputs("beta_above_1", seed=2)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, DV))
    monkeypatch.setattr(delta_rule, "_BLOCK_BYTES", budget)
    assert delta_rule._block_plan(B, T, H, DK, DV, 16) == plan
    run = lambda fn: jax.value_and_grad(
        lambda *a: jnp.sum(w * fn(*a, 16)), argnums=range(5))(*args)
    (want, want_g), (got, got_g) = run(delta_rule._chunked), run(gated_delta_rule)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=1e-6 * float(jnp.max(jnp.abs(b))), rtol=1e-5)


@pytest.mark.parametrize("shape, plan", [
    ((2, 8192, 30, 96, 192, 64), (1, 10)),      # the hybrid cell: 132 MB a head
    ((16, 1024, 30, 96, 192, 64), (2, 30)),     # short rows: whole rows, two at a time
    ((1, 65536, 30, 96, 192, 64), (1, 1)),      # the extended context: a head at a time
    ((3, 8192, 7, 96, 192, 64), (1, 7)),        # divisors only: 7 heads fit, 2 rows do not
])
def test_the_block_plan_follows_the_shapes_and_the_byte_budget(shape, plan):
    assert delta_rule._block_plan(*shape) == plan
    rows, heads = plan
    b, t, h, dk, dv, chunk = shape
    per_head = 8 * t * (6 * dk + 5 * dv + 3 * chunk + dk * dv // chunk + 2)
    assert rows * heads * per_head <= delta_rule._BLOCK_BYTES or (rows, heads) == (1, 1)
