"""The gated delta rule in chunked form (``ops/delta_rule.py``) against the
recurrence token by token, forward and gradients, where the write strength
passes 1 and where the decay is strong: XLA's chunked form on the path the CPU
takes, the Pallas kernels through the interpreter; and the two small ops
beside it."""
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


def _inputs(regime: str, seed: int = 0, T: int = T):
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


def _kernels(chunk):
    return lambda *a: delta_rule._kernels(*a, chunk, interpret=True)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("regime", ["beta_above_1", "strong_decay"])
def test_kernels_forward_equals_the_recurrence(regime, chunk):
    args = _inputs(regime)
    want = gated_delta_rule_reference(*args)
    got = _kernels(chunk)(*args)
    assert got.shape == (B, T, H, DV) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-5)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("regime", ["beta_above_1", "strong_decay"])
def test_kernels_gradients_equal_the_recurrences(regime, chunk):
    """The backward kernel's own cotangents (nothing is differentiated
    through) against autodiff of the recurrence."""
    args = _inputs(regime, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, DV))
    loss = lambda fn: lambda *a: jnp.sum(w * fn(*a))
    want = jax.grad(loss(gated_delta_rule_reference), argnums=range(5))(*args)
    got = jax.grad(loss(_kernels(chunk)), argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, rtol=1e-4, err_msg=name)


def test_kernels_at_the_cells_head_sizes_in_bfloat16():
    """d_k 96, d_v 192, chunk 64, q, k, v in bfloat16 widened inside, three
    blocks of chunks a head: ``o`` and the cotangents of q, k, v come back on
    bfloat16's grid (one step of it is the limit there); those of g and beta
    are float32 and hold the float32 limits."""
    b, t, h, dk, dv = 1, 24 * 64, 2, 96, 192
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    bf = lambda x: x.astype(jnp.bfloat16)
    q = bf(l2_normalize(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5)
    k = bf(l2_normalize(jax.random.normal(ks[1], (b, t, h, dk))))
    v = bf(jax.random.normal(ks[2], (b, t, h, dv)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h)) + 1.0)
    g = -jax.nn.softplus(jax.random.normal(ks[4], (b, t, h)) - 3.0)
    w = bf(jax.random.normal(ks[5], (b, t, h, dv))).astype(jnp.float32)
    assert delta_rule._kernels_take(q, k, v, 64)
    run = lambda fn: jax.value_and_grad(lambda *a: jnp.sum(w * fn(*a)), argnums=range(5))
    out = _kernels(64)(q, k, v, g, beta)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32),
                               gated_delta_rule_reference(q, k, v, g, beta),
                               atol=2e-3, rtol=2 ** -8)
    _, got = run(lambda *a: _kernels(64)(*a).astype(jnp.float32))(q, k, v, g, beta)
    _, want = run(gated_delta_rule_reference)(q, k, v, g, beta)
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        if a.dtype == jnp.bfloat16:
            np.testing.assert_allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                                       atol=2 ** -8 * scale, rtol=2 ** -7, err_msg=name)
        else:
            np.testing.assert_allclose(a, b, atol=2e-5 * scale, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("chunk, tokens, dtype", [
    (64, 128, "float32"),       # the cell's form: a pair of chunks a tile
    (64, 192, "float32"),       # an odd count: the last pair's second chunk is padding
    (64, 64, "float32"),        # one chunk: half its tile is padding
    (128, 256, "float32"),      # a chunk that fills the tile alone
    (32, 96, "float32"),        # four chunks a tile, three of them tokens
    (16, 128, "float32"),       # eight chunks a tile
    (32, 128, "bfloat16"),
    (64, 2048, "float32"),      # two grid steps of eight spans: the state carried across
    (64, 1152, "float32"),      # nine spans: two grid steps of five, one span of padding
])
def test_the_kernels_and_the_chunked_form_in_xla_agree(chunk, tokens, dtype):
    """The two forms of one arithmetic, output and gradients, at every shape
    of tile the dispatch accepts (one chunk a tile, two, four, eight),
    on sequences padded to whole tiles and whole grid steps."""
    args = _inputs("beta_above_1", seed=2, T=tokens)
    args = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    assert delta_rule._kernels_take(*args[:3], chunk)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape).astype(dtype)

    def run(fn):    # o, and the gradients of sum(w o)
        o, vjp = jax.vjp(fn, *args)
        return (o,) + vjp(w)

    want, got = run(lambda *a: delta_rule._chunked(*a, chunk)), run(_kernels(chunk))
    # bfloat16: both forms round float32 results once, a unit apart at most
    rtol = 1e-5 if dtype == "float32" else 2 ** -7
    for a, b in zip(got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        np.testing.assert_allclose(a, b, atol=rtol * float(jnp.max(jnp.abs(b))), rtol=rtol)


def test_the_plan_pads_a_sequence_instead_of_refusing_it():
    """(chunks a span, spans a grid step, grid steps): the cell's 8,192 tokens
    go into eight steps of eight pairs with nothing added; other lengths get
    the fewest steps of at most eight spans, dealt evenly."""
    assert delta_rule._plan(8192, 64) == (2, 8, 8)
    assert delta_rule._plan(2560, 64) == (2, 7, 3)      # 20 pairs: 21 with one of padding
    assert delta_rule._plan(3584, 64) == (2, 7, 4)      # 28 pairs: none
    assert delta_rule._plan(8704, 64) == (2, 8, 9)      # 68 pairs: 72
    assert delta_rule._plan(8256, 64) == (2, 8, 9)      # 129 chunks: 65 pairs
    assert delta_rule._plan(64, 64) == (2, 1, 1)        # a tile is 128 tokens wide
    assert delta_rule._plan(1024, 128) == (1, 8, 1)
    assert delta_rule._plan(2048, 16) == (8, 8, 2)      # 1,024 tokens a step at most


def test_off_the_chip_the_call_takes_the_chunked_form_in_xla():
    """No argument chooses: the CPU has no compiled backend, so the public
    entry point traces no kernel; and what the kernels tile is read from the
    call (a chunk of whole sublane tiles of the operands' type, head sizes of
    whole tiles, one type), never from its length."""
    args = _inputs("beta_above_1")
    assert "pallas_call" not in str(jax.make_jaxpr(gated_delta_rule)(*args))
    assert "pallas_call" in str(jax.make_jaxpr(_kernels(64))(*args))
    q, k, v = args[:3]
    bf = lambda x: x.astype(jnp.bfloat16)
    for chunk in (16, 32, 64, 128):
        assert delta_rule._kernels_take(q, k, v, chunk)
        assert delta_rule._kernels_take(bf(q), bf(k), bf(v), chunk)
    for chunk in (8, 24, 48, 256):      # no whole number of them is a tile
        assert not delta_rule._kernels_take(q, k, v, chunk)
    assert not delta_rule._kernels_take(q[..., :12], k[..., :12], v, 16)
    assert not delta_rule._kernels_take(bf(q)[..., :8], bf(k)[..., :8], bf(v), 16)
    assert not delta_rule._kernels_take(q, k, bf(v), 16)
