"""``transformer/moe.py:routed_experts_mlp``: softmax routing over all
experts, the share of them held here computed without drops at any imbalance,
against a dense loop over the held experts; the shares of an expert-parallel
deployment add up to the uncut layer; the layout's arithmetic."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.transformer.moe import (
    RoutedExpertsConfig,
    route_softmax_top_k,
    routed_expert_shapes,
    routed_experts_mlp,
    routing_facts,
)

T, H, F, E, K = 96, 16, 8, 8, 2


def _params(count, key=0, router_scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    shapes = routed_expert_shapes(H, F, E, count)
    p = {name: 0.3 * jax.random.normal(k, shapes[name], jnp.float32)
         for name, k in zip(("router", "w_gate", "w_up", "w_down"), ks)}
    p["router"] = p["router"] * router_scale / 0.3
    return p


def _x(key=1, n=T):
    return jax.random.normal(jax.random.PRNGKey(key), (n, H), jnp.float32)


def _dense(p, x, first, count, top_k=K):
    """Every held expert over every position, weighted where it was chosen."""
    s = jax.nn.softmax(jnp.dot(x, p["router"], precision="highest"), axis=-1)
    chosen, idx = jax.lax.top_k(s, top_k)
    w = chosen / chosen.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(count):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        out = (jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])) @ p["w_down"][e]
        y = y + we[:, None] * out
    return y


def _cfg():
    return RoutedExpertsConfig(num_experts=E, top_k=K)     # 96 positions: tiles of 8 rows


def _layer(p, x, held):
    return routed_experts_mlp(p, x, _cfg(), held)[0]


def _loads(p, x, first, count):
    idx, _ = route_softmax_top_k(x, p["router"], K)
    return np.bincount(np.asarray(idx).reshape(-1), minlength=E)[first:first + count]


@pytest.mark.parametrize("first,count", [(0, 8), (0, 2), (2, 3), (6, 2), (3, 1)])
def test_layer_equals_the_dense_loop_over_the_held_experts(first, count):
    p, x = _params(count), _x()
    with jax.default_matmul_precision("highest"):
        got, loads = routed_experts_mlp(p, x, _cfg(), (first, count))
        want = _dense(p, x, first, count)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(loads, _loads(p, x, first, count))


@pytest.mark.parametrize("bias,passes", [(0.0, 1), (3.0, 2), (30.0, 3)])
def test_gradients_equal_the_dense_loops_at_any_imbalance(bias, passes):
    """A router biased to the one held expert loads it more (25, 53 and 96
    of the 96 positions), and the pairs past the first pass's buffer of 40
    rows go through the later passes."""
    first, count = 2, 1
    p, x = _params(count, key=3), _x(4).at[:, 0].set(1.0)
    p["router"] = p["router"].at[0, first].add(bias)
    assert routing_facts(_loads(p, x, first, count), T, _cfg())["passes_run"] == passes
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda p, x: _layer(p, x, (first, count))), argnums=(0, 1))(p, x)
        want = jax.grad(loss(lambda p, x: _dense(p, x, first, count)), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_no_position_is_dropped_when_every_one_chooses_the_same_held_expert():
    """Total imbalance: the router sends every position to expert 2 (held)
    and expert 5 (not held). The first pass's buffer holds a part of the
    pairs; the later passes run the rest, and the result is exact."""
    first, count = 2, 2
    p = _params(count, key=5)
    x = _x(6).at[:, 0].set(10.0)
    p["router"] = jnp.zeros((H, E)).at[0, 2].set(5.0).at[0, 5].set(4.0)
    with jax.default_matmul_precision("highest"):
        got, loads = routed_experts_mlp(p, x, _cfg(), (first, count))
        want = _dense(p, x, first, count)
    facts = routing_facts(loads, T, _cfg())
    assert facts["pairs_held"] == T and facts["passes_run"] == 2
    assert facts["max_load_over_mean"] == pytest.approx(2.0)
    assert float(jnp.abs(want).min(axis=1).max()) > 0      # every position has a result
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """Four shares of two experts each, the router counted once (every share
    routes over all eight and normalises over both chosen): their sum is the
    layer with all eight experts."""
    whole = _params(E, key=7)
    x = _x(8)
    cfg = _cfg()
    with jax.default_matmul_precision("highest"):
        total = jnp.zeros_like(x)
        for first in range(0, E, 2):
            share = {"router": whole["router"],
                     **{n: whole[n][first:first + 2] for n in ("w_gate", "w_up", "w_down")}}
            total = total + routed_experts_mlp(share, x, cfg, (first, 2))[0]
        uncut = _dense(whole, x, 0, E)
    np.testing.assert_allclose(total, uncut, atol=3e-5)


def test_weights_are_normalised_over_all_the_chosen_not_over_the_held():
    p, x = _params(1, key=9), _x(10)
    idx, w = route_softmax_top_k(x, p["router"], K)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    held_only = jnp.sum(jnp.where(idx == 3, w, 0.0), -1)
    assert float(held_only.max()) < 1.0           # the other choice keeps its part


def test_the_router_is_float32_whatever_the_models_type():
    """bfloat16 activations and router: the product, the softmax and the
    weights are float32 (the logits are never rounded to bfloat16), which the
    benchmark's limits do not hold (PERF.md §7) and this test does."""
    x = _x(13, n=512).astype(jnp.bfloat16)
    router = _params(1, key=14)["router"].astype(jnp.bfloat16)
    idx, w = route_softmax_top_k(x, router, K)
    assert w.dtype == jnp.float32
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    top = np.sort(s, axis=-1)[:, ::-1][:, :K]
    np.testing.assert_allclose(w, top / top.sum(-1, keepdims=True), atol=2e-6)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(np.argsort(-logits, -1)[:, :K], -1))
    rounded = np.asarray(jnp.asarray(logits, jnp.bfloat16), np.float64)
    s = np.exp(rounded - rounded.max(-1, keepdims=True))
    low = np.sort(s, axis=-1)[:, ::-1][:, :K]
    assert np.abs(low / low.sum(-1, keepdims=True) - np.asarray(w)).max() > 1e-4


def test_leading_axes_and_bfloat16():
    first, count = 0, 4
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _params(count, key=11))
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 48, H), jnp.bfloat16)
    got = _layer(p, x, (first, count))
    assert got.shape == x.shape and got.dtype == jnp.bfloat16
    want = _dense(jax.tree.map(lambda a: a.astype(jnp.float32), p),
                  x.reshape(-1, H).astype(jnp.float32), first, count)
    np.testing.assert_allclose(np.asarray(got, np.float32).reshape(-1, H), want, atol=6e-2)


def test_the_layouts_arithmetic_at_the_cells_size():
    cfg = RoutedExpertsConfig()         # 128 experts, 8 a token
    tokens, count = 32768, 32
    assert cfg.tile_rows(tokens) == 1024                      # half of the mean load of 2,048
    assert cfg.rows_per_pass(tokens, count) == 98304          # 1.5 x 65,536
    assert cfg.worst_rows(tokens, count) == 294912            # 262,144 + 32 x 1,023, tiled
    assert cfg.passes(tokens, count) == 3
    # a uniform router: 2,048 pairs an expert, every span two whole tiles
    facts = routing_facts(np.full(count, 2048), tokens, cfg)
    assert facts == {"pairs_held": 65536, "pairs_uniform": 65536.0, "max_load_over_mean": 1.0,
                     "tiled_rows": 65536, "padding_rows": 32768, "passes_run": 1}
    # one pair more on every expert takes a third tile each, all in the one pass
    assert routing_facts(np.full(count, 2049), tokens, cfg)["tiled_rows"] == 98304
    # the tile follows the mean load down to 8 rows and never passes 1,024
    assert [cfg.tile_rows(t) for t in (16, 512, 4096, 2 ** 20)] == [8, 16, 128, 1024]


def test_the_counters_are_the_contracts():
    from apex_tpu.monitor.trace import ROUTING_COUNTERS
    facts = routing_facts(np.zeros(2, np.int64), 4, _cfg())
    assert set(ROUTING_COUNTERS) - {"masked_positions"} == set(facts)


def test_each_experts_rows_start_on_a_tile():
    from apex_tpu.transformer.moe import _layout
    key = jnp.asarray(np.random.default_rng(0).integers(0, 5, size=200), jnp.int32)
    order, rank, sizes, first_place, first_row, spans = _layout(key, 4, 8)
    assert np.all(np.asarray(first_row) % 8 == 0) and np.all(np.asarray(spans) % 8 == 0)
    rank, key = np.asarray(rank), np.asarray(key)
    for e in range(4):
        rows = np.sort(rank[key == e])
        np.testing.assert_array_equal(rows, int(first_row[e]) + np.arange(int(sizes[e])))
    assert np.all(rank[key == 4] > 10 ** 8)       # not held: no row
