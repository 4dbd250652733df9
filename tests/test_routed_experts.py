"""``transformer/moe.py:routed_experts_mlp``: softmax routing over all
experts, the share of them held here computed without drops at any imbalance,
against a dense loop over the held experts; the shares of an expert-parallel
deployment add up to the uncut layer; the layout's arithmetic."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.transformer import moe
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
    idx, _, _ = route_softmax_top_k(x, p["router"], K)
    return np.bincount(np.asarray(idx).reshape(-1), minlength=E)[first:first + count]


@pytest.mark.parametrize("first,count", [(0, 8), (0, 2), (2, 3), (6, 2), (3, 1)])
def test_layer_equals_the_dense_loop_over_the_held_experts(first, count):
    p, x = _params(count), _x()
    with jax.default_matmul_precision("highest"):
        got, counted, _ = routed_experts_mlp(p, x, _cfg(), (first, count))
        want = _dense(p, x, first, count)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(counted["expert_loads"], _loads(p, x, first, count))


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


def test_the_passes_past_those_written_out_loop_and_equal_the_dense_loops():
    """32 experts, one held: a pass holds 16 rows and the worst case takes 7
    passes, the last four as turns of one loop. A router biased to the held
    expert sends it all 96 positions, six passes' worth; the layer and its
    gradients are the dense loop's."""
    cfg, e, first = RoutedExpertsConfig(num_experts=32, top_k=K), 32, 2
    assert (cfg.passes(T, 1), cfg.rows_per_pass(T, 1)) == (7, 16)
    assert cfg.passes(T, 1) > moe.PASSES_WRITTEN_OUT
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    shapes = routed_expert_shapes(H, F, e, 1)
    p = {name: 0.3 * jax.random.normal(k, shapes[name], jnp.float32)
         for name, k in zip(("router", "w_gate", "w_up", "w_down"), ks)}
    p["router"] = p["router"].at[0, first].add(30.0)
    x = _x(4).at[:, 0].set(1.0)

    def dense(p, x):
        s = jax.nn.softmax(jnp.dot(x, p["router"], precision="highest"), axis=-1)
        chosen, idx = jax.lax.top_k(s, K)
        w = jnp.sum(jnp.where(idx == first, chosen / chosen.sum(-1, keepdims=True), 0.0), -1)
        out = (jax.nn.silu(x @ p["w_gate"][0]) * (x @ p["w_up"][0])) @ p["w_down"][0]
        return w[:, None] * out

    layer = lambda p, x: routed_experts_mlp(p, x, cfg, (first, 1))[0]
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    with jax.default_matmul_precision("highest"):
        _, counted, _ = routed_experts_mlp(p, x, cfg, (first, 1))
        np.testing.assert_allclose(layer(p, x), dense(p, x), atol=2e-5)
        got = jax.grad(loss(layer), argnums=(0, 1))(p, x)
        want = jax.grad(loss(dense), argnums=(0, 1))(p, x)
    assert routing_facts(counted["expert_loads"], T, cfg)["passes_run"] == 6
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
        got, counted, _ = routed_experts_mlp(p, x, _cfg(), (first, count))
        want = _dense(p, x, first, count)
    facts = routing_facts(counted["expert_loads"], T, _cfg())
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
    idx, w, _ = route_softmax_top_k(x, p["router"], K)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    held_only = jnp.sum(jnp.where(idx == 3, w, 0.0), -1)
    assert float(held_only.max()) < 1.0           # the other choice keeps its part


def test_the_router_is_float32_whatever_the_models_type():
    """bfloat16 activations and router: the product, the softmax and the
    weights are float32 (the logits are never rounded to bfloat16), which the
    benchmark's limits do not hold (PERF.md §7) and this test does."""
    x = _x(13, n=512).astype(jnp.bfloat16)
    router = _params(1, key=14)["router"].astype(jnp.bfloat16)
    idx, w, _ = route_softmax_top_k(x, router, K)
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


# -- the gathers follow the pairs held (ISSUE 34) --------------------------------------------------

def _per_place(a, rank, weight=None):
    """What ``_sum_rows`` replaces (PR 33's ``_over_places``): a scan over the places, every
    position's row gathered a place and added in float32."""
    from apex_tpu.transformer.moe import _rows_at

    def step(acc, place):
        r, j = place
        got = _rows_at(a, r).astype(jnp.float32)
        return acc + (got if weight is None else jnp.take(weight, j, axis=1)[:, None] * got), None

    acc, _ = jax.lax.scan(step, jnp.zeros((rank.shape[0], a.shape[1]), jnp.float32),
                          (rank.T, jnp.arange(rank.shape[1])))
    return acc.astype(a.dtype)


def _hand_rank(held, k, n, seed=0):
    """(positions, k) int32: position ``t`` holds ``held[t]`` places with a row among ``n`` (rows
    drawn anew for every pair), at places drawn at random; the others lie before the buffer or
    past it."""
    rng = np.random.default_rng(seed)
    rank = np.where(rng.random((len(held), k)) < 0.5, -1 - rng.integers(0, 9, (len(held), k)),
                    n + rng.integers(0, 9, (len(held), k)))
    for t, c in enumerate(held):
        places = rng.permutation(k)[:c]
        rank[t, places] = rng.integers(0, n, c)
    return jnp.asarray(rank, jnp.int32)


def _held_with(m, positions=96, seed=0):
    """Counts a position such that ``m[j]`` positions hold more than ``j`` places, shuffled."""
    held = np.zeros(positions, np.int64)
    for mj in m:
        held[:mj] += 1
    return np.random.default_rng(seed).permutation(held)


_CHUNK = 8          # _chunk_rows(96)
_STAIRS = {"every-place": [96] * 4, "no-place": [], "one-position": [96, 1],
           "a-chunk": [96, _CHUNK], "a-chunk-and-one": [96, _CHUNK + 1], "first-empty": [40, 17, 9, 8]}


@pytest.mark.parametrize("weighted", [True, False], ids=["weighted", "unweighted"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("stairs", list(_STAIRS))
def test_the_sum_over_the_places_held_equals_the_sum_over_every_place_bit_for_bit(
        stairs, dtype, weighted):
    from apex_tpu.transformer.moe import _chunk_rows, _sum_rows
    k, n = 4, 40
    assert _chunk_rows(96) == _CHUNK
    rank = _hand_rank(_held_with(_STAIRS[stairs]), k, n, seed=len(stairs))
    a = jax.random.normal(jax.random.PRNGKey(20), (n, H), jnp.float32).astype(dtype)
    w = jax.random.uniform(jax.random.PRNGKey(21), (96, k), jnp.float32) if weighted else None
    got, want = jax.jit(_sum_rows)(a, rank, w), jax.jit(_per_place)(a, rank, w)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    if stairs == "no-place":
        assert not np.asarray(got, np.float32).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_later_passes_sum_their_own_rows_inside_a_conditional_and_a_checkpoint(dtype):
    """Every position on one held expert, 96 rows over three passes of a 40-row buffer, each pass
    as ``routed_experts_mlp`` runs its later ones: the pass's rows are ``rank - pass x 40``."""
    from apex_tpu.transformer.moe import _from_rows
    n, passes = 40, 3
    rank = jnp.stack([jnp.full((T,), -7, jnp.int32), jnp.arange(T, dtype=jnp.int32)], axis=1)
    ys = jax.random.normal(jax.random.PRNGKey(22), (passes, n, H), jnp.float32).astype(dtype)
    w = jax.random.uniform(jax.random.PRNGKey(23), (T, 2), jnp.float32)

    def pair(c):        # row r of pass c holds position c * n + r's second place, or nothing
        at = c * n + jnp.arange(n, dtype=jnp.int32)
        return jnp.where(at < T, 2 * at + 1, -1)

    def layer(sum_rows):
        def total(ys, w):
            one = jax.checkpoint(lambda c: sum_rows(jnp.take(ys, c, axis=0), rank - c * n, w, pair(c)))
            y = jnp.zeros((T, H), dtype)
            for c in range(passes):
                y = y + jax.lax.cond(c * n < T, one, lambda c: jnp.zeros((T, H), dtype), jnp.int32(c))
            return y
        return total

    new = layer(lambda ys, r, w, pair: _from_rows(ys, w, pair, r))
    old = layer(lambda ys, r, w, pair: _per_place(ys, r, w))
    np.testing.assert_array_equal(np.asarray(jax.jit(new)(ys, w), np.float32),
                                  np.asarray(jax.jit(old)(ys, w), np.float32))
    assert float(jnp.abs(jax.jit(new)(ys, w).astype(jnp.float32)).min(axis=1).min()) > 0
    # both cotangents through the three passes, against autodiff of the scan
    loss = lambda f: lambda ys, w: jnp.sum(jnp.sin(f(ys, w).astype(jnp.float32)))
    got, want = (jax.grad(loss(f), argnums=(0, 1))(ys, w) for f in (new, old))
    np.testing.assert_array_equal(np.asarray(got[0], np.float32), np.asarray(want[0], np.float32))
    np.testing.assert_allclose(got[1], want[1], rtol=1e-6, atol=1e-6 * float(jnp.abs(want[1]).max()))
    assert float(jnp.abs(got[1][:, 1]).min()) > 0 and not np.asarray(got[1][:, 0]).any()


def test_the_sum_runs_under_shard_map_each_shard_over_its_own_rows():
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.transformer.moe import _sum_rows
    dp, k, n = 2, 4, 40
    mesh = build_mesh(tp=1, pp=1, sp=1, dp=dp, devices=jax.devices()[:dp])
    ranks = jnp.concatenate([_hand_rank(_held_with([96, 30, 9], seed=s), k, n, seed=s)
                             for s in range(dp)])
    a = jax.random.normal(jax.random.PRNGKey(24), (dp * n, H), jnp.float32)
    w = jax.random.uniform(jax.random.PRNGKey(25), (dp * 96, k), jnp.float32)
    got = jax.jit(jax.shard_map(_sum_rows, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(
        a, ranks, w)
    for s in range(dp):
        want = _per_place(a[s * n:(s + 1) * n], ranks[s * 96:(s + 1) * 96], w[s * 96:(s + 1) * 96])
        np.testing.assert_array_equal(got[s * 96:(s + 1) * 96], want)


@pytest.mark.parametrize("first,count", [(0, 8), (0, 2), (2, 3), (3, 1)])
def test_the_weights_cotangent_from_the_rows_equals_the_dense_loops(first, count):
    """``dw`` by row and a gather of scalars (``_from_rows_bwd``), through the router's leaf,
    against ``jax.grad`` of the dense loop: to 1e-6 of the gradient's size."""
    p, x = _params(count, key=15), _x(16)
    loss = lambda f: lambda router: jnp.sum(jnp.sin(f({**p, "router": router}, x)))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda p, x: _layer(p, x, (first, count))))(p["router"])
        want = jax.grad(loss(lambda p, x: _dense(p, x, first, count)))(p["router"])
    assert float(jnp.abs(want).max()) > 0
    assert float(jnp.abs(got - want).max()) <= 1e-6 * float(jnp.abs(want).max())


def _rows_visited(rank, n):
    """The rows ``_sum_rows``' gathers visit, by hand: the positions by places held, most first;
    a chunk as often as its first position holds places; every position once more."""
    from apex_tpu.transformer.moe import _chunk_rows
    rank = np.asarray(rank)
    chunk = _chunk_rows(len(rank))
    held = np.sort(((rank >= 0) & (rank < n)).sum(1))[::-1]
    return int(chunk * held[::chunk].sum()) + len(rank)


@pytest.mark.parametrize("stairs", list(_STAIRS))
def test_rows_gathered_is_the_sums_own_count(stairs):
    k, n = 4, 40
    held = _held_with(_STAIRS[stairs])
    rank = _hand_rank(held, k, n)
    cfg = RoutedExpertsConfig(num_experts=E, top_k=k)
    facts = routing_facts(np.asarray([held.sum()]), 96, cfg, np.bincount(held, minlength=k + 1))
    assert facts["rows_gathered"] == _rows_visited(rank, n)
    assert facts["rows_gathered"] <= held.sum() + k * _CHUNK + 96
    if held.sum():
        assert facts["rows_gathered_over_held"] == facts["rows_gathered"] / held.sum()
    assert set(routing_facts(np.asarray([held.sum()]), 96, cfg)) == set(facts) - {
        "rows_gathered", "rows_gathered_over_held"}


@pytest.mark.parametrize("first,count", [(0, 8), (0, 2), (2, 3), (6, 2), (3, 1)])
def test_held_places_counts_the_positions_by_the_pairs_they_hold(first, count):
    p, x = _params(count), _x()
    counted = routed_experts_mlp(p, x, _cfg(), (first, count))[1]
    held_places, loads = np.asarray(counted["held_places"]), np.asarray(counted["expert_loads"])
    assert held_places.shape == (K + 1,) and held_places.dtype == np.int32
    assert held_places.sum() == T
    assert routing_facts(loads, T, _cfg())["passes_run"] == 1       # one pass holds every pair
    assert (np.arange(K + 1) * held_places).sum() == loads.sum()
    idx = np.asarray(route_softmax_top_k(x, p["router"], K)[0])
    by_hand = ((idx >= first) & (idx < first + count)).sum(1)
    np.testing.assert_array_equal(held_places, np.bincount(by_hand, minlength=K + 1))


def _gathers(jaxpr, loops=()):
    """``(loops round it, output shape)`` of every gather in ``jaxpr``, its sub-programs
    included; a loop is ``("scan", trips)`` or ``("while", None)``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            found.append((loops, eqn.outvars[0].aval.shape))
        inner = loops
        if eqn.primitive.name == "scan":
            inner = loops + (("scan", eqn.params["length"]),)
        elif eqn.primitive.name == "while":
            inner = loops + (("while", None),)
        stack = list(eqn.params.values())
        while stack:
            v = stack.pop()
            if isinstance(v, (tuple, list)):
                stack.extend(v)
            elif hasattr(v, "eqns"):
                found += _gathers(v, inner)
            elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                found += _gathers(v.jaxpr, inner)
    return found


def test_the_work_follows_the_pairs_held_not_positions_times_top_k():
    """``jax.grad`` of the layer, a quarter of the experts held: no loop of ``top_k`` trips is
    left; inside a loop a gather reads a chunk's rows, never every position's; and what the
    counters say was gathered is within a chunk a place of the pairs held and the positions."""
    from apex_tpu.transformer.moe import _chunk_rows
    t, e, k, first, count = 512, 32, 8, 8, 8
    cfg = RoutedExpertsConfig(num_experts=e, top_k=k)
    ks = jax.random.split(jax.random.PRNGKey(30), 5)
    shapes = routed_expert_shapes(H, F, e, count)
    p = {name: 0.3 * jax.random.normal(key, shapes[name], jnp.float32)
         for name, key in zip(shapes, ks)}
    x = jax.random.normal(ks[4], (t, H), jnp.float32)
    layer = lambda p, x: routed_experts_mlp(p, x, cfg, (first, count))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(jnp.sin(layer(p, x)[0])), argnums=(0, 1)))(p, x)
    gathers = _gathers(jaxpr.jaxpr)
    chunk = _chunk_rows(t)
    assert chunk == 32 and len(gathers) > 10
    in_loops = [(loops, shape) for loops, shape in gathers if loops]
    assert in_loops and all(("scan", k) not in loops for loops, _ in gathers)
    for loops, shape in in_loops:
        assert shape[0] in (chunk, 1) or len(shape) < 2, (loops, shape)
    # rows of the hidden width gathered for every position: outside every loop, and one a sum
    # (the restore) beside the router's and the layout's own
    whole = [loops for loops, shape in gathers if shape == (t, H)]
    assert whole and not any(whole)
    counted = layer(p, x)[1]
    facts = routing_facts(counted["expert_loads"], t, cfg, counted["held_places"])
    assert facts["passes_run"] == 1 and 0 < facts["pairs_held"] < t * k // 2
    assert facts["rows_gathered"] <= facts["pairs_held"] + k * chunk + t
    assert facts["rows_gathered"] < t * k // 2


# ---------------------------------------------------------------------------
# the two published keys: norm_topk_prob and routed_scaling_factor; the scores
# handed out and the balance loss a sequence

from apex_tpu.transformer.moe import sequence_balance_loss


def test_the_renormalised_path_is_bit_for_bit_the_parents():
    """``norm_topk_prob`` true, factor 1 (the defaults, the block-diffusion
    decoder's): the parent's two lines, evaluated as the parent did."""
    p, x = _params(1, key=40), _x(41)
    idx, w, scores = route_softmax_top_k(x, p["router"], K)
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    chosen, want_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_array_equal(w, chosen / jnp.sum(chosen, axis=-1, keepdims=True))
    np.testing.assert_array_equal(scores, jax.nn.softmax(logits, axis=-1))
    same = route_softmax_top_k(x, p["router"], K, True, 1.0)
    np.testing.assert_array_equal(w, same[1])
    assert RoutedExpertsConfig(E, K) == RoutedExpertsConfig(E, K, True, 1.0)


@pytest.mark.parametrize("factor", [1.0, 2.5])
def test_weights_as_scored_are_the_scores_times_the_factor(factor):
    p, x = _params(1, key=42), _x(43)
    idx, w, scores = route_softmax_top_k(x, p["router"], K, False, factor)
    np.testing.assert_allclose(w, factor * jnp.take_along_axis(scores, idx, axis=-1), rtol=1e-6)
    assert float((w.sum(-1) / factor).max()) < 1.0        # not renormalised
    np.testing.assert_allclose(scores.sum(-1), 1.0, atol=1e-6)


@pytest.mark.parametrize("factor", [1.0, 2.5])
def test_the_layer_as_scored_against_the_dense_loop(factor):
    """The layer with ``norm_topk_prob`` false: each held expert's result
    times its score as it is (times the factor), forward and gradients."""
    first, count = 2, 4
    p, x = _params(count, key=44), _x(45)
    cfg = RoutedExpertsConfig(E, K, norm_topk_prob=False, routed_scaling_factor=factor)

    def dense(p, x):
        s = jax.nn.softmax(jnp.dot(x, p["router"], precision="highest"), axis=-1)
        chosen, idx = jax.lax.top_k(s, K)
        y = jnp.zeros_like(x)
        for e in range(count):
            we = factor * jnp.sum(jnp.where(idx == first + e, chosen, 0.0), axis=-1)
            out = (jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])) @ p["w_down"][e]
            y = y + we[:, None] * out
        return y

    with jax.default_matmul_precision("highest"):
        got, _, routed = routed_experts_mlp(p, x, cfg, (first, count))
        np.testing.assert_allclose(got, dense(p, x), atol=3e-5)
        loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
        g = jax.grad(loss(lambda p, x: routed_experts_mlp(p, x, cfg, (first, count))[0]),
                     argnums=(0, 1))(p, x)
        want = jax.grad(loss(dense), argnums=(0, 1))(p, x)
    for a, e in zip(jax.tree.leaves(g), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, e, atol=1e-4)
    assert routed["scores"].shape == (T, E) and routed["scores"].dtype == jnp.float32
    assert routed["idx"].shape == (T, K)


def test_the_balance_loss_by_hand_and_its_gradient_goes_through_p_only():
    rows, length, alpha = 2, 48, 0.01
    p, x = _params(1, key=46), _x(47)
    idx, _, scores = route_softmax_top_k(x, p["router"], K, False)
    got = sequence_balance_loss(scores, idx, rows, alpha)
    s, i = np.asarray(scores, np.float64).reshape(rows, length, E), np.asarray(idx).reshape(rows, -1)
    want = 0.0
    for r in range(rows):
        f = np.bincount(i[r], minlength=E) * E / (K * length)
        want += alpha * float(np.sum(f * s[r].mean(0))) / rows
    assert float(got) == pytest.approx(want, rel=1e-5)
    # a uniform router reads alpha; a collapsed one more
    flat = jnp.full((T, E), 1.0 / E)
    even = jnp.tile(jnp.arange(E).reshape(-1, K), (T * K // E, 1))
    assert float(sequence_balance_loss(flat, even, rows, alpha)) == pytest.approx(alpha)
    # the gradient: d/ds of alpha mean_rows sum_e f_e mean_t s_e, f held still
    g = jax.grad(lambda s: sequence_balance_loss(s, idx, rows, alpha))(scores)
    f = np.stack([np.bincount(i[r], minlength=E) * E / (K * length) for r in range(rows)])
    np.testing.assert_allclose(np.asarray(g).reshape(rows, length, E),
                               np.broadcast_to(alpha * f[:, None, :] / (length * rows),
                                               (rows, length, E)), rtol=1e-5)


@pytest.mark.parametrize("first,count,activation", [(0, 8, "silu"), (2, 3, "relu")])
def test_routing_given_from_outside_equals_the_layers_own(first, count, activation):
    """The routing handed in as ``(idx, weight)`` from the same tensor the
    layer would route: the layer's output and the gradients of its leaves
    and of its input equal the layer routing itself; the router's gradient
    leaves through the weights handed in."""
    p, x = _params(count, key=5), _x(6)
    act = getattr(jax.nn, activation)

    def own(p, x):
        return jnp.sum(jnp.sin(routed_experts_mlp(p, x, _cfg(), (first, count),
                                                  activation=act)[0]))

    def given(p, x):
        idx, weight, _ = route_softmax_top_k(x, p["router"], K)
        y, _, routed = routed_experts_mlp(p, x, _cfg(), (first, count), (idx, weight), act)
        assert routed["scores"] is None
        return jnp.sum(jnp.sin(y))

    with jax.default_matmul_precision("highest"):
        a, ga = jax.value_and_grad(own, argnums=(0, 1))(p, x)
        b, gb = jax.value_and_grad(given, argnums=(0, 1))(p, x)
    np.testing.assert_allclose(a, b, rtol=1e-6)
    for u, v in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(u, v, atol=1e-6 * max(float(jnp.abs(u).max()), 1.0))
    assert float(jnp.abs(gb[0]["router"]).max()) > 0


@pytest.mark.parametrize("first,count", [(0, 8), (2, 3)])
def test_the_relu_activation_against_a_plain_product(first, count):
    """``activation=jax.nn.relu``: ReGLU experts, ``(ReLU(x W_gate) * x
    W_up) W_down``, against the dense loop written with ``max(., 0)``; the
    routing taken from another tensor than the experts' input."""
    p, x, r = _params(count, key=7), _x(8), _x(9)
    with jax.default_matmul_precision("highest"):
        idx, weight, _ = route_softmax_top_k(r, p["router"], K)
        got = routed_experts_mlp(p, x, _cfg(), (first, count), (idx, weight), jax.nn.relu)[0]
        want = jnp.zeros_like(x)
        for e in range(count):
            we = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
            out = (jnp.maximum(x @ p["w_gate"][e], 0.0) * (x @ p["w_up"][e])) @ p["w_down"][e]
            want = want + we[:, None] * out
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1
