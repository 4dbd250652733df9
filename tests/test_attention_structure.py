"""The mask as a structure (``ops.attention.MaskStructure``) on the flash
kernels' listed schedule, and grouped heads: the tile kinds against the dense
mask, the kernels (Pallas interpret mode) against the dense reference forward
and backward at a length where tiles are skipped, whole and straddled, K/V
with fewer heads against K/V repeated, the sliding window at Laguna's groups
of 9 and 6, and ``causal`` through the structure bit for bit what
``causal=True`` gives."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops import attention as A
from apex_tpu.ops.attention import (
    CAUSAL,
    MaskStructure,
    attention_reference,
    block_diffusion_mask,
    flash_attention,
    sliding_window_mask,
)


def _qkv(key, b, h, hk, s, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, h, s, d), jnp.float32).astype(dtype),
            jax.random.normal(kk, (b, hk, s, d), jnp.float32).astype(dtype),
            jax.random.normal(kv, (b, hk, s, d), jnp.float32).astype(dtype))


def _dense_hidden(structure, s):
    i = np.arange(s)[:, None]
    j = np.arange(s)[None, :]
    return np.asarray(structure.hidden(i, j, s))


def _by_hand(s, block):
    """The three predicates, written out a pair at a time."""
    half = s // 2
    seen = np.zeros((s, s), bool)
    for i in range(s):
        for j in range(s):
            bi, bj = (i % half) // block, (j % half) // block
            if i < half and j < half:
                seen[i, j] = bi == bj
            elif i < half <= j:
                seen[i, j] = bj < bi
            elif i >= half and j >= half:
                seen[i, j] = bj <= bi
    return seen


@pytest.mark.parametrize("s,block", [(16, 2), (32, 4), (48, 8)])
def test_block_diffusion_rule_is_the_three_predicates(s, block):
    np.testing.assert_array_equal(~_dense_hidden(block_diffusion_mask(block), s),
                                  _by_hand(s, block))


@pytest.mark.parametrize("s,window", [(16, 1), (32, 5), (48, 8), (40, 64)])
def test_the_window_rule_is_the_last_window_positions(s, window):
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    np.testing.assert_array_equal(~_dense_hidden(sliding_window_mask(window), s),
                                  (i - window < j) & (j <= i))


@pytest.mark.parametrize("structure,s,bq,bk", [
    (sliding_window_mask(64), 256, 64, 64), (sliding_window_mask(64), 256, 32, 32),
    (sliding_window_mask(48), 256, 32, 64), (sliding_window_mask(100), 512, 64, 32),
    (sliding_window_mask(512), 2048, 256, 256), (sliding_window_mask(1), 128, 16, 16),
    (CAUSAL, 256, 64, 64), (CAUSAL, 256, 32, 128),
    (block_diffusion_mask(4), 256, 32, 32), (block_diffusion_mask(4), 512, 64, 128),
    (block_diffusion_mask(16), 256, 8, 8), (block_diffusion_mask(64), 512, 32, 64),
])
def test_tile_kinds_are_what_the_dense_mask_says(structure, s, bq, bk):
    assert structure.tiles(s, s, bq, bk)
    seen = ~_dense_hidden(structure, s)
    tiles = seen.reshape(s // bq, bq, s // bk, bk)
    live, interior = structure.tile_kinds(s, bq, bk)
    np.testing.assert_array_equal(np.broadcast_to(live, (s // bq, s // bk)),
                                  tiles.any(axis=(1, 3)))
    np.testing.assert_array_equal(np.broadcast_to(interior, (s // bq, s // bk)),
                                  tiles.all(axis=(1, 3)))


def test_the_cells_plan_visits_288_of_1024_tiles_and_masks_48():
    plan = A._tile_plan(16384, 16384, 128, jnp.bfloat16, block_diffusion_mask(4),
                        group=8)
    assert plan == A.TilePlan("listed", 512, 512, 288, 48, 1024)
    causal = A._tile_plan(16384, 16384, 128, jnp.bfloat16, CAUSAL, group=8)
    assert (causal.schedule, causal.visited, causal.masked) == ("listed", 528, 32)
    by_row, by_col = A._listed_tiles(block_diffusion_mask(4), 16384, 16384, 512, 512)
    assert by_row.shape == by_col.shape == (3, 288)
    # 16 on the noised diagonal, 136 noised-on-clean, 136 clean-on-clean
    q, k = by_row[0], by_row[1]
    assert (int(((q < 16) & (k < 16)).sum()), int(((q < 16) & (k >= 16)).sum()),
            int(((q >= 16) & (k >= 16)).sum()), int(((q >= 16) & (k < 16)).sum())
            ) == (16, 136, 136, 0)


@pytest.mark.parametrize("block,visited,masked", [(512, 31, 31), (256, 93, 62)])
def test_the_window_plan_at_8192_lists_the_live_tiles_alone(block, visited, masked):
    """Laguna's sliding layers (s 8,192, window 512, 72 query heads over 8):
    a q tile's live K/V tiles are its own and the one or two before it."""
    plan = A._tile_plan(8192, 8192, 128, jnp.bfloat16, sliding_window_mask(512),
                        block_q=block, block_k=block, group=9)
    n = 8192 // block
    assert plan == A.TilePlan("listed", block, block, visited, masked, n * n)
    by_row, _ = A._listed_tiles(sliding_window_mask(512), 8192, 8192, block, block)
    assert np.all(by_row[1] <= by_row[0]) and np.all(by_row[0] - by_row[1] <= 512 // block)


@pytest.mark.parametrize("causal", [False, True])
def test_causal_and_no_mask_keep_the_schedules_they_had(causal):
    """Either spelling gives one plan: resident where a head fits, listed
    where it does not (as any structure's) and the caller owns whole rows,
    streamed with a bias or as a ring block."""
    for shape, schedule in (((1024, 64), "resident"), ((8192, 128), "listed")):
        s, d = shape
        as_bool = A._tile_plan(s, s, d, jnp.bfloat16, causal, whole_rows=True)
        as_structure = A._tile_plan(s, s, d, jnp.bfloat16,
                                    CAUSAL if causal else None, whole_rows=True)
        assert as_bool == as_structure and as_bool.schedule == schedule
        assert A._tile_plan(s, s, d, jnp.bfloat16, causal, has_bias=True,
                            whole_rows=True).schedule == "streamed"
        ring = A._tile_plan(s, s, d, jnp.bfloat16, causal).schedule
        assert ring == ("resident" if schedule == "resident" else "streamed")


@pytest.mark.parametrize("seq,d,dv,structure,kw,want", [
    (16384, 128, 128, block_diffusion_mask(4), dict(group=8), ("listed", 1024, 1024, 80)),
    (16384, 192, 128, CAUSAL, dict(whole_rows=True), ("listed", 1024, 1024, 136)),
    (16384, 576, 512, CAUSAL, dict(whole_rows=True), ("listed", 512, 1024, 272)),
    (8192, 128, 128, CAUSAL, dict(whole_rows=True), ("listed", 1024, 1024, 36)),
    (8192, 128, 128, sliding_window_mask(512), dict(group=9), ("listed", 512, 512, 31)),
    (16384, 128, 128, sliding_window_mask(4096), dict(group=7), ("listed", 1024, 1024, 70)),
    (1024, 64, 64, CAUSAL, dict(whole_rows=True), ("resident", 512, 512, 3)),
    (8192, 128, 128, CAUSAL, dict(), ("streamed", 512, 512, 136)),
    (8192, 128, 128, CAUSAL, dict(whole_rows=True, has_bias=True), ("streamed", 512, 512, 136)),
    (8192, 128, 128, CAUSAL, dict(whole_rows=True, dropout=True), ("streamed", 512, 512, 136)),
], ids=["blockdiff-16k-group8", "causal-16k-keys192", "causal-16k-keys576-vmem", "causal-8k",
        "window512-8k", "window4096-16k", "gpt2-resident", "ring-block", "bias", "dropout"])
def test_the_plan_picks_the_tile_a_call_leaves_to_it(seq, d, dv, structure, kw, want):
    """``block_q = block_k = None``: on the listed schedule the candidate of
    least fitted cost (steps x ``_STEP_US`` + pairs x ``_PAIR_US``) whose
    kernels ``_listed_vmem`` counts inside the scoped VMEM (at keys of 576
    over 512 it counts the square over: a rectangle); 512 where Laguna's window of 512 would compute twice
    the pairs at 1,024 (PERF.md section 5); 512 as ever on the resident and
    streamed schedules (GPT-2's call, a ring block, a bias, dropout).
    (Counted, not run.)"""
    plan = A._tile_plan(seq, seq, d, jnp.bfloat16, structure, None, None, dv=dv, **kw)
    assert (plan.schedule, plan.block_q, plan.block_k, plan.visited) == want
    assert A._listed_vmem(plan.block_q, plan.block_k, d, dv, 2) <= A._LISTED_VMEM_BYTES


def _grids(fn, *args):
    """Each pallas_call's grid in ``fn``'s jaxpr, by kernel name."""
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids[eqn.params["name"]] = tuple(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.parametrize("structure,heads,kv,d,dv,steps", [
    (CAUSAL, 2, 2, 32, 16, 3), (block_diffusion_mask(4), 4, 2, 32, 32, 3)],
    ids=["causal-value-width", "blockdiff-group2"])
def test_the_plans_1024_tiles_match_the_dense_reference(structure, heads, kv, d, dv, steps):
    """s 2,048, so a 1,024 tile is two a side: causal with keys wider than
    values, and block diffusion with grouped heads, on the tile the plan
    picks when the call leaves it; the output and dq, dk, dv against the
    dense reference within the 512-tile tests' tolerances."""
    s = 2048
    plan = A._tile_plan(s, s, d, jnp.float32, structure, None, None, group=heads // kv,
                        whole_rows=True, dv=dv)
    assert (plan.schedule, plan.block_q, plan.block_k, plan.visited) == (
        "listed", 1024, 1024, steps)
    q, k, _ = _qkv(jax.random.PRNGKey(heads + d), 1, heads, kv, s, d)
    v = jax.random.normal(jax.random.PRNGKey(dv), (1, kv, s, dv), jnp.float32)
    flash = lambda q, k, v: flash_attention(q, k, v, structure=structure, use_pallas=True)
    dense = lambda q, k, v: attention_reference(q, k, v, structure=structure)
    grids = _grids(jax.grad(lambda *a: jnp.sum(flash(*a)), argnums=(0, 1, 2)), q, k, v)
    assert {name: grid[1] for name, grid in grids.items()} == dict.fromkeys(
        ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"), steps)
    o, grads = _value_and_grads(flash, q, k, v)
    o_ref, grads_ref = _value_and_grads(dense, q, k, v)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    for got, want in zip(grads, grads_ref):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=5e-5)


def _value_and_grads(fn, q, k, v):
    def loss(q, k, v):
        o = fn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * jnp.cos(o.astype(jnp.float32))), o
    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return o, grads


@pytest.mark.parametrize("structure,group", [
    (block_diffusion_mask(4), 1), (block_diffusion_mask(4), 2), (block_diffusion_mask(4), 4),
    (block_diffusion_mask(32), 1), (block_diffusion_mask(32), 2), (block_diffusion_mask(32), 4),
    (CAUSAL, 2), (CAUSAL, 4), (None, 2), (None, 4),
], ids=lambda v: str(v) if isinstance(v, int) else ("none" if v is None else f"{v.kind}{v.block}"))
def test_listed_kernels_match_the_dense_mask_forward_and_backward(structure, group):
    """s 512 at 64 x 64 tiles: of 64 tiles the block mask skips 40, computes
    12 whole and 12 straddling. (One head count with causal or no mask runs
    the kernels that were there.)"""
    q, k, v = _qkv(jax.random.PRNGKey(group), 1, 4, 4 // group, 512, 32)
    flash = lambda q, k, v: flash_attention(q, k, v, structure=structure, block_q=64,
                                            block_k=64, use_pallas=True)
    dense = lambda q, k, v: attention_reference(q, k, v, structure=structure)
    o, grads = _value_and_grads(flash, q, k, v)
    o_ref, grads_ref = _value_and_grads(dense, q, k, v)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    for got, want in zip(grads, grads_ref):
        assert got.shape == want.shape          # dk, dv in K/V's own head count
        np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("window,heads,kv", [(100, 9, 1), (64, 18, 2), (100, 6, 1), (64, 12, 2),
                                             (256, 7, 1)],
                         ids=["w100-group9", "w64-group9", "w100-group6", "w64-group6",
                              "w256-group7"])
def test_listed_kernels_match_the_window_forward_and_backward(window, heads, kv):
    """Laguna's groups (72 over 8 on sliding layers, 48 over 8 on full ones)
    at s 512 on 64 x 64 tiles: a window of 64 lies on the tiles' edges, one
    of 100 straddles them; SmallThinker's group of 7 with the window four
    tiles wide (4,096 on its 1,024 x 1,024 tiles); the output and dq, dk, dv
    against the dense reference."""
    structure = sliding_window_mask(window)
    q, k, v = _qkv(jax.random.PRNGKey(window + heads), 1, heads, kv, 512, 32)
    flash = lambda q, k, v: flash_attention(q, k, v, structure=structure, block_q=64,
                                            block_k=64, use_pallas=True)
    dense = lambda q, k, v: attention_reference(q, k, v, structure=structure)
    o, grads = _value_and_grads(flash, q, k, v)
    o_ref, grads_ref = _value_and_grads(dense, q, k, v)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    for got, want in zip(grads, grads_ref):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=5e-5)


def test_listed_kernels_in_bfloat16():
    structure = block_diffusion_mask(4)
    q, k, v = _qkv(jax.random.PRNGKey(9), 2, 4, 1, 256, 64, jnp.bfloat16)
    got = flash_attention(q, k, v, structure=structure, block_q=64, block_k=64,
                          use_pallas=True)
    want = attention_reference(q, k, v, structure=structure)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=3e-2)


@pytest.mark.parametrize("structure", [block_diffusion_mask(4), CAUSAL, None],
                         ids=["blockdiff", "causal", "none"])
def test_grouped_heads_equal_repeated_key_value_heads(structure):
    q, k, v = _qkv(jax.random.PRNGKey(4), 2, 8, 2, 128, 16)
    rep = lambda a: jnp.repeat(a, 4, axis=1)
    grouped = attention_reference(q, k, v, structure=structure)
    repeated = attention_reference(q, rep(k), rep(v), structure=structure)
    np.testing.assert_array_equal(grouped, repeated)
    kernel = flash_attention(q, k, v, structure=structure, block_q=32, block_k=32,
                             use_pallas=True)
    np.testing.assert_allclose(kernel, repeated, atol=2e-5)
    # dk of a K/V head is the sum of what its four query heads send it
    dk = jax.grad(lambda k: jnp.sum(jnp.sin(flash_attention(
        q, k, v, structure=structure, block_q=32, block_k=32, use_pallas=True))))(k)
    dk_rep = jax.grad(lambda kr: jnp.sum(jnp.sin(attention_reference(
        q, kr, rep(v), structure=structure))))(rep(k))
    np.testing.assert_allclose(dk, dk_rep.reshape(2, 2, 4, 128, 16).sum(2), atol=5e-5)


@pytest.mark.parametrize("s,d", [(128, 32), (2048, 16)], ids=["resident", "streamed"])
def test_causal_through_the_structure_is_bit_for_bit_todays(s, d):
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 2, 2, s, d)
    run = lambda **kw: _value_and_grads(
        lambda q, k, v: flash_attention(q, k, v, use_pallas=True, **kw), q, k, v)
    o_old, g_old = run(causal=True)
    o_new, g_new = run(structure=CAUSAL)
    np.testing.assert_array_equal(o_old, o_new)
    for a, b in zip(g_old, g_new):
        np.testing.assert_array_equal(a, b)
    both, _ = run(causal=True, structure=CAUSAL)
    np.testing.assert_array_equal(o_old, both)


def test_the_listed_schedule_runs_causal_as_the_streamed_one_does():
    """The same tiles in the same order with the same arithmetic: a causal
    call's listed kernels against the streamed ones, which a bias, dropout
    and the ring's partial rows keep."""
    q, k, v = _qkv(jax.random.PRNGKey(6), 1, 2, 2, 512, 16)
    q3, k3, v3 = (a.reshape(2, 512, 16) for a in (q, k, v))
    streamed = A._flash3(q3, k3, v3, jnp.zeros((1,), jnp.int32), 0.25, True, 64, 64,
                         True, 0.0)
    listed = A._flash3_listed(q3, k3, v3, 0.25, CAUSAL, 64, 64, True)
    np.testing.assert_allclose(listed, streamed, atol=1e-6)


def test_reference_takes_the_structure_as_its_dense_mask():
    structure = block_diffusion_mask(8)
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 2, 2, 64, 8)
    hidden = jnp.asarray(_dense_hidden(structure, 64))
    np.testing.assert_array_equal(attention_reference(q, k, v, structure=structure),
                                  attention_reference(q, k, v, mask=hidden))
    # off the kernels (an odd length) flash_attention falls to the same
    odd = tuple(a[:, :, :60] for a in (q, k, v))
    np.testing.assert_array_equal(
        flash_attention(*odd, structure=structure),
        attention_reference(*odd, structure=structure))


def test_what_is_refused():
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, 4, 2, 128, 16)
    with pytest.raises(ValueError, match="two masks"):
        flash_attention(q, k, v, causal=True, structure=block_diffusion_mask(4))
    with pytest.raises(ValueError, match="divide the query heads"):
        flash_attention(q[:, :3], k, v)
    with pytest.raises(NotImplementedError, match="listed schedule"):
        flash_attention(q, k, v, structure=block_diffusion_mask(4), dropout_rate=0.1,
                        dropout_seed=1)
    with pytest.raises(ValueError, match="quadrants"):     # a tile would straddle the halves
        flash_attention(q, k, v, structure=block_diffusion_mask(4), block_q=128,
                        block_k=128, use_pallas=True)
    with pytest.raises(ValueError, match="MaskStructure"):
        flash_attention(q, k[:, :1].repeat(4, 1), v[:, :1].repeat(4, 1),
                        mask=jnp.zeros((128, 128), bool), use_pallas=True)
    with pytest.raises(ValueError, match="unknown mask structure"):
        MaskStructure("dilated")
    with pytest.raises(ValueError, match="block must be"):
        sliding_window_mask(0)
