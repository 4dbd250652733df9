"""``ops/grouped_matmul.py``: the grouped kernels in interpret mode against
``lax.ragged_dot``, the forward and both cotangents, over buffers laid out
in whole row tiles as ``transformer.moe._layout`` lays out a pass; and the
row tiles they visit against the routing counters."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.ops.grouped_matmul import grouped_matmul, kernel_row_tile, row_tiles
from apex_tpu.transformer.moe import (
    RoutedExpertsConfig,
    _layout,
    route_softmax_top_k,
    routing_facts,
)

F32 = jnp.float32

CASES = {
    # the widths of both routed cells' products, cut to a few row tiles
    "latent-attention-gate": (96, 2048, 1408, 16, [16, 32, 16], F32),
    "latent-attention-down": (64, 1408, 2048, 16, [32, 16], F32),
    "block-diffusion-gate": (96, 2048, 768, 32, [32, 0, 32], F32),
    "block-diffusion-down": (96, 768, 2048, 32, [64, 32], F32),
    # Laguna's hidden width, past what the kernels took before it
    "hidden-3072-gate": (64, 3072, 1024, 16, [16, 0, 32], F32),
    "hidden-3072-down": (64, 1024, 3072, 16, [32, 16], F32),
    "an-empty-group": (128, 256, 128, 16, [32, 0, 48, 16], F32),
    "one-group-holds-every-row": (128, 128, 256, 16, [0, 128, 0], F32),
    "groups-fill-the-buffer": (128, 256, 128, 16, [32, 64, 32], F32),
    "room-past-the-last-span": (128, 128, 128, 16, [16, 16, 0], F32),
    "bfloat16": (128, 256, 384, 16, [48, 16, 0, 32], jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_ragged_dot_forward_and_both_cotangents(case):
    """The buffer's room past the last group holds NaN in ``xs`` and in the
    output's cotangent: the kernels never read it, so every result over the
    groups' rows and every weight gradient is finite and equal to
    ``ragged_dot``'s over the same rows with the room zeroed. A group with
    no rows gets a zero weight gradient."""
    n, a, b, tile, spans, dtype = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    sizes = jnp.asarray(spans, jnp.int32)
    filled = sum(spans)
    inside = (jnp.arange(n) < filled)[:, None]
    xs = jax.random.normal(ks[0], (n, a), F32).astype(dtype)
    w = (jax.random.normal(ks[1], (len(spans), a, b), F32) * a ** -0.5).astype(dtype)
    dy = jax.random.normal(ks[2], (n, b), F32).astype(dtype)
    room = lambda v: jnp.where(inside, v, jnp.nan).astype(dtype)
    zeroed = lambda v: jnp.where(inside, v, 0).astype(F32)

    out, vjp = jax.vjp(lambda x, v: grouped_matmul(x, v, sizes, tile, use_pallas=True),
                       room(xs), w)
    dxs, dw = vjp(room(dy))
    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(lambda x, v: lax.ragged_dot(x, v, sizes), zeroed(xs),
                                 w.astype(F32))
        want_dxs, want_dw = want_vjp(zeroed(dy))

    assert out.dtype == dxs.dtype == dw.dtype == dtype
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out[:filled], F32), want[:filled], **tol)
    np.testing.assert_allclose(np.asarray(dxs[:filled], F32), want_dxs[:filled], **tol)
    np.testing.assert_allclose(np.asarray(dw, F32), want_dw, **tol)
    for g, size in enumerate(spans):
        if size == 0:
            assert not np.any(np.asarray(dw[g], F32))


def test_the_kernels_take_only_the_layouts_they_tile():
    xs, w = jnp.zeros((4096, 2048), jnp.bfloat16), jnp.zeros((16, 2048, 1408), jnp.bfloat16)
    assert kernel_row_tile(xs, w, 512) == 512
    assert kernel_row_tile(jnp.zeros((98304, 2048), jnp.bfloat16),
                           jnp.zeros((32, 2048, 768), jnp.bfloat16), 1024) == 1024
    assert kernel_row_tile(jnp.zeros((4096, 2048), jnp.bfloat16), w, 2048) == 1024
    assert kernel_row_tile(xs, w, 8) is None                          # under a bfloat16 tile
    assert kernel_row_tile(xs, w.astype(F32), 512) is None            # mixed types
    assert kernel_row_tile(xs[:, :100], w[:, :100], 512) is None      # width no multiple of 128
    wide = jnp.zeros((8, 3072, 1024), jnp.bfloat16)                    # Laguna's widths
    assert kernel_row_tile(jnp.zeros((7680, 3072), jnp.bfloat16), wide, 256) == 256
    assert kernel_row_tile(jnp.zeros((7680, 1024), jnp.bfloat16),
                           jnp.swapaxes(wide, 1, 2), 256) == 256
    assert kernel_row_tile(jnp.zeros((4096, 4096), jnp.bfloat16),     # past MAX_WIDTH
                           jnp.zeros((8, 4096, 1024), jnp.bfloat16), 512) is None
    with pytest.raises(ValueError, match="grouped kernels need"):
        grouped_matmul(xs, w, jnp.zeros((16,), jnp.int32), 8, use_pallas=True)


@pytest.mark.parametrize("bias", [0.0, 2.0])
def test_the_kernels_visit_the_row_tiles_the_held_experts_fill(bias):
    """A pass laid out as ``routed_experts_mlp`` lays out its first: the
    kernels' grid walks ``routing_facts``' ``tiled_rows // tile`` row tiles,
    each the tile of the expert whose span holds it."""
    tokens, hidden, cfg, count = 512, 128, RoutedExpertsConfig(num_experts=8, top_k=2), 4
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, hidden), F32)
    router = jax.random.normal(jax.random.PRNGKey(1), (hidden, 8), F32).at[:, 1].add(bias / 8)
    idx, _, _ = route_softmax_top_k(x, router, cfg.top_k)
    key = jnp.where(idx < count, idx, count).reshape(-1)
    tile = cfg.tile_rows(tokens)
    n = cfg.rows_per_pass(tokens, count)
    _, _, sizes, _, first_row, spans = _layout(key, count, tile)
    rows = jnp.clip(first_row + spans, 0, n) - jnp.clip(first_row, 0, n)
    facts = routing_facts(sizes, tokens, cfg)
    assert facts["passes_run"] == 1

    group, visited = row_tiles(rows, n, tile)
    assert int(visited) == facts["tiled_rows"] // tile
    want = np.repeat(np.arange(count), np.asarray(spans) // tile)
    np.testing.assert_array_equal(np.asarray(group)[:int(visited)], want)
