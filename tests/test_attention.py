"""Flash-attention kernel tests — ref apex/contrib/test/fmha/test_fmha.py and
multihead_attn tests: fused kernel vs pure reference, fwd + bwd, causal and
masked, fp32 and bf16 (Pallas interpret mode on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops.attention import (
    attention_reference,
    flash_attention,
    flash_attention_with_lse,
)


def _qkv(key, b, h, sq, sk, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, sq, d), dtype=jnp.float32)
    k = jax.random.normal(kk, (b, h, sk, d), dtype=jnp.float32)
    v = jax.random.normal(kv, (b, h, sk, d), dtype=jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_forward_matches_reference(causal, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 3, 64, 64, 32, dtype)
    got = flash_attention(q, k, v, causal=causal, use_pallas=True)
    want = attention_reference(q, k, v, causal=causal)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


def test_flash_cross_attention_rectangular():
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 2, 32, 128, 16)
    got = flash_attention(q, k, v, use_pallas=True, block_q=16, block_k=32)
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(32, 32), (32, 16), (16, 32)])
def test_flash_backward_matches_reference(causal, block_q, block_k):
    # unequal blocks exercise both directions of the causal-diagonal index
    # clamp ((i*bq+bq-1)//bk forward, (j*bk)//bq in dK/dV)
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 2, 64, 64, 32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, use_pallas=True,
                            block_q=block_q, block_k=block_k)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(attention_reference(q, k, v, causal=causal)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), atol=1e-4, err_msg=name
        )


def test_mask_path_falls_back_to_reference():
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 1, 16, 16, 8)
    # padding mask: last 5 keys masked out
    mask = jnp.arange(16)[None, None, None, :] >= 11
    got = flash_attention(q, k, v, mask=mask)
    want = attention_reference(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # masked keys must not receive grad through v
    g = jax.grad(lambda v: jnp.sum(flash_attention(q, k, v, mask=mask)))(v)
    assert np.abs(np.asarray(g)[:, :, 11:, :]).max() == 0.0


def test_lse_variant_matches_log_sum_exp():
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 1, 32, 32, 16)
    scale = 1.0 / np.sqrt(16)
    o, lse = flash_attention_with_lse(
        q.reshape(1, 32, 16), k.reshape(1, 32, 16), v.reshape(1, 32, 16),
        scale, False, 16, 16, True)
    s = np.einsum("bqd,bkd->bqk", np.asarray(q[0]), np.asarray(k[0])) * scale
    want_lse = np.log(np.sum(np.exp(s), axis=-1))
    np.testing.assert_allclose(np.asarray(lse), want_lse, atol=1e-5)


def test_flash_is_jittable():
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 2, 32, 32, 16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                use_pallas=True))
    got = f(q, k, v)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bias_matches_reference(causal):
    """Additive (heads, sq, sk) logit bias (the T5 relative-position-bias
    contract) inside the Pallas kernels: fwd and all four grads (q, k, v,
    AND bias — the batch-reducing dbias kernel) vs the dense reference."""
    q, k, v = _qkv(jax.random.PRNGKey(6), 2, 3, 64, 64, 32)
    bias = jax.random.normal(jax.random.PRNGKey(7), (3, 64, 64)) * 2.0

    def loss_flash(q, k, v, bias):
        o = flash_attention(q, k, v, causal=causal, use_pallas=True,
                            block_q=32, block_k=32, bias=bias)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v, bias):
        return jnp.sum(jnp.sin(attention_reference(
            q, k, v, causal=causal, bias=bias)))

    np.testing.assert_allclose(float(loss_flash(q, k, v, bias)),
                               float(loss_ref(q, k, v, bias)), rtol=1e-5)
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, e, name in zip(g1, g2, ("q", "k", "v", "bias")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), atol=2e-4,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_flash_bias_rectangular_cross_attn_shape():
    """Bias on a rectangular (sq != sk) non-causal core — the enc-dec
    geometry — stays on the Pallas path and matches the reference."""
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, 2, 32, 128, 16)
    bias = jax.random.normal(jax.random.PRNGKey(9), (2, 32, 128))
    got = flash_attention(q, k, v, use_pallas=True, block_q=16, block_k=32,
                          bias=bias)
    want = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_bias_shape_validated():
    q, k, v = _qkv(jax.random.PRNGKey(10), 2, 2, 16, 16, 8)
    with pytest.raises(ValueError, match="batch-shared"):
        flash_attention(q, k, v, bias=jnp.zeros((2, 2, 16, 16)))


def test_flash_bwd_kernels_respect_global_offsets():
    """The [seed, q_off, k_off] operand in the BACKWARD kernels (reviewer
    find: only the forward had off-TPU offset coverage): chunked _fa_bwd
    calls against the global lse with per-chunk k offsets must reproduce
    the dense kernel's gradients — the ring-SP backward contract, in
    interpret mode."""
    from apex_tpu.ops.attention import _fa_bwd, _fa_fwd, flash_attention

    b, h, s, d = 1, 2, 256, 16
    rate, seed, scale = 0.3, 99, 1.0 / d ** 0.5
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=False, dropout_rate=rate,
            dropout_seed=jnp.int32(seed), use_pallas=True,
            interpret=True) ** 2)

    g_dense = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    bh, half = b * h, s // 2
    q3, k3, v3 = (x.reshape(bh, s, d) for x in (q, k, v))
    sv = lambda k_off: jnp.asarray([seed, 0, k_off], jnp.int32)
    o3, lse3 = _fa_fwd(q3, k3, v3, scale, False, 128, 128, interpret=True,
                       dropout_rate=rate, seed=sv(0))
    do3 = (2.0 * o3.astype(jnp.float32)).astype(o3.dtype)
    dq_sum, dks, dvs = 0.0, [], []
    for k_off in (0, half):
        dq_c, dk_c, dv_c, _ = _fa_bwd(
            q3, k3[:, k_off:k_off + half], v3[:, k_off:k_off + half],
            o3, lse3, do3, scale, False, 128, 128, interpret=True,
            dropout_rate=rate, seed=sv(k_off))
        dq_sum = dq_sum + dq_c
        dks.append(dk_c)
        dvs.append(dv_c)
    got = (dq_sum, jnp.concatenate(dks, 1), jnp.concatenate(dvs, 1))
    for a, e, name in zip(got, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a).reshape(b, h, s, d), np.asarray(e), atol=2e-4,
            err_msg=name)


# ---------------------------------------------------------------------------
# The tile schedule (``_tile_plan``): resident (one head whole in VMEM, the
# tile loops unrolled inside the kernel) against streamed (tiles on the grid).

def _force_schedule(monkeypatch, schedule):
    """Steer ``_tile_plan`` from the test through the cap on unrolled tile
    bodies: none allowed means every call streams, no cap means every call
    whose head fits VMEM is resident."""
    from apex_tpu.ops import attention

    monkeypatch.setattr(attention, "_RESIDENT_MAX_BODIES",
                        0 if schedule == "streamed" else 10 ** 6)


@pytest.mark.parametrize("rate", [0.0, 0.3])
@pytest.mark.parametrize("block_q,block_k", [(32, 32), (32, 16), (16, 32)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("schedule", ["resident", "streamed"])
def test_schedule_matches_reference(monkeypatch, schedule, causal, block_q,
                                    block_k, rate):
    """Forward and all three gradients on either schedule, interior and
    diagonal tiles, equal and unequal tiles (several diagonal tiles a q
    tile, or one K/V tile across several q tiles), with and without the
    in-kernel dropout."""
    from apex_tpu.ops.attention import _tile_plan, attention_dropout_mask

    _force_schedule(monkeypatch, schedule)
    b, h, s, d = 1, 2, 64, 32
    q, k, v = _qkv(jax.random.PRNGKey(12), b, h, s, s, d)
    plan = _tile_plan(s, s, d, q.dtype, causal, block_q, block_k)
    assert plan[:3] == (schedule, block_q, block_k)
    seed = jnp.int32(41)
    keep = None
    if rate:
        keep = attention_dropout_mask(seed, rate, b * h, s, s).reshape(
            b, h, s, s)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, use_pallas=True,
                            block_q=block_q, block_k=block_k,
                            dropout_rate=rate,
                            dropout_seed=seed if rate else None)
        return jnp.sum(jnp.sin(o)), o

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=causal, dropout_rate=rate,
                                dropout_keep=keep)
        return jnp.sum(jnp.sin(o)), o

    g1, o1 = jax.grad(loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    g2, o2 = jax.grad(loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)
    for a, e, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), atol=1e-4, err_msg=name)


@pytest.mark.parametrize("schedule", ["resident", "streamed"])
def test_schedule_respects_global_offsets(monkeypatch, schedule):
    """``[seed, q_off, k_off]`` on either schedule, as the ring passes them.
    K/V in two chunks at their global column offsets, merged by lse,
    reproduce the dense call's output, gradients and dropout mask; the
    second half of the q rows at its global row offset reproduces those
    rows of the dense output."""
    from apex_tpu.ops.attention import _fa_bwd, _fa_fwd, _tile_plan

    _force_schedule(monkeypatch, schedule)
    b, h, s, d = 1, 2, 128, 16
    rate, seed, scale = 0.3, 77, 1.0 / d ** 0.5
    q, k, v = (x.reshape(b * h, s, d)
               for x in _qkv(jax.random.PRNGKey(13), b, h, s, s, d))
    half = s // 2
    assert _tile_plan(s, half, d, q.dtype, False, 32, 32)[0] == schedule
    sv = lambda q_off, k_off: jnp.asarray([seed, q_off, k_off], jnp.int32)
    o, lse = _fa_fwd(q, k, v, scale, False, 32, 32, True, rate, sv(0, 0))
    do = jnp.cos(o)
    want = _fa_bwd(q, k, v, o, lse, do, scale, False, 32, 32, True, rate,
                   sv(0, 0))[:3]

    parts = [_fa_fwd(q, k[:, c:c + half], v[:, c:c + half], scale, False,
                     32, 32, True, rate, sv(0, c)) for c in (0, half)]
    w = [jnp.exp(l - lse) for _, l in parts]
    np.testing.assert_allclose(
        np.asarray(sum(wi * oi for wi, (oi, _) in zip(w, parts))),
        np.asarray(o), atol=2e-5)
    got = [_fa_bwd(q, k[:, c:c + half], v[:, c:c + half], o, lse, do, scale,
                   False, 32, 32, True, rate, sv(0, c)) for c in (0, half)]
    got = (got[0][0] + got[1][0],
           jnp.concatenate([got[0][1], got[1][1]], 1),
           jnp.concatenate([got[0][2], got[1][2]], 1))
    for a, e, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=1e-4,
                                   err_msg=name)

    o_low, lse_low = _fa_fwd(q[:, half:], k, v, scale, False, 32, 32, True,
                             rate, sv(half, 0))
    np.testing.assert_allclose(np.asarray(o_low), np.asarray(o[:, half:]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_low),
                               np.asarray(lse[:, half:]), atol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(32, 32), (16, 32)])
def test_wholly_masked_row_gives_zero_output_and_neg_inf_lse(block_q,
                                                             block_k):
    """A row no key reaches (here: a -inf bias row; under the ring, a
    partial block) leaves l == 0: the kernel must emit o = 0 and
    lse = NEG_INF, the merge's identity, and not 0/0. Such a call carries a
    bias, so it streams: a resident row always sees its own diagonal."""
    from apex_tpu.ops.attention import NEG_INF, _fa_fwd, _tile_plan

    q, k, v = (x.reshape(2, 64, 16)
               for x in _qkv(jax.random.PRNGKey(14), 1, 2, 64, 64, 16))
    assert _tile_plan(64, 64, 16, q.dtype, False, block_q, block_k,
                      has_bias=True).schedule == "streamed"
    bias = jnp.zeros((2, 64, 64)).at[:, 5, :].set(-jnp.inf)
    o, lse = _fa_fwd(q, k, v, 0.25, False, block_q, block_k, True, bias=bias)
    assert np.all(np.asarray(o)[:, 5] == 0.0)
    assert np.all(np.asarray(lse)[:, 5, 0] == NEG_INF)
    assert np.isfinite(np.asarray(o)).all()
    want = attention_reference(q, k, v, scale=0.25)
    rest = np.arange(64) != 5
    np.testing.assert_allclose(np.asarray(o)[:, rest],
                               np.asarray(want)[:, rest], atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [64, 24])
def test_scale_folded_into_operand_matches_scaled_scores(monkeypatch, d,
                                                         dtype):
    """1/sqrt(64) is a power of two and multiplies the q (or k) tile of a
    resident kernel exactly; 1/sqrt(24) is not and stays on the fp32 scores.
    Either way the result is the unfolded kernel's."""
    from apex_tpu.ops import attention

    assert attention._scale_folds(1.0 / d ** 0.5) == (d == 64)
    q, k, v = _qkv(jax.random.PRNGKey(15), 1, 2, 64, 64, d, dtype)
    assert attention._tile_plan(64, 64, d, dtype, True, 32,
                                32).schedule == "resident"

    def run(q, k, v):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, use_pallas=True,
                                block_q=32, block_k=32)
            return jnp.sum(jnp.sin(o.astype(jnp.float32))), o
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    g1, o1 = run(q, k, v)
    monkeypatch.setattr(attention, "_scale_folds", lambda scale: False)
    g2, o2 = run(q, k, v)
    f32 = lambda x: np.asarray(x, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(f32(o1), f32(o2), atol=2e-5)
        for a, e, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(f32(a), f32(e), atol=1e-4,
                                       err_msg=name)
    else:
        # bf16 operands: the fold is exact, so not one bit moves forward
        np.testing.assert_array_equal(f32(o1), f32(o2))
        for a, e, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(f32(a), f32(e), atol=3e-2,
                                       err_msg=name)


# (sq, sk, causal, bound, has_bias) -> the plan; d 64, bf16 throughout
_PLANS = {
    "cells": ((1024, 1024, True, 512, False),
              ("resident", 512, 512, 3, 2, 4, 3)),
    "s2048": ((2048, 2048, True, 512, False),
              ("streamed", 512, 512, 10, 10, 16, 1)),
    "s8192": ((8192, 8192, True, 512, False),
              ("streamed", 512, 512, 136, 136, 256, 1)),
    "biased": ((1024, 1024, True, 512, True),
               ("streamed", 512, 512, 3, 3, 4, 1)),
    "noncausal": ((1024, 1024, False, 512, False),
                  ("resident", 512, 512, 4, 0, 4, 4)),
    "noncausal_s1536": ((1536, 1536, False, 512, False),
                        ("streamed", 512, 512, 9, 0, 9, 1)),
    "causal_s1536": ((1536, 1536, True, 512, False),
                     ("streamed", 512, 512, 6, 6, 9, 1)),
    "one_tile": ((512, 512, True, 512, False),
                 ("resident", 512, 512, 1, 1, 1, 1)),
    "bound_256": ((1024, 1024, True, 256, False),
                  ("streamed", 256, 256, 10, 10, 16, 1)),
    "causal_rectangle": ((512, 1024, True, 512, False),
                         ("streamed", 512, 512, 1, 1, 2, 1)),
}


@pytest.mark.parametrize("case", sorted(_PLANS))
def test_tile_plan_counts(case):
    """The plan is the mechanism's counter: schedule, tile, tiles visited /
    masked / in the rectangle per head, and unrolled bodies a kernel —
    static per shape. The benchmark cells' call is resident at three bodies
    and a non-causal 2 x 2 at four; longer heads, a bias, a call over the cap
    and a causal rectangle stream."""
    from apex_tpu.ops.attention import _tile_plan

    (sq, sk, causal, bound, has_bias), want = _PLANS[case]
    plan = _tile_plan(sq, sk, 64, jnp.bfloat16, causal, bound, bound,
                      has_bias)
    assert (*plan, plan.bodies) == want


@pytest.mark.parametrize("cap,cells,noncausal", [
    (2, "streamed", "streamed"), (3, "resident", "streamed"),
    (4, "resident", "resident")])
def test_tile_plan_holds_the_cap_on_unrolled_bodies(monkeypatch, cap, cells,
                                                    noncausal):
    """``_RESIDENT_MAX_BODIES`` is the program-size budget: a kernel that
    would unroll more tile bodies streams, whatever its kernel time."""
    from apex_tpu.ops import attention

    assert attention._RESIDENT_MAX_BODIES == 4
    monkeypatch.setattr(attention, "_RESIDENT_MAX_BODIES", cap)
    plan = attention._tile_plan(1024, 1024, 64, jnp.bfloat16, True)
    assert plan.schedule == cells and plan.bodies <= cap
    plan = attention._tile_plan(1024, 1024, 64, jnp.bfloat16, False)
    assert plan.schedule == noncausal and plan.bodies <= cap


@pytest.mark.parametrize("d,dtype,block,want", [
    (64, jnp.bfloat16, 512, "resident"),
    (128, jnp.float32, 512, "resident"),
    (256, jnp.float32, 512, "streamed"),    # the head outgrows VMEM
    (64, jnp.bfloat16, 1024, "streamed"),   # the score tile does
    (64, jnp.bfloat16, 8, "streamed"),      # off bf16's 16-row tiling
])
def test_tile_plan_vmem_budget_and_tiling(monkeypatch, d, dtype, block, want):
    """With the cap out of the way: a head, or one tile's scores, that does
    not fit the VMEM budget streams, and so does a tile that does not sit on
    the dtype's sublane tiling (the kernel slices whole operands by it)."""
    from apex_tpu.ops.attention import _tile_plan

    _force_schedule(monkeypatch, "resident")
    s = 1024 if block >= 512 else 16
    assert _tile_plan(s, s, d, dtype, True, block, block).schedule == want


# ---------------------------------------------------------------------------
# a value width of its own (latent attention's expanded heads: keys of
# 128 + 64 over values of 128)

def _qkv_widths(key, b, h, s, d, dv, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, h, s, d), dtype),
            jax.random.normal(ks[1], (b, h, s, d), dtype),
            jax.random.normal(ks[2], (b, h, s, dv), dtype))


@pytest.mark.parametrize("d,dv", [(192, 128), (24, 16), (16, 24)])
@pytest.mark.parametrize("schedule", ["resident", "streamed"])
def test_value_width_of_its_own_matches_reference(monkeypatch, schedule, d, dv):
    """Causal, forward and all three gradients against the reference: the
    score's products run over q's width, ``p @ v`` and ``do @ v.T`` over
    v's, on either schedule; the output and dv have v's width."""
    from apex_tpu.ops.attention import _tile_plan

    _force_schedule(monkeypatch, schedule)
    b, h, s = 1, 2, 128
    q, k, v = _qkv_widths(jax.random.PRNGKey(d + dv), b, h, s, d, dv)
    assert _tile_plan(s, s, d, q.dtype, True, 64, 64).schedule == schedule
    scale = 0.114721 if d == 192 else None        # the cell's: 192^-1/2 m^2

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, use_pallas=True, scale=scale,
                            block_q=64, block_k=64)
        return jnp.sum(jnp.sin(o)), o

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=True, scale=scale)
        return jnp.sum(jnp.sin(o)), o

    g1, o1 = jax.grad(loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    g2, o2 = jax.grad(loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert o1.shape == (b, h, s, dv)
    assert [g.shape for g in g1] == [q.shape, k.shape, v.shape]
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)
    for a, e, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=2e-4,
                                   err_msg=name)


def test_value_width_of_its_own_in_bfloat16_and_on_the_listed_schedule():
    """bf16 at the cell's widths; and grouped heads (the listed schedule)
    take a value width of their own too."""
    q, k, v = _qkv_widths(jax.random.PRNGKey(5), 1, 4, 128, 192, 128, jnp.bfloat16)
    o = flash_attention(q, k, v, causal=True, use_pallas=True, block_q=64, block_k=64)
    want = attention_reference(q, k, v, causal=True)
    assert o.dtype == jnp.bfloat16 and o.shape == (1, 4, 128, 128)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(want, np.float32),
                               atol=3e-2)
    q, k, v = _qkv_widths(jax.random.PRNGKey(6), 1, 4, 128, 24, 16)
    k, v = k[:, :2], v[:, :2]           # two K/V heads under four query heads
    loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, use_pallas=True, block_q=64, block_k=64)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: attention_reference(q, k, v, causal=True)),
                    argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(got, want):
        assert a.shape == e.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=2e-4)


def test_keys_of_another_width_than_queries_are_refused():
    q, k, v = _qkv_widths(jax.random.PRNGKey(7), 1, 2, 64, 32, 32)
    with pytest.raises(ValueError, match="one width"):
        flash_attention(q, k[..., :16], v)


# -- the packed entry: head size 64, half of the 128 lanes (ISSUE 36) --------------

def _packed_pair(heads, seq, causal, d=64):
    """(packed loss, unpacked loss) over one QKV product (1, seq, heads x 3
    x d) in bfloat16: the packed kernels, and ``flash_attention`` on the
    transposed operands with its result turned back."""
    from apex_tpu.ops.attention import flash_attention_packed, unpack_qkv

    qkv = jax.random.normal(jax.random.PRNGKey(heads + seq),
                            (1, seq, heads * 3 * d), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(7), (1, seq, heads * d))

    def packed(qkv):
        o = flash_attention_packed(qkv, heads, causal=causal, use_pallas=True,
                                   interpret=True)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def unpacked(qkv):
        o = flash_attention(*unpack_qkv(qkv, heads), causal=causal,
                            use_pallas=True, interpret=True)
        o = o.transpose(0, 2, 1, 3).reshape(1, seq, heads * d)
        return jnp.sum(o.astype(jnp.float32) * w), o

    return qkv, packed, unpacked


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [512, 1024])
@pytest.mark.parametrize("heads", [16, 20])
def test_packed_entry_equals_flash_attention_bit_for_bit(heads, seq, causal):
    """The packed kernels run the resident schedule's tile bodies on the
    product's 128-lane blocks: o and the gradient of the product (dq, dk and
    dv side by side a head) equal ``flash_attention``'s on the transposed
    operands to the bit, at the GPT-2 cells' head counts and head size."""
    from apex_tpu.ops.attention import packed_plan

    assert packed_plan(seq, heads, 64, jnp.bfloat16, causal) is not None
    qkv, packed, unpacked = _packed_pair(heads, seq, causal)
    got_g, got_o = jax.grad(packed, has_aux=True)(qkv)
    want_g, want_o = jax.grad(unpacked, has_aux=True)(qkv)
    assert got_o.dtype == want_o.dtype == got_g.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got_o, np.float32),
                                  np.asarray(want_o, np.float32))
    np.testing.assert_array_equal(np.asarray(got_g, np.float32),
                                  np.asarray(want_g, np.float32))


@pytest.mark.parametrize("heads,seq,d,causal,why", [
    (4, 256, 128, True, "a head fills the lanes"),
    (3, 256, 64, True, "half a pair of heads"),
    (4, 2048, 64, True, "ten causal tiles stream"),
    (4, 256, 32, True, "a quarter of the lanes: a rotation by head"),
])
def test_packed_plan_refuses_what_the_packed_kernels_do_not_hold(
        heads, seq, d, causal, why):
    """No plan, so the entry goes through ``flash_attention``'s layout (and
    says so when the kernels are forced)."""
    from apex_tpu.ops.attention import flash_attention_packed, packed_plan

    assert packed_plan(seq, heads, d, jnp.bfloat16, causal) is None, why
    qkv = jnp.zeros((1, seq, heads * 3 * d), jnp.bfloat16)
    with pytest.raises(ValueError, match="packed flash kernels need"):
        flash_attention_packed(qkv, heads, causal=causal, use_pallas=True)
    if seq <= 256:
        out = flash_attention_packed(qkv, heads, causal=causal)
        assert out.shape == (1, seq, heads * d)


def _attention_lowered(heads, seq, head_dim, mask=None, sp=1, dropout=0.0):
    """``standalone_gpt._attention``'s forward lowered for the TPU with the
    kernels on, as text."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.ops._pallas_util import force_compiled
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.transformer.testing import GPTConfig
    from apex_tpu.transformer.testing.standalone_gpt import _attention

    hidden = heads * head_dim
    cfg = GPTConfig(vocab_size=128, max_seq=seq * sp, hidden=hidden,
                    num_layers=1, num_heads=heads, dtype=jnp.bfloat16,
                    attention_dropout=dropout)
    p = {"qkv_kernel": jnp.zeros((hidden, 3 * hidden), jnp.bfloat16),
         "qkv_bias": jnp.zeros((3 * hidden,), jnp.bfloat16),
         "out_kernel": jnp.zeros((hidden, hidden), jnp.bfloat16),
         "out_bias": jnp.zeros((hidden,), jnp.bfloat16)}
    x = jnp.zeros((1, seq * sp, hidden), jnp.bfloat16)
    key = jax.random.PRNGKey(0) if dropout else None
    mesh = build_mesh(tp=1, pp=1, sp=sp, dp=1, devices=jax.devices()[:sp])

    def fn(p, x):
        body = lambda p, x: _attention(p, x, cfg, heads, True, mask,
                                       dropout_key=key)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(), P(None, "sp", None)),
                             out_specs=P(None, "sp", None),
                             check_vma=False)(p, x)

    with force_compiled():
        return jax.jit(fn).trace(p, x).lower(
            lowering_platforms=("tpu",)).as_text()


_OLD_PATH = {
    "packed": dict(heads=4, seq=256, head_dim=64),
    "head-128": dict(heads=2, seq=256, head_dim=128),
    "odd-heads": dict(heads=3, seq=256, head_dim=64),
    "mask": dict(heads=4, seq=256, head_dim=64,
                 mask=jnp.zeros((256, 256), bool)),
    "sp-2": dict(heads=4, seq=256, head_dim=64, sp=2),
    "streams": dict(heads=4, seq=2048, head_dim=64),
    "dropout": dict(heads=4, seq=256, head_dim=64, dropout=0.1),
}


@pytest.mark.parametrize("case", sorted(_OLD_PATH))
def test_attention_takes_the_packed_entry_from_what_it_observes(case):
    """``_attention`` hands the product to the packed kernels only where
    every condition holds; a head of 128, an odd local head count, a dense
    mask, the sequence sharded over ``sp``, a sequence that streams and
    attention dropout each keep the (b, heads, s, head_dim) path: its
    transposes are in the lowered text and the kernels (where that path
    has them) take one head's (rows, head_dim) operands."""
    if case == "sp-2" and len(jax.devices()) < 2:
        pytest.skip("needs two virtual devices")
    kw = _OLD_PATH[case]
    text = _attention_lowered(**kw)
    heads, seq, d = kw["heads"], kw["seq"], kw["head_dim"]
    packed_operand = f"tensor<1x{seq}x{heads * 3 * d}xbf16>"
    kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
    if case == "packed":
        assert "stablehlo.transpose" not in text
        assert len(kernels) == 1 and packed_operand in kernels[0], kernels
        return
    assert "stablehlo.transpose" in text
    assert not any(packed_operand in line for line in kernels), kernels
    if case in ("head-128", "odd-heads", "streams", "dropout"):
        assert len(kernels) == 1
        assert f"tensor<{heads}x{seq}x{d}xbf16>" in kernels[0], kernels
    if case == "mask":
        assert not kernels
