"""apex_tpu.comm unit tests — the int8 codec, the EF state round-trip and
the bytes-on-wire accounting, all mesh-free (the collective-level tests
live in tests/test_comm_mesh.py; the wire-byte regression gate in
tests/test_collective_counts.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.comm import (
    CompressionConfig,
    collective_report,
    dequantize_blockwise,
    init_error_feedback,
    quantization_error,
    quantize_blockwise,
)
from apex_tpu.comm import error_feedback as ef


# ---------------------------------------------------------------------------
# codec

def test_quantize_roundtrip_half_step_bound():
    """|x - dq(q(x))| <= scale/2 per element, scale = block absmax/127."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    q, s = quantize_blockwise(x, 256)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert q.shape == (4096,) and s.shape == (16,)
    y = dequantize_blockwise(q, s, 256)
    err = np.abs(np.asarray(y) - np.asarray(x)).reshape(16, 256)
    step = np.abs(np.asarray(x)).reshape(16, 256).max(1) / 127.0
    assert (err <= step[:, None] * 0.5 + 1e-6).all()


def test_quantize_zero_block():
    """All-zero blocks must quantize to zero codes with a finite scale."""
    x = jnp.zeros((512,))
    q, s = quantize_blockwise(x, 256)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.isfinite(np.asarray(s)))
    np.testing.assert_array_equal(
        np.asarray(dequantize_blockwise(q, s, 256)), 0.0)


def test_quantize_per_block_scales_isolate_outliers():
    """A huge element in one block must not destroy resolution elsewhere —
    the point of BLOCKWISE scales vs one per-tensor scale."""
    x = np.random.RandomState(0).normal(size=1024).astype(np.float32)
    x[0] = 1e4
    y = np.asarray(dequantize_blockwise(
        *quantize_blockwise(jnp.asarray(x), 256), 256))
    # the outlier's own block is coarse; the other blocks stay fine-grained
    assert np.abs(y[256:] - x[256:]).max() < np.abs(x[256:]).max() / 100.0


def test_quantize_validates():
    with pytest.raises(ValueError):
        quantize_blockwise(jnp.zeros((100,)), 256)  # not a block multiple
    with pytest.raises(ValueError):
        quantize_blockwise(jnp.zeros((4, 64)), 64)  # not flat
    with pytest.raises(ValueError):
        quantize_blockwise(jnp.zeros((256,)), 256, stochastic=True)  # no seed
    with pytest.raises(ValueError):
        # pallas path needs lane-aligned blocks
        quantize_blockwise(jnp.zeros((256,)), 64, use_pallas=True)


def test_stochastic_rounding_unbiased_and_seeded():
    x = jnp.full((256,), 0.3)
    outs = []
    for seed in range(64):
        q, s = quantize_blockwise(x, 256, stochastic=True, seed=seed)
        outs.append(np.asarray(dequantize_blockwise(q, s, 256)))
    m = float(np.mean(outs))
    assert abs(m - 0.3) < 0.005, m  # unbiased across seeds
    q1, _ = quantize_blockwise(x, 256, stochastic=True, seed=11)
    q2, _ = quantize_blockwise(x, 256, stochastic=True, seed=11)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


def test_pallas_interpret_matches_reference():
    """The kernel and the XLA path are the same codec (codes equal up to
    the 1-ulp scale difference of reassociated maxes)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (32 * 128,))
    q_ref, s_ref = quantize_blockwise(x, 128)
    q_pl, s_pl = quantize_blockwise(x, 128, use_pallas=True)
    np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s_pl),
                               rtol=1e-6)
    assert np.abs(np.asarray(q_ref, np.int32)
                  - np.asarray(q_pl, np.int32)).max() <= 1
    y_ref = dequantize_blockwise(q_pl, s_pl, 128)
    y_pl = dequantize_blockwise(q_pl, s_pl, 128, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pl),
                               rtol=1e-6)


def test_quantization_error_is_the_ef_residual():
    x = jax.random.normal(jax.random.PRNGKey(2), (512,))
    e = quantization_error(x, 256)
    q, s = quantize_blockwise(x, 256)
    want = np.asarray(x) - np.asarray(dequantize_blockwise(q, s, 256))
    # both sides subtract a float32 round trip of x from x, so each is good
    # to an ulp of |x| (1.2e-7 at 1, 2.4e-7 at 2), not of the residual
    np.testing.assert_allclose(
        np.asarray(e), want,
        atol=2 * np.finfo(np.float32).eps * float(np.abs(x).max()))


# ---------------------------------------------------------------------------
# the 4-bit codec


def test_int4_pack_unpack_exact_inverse():
    from apex_tpu.comm import pack_int4, unpack_int4

    q = jax.random.randint(jax.random.PRNGKey(0), (256,), -8, 8
                           ).astype(jnp.int8)
    packed = pack_int4(q)
    assert packed.dtype == jnp.uint8 and packed.shape == (128,)
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(q))
    with pytest.raises(ValueError):
        pack_int4(jnp.zeros((3,), jnp.int8))  # odd axis


def test_int4_roundtrip_half_step_bound():
    """|x - dq(q(x))| <= scale/2 per element, scale = group absmax/7 —
    the 4-bit analogue of the int8 bound (16x coarser steps: why EF
    matters at this tier)."""
    from apex_tpu.comm import (
        dequantize_blockwise_int4,
        quantize_blockwise_int4,
    )

    x = jax.random.normal(jax.random.PRNGKey(3), (4096,))
    q, s = quantize_blockwise_int4(x, 128)
    assert q.dtype == jnp.uint8 and q.shape == (2048,)  # two codes/byte
    assert s.dtype == jnp.float32 and s.shape == (32,)
    y = dequantize_blockwise_int4(q, s, 128)
    err = np.abs(np.asarray(y) - np.asarray(x)).reshape(32, 128)
    step = np.abs(np.asarray(x)).reshape(32, 128).max(1) / 7.0
    assert (err <= step[:, None] * 0.5 + 1e-6).all()
    # all-zero groups: zero codes, finite scales
    q0, s0 = quantize_blockwise_int4(jnp.zeros((256,)), 128)
    assert np.all(np.asarray(q0) == 0)
    np.testing.assert_array_equal(
        np.asarray(dequantize_blockwise_int4(q0, s0, 128)), 0.0)


def test_int4_stochastic_unbiased_and_seeded():
    from apex_tpu.comm import (
        dequantize_blockwise_int4,
        quantize_blockwise_int4,
    )

    x = jnp.full((256,), 0.3)
    outs = []
    for seed in range(64):
        q, s = quantize_blockwise_int4(x, 128, stochastic=True, seed=seed)
        outs.append(np.asarray(dequantize_blockwise_int4(q, s, 128)))
    m = float(np.mean(outs))
    assert abs(m - 0.3) < 0.01, m  # unbiased across seeds
    q1, _ = quantize_blockwise_int4(x, 128, stochastic=True, seed=11)
    q2, _ = quantize_blockwise_int4(x, 128, stochastic=True, seed=11)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


def test_int4_pallas_interpret_matches_reference():
    """The shared Pallas rounding kernels at the ±7 code range: same codec
    as the XLA path up to 1-ulp scale reassociation."""
    from apex_tpu.comm import quantize_blockwise_int4, unpack_int4

    x = jax.random.normal(jax.random.PRNGKey(4), (32 * 128,))
    q_ref, s_ref = quantize_blockwise_int4(x, 128)
    q_pl, s_pl = quantize_blockwise_int4(x, 128, use_pallas=True)
    np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s_pl),
                               rtol=1e-6)
    assert np.abs(np.asarray(unpack_int4(q_ref), np.int32)
                  - np.asarray(unpack_int4(q_pl), np.int32)).max() <= 1


def test_int4_validates():
    from apex_tpu.comm import quantize_blockwise_int4

    with pytest.raises(ValueError):
        quantize_blockwise_int4(jnp.zeros((100,)), 128)  # not a multiple
    with pytest.raises(ValueError):
        quantize_blockwise_int4(jnp.zeros((4, 64)), 64)  # not flat
    with pytest.raises(ValueError):
        quantize_blockwise_int4(jnp.zeros((254,)), 127)  # odd group
    with pytest.raises(ValueError):
        quantize_blockwise_int4(jnp.zeros((256,)), 128,
                                stochastic=True)  # no seed


def test_int4_wire_models():
    """The packed-payload wire math: codes at 0.5 B/elem + fp32 scales,
    and the modeled fp32/int4 allreduce ratio clears the acceptance gate
    (>=6.5x; 7.53x at group 128)."""
    from apex_tpu.comm import allreduce_wire_bytes, psum_scatter_wire_bytes

    cfg = CompressionConfig(policy="int4_ef", block_size=128,
                            min_elements=128)
    n, w = 4096, 8
    fp32 = allreduce_wire_bytes(n, 4, w, None)
    i4 = allreduce_wire_bytes(n, 4, w, cfg)
    # two passes of (n/2 codes + 4n/128 scales), ring-scaled
    assert i4 == pytest.approx(2.0 * (n / 2 + 4.0 * n / 128) * (w - 1) / w)
    assert fp32 / i4 >= 6.5, fp32 / i4
    rs4 = psum_scatter_wire_bytes(n, 4, w, cfg, shard_multiple=128)
    assert rs4 == pytest.approx((n / 2 + 4.0 * n / 128) * (w - 1) / w)
    # sub-min_elements buffers fall back to the fp32 path
    assert allreduce_wire_bytes(64, 4, w, cfg) == \
        allreduce_wire_bytes(64, 4, w, None)


# ---------------------------------------------------------------------------
# config

def test_compression_config_validates():
    with pytest.raises(ValueError):
        CompressionConfig(policy="int2")  # not a codec tier
    with pytest.raises(ValueError):
        CompressionConfig(block_size=0)
    with pytest.raises(ValueError):
        CompressionConfig(policy="int4", block_size=129)  # odd group
    cfg = CompressionConfig(policy="int8_ef", min_elements=100)
    assert cfg.enabled and cfg.error_feedback and cfg.bits == 8
    assert cfg.compresses(100) and not cfg.compresses(99)
    assert not CompressionConfig(policy="none").enabled
    cfg4 = CompressionConfig(policy="int4_ef", block_size=128)
    assert cfg4.enabled and cfg4.error_feedback and cfg4.bits == 4
    # packed codes at 0.5 B/elem + fp32 scale per group
    assert cfg4.payload_bytes(4096) == 4096 * 0.5 + 4 * 4096 / 128


# ---------------------------------------------------------------------------
# error-feedback state

def test_error_feedback_state_dict_roundtrip():
    grads = {"layer": {"w": jnp.ones((3, 4), jnp.bfloat16),
                       "b": jnp.zeros((7,))},
             "head": jnp.full((2,), 0.5)}
    r = init_error_feedback(grads)
    # residuals are fp32 regardless of grad dtype
    assert all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(r))
    r = jax.tree_util.tree_map(
        lambda x: x + np.random.RandomState(0).normal(size=x.shape), r)
    d = ef.state_dict(r)
    r2 = ef.load_state_dict(init_error_feedback(grads), d)
    for a, b in zip(jax.tree_util.tree_leaves(r),
                    jax.tree_util.tree_leaves(r2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_error_feedback_load_rejects_mismatch():
    r = init_error_feedback({"a": jnp.zeros((4,)), "b": jnp.zeros((2,))})
    d = ef.state_dict(r)
    with pytest.raises(ValueError):  # different structure, same leaf count
        ef.load_state_dict(
            init_error_feedback({"a": jnp.zeros((4,)), "c": jnp.zeros((2,))}),
            d)
    with pytest.raises(ValueError):  # same structure, different shapes
        bad = dict(d, treedef=None)
        ef.load_state_dict(
            init_error_feedback({"a": jnp.zeros((4,)), "b": jnp.zeros((3,))}),
            bad)


# ---------------------------------------------------------------------------
# accounting — the HLO pricer itself (compiled-program integration is in
# test_collective_counts.py, which needs the 8-device mesh)

_HLO = """
HloModule test
  %all-reduce.1 = f32[1024]{0} all-reduce(f32[1024]{0} %p), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %ag = s8[4096]{0} all-gather(s8[512]{0} %q), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %a2a = (s8[128]{0}, s8[128]{0}, /*index=2*/s8[128]{0}, s8[128]{0}) all-to-all(s8[128]{0} %a, s8[128]{0} %b, s8[128]{0} %c, s8[128]{0} %d), replica_groups={{0,1,2,3}}
  %rs = f32[64]{0} reduce-scatter(f32[512]{0} %x), replica_groups=[1,8]<=[8], dimensions={0}
  %start = bf16[256]{0} all-reduce-start(bf16[256]{0} %y), replica_groups={{0,1}}
  %done = bf16[256]{0} all-reduce-done(bf16[256]{0} %start)
  %gte = s8[128]{0} get-tuple-element((s8[128]{0}, s8[128]{0}) %all-to-all.9), index=0
  %cp = f32[32]{0} collective-permute(f32[32]{0} %z), source_target_pairs={{0,1}}
"""


def test_accounting_counts_and_prices():
    rep = collective_report(_HLO)
    assert rep.counts == {"all-reduce": 2, "all-gather": 1,
                          "reduce-scatter": 1, "all-to-all": 1,
                          "collective-permute": 1}
    # all-reduce: 2*4096*(7/8) + 2*512*(1/2); gather: 4096*(7/8);
    # a2a: 512*(3/4); rs: 256*7; permute: 128
    assert rep.wire_bytes_by_kind["all-reduce"] == pytest.approx(
        2 * 4096 * 7 / 8 + 2 * 512 * 1 / 2)
    assert rep.wire_bytes_by_kind["all-gather"] == pytest.approx(
        4096 * 7 / 8)
    assert rep.wire_bytes_by_kind["all-to-all"] == pytest.approx(512 * 3 / 4)
    assert rep.wire_bytes_by_kind["reduce-scatter"] == pytest.approx(256 * 7)
    assert rep.wire_bytes_by_kind["collective-permute"] == pytest.approx(128)
    assert rep.wire_bytes == pytest.approx(sum(
        rep.wire_bytes_by_kind.values()))


def test_accounting_single_device_groups_are_free():
    rep = collective_report(
        "%ar = f32[64]{0} all-reduce(f32[64]{0} %p), replica_groups={{0}}")
    assert rep.counts["all-reduce"] == 1
    assert rep.wire_bytes == 0.0


# ---------------------------------------------------------------------------
# async-emitted HLO (what the TPU latency-hiding scheduler produces, and
# what comm.overlap's decomposed rings make common): the '-start' result is
# a TUPLE aliasing the operand next to the output plus u32[] context
# scalars, so pricing it like a sync result double-charges — the pricer
# must price '-start' ops from their operands, once.

_ASYNC_HLO = """
HloModule async_test, is_scheduled=true

ENTRY %main (p0: f32[16,32], p1: f32[32,8]) -> f32[16,8] {
  %p0 = f32[16,32]{1,0} parameter(0)
  %p1 = f32[32,8]{1,0} parameter(1)
  %collective-permute-start.1 = (f32[16,32]{1,0}, f32[16,32]{1,0}, u32[], u32[]) collective-permute-start(f32[16,32]{1,0} %p0), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %dot.1 = f32[16,8]{1,0} dot(f32[16,32]{1,0} %p0, f32[32,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %collective-permute-done.1 = f32[16,32]{1,0} collective-permute-done((f32[16,32]{1,0}, f32[16,32]{1,0}, u32[], u32[]) %collective-permute-start.1)
  %dot.2 = f32[16,8]{1,0} dot(f32[16,32]{1,0} %collective-permute-done.1, f32[32,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %all-gather-start.1 = (f32[16,8]{1,0}, f32[64,8]{1,0}) all-gather-start(f32[16,8]{1,0} %dot.2), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}
  %all-gather-done.1 = f32[64,8]{1,0} all-gather-done((f32[16,8]{1,0}, f32[64,8]{1,0}) %all-gather-start.1)
  ROOT %add.1 = f32[16,8]{1,0} add(f32[16,8]{1,0} %dot.1, f32[16,8]{1,0} %dot.2)
}
"""


def test_accounting_async_start_priced_once_from_operands():
    rep = collective_report(_ASYNC_HLO)
    # one pair each, counted once at the '-start'
    assert rep.counts["collective-permute"] == 1, rep
    assert rep.counts["all-gather"] == 1, rep
    # cp: ONE hop of the f32[16,32] operand = 2048 bytes — NOT the start
    # tuple's 2*2048 + 8 (operand alias + u32 contexts double-charge)
    assert rep.wire_bytes_by_kind["collective-permute"] == pytest.approx(
        2048)
    # ag: sync result reconstructed as operand*W -> 64*8*4 * (3/4)
    assert rep.wire_bytes_by_kind["all-gather"] == pytest.approx(
        64 * 8 * 4 * 3 / 4)


def test_overlap_report_async_windows():
    from apex_tpu.comm import overlap_report

    rep = overlap_report(_ASYNC_HLO)
    # dot.1 is scheduled inside the start.1/done.1 window -> hidden
    assert rep.async_pairs == 1 and rep.async_hidden == 1, rep
    assert rep.hidden_wire_bytes == pytest.approx(2048)
    assert rep.exposed_wire_bytes == 0.0, rep
    # removing the in-window dot exposes the permute
    exposed = overlap_report(_ASYNC_HLO.replace(
        "  %dot.1 = f32[16,8]{1,0} dot(f32[16,32]{1,0} %p0, "
        "f32[32,8]{1,0} %p1), lhs_contracting_dims={1}, "
        "rhs_contracting_dims={0}\n", ""))
    assert exposed.async_hidden == 0, exposed
    assert exposed.exposed_wire_bytes == pytest.approx(2048)


_SYNC_RING_HLO = """
ENTRY %main (p0: f32[16,32], p1: f32[32,8]) -> f32[16,8] {
  %p0 = f32[16,32]{1,0} parameter(0)
  %p1 = f32[32,8]{1,0} parameter(1)
  %collective-permute.1 = f32[16,32]{1,0} collective-permute(f32[16,32]{1,0} %p0), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %dot.1 = f32[16,8]{1,0} dot(f32[16,32]{1,0} %p0, f32[32,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %dot.2 = f32[16,8]{1,0} dot(f32[16,32]{1,0} %collective-permute.1, f32[32,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %add.1 = f32[16,8]{1,0} add(f32[16,8]{1,0} %dot.1, f32[16,8]{1,0} %dot.2)
}
"""


def test_overlap_report_sync_independence():
    """Pre-schedule/CPU modules emit synchronous collective-permute; a hop
    counts as hideable iff some dot neither feeds it nor consumes it."""
    from apex_tpu.comm import overlap_report

    rep = overlap_report(_SYNC_RING_HLO)
    # dot.1 is independent of the permute (dot.2 consumes it)
    assert rep.sync_permutes == 1 and rep.sync_hidden == 1, rep
    # drop the independent dot: the only remaining dot DEPENDS on the
    # permute -> nothing a scheduler could overlap
    dep_only = overlap_report(_SYNC_RING_HLO.replace(
        "  %dot.1 = f32[16,8]{1,0} dot(f32[16,32]{1,0} %p0, "
        "f32[32,8]{1,0} %p1), lhs_contracting_dims={1}, "
        "rhs_contracting_dims={0}\n", "").replace(
        "f32[16,8]{1,0} %dot.1", "f32[16,8]{1,0} %dot.2"))
    assert dep_only.sync_permutes == 1 and dep_only.sync_hidden == 0, \
        dep_only


def test_overlap_report_fusion_wrapped_dot_counts():
    """On TPU the partial GEMMs ride inside fusions — a fusion calling a
    dot-bearing computation must count as a dot for the window check."""
    from apex_tpu.comm import overlap_report

    hlo = """
%fused_dot (pa: f32[16,32], pb: f32[32,8]) -> f32[16,8] {
  %pa = f32[16,32]{1,0} parameter(0)
  %pb = f32[32,8]{1,0} parameter(1)
  ROOT %dot.9 = f32[16,8]{1,0} dot(f32[16,32]{1,0} %pa, f32[32,8]{1,0} %pb), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main (p0: f32[16,32], p1: f32[32,8]) -> f32[16,8] {
  %p0 = f32[16,32]{1,0} parameter(0)
  %p1 = f32[32,8]{1,0} parameter(1)
  %collective-permute-start.1 = (f32[16,32]{1,0}, f32[16,32]{1,0}, u32[], u32[]) collective-permute-start(f32[16,32]{1,0} %p0), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %fusion.1 = f32[16,8]{1,0} fusion(f32[16,32]{1,0} %p0, f32[32,8]{1,0} %p1), kind=kOutput, calls=%fused_dot
  %collective-permute-done.1 = f32[16,32]{1,0} collective-permute-done((f32[16,32]{1,0}, f32[16,32]{1,0}, u32[], u32[]) %collective-permute-start.1)
  ROOT %tail = f32[16,8]{1,0} add(f32[16,8]{1,0} %fusion.1, f32[16,8]{1,0} %fusion.1)
}
"""
    rep = overlap_report(hlo)
    assert rep.async_pairs == 1 and rep.async_hidden == 1, rep


def test_overlap_wire_models_match_ring_shape():
    """The comm.overlap byte models must equal the monolithic collective
    models — the decomposition is wire-neutral by design: (W-1) hops of
    one shard vs the ring cost of the fused collective."""
    from apex_tpu.comm import (
        all_gather_matmul_wire_bytes,
        all_gather_wire_bytes,
        allreduce_wire_bytes,
        matmul_all_reduce_wire_bytes,
        matmul_reduce_scatter_wire_bytes,
    )

    w, shard, item = 8, 16 * 128, 4
    full = shard * w
    assert all_gather_matmul_wire_bytes(shard, item, w) == pytest.approx(
        all_gather_wire_bytes(full, item, w))
    # monolithic reduce-scatter: result shard bytes * (W-1)
    assert matmul_reduce_scatter_wire_bytes(shard, item, w) == \
        pytest.approx(float(shard) * item * (w - 1))
    assert matmul_all_reduce_wire_bytes(shard, item, w) == pytest.approx(
        allreduce_wire_bytes(full, item, w, None))
    for fn in (all_gather_matmul_wire_bytes,
               matmul_reduce_scatter_wire_bytes,
               matmul_all_reduce_wire_bytes):
        assert fn(shard, item, 1) == 0.0
