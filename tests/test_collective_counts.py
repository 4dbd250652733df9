"""Collective-count regression guards for the compiled SPMD programs.

An accidental extra all-gather in a TP block or a psum that stops fusing
is a silent perf bug — the program stays correct and slower. These tests
compile the tp=2 GPT grad program on the virtual mesh and bound the
collective counts (loose bounds: XLA may legally fuse/split a few), plus
assert the *semantic* shape of Megatron-SP: it must replace TP-block
boundary all-reduces with all-gather (entry ``g``) / reduce-scatter
(exit ``ḡ``) pairs — their presence is the feature.

Measured at pin time (2 layers, tp=2, dp=4): 35 all-reduces plain
(TP psums + per-param dp grad psums from the shard_map transpose +
loss replication); 33 AR + 8 AG + 7 RS under megatron_sp.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.parallel.mesh import build_mesh
from apex_tpu.transformer.pipeline_parallel.schedules.common import (
    replicate_loss,
)
from apex_tpu.transformer.testing import (
    GPTConfig,
    gpt_loss,
    gpt_param_specs,
    init_gpt_params,
)

BASE = GPTConfig(vocab_size=256, max_seq=64, hidden=128, num_layers=2,
                 num_heads=2, dtype=jnp.bfloat16)


def _compiled_text(megatron_sp: bool, overlap_comm: bool = False) -> str:
    """Compiled flagship tp=2 grad-program HLO on the virtual mesh."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=2, pp=1, sp=1, dp=4)
    cfg = dataclasses.replace(BASE, megatron_sp=megatron_sp,
                              overlap_comm=overlap_comm)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((4, 64), jnp.int32)

    def loss(p, t, y):
        def body(p, a, b):
            return replicate_loss(gpt_loss(p, a, b, cfg), mesh,
                                  masked_axis=None)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(gpt_param_specs(cfg), P("dp"), P("dp")),
            out_specs=P())(p, t, y)

    return jax.jit(jax.grad(loss)).lower(params, tok, tok).compile().as_text()


def _counts(megatron_sp: bool):
    txt = _compiled_text(megatron_sp)
    return {k: len(re.findall(k, txt)) for k in
            ("all-reduce", "all-gather", "reduce-scatter")}


def test_tp_program_collective_budget():
    c = _counts(megatron_sp=False)
    assert c["all-reduce"] <= 42, c
    # plain TP has no sequence resharding: gathers/scatters would mean a
    # sharding annotation leaked
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0, c


def test_moe_dispatch_rides_all_to_all():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=2, pp=1, sp=1, dp=4)
    cfg = dataclasses.replace(BASE, num_experts=4, moe_top_k=2)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((4, 64), jnp.int32)

    def loss(p, t, y):
        def body(p, a, b):
            return replicate_loss(gpt_loss(p, a, b, cfg), mesh,
                                  masked_axis=None)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(gpt_param_specs(cfg), P("dp"), P("dp")),
            out_specs=P())(p, t, y)

    txt = jax.jit(jax.grad(loss)).lower(params, tok, tok).compile().as_text()
    c = {k: len(re.findall(k, txt)) for k in
         ("all-gather", "all-to-all")}
    # expert dispatch/combine must be all_to_all over the ep(=dp) axis —
    # a fallback to gather-everything would be a silent traffic blow-up
    assert c["all-to-all"] >= 4, c
    assert c["all-to-all"] <= 44, c
    assert c["all-gather"] == 0, c


def test_megatron_sp_uses_gather_scatter_pairs():
    c = _counts(megatron_sp=True)
    # the feature itself: TP-block entry all-gathers + exit reduce-scatters
    assert c["all-gather"] >= 4, c
    assert c["reduce-scatter"] >= 4, c
    assert c["all-gather"] <= 12 and c["reduce-scatter"] <= 11, c
    assert c["all-reduce"] <= 40, c


# ---------------------------------------------------------------------------
# bytes-on-wire: counts guard the program SHAPE; the comm subsystem's claim
# is about BYTES, so it is asserted from the same compiled-HLO source of
# truth via apex_tpu.comm.accounting's ring-model pricer.


def _ddp_grad_program(compression, allreduce_always_fp32):
    """Compiled dp=8 GPT grad+allreduce step (the GPT-2 DP fixture)."""
    from apex_tpu.comm import collective_report
    from apex_tpu.parallel import DistributedDataParallel

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=1, pp=1, sp=1)  # dp=8
    cfg = dataclasses.replace(BASE, dtype=jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((8, 64), jnp.int32)
    ddp = DistributedDataParallel(
        compression=compression,
        allreduce_always_fp32=allreduce_always_fp32)

    def step(p, t, y):
        g = jax.grad(lambda p: gpt_loss(p, t, y, cfg))(ddp.replicate(p))
        return ddp.average_gradients(g)

    specs = jax.tree_util.tree_map(lambda _: P(), params)
    compiled = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(specs, P("dp"), P("dp")),
        out_specs=specs, check_vma=False,
    )).lower(params, tok, tok).compile()
    return collective_report(compiled)


def assert_overlapped(hlo, min_hidden: int = 1):
    """The comm/compute-overlap acceptance gate, from the compiled HLO (the
    repo's prove-it-from-the-program methodology — a CPU test box has no
    device to profile).

    On a SCHEDULED module (TPU: async ``collective-permute-start``/``-done``
    pairs) this demands ≥1 pair with a ``dot`` scheduled inside the
    start→done window — execution-order proof that the hop travels behind a
    GEMM. On pre-schedule/CPU modules (synchronous ``collective-permute``)
    it demands hops with data-INDEPENDENT dots — the eligibility a
    latency-hiding scheduler needs; a monolithic collective→matmul chain
    has no permutes at all and fails immediately. Returns the
    :class:`~apex_tpu.comm.OverlapReport` for further assertions."""
    from apex_tpu.comm import overlap_report

    rep = overlap_report(hlo)
    assert rep.permutes > 0, f"no collective-permute rings in program: {rep}"
    assert rep.hidden >= min_hidden, rep
    if rep.async_pairs:  # scheduled module: the window proof must hold
        assert rep.async_hidden >= 1, rep
    return rep


@pytest.mark.parametrize("megatron_sp", [False, True])
def test_flagship_overlap_comm_decomposed_and_proven(megatron_sp):
    """overlap_comm=True on the flagship tp=2 program (plain TP and
    Megatron-SP): the TP-boundary collectives must actually decompose into
    ppermute rings (the monolithic op counts DROP, permutes appear) and
    the rings must be overlap-eligible/proven per assert_overlapped."""
    from apex_tpu.comm import collective_report

    txt_off = _compiled_text(megatron_sp)
    txt_on = _compiled_text(megatron_sp, overlap_comm=True)
    off = collective_report(txt_off)
    on = collective_report(txt_on)
    # the decomposition happened: permute rings replace monolithic ops
    assert off.counts["collective-permute"] == 0, off
    assert on.counts["collective-permute"] >= 4, on
    if megatron_sp:
        # the SP entry/exit all-gather+reduce-scatter pairs became rings
        # (the embedding exit / LM-head entry keep their monolithic ops)
        assert on.counts["all-gather"] < off.counts["all-gather"], (on, off)
        assert on.counts["reduce-scatter"] < off.counts["reduce-scatter"], \
            (on, off)
    else:
        # the row-parallel exit psums became rings
        assert on.counts["all-reduce"] < off.counts["all-reduce"], (on, off)
    rep = assert_overlapped(txt_on, min_hidden=2)
    # the overwhelming share of ring traffic must be hideable
    assert rep.hidden_fraction >= 0.5, rep


def test_int4_allreduce_wire_byte_reduction_and_model_agreement():
    """The sub-8-bit acceptance gate: the 4-bit EF allreduce must move
    >= 6.5x fewer bytes than fp32 on the same model (theory:
    8 / (1 + 8/group) ~ 7.5x at group 128 — nibble-packed codes at
    0.5 B/elem plus the fp32 scale sidecar), asserted from the compiled
    HLO. The packed-payload wire MODEL must agree with the HLO pricer to
    the byte on a single flat-buffer program."""
    from apex_tpu.comm import (
        CompressionConfig,
        allreduce_wire_bytes,
        collective_report,
        compressed_allreduce,
    )

    cfg = CompressionConfig(policy="int4_ef", block_size=128,
                            min_elements=128)
    fp32 = _ddp_grad_program(None, allreduce_always_fp32=True)
    # the DDP fixture threads no EF state; the wire is policy-identical
    # (EF only adds local element-wise math), so the program ratio is
    # measured on plain int4 and the EF program is priced below
    int4 = _ddp_grad_program(
        CompressionConfig(policy="int4", block_size=128, min_elements=128),
        allreduce_always_fp32=False)
    assert fp32.wire_bytes > 0 and int4.wire_bytes > 0, (fp32, int4)
    # the compressed program really rides the two-pass decomposition
    assert int4.counts["all-to-all"] >= 2, int4
    assert int4.counts["all-gather"] >= 2, int4
    ratio = fp32.wire_bytes / int4.wire_bytes
    assert ratio >= 6.5, (ratio, fp32, int4)

    # model<->HLO agreement on one flat buffer: the pricer reads u8
    # packed codes + f32 scales off the program XLA emitted; the model
    # predicts the same bytes from (n, config) alone
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=1, pp=1, sp=1)  # dp=8
    n = 8192

    def body(flat, r):
        out, r2 = compressed_allreduce(flat, "dp", cfg,
                                       residual=r.reshape(-1))
        return out, r2.reshape(r.shape)

    from jax.sharding import PartitionSpec as P2
    compiled = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P2(), P2("dp")),
        out_specs=(P2(), P2("dp")), check_vma=False,
    )).lower(jnp.zeros((n,)), jnp.zeros((8, n))).compile()
    priced = collective_report(compiled).wire_bytes
    modeled = allreduce_wire_bytes(n, 4, 8, cfg)
    assert priced == pytest.approx(modeled), (priced, modeled)
    # and the EF program itself clears the gate vs a same-shape fp32 psum
    psum = jax.jit(jax.shard_map(
        lambda flat: jax.lax.psum(flat, "dp"), mesh=mesh, in_specs=P2(),
        out_specs=P2(), check_vma=False,
    )).lower(jnp.zeros((n,))).compile()
    fp32_flat = collective_report(psum).wire_bytes
    assert fp32_flat / priced >= 6.5, (fp32_flat, priced)


def test_int8_allreduce_wire_byte_reduction():
    """The comm subsystem's acceptance gate: int8 gradient allreduce must
    move >= 3.5x fewer bytes than the fp32 allreduce on the same model
    (theory: 4 / (1 + 4/block) ~ 3.94x at block 256; the scales' fp32
    sidecar is the only overhead)."""
    from apex_tpu.comm import CompressionConfig

    fp32 = _ddp_grad_program(None, allreduce_always_fp32=True)
    int8 = _ddp_grad_program(
        CompressionConfig(policy="int8", block_size=256, min_elements=256),
        allreduce_always_fp32=False)
    assert fp32.wire_bytes > 0 and int8.wire_bytes > 0, (fp32, int8)
    # the compressed program really rides the two-pass decomposition
    assert int8.counts["all-to-all"] >= 2, int8
    assert int8.counts["all-gather"] >= 2, int8
    ratio = fp32.wire_bytes / int8.wire_bytes
    assert ratio >= 3.5, (ratio, fp32, int8)
