"""AOT TPU-lowering guard for every compiled Pallas kernel path.

``jit(f).trace(args).lower(lowering_platforms=("tpu",))`` runs the full
Pallas→Mosaic lowering — block-shape tiling rules, layout checks, scalar
prefetch plumbing — on a CPU-only box, with no TPU attached. Interpret
mode (what the rest of the CPU suite exercises) skips exactly those
checks, which is how the varlen kernels' seg-id block shape
(``(1, block)`` slice of a ``(b, s)`` array — sublane dim neither
8-divisible nor full) passed 300+ tests while being unlowerable on
hardware (round-4 find; fixed by the jax-flash-style widened id layout,
``attention_varlen._seg_wide``).

Every kernel the TPU smoke (``benchmarks/smoke_tpu.py``) executes on the
chip must lower here first. Reference parity note: the reference compiles
its CUDA kernels at build time (``setup.py:119-630``) so an unbuildable
kernel fails CI without a GPU; this is the TPU analogue.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops._pallas_util import force_compiled


def _lower_tpu(f, *args):
    return jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))


B, H, S, D = 2, 4, 1024, 64


@pytest.fixture()
def qkv():
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (B, H, S, D), jnp.bfloat16)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, H, S, D),
                           jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, H, S, D),
                          jnp.bfloat16)
    return q, kk, v


def _flash_sq_loss(q, k, v):
    from apex_tpu.ops.attention import flash_attention

    o = flash_attention(q, k, v, causal=True, use_pallas=True,
                        interpret=False)
    return jnp.sum(o.astype(jnp.float32) ** 2)


def test_flash_attention_fwd_bwd_causal(qkv):
    with force_compiled():
        _lower_tpu(jax.grad(_flash_sq_loss, argnums=(0, 1, 2)), *qkv)


def test_flash_attention_dropout(qkv):
    from apex_tpu.ops.attention import flash_attention

    q, k, v = qkv

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, use_pallas=True,
                            interpret=False, dropout_rate=0.1,
                            dropout_seed=jnp.int32(7))
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with force_compiled():
        _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


def test_flash_attention_bias(qkv):
    from apex_tpu.ops.attention import flash_attention

    q, k, v = qkv
    bias = jnp.zeros((H, S, S), jnp.float32)

    def loss(q, k, v, bias):
        o = flash_attention(q, k, v, causal=True, bias=bias,
                            use_pallas=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with force_compiled():
        _lower_tpu(jax.grad(loss, argnums=(0, 1, 2, 3)), q, k, v, bias)


def test_flash_attention_unequal_blocks(qkv):
    from apex_tpu.ops.attention import flash_attention

    q, k, v = qkv

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, use_pallas=True,
                            interpret=False, block_q=256, block_k=512)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with force_compiled():
        _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


@pytest.mark.parametrize("seq,schedule", [(S, "resident"),
                                          (8192, "listed")])
def test_flash_attention_compiles_on_both_schedules(seq, schedule):
    """The rows above only *lower* the benchmark cells' shape (s 1024, d 64,
    bf16), which the tile plan runs resident: one whole head a grid step in
    VMEM, three tile bodies unrolled inside each kernel. Here that shape and
    one too long to hold (listed: the live tiles on the grid) go through
    Mosaic's own compiler for a described v5e, where a VMEM overflow or an
    unaligned slice of a resident operand would surface, and all three
    kernels must be in the compiled program once, under the names the trace
    and ``flash_attn_roofline`` read."""
    from apex_tpu.ops._pallas_util import compile_for_tpu, mosaic_calls
    from apex_tpu.ops.attention import _tile_plan

    assert _tile_plan(seq, seq, D, jnp.bfloat16, True,
                      whole_rows=True).schedule == schedule
    q = jax.ShapeDtypeStruct((2, H, seq, D), jnp.bfloat16)
    _, compiled = compile_for_tpu(
        jax.jit(jax.grad(_flash_sq_loss, argnums=(0, 1, 2))), q, q, q)
    # under grad the segment reads jvp(flash_fwd), transpose(jvp(...))
    calls = mosaic_calls(compiled.as_text())
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(n for name, n in calls.items() if kernel in name) == 1, (
            kernel, calls)


@pytest.mark.parametrize("structure,visited", [("blockdiff", 288), ("causal", 528)])
def test_flash_attention_listed_schedule_compiles_at_the_block_diffusion_cells_shape(
        structure, visited):
    """The listed schedule (a ``MaskStructure`` other than causal, or grouped
    heads) at the block-diffusion cell's attention shape: 32 query heads over
    4 key/value heads of 128, 16,384 positions, bf16, on the 1,024 x 1,024
    tiles the plan picks when the call leaves the tile to it (80 and 136 live
    tiles a head, where 512 x 512 has ``visited``). The three
    kernels go through Mosaic's own compiler for a described v5e
    (scalar-prefetched tile tables, the lane-dense lse and its in-kernel
    transposes, dK and dV summed over a group) under the names the trace and
    ``blockdiff_attn_roofline`` read; lse and delta cost 32 MiB, not the GiB
    a column layout pads to."""
    from apex_tpu.ops._pallas_util import compile_for_tpu, mosaic_calls
    from apex_tpu.ops.attention import (CAUSAL, _tile_plan, block_diffusion_mask,
                                        flash_attention)

    mask = block_diffusion_mask(4) if structure == "blockdiff" else CAUSAL
    plan = _tile_plan(16384, 16384, 128, jnp.bfloat16, mask, group=8)
    assert (plan.schedule, plan.visited) == ("listed", visited)
    plan = _tile_plan(16384, 16384, 128, jnp.bfloat16, mask, None, None, group=8)
    assert (plan.schedule, plan.block_q, plan.block_k, plan.visited) == (
        "listed", 1024, 1024, {288: 80, 528: 136}[visited])

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, structure=mask).astype(jnp.float32) ** 2)

    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)
    _, compiled = compile_for_tpu(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, kv, kv)
    calls = mosaic_calls(compiled.as_text())
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(n for name, n in calls.items() if kernel in name) == 1, (kernel, calls)
    dq, dk, dv = compiled.output_shardings and jax.eval_shape(
        jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert dq.shape == q.shape and dk.shape == dv.shape == kv.shape
    # q, its gradient, o and do are 128 MiB each; the statistics must stay small
    assert compiled.memory_analysis().temp_size_in_bytes < 400 * 2**20


@pytest.mark.parametrize("kind,heads,visited", [("sliding", 72, None), ("full", 48, 136)])
def test_flash_attention_listed_schedule_compiles_at_the_window_cells_shape(kind, heads, visited):
    """Laguna's attention at the window cell's shape: 72 (sliding) or 48
    (full) query heads over 8 key/value heads of 128, 8,192 positions, bf16.
    The sliding layers' grid lists only the window's live tiles (a q tile's
    own and the one or two before it: 31 at 512 x 512, 93 at 256 x 256), the
    full layers' the 136 on or under the diagonal; the three kernels compile
    for a described v5e, once each."""
    from apex_tpu.ops._pallas_util import compile_for_tpu, mosaic_calls
    from apex_tpu.ops.attention import CAUSAL, _tile_plan, flash_attention, sliding_window_mask
    from apex_tpu.transformer.laguna import WINDOW_BLOCK

    block = WINDOW_BLOCK if kind == "sliding" else 512
    mask = sliding_window_mask(512) if kind == "sliding" else CAUSAL
    plan = _tile_plan(8192, 8192, 128, jnp.bfloat16, mask, block, block, group=heads // 8)
    n = 8192 // block
    want = {512: 31, 256: 93}[block] if visited is None else visited
    assert (plan.schedule, plan.block_q, plan.visited, plan.rectangle) == ("listed", block, want, n * n)

    def loss(q, k, v):
        o = flash_attention(q, k, v, structure=mask, block_q=block, block_k=block,
                            scale=128 ** -0.5)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    q = jax.ShapeDtypeStruct((1, heads, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16)
    _, compiled = compile_for_tpu(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, kv, kv)
    calls = mosaic_calls(compiled.as_text())
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(c for call, c in calls.items() if name in call) == 1, (name, calls)


def test_flash_attention_listed_schedule_compiles_at_smallthinkers_window():
    """SmallThinker's window layers at their cell's shape: 28 query heads
    over 4 key/value heads of 128, 16,384 positions, a window of 4,096, bf16,
    on the program's tile (``smallthinker.WINDOW_BLOCK``): 252 live tiles a
    head at 512 x 512 of the 1,024, 56 of them masked; the three kernels
    compile for a described v5e, once each."""
    from apex_tpu.ops._pallas_util import compile_for_tpu, mosaic_calls
    from apex_tpu.ops.attention import _tile_plan, flash_attention, sliding_window_mask
    from apex_tpu.transformer.smallthinker import WINDOW_BLOCK

    block, mask = WINDOW_BLOCK, sliding_window_mask(4096)
    plan = _tile_plan(16384, 16384, 128, jnp.bfloat16, mask, block, block, group=7)
    want = {512: (252, 56), 1024: (70, 28)}[block]
    assert (plan.schedule, (plan.visited, plan.masked)) == ("listed", want)

    def loss(q, k, v):
        o = flash_attention(q, k, v, structure=mask, block_q=block, block_k=block,
                            scale=128 ** -0.5)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    q = jax.ShapeDtypeStruct((1, 28, 16384, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)
    _, compiled = compile_for_tpu(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, kv, kv)
    calls = mosaic_calls(compiled.as_text())
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(c for call, c in calls.items() if name in call) == 1, (name, calls)


@pytest.mark.parametrize("heads,seq,d,dv,schedule", [
    (16, 16384, 192, 128, "listed"), (4, 1024, 24, 16, "resident")])
def test_flash_attention_compiles_with_a_value_width_of_its_own(heads, seq, d, dv, schedule):
    """Keys of 192 over values of 128 at the latent-attention cell's shape
    (16 heads, 16,384 positions, causal, bf16: the listed schedule, on the
    1,024 x 1,024 tiles the plan picks when the call leaves the tile to it,
    whose kernels it counts inside the scoped VMEM), and a small pair on the
    resident one (512), through Mosaic's own compiler for a
    described v5e: 192 is not a multiple of the 128 lanes, and nothing is
    padded in HBM to make it one (q, k and their gradients keep 192, v, o and
    their gradients 128). All three kernels are in the compiled program once,
    under the names the trace and ``mla_attn_roofline`` read. At the cell's
    shape lse and delta are lane-dense rows: as (rows, 1) float32 columns
    each would be padded to 128 lanes, 128 MiB (the streamed schedule's
    program planned 640 MiB of temporaries, this one 384)."""
    from apex_tpu.ops._pallas_util import compile_for_tpu, mosaic_calls
    from apex_tpu.ops.attention import _tile_plan, flash_attention

    tile = {"listed": 1024, "resident": 512}[schedule]
    plan = _tile_plan(seq, seq, d, jnp.bfloat16, True, None, None, whole_rows=True, dv=dv)
    assert (plan.schedule, plan.block_q, plan.block_k) == (schedule, tile, tile)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, scale=0.114721)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    q = jax.ShapeDtypeStruct((1, heads, seq, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, heads, seq, dv), jnp.bfloat16)
    grad = jax.grad(loss, argnums=(0, 1, 2))
    _, compiled = compile_for_tpu(jax.jit(grad), q, q, v)
    calls = mosaic_calls(compiled.as_text())
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(n for name, n in calls.items() if kernel in name) == 1, (kernel, calls)
    dq, dk, dvv = jax.eval_shape(grad, q, q, v)
    assert dq.shape == dk.shape == q.shape and dvv.shape == v.shape
    assert compiled.memory_analysis().temp_size_in_bytes < 448 * 2**20


# Characters of the lowered text (StableHLO with the three kernels' Mosaic
# payloads) of one ``jax.checkpoint``ed flash forward-and-backward at the
# benchmark cells' shape (16 x 20 heads, s 1024, d 64, bf16, causal), at the
# commit before the resident schedule (25a2ffd: streamed, one tile body a
# kernel) and with it (three unrolled bodies a kernel). The checkout's path
# is in the payloads' locations and moves either by a few hundred.
_FLASH_LOWERED_CHARS_STREAMED = 26_047
_FLASH_LOWERED_CHARS_RESIDENT = 22_947
# The packed entry's kernels (PR 36: the same three bodies a kernel, called
# through refs that zero the neighbour head's half of a 128-lane block and
# write a result over its own), the same 16 x 20 heads as one (16, 1024, 3840)
# product.
_FLASH_LOWERED_CHARS_PACKED = 30_842

# products a tile body holds: s and p @ v; s, dp and ds @ k; s, dp, p.T @ do and ds.T @ q
_DOTS_A_TILE = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}


def _packed_sq_loss(qkv):
    from apex_tpu.ops.attention import flash_attention_packed

    o = flash_attention_packed(qkv, 20, causal=True, use_pallas=True, interpret=False)
    return jnp.sum(o.astype(jnp.float32) ** 2)


def _tile_bodies(fn, *args):
    """{kernel name: tile bodies its program holds}, counted as the matrix
    products in each ``pallas_call``'s own jaxpr over the products a tile."""
    def dots(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == "dot_general"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += dots(sub)
        return n

    bodies = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                bodies[name] = dots(eqn.params["jaxpr"]) / _DOTS_A_TILE[name]
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    with force_compiled():
        walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return bodies


@pytest.mark.parametrize("entry", ["heads-major", "packed"])
def test_flash_program_size_at_the_cells_shape(entry):
    """Every unrolled tile body is traced, lowered, hashed into the compile
    cache's key and loaded at each warm set-up, and compiled at each cold
    one: PR 26's 20 bodies a kernel cost ``setup_s`` 12% at gpt2-medium and
    the PR with it. The program's size is countable here: the lowered text
    may not pass 1.25 times the streamed program's, so a later PR that
    unrolls more is told before a chip is; and no kernel holds more tile
    bodies than ``_RESIDENT_MAX_BODIES``: the packed kernels hold a pair of
    heads a block and still one set of bodies."""
    from apex_tpu.ops.attention import _RESIDENT_MAX_BODIES, _tile_plan, packed_plan

    plan = _tile_plan(S, S, D, jnp.bfloat16, True)
    assert plan.schedule == "resident"
    assert plan.bodies == 3 <= _RESIDENT_MAX_BODIES
    if entry == "packed":
        assert packed_plan(S, 20, D, jnp.bfloat16, True) == plan
        loss, args = _packed_sq_loss, (jax.ShapeDtypeStruct((16, S, 20 * 3 * D), jnp.bfloat16),)
        argnums, recorded = 0, _FLASH_LOWERED_CHARS_PACKED
    else:
        loss, args = _flash_sq_loss, (jax.ShapeDtypeStruct((16, 20, S, D), jnp.bfloat16),) * 3
        argnums, recorded = (0, 1, 2), _FLASH_LOWERED_CHARS_RESIDENT
    grad = jax.grad(jax.checkpoint(loss), argnums=argnums)
    with force_compiled():
        text = _lower_tpu(grad, *args).as_text()
    assert text.count("tpu_custom_call") >= 3
    assert len(text) <= 1.25 * _FLASH_LOWERED_CHARS_STREAMED, (
        len(text), _FLASH_LOWERED_CHARS_STREAMED, recorded)
    bodies = _tile_bodies(grad, *args)
    assert bodies == {"flash_fwd": 3, "flash_bwd_dq": 3, "flash_bwd_dkv": 3}, bodies
    assert max(bodies.values()) <= _RESIDENT_MAX_BODIES


def test_varlen_fwd_bwd(qkv):
    from apex_tpu.ops.attention_varlen import flash_attention_varlen

    q, k, v = qkv
    # two packed sequences + trailing pad per row
    seg = jnp.where(jnp.arange(S) < 600, 0,
                    jnp.where(jnp.arange(S) < 1000, 1, -1))
    seg = jnp.broadcast_to(seg, (B, S)).astype(jnp.int32)

    def loss(q, k, v):
        o = flash_attention_varlen(q, k, v, seg, causal=True,
                                   use_pallas=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with force_compiled():
        _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


def test_varlen_sub128_seq_lowers_or_falls_back():
    """seqs divisible by 8 but not 128 (reviewer repro: s=192): the widened
    seg-id lane layout forbids sub-128 kv blocks, so the picker must choose
    one full-seq block (legal: block == array dim) — and the forced Pallas
    path must lower."""
    from apex_tpu.ops.attention_varlen import flash_attention_varlen

    s = 192
    q = jnp.zeros((B, H, s, D), jnp.bfloat16)
    seg = jnp.zeros((B, s), jnp.int32)

    def loss(q):
        o = flash_attention_varlen(q, q, q, seg, causal=True,
                                   use_pallas=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with force_compiled():
        _lower_tpu(jax.grad(loss), q)


@pytest.mark.parametrize("s", [100, 2056])
def test_varlen_misaligned_seq_pads_and_lowers(s):
    """Seqs with no legal block pad to the next 128-multiple with seg=-1
    instead of raising (s=100) or silently falling back to the dense
    O(s^2) reference (s=2056: 8-aligned, not 128-divisible, past the
    one-block VMEM cap — the advisor's repro). The padded dispatch must
    lower for TPU end to end, fwd + bwd."""
    from apex_tpu.ops.attention_varlen import flash_attention_varlen

    q = jnp.zeros((B, H, s, D), jnp.bfloat16)
    seg = jnp.zeros((B, s), jnp.int32)

    def loss(q):
        o = flash_attention_varlen(q, q, q, seg, causal=True,
                                   use_pallas=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with force_compiled():
        _lower_tpu(jax.grad(loss), q)


def test_varlen_bad_head_dim_raises_when_forced():
    from apex_tpu.ops.attention_varlen import flash_attention_varlen

    q = jnp.zeros((B, H, 256, 12), jnp.bfloat16)  # head_dim % 8 != 0
    seg = jnp.zeros((B, 256), jnp.int32)
    with pytest.raises(ValueError, match="head_dim"):
        flash_attention_varlen(q, q, q, seg, use_pallas=True)


def test_varlen_unfixable_block_hint_raises_not_recurses():
    """Padding cannot fix a block hint < 8 on an already-aligned seq; the
    dispatcher must raise (reviewer find: it used to recurse forever)."""
    from apex_tpu.ops.attention_varlen import flash_attention_varlen

    q = jnp.zeros((B, H, 256, D), jnp.bfloat16)
    seg = jnp.zeros((B, 256), jnp.int32)
    with pytest.raises(ValueError, match="block"):
        flash_attention_varlen(q, q, q, seg, use_pallas=True, block_q=7)


def test_interpret_arg_rejected_on_reference_path():
    """interpret= silently ignored on the fallback path was the round-4
    silent-fallback trap; both entry points must reject it loudly."""
    from apex_tpu.ops.attention import flash_attention
    from apex_tpu.ops.attention_varlen import flash_attention_varlen

    q = jnp.zeros((B, H, 256, D), jnp.bfloat16)
    seg = jnp.zeros((B, 256), jnp.int32)
    with pytest.raises(ValueError, match="interpret= only applies"):
        flash_attention(q, q, q, mask=jnp.zeros((256, 256), bool),
                        interpret=False)
    with pytest.raises(ValueError, match="interpret= only applies"):
        flash_attention_varlen(q, q, q, seg, use_pallas=False,
                               interpret=False)


def _ring_loss(op_body, in_specs, x, w):
    """Scalar loss through a shard_map'd decomposed ring — the form whose
    grad program we must be able to AOT-lower for TPU."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.mesh import build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=8, pp=1, sp=1)

    def body(x, w):
        y = op_body(x, w)
        return jax.lax.psum(jnp.sum(y.astype(jnp.float32) ** 2), "tp")

    def loss(x, w):
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=P())(x, w)

    return loss


def test_all_gather_matmul_ring_lowers_for_tpu():
    """AOT TPU lowering of the decomposed all-gather-matmul ring, fwd+bwd
    (the varlen lesson: what only ever EXECUTES on the CPU sim skips every
    platform lowering rule — here the SPMD collective-permute lowering and
    the partitioner's handling of the custom-VJP ring bodies)."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.comm import all_gather_matmul

    x = jnp.zeros((2, 64, 32), jnp.bfloat16)
    w = jnp.zeros((32, 48), jnp.bfloat16)
    for bidir in (False, True):
        loss = _ring_loss(
            lambda a, b, bd=bidir: all_gather_matmul(
                a, b, gather_axis=1, bidirectional=bd),
            (P(None, "tp", None), P(None, "tp")), x, w)
        _lower_tpu(jax.grad(loss, argnums=(0, 1)), x, w)


def test_matmul_reduce_scatter_ring_lowers_for_tpu():
    """AOT TPU lowering of the shifting-accumulator reduce-scatter ring
    (and its fused dx/dw backward ring), fwd+bwd."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.comm import matmul_reduce_scatter

    x = jnp.zeros((2, 64, 32), jnp.bfloat16)
    w = jnp.zeros((32, 48), jnp.bfloat16)
    loss = _ring_loss(
        lambda a, b: matmul_reduce_scatter(a, b, scatter_axis=1),
        (P(None, None, "tp"), P("tp", None)), x, w)
    _lower_tpu(jax.grad(loss, argnums=(0, 1)), x, w)


@pytest.mark.parametrize("hidden", [1024, 16384])
def test_layer_norm(hidden):
    from apex_tpu.ops.layer_norm import layer_norm

    x = jnp.ones((256, hidden), jnp.bfloat16)
    w = jnp.ones((hidden,), jnp.float32)
    b = jnp.zeros((hidden,), jnp.float32)

    def loss(x, w, b):
        return jnp.sum(layer_norm(x, w, b, use_pallas=True)
                       .astype(jnp.float32) ** 2)

    with force_compiled():
        _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), x, w, b)


def test_lm_head_loss():
    from apex_tpu.ops.lm_head_loss import lm_head_loss

    n, h, vocab = 512, 768, 50304
    x = jnp.ones((n, h), jnp.bfloat16)
    w = jnp.ones((vocab, h), jnp.bfloat16)
    t = jnp.zeros((n,), jnp.int32)

    def loss(x, w):
        return jnp.sum(lm_head_loss(x, w, t, use_pallas=True))

    with force_compiled():
        _lower_tpu(jax.grad(loss, argnums=(0, 1)), x, w)


def test_lm_head_loss_row_block_follows_the_hidden_width():
    """The row block is chosen from what the call can observe: the GPT-2
    cells' widths keep 1,024 rows a tile (their compiled step is the
    parent's), and a hidden of 3,840 takes 256, where 1,024 asked Mosaic for
    48 MiB of scoped VMEM against a limit of 32."""
    from apex_tpu.ops.lm_head_loss import DEFAULT_BLOCK_V, _widest_block_n

    for hidden in (768, 1024, 1280, 1600):
        assert _widest_block_n(hidden, 1024, DEFAULT_BLOCK_V) == 1024
    assert _widest_block_n(3840, 1024, DEFAULT_BLOCK_V) == 256
    assert _widest_block_n(3840, 128, DEFAULT_BLOCK_V) == 128


def test_hybrid_cell_kernels_compile_at_its_widths():
    """The hybrid decoder's cell (hidden 3,840, 12,544 rows of the
    vocabulary, 2 x 8,192 tokens) through Mosaic's own compiler for a
    described v5e: the fused head's three kernels and the RMSNorm pair at the
    third width, where a VMEM overflow would surface."""
    from apex_tpu.ops._pallas_util import compile_for_tpu, mosaic_calls
    from apex_tpu.ops.layer_norm import rms_norm
    from apex_tpu.ops.lm_head_loss import lm_head_loss

    n, h, vocab = 16384, 3840, 12544

    def loss(x, w, nw, t):
        return jnp.sum(lm_head_loss(rms_norm(x, nw, 1e-6), w, t))

    x = jax.ShapeDtypeStruct((n, h), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((vocab, h), jnp.bfloat16)
    nw = jax.ShapeDtypeStruct((h,), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((n,), jnp.int32)
    _, compiled = compile_for_tpu(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                                  x, w, nw, t)
    calls = mosaic_calls(compiled.as_text())
    for kernel in ("lm_head_fwd", "lm_head_bwd_dx", "lm_head_bwd_dw",
                   "rms_norm_fwd", "rms_norm_bwd"):
        assert sum(c for name, c in calls.items() if kernel in name) == 1, (
            kernel, calls)


def _delta_rule_args(b=2, t=8192, h=30, dk=96, dv=192, dtype=jnp.bfloat16):
    sds = lambda *shape, dtype=dtype: jax.ShapeDtypeStruct(shape, dtype)
    return (sds(b, t, h, dk), sds(b, t, h, dk), sds(b, t, h, dv),
            sds(b, t, h, dtype=jnp.float32), sds(b, t, h, dtype=jnp.float32))


def _delta_rule_sq_loss(chunk, devices):
    """The squared output of the delta rule as the train step calls it: in a
    fully manual ``shard_map`` (over one device here), which is where a
    Mosaic kernel can be placed (``mosaic_placeable``)."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from apex_tpu.ops.delta_rule import gated_delta_rule

    def loss(q, k, v, g, beta):
        o = gated_delta_rule(q, k, v, g, beta, chunk=chunk)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    return jax.shard_map(loss, mesh=Mesh(list(devices)[:1], ("dp",)),
                         in_specs=P(), out_specs=P())


def test_delta_rule_kernels_compile_at_the_cells_shape():
    """The cell's delta rule (2 x 8,192 tokens, 30 heads, d_k 96, d_v 192,
    chunk 64), forward and backward, through Mosaic's own compiler for a
    described v5e: both kernels are in the compiled program once, under the
    names the scope table and the ledger's ``device_ops`` read, and what the
    forward leaves for the backward (a state a grid step, an inverse a pair
    of chunks) is all the temporaries there are, 0.34 GiB: the operands are
    read with time last, which is how they arrive, so nothing is copied or
    padded (XLA's chunked form a row and ten heads at a time asked for under
    3 GiB, all heads at once for some 8)."""
    from apex_tpu.ops._pallas_util import compile_for_tpu, mosaic_calls, tpu_topology_devices

    loss = _delta_rule_sq_loss(64, tpu_topology_devices())
    _, compiled = compile_for_tpu(jax.jit(jax.grad(loss, argnums=range(5))), *_delta_rule_args())
    calls = mosaic_calls(compiled.as_text())
    for kernel in ("delta_rule_fwd", "delta_rule_bwd"):
        assert sum(n for name, n in calls.items() if kernel in name) == 1, (kernel, calls)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30


# Characters of the lowered text (StableHLO with the two kernels' Mosaic
# payloads) of one ``jax.checkpoint``ed delta rule, forward and backward, at
# the cell's shape, as this file's flash case counts them: chunks are a grid
# axis and a ``fori_loop``, heads a grid axis, and what is written out twice
# is a pair of chunks' products with the state. The flash kernels' three
# bodies a kernel stand at 22,947.
_DELTA_RULE_LOWERED_CHARS = 42_732


def test_delta_rule_program_size_at_the_cells_shape():
    """The step traces every kernel several times and a warm set-up lowers
    and hashes the payloads again (PR 26's lesson): the lowered text may not
    pass 1.25 times what it is today, so a later PR that unrolls the chunks
    or the heads is told before a chip is."""
    with force_compiled():
        text = _lower_tpu(
            jax.grad(jax.checkpoint(_delta_rule_sq_loss(64, jax.devices())), argnums=range(5)),
            *_delta_rule_args()).as_text()
    assert text.count("tpu_custom_call") == 2       # the forward replayed, the backward
    assert len(text) <= 1.25 * _DELTA_RULE_LOWERED_CHARS, (
        len(text), _DELTA_RULE_LOWERED_CHARS)


def test_delta_rule_takes_the_chunked_form_in_xla_at_a_shape_the_kernels_refuse():
    """No whole number of chunks of 24 tokens is a tile of 128: nothing
    chooses but the shapes, the call runs as XLA's triangular solve and scan,
    and the compiled program holds no ``tpu_custom_call``. A length is no
    reason to refuse: 2,560 tokens are 20 pairs of chunks, three grid steps
    of seven with one pair of padding, and the kernels take them."""
    from apex_tpu.ops._pallas_util import compile_for_tpu, mosaic_calls, tpu_topology_devices

    args = _delta_rule_args(b=1, t=384, h=2)
    grad = lambda chunk: jax.jit(jax.grad(
        _delta_rule_sq_loss(chunk, tpu_topology_devices()), argnums=range(5)))
    _, compiled = compile_for_tpu(grad(24), *args)
    assert not mosaic_calls(compiled.as_text())
    assert "tpu_custom_call" not in compiled.as_text()
    _, compiled = compile_for_tpu(grad(16), *args)       # eight chunks a tile: the kernels
    assert len(mosaic_calls(compiled.as_text())) == 2
    _, compiled = compile_for_tpu(grad(64), *_delta_rule_args(b=1, t=2560, h=2))
    assert len(mosaic_calls(compiled.as_text())) == 2


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_attention_kernel_lowers_for_tpu(quantized):
    """AOT TPU lowering of the serve gather-attend kernel: scalar-prefetch
    block-table plumbing, the (H, 1, bs, D) pool block shape, and the int8
    code + fp32 scale dequant path all pass Mosaic's layout rules."""
    from apex_tpu.serve import KVCacheConfig, init_kv_cache
    from apex_tpu.serve.decode import paged_attention

    kv = KVCacheConfig(num_layers=1, num_heads=8, head_dim=64,
                       num_blocks=16, block_size=128, dtype=jnp.bfloat16,
                       quantized=quantized)
    cl = {k: v[0] for k, v in init_kv_cache(kv).items()}
    q = jnp.zeros((4, 8, 64), jnp.bfloat16)
    bt = jnp.zeros((4, 4), jnp.int32)
    lens = jnp.zeros((4,), jnp.int32)

    def f(q, cl, bt, lens):
        return paged_attention(q, cl, kv, bt, lens, use_pallas=True,
                               interpret=False)

    with force_compiled():
        _lower_tpu(f, q, cl, bt, lens)


@pytest.mark.parametrize("quantized", [False, True])
def test_fused_layer_decode_kernel_lowers_for_tpu(quantized):
    """AOT TPU lowering of the megakernel fused layer block: resident
    weight BlockSpecs (constant index maps), the clamped pool-walk DMA,
    the in-register current-token fold and the in-kernel int8 dequant all
    pass Mosaic's tiling/layout rules at a serving-sized shape."""
    from apex_tpu.serve import KVCacheConfig, init_kv_cache
    from apex_tpu.serve.megakernel import fused_layer_decode, megakernel_ok
    from apex_tpu.transformer.testing import GPTConfig

    cfg = GPTConfig(vocab_size=512, max_seq=1024, hidden=512, num_layers=1,
                    num_heads=8, dtype=jnp.bfloat16, fused_loss=False)
    kv = KVCacheConfig(num_layers=1, num_heads=8, head_dim=64,
                       num_blocks=16, block_size=128, dtype=jnp.bfloat16,
                       quantized=quantized)
    assert megakernel_ok(cfg, kv)
    h, f3, hd = cfg.hidden, 3 * cfg.hidden, cfg.num_heads * cfg.head_dim
    f = cfg.ffn_hidden
    dt = jnp.bfloat16
    lp = {
        "ln1_w": jnp.ones((h,), dt), "ln1_b": jnp.zeros((h,), dt),
        "qkv_kernel": jnp.zeros((h, f3), dt),
        "qkv_bias": jnp.zeros((f3,), dt),
        "out_kernel": jnp.zeros((hd, h), dt),
        "out_bias": jnp.zeros((h,), dt),
        "ln2_w": jnp.ones((h,), dt), "ln2_b": jnp.zeros((h,), dt),
        "fc1_kernel": jnp.zeros((h, f), dt),
        "fc1_bias": jnp.zeros((f,), dt),
        "fc2_kernel": jnp.zeros((f, h), dt),
        "fc2_bias": jnp.zeros((h,), dt),
    }
    cl = {k: v[0] for k, v in init_kv_cache(kv).items()}
    x = jnp.zeros((4, h), dt)
    bt = jnp.zeros((4, 4), jnp.int32)
    lens = jnp.zeros((4,), jnp.int32)

    def fn(x, lp, cl, bt, lens):
        return fused_layer_decode(x, lp, cl, cfg, kv, bt, lens,
                                  interpret=False)

    with force_compiled():
        _lower_tpu(fn, x, lp, cl, bt, lens)


def _mega_layer_fixture(quantized):
    """Shared serving-sized layer for the tier-2 megakernel lowering
    rows: 512 hidden bf16, lane-aligned weight tiles available."""
    from apex_tpu.serve import KVCacheConfig, init_kv_cache
    from apex_tpu.transformer.testing import GPTConfig

    cfg = GPTConfig(vocab_size=512, max_seq=1024, hidden=512, num_layers=1,
                    num_heads=8, dtype=jnp.bfloat16, fused_loss=False)
    kv = KVCacheConfig(num_layers=1, num_heads=8, head_dim=64,
                       num_blocks=16, block_size=128, dtype=jnp.bfloat16,
                       quantized=quantized)
    h, f3, hd = cfg.hidden, 3 * cfg.hidden, cfg.num_heads * cfg.head_dim
    f = cfg.ffn_hidden
    dt = jnp.bfloat16
    lp = {
        "ln1_w": jnp.ones((h,), dt), "ln1_b": jnp.zeros((h,), dt),
        "qkv_kernel": jnp.zeros((h, f3), dt),
        "qkv_bias": jnp.zeros((f3,), dt),
        "out_kernel": jnp.zeros((hd, h), dt),
        "out_bias": jnp.zeros((h,), dt),
        "ln2_w": jnp.ones((h,), dt), "ln2_b": jnp.zeros((h,), dt),
        "fc1_kernel": jnp.zeros((h, f), dt),
        "fc1_bias": jnp.zeros((f,), dt),
        "fc2_kernel": jnp.zeros((f, h), dt),
        "fc2_bias": jnp.zeros((h,), dt),
    }
    cl = {k: v[0] for k, v in init_kv_cache(kv).items()}
    return cfg, kv, lp, cl


@pytest.mark.parametrize("quantized", [False, True])
def test_fused_layer_decode_tiled_kernel_lowers_for_tpu(quantized):
    """AOT TPU lowering of the WEIGHT-STREAMING fused layer: multi-tile
    BlockSpecs with phase-clamped index maps over the flattened grid
    axis (qkv 3-way, out-proj 2-way, ffn 4-way column/row tiles), fp32
    partial accumulation across fc2 row tiles — the tier-2 path that
    lifts the VMEM residency gate past Mosaic's tiling rules."""
    from apex_tpu.serve.megakernel import _check_tiles, fused_layer_decode

    cfg, kv, lp, cl = _mega_layer_fixture(quantized)
    tiles = (3, 2, 4)  # 1536/3, 512/2, 2048/4 — all lane-aligned
    _check_tiles(cfg, tiles, True)
    x = jnp.zeros((4, cfg.hidden), jnp.bfloat16)
    bt = jnp.zeros((4, 4), jnp.int32)
    lens = jnp.zeros((4,), jnp.int32)

    def fn(x, lp, cl, bt, lens):
        return fused_layer_decode(x, lp, cl, cfg, kv, bt, lens,
                                  interpret=False, tiles=tiles)

    with force_compiled():
        _lower_tpu(fn, x, lp, cl, bt, lens)


@pytest.mark.parametrize("quantized", [False, True])
def test_fused_layer_verify_kernel_lowers_for_tpu(quantized):
    """AOT TPU lowering of the fused VERIFY layer (q_len = k+1 = 3 rows
    per slot): the per-row-unrolled online softmax, the causal
    within-window fold across fed rows and the per-row codec round-trip
    emission all pass Mosaic's layout rules."""
    from apex_tpu.serve.megakernel import fused_layer_verify

    cfg, kv, lp, cl = _mega_layer_fixture(quantized)
    x = jnp.zeros((4, 3, cfg.hidden), jnp.bfloat16)
    bt = jnp.zeros((4, 4), jnp.int32)
    start_ctx = jnp.zeros((4,), jnp.int32)

    def fn(x, lp, cl, bt, start_ctx):
        return fused_layer_verify(x, lp, cl, cfg, kv, bt, start_ctx,
                                  interpret=False)

    with force_compiled():
        _lower_tpu(fn, x, lp, cl, bt, start_ctx)


@pytest.mark.parametrize("with_norms", [False, True])
def test_fused_update_tail_lowers_for_tpu(with_norms):
    """AOT TPU lowering of the fused Adam/LAMB update-tail kernel: the
    SMEM scalar block, the padded (rows, 128) row blocking and the LAMB
    variant's sequential (1, 1) norm accumulators."""
    from apex_tpu.ops.fused_update import fused_adam_tail, fused_lamb_tail

    n = 70_001  # deliberately unaligned: exercises the padding path
    g = jnp.zeros((n,), jnp.float32)
    c = jnp.float32(0.5)

    def fn(g, c):
        tail = fused_lamb_tail if with_norms else fused_adam_tail
        return tail(g, g, g, g, c, c, betas=(0.9, 0.999), eps=1e-8,
                    weight_decay=0.01, use_pallas=True, interpret=False)

    with force_compiled():
        _lower_tpu(fn, g, c)


# ---------------------------------------------------------------------------
# Composed rows: the engine's own compiled programs at the GPT-2-124M serve
# shape, beside benchmarks/preflight_lowering.py's train rows. Each is
# lowered (the Pallas->Mosaic MLIR checks, with a minimum Mosaic-call count
# so a dispatch site that fell back to the reference fails) and then
# COMPILED for a v5e topology description — XLA:TPU plus Mosaic's own
# compiler, where VMEM overflows and unsupported vector ops surface. The
# serve path stopped lowering unseen once (PRs 8-20, jax 0.9.0); this is
# what sees it.


@pytest.fixture(scope="module")
def flagship_serve():
    """GPT-2-124M widths, two layers (the layer body is scanned: depth does
    not change what lowers), zero weights."""
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    cfg = GPTConfig(vocab_size=50304, max_seq=1024, hidden=768,
                    num_layers=2, num_heads=12, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda: init_gpt_params(jax.random.PRNGKey(0), cfg))
    return cfg, jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("megakernel,kernel", [("auto", "fused"),
                                               ("off", "pallas")])
def test_engine_programs_compile_for_tpu(flagship_serve, megakernel, kernel,
                                         kv_quant):
    from apex_tpu.ops._pallas_util import compile_for_tpu
    from apex_tpu.serve import InferenceEngine, ServeConfig

    cfg, params = flagship_serve
    with force_compiled():  # "auto" resolves as it does on the chip
        eng = InferenceEngine(params, cfg, ServeConfig(
            megakernel=megakernel, kv_quant=kv_quant, spec_k=2))
        assert eng.decode_kernel == kernel
    n, mb = eng.serve_cfg.num_slots, eng._blocks_per_slot
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)
    keys = jnp.zeros((n, 2), jnp.uint32)
    active = jnp.zeros((n,), bool)
    programs = {
        # (args after params/cache, minimum Mosaic calls: the paged kernel
        # or fused block in the layer scan; chunk_prefill also runs Pallas
        # LayerNorm at its 32 rows)
        "chunk_prefill": ((ints(eng.serve_cfg.prefill_chunk), jnp.int32(0),
                           jnp.int32(1), ints(mb), keys[0]), 2),
        "decode": ((ints(n), ints(n), active, ints(n, mb), keys), 1),
        "verify": ((ints(n, 3), ints(n), ints(n), active, ints(n, mb),
                    keys), 1),
    }
    for name, (args, min_calls) in programs.items():
        lowered, compiled = compile_for_tpu(
            eng.programs()[name], eng.params, eng.cache, *args)
        calls = lowered.as_text().count("tpu_custom_call")
        assert calls >= min_calls, (name, calls)
        assert compiled is not None


# -- the train step's optimizer pass (ISSUE 31) -------------------------------------

def _gpt(hidden=1024, heads=16):
    from apex_tpu.transformer.testing import GPTConfig
    return GPTConfig(vocab_size=50304, max_seq=1024, hidden=hidden, num_layers=2, num_heads=heads,
                     dtype=jnp.bfloat16, remat=True, remat_policy="full"), 16, 1024


def _hybrid():
    from apex_tpu.transformer.hybrid import HybridConfig
    return HybridConfig(vocab_held=1024, hidden=512, ffn_hidden=1408,
                        layer_types=("linear_attention",) * 3 + ("full_attention",),
                        num_heads=4, head_dim=128, linear_heads=30, linear_key_dim=96,
                        linear_value_dim=192, conv_width=4, chunk=64,
                        dtype=jnp.bfloat16, remat=True), 2, 1024


def _sdar():
    from apex_tpu.transformer.sdar import SDARConfig
    return SDARConfig(vocab_held=1024, hidden=512, num_layers=2, num_heads=8, num_kv_heads=1,
                      head_dim=128, num_experts=16, experts_held=(0, 4), top_k=2,
                      expert_hidden=768, mask_id=1023, dtype=jnp.bfloat16), 2, 1024


@pytest.mark.parametrize("model,dp", [(_gpt, 1), (_gpt, 4), (_hybrid, 1), (_sdar, 1)],
                         ids=["gpt2-dp1", "gpt2-dp4", "hybrid-dp1", "sdar-dp1"])
def test_the_compiled_train_step_moves_no_leaf_under_opt_and_writes_in_place(model, dp):
    """At the cells' widths and leaf shapes (depth and, at the hybrid, widths
    cut): under the scope ``opt`` every instruction that is not one leaf's
    fusion is smaller than the smallest matrix, no fusion there only moves
    data, no kernel is left, and the compiled step aliases every leaf of
    params, ``mu`` and ``nu`` to an output."""
    from apex_tpu.monitor.trace import split_scope
    from apex_tpu.ops._pallas_util import compile_for_tpu, tpu_topology_devices
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.pyprof.prof import _parse_hlo, _parse_shape, instruction_scopes
    from apex_tpu.train import abstract_train_args, train_step_fn

    cfg, rows, seq = model()
    mesh = build_mesh(tp=1, pp=1, sp=1, dp=dp, devices=tpu_topology_devices()[:dp])
    step, opt = train_step_fn(cfg, mesh)
    args = abstract_train_args(cfg, opt, mesh, rows * dp, seq)
    _, compiled = compile_for_tpu(step, *args)
    text = compiled.as_text()

    leaves = jax.tree.leaves(args[0])
    n_state = 3 * len(leaves) + 1                     # params, mu, nu, count
    aliased = {int(i) for i in re.findall(r"\{(\d+)\}: \(\d+, \{\}, (?:may|must)-alias\)",
                                          text.split("\n", 1)[0])}
    assert set(range(n_state)) <= aliased, sorted(set(range(n_state)) - aliased)

    matrix = min(int(np.prod(a.shape)) for a in leaves if a.ndim >= 2 and a.shape[-1] >= 128
                 and int(np.prod(a.shape)) >= 128 * 128)
    table = instruction_scopes(text)
    types = {i.name: i.type_str for instrs in _parse_hlo(text)[0].values() for i in instrs}
    under_opt = {name: rec for name, rec in table.items()
                 if split_scope(rec["op_name"])[1].split("/")[0] == "opt"}
    tails = [n for n, rec in under_opt.items()
             if rec["opcode"] == "fusion" and types[n].startswith("(")]
    assert len(tails) == len(leaves), (len(tails), len(leaves))
    for name, rec in under_opt.items():
        assert rec["opcode"] != "custom-call", name
        assert not (rec["opcode"] == "fusion" and rec["moves_only"]), name
        if name not in tails and rec["opcode"] != "get-tuple-element":
            largest = max((int(np.prod(dims)) for _, dims in _parse_shape(types[name])),
                          default=0)
            assert largest < matrix, (name, rec["opcode"], types[name])


# -- the GPT-2 attention sublayer's layouts (ISSUE 36) ---------------------------------

def _gpt_large():
    return _gpt(hidden=1280, heads=20)


@pytest.mark.parametrize("model,dp", [(_gpt, 1), (_gpt, 4), (_gpt_large, 1), (_gpt_large, 4)],
                         ids=["medium-dp1", "medium-dp4", "large-dp1", "large-dp4"])
def test_the_compiled_gpt2_train_step_changes_no_layout_round_attention(model, dp):
    """At both GPT-2 cells' widths (two layers: the layer body is scanned),
    remat full: the flash kernels at head size 64 read the packed QKV product
    as it stands and write o as the output product reads it, so under a scope
    with ``attn/`` the compiled step holds no ``copy`` and no fusion that only
    moves data of 1 MiB or more in the forward and the replay and at most one
    in the backward (the parent held 17 copies and two such fusions a layer),
    and its Mosaic calls are the ones it had: ``flash_fwd`` twice (forward and
    replay), ``flash_bwd_dq`` and ``flash_bwd_dkv`` once."""
    from apex_tpu.monitor.trace import split_scope
    from apex_tpu.ops._pallas_util import compile_for_tpu, mosaic_calls, tpu_topology_devices
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.pyprof.prof import _nbytes, _parse_hlo, instruction_scopes
    from apex_tpu.train import abstract_train_args, train_step_fn

    cfg, rows, seq = model()
    mesh = build_mesh(tp=1, pp=1, sp=1, dp=dp, devices=tpu_topology_devices()[:dp])
    step, opt = train_step_fn(cfg, mesh)
    _, compiled = compile_for_tpu(step, *abstract_train_args(cfg, opt, mesh, rows * dp, seq))
    text = compiled.as_text()

    calls = mosaic_calls(text)
    assert set(calls) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "layer_norm_fwd",
                          "layer_norm_bwd", "lm_head_fwd", "lm_head_bwd_dx", "lm_head_bwd_dw"}
    assert (calls["flash_fwd"], calls["flash_bwd_dq"], calls["flash_bwd_dkv"]) == (2, 1, 1)

    table = instruction_scopes(text)
    types = {i.name: i.type_str for instrs in _parse_hlo(text)[0].values() for i in instrs}
    moved = {"fwd": [], "recompute": [], "bwd": []}
    for name, rec in table.items():
        phase, scope = split_scope(rec["op_name"])
        if "attn/" not in scope + "/" or phase not in moved:
            continue
        moves = rec["opcode"] == "copy" or (rec["opcode"] == "fusion" and rec["moves_only"])
        if moves and _nbytes(types[name]) >= 2**20:
            moved[phase].append((name, types[name], scope))
    assert not moved["fwd"] and not moved["recompute"], moved
    assert len(moved["bwd"]) <= 1, moved
    # every flash kernel is under the scope the trace joins on
    flash = sorted(split_scope(rec["op_name"]) for rec in table.values()
                   if rec["opcode"] == "custom-call" and "flash_" in rec["op_name"])
    assert flash == [("bwd", "layer/attn/core/flash_bwd_dkv"), ("bwd", "layer/attn/core/flash_bwd_dq"),
                     ("fwd", "layer/attn/core/flash_fwd"), ("recompute", "layer/attn/core/flash_fwd")]


# -- the routed experts' grouped products ------------------------------------------

def _dsv2():
    from apex_tpu.transformer.deepseek import DeepSeekConfig
    return DeepSeekConfig(vocab_held=1024, hidden=512, num_layers=2, num_heads=4, dense_hidden=1408,
                          num_experts=16, experts_held=(0, 4), shared_hidden=1408), 1, 2048


def _laguna():
    from apex_tpu.transformer.laguna import FULL, SLIDING, LagunaConfig
    return LagunaConfig(vocab_held=1024, layer_types=(FULL, SLIDING, FULL), full_heads=8,
                        sliding_heads=16, dense_hidden=1024, num_experts=64,
                        experts_held=(0, 4)), 1, 2048


@pytest.mark.parametrize("model", [_sdar, _dsv2, _laguna],
                         ids=["block-diffusion", "latent-attention", "window-hidden-3072"])
def test_the_routed_layers_grouped_products_are_the_programs_kernels(model):
    """At the cells' expert widths (depth, hidden and experts cut): the
    compiled step holds no XLA ``ragged-dot`` and runs the experts' products
    as ``grouped_fwd`` (forward and replay), ``grouped_dx`` and ``grouped_dw``
    (backward), the first pass's under ``layer/moe/experts``, the scope the
    trace joins on (a later pass, run only where the first pass's buffer
    overflows, sits inside ``layer/moe/combine``'s conditional)."""
    from apex_tpu.monitor.trace import split_scope
    from apex_tpu.ops._pallas_util import compile_for_tpu, tpu_topology_devices
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.pyprof.prof import instruction_scopes
    from apex_tpu.train import abstract_train_args, train_step_fn

    cfg, rows, seq = model()
    mesh = build_mesh(tp=1, pp=1, sp=1, dp=1, devices=tpu_topology_devices()[:1])
    step, opt = train_step_fn(cfg, mesh)
    _, compiled = compile_for_tpu(step, *abstract_train_args(cfg, opt, mesh, rows, seq))
    text = compiled.as_text()
    assert "ragged-dot" not in text and "ragged_dot" not in text

    grouped = {split_scope(rec["op_name"]) for rec in instruction_scopes(text).values()
               if rec["opcode"] == "custom-call" and "grouped_" in rec["op_name"]}
    experts = "layer/moe/experts/"
    assert all(scope.split("/")[-3:-1] == ["moe", "experts"] for _, scope in grouped), grouped
    grouped = {(phase, scope) for phase, scope in grouped if scope.startswith(experts)}
    assert grouped == {("fwd", experts + "grouped_fwd"), ("recompute", experts + "grouped_fwd"),
                       ("bwd", experts + "grouped_dx"), ("bwd", experts + "grouped_dw")}, grouped
