"""Megakernel decode + fused optimizer update tail (ROADMAP item 4).

Two fused hot paths, each pinned against the per-op program it replaces:

* ``serve.megakernel`` — the per-layer fused Pallas decode block must
  agree with ``decode.gpt_decode_step`` (the pure-JAX/paged-kernel
  oracle): fp32 logits + written pools within fp tolerance, int8 pools
  with IDENTICAL codes, and — the acceptance gate — the engine's streams
  equal between ``megakernel="on"`` and ``"off"`` (greedy AND same-key
  sampled, speculative included) with the compile-count gate intact.
* ``ops.fused_update`` — the Adam/LAMB tail kernels must match the
  ``upd`` closure math the ZeRO optimizers ran before fusion, including
  the padding edges (leaves far from tile multiples) and the LAMB
  trust-ratio composition. (``FusedAdam`` itself runs XLA's fusions since
  PR 31: ``tests/test_fused_adam_step.py``.)

All stock-jax-safe (interpret-mode Pallas, no mesh); the AOT Mosaic
lowering rows live in ``tests/test_tpu_lowering.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops.fused_update import (
    adam_tail_reference,
    fused_adam_tail,
    fused_lamb_tail,
    lamb_tail_reference,
    resolve_fused,
)
from apex_tpu.serve import (
    InferenceEngine,
    KVCacheConfig,
    Request,
    SamplingConfig,
    ServeConfig,
    init_kv_cache,
    megakernel_ok,
)
from apex_tpu.serve.decode import (
    gpt_decode_step,
    gpt_prefill,
    gpt_verify_step,
)
from apex_tpu.serve.megakernel import (
    default_tiles,
    fused_layer_decode,
    fused_live_bytes,
    gpt_decode_step_fused,
    gpt_verify_step_fused,
    layer_weight_bytes,
    megakernel_refusal,
)
from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

CFG = GPTConfig(vocab_size=97, max_seq=64, hidden=32, num_layers=2,
                num_heads=4, dtype=jnp.float32, fused_loss=False)
PARAMS = init_gpt_params(jax.random.PRNGKey(0), CFG)

REQS = [
    Request("a", [1, 2, 3, 4, 5], max_new_tokens=6),
    Request("b", [7, 8, 9], max_new_tokens=4),
    Request("c", list(range(10, 22)), max_new_tokens=5),
]


def _engine(megakernel, sampling=None, **kw):
    scfg = ServeConfig(num_slots=3, block_size=8, prefill_chunk=8,
                       megakernel=megakernel,
                       sampling=sampling or SamplingConfig(), **kw)
    return InferenceEngine(PARAMS, CFG, scfg)


def _prefilled(kv, prompts):
    """Prefill ``prompts`` into a fresh cache, one slot per prompt, block
    rows carved consecutively; returns (cache, block_tables)."""
    bpslot = kv.num_blocks // len(prompts)
    rows = np.arange(len(prompts) * bpslot,
                     dtype=np.int32).reshape(len(prompts), bpslot)
    bt = jnp.asarray(rows)
    cache = init_kv_cache(kv)
    for s, pr in enumerate(prompts):
        toks = jnp.zeros((16,), jnp.int32).at[:len(pr)].set(jnp.asarray(pr))
        cache, _ = gpt_prefill(PARAMS, toks, jnp.int32(len(pr)), cache,
                               bt[s], CFG, kv)
    return cache, bt


# ---------------------------------------------------------------------------
# fused decode step vs the per-op oracle


@pytest.mark.parametrize("kv_mode", ["none", "int8", "int4"])
def test_fused_decode_matches_unfused(kv_mode):
    """Multi-step decode: the fused per-layer block produces the same
    logits AND the same written pools as gpt_decode_step — fp32 within fp
    tolerance, int8/int4 codes bitwise (both paths quantize identical
    values through the same codec; the int4 path dequantizes nibble-packed
    codes + bf16 group scales IN kernel). Includes an inactive slot
    (ctx 0): junk but finite logits, no pool writes."""
    quantized = kv_mode != "none"
    kv = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                       num_blocks=24, block_size=4, dtype=jnp.float32,
                       quantized=quantized,
                       bits=4 if kv_mode == "int4" else 8)
    cache, bt = _prefilled(kv, [[3, 14, 15, 92, 6], [7, 8, 9],
                                [1]])  # slot 2 then marked inactive
    cache_f = jax.tree.map(lambda a: a, cache)
    lens = np.array([5, 3, 0], np.int32)
    last = np.array([10, 20, 0], np.int32)
    active = jnp.asarray([True, True, False])
    for _ in range(4):
        cache, lg_u = gpt_decode_step(
            PARAMS, jnp.asarray(last), jnp.asarray(lens), active, cache,
            bt, CFG, kv)
        cache_f, lg_f = gpt_decode_step_fused(
            PARAMS, jnp.asarray(last), jnp.asarray(lens), active, cache_f,
            bt, CFG, kv)
        np.testing.assert_allclose(np.asarray(lg_f[:2]),
                                   np.asarray(lg_u[:2]), atol=5e-5)
        assert np.isfinite(np.asarray(lg_f)).all()
        for key, pool in cache.items():
            if quantized and key in ("k", "v"):
                np.testing.assert_array_equal(np.asarray(pool),
                                              np.asarray(cache_f[key]))
            else:
                np.testing.assert_allclose(np.asarray(cache_f[key]),
                                           np.asarray(pool), atol=1e-5)
        last = np.asarray(jnp.argmax(lg_u, -1))
        lens = lens + np.array([1, 1, 0], np.int32)


@pytest.mark.parametrize("kv_mode", ["none", "int8", "int4"])
def test_fused_verify_matches_unfused(kv_mode):
    """Multi-round VERIFY parity: gpt_verify_step_fused (q=k+1 rows per
    slot, causal-within-window fold in-kernel) produces the same
    valid-row logits AND the same written pools as the unfused
    gpt_verify_step — fp32 within fp tolerance, int8/int4 codes bitwise.
    Rounds 2-3 accept FEWER tokens than were fed (rejected drafts), so
    the stale K/V those rows wrote must be masked by the next window and
    overwritten identically on both paths — the no-rollback contract."""
    quantized = kv_mode != "none"
    kv = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                       num_blocks=24, block_size=4, dtype=jnp.float32,
                       quantized=quantized,
                       bits=4 if kv_mode == "int4" else 8)
    cache, bt = _prefilled(kv, [[3, 14, 15, 92, 6], [7, 8, 9], [1]])
    cache_f = jax.tree.map(lambda a: a, cache)
    lens = np.array([5, 3, 0], np.int32)
    active = jnp.asarray([True, True, False])
    rng = np.random.default_rng(7)
    fed = rng.integers(1, 96, (3, 3)).astype(np.int32)
    for n_fed, accept in [(np.array([3, 2, 0], np.int32), (1, 2)),
                          (np.array([2, 3, 0], np.int32), (2, 1)),
                          (np.array([3, 1, 0], np.int32), (3, 1))]:
        cache, lg_u = gpt_verify_step(
            PARAMS, jnp.asarray(fed), jnp.asarray(lens),
            jnp.asarray(n_fed), active, cache, bt, CFG, kv)
        cache_f, lg_f = gpt_verify_step_fused(
            PARAMS, jnp.asarray(fed), jnp.asarray(lens),
            jnp.asarray(n_fed), active, cache_f, bt, CFG, kv)
        valid = np.asarray(active)[:, None] & (
            np.arange(3)[None, :] < n_fed[:, None])
        np.testing.assert_allclose(np.asarray(lg_f)[valid],
                                   np.asarray(lg_u)[valid], atol=5e-5)
        assert np.isfinite(np.asarray(lg_f)).all()
        for key, pool in cache.items():
            if quantized and key in ("k", "v"):
                np.testing.assert_array_equal(np.asarray(pool),
                                              np.asarray(cache_f[key]))
            else:
                np.testing.assert_allclose(np.asarray(cache_f[key]),
                                           np.asarray(pool), atol=1e-5)
        # accept a PREFIX of what was fed (possibly rejecting drafts):
        # only the accepted count advances the context
        lens = lens + np.array([accept[0], accept[1], 0], np.int32)
        fed = rng.integers(1, 96, (3, 3)).astype(np.int32)


def test_fused_verify_single_row_matches_decode():
    """q=1 verify (no drafts proposed) degenerates to the decode step:
    same logits, same pools — the fused block's q generalization is a
    strict superset of the PR-8 q=1 kernel."""
    kv = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                       num_blocks=24, block_size=4, dtype=jnp.float32)
    cache, bt = _prefilled(kv, [[3, 14, 15], [7, 8, 9, 10]])
    cache_v = jax.tree.map(lambda a: a, cache)
    lens = jnp.asarray([3, 4], jnp.int32)
    active = jnp.asarray([True, True])
    last = jnp.asarray([10, 20], jnp.int32)
    cache, lg_d = gpt_decode_step_fused(
        PARAMS, last, lens, active, cache, bt, CFG, kv)
    cache_v, lg_v = gpt_verify_step_fused(
        PARAMS, last[:, None], lens, jnp.asarray([1, 1], jnp.int32),
        active, cache_v, bt, CFG, kv)
    np.testing.assert_array_equal(np.asarray(lg_v[:, 0]), np.asarray(lg_d))
    for key, pool in cache.items():
        np.testing.assert_array_equal(np.asarray(pool),
                                      np.asarray(cache_v[key]))


def test_tile_validation_and_multi_tile_parity():
    """Tile-boundary edges: a count that does not divide its dim refuses
    loudly with the valid counts listed; compiled Mosaic additionally
    refuses lane-misaligned tiles; explicit ``(1, 1, 1)`` is the SAME
    program as ``tiles=None`` here (default_tiles resolves to full
    residency — the PR-8 path — bitwise); multi-tile streaming agrees
    with full residency (column tiles keep contractions whole, only the
    fc2 row tiles reassociate the fp32 ffn accumulation)."""
    from apex_tpu.serve.megakernel import _check_tiles

    kv = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                       num_blocks=8, block_size=8, dtype=jnp.float32)
    cache, bt = _prefilled(kv, [[5, 6, 7], [11, 12]])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, CFG.hidden))
    lp = jax.tree.map(lambda a: a[0], PARAMS["layers"])
    cl = {k: v[0] for k, v in cache.items()}
    lens = jnp.asarray([3, 2], jnp.int32)
    with pytest.raises(ValueError, match="does not divide"):
        fused_layer_decode(x, lp, cl, CFG, kv, bt, lens, tiles=(5, 1, 1))
    with pytest.raises(ValueError, match="lane-aligned"):
        _check_tiles(CFG, (2, 1, 1), True)  # 96 / 2 = 48: not 128-aligned
    assert default_tiles(CFG, kv, compiled=False) == (1, 1, 1)
    base = fused_layer_decode(x, lp, cl, CFG, kv, bt, lens,
                              tiles=(1, 1, 1))
    auto = fused_layer_decode(x, lp, cl, CFG, kv, bt, lens)
    for a, b in zip(base, auto):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for tiles in [(2, 2, 2), (3, 1, 4)]:
        got = fused_layer_decode(x, lp, cl, CFG, kv, bt, lens,
                                 tiles=tiles)
        for a, b in zip(base, got):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-5)


def test_fused_layer_single_block_table():
    """nb == 1 edge: the j==0 grid step is also the last — init, QKV,
    block attend and the current-token fold all land in one step."""
    kv = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                       num_blocks=4, block_size=8, dtype=jnp.float32)
    cache, bt = _prefilled(kv, [[5, 6, 7], [11]])
    assert bt.shape[1] == 2
    bt1 = bt[:, :1]  # single-block tables (max_context <= block_size)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, CFG.hidden))
    lp = jax.tree.map(lambda a: a[0], PARAMS["layers"])
    cl = {k: v[0] for k, v in cache.items()}
    x2, k_new, v_new = fused_layer_decode(
        x, lp, cl, CFG, kv, bt1, jnp.asarray([3, 1], jnp.int32))
    assert x2.shape == x.shape and k_new.shape == (2, 4, 8)
    assert np.isfinite(np.asarray(x2)).all()


# ---------------------------------------------------------------------------
# engine acceptance: stream equality on/off, compile gate, gating


@pytest.mark.parametrize("sampling", [
    SamplingConfig(),
    SamplingConfig(temperature=0.8, top_k=20),
])
def test_engine_streams_equal_megakernel_on_off(sampling):
    """ACCEPTANCE: the fused decode program changes no stream — greedy
    and same-key sampled outputs are equal request-for-request."""
    outs = {}
    for mode in ("on", "off"):
        eng = _engine(mode, sampling=sampling)
        outs[mode] = eng.run([Request(r.uid, r.tokens, r.max_new_tokens)
                              for r in REQS])
        assert eng.megakernel_enabled == (mode == "on")
    assert outs["on"] == outs["off"]


@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
def test_engine_streams_equal_with_speculation_and_quant_kv(kv_quant):
    """The fused decode program composes with the FUSED speculative
    verify program (megakernel='on' now drives both jit sites) and the
    quantized caches: streams stay equal to the fully-unfused engine for
    int8 AND the nibble-packed int4 pools."""
    outs = {}
    for mode in ("on", "off"):
        eng = _engine(mode, spec_k=2, kv_quant=kv_quant)
        outs[mode] = eng.run([Request(r.uid, r.tokens, r.max_new_tokens)
                              for r in REQS])
    assert outs["on"] == outs["off"]


def test_engine_compile_gate_holds_with_megakernel():
    """The tightened PR-7 compile gate survives fusion: exactly 1 chunked
    prefill + 1 decode program (pinned through the shared
    ``analyze.recompile_guard`` sentinel)."""
    from apex_tpu.analyze import recompile_guard

    eng = _engine("on")
    with recompile_guard(eng.programs()):  # warmup contract
        eng.run([Request(r.uid, r.tokens, r.max_new_tokens) for r in REQS])
    counts = eng.compile_counts()
    assert counts["chunk_prefill"] == 1
    assert counts["decode"] == 1
    assert eng.stats()["megakernel"] is True


def test_megakernel_gating_and_validation():
    """auto falls back off-TPU; unsupported shapes refuse 'on' loudly
    WITH the reason; the VMEM gate is now a tile-budget computation —
    GPT-2-124M-class layers (whose full weight set is over budget) gate
    ON because their weight TILES fit, and only never-fits shapes
    refuse, reporting the measured bytes."""
    from apex_tpu.ops._pallas_util import force_compiled

    kv = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                       num_blocks=8, block_size=8, dtype=jnp.float32)
    assert megakernel_ok(CFG, kv)
    # auto on a CPU backend -> the unfused program
    eng = _engine("auto")
    assert eng.megakernel_enabled is False
    with pytest.raises(ValueError, match="megakernel"):
        ServeConfig(megakernel="bogus").validate()
    # MoE unsupported
    moe = GPTConfig(vocab_size=97, max_seq=64, hidden=32, num_layers=2,
                    num_heads=4, num_experts=2, dtype=jnp.float32)
    assert not megakernel_ok(moe, kv)
    assert "dense FFN" in megakernel_refusal(moe, kv)
    # head_dim % 8 gate — and 'on' surfaces the reason in the raise
    odd = GPTConfig(vocab_size=97, max_seq=64, hidden=36, num_layers=2,
                    num_heads=4, dtype=jnp.float32)
    kv9 = KVCacheConfig(num_layers=2, num_heads=4, head_dim=9,
                        num_blocks=8, block_size=8, dtype=jnp.float32)
    assert not megakernel_ok(odd, kv9)
    with pytest.raises(ValueError, match="megakernel='on'.*head_dim"):
        InferenceEngine(init_gpt_params(jax.random.PRNGKey(0), odd), odd,
                        ServeConfig(num_slots=1, block_size=8,
                                    megakernel="on"))
    # THE LIFTED GATE: a 124M-shaped layer (768 hidden, 3072 ffn) in
    # fp32 is ~28 MB of weights — over the old full-residency budget —
    # but its streamed tile set fits, so it now gates ON
    big = GPTConfig(vocab_size=128, max_seq=64, hidden=768, num_layers=2,
                    num_heads=12, dtype=jnp.float32)
    kv_big = KVCacheConfig(num_layers=2, num_heads=12, head_dim=64,
                           num_blocks=8, block_size=8, dtype=jnp.float32)
    assert layer_weight_bytes(big) > 10 * 1024 * 1024
    assert megakernel_ok(big, kv_big)
    tiles = default_tiles(big, kv_big, compiled=False)
    assert tiles is not None and tiles != (1, 1, 1)
    assert fused_live_bytes(big, kv_big, tiles) <= 10 * 1024 * 1024
    # the GPT-2-124M flagship serve shape (bf16, lane-aligned tiles on
    # a compiled backend) gates ON too — the acceptance criterion
    flag = GPTConfig(vocab_size=50304, max_seq=1024, hidden=768,
                     num_layers=12, num_heads=12, dtype=jnp.bfloat16)
    kv_flag = KVCacheConfig(num_layers=12, num_heads=12, head_dim=64,
                            num_blocks=64, block_size=16,
                            dtype=jnp.bfloat16)
    assert layer_weight_bytes(flag) > 10 * 1024 * 1024
    with force_compiled():
        assert megakernel_ok(flag, kv_flag)
        assert megakernel_ok(flag, kv_flag, q=5)  # spec_k=4 verify fits
        # never-fits: even the finest lane-aligned tiling of an 8192-
        # hidden fp32 layer keeps >10 MB live; the refusal reports the
        # MEASURED bytes, not a bare no
        huge = GPTConfig(vocab_size=128, max_seq=64, hidden=8192,
                         num_layers=1, num_heads=64, dtype=jnp.float32)
        kv_huge = KVCacheConfig(num_layers=1, num_heads=64, head_dim=128,
                                num_blocks=8, block_size=8,
                                dtype=jnp.float32)
        refusal = megakernel_refusal(huge, kv_huge)
        assert refusal is not None and "VMEM" in refusal
        assert str(layer_weight_bytes(huge)) in refusal
        assert "finest weight tiling" in refusal


def test_engine_streams_equal_at_124m_shaped_config():
    """ACCEPTANCE: a GPT-2-124M-shaped config (768 hidden, fp32 — the
    shape the old full-residency gate refused) now serves with
    megakernel='on' + spec_k, and its streams equal both the unfused
    speculative engine AND the no-speculation reference. An oracle
    drafter (replays the reference continuation) guarantees the FUSED
    verify program actually runs."""
    big = GPTConfig(vocab_size=256, max_seq=64, hidden=768, num_layers=1,
                    num_heads=12, dtype=jnp.float32, fused_loss=False)
    assert layer_weight_bytes(big) > 10 * 1024 * 1024  # previously OFF
    params = init_gpt_params(jax.random.PRNGKey(1), big)
    reqs = [Request("a", [5, 6, 7, 8], max_new_tokens=4),
            Request("b", [9, 10, 11], max_new_tokens=3)]
    base = InferenceEngine(params, big, ServeConfig(
        num_slots=2, block_size=8, prefill_chunk=8, megakernel="off"))
    ref = base.run([Request(r.uid, r.tokens, r.max_new_tokens)
                    for r in reqs])
    conts = [list(r.tokens) + ref[r.uid] for r in reqs]
    outs, stats = {}, {}
    for mode in ("on", "off"):
        scfg = ServeConfig(num_slots=2, block_size=8, prefill_chunk=8,
                           megakernel=mode, spec_k=2)
        eng = InferenceEngine(params, big, scfg,
                              drafter=_OracleDrafter(conts))
        assert eng.megakernel_enabled == (mode == "on")
        outs[mode] = eng.run([Request(r.uid, r.tokens, r.max_new_tokens)
                              for r in reqs])
        stats[mode] = eng.stats()
    assert outs["on"] == outs["off"] == ref
    assert stats["on"]["decode_kernel"] == "fused"
    assert stats["on"]["verify_kernel"] == "fused"
    assert stats["on"]["speculative"]["verify_steps"] > 0
    assert stats["on"]["spec_acceptance_rate"] == 1.0


class _OracleDrafter:
    """Proposes exactly the continuation a reference run produced —
    every draft matches, so acceptance must be 1.0 and every speculative
    step emits k+1 tokens."""

    def __init__(self, continuations):
        self._conts = continuations  # full prompt+generated token lists

    def propose(self, tokens, k):
        t = list(tokens)
        for full in self._conts:
            if len(full) >= len(t) and full[:len(t)] == t:
                return full[len(t):len(t) + k]
        return []


@pytest.mark.parametrize("sampling", [
    SamplingConfig(),
    SamplingConfig(temperature=0.8, top_k=20),
])
def test_oracle_drafter_full_acceptance_on_fused_verify(sampling):
    """ACCEPTANCE: with an oracle drafter (proposes the recorded
    baseline continuation) the fused verify path accepts EVERY draft —
    acceptance_rate == 1.0 greedy AND sampled — and the streams stay
    equal to the unfused no-speculation baseline. Sampling draws are
    position-keyed, so the verify step's parallel draws equal the
    sequential ones."""
    base = _engine("off", sampling=sampling)
    ref = base.run([Request(r.uid, r.tokens, r.max_new_tokens)
                    for r in REQS])
    conts = [list(r.tokens) + ref[r.uid] for r in REQS]
    scfg = ServeConfig(num_slots=3, block_size=8, prefill_chunk=8,
                       megakernel="on", spec_k=2, sampling=sampling)
    eng = InferenceEngine(PARAMS, CFG, scfg,
                          drafter=_OracleDrafter(conts))
    outs = eng.run([Request(r.uid, r.tokens, r.max_new_tokens)
                    for r in REQS])
    assert outs == ref
    st = eng.stats()
    assert st["speculative"]["proposed"] > 0
    assert st["spec_acceptance_rate"] == 1.0
    assert st["verify_kernel"] == "fused"


def test_verify_kernel_field_reports_actual_path():
    """stats()/record field ``verify_kernel``: None without a verify
    program (spec_k == 0), 'fused' when the megakernel drives the verify
    jit site, 'reference'/'pallas' mirroring decode_kernel otherwise —
    the verify A/B gate's fallback-vs-regression discriminator."""
    from apex_tpu.ops._pallas_util import force_compiled

    assert _engine("on").verify_kernel is None  # no verify program
    eng_on = _engine("on", spec_k=2)
    assert eng_on.verify_kernel == "fused"
    assert eng_on.stats()["verify_kernel"] == "fused"
    eng_off = _engine("off", spec_k=2)
    assert eng_off.verify_kernel == "reference"  # CPU: no compiled Mosaic
    with force_compiled():
        assert eng_off.verify_kernel == "pallas"


def test_megakernel_auto_fallback_warns_once_with_reason():
    """megakernel='auto' falling back on a COMPILED backend logs ONE
    warning per reason, carrying the reason text (here: LoRA adapters
    ride the per-op path) — a slower serve run must be diagnosable from
    the log. The normal CPU auto fallback (no compiled Mosaic — nothing
    to miss) stays silent."""
    import logging

    from apex_tpu.ops._pallas_util import force_compiled
    from apex_tpu.serve.megakernel import _FALLBACK_WARNED

    records = []

    class Grab(logging.Handler):
        def emit(self, record):
            records.append(record)

    logger = logging.getLogger("apex_tpu.serve")
    handler = Grab(level=logging.WARNING)
    logger.addHandler(handler)
    try:
        _FALLBACK_WARNED.clear()
        with force_compiled():
            for _ in range(2):  # second construction: no duplicate warn
                eng = InferenceEngine(PARAMS, CFG, ServeConfig(
                    num_slots=2, block_size=8, prefill_chunk=8,
                    megakernel="auto", lora_rank=4, max_adapters=2))
                assert eng.megakernel_enabled is False
        warns = [r for r in records if "falling back" in r.getMessage()]
        assert len(warns) == 1
        assert "LoRA" in warns[0].getMessage()
        # off-TPU auto-resolution (the normal CPU path) does not warn
        records.clear()
        _FALLBACK_WARNED.clear()
        assert _engine("auto").megakernel_enabled is False
        assert not [r for r in records if "falling back" in r.getMessage()]
    finally:
        logger.removeHandler(handler)


# ---------------------------------------------------------------------------
# fused optimizer update tail


@pytest.mark.parametrize("shape", [(7, 13), (300, 700), (1,), (1024,)])
@pytest.mark.parametrize("wd,adam_w", [(0.0, True), (0.01, True),
                                       (0.01, False)])
def test_adam_tail_kernel_matches_reference(shape, wd, adam_w):
    """The fused kernel equals the per-op Adam tail on every leaf shape,
    including leaves far from the (8, 128) tile (padding lanes sliced
    back off). Tolerance is fp reassociation noise, not algorithmic."""
    k = jax.random.PRNGKey(0)
    g, m, v, p = (jax.random.normal(jax.random.fold_in(k, i), shape)
                  for i in range(4))
    v = jnp.abs(v)
    c1, c2 = jnp.float32(1 - 0.9 ** 3), jnp.float32(1 - 0.999 ** 3)
    kw = dict(betas=(0.9, 0.999), eps=1e-8, weight_decay=wd,
              adam_w_mode=adam_w)
    ref = adam_tail_reference(g, m, v, p, c1, c2, **kw)
    fus = fused_adam_tail(g, m, v, p, c1, c2, use_pallas=True, **kw)
    for a, b in zip(ref, fus):
        assert b.shape == shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-6, atol=5e-7)


def test_lamb_tail_kernel_matches_reference_and_trust_composition():
    """LAMB kernel: tail + in-kernel Σp²/Σu² accumulated across grid
    steps match the reference, and the composed p' (trust ratio applied
    outside, world=1 so psum == identity) matches the DistributedFusedLAMB
    ``upd`` math."""
    k = jax.random.PRNGKey(1)
    shape = (300, 700)  # multi-block grid: accumulation across steps
    g, m, v, p = (jax.random.normal(jax.random.fold_in(k, i), shape)
                  for i in range(4))
    v = jnp.abs(v)
    c1, c2 = jnp.float32(1 - 0.9 ** 5), jnp.float32(1 - 0.999 ** 5)
    kw = dict(betas=(0.9, 0.999), eps=1e-6, weight_decay=0.01)
    ref = lamb_tail_reference(g, m, v, p, c1, c2, **kw)
    fus = fused_lamb_tail(g, m, v, p, c1, c2, use_pallas=True, **kw)
    for a, b in zip(ref[:3], fus[:3]):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-6, atol=5e-7)
    np.testing.assert_allclose(np.asarray(fus[3]), np.asarray(ref[3]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fus[4]), np.asarray(ref[4]),
                               rtol=1e-5)
    # trust-ratio composition == the unfused upd closure
    lr = 1e-2
    u, _, _, wsq, usq = fus
    w_norm, u_norm = jnp.sqrt(wsq), jnp.sqrt(usq)
    trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
    got = p - lr * trust * u
    b1, b2 = 0.9, 0.999
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * g * g
    u_ref = (m_new / c1) / (jnp.sqrt(v_new / c2) + 1e-6) + 0.01 * p
    wn = jnp.sqrt(jnp.sum(p * p))
    un = jnp.sqrt(jnp.sum(u_ref * u_ref))
    want = p - lr * jnp.where((wn > 0) & (un > 0), wn / un, 1.0) * u_ref
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_resolve_fused_modes():
    assert resolve_fused("off") is False
    assert resolve_fused("on") is True  # pallas importable on this box
    # auto off-TPU: interpret mode saves no dispatch -> stays off
    assert resolve_fused("auto") is False
    with pytest.raises(ValueError, match="fused_update"):
        resolve_fused("bogus")


def test_decode_kernel_field_reports_actual_path():
    """stats()/record field ``decode_kernel``: 'fused' when the
    megakernel serves, 'reference' when auto-resolution fell back
    off-TPU, 'pallas' when the per-op body would pick the gather-attend
    kernel on a compiled backend — the stage-12 gate's fallback-vs-
    regression discriminator."""
    from apex_tpu.ops._pallas_util import force_compiled

    eng_on = _engine("on")
    assert eng_on.decode_kernel == "fused"
    assert eng_on.stats()["decode_kernel"] == "fused"
    eng_off = _engine("off")
    assert eng_off.decode_kernel == "reference"  # CPU: no compiled Mosaic
    with force_compiled():
        assert eng_off.decode_kernel == "pallas"  # head_dim 8: kernel-ok


def test_paged_attention_reference_fallback_warns_once():
    """The silent kernel->reference fallback (head_dim % 8 != 0 on a
    compiled backend) logs ONE warning — a 10x slower serve run must be
    diagnosable from the log, not only from the bench line. (Handler
    attached directly: the apex_tpu root logger does not propagate.)"""
    import logging

    from apex_tpu.ops._pallas_util import force_compiled
    from apex_tpu.serve import paged_attention
    from apex_tpu.serve.decode import _FALLBACK_WARNED

    kv = KVCacheConfig(num_layers=1, num_heads=2, head_dim=9,
                       num_blocks=4, block_size=4, dtype=jnp.float32)
    cache = init_kv_cache(kv)
    cl = {k: v[0] for k, v in cache.items()}
    q = jnp.zeros((2, 2, 9))
    bt = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.zeros((2,), jnp.int32)

    records = []

    class Grab(logging.Handler):
        def emit(self, record):
            records.append(record)

    logger = logging.getLogger("apex_tpu.serve")
    handler = Grab(level=logging.WARNING)
    logger.addHandler(handler)
    try:
        _FALLBACK_WARNED.clear()
        with force_compiled():
            paged_attention(q, cl, kv, bt, lens)
            paged_attention(q, cl, kv, bt, lens)  # second call: no dup
        warns = [r for r in records if "falling back" in r.getMessage()]
        assert len(warns) == 1
        assert "head_dim 9" in warns[0].getMessage()
        # off-TPU auto-resolution (the normal CPU path) does not warn
        _FALLBACK_WARNED.clear()
        records.clear()
        paged_attention(q, cl, kv, bt, lens)
        assert not [r for r in records if "falling back" in r.getMessage()]
    finally:
        logger.removeHandler(handler)
