"""apex_tpu.serve.sharded — one ParallelismPlan from training to
pod-scale inference.

Gates, per residency strategy (``tp`` / ``pp`` / ``fsdp``):

* **stream parity** — plan-sharded decode/verify/chunked-prefill token
  streams equal the single-chip oracle's, greedy AND sampled, int8/int4
  quantized KV included. ``pp``/``fsdp`` are bitwise claims (stage
  splits reorder no op; uncompressed gather is slice-concat identity);
  ``tp`` logits differ by psum ring association only and the STREAMS
  still match exactly on these workloads;
* **compile-count gate** — the plan engines keep the plain engine's
  warmup contract (one compile per cold program) and run steady-state
  workloads under ``recompile_guard(budget=0)``;
* **overlap proof** — the TP q_len>1 programs' row exits are proven
  overlapped from their compiled HLO (``overlap_assertion``,
  hidden_fraction >= 0.5) while q_len=1 decode stays monolithic (zero
  collective-permutes — the PR-5 pin);
* **plan validation** (stock-safe) — ``serve_overrides()`` refuses
  optimizer-coupled knobs with the arithmetic, ``serve_strategy()``
  refuses composed sharding, ``describe()`` tells the serve story, and
  ``fsdp.accounting.hbm_serve_bytes`` prices each strategy under a chip
  budget.

All mesh rows run on the conftest's 8 virtual devices — the same
validation idiom as the PR-9/12 mesh suites.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.analyze import recompile_guard
from apex_tpu.analyze.collectives import overlap_assertion
from apex_tpu.comm import CompressionConfig
from apex_tpu.fsdp.accounting import hbm_serve_bytes, param_gather_wire_bytes
from apex_tpu.fsdp.core import LeafMeta
from apex_tpu.parallel import ParallelismPlan
from apex_tpu.serve import (
    InferenceEngine,
    PPStagedEngine,
    Request,
    SamplingConfig,
    ServeConfig,
    build_engine,
)
from apex_tpu.serve.sharded import plan_world, program_hlo
from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

MESH_OK = jax.device_count() >= 8
mesh_only = pytest.mark.skipif(
    not MESH_OK,
    reason="plan-sharded engines need >= 8 devices (conftest forces 8 "
           "virtual CPU devices)")

CFG = GPTConfig(vocab_size=64, max_seq=64, hidden=32, num_layers=4,
                num_heads=4, dtype=jnp.float32, fused_loss=False)
PARAMS = init_gpt_params(jax.random.PRNGKey(0), CFG)

PLANS = {
    "tp": ParallelismPlan(tp=4, overlap_comm=True),
    "pp": ParallelismPlan(pp=2),
    "fsdp": ParallelismPlan("fsdp", dp=8),
}
SAMPLED = SamplingConfig(temperature=0.8, top_k=16)


def _reqs():
    return [Request("a", [1, 2, 3, 4, 5], max_new_tokens=6),
            Request("b", [7, 8, 9], max_new_tokens=4),
            Request("c", list(range(10, 22)), max_new_tokens=5),
            Request("d", [5, 4, 3], max_new_tokens=5)]


def _scfg(plan=None, **kw):
    return ServeConfig(num_slots=4, block_size=8, prefill_chunk=8,
                       plan=plan, **kw)


_ORACLE = {}


def _oracle(**kw):
    """Single-chip reference stream, cached per engine shape."""
    key = tuple(sorted(kw.items()))
    if key not in _ORACLE:
        _ORACLE[key] = InferenceEngine(PARAMS, CFG, _scfg(**kw)).run(_reqs())
    return _ORACLE[key]


# ---------------------------------------------------------------------------
# stream parity: sharded streams vs the single-chip oracle


@mesh_only
@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
@pytest.mark.parametrize("strategy", sorted(PLANS))
def test_stream_parity(strategy, sampling):
    """Decode + chunked-prefill streams match the oracle exactly —
    bitwise claims for pp/fsdp, ring-reordered logits for tp (streams
    still equal; both greedy and same-key sampled draws)."""
    kw = {} if sampling == "greedy" else {"sampling": SAMPLED}
    eng = build_engine(PARAMS, CFG, _scfg(plan=PLANS[strategy], **kw))
    assert eng.run(_reqs()) == _oracle(**kw)
    assert eng.stats()["plan"] == strategy


@mesh_only
@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
@pytest.mark.parametrize("strategy", sorted(PLANS))
def test_stream_parity_quantized_kv(strategy, kv_quant):
    """The quantized pools shard like the fp pools (heads at dim 1 on
    every leaf, scales included) — codec streams match the same-codec
    oracle."""
    eng = build_engine(PARAMS, CFG,
                       _scfg(plan=PLANS[strategy], kv_quant=kv_quant))
    assert eng.run(_reqs()) == _oracle(kv_quant=kv_quant)


@mesh_only
@pytest.mark.parametrize("strategy", sorted(PLANS))
def test_verify_stream_parity(strategy):
    """Speculative q_len=k+1 verify runs sharded too: spec_k=3 streams
    match the spec_k=3 oracle (which itself matches plain greedy — the
    spec contract)."""
    eng = build_engine(PARAMS, CFG, _scfg(plan=PLANS[strategy], spec_k=3))
    assert eng.run(_reqs()) == _oracle(spec_k=3)
    assert _oracle(spec_k=3) == _oracle()


# ---------------------------------------------------------------------------
# compile-count gate (the tightened PR-5 contract, now per strategy)


@mesh_only
@pytest.mark.parametrize("strategy", sorted(PLANS))
def test_compile_count_gate(strategy):
    """Warmup contract: one compile per cold program (the PP stage jits
    serve prefill/decode/verify shapes from ONE callable, so their
    budget is the shape count); steady state: a second workload
    compiles NOTHING."""
    eng = build_engine(PARAMS, CFG, _scfg(plan=PLANS[strategy], spec_k=3))
    budget = 3 if strategy == "pp" else None  # q in {chunk, 1, spec_k+1}
    with recompile_guard(eng.programs(), budget=budget):
        eng.run(_reqs())
    with recompile_guard(eng.programs(), budget=0):
        eng.run(_reqs())
    counts = eng.compile_counts()
    if any(v is None for v in counts.values()):
        pytest.skip("this jax cannot report jit cache sizes")
    if strategy != "pp":
        assert counts["chunk_prefill"] == 1
        assert counts["decode"] == 1
        assert counts["verify"] == 1


# ---------------------------------------------------------------------------
# overlap proof from compiled HLO (tp): q>1 rings hidden, q=1 monolithic


@mesh_only
@pytest.mark.parametrize("program", ["chunk_prefill", "verify"])
def test_tp_qgt1_exits_overlapped_in_hlo(program):
    """The q_len>1 TP programs route row exits through the comm.overlap
    rings — proven from the compiled HLO: >= 0.5 of the permute wire
    bytes ride behind partial GEMMs."""
    eng = build_engine(PARAMS, CFG, _scfg(plan=PLANS["tp"], spec_k=3))
    rep = overlap_assertion(program_hlo(eng, program), 0.5)
    assert rep.permutes > 0          # the rings are actually there
    assert rep.hidden_fraction >= 0.5


@mesh_only
def test_tp_decode_stays_monolithic():
    """q_len=1 decode keeps monolithic psum exits (the PR-5 pin: a
    single-row GEMM has nothing to hide a ring hop behind)."""
    hlo = program_hlo(build_engine(PARAMS, CFG, _scfg(plan=PLANS["tp"])),
                      "decode")
    assert "collective-permute" not in hlo
    assert "all-reduce" in hlo       # the exits still reduce


# ---------------------------------------------------------------------------
# pp: bubble accounting + stage validation


@mesh_only
def test_pp_bubble_and_stats():
    eng = build_engine(PARAMS, CFG, _scfg(plan=PLANS["pp"]))
    assert isinstance(eng, PPStagedEngine)
    eng.run(_reqs())
    st = eng.stats()
    assert st["plan"] == "pp" and st["plan_world"] == 2
    S, M = 2, st["pp_microbatches"]
    assert st["pp_bubble_fraction_modeled"] == (S - 1) / (M + S - 1)
    # measured bubble: some ticks MUST idle a stage (fill/drain), but a
    # microbatched steady loop keeps most cells busy
    assert 0.0 < st["pp_bubble_fraction"] < 1.0
    assert st["hbm_chip_bytes"] < st["hbm_model_bytes"] + st["hbm_chip_bytes"]


@mesh_only
def test_pp_engine_validation():
    with pytest.raises(ValueError, match="divisible by the stage count"):
        PPStagedEngine(PARAMS, dataclasses.replace(CFG, num_layers=3),
                       _scfg(plan=ParallelismPlan(pp=2)))
    with pytest.raises(ValueError, match="must divide num_slots"):
        PPStagedEngine(PARAMS, CFG, _scfg(plan=PLANS["pp"]),
                       microbatches=3)
    with pytest.raises(ValueError, match="stage_window"):
        PPStagedEngine(PARAMS, CFG, _scfg(plan=PLANS["pp"]),
                       stage_window=0)
    with pytest.raises(ValueError, match="needs ServeConfig.plan"):
        PPStagedEngine(PARAMS, CFG, _scfg(plan=PLANS["tp"]))


# ---------------------------------------------------------------------------
# fsdp: gather stats + codec wire accounting


@mesh_only
def test_fsdp_gather_stats_and_codec_stream():
    eng = build_engine(PARAMS, CFG, _scfg(plan=PLANS["fsdp"]))
    out = eng.run(_reqs())
    st = eng.stats()
    assert st["plan"] == "fsdp" and st["plan_world"] == 8
    assert st["weight_gather_ms"] > 0.0        # measured, not modeled
    assert st["weight_gather_wire_bytes"] > 0
    # the int8 weight_gather codec serves the same greedy stream here
    # (lossy within codec tolerance; greedy argmax is stable to it)
    plan8 = ParallelismPlan("fsdp", dp=8,
                            weight_gather=CompressionConfig(policy="int8"))
    assert build_engine(PARAMS, CFG, _scfg(plan=plan8)).run(_reqs()) == out


def test_param_gather_codec_halves_wire_at_size():
    """At real leaf sizes the int8 gather wire is <= ~1/2 the fp32 wire
    (codes + block scales); tiny leaves pad toward the codec block and
    the model reports that honestly — both directions pinned."""
    big = {"qkv": LeafMeta((1024, 3, 1024), "float32"),
           "fc1": LeafMeta((1024, 4096), "float32")}
    wg = CompressionConfig(policy="int8")
    full = param_gather_wire_bytes(big, 8, None, 1)
    coded = param_gather_wire_bytes(big, 8, wg, 128)
    assert coded < 0.5 * full
    tiny = {"ln": LeafMeta((32,), "float32")}
    assert (param_gather_wire_bytes(tiny, 8, wg, 128)
            > param_gather_wire_bytes(tiny, 8, None, 1))


# ---------------------------------------------------------------------------
# stock-safe: plan plumbing, validation, accounting


def test_build_engine_plan_none_is_plain_engine():
    eng = build_engine(PARAMS, CFG, _scfg())
    assert type(eng) is InferenceEngine
    assert "plan" not in eng.stats()


def test_plan_world():
    assert plan_world(PLANS["tp"]) == 4
    assert plan_world(PLANS["pp"]) == 2
    assert plan_world(PLANS["fsdp"]) == 8
    assert plan_world(ParallelismPlan("fsdp"), devices=list(range(6))) == 6


def test_serve_strategy_refuses_composition_and_nothing():
    with pytest.raises(NotImplementedError, match="ONE"):
        ParallelismPlan("fsdp", tp=2, overlap_comm=True).serve_strategy()
    with pytest.raises(ValueError, match="shards nothing"):
        ParallelismPlan().serve_strategy()


def test_serve_overrides_refuses_optimizer_coupled_knobs():
    with pytest.raises(ValueError, match="zero1"):
        ParallelismPlan("zero1").serve_overrides()
    with pytest.raises(ValueError, match="e5m2_allgather"):
        ParallelismPlan("zero1", tp=2, e5m2_allgather=True,
                        overlap_comm=True).serve_overrides()
    with pytest.raises(ValueError, match="error-feedback|error feedback"):
        ParallelismPlan(tp=2, overlap_comm=True,
                        compression=CompressionConfig(policy="int8_ef")
                        ).serve_overrides()


def test_serve_overrides_contents():
    ov = PLANS["tp"].serve_overrides()
    assert ov["strategy"] == "tp" and ov["tp"] == 4 and ov["overlap_comm"]
    ov = PLANS["pp"].serve_overrides()
    assert ov["strategy"] == "pp" and ov["pp"] == 2
    ov = PLANS["fsdp"].serve_overrides()
    assert ov["strategy"] == "fsdp" and ov["dp_axis"] == "dp"


def test_describe_tells_the_serve_story():
    assert "q_len=1 monolithic" in PLANS["tp"].describe()
    assert "staged layer shards" in PLANS["pp"].describe()
    assert "gathered on demand" in PLANS["fsdp"].describe()
    assert "single-chip engine" in ParallelismPlan().describe()


def test_serve_config_plan_validation():
    with pytest.raises(ValueError, match="must be a ParallelismPlan"):
        _scfg(plan=object()).validate()
    with pytest.raises(ValueError, match="zero1"):
        _scfg(plan=ParallelismPlan("zero1")).validate()
    with pytest.raises(NotImplementedError, match="LoRA|lora"):
        InferenceEngine(PARAMS, CFG,
                        _scfg(plan=PLANS["pp"], lora_rank=4, max_adapters=1))


def test_regress_polarity_covers_serve_plan_headliners():
    """The stage-24 bank's gate fields classify with the right sign:
    gather latency, PP bubble and the modeled residency footprint are
    lower-is-better; the goodput headline stays higher-is-better."""
    from apex_tpu.monitor.regress import classify_metric

    assert classify_metric("weight_gather_ms") == "lower"
    assert classify_metric("pp_bubble_fraction") == "lower"
    assert classify_metric("hbm_model_bytes") == "lower"
    assert classify_metric("hbm_chip_bytes") == "lower"
    assert classify_metric("goodput_rps") == "higher"
    # plan_world is topology, not a metric — never gated
    assert classify_metric("plan_world") is None


def test_hbm_serve_accounting_splits_strategies():
    """tp divides everything by world; pp divides layers only; fsdp
    shards layers and carries a one-layer gather workspace."""
    kv = 1000.0
    single = hbm_serve_bytes(PARAMS, strategy="single", world=1, kv_bytes=kv)
    tp = hbm_serve_bytes(PARAMS, strategy="tp", world=4, kv_bytes=kv / 4,
                         num_layers=CFG.num_layers)
    pp = hbm_serve_bytes(PARAMS, strategy="pp", world=2, kv_bytes=kv / 2,
                         num_layers=CFG.num_layers)
    fsdp = hbm_serve_bytes(PARAMS, strategy="fsdp", world=8, kv_bytes=kv,
                           num_layers=CFG.num_layers)
    assert single["total"] > max(tp["total"], pp["total"])
    assert tp["params_bytes"] == pytest.approx(single["params_bytes"] / 4)
    # pp keeps a full embed/head replica on the edge stages
    assert pp["params_bytes"] > single["params_bytes"] / 2 / 2
    assert fsdp["gather_workspace_bytes"] > 0
    assert single["gather_workspace_bytes"] == 0
    with pytest.raises(ValueError, match="strategy"):
        hbm_serve_bytes(PARAMS, strategy="zz", world=2, kv_bytes=0.0)
