"""The Laguna cell kind (``train_laguna``) end to end on the CPU at toy size:
a sound run is ``correct`` and prints its counters and its attention plan;
each control and fault the limits were set against is not; the
configuration against the catalog's row; the counts against counts by hand;
the window's tiles; the readers on hand-made events. The program against
the reference leaf by leaf, and the four chips' shares, are
``tests/test_laguna_model.py``'s."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import counts_laguna
import reference_laguna
import run as runner
import scopes_laguna
import test_perfbench_appends
import traffic
import weights_laguna
import xplane

from apex_tpu.monitor.trace import ROUTING_COUNTERS, split_scope
from apex_tpu.transformer import laguna

SEED = 2**31 + 4242
CELL = "toy-laguna.train-toy-laguna"
BENCH = os.path.join(_paths.DATA, "BENCHMARK.laguna.json")
REAL = "laguna-s-2.1"
REAL_CELL = "laguna-s-2.1.train-swa-s8k"
NEW_METRICS = ("laguna_step_mfu_pct", "swa_attn_roofline", "swa_step_share_pct",
               "full_attn_laguna_roofline", "moe_experts_laguna_roofline",
               "moe_laguna_step_share_pct", "lm_head_loss_laguna_roofline",
               "adam_step_laguna_roofline")
SHARES = ("swa_step_share_pct", "moe_laguna_step_share_pct")   # no peak needed
SHARED_METRICS = ("device_idle_pct.train", "train_scope_coverage_pct", "train_recompute_pct",
                  "train_layout_pct", "setup_compile_s", "setup_lower_s", "setup_steps_s",
                  "setup_step_compiles", "setup_cache_misses")
LIMITS = ("grad_norm_gap", "grad_error", "grad_error_worst_leaf", "update_norm_gap", "loss_gap")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    return _load(_paths.DATA, "perfbench", "configs", "toy-laguna.json")


@pytest.fixture(scope="module")
def real():
    return _load(_paths.PERFBENCH, "configs", REAL + ".json")


@pytest.fixture(scope="module")
def kind():
    return runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train_laguna.py"), "kind_tl")


@pytest.fixture(scope="module")
def sound():
    return runner.run_cell(CELL, SEED, 1.0, False, require_tpu=False, bench_path=BENCH)


def test_toy_cell_is_correct_and_reports_its_end_to_end_metrics(sound):
    assert sound["correct"] is True, sound["compared"]
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["compared"]) == set(LIMITS) | {"compiles_in_window"}


def test_a_traced_cpu_run_writes_no_device_metric_and_prints_its_counters(capsys):
    r = runner.run_cell(CELL, SEED + 1, 1.0, True, require_tpu=False, bench_path=BENCH)
    assert r["correct"] is True and r["metrics"] == {}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    counted = next(l for l in lines if l.get("phase") == "routing counted")
    assert set(ROUTING_COUNTERS) | {"rows_gathered", "rows_gathered_over_held"} <= set(counted)
    # 2 rows x 128 positions, 3 of 8 a position, 3 held, four expert layers
    assert counted["pairs_uniform"] == 4 * 256 * 3 * 3 / 8 and counted["pairs_held"] > 0
    planned = next(l for l in lines if l.get("phase") == "attention planned")
    assert planned["sliding_attention"]["heads"] == 6 and planned["full_attention"]["heads"] == 4
    window = next(l for l in lines if l.get("phase") == "window closed")
    assert counted["steps_counted"] == window["steps"] == r["attempted"]


# ---------------------------------------------------------------------------
# the reference: the controls and the faults are not correct; the program is

def _batches(toy, seed=SEED, rows=2, seq=128):
    return [tuple(map(jnp.asarray, traffic.train_batch(seed, i, rows, seq, toy["vocab_size"])))
            for i in (1, 2, 3)]


@pytest.fixture(scope="module")
def judged(toy, kind):
    limits = _load(_paths.DATA, "perfbench", "limits", CELL + ".json")["limits"]
    make = lambda: weights_laguna.make_params(toy, SEED)
    shape = reference_laguna.model_shape(toy)
    run = lambda **kw: reference_laguna.train_reference(make, _batches(toy), toy["train"], shape,
                                                        1, **kw)
    ref = run()
    return lambda **kw: {n["name"]: n for n in kind.train.compare(run(**kw) if kw else ref, ref,
                                                                  limits)}


def test_the_reference_against_itself_is_correct(judged):
    assert all(n["ok"] for n in judged().values())


@pytest.mark.parametrize("how,by", [
    (dict(quant="fp8"), "grad_error"), (dict(rows=slice(0, 1)), "grad_error"),
    (dict(fault="no-window"), "grad_norm_gap"), (dict(fault="no-gate"), "grad_norm_gap"),
    (dict(fault="full-rotation"), "grad_norm_gap"), (dict(fault="no-yarn"), "grad_norm_gap"),
    (dict(fault="no-scale"), "grad_norm_gap"), (dict(fault="no-shared-gate"), "grad_norm_gap"),
], ids=["fp8", "half-batch", "no-window", "no-gate", "full-rotation", "no-yarn", "no-scale",
        "no-shared-gate"])
def test_each_control_and_fault_is_not_correct(judged, sound, how, by):
    verdict = judged(**how)
    assert not verdict[by]["ok"], verdict
    assert verdict[by]["value"] >= 2 * sound["compared"][by]["value"]


def test_the_faults_planted_are_the_ones_the_limits_name():
    assert set(reference_laguna.FAULTS) == {"no-window", "no-gate", "full-rotation", "no-yarn",
                                            "no-scale", "no-shared-gate"}
    limits = _load(_paths.PERFBENCH, "limits", REAL_CELL + ".json")
    text = json.dumps(limits)
    for fault in reference_laguna.FAULTS + ("half_batch", "fp8", "int8"):
        assert fault in text, fault
    assert set(limits["limits"]) == set(LIMITS)


@pytest.mark.parametrize("name", LIMITS)
def test_each_limit_lies_between_its_two_readings_with_room_on_both_sides(name):
    """``lower`` is the largest sound reading, ``upper`` the least reading of
    a control or fault that stands clear of the sound runs on that number."""
    limits = _load(_paths.PERFBENCH, "limits", REAL_CELL + ".json")
    limit, read = limits["limits"][name], limits["readings"][name]
    assert 1.5 * read["lower"] <= limit <= read["upper"] / 1.5, (read["lower"], limit, read["upper"])


def test_a_fault_planted_in_the_program_is_not_correct(monkeypatch):
    """The window left out of the timed path itself: the sliding layers run
    causal over the whole row."""
    from apex_tpu.ops.attention import CAUSAL
    monkeypatch.setattr(laguna, "sliding_window_mask", lambda window: CAUSAL)
    r = runner.run_cell(CELL, SEED, 1.0, False, require_tpu=False, bench_path=BENCH)
    assert r["correct"] is False
    assert not r["compared"]["grad_norm_gap"]["ok"]


def test_the_float32_reference_sets_the_matmul_precision():
    import inspect
    for fn in (reference_laguna.loss_fn, reference_laguna.step_gradient,
               reference_laguna.step_loss):
        assert 'default_matmul_precision("highest")' in inspect.getsource(fn)


def test_the_same_seed_gives_the_same_weights_and_a_large_one_is_taken(toy):
    a = weights_laguna.make_params(toy, 2**31 + 7)
    b = weights_laguna.make_params(toy, 2**31 + 7)
    c = weights_laguna.make_params(toy, 2**31 + 8)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert float(jnp.abs(a["head"]["lm"].astype(jnp.float32)
                         - c["head"]["lm"].astype(jnp.float32)).max()) > 0
    assert abs(float(jnp.std(a["embed"]["tok"].astype(jnp.float32))) - 1.0) < 0.05
    sliding = a["periods"][weights_laguna.SLIDING]
    assert sliding["wq"].shape == (1, 3, 64, 6 * 16) and sliding["w_gate"].shape == (1, 3, 3, 64, 32)
    assert a["periods"][weights_laguna.FULL]["wg"].shape == (1, 1, 64, 4)
    assert a["periods"][weights_laguna.DENSE]["w_up"].shape == (1, 1, 64, 96)


# ---------------------------------------------------------------------------
# the counts, by hand, the window's tiles, and the configuration

def test_counts_at_the_published_widths_against_counts_by_hand(real, kind):
    full = 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48
    sliding = 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72
    assert (full, sliding) == (counts_laguna.attn_params(real, "full_attention"),
                               counts_laguna.attn_params(real, "sliding_attention"))
    norms = 2 * 3072 + 2 * 128
    assert full + norms == 44_194_048 and sliding + norms == 63_142_144
    expert = 3 * 3072 * 1024
    assert expert == counts_laguna.expert_params(real) == 9_437_184
    outside = 786_432 + 9_437_184 + 3072
    layer0 = full + norms + 113_246_208
    held = (layer0 + 3 * (sliding + norms + outside + 8 * expert)
            + (full + norms + outside + 8 * expert) + 2 * 12_544 * 3072 + 3072)
    assert (layer0, held) == (157_440_256, counts_laguna.n_params(real)) == (157_440_256, 811_030_784)
    assert sliding + norms + outside + 256 * expert == 2_489_287_936   # a sliding layer, whole

    class Ctx:
        config = real
    shapes = jax.eval_shape(lambda: kind._model(Ctx).init_params(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == held


def test_attention_and_step_operations_at_the_cells_size(real):
    assert counts_laguna.window_pairs(8192, 512) == 8192 * 512 - 512 * 511 // 2 == 4_063_488
    assert counts_laguna.full_pairs(8192) == 33_558_528
    swa = 3.5 * 512 * 3 * 72 * 4_063_488 * 2
    full = 3.5 * 512 * 2 * 48 * 33_558_528 * 2
    assert counts_laguna.swa_attn_flops_per_step(real, 2, 8192) == pytest.approx(swa)
    assert counts_laguna.attn_flops_per_step(real, 2, 8192) == pytest.approx(swa + full)
    pairs = 4 * 16_384 * 10 * 8 / 256
    dense = 6.0 * 16_384 * (2 * 44_187_648 + 3 * 63_135_744 + 113_246_208
                            + 4 * (786_432 + 9_440_256))
    head = 3 * 2.0 * 16_384 * 3072 * 12_544
    total = counts_laguna.train_flops_per_step(real, 2, 8192, pairs)
    assert total == pytest.approx(dense + swa + full + 6.0 * 9_437_184 * pairs + head)
    # the window layers do about half of the attention's work a position forward
    assert 0.4 < (3 * 72 * 4_063_488) / (3 * 72 * 4_063_488 + 2 * 48 * 33_558_528) * 2.2 < 0.7


def test_the_window_tiles_the_plan_visits_hold_the_pairs_the_roofline_credits(real):
    """The roofline credits the visible pairs; the tiles the listed schedule
    visits hold them all, and at the program's tile no more than twice."""
    from apex_tpu.ops.attention import _listed_tiles, sliding_window_mask

    seq = _load(_paths.PERFBENCH, "traffic", "train-swa-s8k.json")["seq"]
    tile = laguna.WINDOW_BLOCK
    by_row, _ = _listed_tiles(sliding_window_mask(real["sliding_window"]), seq, seq, tile, tile)
    visited = by_row.shape[1] * tile * tile
    visible = counts_laguna.window_pairs(seq, real["sliding_window"])
    assert visible <= visited <= 2.05 * visible


def check_benchmark(bench):
    """This family's entries in a ``BENCHMARK.json``, found by name."""
    row = _load(_paths.DATA, "catalog_row.laguna-s-2.1.json")
    entry = next(c for c in bench["configs"] if c["name"] == REAL)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "train-swa-s8k", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [REAL_CELL] and by_name[name]["moves"] == "train_tokens_per_s"
        assert os.path.exists(os.path.join(_paths.PERFBENCH, "metrics", name + ".py"))
    for name in SHARED_METRICS:
        assert REAL_CELL in by_name[name]["workloads"]
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert REAL_CELL in tokens["workloads"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 4)


def test_the_configuration_holds_every_catalog_key_but_the_reduced_ones(real):
    row = _load(_paths.DATA, "catalog_row.laguna-s-2.1.json")
    bench = _load(_paths.ROOT, "BENCHMARK.json")
    check_benchmark(bench)
    entry = next(c for c in bench["configs"] if c["name"] == REAL)
    assert (real["source"], real["reduced"]) == (entry["source"], entry["reduced"])
    for key, value in row["config"].items():
        if key in real["reduced"]:
            assert real["reduced_from"][key] == value and real[key] < value
        else:
            assert real[key] == value, key
    assert real["experts_held"] == [0, real["num_experts"]]
    assert (real["num_hidden_layers"], real["num_experts"], real["vocab_size"]) == (5, 8, 12_544)
    for key in ("qk_norm", "gate", "shared_expert_gate", "router", "balance_loss", "window",
                "rope_full", "rope_sliding", "sequence", "param_dtype", "init", "embedding_std"):
        assert key in real["assumed"], key
    assert "low 9" in real["assumed"]["rope_full"] and "high 18" in real["assumed"]["rope_full"]
    for key in ("reduced_why", "deployment"):
        assert key in real
    assert "32 v5e chips" in real["deployment"]
    mix = _load(_paths.PERFBENCH, "traffic", "train-swa-s8k.json")
    assert (mix["kind"], mix["rows_per_chip"], mix["seq"], mix["in_flight_steps"]) == (
        "train_laguna", 2, 8192, 8)
    assert (mix["mesh"], mix["check"]["steps"]) == ({"dp": 1, "tp": 1}, 3)


def test_the_kind_builds_the_model_the_configuration_publishes(real, kind):
    class Ctx:
        config = real
    model = kind._model(Ctx)
    assert model == laguna.LagunaConfig()             # the program's defaults are the file's
    assert model.full_rope[1].ramp_bounds(64, 500000.0) == (9, 18)
    assert model.routed.tile_rows(16_384) == 256 and model.routed.rows_per_pass(16_384, 8) == 7680


def test_no_other_family_claims_the_configuration(real, toy):
    """Exactly one family's predicate holds of this configuration, real or
    toy: this one's."""
    preds = test_perfbench_appends._predicates()
    for model in (real, toy):
        held = [name for name, pred in preds.items() if pred({"kind": "train", "model": model})]
        assert held == ["scopes_laguna.is_laguna"], held


# ---------------------------------------------------------------------------
# the readers, on hand-made events

MS = 1e6  # ns
FWD = "jit(train_step)/jvp()/while/body/layer/"
BWD = "jit(train_step)/transpose(jvp())/while/body/layer/checkpoint/"


def _rec(opcode, op_name, moves_only=False):
    return {"op_name": op_name, "opcode": opcode, "moves_only": moves_only, "container": False,
            "operands": []}


def _reader(name):
    return runner._module(os.path.join(_paths.PERFBENCH, "metrics", name + ".py"),
                          "metric_" + name).read


def _hand_made(real):
    """One step of 200 ms: the window layers' flash 10 + 15 + 15 and their
    projections 20 and gate 5; the full layers' flash 30 + 25 (under
    layer/attn); the routed layer 40; the optimizer 25; the head's kernels
    5 + 5 + 5."""
    table = {
        "flash_fwd.1": _rec("custom-call", FWD + "swa/core/flash_fwd/pallas_call"),
        "flash_bwd_dq.2": _rec("custom-call", BWD + "swa/core/flash_bwd_dq/pallas_call"),
        "flash_bwd_dkv.3": _rec("custom-call", BWD + "swa/core/flash_bwd_dkv/pallas_call"),
        "fusion.4": _rec("fusion", FWD + "swa/qkv/dot_general"),
        "fusion.5": _rec("fusion", FWD + "swa/gate/logistic"),
        "flash_fwd.6": _rec("custom-call", FWD + "attn/core/flash_fwd/pallas_call"),
        "flash_bwd_dkv.7": _rec("custom-call", BWD + "attn/core/flash_bwd_dkv/pallas_call"),
        "fusion.8": _rec("fusion", FWD + "moe/experts/grouped_fwd/pallas_call"),
        "fusion.9": _rec("fusion", "jit(train_step)/opt/mul"),
        "lm_head_fwd.10": _rec("custom-call", "jit(train_step)/jvp()/lm_head_loss/lm_head_fwd/pallas_call"),
        "lm_head_bwd_dx.11": _rec("custom-call", "jit(train_step)/transpose(jvp())/lm_head_loss/lm_head_bwd_dx/pallas_call"),
        "lm_head_bwd_dw.12": _rec("custom-call", "jit(train_step)/transpose(jvp())/lm_head_loss/lm_head_bwd_dw/pallas_call"),
    }
    spans = [("flash_fwd.1", "custom-call", 10), ("flash_bwd_dq.2", "custom-call", 15),
             ("flash_bwd_dkv.3", "custom-call", 15), ("fusion.4", "fusion", 20),
             ("fusion.5", "fusion", 5), ("flash_fwd.6", "custom-call", 30),
             ("flash_bwd_dkv.7", "custom-call", 25), ("fusion.8", "fusion", 40),
             ("fusion.9", "fusion", 25), ("lm_head_fwd.10", "custom-call", 5),
             ("lm_head_bwd_dx.11", "custom-call", 5), ("lm_head_bwd_dw.12", "custom-call", 5)]
    assert sum(ms for _, _, ms in spans) == 200
    ops, at = [], 0.0
    for name, opcode, ms in spans:
        ops.append((f"%{name} = bf16[8]{{0}} {opcode}(%a)", at, ms * MS))
        at += ms * MS
    trace = xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(
        ops, [("jit_train_step(7)", 0, 200 * MS)])}, [])
    import peaks
    facts = {"kind": "train", "rows": 2, "seq": 8192, "chips": 1, "model": real,
             "peaks": peaks.peaks_for("TPU v5 lite"), "counters": {"pairs_held": 20480.0},
             "_scope_tables": {"jit_train_step": (table, split_scope)}}
    return facts, trace


def test_the_attention_and_step_readers_on_hand_made_events(real):
    facts, trace = _hand_made(real)
    flops = counts_laguna.swa_attn_flops_per_step(real, 2, 8192)
    assert _reader("swa_attn_roofline")(facts, trace) == pytest.approx(100 * flops / 197e12 / 0.040)
    assert _reader("swa_step_share_pct")(facts, trace) == pytest.approx(100 * 65 / 200)
    assert _reader("laguna_step_mfu_pct")(facts, trace) == pytest.approx(
        100 * counts_laguna.train_flops_per_step(real, 2, 8192, 20480.0) / 0.200 / 197e12)
    full = counts_laguna.full_attn_flops_per_step(real, 2, 8192)
    assert full + flops == pytest.approx(counts_laguna.attn_flops_per_step(real, 2, 8192))
    assert _reader("full_attn_laguna_roofline")(facts, trace) == pytest.approx(
        100 * full / 197e12 / 0.055)


def test_the_routed_head_and_optimizer_readers_on_hand_made_events(real):
    facts, trace = _hand_made(real)
    assert _reader("moe_laguna_step_share_pct")(facts, trace) == pytest.approx(100 * 40 / 200)
    least = max(counts_laguna.experts_flops_per_step(real, 20480.0) / 197e12,
                counts_laguna.experts_bytes_per_step(real, 20480.0) / 819e9)
    assert _reader("moe_experts_laguna_roofline")(facts, trace) == pytest.approx(
        100 * least / 0.040)
    # 4 expert layers x 8 experts, weights read twice and written once, and
    # 20,480 pairs' rows of 3,072 in and out both ways
    assert counts_laguna.experts_bytes_per_step(real, 20480.0) == (
        3 * 2 * 4 * 8 * 9_437_184 + 4 * 2 * 3072 * 20480.0)
    assert _reader("lm_head_loss_laguna_roofline")(facts, trace) == pytest.approx(
        100 * counts_laguna.lm_head_loss_flops_per_step(real, 2, 8192) / 197e12 / 0.015)
    assert _reader("adam_step_laguna_roofline")(facts, trace) == pytest.approx(
        100 * 811_030_784 * 22 / 819e9 / 0.025)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_there_is_nothing_to_read(real, name):
    """No trace; no chip's peaks; a step of another family; the kind's
    counters missing (a program that hands none out)."""
    read = _reader(name)
    facts, trace = _hand_made(real)
    assert read(dict(facts), None) is None
    if name not in SHARES:
        assert read(dict(facts, peaks=None), trace) is None
    for other in ("olmo-hybrid-7b.json", "sdar-30b-a3b.json", "deepseek-v2-lite.json",
                  "gpt2-medium.json"):
        model = _load(_paths.PERFBENCH, "configs", other)
        assert read(dict(facts, model=model), trace) is None, other
    if name in ("laguna_step_mfu_pct", "moe_experts_laguna_roofline"):
        assert read({k: v for k, v in facts.items() if k != "counters"}, trace) is None


def test_the_accepted_families_readers_find_nothing_of_theirs_in_this_step(real):
    facts, trace = _hand_made(real)
    for other in ("dsv2_step_mfu_pct", "mla_attn_roofline", "mla_step_share_pct",
                  "sdar_step_mfu_pct", "blockdiff_attn_roofline", "hybrid_step_mfu_pct",
                  "linattn_step_share_pct", "moe_dsv2_step_share_pct", "moe_experts_dsv2_roofline",
                  "lm_head_loss_dsv2_roofline", "adam_step_dsv2_roofline"):
        assert _reader(other)(dict(facts), trace) is None, other
