"""The SmallThinker cell kind (``train_smallthinker``) end to end on the CPU
at toy size: a sound run is ``correct`` and prints its counters and its
attention plan; each control and fault the limits were set against is not,
in the reference and planted in the program; the configuration against the
catalog's row; the counts against counts by hand; the readers on hand-made
events. The program against the reference leaf by leaf, and the four chips'
shares, are ``tests/test_smallthinker_model.py``'s."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import _paths
import counts_smallthinker
import reference_smallthinker
import run as runner
import test_perfbench_appends
import test_perfbench_laguna
import traffic
import weights_smallthinker

from apex_tpu.monitor.trace import ROUTING_COUNTERS
from apex_tpu.transformer import smallthinker

SEED = 2**31 + 4242
CELL = "toy-smallthinker.train-toy-smallthinker"
BENCH = os.path.join(_paths.DATA, "BENCHMARK.smallthinker.json")
REAL = "smallthinker-21b-a3b"
REAL_CELL = "smallthinker-21b-a3b.train-swa4k-s16k"
NEW_METRICS = ("smallthinker_step_mfu_pct", "swa_attn_smallthinker_roofline",
               "nope_attn_roofline", "moe_experts_smallthinker_roofline",
               "moe_smallthinker_step_share_pct", "lm_head_loss_smallthinker_roofline")
SHARES = ("moe_smallthinker_step_share_pct",)   # no peak needed
SHARED_METRICS = test_perfbench_laguna.SHARED_METRICS
LIMITS = test_perfbench_laguna.LIMITS


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    return _load(_paths.DATA, "perfbench", "configs", "toy-smallthinker.json")


@pytest.fixture(scope="module")
def real():
    return _load(_paths.PERFBENCH, "configs", REAL + ".json")


@pytest.fixture(scope="module")
def kind():
    return runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train_smallthinker.py"),
                          "kind_ts")


def test_toy_cell_is_correct_reports_its_metrics_and_prints_its_counters(capsys):
    r = runner.run_cell(CELL, SEED, 1.0, False, require_tpu=False, bench_path=BENCH)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["compared"]) == set(LIMITS) | {"compiles_in_window"}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    counted = next(l for l in lines if l.get("phase") == "routing counted")
    assert set(ROUTING_COUNTERS) | {"rows_gathered", "rows_gathered_over_held"} <= set(counted)
    # 2 rows x 128 positions, 3 of 8 a position, 3 held, two layers
    assert counted["pairs_uniform"] == 2 * 256 * 3 * 3 / 8 and counted["pairs_held"] > 0
    planned = next(l for l in lines if l.get("phase") == "attention planned")
    assert planned["window"]["layers"] == 1 and planned["full"]["layers"] == 1
    window = next(l for l in lines if l.get("phase") == "window closed")
    assert counted["steps_counted"] == window["steps"] == r["attempted"]


def test_a_fault_planted_in_the_program_is_not_correct(monkeypatch):
    """SiLU in place of ReLU in the timed path itself."""
    real_mlp = smallthinker.routed_experts_mlp
    monkeypatch.setattr(smallthinker, "routed_experts_mlp",
                        lambda p, m, cfg, held, routing, act: real_mlp(p, m, cfg, held, routing,
                                                                       jax.nn.silu))
    r = runner.run_cell(CELL, SEED, 1.0, False, require_tpu=False, bench_path=BENCH)
    assert r["correct"] is False
    assert not r["compared"]["grad_norm_gap"]["ok"]


# ---------------------------------------------------------------------------
# the reference: the controls and the faults are not correct

def _batches(toy, seed=SEED, rows=2, seq=128):
    return [tuple(map(jnp.asarray, traffic.train_batch(seed, i, rows, seq, toy["vocab_size"])))
            for i in (1, 2, 3)]


@pytest.fixture(scope="module")
def judged(toy, kind):
    limits = _load(_paths.DATA, "perfbench", "limits", CELL + ".json")["limits"]
    make = lambda: weights_smallthinker.make_params(toy, SEED)
    shape = reference_smallthinker.model_shape(toy)
    run = lambda **kw: reference_smallthinker.train_reference(make, _batches(toy), toy["train"],
                                                              shape, 1, **kw)
    ref = run()
    return lambda **kw: {n["name"]: n for n in kind.train.compare(run(**kw) if kw else ref, ref,
                                                                  limits)}


@pytest.mark.parametrize("how,by", [
    ({}, None), (dict(quant="fp8"), "grad_error"), (dict(rows=slice(0, 1)), "grad_error"),
    (dict(fault="router-reads-m"), "grad_error_worst_leaf"), (dict(fault="silu"), "grad_norm_gap"),
    (dict(fault="full-rotation"), "grad_norm_gap"), (dict(fault="no-window"), "grad_norm_gap"),
    (dict(fault="no-renorm"), "grad_norm_gap"),
], ids=["itself", "fp8", "half-batch", "router-reads-m", "silu", "full-rotation", "no-window",
        "no-renorm"])
def test_the_reference_against_itself_is_correct_and_each_control_and_fault_is_not(
        judged, how, by):
    verdict = judged(**how)
    if by is None:
        assert all(n["ok"] for n in verdict.values())
    else:
        assert not verdict[by]["ok"], verdict


def test_the_faults_planted_are_the_ones_the_limits_name():
    assert set(reference_smallthinker.FAULTS) == {"router-reads-m", "silu", "full-rotation",
                                                  "no-window", "no-renorm"}
    limits = _load(_paths.PERFBENCH, "limits", REAL_CELL + ".json")
    text = json.dumps(limits)
    for fault in reference_smallthinker.FAULTS + ("half_batch", "fp8", "int8"):
        assert fault in text, fault
    assert set(limits["limits"]) == set(LIMITS)


@pytest.mark.parametrize("name", LIMITS)
def test_each_limit_lies_between_its_two_readings_with_room_on_both_sides(name):
    """``lower`` is the largest sound reading, ``upper`` the least reading of
    a control or fault that stands clear of the sound runs on that number."""
    limits = _load(_paths.PERFBENCH, "limits", REAL_CELL + ".json")
    limit, read = limits["limits"][name], limits["readings"][name]
    assert 1.5 * read["lower"] <= limit <= read["upper"] / 1.5, (read["lower"], limit, read["upper"])


def test_the_float32_reference_sets_the_matmul_precision_and_a_large_seed_is_taken(toy):
    import inspect
    for fn in (reference_smallthinker.knobbed_loss, reference_smallthinker.step_gradient,
               reference_smallthinker.step_loss):
        assert 'default_matmul_precision("highest")' in inspect.getsource(fn)
    a = weights_smallthinker.make_params(toy, 2**31 + 7)
    b = weights_smallthinker.make_params(toy, 2**31 + 8)
    assert float(jnp.abs(a["head"]["lm"].astype(jnp.float32)
                         - b["head"]["lm"].astype(jnp.float32)).max()) > 0
    assert abs(float(jnp.std(a["embed"]["tok"].astype(jnp.float32))) - 1.0) < 0.05
    window = a["periods"][weights_smallthinker.WINDOW]
    assert window["wq"].shape == (1, 1, 64, 4 * 16) and window["w_gate"].shape == (1, 1, 3, 64, 32)
    assert a["periods"][weights_smallthinker.FULL]["router"].shape == (1, 1, 64, 8)


# ---------------------------------------------------------------------------
# the counts, by hand, the window's tiles, and the configuration

def test_counts_at_the_published_widths_against_counts_by_hand(real, kind):
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert attn == counts_smallthinker.attn_params(real) == 20_971_520
    outside = attn + 2 * 2560 + 2560 * 64
    assert outside == 21_140_480
    expert = 3 * 2560 * 768
    assert expert == counts_smallthinker.expert_params(real) == 5_898_240
    held = 4 * (outside + 16 * expert) + 2 * 37_984 * 2560 + 2560
    assert held == counts_smallthinker.n_params(real) == 656_529_920
    assert outside + 64 * expert == 398_627_840                     # a layer, whole

    class Ctx:
        config = real
    shapes = jax.eval_shape(lambda: kind._model(Ctx).init_params(jax.random.PRNGKey(0)))
    assert sum(int(jnp.size(a)) for a in jax.tree.leaves(shapes)) == held


def test_attention_and_step_operations_at_the_cells_size(real):
    window = 16_384 * 4096 - 4096 * 4095 // 2
    full = 16_384 * 16_385 // 2
    swa = 3.5 * 512 * 28 * 3 * window * 2
    nope = 3.5 * 512 * 28 * full * 2
    assert counts_smallthinker.swa_attn_flops_per_step(real, 2, 16_384) == pytest.approx(swa)
    assert counts_smallthinker.nope_attn_flops_per_step(real, 2, 16_384) == pytest.approx(nope)
    pairs = 4 * 32_768 * 6 * 16 / 64
    dense = 6.0 * 32_768 * 4 * (20_971_520 + 163_840)
    head = 3 * 2.0 * 32_768 * 2560 * 37_984
    total = counts_smallthinker.train_flops_per_step(real, 2, 16_384, pairs)
    assert total == pytest.approx(dense + swa + nope + 6.0 * 5_898_240 * pairs + head)


def test_the_window_tiles_the_plan_visits_hold_the_pairs_the_roofline_credits(real, kind):
    """The tiles the window layers' grid visits hold the visible pairs, and
    at the program's tile no more than 1.3 times them: 70 tiles a head at
    1,024 x 1,024 (73.4 M pairs for 58.7 M), 252 at 512 x 512."""
    from apex_tpu.ops.attention import _listed_tiles, sliding_window_mask

    seq = _load(_paths.PERFBENCH, "traffic", "train-swa4k-s16k.json")["seq"]
    tile = smallthinker.WINDOW_BLOCK
    by_row, _ = _listed_tiles(sliding_window_mask(real["sliding_window_size"]), seq, seq, tile, tile)
    visited = by_row.shape[1] * tile * tile
    visible = counts_smallthinker.window_pairs(seq, real["sliding_window_size"])
    assert visible <= visited <= 1.3 * visible
    assert by_row.shape[1] == {512: 252, 1024: 70}[tile]


def check_benchmark(bench):
    """This family's entries in a ``BENCHMARK.json``, found by name."""
    row = _load(_paths.DATA, "catalog_row.smallthinker-21b-a3b.json")
    entry = next(c for c in bench["configs"] if c["name"] == REAL)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "train-swa4k-s16k", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [REAL_CELL] and by_name[name]["moves"] == "train_tokens_per_s"
        assert os.path.exists(os.path.join(_paths.PERFBENCH, "metrics", name + ".py"))
    for name in SHARED_METRICS:
        assert REAL_CELL in by_name[name]["workloads"]
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert REAL_CELL in tokens["workloads"]


def test_the_configuration_holds_every_catalog_key_but_the_reduced_ones(real, kind):
    row = _load(_paths.DATA, "catalog_row.smallthinker-21b-a3b.json")
    bench = _load(_paths.ROOT, "BENCHMARK.json")
    check_benchmark(bench)
    entry = next(c for c in bench["configs"] if c["name"] == REAL)
    assert (real["source"], real["reduced"]) == (entry["source"], entry["reduced"])
    for key, value in row["config"].items():
        if key in real["reduced"]:
            assert real["reduced_from"][key] == value and real[key] < value
        else:
            assert real[key] == value, key
    assert real["experts_held"] == [0, real["moe_num_primary_experts"]]
    assert (real["num_hidden_layers"], real["moe_num_primary_experts"], real["vocab_size"]) == (
        4, 16, 37_984)
    for key in ("router_input", "qk_norm", "experts", "balance_loss", "window", "rope",
                "sequence", "param_dtype", "init", "embedding_std"):
        assert key in real["assumed"], key
    assert "four v5e chips" in real["deployment"] and "656,529,920" in real["reduced_why"]
    mix = _load(_paths.PERFBENCH, "traffic", "train-swa4k-s16k.json")
    assert (mix["kind"], mix["rows_per_chip"], mix["seq"], mix["in_flight_steps"]) == (
        "train_smallthinker", 2, 16_384, 8)
    assert (mix["mesh"], mix["check"]) == ({"dp": 1, "tp": 1},
                                           {"steps": 3, "reference_rows_per_block": 1})

    class Ctx:
        config = real
    model = kind._model(Ctx)
    assert model == smallthinker.SmallThinkerConfig()    # the program's defaults are the file's
    assert model.routed.tile_rows(32_768) == 1024 and model.routed.passes(32_768, 16) == 3


def test_no_other_family_claims_the_configuration(real, toy):
    preds = test_perfbench_appends._predicates()
    for model in (real, toy):
        held = [name for name, pred in preds.items() if pred({"kind": "train", "model": model})]
        assert held == ["scopes_smallthinker.is_smallthinker"], held


# ---------------------------------------------------------------------------
# the readers, on hand-made events

def _reader(name):
    return runner._module(os.path.join(_paths.PERFBENCH, "metrics", name + ".py"),
                          "metric_" + name).read


def _hand_made(real):
    """The Laguna test's step of 200 ms, read as this model's: the window
    layers' flash 10 + 15 + 15, the full layer's 30 + 25, the routed layer
    40, the head's kernels 5 + 5 + 5."""
    facts, trace = test_perfbench_laguna._hand_made(real)
    return dict(facts, seq=16_384), trace


def test_the_readers_on_hand_made_events(real):
    facts, trace = _hand_made(real)
    swa = counts_smallthinker.swa_attn_flops_per_step(real, 2, 16_384)
    nope = counts_smallthinker.nope_attn_flops_per_step(real, 2, 16_384)
    assert _reader("swa_attn_smallthinker_roofline")(facts, trace) == pytest.approx(
        100 * swa / 197e12 / 0.040)
    assert _reader("nope_attn_roofline")(facts, trace) == pytest.approx(100 * nope / 197e12 / 0.055)
    assert _reader("smallthinker_step_mfu_pct")(facts, trace) == pytest.approx(
        100 * counts_smallthinker.train_flops_per_step(real, 2, 16_384, 20480.0) / 0.200 / 197e12)
    assert _reader("moe_smallthinker_step_share_pct")(facts, trace) == pytest.approx(100 * 40 / 200)
    least = max(counts_smallthinker.experts_flops_per_step(real, 20480.0) / 197e12,
                counts_smallthinker.experts_bytes_per_step(real, 20480.0) / 819e9)
    assert _reader("moe_experts_smallthinker_roofline")(facts, trace) == pytest.approx(
        100 * least / 0.040)
    assert _reader("lm_head_loss_smallthinker_roofline")(facts, trace) == pytest.approx(
        100 * counts_smallthinker.lm_head_loss_flops_per_step(real, 2, 16_384) / 197e12 / 0.015)


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
                  "laguna-s-2.1.json", "gpt2-medium.json"):
        model = _load(_paths.PERFBENCH, "configs", other)
        assert read(dict(facts, model=model), trace) is None, other
    if name in ("smallthinker_step_mfu_pct", "moe_experts_smallthinker_roofline"):
        assert read({k: v for k, v in facts.items() if k != "counters"}, trace) is None


def test_the_accepted_families_readers_find_nothing_of_theirs_in_this_step(real):
    facts, trace = _hand_made(real)
    for other in test_perfbench_laguna.NEW_METRICS + (
            "dsv2_step_mfu_pct", "mla_attn_roofline", "mla_step_share_pct", "sdar_step_mfu_pct",
            "blockdiff_attn_roofline", "hybrid_step_mfu_pct", "linattn_step_share_pct",
            "moe_dsv2_step_share_pct", "moe_experts_dsv2_roofline"):
        assert _reader(other)(dict(facts), trace) is None, other
