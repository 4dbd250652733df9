"""The latent-attention cell kind (``train_dsv2``) end to end on the CPU at
toy size: a sound run is ``correct`` and prints its counters; each control
and fault the limits were set against is not; the configuration against the
catalog's row; the counts against counts by hand; the weights; the readers on
hand-made events. The program against the reference leaf by leaf, and the
four chips' shares, are ``tests/test_deepseek_model.py``'s."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import counts_dsv2
import reference_dsv2
import run as runner
import scopes_dsv2
import traffic
import weights_dsv2
import xplane

from apex_tpu.monitor.trace import ROUTING_COUNTERS, ROUTING_COUNTERS_MORE, split_scope
from apex_tpu.transformer import deepseek

SEED = 2**31 + 4242
CELL = "toy-dsv2.train-toy-dsv2"
BENCH = os.path.join(_paths.DATA, "BENCHMARK.dsv2.json")
REAL = "deepseek-v2-lite"
REAL_CELL = "deepseek-v2-lite.train-s16k"
NEW_METRICS = ("dsv2_step_mfu_pct", "mla_attn_roofline", "mla_step_share_pct",
               "moe_experts_dsv2_roofline", "moe_dsv2_step_share_pct", "moe_dsv2_overhead_pct",
               "lm_head_loss_dsv2_roofline", "adam_step_dsv2_roofline")
SHARED_METRICS = ("device_idle_pct.train", "train_scope_coverage_pct", "train_recompute_pct",
                  "train_layout_pct")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    return _load(_paths.DATA, "perfbench", "configs", "toy-dsv2.json")


@pytest.fixture(scope="module")
def real():
    return _load(_paths.PERFBENCH, "configs", REAL + ".json")


@pytest.fixture(scope="module")
def kind():
    return runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train_dsv2.py"), "kind_td")


@pytest.fixture(scope="module")
def sound():
    return runner.run_cell(CELL, SEED, 1.0, False, require_tpu=False, bench_path=BENCH)


def test_toy_cell_is_correct_and_reports_its_end_to_end_metrics(sound):
    assert sound["correct"] is True, sound["compared"]
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["compared"]) == {"grad_norm_gap", "grad_error", "grad_error_worst_leaf",
                                      "update_norm_gap", "loss_gap", "compiles_in_window"}
    assert sound["compared"]["compiles_in_window"]["value"] == 0


def test_a_traced_cpu_run_writes_no_device_metric_and_prints_the_counters(capsys):
    r = runner.run_cell(CELL, SEED + 1, 1.0, True, require_tpu=False, bench_path=BENCH)
    assert r["correct"] is True and r["metrics"] == {}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    counted = next(l for l in lines if l.get("phase") == "routing counted")
    # both tuples of the contract, and the two the acceptance asks every run for
    assert set(ROUTING_COUNTERS) | set(ROUTING_COUNTERS_MORE) <= set(counted)
    assert ROUTING_COUNTERS_MORE == ("rows_gathered", "rows_gathered_over_held", "aux_loss")
    assert {"tiled_rows_most", "passes_run"} <= set(counted)
    # 2 rows x 128 positions, 3 of 8 a position, 3 held, two expert layers
    assert 0 < counted["pairs_held"] and counted["pairs_uniform"] == 2 * 256 * 3 * 3 / 8
    assert counted["masked_positions"] == 0
    assert 1.0 <= counted["rows_gathered_over_held"] < 8
    assert 0.0019 < counted["aux_loss"] < 0.0030          # two layers near alpha each
    window = next(l for l in lines if l.get("phase") == "window closed")
    assert counted["steps_counted"] == window["steps"] == r["attempted"]
    assert counted["pairs_held_least"] <= counted["pairs_held"] <= counted["pairs_held_most"]
    assert counted["passes_run"] >= 1 and counted["tiled_rows"] <= counted["tiled_rows_most"]


# ---------------------------------------------------------------------------
# the reference: the controls and the faults are not correct; the program is

def _batches(toy, seed=SEED, rows=2, seq=128):
    return [tuple(map(jnp.asarray, traffic.train_batch(seed, i, rows, seq, toy["vocab_size"])))
            for i in (1, 2, 3)]


@pytest.fixture(scope="module")
def judged(toy, kind):
    limits = _load(_paths.DATA, "perfbench", "limits", CELL + ".json")["limits"]
    make = lambda: weights_dsv2.make_params(toy, SEED)
    shape = reference_dsv2.model_shape(toy)
    run = lambda **kw: reference_dsv2.train_reference(make, _batches(toy), toy["train"], shape,
                                                      1, **kw)
    ref = run()
    return lambda **kw: {n["name"]: n for n in kind.compare(run(**kw) if kw else ref, ref, limits)}


def test_the_reference_against_itself_is_correct(judged):
    assert all(n["ok"] for n in judged().values())


@pytest.mark.parametrize("how,by", [
    (dict(quant="fp8"), "grad_error"), (dict(cols=slice(0, 64)), "grad_error"),
    (dict(fault="renorm"), "grad_norm_gap"), (dict(fault="no-shared"), "grad_norm_gap"),
    (dict(fault="no-routed"), "update_norm_gap"),
    (dict(fault="no-mscale"), "grad_norm_gap"), (dict(fault="no-kv-norm"), "grad_norm_gap"),
    (dict(fault="no-aux"), "grad_norm_gap"), (dict(fault="no-aux"), "loss_gap"),
], ids=["fp8", "half-batch", "renorm", "no-shared", "no-routed", "no-mscale", "no-kv-norm",
        "no-aux", "no-aux-by-the-loss"])
def test_each_control_and_fault_is_not_correct(judged, sound, how, by):
    verdict = judged(**how)
    assert not verdict[by]["ok"], verdict
    assert verdict[by]["value"] >= 2 * sound["compared"][by]["value"]


def test_plain_frequencies_move_the_gradient_though_the_toy_limits_cannot_tell(judged, sound):
    """128 positions over an original 32 at a rotated width of 8: YaRN's
    frequencies differ in three pairs of four, by little that a toy step
    feels. The full-size cell's limits catch the fault (its limits' file);
    what holds the frequencies themselves is ``tests/test_rope.py``."""
    verdict = judged(fault="no-yarn")
    assert verdict["grad_error_worst_leaf"]["value"] > 0.01 and verdict["grad_error"]["value"] > 0


def test_the_faults_planted_are_the_eight_the_limits_name(real):
    assert set(reference_dsv2.FAULTS) == {"renorm", "no-shared", "no-routed", "no-yarn",
                                          "no-mscale", "no-kv-norm", "no-aux"}
    limits = _load(_paths.PERFBENCH, "limits", REAL_CELL + ".json")
    text = json.dumps(limits)
    for fault in reference_dsv2.FAULTS + ("half_batch", "fp8", "int8"):
        assert fault in text, fault
    assert set(limits["limits"]) == {"grad_norm_gap", "grad_error", "grad_error_worst_leaf",
                                     "update_norm_gap", "loss_gap"}


@pytest.mark.parametrize("name", ["grad_norm_gap", "grad_error", "grad_error_worst_leaf",
                                  "update_norm_gap", "loss_gap"])
def test_each_limit_lies_between_its_two_readings_with_room_on_both_sides(name):
    """``lower`` is the largest sound reading, ``upper`` the least reading of
    a control or fault that stands clear of the sound runs on that number: a
    limit over its ``upper`` lets that control or fault pass."""
    limits = _load(_paths.PERFBENCH, "limits", REAL_CELL + ".json")
    limit, read = limits["limits"][name], limits["readings"][name]
    assert 1.5 * read["lower"] <= limit <= read["upper"] / 1.5, (read["lower"], limit, read["upper"])


def test_a_fault_planted_in_the_program_is_not_correct(monkeypatch):
    """kv_a_layernorm left out of the timed path itself."""
    real_norm = deepseek.rms_norm
    monkeypatch.setattr(deepseek, "rms_norm", lambda x, w, eps, **kw: (
        x if kw.get("use_pallas") is False else real_norm(x, w, eps, **kw)))
    r = runner.run_cell(CELL, SEED, 1.0, False, require_tpu=False, bench_path=BENCH)
    assert r["correct"] is False
    assert not r["compared"]["grad_norm_gap"]["ok"]


def test_the_float32_reference_sets_the_matmul_precision():
    import inspect
    for fn in (reference_dsv2.loss_fn, reference_dsv2.step_gradient, reference_dsv2.step_loss):
        assert 'default_matmul_precision("highest")' in inspect.getsource(fn)


# ---------------------------------------------------------------------------
# the weights

def test_the_same_seed_gives_the_same_weights_and_a_large_one_is_taken(toy):
    a = weights_dsv2.make_params(toy, 2**31 + 7)
    b = weights_dsv2.make_params(toy, 2**31 + 7)
    c = weights_dsv2.make_params(toy, 2**31 + 8)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert float(jnp.abs(a["head"]["lm"].astype(jnp.float32)
                         - c["head"]["lm"].astype(jnp.float32)).max()) > 0
    assert abs(float(jnp.std(a["embed"]["tok"].astype(jnp.float32))) - 1.0) < 0.05
    experts = a["periods"][weights_dsv2.EXPERTS]
    assert experts["router"].shape == (2, 1, 64, 8) and experts["w_gate"].shape == (2, 1, 3, 64, 32)
    assert experts["shared_down"].shape == (2, 1, 64, 64)
    assert a["periods"][weights_dsv2.DENSE]["w_up"].shape == (1, 1, 64, 96)


def test_the_weights_keep_the_load_near_uniform_with_no_pin(toy):
    """Ids uniform over the rows held and embedding rows at 1.0: every expert
    of the router's eight is chosen, none by more than twice its share."""
    cfg = dict(toy, n_routed_experts=8, experts_held=[0, 8])
    p = weights_dsv2.make_params(cfg, SEED)
    tok, _ = traffic.train_batch(SEED, 1, 4, 128, cfg["vocab_size"])
    lp = jax.tree.map(lambda a: a[0, 0].astype(jnp.float32), p["periods"][weights_dsv2.EXPERTS])
    x = jnp.take(p["embed"]["tok"].astype(jnp.float32), jnp.asarray(tok), axis=0)
    m = reference_dsv2._rms_norm(x, lp["norm2"], 1e-6).reshape(-1, 64)
    _, idx = jax.lax.top_k(jax.nn.softmax(m @ lp["router"], axis=-1), 3)
    loads = np.bincount(np.asarray(idx).reshape(-1), minlength=8)
    assert loads.min() > 0 and loads.max() < 2 * loads.mean()


# ---------------------------------------------------------------------------
# the counts, by hand, and the configuration

def test_counts_at_the_published_widths_against_counts_by_hand(real, kind):
    attention = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attention == counts_dsv2.attn_params_per_layer(real) == 13_762_560
    assert attention + 512 == 13_763_072                      # with kv_a_layernorm
    dense = attention + 512 + 2 * 2048 + 3 * 2048 * 10_944
    assert dense == 81_007_104
    outside = attention + 512 + 2 * 2048 + 2048 * 64 + 3 * 2048 * 2816
    assert outside == 31_199_744
    expert = 3 * 2048 * 1408
    assert expert == counts_dsv2.expert_params(real) == 8_650_752
    assert outside + 64 * expert == 584_847_872               # one expert layer, whole
    assert outside + 16 * expert == 169_611_776               # and this chip's share of it
    held = dense + 4 * (outside + 16 * expert) + 2 * 25_600 * 2048 + 2048
    assert held == counts_dsv2.n_params(real) == 864_313_856
    whole = dict(real, num_hidden_layers=27, n_routed_experts=64, vocab_size=102_400)
    assert counts_dsv2.n_params(whole) == 15_706_484_224
    # the program's tree at the cell's configuration holds as many, leaf for leaf

    class Ctx:
        config = real
    shapes = jax.eval_shape(lambda: kind._model(Ctx).init_params(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == held
    made = jax.eval_shape(lambda: weights_dsv2._make(jnp.uint32(0), jnp.uint32(0), config=real))
    assert jax.tree.map(lambda a: a.shape, made) == jax.tree.map(lambda a: a.shape, shapes)


def test_attention_and_step_operations_at_the_cells_size(real):
    assert counts_dsv2.causal_pairs(16_384) == 16_384 * 16_385 // 2 == 134_225_920
    assert counts_dsv2.causal_tile_pairs(16_384) == 528 * 512 * 512
    assert counts_dsv2.attn_ops_per_pair(real) == 640
    attn = 5 * 3.5 * 640 * 16 * 134_225_920
    assert counts_dsv2.attn_flops_per_step(real, 1, 16_384) == pytest.approx(attn)
    assert counts_dsv2.attn_tile_flops_per_step(real, 1, 16_384) == pytest.approx(
        attn * 528 * 512 * 512 / 134_225_920)
    pairs = 4 * 24_576.0
    experts = 6.0 * 8_650_752 * pairs
    assert counts_dsv2.experts_flops_per_step(real, pairs) == pytest.approx(experts)
    head = 3 * 2.0 * 16_384 * 2048 * 25_600
    dense = 6.0 * 16_384 * (5 * 13_762_560 + 3 * 2048 * 10_944
                            + 4 * (2048 * 64 + 3 * 2048 * 2816))
    total = counts_dsv2.train_flops_per_step(real, 1, 16_384, pairs)
    assert total == pytest.approx(dense + attn + experts + head)
    assert total / 1e12 == pytest.approx(54.5, abs=0.05)     # ISSUE 35's count
    assert attn / 1e12 == pytest.approx(24.05, abs=0.01) and experts / 1e12 == pytest.approx(5.10, abs=0.01)
    assert counts_dsv2.experts_bytes_per_step(real, pairs) == pytest.approx(
        3 * 2 * 4 * 16 * 8_650_752 + 4 * 2 * 2048 * pairs)


def test_the_tiles_the_roofline_credits_are_the_tiles_the_plan_visits(real):
    """``counts_dsv2.causal_tile_pairs`` fixes the tile at 512: were
    ``_tile_plan`` to pick smaller blocks for the cell's call, the share
    would credit pairs no kernel computes and read high."""
    from apex_tpu.ops.attention import _tile_plan

    seq = _load(_paths.PERFBENCH, "traffic", "train-s16k.json")["seq"]
    d = real["qk_nope_head_dim"] + real["qk_rope_head_dim"]
    plan = _tile_plan(seq, seq, d, jnp.bfloat16, True)
    assert (plan.schedule, plan.block_q, plan.block_k) == ("streamed", 512, 512)
    assert plan.visited * plan.block_q * plan.block_k == counts_dsv2.causal_tile_pairs(seq)
    # whole diagonal tiles, 3% over the pairs the mask lets through
    assert counts_dsv2.causal_tile_pairs(seq) / counts_dsv2.causal_pairs(seq) == pytest.approx(
        1.031, abs=1e-3)


def check_benchmark(bench):
    """This family's entries in a ``BENCHMARK.json``, found by name: a later
    PR appends its own configuration, cell and metrics, and adds its cell to
    the shared metrics' lists, and these checks still hold."""
    row = _load(_paths.DATA, "catalog_row.deepseek-v2-lite.json")
    entry = next(c for c in bench["configs"] if c["name"] == REAL)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "train-s16k", 1)
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [REAL_CELL] and by_name[name]["moves"] == "train_tokens_per_s"
        assert os.path.exists(os.path.join(_paths.PERFBENCH, "metrics", name + ".py"))
    for name in SHARED_METRICS:
        assert REAL_CELL in by_name[name]["workloads"]
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert REAL_CELL in tokens["workloads"]
    # a quarter of the cells, rounded down, may take four chips, and one always may
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 4)


def test_the_configuration_holds_every_catalog_key_but_the_reduced_ones(real):
    row = _load(_paths.DATA, "catalog_row.deepseek-v2-lite.json")
    bench = _load(_paths.ROOT, "BENCHMARK.json")
    check_benchmark(bench)
    entry = next(c for c in bench["configs"] if c["name"] == REAL)
    assert (real["source"], real["reduced"]) == (entry["source"], entry["reduced"])
    for key, value in row["config"].items():
        if key in real["reduced"]:
            assert real["reduced_from"][key] == value and real[key] < value
        else:
            assert real[key] == value, key
    assert real["experts_held"] == [0, real["n_routed_experts"]]
    assert (real["num_hidden_layers"], real["n_routed_experts"], real["vocab_size"]) == (5, 16, 25_600)
    for key in ("aux_loss_alpha", "balance_loss", "sequence", "sequence_why", "pair_layout",
                "param_dtype", "init", "embedding_std"):
        assert key in real["assumed"], key
    assert real["assumed"]["aux_loss_alpha"] == 0.001 and real["assumed"]["sequence"] == 16_384
    for key in ("reduced_why", "deployment"):
        assert key in real
    mix = _load(_paths.PERFBENCH, "traffic", "train-s16k.json")
    assert (mix["kind"], mix["rows_per_chip"], mix["seq"], mix["in_flight_steps"]) == (
        "train_dsv2", 1, 16_384, 8)
    assert (mix["mesh"], mix["check"]["steps"]) == ({"dp": 1, "tp": 1}, 3)


def test_the_kind_builds_the_model_the_configuration_publishes(real, kind):
    class Ctx:
        config = real
    model = kind._model(Ctx)
    assert model == deepseek.DeepSeekConfig()         # the program's defaults are the file's
    assert (model.routed.norm_topk_prob, model.routed.routed_scaling_factor) == (False, 1.0)
    assert model.rope_scaling.ramp_bounds(64, 10000.0) == (10, 23)
    assert model.routed.tile_rows(16_384) == 512 and model.routed.rows_per_pass(16_384, 16) == 36_864


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
    """One step of 200 ms: flash 20 + 30 + 30; the latent's expansion 10 and
    the query projection 10; the shared expert 12; the routed layer 4 of
    routing, 8 of dispatch, 6 of combine and 10 + 6 of grouped products
    (XLA's own name, no scope); the balance loss 2; the head's three kernels
    5 each; the optimizer 25; the dense layer's FFN 12."""
    table = {
        "flash_fwd.1": _rec("custom-call", FWD + "attn/core/flash_fwd/pallas_call"),
        "flash_bwd_dq.3": _rec("custom-call", BWD + "attn/core/flash_bwd_dq/pallas_call"),
        "flash_bwd_dkv.4": _rec("custom-call", BWD + "attn/core/flash_bwd_dkv/pallas_call"),
        "fusion.2": _rec("fusion", FWD + "attn/kv_up/dot_general"),
        "fusion.15": _rec("fusion", FWD + "attn/q_proj/dot_general"),
        "fusion.16": _rec("fusion", FWD + "shared/gate_up/dot_general"),
        "fusion.5": _rec("fusion", FWD + "moe/route/dot_general"),
        "fusion.6": _rec("fusion", FWD + "moe/dispatch/gather"),
        "fusion.7": _rec("fusion", BWD + "moe/combine/gather"),
        "ragged-dot-none.8": _rec("fusion", "ragged-dot-none"),
        "ragged-dot-none.9": _rec("fusion", "ragged-dot-none"),
        "fusion.17": _rec("fusion", FWD + "aux_loss/reduce_sum"),
        "lm_head_fwd.10": _rec("custom-call", "jit(train_step)/jvp()/lm_head_loss/lm_head_fwd/pallas_call"),
        "lm_head_bwd_dx.11": _rec("custom-call", "jit(train_step)/transpose(jvp())/lm_head_loss/lm_head_bwd_dx/pallas_call"),
        "lm_head_bwd_dw.12": _rec("custom-call", "jit(train_step)/transpose(jvp())/lm_head_loss/lm_head_bwd_dw/pallas_call"),
        "fusion.13": _rec("fusion", "jit(train_step)/opt/mul"),
        "fusion.14": _rec("fusion", "jit(train_step)/jvp()/layer/mlp/gate_up/dot_general"),
    }
    spans = [("flash_fwd.1", "custom-call", 20), ("flash_bwd_dq.3", "custom-call", 30),
             ("flash_bwd_dkv.4", "custom-call", 30), ("fusion.2", "fusion", 10),
             ("fusion.15", "fusion", 10), ("fusion.16", "fusion", 12),
             ("fusion.5", "fusion", 4), ("fusion.6", "fusion", 8), ("fusion.7", "fusion", 6),
             ("ragged-dot-none.8", "fusion", 10), ("ragged-dot-none.9", "fusion", 6),
             ("fusion.17", "fusion", 2),
             ("lm_head_fwd.10", "custom-call", 5), ("lm_head_bwd_dx.11", "custom-call", 5),
             ("lm_head_bwd_dw.12", "custom-call", 5), ("fusion.13", "fusion", 25),
             ("fusion.14", "fusion", 12)]
    assert sum(ms for _, _, ms in spans) == 200
    ops, at = [], 0.0
    for name, opcode, ms in spans:
        ops.append((f"%{name} = bf16[8]{{0}} {opcode}(%a)", at, ms * MS))
        at += ms * MS
    trace = xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(
        ops, [("jit_train_step(7)", 0, 200 * MS)])}, [])
    import peaks
    facts = {"kind": "train", "rows": 1, "seq": 16384, "chips": 1, "model": real,
             "peaks": peaks.peaks_for("TPU v5 lite"), "counters": {"pairs_held": 98304.0},
             "_scope_tables": {"jit_train_step": (table, split_scope)}}
    return facts, trace


def test_the_routed_layers_readers_on_hand_made_events(real):
    facts, trace = _hand_made(real)
    got = scopes_dsv2.moe_seconds(facts, trace)
    assert got["experts"] == pytest.approx(0.016) and got["dispatch"] == pytest.approx(0.008)
    assert got["route"] == pytest.approx(0.004) and got["combine"] == pytest.approx(0.006)
    # the shared expert and the balance loss are no part of the routed layer
    assert _reader("moe_dsv2_step_share_pct")(facts, trace) == pytest.approx(100 * 34 / 200)
    assert _reader("moe_dsv2_overhead_pct")(facts, trace) == pytest.approx(100 * 18 / 34)
    least = max(counts_dsv2.experts_flops_per_step(real, 98304.0) / 197e12,
                counts_dsv2.experts_bytes_per_step(real, 98304.0) / 819e9)
    assert _reader("moe_experts_dsv2_roofline")(facts, trace) == pytest.approx(100 * least / 0.016)


def test_the_kernels_and_the_steps_readers_on_hand_made_events(real):
    facts, trace = _hand_made(real)
    flops = counts_dsv2.attn_tile_flops_per_step(real, 1, 16384)
    assert _reader("mla_attn_roofline")(facts, trace) == pytest.approx(100 * flops / 197e12 / 0.080)
    assert _reader("mla_step_share_pct")(facts, trace) == pytest.approx(100 * 100 / 200)
    assert _reader("lm_head_loss_dsv2_roofline")(facts, trace) == pytest.approx(
        100 * counts_dsv2.lm_head_loss_flops_per_step(real, 1, 16384) / 197e12 / 0.015)
    assert _reader("adam_step_dsv2_roofline")(facts, trace) == pytest.approx(
        100 * 864_313_856 * 22 / 819e9 / 0.025)
    assert _reader("dsv2_step_mfu_pct")(facts, trace) == pytest.approx(
        100 * counts_dsv2.train_flops_per_step(real, 1, 16384, 98304.0) / 0.200 / 197e12)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_there_is_nothing_to_read(real, name):
    """No trace; no chip's peaks; the kind's counters missing (a program
    that hands none out); a step of another family."""
    read = _reader(name)
    facts, trace = _hand_made(real)
    assert read(dict(facts), None) is None
    if name not in ("moe_dsv2_step_share_pct", "moe_dsv2_overhead_pct", "mla_step_share_pct"):
        assert read(dict(facts, peaks=None), trace) is None
    for other in ("olmo-hybrid-7b.json", "sdar-30b-a3b.json", "gpt2-medium.json"):
        model = _load(_paths.PERFBENCH, "configs", other)
        assert read(dict(facts, model=model), trace) is None, other
    assert read(dict(facts, _scope_tables={"jit_train_step": None}), None) is None
    if name in ("dsv2_step_mfu_pct", "moe_experts_dsv2_roofline"):
        assert read({k: v for k, v in facts.items() if k != "counters"}, trace) is None


def test_the_accepted_families_readers_find_nothing_of_theirs_in_this_step(real):
    facts, trace = _hand_made(real)
    for other in ("hybrid_step_mfu_pct", "delta_rule_roofline", "linattn_step_share_pct",
                  "flash_streamed_roofline", "lm_head_loss_hybrid_roofline"):
        assert _reader(other)(dict(facts), trace) is None, other
