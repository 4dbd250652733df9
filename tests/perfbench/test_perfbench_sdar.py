"""The block-diffusion decoder's cell kind (``train_sdar``) end to end on the
CPU at toy size: a sound run is ``correct``; each control and fault the
limits were set against is not; the program's loss and every leaf's gradient
equal the benchmark's own reference; the four chips' shares of a layer add up
to the uncut reference; the batch, the weights' expert placement, the counts
against counts by hand, the configuration against the catalog's row, and the
readers on hand-made events."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import counts_sdar
import reference_sdar
import run as runner
import scopes_sdar
import weights_sdar
import xplane

from apex_tpu.monitor.trace import split_scope
from apex_tpu.transformer import sdar

SEED = 2**31 + 4242
CELL = "toy-sdar.train-toy-sdar"
BENCH = os.path.join(_paths.DATA, "BENCHMARK.sdar.json")
REAL = "sdar-30b-a3b"
REAL_CELL = "sdar-30b-a3b.train-blockdiff-s8k"
NEW_METRICS = ("sdar_step_mfu_pct", "blockdiff_attn_roofline", "moe_experts_roofline",
               "moe_step_share_pct", "moe_overhead_pct", "lm_head_loss_sdar_roofline",
               "adam_step_sdar_roofline")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    return _load(_paths.DATA, "perfbench", "configs", "toy-sdar.json")


@pytest.fixture(scope="module")
def real():
    return _load(_paths.PERFBENCH, "configs", REAL + ".json")


@pytest.fixture(scope="module")
def kind():
    return runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train_sdar.py"), "kind_ts")


@pytest.fixture(scope="module")
def sound():
    return runner.run_cell(CELL, SEED, 1.0, False, require_tpu=False, bench_path=BENCH)


def test_toy_cell_is_correct_and_reports_its_end_to_end_metrics(sound):
    assert sound["correct"] is True, sound["compared"]
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["compared"]) == {"grad_norm_gap", "grad_error", "grad_error_worst_leaf",
                                      "update_norm_gap", "compiles_in_window"}
    assert sound["compared"]["compiles_in_window"]["value"] == 0


def test_a_traced_cpu_run_writes_no_device_metric_and_prints_the_counters(capsys):
    r = runner.run_cell(CELL, SEED + 1, 1.0, True, require_tpu=False, bench_path=BENCH)
    assert r["correct"] is True and r["metrics"] == {}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    counted = next(l for l in lines if l.get("phase") == "routing counted")
    from apex_tpu.monitor.trace import ROUTING_COUNTERS
    assert set(ROUTING_COUNTERS) <= set(counted)
    assert 0 < counted["pairs_held"] and counted["pairs_uniform"] == 2 * 4 * 256 * 2 * 3 / 8
    # the window's own steps, every one of them
    window = next(l for l in lines if l.get("phase") == "window closed")
    assert counted["steps_counted"] == window["steps"] == r["attempted"]
    assert counted["pairs_held_least"] <= counted["pairs_held"] <= counted["pairs_held_most"]
    assert counted["passes_run"] >= 1 and counted["tiled_rows"] <= counted["tiled_rows_most"]


# ---------------------------------------------------------------------------
# the reference: the controls and the faults are not correct; the program is

def _batches(toy, seed=SEED, rows=4, seq=128):
    a = toy["assumed"]
    return [tuple(map(jnp.asarray, reference_sdar.train_batch(
        seed, i, rows, seq, a["mask_id"], a["block_length"]))) for i in (1, 2, 3)]


@pytest.fixture(scope="module")
def judged(toy, kind):
    limits = _load(_paths.DATA, "perfbench", "limits", CELL + ".json")["limits"]
    make = lambda: weights_sdar.make_params(toy, SEED)
    shape = reference_sdar.model_shape(toy)
    run = lambda **kw: reference_sdar.train_reference(make, _batches(toy), toy["train"], shape,
                                                      2, **kw)
    ref = run()
    return lambda **kw: {n["name"]: n for n in kind.compare(run(**kw) if kw else ref, ref, limits)}


def test_the_reference_against_itself_is_correct(judged):
    assert all(n["ok"] for n in judged().values())


@pytest.mark.parametrize("how,by", [
    (dict(quant="fp8"), "grad_error"), (dict(rows=slice(0, 2)), "grad_error"),
    (dict(fault="no-routed"), "grad_norm_gap"), (dict(fault="no-renorm"), "update_norm_gap"),
    (dict(fault="causal-mask"), "grad_norm_gap"), (dict(fault="no-rope"), "grad_error_worst_leaf"),
], ids=["fp8", "half-batch", "no-routed", "no-renorm", "causal-mask", "no-rope"])
def test_each_control_and_fault_is_not_correct(judged, sound, how, by):
    verdict = judged(**how)
    assert not verdict[by]["ok"], verdict
    assert verdict[by]["value"] >= 2 * sound["compared"][by]["value"]


def test_the_router_in_bfloat16_flips_choices_and_moves_little_else(judged, sound):
    """The control of the router's own precision (``quant`` leaves the
    router's product alone): logits rounded to bfloat16 flip some last
    choices, which the leaves' norms read and the other numbers hardly do.
    Whether a limit fails it at full size is PERF.md §7's to say; what holds
    the router to float32 is ``tests/test_routed_experts.py``."""
    verdict = judged(fault="router-bf16")
    assert verdict["grad_norm_gap"]["value"] >= 5 * sound["compared"]["grad_norm_gap"]["value"]
    assert verdict["grad_error"]["ok"] and verdict["update_norm_gap"]["ok"]


def test_a_fault_planted_in_the_program_is_not_correct(monkeypatch):
    """The rotation left out of the timed path itself."""
    monkeypatch.setattr(sdar, "apply_rotary", lambda x, positions, theta: x)
    r = runner.run_cell(CELL, SEED, 1.0, False, require_tpu=False, bench_path=BENCH)
    assert r["correct"] is False
    assert not r["compared"]["grad_error_worst_leaf"]["ok"]


def _program_and_reference(toy, remat="sublayer"):
    """(loss, gradients) of the program and of the reference at toy size, in
    float32 on both sides, on the benchmark's weights and first batch."""
    model = sdar.SDARConfig(
        vocab_held=toy["vocab_size"], hidden=toy["hidden_size"],
        num_layers=toy["num_hidden_layers"], num_heads=toy["num_attention_heads"],
        num_kv_heads=toy["num_key_value_heads"], head_dim=toy["head_dim"],
        rope_theta=float(toy["rope_theta"]), num_experts=toy["reduced_from"]["num_experts"],
        experts_held=tuple(toy["experts_held"]), top_k=toy["num_experts_per_tok"],
        expert_hidden=toy["moe_intermediate_size"], block=toy["assumed"]["block_length"],
        mask_id=toy["assumed"]["mask_id"], dtype=jnp.float32, remat=remat)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), weights_sdar.make_params(toy, SEED))
    tok, noise = _batches(toy)[0]
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(model.loss)(params, tok, noise)
    want = reference_sdar.step_gradient(params, tok, noise, reference_sdar.model_shape(toy), 2)
    return got, want


@pytest.fixture(scope="module")
def both(toy):
    return _program_and_reference(toy)


def test_the_programs_loss_equals_the_references(both):
    (loss, _), (ref_loss, _) = both
    assert abs(float(loss) - float(ref_loss)) < 2e-5 * abs(float(ref_loss))


LEAVES = ["embed.tok", "head.norm", "head.lm"] + [
    "periods." + n for n in ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "norm2",
                             "router", "w_gate", "w_up", "w_down")]


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_equals_the_references(both, leaf):
    (_, grads), (_, ref) = both
    at = lambda tree: (tree["periods"][weights_sdar.LAYER][leaf.split(".")[1]]
                       if leaf.startswith("periods.") else
                       tree[leaf.split(".")[0]][leaf.split(".")[1]])
    got, want = np.asarray(at(grads)), np.asarray(at(ref))
    assert got.shape == want.shape
    scale = float(np.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=2e-4 * scale)


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer(toy):
    """One layer, the router's eight experts all drawn: four shares of two
    experts each through the program (attention and the router counted once)
    equal the reference's layer with all eight."""
    whole = dict(toy, num_experts=8, experts_held=[0, 8], num_hidden_layers=1)
    p = jax.tree.map(lambda a: a[0, 0].astype(jnp.float32),
                     weights_sdar.make_params(whole, SEED)["periods"][weights_sdar.LAYER])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, toy["hidden_size"]), jnp.float32)
    model = lambda first: sdar.SDARConfig(
        vocab_held=256, hidden=64, num_layers=1, num_heads=4, num_kv_heads=2, head_dim=32,
        num_experts=8, experts_held=(first, 2), top_k=2, expert_hidden=32, mask_id=255,
        dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = sdar._attention_sublayer(p, x, model(0))        # what every chip computes alike
        routed = jnp.zeros_like(x)
        for first in range(0, 8, 2):
            share = {**p, **{n: p[n][first:first + 2] for n in ("w_gate", "w_up", "w_down")}}
            routed = routed + (sdar._experts_sublayer(share, h, model(first))[0] - h)
    uncut = reference_sdar.layer_fn(p, x, reference_sdar.model_shape(whole))
    np.testing.assert_allclose(h + routed, uncut, atol=5e-5)


# ---------------------------------------------------------------------------
# the batch and the weights

def test_the_batch_is_the_seeds_and_keeps_to_the_configuration(real):
    a = real["assumed"]
    tok, noise = reference_sdar.train_batch(SEED, 3, 2, 8192, a["mask_id"], a["block_length"])
    again = reference_sdar.train_batch(SEED, 3, 2, 8192, a["mask_id"], a["block_length"])
    np.testing.assert_array_equal(tok, again[0])
    np.testing.assert_array_equal(noise, again[1])
    other = reference_sdar.train_batch(SEED, 4, 2, 8192, a["mask_id"], a["block_length"])
    assert (tok != other[0]).mean() > 0.99
    assert tok.dtype == noise.dtype == np.int32 and tok.shape == noise.shape == (2, 8192)
    assert 0 <= tok.min() and tok.max() < a["mask_id"] == real["vocab_size"] - 1
    t = (noise >> 1) / 2.0 ** 20
    assert 0.001 <= t.min() and t.max() <= 1.0
    np.testing.assert_array_equal(t.reshape(2, -1, 4), t.reshape(2, -1, 4)[:, :, :1].repeat(4, 2))
    assert abs((noise & 1).mean() - 0.5) < 0.02             # E[t] is a half
    # what the program reads out of it is what the reference reads
    model = sdar.SDARConfig()
    got = sdar.noised_batch(jnp.asarray(tok), jnp.asarray(noise), model)
    want = reference_sdar.noised(jnp.asarray(tok), jnp.asarray(noise),
                                 reference_sdar.model_shape(real))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], rtol=1e-6)


def test_the_weights_deal_the_mask_tokens_experts_over_the_chips(toy):
    """Every layer's experts ranked by affinity to the mask token lie there
    and back over the chips' ranges: ranks 0-3 on chips 0 1 2 3, ranks 4-7
    on chips 3 2 1 0, so each chip holds two of the top eight; the chosen
    lead the rest by eight logits."""
    cfg = dict(toy, num_experts=4, reduced_from=dict(toy["reduced_from"], num_experts=16),
               experts_held=[0, 4])
    p = weights_sdar.make_params(cfg, SEED)
    layer = p["periods"][weights_sdar.LAYER]
    u = p["embed"]["tok"][cfg["assumed"]["mask_id"]].astype(jnp.float32)
    u = u / jnp.sqrt(jnp.mean(u * u) + 1e-6)
    for l in range(cfg["num_hidden_layers"]):
        logits = (u * layer["norm2"][l, 0].astype(jnp.float32)) @ layer["router"][l, 0].astype(jnp.float32)
        by_rank = np.argsort(-np.asarray(logits))
        np.testing.assert_array_equal(by_rank[:8] // 4, [0, 1, 2, 3, 3, 2, 1, 0])
        np.testing.assert_array_equal(by_rank[:8] % 4, [0, 0, 0, 0, 1, 1, 1, 1])
        # and its choice is pinned: the last chosen leads the next by MASK_GAP
        ranked = np.sort(np.asarray(logits))[::-1]
        k = cfg["num_experts_per_tok"]
        assert ranked[k - 1] - ranked[k] >= weights_sdar.MASK_GAP - 0.25
    assert layer["router"].shape == (2, 1, 64, 16) and layer["w_gate"].shape == (2, 1, 4, 64, 32)


def test_the_same_seed_gives_the_same_weights_and_a_large_one_is_taken(toy):
    a = weights_sdar.make_params(toy, 2**31 + 7)
    b = weights_sdar.make_params(toy, 2**31 + 7)
    c = weights_sdar.make_params(toy, 2**31 + 8)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert float(jnp.abs(a["head"]["lm"].astype(jnp.float32)
                         - c["head"]["lm"].astype(jnp.float32)).max()) > 0
    assert abs(float(jnp.std(a["embed"]["tok"].astype(jnp.float32))) - 1.0) < 0.05


# ---------------------------------------------------------------------------
# the counts, by hand, and the configuration

def test_counts_at_the_published_widths_against_counts_by_hand(real):
    attention = 2048 * 4096 * 2 + 2048 * 512 * 2
    outside = attention + 2 * 2048 + 2 * 128 + 2048 * 128       # norms, QK-norm, router
    assert outside == 19_140_864
    expert = 3 * 2048 * 768
    assert expert == counts_sdar.expert_params(real) == 4_718_592
    assert 128 * expert + outside == 623_120_640                 # one layer, whole
    held = 4 * (outside + 32 * expert) + 2 * 37_984 * 2048 + 2048
    assert held == counts_sdar.n_params(real) == 836_127_744
    whole = dict(real, num_hidden_layers=48, num_experts=128, vocab_size=151_936)
    assert counts_sdar.n_params(whole) == 30_532_122_624
    # the program's tree at the cell's configuration holds as many
    kind = runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train_sdar.py"), "kind_ts2")

    class Ctx:
        config = real
    shapes = jax.eval_shape(lambda: kind._model(Ctx).init_params(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == held
    made = jax.eval_shape(lambda: weights_sdar._make(jnp.uint32(0), jnp.uint32(0), config=real))
    assert jax.tree.map(lambda a: a.shape, made) == jax.tree.map(lambda a: a.shape, shapes)


@pytest.mark.parametrize("seq,block", [(16, 4), (64, 4), (64, 16), (96, 8)])
def test_visible_pairs_against_the_dense_mask(seq, block):
    i = np.arange(2 * seq)[:, None]
    j = np.arange(2 * seq)[None, :]
    dense = np.asarray(reference_sdar.visible(i, j, seq, block))
    assert counts_sdar.block_mask_pairs(seq, block) == int(dense.sum())


def test_attention_and_step_operations_at_the_cells_size(real):
    assert counts_sdar.block_mask_pairs(8192, 4) == 67_141_632
    assert counts_sdar.block_mask_tile_pairs(8192, 4) == 288 * 512 * 512
    attn = 4 * 3.5 * 2 * 2.0 * 128 * 32 * 67_141_632 * 2
    assert counts_sdar.attn_flops_per_step(real, 2, 8192) == pytest.approx(attn)
    assert counts_sdar.attn_tile_flops_per_step(real, 2, 8192) == pytest.approx(
        attn * 288 * 512 * 512 / 67_141_632)
    pairs = 4 * 65_536.0
    experts = 6.0 * 4_718_592 * pairs
    assert counts_sdar.experts_flops_per_step(real, pairs) == pytest.approx(experts)
    head = 3 * 2.0 * 2 * 8192 * 2048 * 37_984
    dense = 6.0 * (2048 * 4096 * 2 + 2048 * 512 * 2 + 2048 * 128) * 4 * 32_768
    assert counts_sdar.train_flops_per_step(real, 2, 8192, pairs) == pytest.approx(
        dense + attn + experts + head)
    # the weights thrice (read in the forward and the backward, the gradient written)
    # and a pair's row in and out both ways
    assert counts_sdar.experts_bytes_per_step(real, pairs) == pytest.approx(
        3 * 2 * 4 * 32 * 4_718_592 + 4 * 2 * 2048 * pairs)


def check_benchmark(bench):
    """This family's entries in a ``BENCHMARK.json``, found by name: a later
    PR appends its own configuration, cell and metrics, and these checks
    still hold."""
    row = _load(_paths.DATA, "catalog_row.sdar-30b-a3b-chat.json")
    entry = next(c for c in bench["configs"] if c["name"] == REAL)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "train-blockdiff-s8k", 1)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [REAL_CELL]
        assert os.path.exists(os.path.join(_paths.PERFBENCH, "metrics", name + ".py"))
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert REAL_CELL in tokens["workloads"]


def test_the_configuration_holds_every_catalog_key_but_the_reduced_ones(real):
    row = _load(_paths.DATA, "catalog_row.sdar-30b-a3b-chat.json")
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
    assert real["assumed"]["mask_id"] == real["vocab_size"] - 1
    mix = _load(_paths.PERFBENCH, "traffic", "train-blockdiff-s8k.json")
    assert (mix["kind"], mix["rows_per_chip"], mix["seq"]) == ("train_sdar", 2, 8192)


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
    """One step of 200 ms: flash 20 + 20 replayed + 30 + 30; the routed layer
    4 of routing, 16 of dispatch, 14 of combine and 10 + 6 of grouped products
    (XLA's own name, no scope); the head's three kernels 5 each; the
    optimizer 25; a projection 35."""
    table = {
        "flash_fwd.1": _rec("custom-call", FWD + "attn/core/flash_fwd/pallas_call"),
        "flash_fwd.2": _rec("custom-call", BWD + "rematted_computation/attn/core/flash_fwd/pallas_call"),
        "flash_bwd_dq.3": _rec("custom-call", BWD + "attn/core/flash_bwd_dq/pallas_call"),
        "flash_bwd_dkv.4": _rec("custom-call", BWD + "attn/core/flash_bwd_dkv/pallas_call"),
        "fusion.5": _rec("fusion", FWD + "moe/route/dot_general"),
        "fusion.6": _rec("fusion", FWD + "moe/dispatch/gather"),
        "fusion.7": _rec("fusion", BWD + "moe/combine/gather"),
        "ragged-dot-none.8": _rec("fusion", "ragged-dot-none"),
        "ragged-dot-none.9": _rec("fusion", "ragged-dot-none"),
        "lm_head_fwd.10": _rec("custom-call", "jit(train_step)/jvp()/lm_head_loss/lm_head_fwd/pallas_call"),
        "lm_head_bwd_dx.11": _rec("custom-call", "jit(train_step)/transpose(jvp())/lm_head_loss/lm_head_bwd_dx/pallas_call"),
        "lm_head_bwd_dw.12": _rec("custom-call", "jit(train_step)/transpose(jvp())/lm_head_loss/lm_head_bwd_dw/pallas_call"),
        "fusion.13": _rec("fusion", "jit(train_step)/opt/mul"),
        "fusion.14": _rec("fusion", FWD + "attn/qkv/dot_general"),
    }
    spans = [("flash_fwd.1", "custom-call", 20), ("flash_fwd.2", "custom-call", 20),
             ("flash_bwd_dq.3", "custom-call", 30), ("flash_bwd_dkv.4", "custom-call", 30),
             ("fusion.5", "fusion", 4), ("fusion.6", "fusion", 16), ("fusion.7", "fusion", 14),
             ("ragged-dot-none.8", "fusion", 10), ("ragged-dot-none.9", "fusion", 6),
             ("lm_head_fwd.10", "custom-call", 5), ("lm_head_bwd_dx.11", "custom-call", 5),
             ("lm_head_bwd_dw.12", "custom-call", 5), ("fusion.13", "fusion", 25),
             ("fusion.14", "fusion", 10)]
    ops, at = [], 0.0
    for name, opcode, ms in spans:
        ops.append((f"%{name} = bf16[8]{{0}} {opcode}(%a)", at, ms * MS))
        at += ms * MS
    trace = xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(
        ops, [("jit_train_step(7)", 0, 200 * MS)])}, [])
    import peaks
    facts = {"kind": "train", "rows": 2, "seq": 8192, "chips": 1, "model": real,
             "peaks": peaks.peaks_for("TPU v5 lite"), "counters": {"pairs_held": 262144.0},
             "_scope_tables": {"jit_train_step": (table, split_scope)}}
    return facts, trace


def test_the_routed_layers_readers_on_hand_made_events(real):
    facts, trace = _hand_made(real)
    got = scopes_sdar.moe_seconds(facts, trace)
    assert got["experts"] == pytest.approx(0.016) and got["dispatch"] == pytest.approx(0.016)
    assert got["route"] == pytest.approx(0.004) and got["combine"] == pytest.approx(0.014)
    assert _reader("moe_step_share_pct")(facts, trace) == pytest.approx(100 * 50 / 200)
    assert _reader("moe_overhead_pct")(facts, trace) == pytest.approx(100 * 34 / 50)
    least = max(counts_sdar.experts_flops_per_step(real, 262144.0) / 197e12,
                counts_sdar.experts_bytes_per_step(real, 262144.0) / 819e9)
    assert _reader("moe_experts_roofline")(facts, trace) == pytest.approx(100 * least / 0.016)


def test_the_kernels_and_the_steps_readers_on_hand_made_events(real):
    facts, trace = _hand_made(real)
    flops = counts_sdar.attn_tile_flops_per_step(real, 2, 8192)
    assert _reader("blockdiff_attn_roofline")(facts, trace) == pytest.approx(
        100 * flops / 197e12 / 0.100)
    assert _reader("lm_head_loss_sdar_roofline")(facts, trace) == pytest.approx(
        100 * counts_sdar.lm_head_loss_flops_per_step(real, 2, 8192) / 197e12 / 0.015)
    assert _reader("adam_step_sdar_roofline")(facts, trace) == pytest.approx(
        100 * 836_127_744 * 22 / 819e9 / 0.025)
    assert _reader("sdar_step_mfu_pct")(facts, trace) == pytest.approx(
        100 * counts_sdar.train_flops_per_step(real, 2, 8192, 262144.0) / 0.200 / 197e12)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_there_is_nothing_to_read(real, name):
    """No trace; no chip's peaks; the kind's counters missing (the parent's
    program prints none); a step of another family, which the routed
    layer's two shares cannot tell: they read the scopes alone."""
    read = _reader(name)
    facts, trace = _hand_made(real)
    assert read(dict(facts), None) is None
    if name not in ("moe_step_share_pct", "moe_overhead_pct"):
        assert read(dict(facts, peaks=None), trace) is None
        # the latent-attention configuration holds experts too: not this family
        for other in ("deepseek-v2-lite.json", "olmo-hybrid-7b.json", "gpt2-medium.json"):
            model = _load(_paths.PERFBENCH, "configs", other)
            assert read(dict(facts, model=model), trace) is None, other
    other = _load(_paths.PERFBENCH, "configs", "olmo-hybrid-7b.json")
    assert read(dict(facts, model=other, _scope_tables={"jit_train_step": None}), trace) is None
    if name in ("sdar_step_mfu_pct", "moe_experts_roofline"):
        assert read({k: v for k, v in facts.items() if k != "counters"}, trace) is None


# ---------------------------------------------------------------------------
# the readers, on the recorded pair

def _recorded(tmp_path, real):
    """``sdar_scoped.*``: two whole steps of the cell on the chip (PR 33,
    ``tools/record_pair.py`` from the archived final tree, seed 2147488204)
    and the scope table of the executable that ran them."""
    import gzip
    import shutil

    import peaks
    path = tmp_path / "sdar_scoped.xplane.pb"
    with gzip.open(os.path.join(_paths.DATA, "sdar_scoped.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(_paths.DATA, "sdar_scoped.scopes.json.gz"), "rt") as f:
        table = json.load(f)
    facts = {"kind": "train", "model": real, "rows": 2, "seq": 8192, "chips": 1,
             "peaks": peaks.peaks_for("TPU v5 lite"), "counters": {"pairs_held": RECORDED_PAIRS},
             "_scope_tables": {"jit_train_step": (table, split_scope)}}
    return facts, xplane.load(str(path))


RECORDED_PAIRS = 263_633.43     # the run's `routing counted` line: its window's mean
# what the run itself printed for its four or five whole steps (my chip run, PR 33)
RECORDED = {"sdar_step_mfu_pct": 22.389, "blockdiff_attn_roofline": 29.395,
            "moe_experts_roofline": 28.270, "moe_step_share_pct": 30.594,
            "moe_overhead_pct": 68.306, "lm_head_loss_sdar_roofline": 55.947,
            "adam_step_sdar_roofline": 81.586}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_each_new_reader_on_the_recorded_pair(tmp_path, real, name):
    facts, trace = _recorded(tmp_path, real)
    got = _reader(name)(facts, trace)
    assert got == pytest.approx(RECORDED[name], rel=0.01), (name, got)
    assert 0 < got < 100


def test_the_recorded_steps_scopes_are_the_contracts(tmp_path, real):
    import scopes
    facts, trace = _recorded(tmp_path, real)
    got = scopes.train_step_scopes(facts, trace)
    assert got["matched"] == pytest.approx(1.0)
    seen = {scope for (_, scope, _) in got["rows"]}
    for scope in ("noise", "embed", "layer/pre_norm", "layer/attn/qkv", "layer/attn/qk_norm",
                  "layer/attn/rope", "layer/attn/core/flash_fwd", "layer/attn/core/flash_bwd_dq",
                  "layer/attn/core/flash_bwd_dkv", "layer/attn/out", "layer/moe/route",
                  "layer/moe/dispatch", "layer/moe/experts", "layer/moe/combine",
                  "layer/residual", "final_norm", "lm_head_loss/lm_head_fwd", "opt"):
        assert scope in seen, scope
    # the grouped products carry no scope: they are read by name
    assert scopes_sdar.grouped_seconds(trace) > 0.05
    parts = scopes_sdar.moe_seconds(facts, trace)
    assert parts["experts"] > scopes_sdar.grouped_seconds(trace)
    # the older readers find nothing of their families in this step
    for other in ("hybrid_step_mfu_pct", "delta_rule_roofline", "linattn_step_share_pct",
                  "flash_streamed_roofline", "lm_head_loss_hybrid_roofline"):
        assert _reader(other)({k: v for k, v in facts.items()}, trace) is None, other
