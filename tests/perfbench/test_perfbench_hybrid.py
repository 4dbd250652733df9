"""The hybrid decoder's cell kind (``train_hybrid``) end to end on the CPU at
toy size: a sound run is ``correct``; a fault planted in the timed path (the
decay left out; half of the batch) is not; the control on float8's grid is
not; the benchmark's own reference equals the program's; the counts against a
count by hand; the configuration at published widths."""
import gzip
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import counts_hybrid
import peaks
import reference_hybrid
import run as runner
import scopes
import traffic
import weights_hybrid
import xplane

from apex_tpu.monitor.trace import split_scope

SEED = 2**31 + 4242
CELL = "toy-hybrid.train-toy-hybrid"
BENCH = os.path.join(_paths.DATA, "BENCHMARK.hybrid.json")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _run(trace=False, seconds=1.0):
    return runner.run_cell(CELL, SEED, seconds, trace, require_tpu=False, bench_path=BENCH)


@pytest.fixture(scope="module")
def toy():
    return _load(_paths.DATA, "perfbench", "configs", "toy-hybrid.json")


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_toy_hybrid_cell_is_correct_and_reports_its_end_to_end_metrics(sound):
    assert sound["correct"] is True, sound["compared"]
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["compared"]["compiles_in_window"]["value"] == 0
    assert set(sound["compared"]) == {"grad_norm_gap", "grad_error", "grad_error_worst_leaf",
                                      "update_norm_gap", "compiles_in_window"}


def test_a_traced_cpu_run_of_the_hybrid_cell_writes_no_device_metric():
    r = _run(trace=True)
    assert r["correct"] is True
    assert r["metrics"] == {}       # shares of a peak and of device time need the chip


def test_fault_the_decay_left_out_is_not_correct(monkeypatch):
    """``alpha = 1`` everywhere (a linear attention that never forgets) is
    another model: the gradient's error shows it."""
    import apex_tpu.transformer.hybrid as hybrid

    real = hybrid.gated_delta_rule
    monkeypatch.setattr(hybrid, "gated_delta_rule",
                        lambda q, k, v, g, beta, **kw: real(q, k, v, jnp.zeros_like(g), beta, **kw))
    r = _run()
    assert r["correct"] is False
    assert not r["compared"]["grad_error"]["ok"]


def test_fault_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    import bench

    real = bench.train_step_fn

    def broken(cfg, mesh):
        step, opt = real(cfg, mesh)

        def half(params, opt_state, tok, tgt):
            n = tok.shape[0] // 2     # the mean is taken over the first half alone
            return step(params, opt_state, jnp.concatenate([tok[:n], tok[:n]]),
                        jnp.concatenate([tgt[:n], tgt[:n]]))
        return half, opt

    monkeypatch.setattr(bench, "train_step_fn", broken)
    r = _run()
    assert r["correct"] is False
    assert not r["compared"]["grad_error"]["ok"]


def _batches(toy, rows=4, seq=128):
    return [tuple(map(jnp.asarray, traffic.train_batch(SEED, i, rows, seq, toy["vocab_size"])))
            for i in (1, 2, 3)]


def test_control_in_fp8_is_not_correct_and_half_the_batch_is_not(sound, toy):
    """Through the harness's own comparison and against the toy cell's
    limits: the reference on float8's grid, put in the program's place, reads
    well above the sound program's gradient error and fails; so does half of
    the batch."""
    limits = _load(_paths.DATA, "perfbench", "limits", CELL + ".json")["limits"]
    kind = runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train_hybrid.py"), "kind_th")
    make = lambda: weights_hybrid.make_params(toy, SEED)
    shape = reference_hybrid.model_shape(toy)
    ref = reference_hybrid.train_reference(make, _batches(toy), toy["train"], shape, 2)
    verdict = lambda seen: {n["name"]: n for n in kind.compare(seen, ref, limits)}
    assert all(n["ok"] for n in verdict(ref).values())
    sound_err = sound["compared"]["grad_error"]["value"]
    fp8 = verdict(reference_hybrid.train_reference(make, _batches(toy), toy["train"], shape, 2,
                                                   quant="fp8"))
    assert not fp8["grad_error"]["ok"] and fp8["grad_error"]["value"] >= 2 * sound_err
    half = verdict(reference_hybrid.train_reference(make, _batches(toy), toy["train"], shape, 2,
                                                    rows=slice(0, 2)))
    assert not half["grad_error"]["ok"] and half["grad_error"]["value"] > 0.5


def test_the_control_on_the_cores_arithmetic_rounds_the_recurrence_and_nothing_else(toy):
    """``CORE_BF16``: the recurrence on bfloat16's grid (its output moves by
    some of bfloat16's 2^-8 and no more), every matrix product float32 (a
    model of full layers alone gives the uncut reference's logits bit for
    bit), and the kind's ``readings`` send it through the harness's
    comparison with each record's ``loss_gap`` beside it."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (unit(jax.random.normal(ks[i], (1, 128, 2, 16))) for i in (0, 1))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, 128, 2)) - 3.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (1, 128, 2)))
    want = reference_hybrid.delta_rule(q, k, v, g, beta)
    low = reference_hybrid.delta_rule(q, k, v, g, beta, low=True)
    err = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    assert 2.0 ** -11 < err < 2.0 ** -5, err
    full = dict(toy, layer_types=["full_attention"] * len(toy["layer_types"]))
    params = weights_hybrid.make_params(full, SEED)
    tok, _ = _batches(toy, rows=2)[0]
    shape = reference_hybrid.model_shape(full)
    np.testing.assert_array_equal(
        reference_hybrid.logits_fn(params, tok, shape, reference_hybrid.CORE_BF16),
        reference_hybrid.logits_fn(params, tok, shape))


def test_the_kinds_readings_carry_every_control_and_the_fault_with_their_loss_gaps(toy):
    import time

    import harness

    kind = runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train_hybrid.py"), "kind_th")
    mix = _load(_paths.DATA, "perfbench", "traffic", "train-toy-hybrid.json")
    ctx = harness.Context(cell=CELL, config=toy, mix=mix, chips=1, seed=SEED, seconds=1.0,
                          trace=False, t_process_start=time.perf_counter(), require_tpu=False)
    harness.setup_jax(ctx)
    (rec,) = kind.readings(ctx, [SEED], [SEED], [SEED])
    compared = {"grad_norm_gap", "grad_error", "grad_error_worst_leaf", "update_norm_gap"}
    for name in ("program", "control_int8", "control_fp8", "control_core-bf16", "fault_half_batch"):
        assert set(rec[name]) == compared | {"loss_gap"}, name
        assert rec[name]["loss_gap"] >= 0
    assert rec["fault_half_batch"]["grad_error"]["value"] > 0.5 > rec["program"]["grad_error"]["value"]
    assert rec["control_fp8"]["grad_error"]["value"] > 2 * rec["control_core-bf16"]["grad_error"]["value"]


@pytest.mark.parametrize("name", ["_program", "first_steps", "run_reference"])
def test_the_hybrid_kind_replaces_functions_that_kinds_train_still_resolves_by_name(name):
    """``kinds/train_hybrid.py`` runs a private instance of ``kinds/train.py``
    with three of its module globals replaced: that holds only while
    ``train.run`` looks each of them up as a global when it is called."""
    kind = runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train_hybrid.py"), "kind_th")
    plain = runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train.py"), "kind_train_plain")
    assert callable(getattr(plain, name)) and name in plain.run.__code__.co_names
    assert name not in plain.run.__code__.co_varnames          # a global, not a local
    assert getattr(kind.train, name) is getattr(kind, name)
    assert kind.run.__globals__[name] is getattr(kind, name)    # what run() will call


def test_the_benchmarks_reference_equals_the_programs_on_a_toy_input(toy):
    """``perfbench/reference_hybrid.py`` imports nothing of the program;
    ``apex_tpu/transformer/testing/hybrid_reference.py`` is the program's
    copy. Same weights, same tokens: the same loss and the same gradients."""
    from apex_tpu.transformer.testing import hybrid_reference

    # in float32, so that neither side rounds a cotangent to the weights' type
    params = jax.tree.map(lambda a: a.astype(jnp.float32), weights_hybrid.make_params(toy, SEED))
    tok, tgt = _batches(toy, rows=2)[0]
    shape = reference_hybrid.model_shape(toy)
    loss, grads = reference_hybrid.step_gradient(params, tok, tgt, shape, 2)
    want_loss, want = hybrid_reference.loss_and_grad(params, tok, tgt, shape)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))), rtol=1e-4)
    with open(os.path.join(_paths.PERFBENCH, "reference_hybrid.py")) as f:
        code = f.read().split('"""', 2)[2]          # less the docstring
    assert "apex_tpu" not in code and "import bench" not in code


def test_weights_put_the_decay_where_the_configuration_says(toy):
    params = weights_hybrid.make_params(toy, SEED)
    lin = params["periods"]["linear_attention"]
    assert lin["A_log"].dtype == lin["dt_bias"].dtype == jnp.float32
    alpha = jnp.exp(-jnp.exp(lin["A_log"]) * jax.nn.softplus(lin["dt_bias"]))
    assert 0.9 < float(alpha.min()) and float(alpha.max()) < 0.999
    assert lin["wq"].shape == (1, 3, 64, 32) and lin["wq"].dtype == jnp.bfloat16
    assert params["periods"]["full_attention"]["wq"].shape == (1, 1, 64, 64)
    other = weights_hybrid.make_params(toy, SEED + 1)
    assert not np.array_equal(params["head"]["lm"], other["head"]["lm"])


# -- the counts, against a count by hand ------------------------------------------
# the cell's configuration: hidden 3840, FFN 11008, 30 heads of 128; linear: 30
# heads of 96 (key) and 192 (value), convolution 4; 3 linear layers + 1 full;
# 12,544 rows of the vocabulary
FFN = 3 * 3840 * 11008                                                  # 126,812,160
LINEAR_MM = 3840 * (2880 + 2880 + 5760 + 5760 + 30 + 30) + 5760 * 3840  # 88,704,000
LINEAR_CONV = 4 * (2880 + 2880 + 5760)                                  # 46,080
FULL_MM = 4 * 3840 * 3840                                               # 58,982,400
HEAD = 12544 * 3840                                                     # 48,168,960
MATMUL = 3 * (LINEAR_MM + LINEAR_CONV + FFN) + FULL_MM + FFN + HEAD     # 880,650,240
NORMS = 3 * (2 * 3840 + 192 + 60) + (2 * 3840 + 2 * 3840) + 3840
DELTA = 3 * 7 * 96 * 192 * 30                                           # 11,612,160 a layer a token


@pytest.fixture(scope="module")
def model():
    return _load(_paths.PERFBENCH, "configs", "olmo-hybrid-7b.json")


def test_counts_hybrid_match_a_count_by_hand(model):
    assert MATMUL == 880_650_240
    assert counts_hybrid.matmul_params(model) == MATMUL
    assert counts_hybrid.n_params(model) == MATMUL + HEAD + NORMS == 928_862_196
    assert counts_hybrid.delta_rule_flops_per_token(model) == DELTA
    assert counts_hybrid.train_flops_per_token(model, 8192) == 6 * MATMUL + 6 * 3840 * 8192 + 3 * DELTA
    # q, k (96 x 30 bf16), v, o (192 x 30 bf16), g, beta (30 float32): read 5 and
    # write 1 forward; read those 6 and o's cotangent's worth, write 5 cotangents
    qkv, gb, o = 2 * 30 * (96 + 96 + 192), 4 * 2 * 30, 2 * 30 * 192
    assert counts_hybrid.delta_rule_bytes_per_token(model) == 2 * (qkv + gb + o) + qkv + gb == 92_880
    assert counts_hybrid.full_attn_flops_per_step(model, 2, 8192) == 3.5 * 2 * 2 * 8192 * 8192 * 3840
    assert counts_hybrid.lm_head_loss_flops_per_step(model, 2, 8192) == 3 * 2 * 16384 * HEAD


def test_published_model_counts_7_43_billion_parameters(model):
    whole = dict(model, **model["reduced_from"])
    assert whole["num_hidden_layers"] == 32 and whole["vocab_size"] == 100352
    assert counts_hybrid.n_params(whole) == 7_430_870_688


def test_configuration_is_at_published_widths_with_depth_and_vocabulary_reduced(model):
    assert model["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert model["reduced_from"] == {"num_hidden_layers": 32, "vocab_size": 100352}
    assert (model["num_hidden_layers"], model["vocab_size"]) == (4, 12544)
    assert model["vocab_size"] * 8 == 100352 and model["vocab_size"] % 128 == 0
    published = dict(hidden_size=3840, intermediate_size=11008, num_attention_heads=30,
                     num_key_value_heads=30, linear_num_key_heads=30, linear_num_value_heads=30,
                     linear_key_head_dim=96, linear_value_head_dim=192, linear_conv_kernel_dim=4,
                     linear_allow_neg_eigval=True, rms_norm_eps=1e-06, hidden_act="silu",
                     max_position_embeddings=65536, attention_bias=False,
                     tie_word_embeddings=False, model_type="olmo_hybrid",
                     rope_parameters={"rope_theta": None})
    for key, value in published.items():
        assert model[key] == value, key
    assert model["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8
    assert counts_hybrid.layers_held(model) == ("linear_attention",) * 3 + ("full_attention",)
    mix = _load(_paths.PERFBENCH, "traffic", "train-s8k.json")
    assert mix["kind"] == "train_hybrid" and mix["seq"] == model["assumed"]["sequence"] == 8192
    assert mix["rows_per_chip"] * mix["seq"] == 16384


# -- the four new readers, on hand-made events with answers worked by hand ------------

MS = 1e6  # ns
LAYER = "jit(train_step)/jvp()/while/body/layer/"


def _reader(name):
    return runner._module(os.path.join(_paths.PERFBENCH, "metrics", name + ".py"),
                          "metric_" + name)


def _rec(opcode, op_name):
    return {"op_name": op_name, "opcode": opcode, "moves_only": False, "container": False,
            "operands": []}


TABLE = {
    "fusion.1": _rec("fusion", LAYER + "linattn/proj/dot_general"),
    "fusion.2": _rec("fusion", LAYER + "linattn/core/while/body/dot_general"),
    "fusion.3": _rec("fusion", "jit(train_step)/transpose(jvp())/while/body/layer/checkpoint/"
                               "rematted_computation/linattn/core/dot_general"),
    "flash_fwd.4": _rec("custom-call", LAYER + "attn/core/flash_fwd/pallas_call"),
    "flash_bwd_dq.5": _rec("custom-call", "jit(train_step)/transpose(jvp())/while/body/layer/"
                                          "attn/core/flash_bwd_dq/pallas_call"),
    "fusion.6": _rec("fusion", LAYER + "mlp/gate_up/dot_general"),
}


def _hand_trace():
    # one step of 100 ms: linattn 10 + 30 + 20 (the core fwd and replayed), flash 8 + 12, FFN 20
    ops = [("%fusion.1 = bf16[8]{0} fusion(%a)", 0, 10 * MS),
           ("%fusion.2 = f32[8]{0} fusion(%a)", 10 * MS, 30 * MS),
           ("%fusion.3 = f32[8]{0} fusion(%a)", 40 * MS, 20 * MS),
           ("%flash_fwd.4 = (bf16[8]{0:T(8,128)(2,1)}) custom-call(%a)", 60 * MS, 8 * MS),
           ("%flash_bwd_dq.5 = (bf16[8]{0:T(8,128)(2,1)}) custom-call(%a)", 68 * MS, 12 * MS),
           ("%fusion.6 = bf16[8]{0} fusion(%a)", 80 * MS, 20 * MS)]
    return xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(
        ops, [("jit_train_step(7)", 0, 100 * MS)])}, [])


def _facts(model, with_table=True):
    return {"kind": "train", "rows": 2, "seq": 8192, "chips": 1, "model": model,
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "_scope_tables": {"jit_train_step": (TABLE, split_scope) if with_table else None}}


def test_the_four_hybrid_readers_on_hand_made_events(model):
    facts, trace = _facts(model), _hand_trace()
    tokens = 2 * 8192
    # the whole step: tokens x operations a token over the 0.1 s span and the peak
    assert _reader("hybrid_step_mfu_pct").read(facts, trace) == pytest.approx(
        100 * tokens * (6 * MATMUL + 6 * 3840 * 8192 + 3 * DELTA) / 0.1 / 197e12)
    # under layer/linattn/*: 10 + 30 + 20 of 100 ms
    assert _reader("linattn_step_share_pct").read(facts, trace) == pytest.approx(60.0)
    # the core: bytes bound it (92,880 B a token a layer at 819 GB/s against 11.6 MFLOP at
    # 197 TFLOP/s), three layers, over the 50 ms under layer/linattn/core in both phases
    assert 92_880 / 819e9 > DELTA / 197e12
    assert _reader("delta_rule_roofline").read(facts, trace) == pytest.approx(
        100 * tokens * 3 * 92_880 / 819e9 / 0.050)
    # flash: one layer's causal forward + backward over the kernels' 20 ms
    assert _reader("flash_streamed_roofline").read(facts, trace) == pytest.approx(
        100 * 3.5 * 2 * 2 * 8192 ** 2 * 3840 / 197e12 / 0.020)


def _head_trace():
    # the head's three kernels in one step: 10 + 14 + 16 ms
    ops = [("%lm_head_fwd.7 = (f32[8]{0:T(8,128)}) custom-call(%a)", 0, 10 * MS),
           ("%lm_head_bwd_dx.8 = (bf16[8]{0:T(8,128)(2,1)}) custom-call(%a)", 10 * MS, 14 * MS),
           ("%lm_head_bwd_dw.9 = (f32[8]{0:T(8,128)}) custom-call(%a)", 24 * MS, 16 * MS)]
    return xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(
        ops, [("jit_train_step(7)", 0, 100 * MS)])}, [])


def test_the_heads_reader_on_hand_made_events(model):
    """logits, dx and dW over the 12,544 rows held, each 2 x 16,384 x 3,840 x
    12,544 operations, over the three kernels' 40 ms."""
    read = _reader("lm_head_loss_hybrid_roofline").read
    assert read(_facts(model), _head_trace()) == pytest.approx(
        100 * 3 * 2 * 16384 * 3840 * 12544 / 197e12 / 0.040)
    assert read(_facts(model), _hand_trace()) is None       # a step without the kernels


@pytest.mark.parametrize("name", ["hybrid_step_mfu_pct", "delta_rule_roofline",
                                  "linattn_step_share_pct", "flash_streamed_roofline",
                                  "lm_head_loss_hybrid_roofline"])
def test_a_hybrid_reader_returns_nothing_where_there_is_nothing_to_read(model, name):
    """No trace, no chip's peaks, no registry in the program (a tree from
    before the scopes), or another family's model: nothing, and no raise."""
    read = _reader(name).read
    trace = _head_trace if name == "lm_head_loss_hybrid_roofline" else _hand_trace
    assert read(_facts(model), None) is None
    if name != "linattn_step_share_pct":        # a share of the step needs no peak
        assert read(dict(_facts(model), peaks=None), trace()) is None
    if name in ("delta_rule_roofline", "linattn_step_share_pct"):
        assert read(_facts(model, with_table=False), _hand_trace()) is None
    gpt = _load(_paths.PERFBENCH, "configs", "gpt2-medium.json")
    facts = _facts(gpt)
    if name == "linattn_step_share_pct":        # it reads scopes alone: a step without them
        table = {k: v for k, v in TABLE.items() if "linattn" not in v["op_name"]}
        facts["_scope_tables"] = {"jit_train_step": (table, facts["_scope_tables"]["jit_train_step"][1])}
        assert read(facts, _hand_trace()) is None       # 60% of the time unmatched: no table proved
        return
    assert read(facts, trace()) is None


# -- the readers on a pair recorded on the chip (PR 28) -------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory, model):
    """One whole step of ``olmo-hybrid-7b.train-s8k`` on the v5e
    (``tools/record_pair.py --steps 1``) and the scope table the program gave
    for the executable that ran it."""
    path = tmp_path_factory.mktemp("xplane") / "hybrid_scoped.xplane.pb"
    with gzip.open(os.path.join(_paths.DATA, "hybrid_scoped.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(_paths.DATA, "hybrid_scoped.scopes.json.gz"), "rt") as f:
        table = json.load(f)
    facts = dict(_facts(model), _scope_tables={"jit_train_step": (table, split_scope)})
    return facts, xplane.load(str(path))


def test_recorded_hybrid_pair_passes_the_identity_check_and_names_the_new_scopes(recorded):
    facts, trace = recorded
    got = scopes.train_step_scopes(facts, trace)
    assert got["matched"] >= 0.99
    assert 1.5 < got["step_s"] < 1.7            # 1.564 s busy a step (PERF.md §5, PR 28)
    seen = {scope for _, scope, _ in got["rows"]}
    assert {"layer/linattn/proj", "layer/linattn/conv", "layer/linattn/core", "layer/linattn/gate_norm",
            "layer/linattn/out", "layer/attn/qkv", "layer/attn/qk_norm", "layer/attn/core/flash_fwd",
            "layer/attn/out", "layer/mlp/gate_up", "layer/mlp/down", "layer/post_norm",
            "layer/residual", "embed", "final_norm", "opt/adam_tail"} <= seen
    assert any(s.startswith("lm_head_loss") for s in seen)
    # the core is replayed twice (the layer's checkpoint, then a block's own)
    core = lambda phase: sum(v for (p, s, _), v in got["rows"].items()
                             if p == phase and s.startswith("layer/linattn/core"))
    assert core("recompute") > 1.5 * core("fwd") > 0


@pytest.mark.parametrize("name,lo,hi", [
    ("hybrid_step_mfu_pct", 27.0, 31.0),            # 29.28 in the run it was cut from
    ("delta_rule_roofline", 1.0, 1.6),              # 1.33
    ("linattn_step_share_pct", 48.0, 56.0),         # 51.1
    ("flash_streamed_roofline", 24.0, 28.0),        # 26.2
    ("lm_head_loss_hybrid_roofline", 52.0, 60.0),   # 56.1: 24.0 ms of operations over 42.8 ms
    ("train_scope_coverage_pct", 90.0, 100.0),      # 95.8
    ("train_recompute_pct", 23.0, 28.0),            # 25.6
    ("train_layout_pct", 15.0, 21.0),               # 16.7
])
def test_readers_on_the_recorded_hybrid_pair(recorded, name, lo, hi, capsys):
    facts, trace = recorded
    value = _reader(name).read(facts, trace)
    assert lo < value < hi, (name, value)
    assert value <= 100.0
