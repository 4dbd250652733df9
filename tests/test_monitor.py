"""apex_tpu.monitor tests — metric pytrees under jit+donation, AMP/DDP/ZeRO
wiring, JSONL schema round-trip + append-after-crash, span visibility in
HLO/trace layer paths, and the compile-accounting gate (monitoring must add
ZERO recompilations; DDP-reported bytes must agree with comm.accounting).

Mesh programs (shard_map + the GPT fixture) run on the conftest's virtual
mesh; the profiler-trace tests are marked slow.

Treedef note exercised throughout: a Metrics carried THROUGH a step must be
pre-seeded with every name the step records (names are treedef aux data, so
a growing name set would retrace). ``jax.eval_shape`` on the step discovers
the full name set without compiling anything.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.monitor import (
    JsonlSink,
    Metrics,
    SCHEMA_VERSION,
    global_norm,
    gpt_analytic_flops_per_token,
    json_record,
    phase_breakdown,
    pipeline_bubble_fraction,
    read_jsonl,
    span,
    span_function,
    train_metrics,
)


# compilation count of a jitted callable (None if this jax can't say) —
# ONE implementation, shared with engine.compile_counts and the
# recompile_guard sentinel (tests/test_analyze.py pins its semantics)
from apex_tpu.analyze.recompile import jit_cache_size as _cache_size  # noqa: E402,E501


# ---------------------------------------------------------------------------
# Metrics pytree


def test_metrics_record_accumulate_merge():
    m = Metrics({"loss": 2.0})
    m = m.record(grad_norm=3.0)
    m = m.accumulate(overflow_total=1.0).accumulate(overflow_total=1.0)
    m = m.merge(Metrics({"loss": 1.0}))
    d = m.as_dict()
    assert d == {"grad_norm": 3.0, "loss": 1.0, "overflow_total": 2.0}
    # names sorted -> treedef stable regardless of insertion order
    assert m.names() == ("grad_norm", "loss", "overflow_total")
    a = Metrics({"x": 1.0, "y": 2.0})
    b = Metrics({"y": 2.0}).record(x=1.0)
    assert (jax.tree_util.tree_structure(a)
            == jax.tree_util.tree_structure(b))


def test_metrics_rejects_nonscalar():
    with pytest.raises(ValueError):
        Metrics({"v": jnp.ones((3,))})


def test_metrics_is_a_pytree():
    m = Metrics({"a": 1.0, "b": 2.0})
    doubled = jax.tree_util.tree_map(lambda x: 2 * x, m)
    assert doubled.as_dict() == {"a": 2.0, "b": 4.0}


def test_global_norm_matches_reference():
    tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": -jnp.ones((4,), jnp.bfloat16)}
    want = np.sqrt(sum((np.asarray(x, np.float32) ** 2).sum()
                       for x in jax.tree_util.tree_leaves(tree)))
    np.testing.assert_allclose(float(global_norm(tree)), want, rtol=1e-6)
    assert float(global_norm({})) == 0.0


def test_metric_pytree_under_jit_and_donation():
    """The tentpole contract: metrics threaded like the scaler state —
    grad norm matches a reference computation, carried counters survive
    donation, the instrumented step computes the same params as the
    uninstrumented one, and 5 steps reuse ONE compilation."""

    def loss_fn(p, x):
        return jnp.mean((x @ p["w"] + p["b"]) ** 2)

    def update(p, x):
        loss, grads = jax.value_and_grad(loss_fn)(p, x)
        new_p = jax.tree_util.tree_map(lambda a, g: a - 0.1 * g, p, grads)
        return new_p, loss, grads

    @jax.jit
    def plain_step(p, x):
        new_p, _, _ = update(p, x)
        return new_p

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, m, x):
        new_p, loss, grads = update(p, x)
        m = train_metrics(m, loss=loss, grads=grads, params=p)
        return new_p, m.accumulate(steps=1.0)

    def init():
        return {"w": jnp.ones((4, 2)), "b": jnp.zeros((2,))}

    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    ref_norm = float(global_norm(jax.grad(loss_fn)(init(), x)))

    # pre-seed every recorded name so the carry treedef never changes
    p, m = init(), Metrics({"steps": 0.0, "loss": 0.0, "grad_norm": 0.0,
                            "param_norm": 0.0})
    p_plain = init()
    for i in range(5):
        p, m = step(p, m, x)
        p_plain = plain_step(p_plain, x)
        if i == 0:
            np.testing.assert_allclose(m.as_dict()["grad_norm"], ref_norm,
                                       rtol=1e-5)
    d = m.as_dict()
    assert d["steps"] == 5.0
    assert d["loss"] >= 0.0 and d["param_norm"] > 0.0
    # monitoring does not change the training math
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(p_plain["w"]),
                               rtol=1e-6)
    # ... and adds ZERO extra compilations: one cache entry after 5 steps
    n = _cache_size(step)
    if n is not None:
        assert n == 1, f"metrics threading retraced: {n} compilations"


# ---------------------------------------------------------------------------
# AMP scaler wiring


def test_scaler_metrics_overflow_steps_recorded():
    from apex_tpu.amp import LossScaler

    scaler = LossScaler("dynamic", init_scale=2.0 ** 8, hysteresis=1)

    @jax.jit
    def step(state, m, g):
        grads, found_inf = scaler.unscale({"g": g}, state)
        state, _skip = scaler.update_scale(state, found_inf)
        return state, LossScaler.metrics(state, found_inf, m)

    state = scaler.init_state()
    m = Metrics({"loss_scale": 0.0, "overflow": 0.0,
                 "overflow_total": 0.0, "skipped_total": 0.0})
    good = jnp.ones((4,)) * 2.0 ** 8
    bad = jnp.array([jnp.inf, 1.0, 1.0, 1.0]) * 2.0 ** 8
    state, m = step(state, m, good)
    assert m.as_dict()["overflow"] == 0.0
    state, m = step(state, m, bad)
    d = m.as_dict()
    assert d["overflow"] == 1.0
    assert d["overflow_total"] == 1.0 and d["skipped_total"] == 1.0
    assert d["loss_scale"] == 2.0 ** 7  # backed off after the overflow
    state, m = step(state, m, good)
    d = m.as_dict()
    assert d["overflow"] == 0.0 and d["overflow_total"] == 1.0
    n = _cache_size(step)
    if n is not None:
        assert n == 1


# ---------------------------------------------------------------------------
# JSONL sink


def test_jsonl_schema_roundtrip(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    m = Metrics({"loss": 1.25, "grad_norm": 3.5})
    with JsonlSink(path, buffer_steps=2, log_every=3) as sink:
        for i in range(5):
            sink.write(step=i, metrics=m, lr=0.1)
    recs = list(read_jsonl(path))
    assert len(recs) == 5
    for i, r in enumerate(recs):
        assert r["schema"] == SCHEMA_VERSION
        assert r["step"] == i and r["loss"] == 1.25
        assert r["grad_norm"] == 3.5 and r["lr"] == 0.1
        assert "ts" in r
    # json_record shares the same schema stamp
    assert json.loads(json_record(metric="x"))["schema"] == SCHEMA_VERSION


def test_jsonl_append_after_crash(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    with JsonlSink(path, buffer_steps=1) as sink:
        sink.write(step=0, metrics={"loss": 1.0})
        sink.write(step=1, metrics={"loss": 2.0})
    # crash mid-write: a partial record with no terminating newline
    with open(path, "a") as f:
        f.write('{"schema": 1, "step": 2, "loss":')
    # the partial tail is skipped, earlier records survive
    recs = list(read_jsonl(path))
    assert [r["step"] for r in recs] == [0, 1]
    # a restarted job appends to the same file; the fragment is terminated
    with JsonlSink(path, buffer_steps=1) as sink:
        sink.write(step=2, metrics={"loss": 3.0})
    recs = list(read_jsonl(path))
    assert [r["step"] for r in recs] == [0, 1, 2]
    with pytest.raises(json.JSONDecodeError):
        list(read_jsonl(path, strict=True))  # the fragment is now interior


def test_jsonl_sink_buffers_until_flush(tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, buffer_steps=100)
    sink.write(step=0, metrics={"x": 1.0})
    assert not os.path.exists(path)  # buffered, nothing written yet
    sink.flush()
    assert len(list(read_jsonl(path))) == 1
    sink.close()


# ---------------------------------------------------------------------------
# logging satellites


def test_metrics_logger_child_exists():
    from apex_tpu import get_logger

    logger = get_logger("apex_tpu.monitor")
    assert logger.metrics.name == "apex_tpu.monitor.metrics"


def test_get_logger_no_duplicate_handlers():
    import logging

    from apex_tpu import _logging

    root = logging.getLogger("apex_tpu")
    _logging.get_logger("apex_tpu.a")
    before = len(root.handlers)
    # simulate a re-import: the module-level guard set is reset, but the
    # handler scan must still find the installed handler
    _logging._configured_roots.clear()
    _logging.get_logger("apex_tpu.b")
    assert len(root.handlers) == before
    rank_handlers = [h for h in root.handlers
                     if type(h.formatter).__name__ == "RankInfoFormatter"]
    assert len(rank_handlers) == 1


def test_log_level_env_var(monkeypatch):
    import logging

    from apex_tpu import _logging

    monkeypatch.setenv("APEX_TPU_LOG_LEVEL", "debug")
    _logging._configured_roots.discard("apex_tpu_lvltest")
    logger = _logging.get_logger("apex_tpu_lvltest")
    assert logging.getLogger("apex_tpu_lvltest").level == logging.DEBUG
    assert logger.metrics.name == "apex_tpu_lvltest.metrics"
    # garbage level is ignored, not fatal
    monkeypatch.setenv("APEX_TPU_LOG_LEVEL", "NOT_A_LEVEL")
    _logging._configured_roots.discard("apex_tpu_lvltest2")
    _logging.get_logger("apex_tpu_lvltest2")


# ---------------------------------------------------------------------------
# spans


def test_span_names_visible_in_hlo_op_table():
    """Static check (no profiler): ops traced under monitor.span carry the
    span name as their pyprof layer path — the same join key the measured
    table and the trace viewer use."""
    from apex_tpu.pyprof import op_table

    def f(x, w):
        with span("fwd"):
            h = jnp.tanh(x @ w)
        with span("opt"):
            return jnp.sum(h * h)

    rows = op_table(f, jnp.ones((64, 32)), jnp.ones((32, 16)),
                    peak_flops=1e12, hbm_bandwidth=1e11)
    # jax version differences add jit(...) wrapper components; the span
    # names must appear as path components either way
    comps = {c for r in rows for c in r["scope"].split("/")}
    assert "fwd" in comps, comps
    assert "opt" in comps, comps


def test_span_function_decorator():
    from apex_tpu.pyprof import op_table

    @span_function(name="layer0")
    def layer(x, w):
        return x @ w

    rows = op_table(lambda x, w: jnp.sum(layer(x, w)),
                    jnp.ones((16, 8)), jnp.ones((8, 8)),
                    peak_flops=1e12, hbm_bandwidth=1e11)
    assert any("layer0" in r["scope"].split("/") for r in rows)


@pytest.mark.slow
def test_span_phases_in_measured_table():
    """Profiler-trace check: spans become measured phases (the trace-join
    half of the capability). Slow: runs jax.profiler."""
    from apex_tpu.monitor import step_report

    def loss(w, x):
        with span("fwd"):
            return jnp.mean((jnp.tanh(x @ w["a"]) @ w["b"]) ** 2)

    def stepf(w, x):
        with span("bwd"):
            g = jax.grad(loss)(w, x)
        with span("opt"):
            return jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg, w, g)

    w = {"a": jnp.ones((128, 256)), "b": jnp.ones((256, 64))}
    x = jnp.ones((32, 128))
    rep = step_report(stepf, w, x, steps=3, peak_flops=1e12)
    assert rep["step_time_ms"] > 0 and 0 < rep["coverage_pct"] <= 100
    phases = rep["phase_ms"]
    assert any(k.startswith("bwd") for k in phases), phases
    assert any(k.startswith("opt") for k in phases), phases
    # the span inside the differentiated loss rolls up to its own name
    # (jvp/transpose wrappers peeled), under whichever outer span it nests
    assert any("fwd" in k or k.startswith("bwd") for k in phases), phases


# ---------------------------------------------------------------------------
# report helpers


def test_phase_breakdown_keeps_the_passes_of_one_scope_apart():
    """A span traced under jax.grad surfaces under jvp(...) and
    transpose(jvp(...)) wrappers, and its replay under a checkpoint's
    rematted_computation: the rollup names the pass (``split_scope``), so
    forward, replay and backward of one scope are three buckets, and a span
    no differentiation wraps keeps its own name."""
    measured = {"rows": [
        {"scope": "jit(main)/jvp(loss)", "time_ms": 2.0},
        {"scope": "jit(main)/jvp()/while/body/closed_call/layer", "time_ms": 1.0},
        {"scope": "jit(main)/transpose(jvp())/while/body/closed_call/layer/"
                  "checkpoint/rematted_computation/attn", "time_ms": 1.5},
        {"scope": "jit(main)/transpose(jvp(loss))", "time_ms": 3.0},
        {"scope": "jit(main)/opt/adam_tail", "time_ms": 4.0},
        {"scope": "opt", "time_ms": 0.25},
        {"scope": "jit(main)", "time_ms": 0.5},
    ]}
    assert phase_breakdown(measured) == {
        "opt": 4.25, "fwd": 3.0, "bwd": 3.0, "recompute": 1.5,
        "<no-scope>": 0.5}


def test_sink_log_every_enables_metrics_logger(tmp_path):
    """log_every is an explicit opt-in: the sink must raise the metrics
    child logger to INFO when the hierarchy default would swallow it."""
    import logging

    child = logging.getLogger("apex_tpu.monitor.metrics")
    old = child.level
    try:
        child.setLevel(logging.NOTSET)
        with JsonlSink(str(tmp_path / "m.jsonl"), buffer_steps=1,
                       log_every=1) as sink:
            sink.write(step=0, metrics={"loss": 1.0})
        assert child.isEnabledFor(logging.INFO)
    finally:
        child.setLevel(old)


def test_pipeline_bubble_fraction():
    assert pipeline_bubble_fraction(8, 4) == pytest.approx(3 / 11)
    assert pipeline_bubble_fraction(8, 1) == 0.0
    with pytest.raises(ValueError):
        pipeline_bubble_fraction(0, 4)


def test_gpt_analytic_flops_per_token():
    # 6N + causal-attention term, the constant an MFU report divides by
    assert gpt_analytic_flops_per_token(100, 2, 8, 16) == \
        6 * 100 + 6 * 2 * 8 * 16


def test_mfu_check_compile_only():
    from apex_tpu.monitor import mfu_check

    def f(x, w):
        return jnp.sum(x @ w)

    analytic = 2 * 64 * 32 * 16
    res = mfu_check(f, jnp.ones((64, 32)), jnp.ones((32, 16)),
                    analytic_flops=analytic)
    assert res["hlo_flops"] > 0
    assert 0.9 < res["hlo_over_analytic"] < 1.1
    assert res["wire_bytes"] == 0.0  # single-device program


# ---------------------------------------------------------------------------
# wire-byte model ↔ accounting pricer agreement (mesh-free: the pricer reads
# HLO text, so synthetic programs pin the exact formulas the DDP metrics use)


def _hlo_line(op, shape, groups=8):
    g = "{{" + ",".join(str(i) for i in range(groups)) + "}}"
    return (f"  %r = {shape} {op}({shape} %x), replica_groups={g}, "
            f"to_apply=%add")


def test_allreduce_wire_model_matches_pricer_uncompressed():
    from apex_tpu.comm import allreduce_wire_bytes, collective_report

    n, world = 4096, 8
    rep = collective_report(_hlo_line("all-reduce", f"f32[{n}]"))
    assert rep.counts["all-reduce"] == 1
    assert rep.wire_bytes == pytest.approx(
        allreduce_wire_bytes(n, 4, world, None))
    rep16 = collective_report(_hlo_line("all-reduce", f"bf16[{n}]"))
    assert rep16.wire_bytes == pytest.approx(
        allreduce_wire_bytes(n, 2, world, None))
    assert allreduce_wire_bytes(n, 4, 1, None) == 0.0


def test_allreduce_wire_model_matches_pricer_compressed():
    """The compressed model must price exactly the op sequence
    compressed_allreduce emits: all_to_all(codes) + all_to_all(scales) +
    all_gather(codes) + all_gather(scales), padded to block·world."""
    from apex_tpu.comm import (
        CompressionConfig,
        allreduce_wire_bytes,
        collective_report,
    )
    from apex_tpu.comm.quantize import padded_size

    n, world = 5000, 8
    cfg = CompressionConfig(policy="int8", block_size=256, min_elements=256)
    size = padded_size(n, cfg.block_size * world)
    nb = size // cfg.block_size
    hlo = "\n".join([
        _hlo_line("all-to-all", f"s8[{size}]"),
        _hlo_line("all-to-all", f"f32[{nb}]"),
        _hlo_line("all-gather", f"s8[{size}]"),
        _hlo_line("all-gather", f"f32[{nb}]"),
    ])
    rep = collective_report(hlo)
    assert rep.counts["all-to-all"] == 2 and rep.counts["all-gather"] == 2
    assert rep.wire_bytes == pytest.approx(
        allreduce_wire_bytes(n, 4, world, cfg))
    # small buffers ride the fp32 psum path
    small = cfg.min_elements - 1
    assert allreduce_wire_bytes(small, 4, world, cfg) == pytest.approx(
        collective_report(
            _hlo_line("all-reduce", f"f32[{small}]")).wire_bytes)


def test_psum_scatter_wire_model_matches_pricer():
    from apex_tpu.comm import (
        CompressionConfig,
        collective_report,
        psum_scatter_wire_bytes,
    )
    from apex_tpu.comm.quantize import padded_size

    n, world = 4100, 8
    # uncompressed: reduce-scatter result is the k-element shard
    k = -(-n // world)
    rep = collective_report(_hlo_line("reduce-scatter", f"f32[{k}]"))
    assert rep.wire_bytes == pytest.approx(
        psum_scatter_wire_bytes(n, 4, world, None))
    # compressed: one all_to_all pass of codes + scales
    cfg = CompressionConfig(policy="int8", block_size=256, min_elements=256)
    kb = -(-(-(-n // world)) // cfg.block_size) * cfg.block_size
    size = max(kb * world, padded_size(n, cfg.block_size * world))
    hlo = "\n".join([
        _hlo_line("all-to-all", f"s8[{size}]"),
        _hlo_line("all-to-all", f"f32[{size // cfg.block_size}]"),
    ])
    assert collective_report(hlo).wire_bytes == pytest.approx(
        psum_scatter_wire_bytes(n, 4, world, cfg,
                                shard_multiple=cfg.block_size))


def test_all_gather_wire_model_matches_pricer():
    from apex_tpu.comm import all_gather_wire_bytes, collective_report

    n, world = 4096, 8
    rep = collective_report(_hlo_line("all-gather", f"f32[{n}]"))
    assert rep.wire_bytes == pytest.approx(
        all_gather_wire_bytes(n, 4, world))


# ---------------------------------------------------------------------------
# mesh integration: DDP-reported bytes vs the compiled HLO; the compile gate
# on the instrumented GPT fixture (the CI/tooling acceptance criterion)


def _gpt_bits():
    from apex_tpu.transformer.testing import (
        GPTConfig,
        gpt_loss,
        init_gpt_params,
    )

    cfg = GPTConfig(vocab_size=256, max_seq=64, hidden=128, num_layers=2,
                    num_heads=2, dtype=jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((8, 64), jnp.int32)
    return cfg, gpt_loss, params, tok


@pytest.mark.parametrize("policy", ["none", "int8"])
def test_ddp_reported_bytes_match_accounting(policy):
    """DDP's in-metrics per-bucket bytes must agree with what
    comm.accounting prices off the SAME compiled HLO — the model is honest
    because both sides see the identical program."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.comm import CompressionConfig, collective_report
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.parallel.mesh import build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=1, pp=1, sp=1)  # dp=8
    cfg, gpt_loss, params, tok = _gpt_bits()
    comp = None if policy == "none" else CompressionConfig(
        policy="int8", block_size=256, min_elements=256)
    ddp = DistributedDataParallel(compression=comp,
                                  allreduce_always_fp32=True)

    def step(p, t, y):
        g = jax.grad(lambda p: gpt_loss(p, t, y, cfg))(ddp.replicate(p))
        return ddp.average_gradients(g, metrics=Metrics())

    specs = jax.tree_util.tree_map(lambda _: P(), params)
    compiled = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(specs, P("dp"), P("dp")),
        out_specs=(specs, P()),
        check_vma=False,
    )).lower(params, tok, tok).compile()
    _, metrics = compiled(params, tok, tok)
    d = metrics.as_dict()
    reported = d["comm_wire_bytes"]
    buckets = sum(v for k, v in d.items()
                  if k.startswith("comm_bucket") and k.endswith("_bytes"))
    assert buckets == pytest.approx(reported)
    priced = collective_report(compiled).wire_bytes
    assert reported == pytest.approx(priced, rel=1e-3), (reported, priced)
    if policy == "int8":
        assert d["comm_compression_ratio"] > 3.5
    else:
        assert d["comm_compression_ratio"] == pytest.approx(1.0)


def test_instrumented_gpt_step_compiles_once_and_sinks_jsonl(tmp_path):
    """The acceptance criterion: 5 monitored GPT steps produce a JSONL
    where every record carries step/loss/grad-norm/loss-scale/overflow/
    comm-bytes, the comm bytes match accounting on the compiled HLO, and
    the compile count is 1 with monitoring on AND off."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from apex_tpu.amp import LossScaler
    from apex_tpu.comm import collective_report
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.parallel.mesh import build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=1, pp=1, sp=1)
    cfg, gpt_loss, params, tok = _gpt_bits()
    ddp = DistributedDataParallel()
    scaler = LossScaler("dynamic", init_scale=2.0 ** 4)
    opt = FusedAdam(lr=1e-3)
    specs = jax.tree_util.tree_map(lambda _: P(), params)

    def build(monitored):
        def body(p, s, scaler_state, m, t, y):
            loss, g = jax.value_and_grad(
                lambda p: scaler.scale_loss(
                    gpt_loss(p, t, y, cfg), scaler_state))(ddp.replicate(p))
            if monitored:
                g, m = ddp.average_gradients(g, metrics=m)
            else:
                g = ddp.average_gradients(g)
            g, found_inf = scaler.unscale(g, scaler_state)
            new_scaler, skip = scaler.update_scale(scaler_state, found_inf)
            updates, new_s = opt.update(g, s, p)
            new_p = jax.tree_util.tree_map(lambda a, u: a + u, p, updates)
            keep = lambda new, old: jax.tree_util.tree_map(  # noqa: E731
                lambda a, b: jnp.where(skip, b, a), new, old)
            p, s = keep(new_p, p), keep(new_s, s)
            unscaled = loss / scaler_state.loss_scale
            if monitored:
                m = train_metrics(m, loss=unscaled, grads=g)
                m = LossScaler.metrics(new_scaler, found_inf, m)
            return p, s, new_scaler, m, unscaled

        sharded = jax.shard_map(
            body, mesh=mesh,
            in_specs=(specs, P(), P(), P(), P("dp"), P("dp")),
            out_specs=(specs, P(), P(), P(), P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0, 1, 2, 3))

    for monitored in (True, False):
        step = build(monitored)
        p = jax.tree_util.tree_map(jnp.copy, params)
        s = opt.init(p)
        scaler_state = scaler.init_state()
        if monitored:
            # discover the step's full metric-name set WITHOUT compiling,
            # then pre-seed so the carried treedef is stable from step 0
            # (through a second jit of the same body: a trace with the
            # empty Metrics would count in the measured step's own cache)
            out_shape = jax.eval_shape(build(True), p, s, scaler_state,
                                       Metrics(), tok, tok)
            m = Metrics({k: 0.0 for k in out_shape[3].names()})
        else:
            m = Metrics()
        # the state placed on the mesh as the step hands it back: a first
        # call on unplaced arrays is another input type, and a second trace
        p, s, scaler_state, m = jax.device_put(
            (p, s, scaler_state, m), NamedSharding(mesh, P()))
        compiled = step.lower(p, s, scaler_state, m, tok, tok).compile()
        path = str(tmp_path / f"gpt_{monitored}.jsonl")
        with JsonlSink(path, buffer_steps=2) as sink:
            for i in range(5):
                p, s, scaler_state, m, loss = step(p, s, scaler_state, m,
                                                   tok, tok)
                if monitored:
                    sink.write(step=i, metrics=m)
        n = _cache_size(step)
        if n is not None:
            assert n == 1, f"monitored={monitored}: {n} compilations"
        if not monitored:
            continue
        recs = list(read_jsonl(path))
        assert len(recs) == 5
        priced = collective_report(compiled).wire_bytes
        for r in recs:
            for field in ("step", "loss", "grad_norm", "loss_scale",
                          "overflow", "comm_wire_bytes"):
                assert field in r, (field, sorted(r))
            assert np.isfinite(r["loss"]) and r["grad_norm"] > 0
            assert r["loss_scale"] == 2.0 ** 4 and r["overflow"] == 0.0
            # DDP-reported bytes == accounting on the same HLO (the grad
            # allreduce dominates; scalar psums ride inside the tolerance)
            assert r["comm_wire_bytes"] == pytest.approx(priced, rel=1e-3)


def test_zero_adam_metrics_shard_norms():
    from jax.sharding import PartitionSpec as P

    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.parallel.mesh import build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=1, pp=1, sp=1)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (13, 7)),
              "b": jax.random.normal(jax.random.PRNGKey(1), (5,))}
    grads = jax.tree_util.tree_map(lambda x: 0.1 * jnp.ones_like(x), params)
    opt = DistributedFusedAdam(lr=1e-2)

    def run(p, g):
        state = opt.init(p)
        p2, state, m = opt.step(g, state, p, metrics=Metrics())
        return p2, m

    p_specs = jax.tree_util.tree_map(lambda _: P(), params)
    got, m = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(p_specs, p_specs),
        out_specs=(p_specs, P()),
        check_vma=False,
    ))(params, grads)
    d = m.as_dict()
    # every rank contributed the same grads; reduce-scatter averages them
    want = float(global_norm(grads))
    np.testing.assert_allclose(d["grad_norm"], want, rtol=1e-5)
    assert d["param_norm"] > 0 and d["update_norm"] > 0
    assert d["comm_wire_bytes"] > 0
