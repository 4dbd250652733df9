"""Per-op attribution report (ref apex/pyprof/parse + prof: kernels mapped to
layers with FLOP/byte estimates, rendered as a table)."""

import jax
import jax.numpy as jnp

from apex_tpu.pyprof import annotate, format_table, op_table

# the test box is a CPU, which has no entry in the peaks table: the roofline
# constants are the caller's (here the v5e figures, as plain numbers)
PEAKS = dict(peak_flops=197e12, hbm_bandwidth=819e9)


def _f(x, w1, w2):
    with annotate("layer1"):
        h = jnp.tanh(x @ w1)
    with annotate("layer2"):
        return jnp.sum(h @ w2)


def test_op_table_attributes_dots_to_scopes_with_exact_flops():
    x = jnp.ones((256, 512), jnp.bfloat16)
    w1 = jnp.ones((512, 512), jnp.bfloat16)
    w2 = jnp.ones((512, 128), jnp.bfloat16)
    rows = op_table(_f, x, w1, w2, **PEAKS)
    scopes = {r["scope"] for r in rows}
    assert any(s.startswith("layer1") for s in scopes)
    assert any(s.startswith("layer2") for s in scopes)
    total_flops = sum(r["flops"] for r in rows)
    expected = 2 * 256 * 512 * 512 + 2 * 256 * 512 * 128
    assert abs(total_flops - expected) / expected < 0.05
    assert all(r["bytes"] > 0 for r in rows if r["op"] != "custom-call")
    # sorted by estimated time, roofline fields present
    times = [r["est_time_s"] for r in rows]
    assert times == sorted(times, reverse=True)
    assert all(r["bound"] in ("compute", "memory") for r in rows)


def test_format_table_renders():
    x = jnp.ones((64, 128), jnp.float32)
    w = jnp.ones((128, 128), jnp.float32)
    rows = op_table(lambda x, w: jnp.sum(x @ w), x, w, **PEAKS)
    text = format_table(rows, top=5)
    assert "GFLOP" in text and "TOTAL est" in text


def test_op_table_on_train_step_with_grad():
    # fwd+bwd+sgd: the report must handle fusions, transposes, reductions
    def loss(w, x):
        with annotate("mlp"):
            return jnp.mean((jnp.tanh(x @ w["a"]) @ w["b"]) ** 2)

    def step(w, x):
        g = jax.grad(loss)(w, x)
        return jax.tree.map(lambda p, gg: p - 0.1 * gg, w, g)

    w = {"a": jnp.ones((128, 256), jnp.float32),
         "b": jnp.ones((256, 64), jnp.float32)}
    x = jnp.ones((32, 128), jnp.float32)
    rows = op_table(step, w, x, **PEAKS)
    assert sum(r["flops"] for r in rows) > 0
    # backward dots exist: total flops ~3x forward dot flops
    fwd = 2 * 32 * 128 * 256 + 2 * 32 * 256 * 64
    assert sum(r["flops"] for r in rows) > 2.0 * fwd


def test_measured_op_table_joins_trace_and_hlo():
    """Ref parse/kernel.py + prof/output.py: MEASURED kernel time joined
    with per-op flops/bytes. On the CPU backend the thunk spans carry the
    HLO instruction names, same as TPU device rows."""
    from apex_tpu.pyprof import format_measured_table, measured_op_table

    def step(x, w1, w2):
        with annotate("mlp"):
            return (jnp.tanh(x @ w1) @ w2).sum()

    x = jnp.ones((256, 256), jnp.float32)
    w1 = jnp.ones((256, 512), jnp.float32)
    w2 = jnp.ones((512, 256), jnp.float32)
    res = measured_op_table(step, x, w1, w2, steps=3,
                            peak_flops=PEAKS["peak_flops"])
    rows = res["rows"]
    assert rows, "no measured rows joined"
    dot = [r for r in rows if r["op"] == "dot"]
    assert dot and all(r["time_ms"] > 0 and r["flops"] > 0 for r in dot)
    # measured time yields a finite achieved-MFU and bandwidth per op
    assert all(r["mfu_pct"] >= 0 and r["gbps"] >= 0 for r in rows)
    assert 0 < res["coverage_pct"] <= 100.0
    # rows sorted by measured time, percentages sum to ~100
    times = [r["time_ms"] for r in rows]
    assert times == sorted(times, reverse=True)
    assert abs(sum(r["pct"] for r in rows) - 100.0) < 1e-6
    text = format_measured_table(res, top=5)
    assert "ms/step" in text and "coverage" in text
