"""The host log (``monitor.trace.host_log``): host spans and JAX's compile
path on one clock, in memory, bounded; the train step's calls as spans with
their index. All on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from apex_tpu.monitor import trace
from apex_tpu.monitor.trace import HostLog, host_log, span


@pytest.fixture
def log(monkeypatch):
    fresh = HostLog()
    monkeypatch.setattr(trace, "_LOG", fresh)
    return fresh


def _fresh_jit(name):
    """A function JAX has never traced, so that every call pays the path."""
    def f(x):
        return jnp.tanh(x) * 2.0

    f.__name__ = name
    return jax.jit(f)


def test_spans_nest_and_record_their_parent(log):
    with span("outer", call=3):
        with span("inner"):
            pass
    inner, outer = host_log()
    assert (inner.name, inner.kind, inner.parent, inner.call) == ("inner", "span", "outer", 3)
    assert (outer.name, outer.parent, outer.call) == ("outer", None, 3)
    assert outer.start <= inner.start <= inner.end <= outer.end


def test_a_span_in_jitted_code_is_a_scope_and_no_record(log):
    def f(x):
        with span("attn/qkv"):
            return x + 1.0

    text = jax.jit(f).lower(jnp.ones(2)).as_text(debug_info=True)
    assert "attn/qkv" in text
    assert not [r for r in host_log() if r.kind == "span"]


def test_a_compile_inside_a_dispatch_carries_its_call_and_new_shapes_compile_again(log):
    f = _fresh_jit("toy_dispatched")
    with span("toy_dispatched", call=1):
        f(jnp.ones(3))
    with span("toy_dispatched", call=2):
        f(jnp.ones(3))                      # cached in memory: no pass at all
    with span("toy_dispatched", call=3):
        with span("nested"):
            f(jnp.ones(4))                  # new shapes
    recs = [r for r in host_log() if r.program == "toy_dispatched"]
    assert [(r.kind, r.call) for r in recs] == [
        ("trace", 1), ("lower", 1), ("compile", 1),
        ("trace", 3), ("lower", 3), ("compile", 3)]
    assert {r.parent for r in recs} == {"toy_dispatched", "nested"}
    assert recs[2].name == "jit(toy_dispatched)" and recs[2].cached is False


def test_nested_traces_fold_into_the_outermost_with_a_count(log):
    inner = _fresh_jit("toy_inner")

    def outer(x):
        return inner(inner(x) + 1.0) * inner(x * 3.0)

    jax.jit(outer).lower(jax.ShapeDtypeStruct((5,), jnp.float32))
    traces = [r for r in host_log() if r.kind == "trace"]
    assert [r.program for r in traces] == ["outer"]
    # the inner function once (its second call at the same shapes hits its
    # cache), and the primitives' own wrappers JAX had not traced yet
    assert traces[0].count >= 2


def test_a_persistent_cache_read_is_marked(log, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(tmp_path), 0.0, -1)):
            jax.config.update(k, v)
        cc.reset_cache()
        _fresh_jit("toy_cached")(jnp.ones(6))           # compiled, written
        _fresh_jit("toy_cached")(jnp.ones(6))           # read back
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    got = [r.cached for r in host_log() if r.kind == "compile" and r.program == "toy_cached"]
    assert got == [False, True]


def test_the_listener_installs_once():
    from jax._src import monitoring

    trace._install()
    trace._install()
    for listeners, fn in ((monitoring.get_scalar_listeners, trace._on_start),
                          (monitoring.get_event_time_span_listeners, trace._on_time_span),
                          (monitoring.get_event_listeners, trace._on_event)):
        assert listeners().count(fn) == 1


def test_the_log_keeps_its_head_whole_and_a_ring_of_the_newest(monkeypatch):
    small = HostLog(head=3, ring=4)
    monkeypatch.setattr(trace, "_LOG", small)
    for i in range(100):
        with span("s", call=i):
            pass
    assert [r.call for r in host_log()] == [0, 1, 2, 96, 97, 98, 99]


def test_the_wrapped_train_step_lowers_and_counts_its_calls(log):
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.train import abstract_train_args, train_step_fn
    from apex_tpu.transformer.testing import GPTConfig

    cfg = GPTConfig(vocab_size=128, max_seq=16, hidden=32, num_layers=1,
                    num_heads=2, dtype=jnp.float32)
    mesh = build_mesh(tp=1, pp=1, sp=1, dp=1, devices=jax.devices()[:1])
    step, opt = train_step_fn(cfg, mesh)
    args = abstract_train_args(cfg, opt, mesh, 2, 16)
    assert "stablehlo" in step.lower(*args).as_text()
    assert step.trace(*args).lower().as_text() == step.lower(*args).as_text()
    lowered = [r for r in host_log() if r.program == "train_step"]
    assert {r.kind for r in lowered} == {"trace", "lower"}    # no call, no compile
    assert step.calls == 0 and {r.call for r in lowered} == {None}
    params = cfg.init_params(jax.random.PRNGKey(0))
    state = opt.init(params)
    tok = jnp.zeros((2, 16), jnp.int32)
    params, state, _ = step(params, state, tok, tok)
    assert step.calls == 1
    compiled, = [r for r in host_log() if r.program == "train_step" and r.kind == "compile"]
    assert (compiled.call, compiled.parent) == (1, "train_step")
    call, = [r for r in host_log() if r.kind == "span"]
    assert (call.name, call.call) == ("train_step", 1)
