"""Scopes from the program into the compiled module: ``split_scope`` on the
path shapes JAX writes, ``instruction_scopes`` / ``moves_only`` on hand-written
HLO, the train step's scopes in a module compiled on the CPU, the program
registry (lazy, holds no array, right under a stale compile cache) and the
engine's host spans. All on the CPU."""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.monitor import trace
from apex_tpu.monitor.trace import scope_table, span, split_scope
from apex_tpu.pyprof.prof import instruction_scopes


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(loss)/while/body/closed_call/layer/attn/qkv/dot_general",
     ("fwd", "loss/layer/attn/qkv")),
    ("jit(step)/jvp()/while/body/closed_call/layer/attn/qkv/dot_general",
     ("fwd", "layer/attn/qkv")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/layer/layer/checkpoint/"
     "rematted_computation/attn/qkv/dot_general", ("recompute", "layer/attn/qkv")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/layer/layer/checkpoint/"
     "attn/qkv/transpose", ("bwd", "layer/attn/qkv")),
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice",
     ("bwd", "scan_carry")),
    ("jit(step)/jvp()/while/body/dynamic_slice", ("fwd", "scan_carry")),
    ("jit(step)/opt/sub", ("opt", "opt")),
    ("jit(step)/jvp(embed)/add", ("fwd", "embed")),
    ("jit(step)/transpose(jvp(lm_head_loss))/mul;jit(step)/transpose("
     "jvp(lm_head_loss))/broadcast_in_dim", ("bwd", "lm_head_loss")),
    ("jit(train_step)/jit(main)/opt/shard_map/adam_tail/pallas_call",
     ("opt", "opt/adam_tail")),
    ("jit(step)/jvp()/while", ("fwd", "")),
    ("params['layers']['fc1_bias']", ("", "")),
    ("", ("", "")),
])
def test_split_scope(op_name, want):
    assert split_scope(op_name) == want


# -- moves_only and containers on hand-written HLO -----------------------------

HLO = """HloModule jit_step, entry_computation_layout={()->f32[8]}

%fused_copy (p0: bf16[4,8]) -> bf16[1,8,4] {
  %p0 = bf16[4,8]{1,0:T(8,128)(2,1)} parameter(0)
  %c0 = s32[]{:T(128)} constant(0)
  %i1 = s32[]{:T(128)} add(s32[] %c0, s32[] %c0)
  %t0 = bf16[8,4]{0,1:T(8,128)(2,1)} transpose(%p0), dimensions={1,0}
  ROOT %b0 = bf16[1,8,4]{2,1,0:T(8,128)(2,1)} bitcast(%t0)
}

%fused_add (p0.1: bf16[4,8], p1.1: bf16[4,8]) -> bf16[4,8] {
  %p0.1 = bf16[4,8]{1,0} parameter(0)
  %p1.1 = bf16[4,8]{1,0} parameter(1)
  %cp = bf16[4,8]{1,0} copy(%p0.1)
  ROOT %add.9 = bf16[4,8]{1,0} add(%cp, %p1.1)
}

%body (arg: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %arg = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte = bf16[4,8]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %fusion.1 = bf16[1,8,4]{2,1,0:T(8,128)(2,1)} fusion(%gte), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(step)/jvp()/while/body/closed_call/layer/attn/qkv/transpose"}
  %fusion.2 = bf16[4,8]{1,0:T(8,128)(2,1)} fusion(%gte, %gte), kind=kLoop, calls=%fused_add, metadata={op_name="jit(step)/jvp()/while/body/closed_call/layer/residual/add"}
  %ss = ((bf16[4,8]{1,0:T(8,128)(2,1)}), bf16[2,8]{1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%fusion.2), slice={[0:2], [0:8]}
  %sd = bf16[2,8]{1,0:T(8,128)(2,1)S(1)} slice-done(%ss)
  %k = (bf16[4,8]{1,0:T(8,128)(2,1)}, f32[4]{0:T(128)}) custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/layer/attn/core/flash_fwd/pallas_call"}
  ROOT %out = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)}) tuple(%gte, %fusion.2)
}

%cond (arg.1: (s32[], bf16[4,8])) -> pred[] {
  %arg.1 = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)}) parameter(0)
  ROOT %lt = pred[]{:T(512)} constant(true)
}

ENTRY %main (x: bf16[4,8]) -> f32[8] {
  %x = bf16[4,8]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="x"}
  %init = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)}) tuple(%x, %x)
  %while.4 = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp()/while"}
  %copy.7 = bf16[4,8]{0,1:T(8,128)(2,1)} copy(%x)
  ROOT %r = f32[8]{0:T(128)} convert(%copy.7), metadata={op_name="jit(step)/opt/convert"}
}
"""


def test_instruction_scopes_on_tpu_style_hlo():
    table = instruction_scopes(HLO)
    # tuple types with tiled layouts parse: the kernel and the async slice
    assert table["k"]["opcode"] == "custom-call"
    assert split_scope(table["k"]["op_name"]) == ("fwd", "layer/attn/core/flash_fwd")
    assert table["ss"]["opcode"] == "slice-start" and table["ss"]["operands"] == ["fusion.2"]
    # instructions of fused computations are not events: left out
    assert "t0" not in table and "add.9" not in table
    assert table["while.4"]["container"] and not table["fusion.1"]["container"]
    assert table["copy.7"]["op_name"] == ""


@pytest.mark.parametrize("name,moves", [
    ("fusion.1", True),     # transpose + bitcast + scalar index arithmetic
    ("fusion.2", False),    # a copy and one add
    ("while.4", False),     # a container
    ("copy.7", True), ("ss", True), ("sd", True),
    ("k", False), ("r", False), ("gte", False),
])
def test_moves_only(name, moves):
    assert instruction_scopes(HLO)[name]["moves_only"] is moves


# -- the train step's scopes in a compiled module --------------------------------

def _toy_step():
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.train import train_step_fn
    from apex_tpu.transformer.testing import GPTConfig

    cfg = GPTConfig(vocab_size=256, max_seq=32, hidden=64, num_layers=2,
                    num_heads=2, dtype=jnp.float32, remat=True)
    mesh = build_mesh(tp=1, pp=1, sp=1, dp=1, devices=jax.devices()[:1])
    return train_step_fn(cfg, mesh)


@pytest.fixture(scope="module")
def toy_table():
    _toy_step()
    return scope_table("jit_train_step", rows=2, seq=32)


SCOPES = ["embed", "layer/ln1", "layer/attn/qkv", "layer/attn/core",
          "layer/attn/out", "layer/ln2", "layer/mlp/fc", "layer/mlp/act",
          "layer/mlp/proj", "layer/residual", "final_ln", "lm_head_loss",
          "opt", "scan_carry"]


@pytest.mark.parametrize("scope", SCOPES)
def test_train_step_has_every_planted_scope(toy_table, scope):
    found = {split_scope(r["op_name"])[1] for r in toy_table.values()}
    assert any(s == scope or s.startswith(scope + "/") for s in found), sorted(found)


def test_train_step_phases_split_forward_replay_and_backward(toy_table):
    by_phase = {}
    for rec in toy_table.values():
        phase, scope = split_scope(rec["op_name"])
        by_phase.setdefault(phase, set()).add(scope)
    assert {"fwd", "recompute", "bwd", "opt"} <= set(by_phase)
    # the same scope is told apart in its three passes
    for phase in ("fwd", "recompute", "bwd"):
        assert any(s.startswith("layer/attn/qkv") for s in by_phase[phase]), phase
    assert "scan_carry" in by_phase["fwd"] | by_phase["bwd"]
    assert all(s.startswith("opt") for s in by_phase["opt"]), by_phase["opt"]


# -- the registry -----------------------------------------------------------------

def test_registry_lowers_nothing_until_asked_and_holds_no_array(monkeypatch):
    calls = []
    monkeypatch.setattr(trace, "_PROGRAMS", {})
    step, _ = _toy_step()
    lower = trace._PROGRAMS["jit_train_step"]
    monkeypatch.setitem(trace._PROGRAMS, "jit_train_step",
                        lambda **kw: calls.append(kw) or lower(**kw))
    assert calls == []                      # registering lowered nothing

    def cells(fn, seen):
        for cell in fn.__closure__ or ():
            v = cell.cell_contents
            for leaf in jax.tree.leaves(v):
                assert not isinstance(leaf, (jax.Array, np.ndarray)), leaf
            if callable(v) and getattr(v, "__closure__", None) and id(v) not in seen:
                seen.add(id(v))
                cells(v, seen)

    cells(lower, set())
    table = scope_table("jit_train_step", rows=2, seq=32)
    assert calls == [{"rows": 2, "seq": 32}]
    assert any(r["opcode"] == "while" and r["container"] for r in table.values())
    assert scope_table("jit_no_such_program") is None


def test_scope_table_is_right_when_the_cache_holds_the_program_without_scopes(tmp_path):
    """The compile cache's key ignores metadata: a program compiled before a
    scope was planted is handed back for the one compiled after, and its text
    has the old metadata. ``scope_table`` must still find the scope."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def make(scoped):
        def layer(x, w):
            with span("attn/qkv") if scoped else span("unnamed"):
                return jnp.tanh(x @ w)

        def step(x, w):
            return lax.fori_loop(0, 3, lambda i, x: layer(x, w), x).sum()

        step.__name__ = "toy_step"
        return jax.jit(step)

    args = (jnp.ones((64, 64)), jnp.ones((64, 64)))
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        make(False).lower(*shapes).compile()            # fills the cache
        assert glob.glob(str(tmp_path / "*toy_step*"))
        stale = make(True).lower(*shapes).compile().as_text()
        assert "attn/qkv" not in stale and "unnamed" in stale     # the trap
        trace.register_program("jit_toy_step", lambda: make(True).lower(*shapes))
        table = scope_table("jit_toy_step")
        scopes = {split_scope(r["op_name"])[1] for r in table.values()}
        assert "attn/qkv" in scopes and "unnamed" not in scopes
        assert not jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        trace._PROGRAMS.pop("jit_toy_step", None)
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


# -- the engine's host spans ---------------------------------------------------------

def _host_event_names(log_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_engine_emits_its_host_spans_and_registers_its_programs(tmp_path, monkeypatch):
    from apex_tpu.serve import InferenceEngine, Request, ServeConfig
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    monkeypatch.setattr(trace, "_PROGRAMS", {})
    cfg = GPTConfig(vocab_size=64, max_seq=64, hidden=32, num_layers=2,
                    num_heads=2, dtype=jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(params, cfg, ServeConfig(
        num_slots=2, block_size=8, prefill_chunk=8))
    assert set(trace._PROGRAMS) == {"jit_decode", "jit_chunk_prefill"}
    engine.submit(Request("a", [1, 2, 3, 4, 5], max_new_tokens=3))
    engine.step()                               # compiles outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        engine.submit(Request("b", [5, 4, 3], max_new_tokens=2))
        while engine.step():
            pass
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    assert {"prefill", "prefill.admit", "decode", "decode.dispatch",
            "decode.fence", "decode.retire"} <= names, sorted(names)[:40]
    # the registry's thunks lower the engine's own programs, scopes and all
    table = scope_table("jit_decode")
    scopes = {split_scope(r["op_name"])[1] for r in table.values()}
    for want in ("layer/ln1", "layer/attn/qkv", "layer/kv_write",
                 "layer/kv_read", "layer/attn/out", "layer/mlp/fc",
                 "layer/mlp/act", "layer/mlp/proj", "final_ln", "lm_head"):
        assert any(s == want or s.startswith(want + "/") for s in scopes), (want, sorted(scopes))
    assert scope_table("jit_chunk_prefill")
