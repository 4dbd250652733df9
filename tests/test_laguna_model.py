"""Laguna's decoder (``transformer/laguna.py``) at toy size: hidden 64, full
layers of 4 query heads and sliding ones of 6 over 2 K/V heads of 16, a
window of 8, YaRN on half of a full layer's head, a dense layer then one
period of (sliding x3, full) expert layers, 8 routed experts, 3 a position,
a gated shared expert. The loss and every leaf's gradient against the
benchmark's plain reference (``perfbench/reference_laguna.py``) in float32;
the four chips' shares of an expert layer add up to the uncut reference's;
the planted faults; the train step with its counters fourth; its scopes."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench")
if _PERFBENCH not in sys.path:
    sys.path.insert(0, _PERFBENCH)

import reference_laguna  # noqa: E402
import weights_laguna  # noqa: E402

from apex_tpu.monitor import trace as monitor_trace  # noqa: E402
from apex_tpu.ops.rope import rope_for_layer_type  # noqa: E402
from apex_tpu.parallel.mesh import build_mesh  # noqa: E402
from apex_tpu.train import abstract_train_args, train_step_fn  # noqa: E402
from apex_tpu.transformer import laguna  # noqa: E402
from apex_tpu.transformer.laguna import (  # noqa: E402
    DENSE,
    FULL,
    SLIDING,
    LagunaConfig,
    layer_shapes,
)

VOCAB, L, SEED = 256, 64, 2**31 + 40
TYPES = [FULL, SLIDING, SLIDING, SLIDING, FULL]
ROPE = {FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
               "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 32,
               "attention_factor": 1.2, "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}}
# a configuration file's dict at the toy widths: what the reference and the
# benchmark's weights read
FILE = {"hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
        "num_attention_heads_per_layer": [4, 6, 6, 6, 4], "layer_types": TYPES,
        "mlp_only_layers": [0], "sliding_window": 8, "rope_parameters": ROPE,
        "rms_norm_eps": 1e-6, "intermediate_size": 96, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 3,
        "num_hidden_layers": 5, "vocab_size": VOCAB, "norm_topk_prob": True,
        "moe_routed_scaling_factor": 2.5, "experts_held": [0, 8],
        "reduced_from": {"num_hidden_layers": 48, "num_experts": 8, "vocab_size": VOCAB},
        "assumed": {"param_dtype": "float32", "embedding_std": 1.0}}


def _cfg(**kw):
    base = dict(vocab_held=VOCAB, hidden=64, layer_types=TYPES, full_heads=4, sliding_heads=6,
                num_kv_heads=2, head_dim=16, sliding_window=8,
                full_rope=rope_for_layer_type(ROPE, FULL),
                sliding_rope=rope_for_layer_type(ROPE, SLIDING), dense_hidden=96,
                num_experts=8, experts_held=(0, 8), top_k=3, expert_hidden=32,
                shared_hidden=32, dtype=jnp.float32)
    return LagunaConfig(**{**base, **kw})


def _file(**kw):
    return {**FILE, **kw}


def _batch(rows=2, seed=1):
    x = np.random.default_rng(seed).integers(0, VOCAB, (rows, L + 1), dtype=np.int32)
    return jnp.asarray(x[:, :-1]), jnp.asarray(x[:, 1:])


SHAPE = reference_laguna.model_shape(FILE)


@pytest.fixture(scope="module")
def both():
    """(loss, gradients) of the program and of the reference on the
    benchmark's weights, float32 on both sides."""
    params = weights_laguna.make_params(FILE, SEED)
    tok, tgt = _batch()
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(_cfg().loss)(params, tok, tgt)
    want = jax.value_and_grad(reference_laguna.loss_fn)(params, tok, tgt, SHAPE)
    return got, want


def test_the_programs_loss_equals_the_references(both):
    (loss, _), (ref_loss, _) = both
    assert abs(float(loss) - float(ref_loss)) < 2e-5 * abs(float(ref_loss))


_ATTN = ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wg", "wo", "norm2")
LEAVES = (["embed.tok", "head.norm", "head.lm"]
          + [f"{DENSE}.{n}" for n in _ATTN + ("w_gate", "w_up", "w_down")]
          + [f"{kind}.{n}" for kind in (SLIDING, FULL)
             for n in _ATTN + ("router", "w_gate", "w_up", "w_down", "shared_gate",
                               "shared_up", "shared_down", "shared_expert_gate")])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_equals_the_references(both, leaf):
    (_, grads), (_, ref) = both
    group, name = leaf.split(".")
    at = lambda tree: (tree["periods"][group][name] if group in (DENSE, SLIDING, FULL)
                       else tree[group][name])
    got, want = np.asarray(at(grads)), np.asarray(at(ref))
    assert got.shape == want.shape
    scale = float(np.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=2e-4 * scale)


def test_the_stepwise_reference_equals_its_one_piece_form(both):
    """``step_gradient`` (a layer at a time, a row at a time: the full-size
    path) against ``loss_fn`` differentiated whole."""
    _, (ref_loss, ref) = both
    params = weights_laguna.make_params(FILE, SEED)
    loss, g = reference_laguna.step_gradient(params, *_batch(), SHAPE, 1)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(float(jnp.abs(b).max()), 1e-6))


def test_the_shares_of_four_chips_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One sliding expert layer, the router's eight experts all drawn: four
    shares of two experts each through the program's routed layer, plus the
    gated shared expert once (every chip computes it alike), equal the
    reference's layer with all eight."""
    from apex_tpu.transformer.moe import routed_experts_mlp
    p = jax.tree.map(lambda a: a[0, 0].astype(jnp.float32),
                     weights_laguna.make_params(FILE, SEED)["periods"][SLIDING])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, L, 64), jnp.float32)
    model = lambda first: _cfg(experts_held=(first, 2))
    with jax.default_matmul_precision("highest"):
        h = laguna._attention_sublayer(p, x, model(0), SLIDING)     # alike on every chip
        m = laguna.rms_norm(h, p["norm2"], 1e-6)
        share = lambda first: {**p, **{n: p[n][first:first + 2]
                                       for n in ("w_gate", "w_up", "w_down")}}
        routed = [routed_experts_mlp(share(first), m, model(first).routed, (first, 2))[0]
                  for first in range(0, 8, 2)]
        whole = laguna._experts_sublayer(p, h, _cfg())[0]
        one = laguna._experts_sublayer(share(0), h, model(0))[0]
    shared = one - h - routed[0]
    uncut = reference_laguna.layer_fn(p, x, reference_laguna.knobs(), SHAPE, SLIDING)
    np.testing.assert_allclose(h + sum(routed) + shared, uncut, atol=5e-5)
    np.testing.assert_allclose(whole, uncut, atol=5e-5)
    want_shared = reference_laguna.shared_fn(m, p, None, reference_laguna.knobs())
    np.testing.assert_allclose(shared, want_shared, atol=5e-5)


@pytest.mark.parametrize("fault", reference_laguna.FAULTS)
def test_each_planted_fault_moves_the_references_loss_or_gradient(fault):
    params = weights_laguna.make_params(FILE, SEED)
    sound = jax.value_and_grad(reference_laguna.loss_fn)(params, *_batch(), SHAPE)
    bad = jax.value_and_grad(reference_laguna.loss_fn)(params, *_batch(), SHAPE, None, fault)
    gap = max(float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-12))
              for a, b in zip(jax.tree.leaves(sound[1]), jax.tree.leaves(bad[1])))
    assert gap > 1e-3, fault


def test_a_sliding_layer_reads_only_its_window_and_a_full_one_reads_all_before():
    """A token changed at position 20: a sliding layer's output moves at 20
    .. 27 alone, a full layer's from 20 on; neither before 20."""
    lp = lambda kind: jax.tree.map(lambda a: a[0, 0],
                                   weights_laguna.make_params(FILE, SEED)["periods"][kind])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, L, 64), jnp.float32)
    moved = x.at[:, 20].add(1.0)
    for kind, seen in ((SLIDING, range(20, 28)), (FULL, range(20, L))):
        a = laguna._attention(lp(kind), x, _cfg(), kind)
        b = laguna._attention(lp(kind), moved, _cfg(), kind)
        diff = np.asarray(jnp.abs(a - b).max(axis=(0, 2)))
        assert set(np.nonzero(diff > 1e-6)[0]) == set(seen), kind


def test_replaying_by_sublayer_gives_the_loss_and_gradients_of_keeping_all():
    params = weights_laguna.make_params(FILE, SEED)
    tok, tgt = _batch()
    out = {how: jax.value_and_grad(_cfg(remat=how).loss)(params, tok, tgt)
           for how in ("sublayer", "none")}
    assert abs(float(out["sublayer"][0]) - float(out["none"][0])) < 1e-6
    for a, b in zip(jax.tree.leaves(out["sublayer"][1]), jax.tree.leaves(out["none"][1])):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_the_published_widths_hold_811_030_784_parameters():
    cfg = LagunaConfig()
    assert cfg.period == (SLIDING, SLIDING, SLIDING, FULL)
    shapes = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 811_030_784
    assert layer_shapes(cfg, DENSE)["wq"] == (3072, 48 * 128)
    assert layer_shapes(cfg, SLIDING)["wq"] == (3072, 72 * 128)
    assert layer_shapes(cfg, SLIDING)["wg"] == (3072, 72)
    assert layer_shapes(cfg, FULL)["router"] == (3072, 256)
    assert shapes["periods"][SLIDING]["w_gate"].shape == (1, 3, 8, 3072, 1024)
    assert shapes["periods"][FULL]["shared_expert_gate"].shape == (1, 1, 3072, 1)
    assert shapes["periods"][DENSE]["w_down"].shape == (1, 1, 12288, 3072)
    # the benchmark's weights have the program's leaves
    import json
    with open(os.path.join(_PERFBENCH, "configs", "laguna-s-2.1.json")) as f:
        published = json.load(f)
    made = jax.eval_shape(lambda: weights_laguna._make(0, 0, config=published))
    assert jax.tree.map(lambda a: a.shape, made) == jax.tree.map(lambda a: a.shape, shapes)


def test_what_the_config_refuses():
    with pytest.raises(ValueError, match="not a range"):
        _cfg(experts_held=(6, 3))
    with pytest.raises(ValueError, match="expert layer"):
        _cfg(first_k_dense=5)
    with pytest.raises(ValueError, match="dense layers are full"):
        _cfg(layer_types=[SLIDING, SLIDING, FULL])
    with pytest.raises(ValueError, match="divide"):
        _cfg(sliding_heads=5)
    with pytest.raises(ValueError, match="remat"):
        _cfg(remat="layer")


def _step(cfg, **mesh_kw):
    mesh_kw = {"tp": 1, "pp": 1, "sp": 1, "dp": 1, **mesh_kw}
    n = mesh_kw["tp"] * mesh_kw["dp"]
    mesh = build_mesh(devices=jax.devices()[:n], **mesh_kw)
    return (*train_step_fn(cfg, mesh), mesh)


def test_three_steps_through_the_train_step_with_the_counters_fourth():
    """The loss falls on a repeated batch; the step's fourth result is each
    expert layer's loads and the positions by places held, stacked over dp;
    two data-parallel shards give the one-device step."""
    cfg = _cfg(experts_held=(2, 3))
    held = _file(num_experts=3, experts_held=[2, 3])
    tok, tgt = _batch(rows=4)
    out = {}
    for dp in (1, 2):
        step, opt, _ = _step(cfg, dp=dp)
        params = weights_laguna.make_params(held, SEED)
        state, losses = opt.init(params), []
        for _ in range(3):
            params, state, loss, counters = step(params, state, tok, tgt)
            losses.append(float(loss))
        out[dp] = (losses, params, jax.device_get(counters))
    losses, _, counters = out[1]
    assert losses[2] < losses[1] < losses[0]
    assert counters["expert_loads"].shape == (1, 4, 3) and counters["held_places"].shape == (1, 4, 4)
    assert (counters["held_places"].sum(-1) == 4 * L).all()
    assert 0 < counters["expert_loads"].sum() < 4 * 4 * L * 3
    np.testing.assert_allclose(out[2][0], losses, atol=1e-5)
    np.testing.assert_array_equal(out[2][2]["expert_loads"].sum(0), counters["expert_loads"][0])
    for a, b in zip(jax.tree.leaves(out[1][1]), jax.tree.leaves(out[2][1])):
        np.testing.assert_allclose(a, b, atol=2e-5)


SCOPES = ("embed", "layer/pre_norm", "layer/attn/qkv", "layer/attn/qk_norm", "layer/attn/rope",
          "layer/attn/core", "layer/attn/gate", "layer/attn/out", "layer/swa/qkv",
          "layer/swa/qk_norm", "layer/swa/rope", "layer/swa/core", "layer/swa/gate",
          "layer/swa/out", "layer/mlp/gate_up", "layer/mlp/act", "layer/mlp/down",
          "layer/shared/gate_up", "layer/shared/act", "layer/shared/down", "layer/shared/gate",
          "layer/moe/route", "layer/moe/dispatch", "layer/moe/experts", "layer/moe/combine",
          "layer/residual", "final_norm", "lm_head_loss", "opt")


def test_the_train_step_carries_the_contracts_scopes():
    cfg = _cfg()
    step, opt, mesh = _step(cfg)
    text = step.lower(*abstract_train_args(cfg, opt, mesh, 2, L)).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope + "/" in text or scope + '"' in text, scope
    table = monitor_trace.scope_table("jit_train_step", rows=2, seq=L)
    seen = {monitor_trace.split_scope(rec["op_name"])[1] for rec in table.values()}
    for scope in ("layer/swa/core", "layer/swa/gate", "layer/attn/gate", "layer/shared/gate",
                  "layer/mlp/down", "layer/moe/route", "opt"):
        assert any(s == scope or s.startswith(scope + "/") for s in seen), scope
    assert not any(s.startswith("layer/moe/shared") for s in seen)
