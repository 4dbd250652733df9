"""SmallThinker's decoder (``transformer/smallthinker.py``) at toy size:
hidden 64, 4 query heads over 2 K/V heads of 16, a window of 8, one period
of (full NoPE, window x3) layers, 8 routed ReLU-gated experts, 3 a position,
routed from the attention's normed input. The loss and every leaf's gradient
against the benchmark's plain reference (``perfbench/reference_smallthinker.py``)
in float32; the program is not the model with the router reading the
experts' input, SiLU experts or a rotated full layer; the four chips' shares
of a layer add up to the uncut reference's; the window's reach; the router
ahead of attention; the train step with its counters fourth and its scopes."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench")
if _PERFBENCH not in sys.path:
    sys.path.insert(0, _PERFBENCH)

import reference_smallthinker  # noqa: E402
import weights_smallthinker  # noqa: E402

from apex_tpu.parallel.mesh import build_mesh  # noqa: E402
from apex_tpu.train import abstract_train_args, train_step_fn  # noqa: E402
from apex_tpu.transformer import smallthinker  # noqa: E402
from apex_tpu.transformer.smallthinker import (  # noqa: E402
    FULL,
    WINDOW,
    SmallThinkerConfig,
    layer_shapes,
)

VOCAB, L, SEED = 256, 64, 2**31 + 42
# a configuration file's dict at the toy widths: what the reference and the
# benchmark's weights read
FILE = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
        "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 8, "rope_theta": 1500000,
        "rms_norm_eps": 1e-6, "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
        "moe_num_active_primary_experts": 3, "num_hidden_layers": 4, "vocab_size": VOCAB,
        "norm_topk_prob": True, "experts_held": [0, 8],
        "reduced_from": {"num_hidden_layers": 52, "moe_num_primary_experts": 8,
                         "vocab_size": VOCAB},
        "assumed": {"param_dtype": "float32", "embedding_std": 1.0}}
SHAPE = reference_smallthinker.model_shape(FILE)


def _cfg(**kw):
    base = dict(vocab_held=VOCAB, hidden=64, num_heads=4, num_kv_heads=2, head_dim=16,
                sliding_window=8, num_experts=8, experts_held=(0, 8), top_k=3,
                expert_hidden=32, dtype=jnp.float32)
    return SmallThinkerConfig(**{**base, **kw})


def _batch(rows=2, seed=1):
    x = np.random.default_rng(seed).integers(0, VOCAB, (rows, L + 1), dtype=np.int32)
    return jnp.asarray(x[:, :-1]), jnp.asarray(x[:, 1:])


@pytest.fixture(scope="module")
def params():
    return weights_smallthinker.make_params(FILE, SEED)


@pytest.fixture(scope="module")
def reference_grad():
    """(loss, gradients) of the reference with the faults as knobs: one
    compiled program for the sound model and each fault."""
    return jax.jit(jax.value_and_grad(
        lambda p, tok, tgt, k: reference_smallthinker.knobbed_loss(p, tok, tgt, SHAPE, k)))


@pytest.fixture(scope="module")
def both(params, reference_grad):
    """(loss, gradients) of the program and of the reference on the
    benchmark's weights, float32 on both sides."""
    tok, tgt = _batch()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(_cfg().loss))(params, tok, tgt)
    return got, reference_grad(params, tok, tgt, reference_smallthinker.knobs())


def test_the_programs_loss_equals_the_references(both):
    (loss, _), (ref_loss, _) = both
    assert abs(float(loss) - float(ref_loss)) < 2e-5 * abs(float(ref_loss))


LEAVES = (["embed.tok", "head.norm", "head.lm"]
          + [f"{kind}.{n}" for kind in (FULL, WINDOW)
             for n in ("norm1", "router", "wq", "wk", "wv", "wo", "norm2", "w_gate", "w_up",
                       "w_down")])


def _leaf(tree, leaf):
    group, name = leaf.split(".")
    return tree["periods"][group][name] if group in (FULL, WINDOW) else tree[group][name]


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_equals_the_references(both, leaf):
    (_, grads), (_, ref) = both
    got, want = np.asarray(_leaf(grads, leaf)), np.asarray(_leaf(ref, leaf))
    assert got.shape == want.shape
    scale = float(np.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=2e-4 * scale)


@pytest.mark.parametrize("fault,leaf", [("router-reads-m", "full.norm1"),
                                        ("silu", "window.w_gate"),
                                        ("full-rotation", "full.wq")])
def test_the_program_is_not_the_model_with_the_fault(params, both, reference_grad, fault, leaf):
    """The router reading ``m`` (the experts' input), SiLU in place of ReLU,
    a rotated full layer: the reference with that fault planted stands far
    from the program on the leaf the fault moves most, where the sound
    reference stands within rounding."""
    (_, grads), (_, ref) = both
    _, bad = reference_grad(params, *_batch(), reference_smallthinker.knobs(fault))
    got, sound, faulty = (np.asarray(_leaf(t, leaf)) for t in (grads, ref, bad))
    scale = float(np.abs(sound).max())
    assert np.abs(got - sound).max() < 2e-4 * scale
    assert np.abs(got - faulty).max() > 100 * np.abs(got - sound).max(), fault


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer(params):
    """One window layer, the router's eight experts all drawn: four shares
    of two experts each through the program's layer (attention and the
    router alike on every chip; no shared expert) equal the reference's
    layer with all eight."""
    p = jax.tree.map(lambda a: a[0, 0], params["periods"][WINDOW])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, L, 64), jnp.float32)
    share = lambda first: {**p, **{n: p[n][first:first + 2] for n in ("w_gate", "w_up", "w_down")}}

    @jax.jit
    def parts(p, x):
        h, routing = smallthinker._attention_sublayer(p, x, _cfg(), WINDOW)
        routed = [smallthinker._experts_sublayer(share(first), h, routing,
                                                 _cfg(experts_held=(first, 2)))[0] - h
                  for first in range(0, 8, 2)]
        return h + sum(routed), smallthinker._experts_sublayer(p, h, routing, _cfg())[0]

    with jax.default_matmul_precision("highest"):
        added, whole = parts(p, x)
    uncut = reference_smallthinker.layer_fn(p, x, reference_smallthinker.knobs(), SHAPE, WINDOW)
    np.testing.assert_allclose(added, uncut, atol=5e-5)
    np.testing.assert_allclose(whole, uncut, atol=5e-5)


def test_a_window_layer_reads_only_its_window_and_the_full_one_reads_all_before(params):
    """A token changed at position 20: a window layer's attention moves at
    20 .. 27 alone, the full one's from 20 on; neither before 20."""
    x = jax.random.normal(jax.random.PRNGKey(5), (1, L, 64), jnp.float32)
    moved = x.at[:, 20].add(1.0)
    for kind, seen in ((WINDOW, range(20, 28)), (FULL, range(20, L))):
        lp = jax.tree.map(lambda a: a[0, 0], params["periods"][kind])
        a = smallthinker._attention(lp, x, _cfg(), kind)
        b = smallthinker._attention(lp, moved, _cfg(), kind)
        diff = np.asarray(jnp.abs(a - b).max(axis=(0, 2)))
        assert set(np.nonzero(diff > 1e-6)[0]) == set(seen), kind


def test_the_router_reads_the_normed_input_before_attention(params):
    """In the attention sublayer the router's span comes before the
    projections', and the routing is a function of ``RMSNorm_1(x)``: the
    same whatever the attention's weights."""
    p = jax.tree.map(lambda a: a[0, 0], params["periods"][WINDOW])
    x = jax.random.normal(jax.random.PRNGKey(7), (1, L, 64), jnp.float32)
    sub = lambda p, x: smallthinker._attention_sublayer(p, x, _cfg(), WINDOW)
    names = [str(e.source_info.name_stack) for e in jax.make_jaxpr(sub)(p, x).eqns]
    first = lambda scope: next(i for i, n in enumerate(names) if scope in n)
    assert first("moe/route") < first("swa/qkv")
    _, (idx, weight) = sub(p, x)
    _, (idx2, weight2) = sub({**p, "wq": 2 * p["wq"], "wo": -p["wo"]}, x)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_array_equal(weight, weight2)
    np.testing.assert_allclose(weight.sum(-1), 1.0, rtol=1e-6)


def test_the_published_widths_hold_656_529_920_parameters():
    cfg = SmallThinkerConfig()
    assert cfg.period == (FULL, WINDOW, WINDOW, WINDOW)
    shapes = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 656_529_920
    assert layer_shapes(cfg)["wq"] == (2560, 28 * 128) and layer_shapes(cfg)["wk"] == (2560, 512)
    assert layer_shapes(cfg)["router"] == (2560, 64)
    assert shapes["periods"][WINDOW]["w_gate"].shape == (1, 3, 16, 2560, 768)
    assert shapes["periods"][FULL]["w_down"].shape == (1, 1, 16, 768, 2560)
    # the benchmark's weights have the program's leaves
    import json
    with open(os.path.join(_PERFBENCH, "configs", "smallthinker-21b-a3b.json")) as f:
        published = json.load(f)
    made = jax.eval_shape(lambda: weights_smallthinker._make(0, 0, config=published))
    assert jax.tree.map(lambda a: a.shape, made) == jax.tree.map(lambda a: a.shape, shapes)


def test_what_the_config_refuses():
    with pytest.raises(ValueError, match="not a range"):
        _cfg(experts_held=(6, 3))
    with pytest.raises(ValueError, match="known"):
        _cfg(layer_types=(FULL, "linear"))
    with pytest.raises(ValueError, match="divide"):
        _cfg(num_kv_heads=3)


def test_three_steps_through_the_train_step_with_the_counters_fourth_and_the_scopes(params):
    """The loss falls on a repeated batch; the step's fourth result is each
    layer's loads and the positions by places held; the lowered step
    carries the contract's scopes, the router under ``layer/moe/route``."""
    cfg = _cfg(experts_held=(2, 3))
    held = {**FILE, "moe_num_primary_experts": 3, "experts_held": [2, 3]}
    tok, tgt = _batch(rows=2)
    mesh = build_mesh(tp=1, pp=1, sp=1, dp=1, devices=jax.devices()[:1])
    step, opt = train_step_fn(cfg, mesh)
    p = weights_smallthinker.make_params(held, SEED)
    state, losses = opt.init(p), []
    for _ in range(3):
        p, state, loss, counters = step(p, state, tok, tgt)
        losses.append(float(loss))
    assert losses[2] < losses[1] < losses[0]
    counters = jax.device_get(counters)
    assert counters["expert_loads"].shape == (1, 4, 3) and counters["held_places"].shape == (1, 4, 4)
    assert (counters["held_places"].sum(-1) == 2 * L).all()
    assert 0 < counters["expert_loads"].sum() < 4 * 2 * L * 3
    text = step.lower(*abstract_train_args(cfg, opt, mesh, 2, L)).as_text(debug_info=True)
    for scope in ("embed", "layer/pre_norm", "layer/moe/route", "layer/attn/qkv",
                  "layer/attn/core", "layer/attn/out", "layer/swa/qkv", "layer/swa/rope",
                  "layer/swa/core", "layer/swa/out", "layer/moe/dispatch", "layer/moe/experts",
                  "layer/moe/combine", "layer/residual", "final_norm", "lm_head_loss", "opt"):
        assert scope + "/" in text or scope + '"' in text, scope
    for absent in ("layer/attn/rope", "layer/attn/qk_norm", "layer/swa/gate", "layer/shared"):
        assert absent not in text, absent
