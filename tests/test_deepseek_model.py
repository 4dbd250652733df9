"""DeepSeek-V2's decoder (``transformer/deepseek.py``) at toy size: hidden 64,
4 heads of 16 + 8 over values of 16, rank 32, one dense layer and two expert
layers, 8 routed experts, 3 a position, a shared expert. The loss and every
leaf's gradient against the benchmark's plain reference
(``perfbench/reference_dsv2.py``) in float32; the four chips' shares of an
expert layer add up to the uncut reference's; weights as scored; the balance
loss; the ways of replaying; the train step with its counters fourth; its
scopes."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench")
if _PERFBENCH not in sys.path:
    sys.path.insert(0, _PERFBENCH)

import reference_dsv2  # noqa: E402
import weights_dsv2  # noqa: E402

from apex_tpu.monitor import trace as monitor_trace  # noqa: E402
from apex_tpu.ops.rope import RopeScaling  # noqa: E402
from apex_tpu.parallel.mesh import build_mesh  # noqa: E402
from apex_tpu.train import abstract_train_args, train_step_fn  # noqa: E402
from apex_tpu.transformer import deepseek  # noqa: E402
from apex_tpu.transformer.deepseek import (  # noqa: E402
    DENSE,
    EXPERTS,
    DeepSeekConfig,
    deepseek_logits,
    deepseek_loss,
    layer_shapes,
)

VOCAB, L, SEED = 256, 128, 2**31 + 35
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 32, "type": "yarn"}
# a configuration file's dict at the toy widths: what the reference and the
# benchmark's weights read
FILE = {"hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "kv_lora_rank": 32, "rope_theta": 10000, "rope_scaling": YARN,
        "rms_norm_eps": 1e-6, "intermediate_size": 96, "moe_intermediate_size": 32,
        "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 3,
        "first_k_dense_replace": 1, "num_hidden_layers": 3, "vocab_size": VOCAB,
        "norm_topk_prob": False, "routed_scaling_factor": 1.0, "experts_held": [0, 8],
        "reduced_from": {"num_hidden_layers": 27, "n_routed_experts": 8, "vocab_size": VOCAB},
        "assumed": {"aux_loss_alpha": 0.001, "param_dtype": "float32", "embedding_std": 1.0}}


def _cfg(**kw):
    base = dict(vocab_held=VOCAB, hidden=64, num_layers=3, first_k_dense=1, num_heads=4,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                rope_scaling=RopeScaling.from_config(YARN), dense_hidden=96, num_experts=8,
                experts_held=(0, 8), top_k=3, expert_hidden=32, shared_hidden=64,
                dtype=jnp.float32)
    return DeepSeekConfig(**{**base, **kw})


def _file(**kw):
    return {**FILE, **kw}


def _batch(rows=2, seed=1):
    x = np.random.default_rng(seed).integers(0, VOCAB, (rows, L + 1), dtype=np.int32)
    return jnp.asarray(x[:, :-1]), jnp.asarray(x[:, 1:])


@pytest.fixture(scope="module")
def both():
    """(loss, gradients) of the program and of the reference on the
    benchmark's weights, float32 on both sides."""
    params = weights_dsv2.make_params(FILE, SEED)
    tok, tgt = _batch()
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(_cfg().loss)(params, tok, tgt)
    want = jax.value_and_grad(reference_dsv2.loss_fn)(
        params, tok, tgt, reference_dsv2.model_shape(FILE))
    return got, want


def test_the_programs_loss_equals_the_references(both):
    (loss, _), (ref_loss, _) = both
    assert abs(float(loss) - float(ref_loss)) < 2e-5 * abs(float(ref_loss))


LEAVES = (["embed.tok", "head.norm", "head.lm"]
          + [f"{DENSE}.{n}" for n in ("norm1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "norm2",
                                      "w_gate", "w_up", "w_down")]
          + [f"{EXPERTS}.{n}" for n in ("norm1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "norm2",
                                        "router", "w_gate", "w_up", "w_down", "shared_gate",
                                        "shared_up", "shared_down")])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_equals_the_references(both, leaf):
    (_, grads), (_, ref) = both
    group, name = leaf.split(".")
    at = lambda tree: tree["periods"][group][name] if group in (DENSE, EXPERTS) else tree[group][name]
    got, want = np.asarray(at(grads)), np.asarray(at(ref))
    assert got.shape == want.shape
    scale = float(np.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=2e-4 * scale)


def test_the_stepwise_reference_equals_its_one_piece_form(both):
    """``step_gradient`` (a layer at a time, a row at a time: the full-size
    path) against ``loss_fn`` differentiated whole."""
    _, (ref_loss, ref) = both
    params = weights_dsv2.make_params(FILE, SEED)
    loss, g = reference_dsv2.step_gradient(params, *_batch(), reference_dsv2.model_shape(FILE), 1)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(float(jnp.abs(b).max()), 1e-6))


def test_the_shares_of_four_chips_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One expert layer, the router's eight experts all drawn: four shares of
    two experts each through the program's routed layer, plus the shared
    expert once (every chip computes it alike), equal the reference's layer
    with all eight."""
    from apex_tpu.transformer.moe import routed_experts_mlp
    p = jax.tree.map(lambda a: a[0, 0].astype(jnp.float32),
                     weights_dsv2.make_params(FILE, SEED)["periods"][EXPERTS])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, L, 64), jnp.float32)
    model = lambda first: _cfg(experts_held=(first, 2))
    with jax.default_matmul_precision("highest"):
        a = deepseek._attention_sublayer(p, x, model(0))       # what every chip computes alike
        m = deepseek.rms_norm(a, p["norm2"], 1e-6)
        share = lambda first: {**p, **{n: p[n][first:first + 2]
                                       for n in ("w_gate", "w_up", "w_down")}}
        routed = [routed_experts_mlp(share(first), m, model(first).routed, (first, 2))[0]
                  for first in range(0, 8, 2)]
        shared = deepseek.gated_ffn(m, p["shared_gate"], p["shared_up"], p["shared_down"],
                                    "shared")
        # and one share's sublayer is its routed part plus the shared expert
        one = deepseek._experts_sublayer(share(0), a, model(0))[0]
    uncut, _ = reference_dsv2.layer_fn(p, x, reference_dsv2.model_shape(FILE), EXPERTS)
    np.testing.assert_allclose(a + sum(routed) + shared, uncut, atol=5e-5)
    np.testing.assert_allclose(one, a + routed[0] + shared, atol=5e-5)


@pytest.mark.parametrize("fault", reference_dsv2.FAULTS)
def test_each_planted_fault_moves_the_references_loss_or_gradient(fault):
    params = weights_dsv2.make_params(FILE, SEED)
    shape = reference_dsv2.model_shape(FILE)
    sound = jax.value_and_grad(reference_dsv2.loss_fn)(params, *_batch(), shape)
    bad = jax.value_and_grad(reference_dsv2.loss_fn)(params, *_batch(), shape, None, fault)
    gap = max(float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-12))
              for a, b in zip(jax.tree.leaves(sound[1]), jax.tree.leaves(bad[1])))
    assert gap > 1e-3, fault


def test_the_weights_are_as_scored_and_a_renormalising_program_differs():
    """A renormalising program equals the reference with that fault planted,
    and its experts' gradients are some three times the sound ones (three of
    eight scores sum to about a third)."""
    cfg = _cfg()
    assert cfg.routed.norm_topk_prob is False and cfg.routed.routed_scaling_factor == 1.0
    params = weights_dsv2.make_params(FILE, SEED)
    tok, tgt = _batch()
    down = lambda g: np.asarray(g["periods"][EXPERTS]["w_down"])
    with jax.default_matmul_precision("highest"):
        as_scored = down(jax.grad(cfg.loss)(params, tok, tgt))
        renormed = down(jax.grad(_cfg(norm_topk_prob=True).loss)(params, tok, tgt))
    want = down(jax.grad(reference_dsv2.loss_fn)(
        params, tok, tgt, reference_dsv2.model_shape(FILE), None, "renorm"))
    np.testing.assert_allclose(renormed, want, atol=2e-4 * np.abs(want).max())
    assert np.linalg.norm(renormed) > 1.5 * np.linalg.norm(as_scored)


def test_the_balance_loss_is_in_the_loss_and_its_gradient_reaches_the_router_alone():
    """loss(alpha) - loss(0) is the layers' balance losses summed, equal to
    what the step hands out; the difference of the gradients is nought on the
    experts and not on the routers."""
    params = weights_dsv2.make_params(FILE, SEED)
    tok, tgt = _batch()
    with jax.default_matmul_precision("highest"):
        (with_aux, counted), g1 = jax.value_and_grad(
            lambda p: deepseek_loss(p, tok, tgt, _cfg()), has_aux=True)(params)
        (without, _), g0 = jax.value_and_grad(
            lambda p: deepseek_loss(p, tok, tgt, _cfg(aux_loss_alpha=0.0)), has_aux=True)(params)
    aux = counted["aux_loss"]
    assert aux.shape == (2, 1) and aux.dtype == jnp.float32
    assert float(with_aux - without) == pytest.approx(float(aux.sum()), rel=1e-3)
    assert 0.0019 < float(aux.sum()) < 0.0030           # two layers near alpha each
    moved = lambda name: float(jnp.abs(g1["periods"][EXPERTS][name]
                                       - g0["periods"][EXPERTS][name]).max())
    assert moved("router") > 1e-7
    # the last layer's experts see the balance loss through no path
    last = lambda name: float(jnp.abs(g1["periods"][EXPERTS][name][-1]
                                      - g0["periods"][EXPERTS][name][-1]).max())
    assert last("w_down") == 0.0 and last("shared_down") == 0.0


def test_a_position_reads_no_later_one_and_yarn_is_in_the_logits():
    cfg = _cfg()
    params = weights_dsv2.make_params(FILE, SEED)
    tok, _ = _batch()
    base = deepseek_logits(params, tok, cfg)
    moved = deepseek_logits(params, tok.at[:, 40].set((tok[:, 40] + 1) % VOCAB), cfg)
    np.testing.assert_allclose(moved[:, :40], base[:, :40], atol=1e-5)
    assert float(jnp.abs(moved[:, 40:] - base[:, 40:]).max()) > 1e-4
    # YaRN's frequencies and its m^2 are in the mixer's output
    lp = jax.tree.map(lambda a: 10.0 * a[0, 0], params["periods"][EXPERTS])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, L, 64), jnp.float32)
    yarn, plain = (deepseek._attention(lp, x, c) for c in (cfg, _cfg(rope_scaling=None)))
    assert float(jnp.abs(yarn - plain).max()) > 1e-2 * float(jnp.abs(yarn).max())
    assert cfg.softmax_scale == pytest.approx(24 ** -0.5 * 1.26080 ** 2, rel=1e-5)
    assert DeepSeekConfig().softmax_scale == pytest.approx(0.114721, abs=5e-7)


def test_replaying_by_sublayer_gives_the_loss_and_gradients_of_keeping_all():
    params = weights_dsv2.make_params(FILE, SEED)
    tok, tgt = _batch()
    out = {how: jax.value_and_grad(_cfg(remat=how).loss)(params, tok, tgt)
           for how in ("sublayer", "none")}
    assert abs(float(out["sublayer"][0]) - float(out["none"][0])) < 1e-6
    for a, b in zip(jax.tree.leaves(out["sublayer"][1]), jax.tree.leaves(out["none"][1])):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_the_published_widths_hold_864_313_856_parameters():
    cfg = DeepSeekConfig()
    shapes = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 864_313_856
    assert layer_shapes(cfg, DENSE)["wq"] == (2048, 16 * 192)
    assert layer_shapes(cfg, EXPERTS)["wkv_b"] == (512, 16 * 256)
    assert layer_shapes(cfg, EXPERTS)["router"] == (2048, 64)
    assert layer_shapes(cfg, EXPERTS)["w_gate"] == (16, 2048, 1408)
    assert shapes["periods"][EXPERTS]["shared_up"].shape == (4, 1, 2048, 2816)
    assert shapes["periods"][DENSE]["w_down"].shape == (1, 1, 10944, 2048)


def test_what_the_config_refuses():
    with pytest.raises(ValueError, match="not a range"):
        _cfg(experts_held=(6, 3))
    with pytest.raises(ValueError, match="expert layer"):
        _cfg(first_k_dense=3)
    for remat in ("full", "layer"):
        with pytest.raises(ValueError, match="remat"):
            _cfg(remat=remat)


def _step(cfg, **mesh_kw):
    mesh_kw = {"tp": 1, "pp": 1, "sp": 1, "dp": 1, **mesh_kw}
    n = mesh_kw["tp"] * mesh_kw["dp"]
    mesh = build_mesh(devices=jax.devices()[:n], **mesh_kw)
    return (*train_step_fn(cfg, mesh), mesh)


def test_tensor_parallelism_is_refused_with_a_message():
    step, opt, mesh = _step(_cfg(), tp=2)
    args = abstract_train_args(_cfg(), opt, mesh, 2, L)
    with pytest.raises(NotImplementedError, match=r"not written for tensor parallelism \(tp = 2\)"):
        step.lower(*args)


def test_three_steps_through_the_train_step_with_the_counters_fourth():
    """The loss falls on a repeated batch; the step's fourth result is each
    expert layer's loads (what its own router chose among the experts held),
    the positions by places held and the layer's balance loss, stacked over
    dp; two data-parallel shards give the one-device step."""
    cfg = _cfg(experts_held=(2, 3))
    held = _file(n_routed_experts=3, experts_held=[2, 3])
    tok, tgt = _batch(rows=4)
    out = {}
    for dp in (1, 2):
        step, opt, _ = _step(cfg, dp=dp)
        params = weights_dsv2.make_params(held, SEED)
        state, losses = opt.init(params), []
        for _ in range(3):
            params, state, loss, counters = step(params, state, tok, tgt)
            losses.append(float(loss))
        out[dp] = (losses, params, jax.device_get(counters))
    losses, _, counters = out[1]
    assert losses[2] < losses[1] < losses[0]
    assert counters["expert_loads"].shape == (1, 2, 3) and counters["held_places"].shape == (1, 2, 4)
    assert counters["aux_loss"].shape == (1, 2, 1)
    assert (counters["held_places"].sum(-1) == 4 * L).all()
    assert 0 < counters["expert_loads"].sum() < 2 * 4 * L * 3      # experts that are not held, too
    np.testing.assert_allclose(out[2][0], losses, atol=1e-5)
    assert out[2][2]["expert_loads"].shape == (2, 2, 3)
    np.testing.assert_array_equal(out[2][2]["expert_loads"].sum(0), counters["expert_loads"][0])
    for a, b in zip(jax.tree.leaves(out[1][1]), jax.tree.leaves(out[2][1])):
        np.testing.assert_allclose(a, b, atol=2e-5)


SCOPES = ("embed", "layer/pre_norm", "layer/attn/q_proj", "layer/attn/kv_down",
          "layer/attn/kv_norm", "layer/attn/kv_up", "layer/attn/rope", "layer/attn/core",
          "layer/attn/out", "layer/mlp/gate_up", "layer/mlp/act", "layer/mlp/down",
          "layer/shared/gate_up", "layer/shared/act", "layer/shared/down", "layer/moe/route",
          "layer/moe/dispatch", "layer/moe/experts", "layer/moe/combine", "layer/aux_loss",
          "layer/residual", "final_norm", "lm_head_loss", "opt")


def test_the_train_step_carries_the_contracts_scopes():
    cfg = _cfg()
    step, opt, mesh = _step(cfg)
    assert "jit_train_step" in monitor_trace._PROGRAMS
    text = step.lower(*abstract_train_args(cfg, opt, mesh, 2, L)).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope + "/" in text or scope + '"' in text, scope
    # and the compiled step's table files instructions under them
    table = monitor_trace.scope_table("jit_train_step", rows=2, seq=L)
    seen = {monitor_trace.split_scope(rec["op_name"])[1] for rec in table.values()}
    for scope in ("layer/attn/kv_up", "layer/shared/gate_up", "layer/mlp/down", "layer/moe/route",
                  "layer/aux_loss", "opt"):
        assert any(s == scope or s.startswith(scope + "/") for s in seen), scope
    # the shared expert is no part of layer/moe: the accepted readers file an
    # unknown part of it under dispatch
    assert not any(s.startswith("layer/moe/shared") for s in seen)
