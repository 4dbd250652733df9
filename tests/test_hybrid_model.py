"""The hybrid decoder (``transformer/hybrid.py``: gated-delta-rule layers
among full ones) at toy size against the plain reference
(``testing/hybrid_reference.py``: token by token, float32): the loss and every
leaf's gradient, the share of the vocabulary, data parallelism, and the
refusal of tensor parallelism. Both model families through the one train
step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor import trace as monitor_trace
from apex_tpu.parallel.mesh import build_mesh
from apex_tpu.train import abstract_train_args, train_step_fn
from apex_tpu.transformer.hybrid import (
    FULL,
    LINEAR,
    HybridConfig,
    hybrid_logits,
    hybrid_loss,
    layer_shapes,
)
from apex_tpu.transformer.testing import GPTConfig, hybrid_reference

VOCAB, SEQ = 256, 128


def _cfg(**kw):
    base = dict(vocab_held=VOCAB, hidden=64, ffn_hidden=128, num_heads=2, head_dim=32,
                linear_heads=2, linear_key_dim=16, linear_value_dim=32, chunk=16,
                dtype=jnp.float32)
    return HybridConfig(**{**base, **kw})


def _params(cfg, seed=0):
    """The program's own initialisation, with noise on what it starts at one
    (norm weights) so that a fault in how they are used shows."""
    p = cfg.init_params(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    return jax.tree.map(lambda a: a + 0.05 * jax.random.normal(next(keys), a.shape, a.dtype), p)


def _batch(rows=2, seq=SEQ, seed=1, vocab=VOCAB):
    x = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, vocab)
    return x[:, :-1], x[:, 1:]


def _leaf_names(cfg):
    names = ["embed.tok", "head.norm", "head.lm"]
    for kind in dict.fromkeys(cfg.period):
        names += [f"periods.{kind}.{name}" for name in layer_shapes(cfg, kind)]
    return names


LEAVES = _leaf_names(_cfg())


@pytest.fixture(scope="module", params=[16, 64], ids=["chunk16", "chunk64"])
def both(request):
    """(loss, gradients) of the program and of the reference, one period,
    sequence 128."""
    cfg = _cfg(chunk=request.param)
    params, (tok, tgt) = _params(cfg), _batch()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(lambda p: hybrid_loss(p, tok, tgt, cfg)))(params)
    want = jax.jit(lambda p: hybrid_reference.loss_and_grad(
        p, tok, tgt, hybrid_reference.model_shape(cfg)))(params)
    return got, want


def test_loss_equals_the_references(both):
    (loss, _), (want, _) = both
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_equals_the_references(both, leaf):
    (_, got), (_, want) = both
    for key in leaf.split("."):
        got, want = got[key], want[key]
    assert got.shape == want.shape and float(jnp.linalg.norm(want)) > 0
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 5e-5, (leaf, err)


def test_layers_are_stacked_by_kind_over_periods_and_a_second_period_counts():
    cfg = _cfg(layer_types=(LINEAR, FULL) * 2)
    assert cfg.period == (LINEAR, FULL) and cfg.num_periods == 2
    params = _params(cfg)
    assert params["periods"][LINEAR]["wq"].shape == (2, 1, 64, 32)
    assert params["periods"][FULL]["wq"].shape == (2, 1, 64, 64)
    tok, tgt = _batch(seq=64)
    with jax.default_matmul_precision("highest"):
        got = hybrid_loss(params, tok, tgt, cfg)
    want = hybrid_reference.loss_fn(params, tok, tgt, hybrid_reference.model_shape(cfg))
    assert abs(float(got) - float(want)) < 2e-5 * float(want)
    assert _cfg().period == (LINEAR, LINEAR, LINEAR, FULL) and _cfg().num_periods == 1


def test_without_remat_the_loss_and_the_gradients_are_the_same():
    params, (tok, tgt) = _params(_cfg()), _batch(seq=64)
    run = lambda remat: jax.jit(jax.value_and_grad(
        lambda p: hybrid_loss(p, tok, tgt, _cfg(remat=remat))))(params)
    (want, want_g), (got, got_g) = run(True), run(False)
    assert abs(float(got) - float(want)) < 1e-6 * float(want)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(b))


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused_by_the_model():
    cfg = _cfg(chunk=64)
    tok, tgt = _batch(seq=100)
    with pytest.raises(ValueError, match="not a multiple of the chunk"):
        hybrid_loss(_params(cfg), tok, tgt, cfg)


def test_each_eighth_of_the_head_gives_its_columns_of_the_uncut_logits():
    """The vocabulary's share tied to the model: eight programs that each hold
    32 rows of a 256-row head give, side by side, the uncut reference's
    logits; and the rows a share holds of the embedding give the uncut
    lookup for the ids in its range."""
    cfg = _cfg()
    params, (tok, _) = _params(cfg), _batch()
    whole = hybrid_reference.logits_fn(params, tok, hybrid_reference.model_shape(cfg))
    share_cfg = _cfg(vocab_held=VOCAB // 8)
    parts = []
    with jax.default_matmul_precision("highest"):
        for i in range(8):
            rows = slice(32 * i, 32 * (i + 1))
            share = {**params, "head": {"norm": params["head"]["norm"],
                                        "lm": params["head"]["lm"][rows]}}
            parts.append(hybrid_logits(share, tok, share_cfg))
            assert parts[-1].shape == (2, SEQ, 32)
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), whole, atol=2e-5, rtol=2e-5)
    table = params["embed"]["tok"]
    looked_up = sum(jnp.where(((tok >= 32 * i) & (tok < 32 * (i + 1)))[..., None],
                              jnp.take(table[32 * i:32 * (i + 1)], jnp.clip(tok - 32 * i, 0, 31),
                                       axis=0), 0.0) for i in range(8))
    np.testing.assert_array_equal(looked_up, jnp.take(table, tok, axis=0))


def _step(cfg, **mesh_kw):
    n = int(np.prod(list(mesh_kw.values())))
    mesh = build_mesh(**{"tp": 1, "pp": 1, "sp": 1, "dp": 1, **mesh_kw},
                      devices=jax.devices()[:n])
    return (*train_step_fn(cfg, mesh), mesh)


def test_tensor_parallelism_is_refused_with_a_message():
    step, opt, mesh = _step(_cfg(), tp=2)
    args = abstract_train_args(_cfg(), opt, mesh, 2, SEQ)
    with pytest.raises(NotImplementedError, match=r"not written for tensor parallelism \(tp = 2\)"):
        step.lower(*args)


def test_data_parallel_step_equals_the_one_device_step():
    cfg = _cfg()
    tok, tgt = _batch(rows=4)
    out = {}
    for dp in (1, 2):
        step, opt, _ = _step(cfg, dp=dp)
        params = _params(cfg)
        new, _, loss = step(params, opt.init(params), tok, tgt)
        out[dp] = (float(loss), new)
    assert abs(out[1][0] - out[2][0]) < 1e-5
    for a, b in zip(jax.tree.leaves(out[1][1]), jax.tree.leaves(out[2][1])):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("cfg", [
    _cfg(),
    GPTConfig(vocab_size=256, max_seq=SEQ, hidden=64, num_layers=2, num_heads=2,
              dtype=jnp.float32),
], ids=["hybrid", "gpt"])
def test_both_families_run_the_one_train_step_registered_as_jit_train_step(cfg):
    """``apex_tpu.train.train_step_fn`` takes a model by three methods; the step it
    returns is the one ``monitor.trace`` knows as ``jit_train_step``."""
    for method in ("param_specs", "init_params", "loss"):
        assert callable(getattr(cfg, method))
    specs = cfg.param_specs()
    params = cfg.init_params(jax.random.PRNGKey(0))
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(s, P)) == jax.tree.structure(params)
    step, opt, _ = _step(cfg)
    tok, tgt = _batch()
    _, _, first = step(params, opt.init(params), tok, tgt)
    assert np.isfinite(float(first))
    table = monitor_trace.scope_table("jit_train_step", rows=2, seq=SEQ)
    scopes = {monitor_trace.split_scope(rec["op_name"])[1] for rec in table.values()}
    want = ({"layer/linattn/core", "layer/linattn/conv", "layer/attn/qk_norm", "layer/mlp/gate_up",
             "layer/post_norm", "final_norm", "lm_head_loss", "opt"} if isinstance(cfg, HybridConfig)
            else {"layer/attn/qkv", "layer/mlp/fc", "final_ln", "lm_head_loss", "opt"})
    assert want <= scopes, want - scopes
