"""The block-diffusion decoder (``transformer/sdar.py``) at toy size: what it
feeds the stack, the ways of replaying it, data parallelism, the refusal of
tensor parallelism, the train step and its scopes. The comparison with the
plain reference is ``tests/perfbench/test_perfbench_sdar.py``'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.monitor import trace as monitor_trace
from apex_tpu.parallel.mesh import build_mesh
from apex_tpu.train import abstract_train_args, train_step_fn
from apex_tpu.transformer.sdar import (
    LAYER,
    RATE_BITS,
    SDARConfig,
    layer_shapes,
    noised_batch,
    sdar_logits,
    sdar_loss,
)

VOCAB, L = 256, 64


def _cfg(**kw):
    base = dict(vocab_held=VOCAB, hidden=64, num_layers=2, num_heads=4, num_kv_heads=2,
                head_dim=16, num_experts=8, experts_held=(2, 3), top_k=2, expert_hidden=32,
                block=4, mask_id=VOCAB - 1, dtype=jnp.float32)
    return SDARConfig(**{**base, **kw})


def _params(cfg, seed=0):
    p = cfg.init_params(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    p = jax.tree.map(lambda a: a + 0.05 * jax.random.normal(next(keys), a.shape, a.dtype), p)
    p["embed"]["tok"] = p["embed"]["tok"] * 20.0    # rows that differ, so routing spreads
    return p


def _batch(rows=2, length=L, seed=1, block=4):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, VOCAB - 1, (rows, length), dtype=np.int32)
    n = np.repeat(rng.integers(1049, 2 ** RATE_BITS + 1, (rows, length // block)), block, 1)
    masked = rng.random((rows, length)) < n / 2.0 ** RATE_BITS
    return jnp.asarray(tokens), jnp.asarray((2 * n + masked).astype(np.int32))


def test_the_model_reads_the_noised_copy_then_the_clean_one():
    cfg = _cfg()
    tokens, noise = _batch()
    seq2, weight = noised_batch(tokens, noise, cfg)
    masked = np.asarray(noise) % 2 == 1
    assert seq2.shape == (2, 2 * L)
    np.testing.assert_array_equal(seq2[:, L:], tokens)
    np.testing.assert_array_equal(np.asarray(seq2[:, :L])[masked], cfg.mask_id)
    np.testing.assert_array_equal(np.asarray(seq2[:, :L])[~masked], np.asarray(tokens)[~masked])
    t = (np.asarray(noise) // 2) / 2.0 ** RATE_BITS
    np.testing.assert_allclose(weight, np.where(masked, 1.0 / t, 0.0), rtol=1e-6)
    assert 0.001 <= t.min() and t.max() <= 1.0


def test_a_clean_token_never_reads_the_noised_copy_and_a_block_not_its_future():
    """Changing the noise of block 5 leaves the logits of blocks 0-4 as they
    were; changing a data token of block 5 leaves blocks 0-4 too."""
    cfg = _cfg()
    params = _params(cfg)
    tokens, noise = _batch()
    base = sdar_logits(params, tokens, noise, cfg)
    other_noise = noise.at[:, 20:24].set(noise[:, 20:24] ^ 1)
    np.testing.assert_allclose(sdar_logits(params, tokens, other_noise, cfg)[:, :20],
                               base[:, :20], atol=1e-5)
    other_tokens = tokens.at[:, 20:24].set((tokens[:, 20:24] + 1) % (VOCAB - 1))
    moved = sdar_logits(params, other_tokens, noise, cfg)
    np.testing.assert_allclose(moved[:, :20], base[:, :20], atol=1e-5)
    assert float(jnp.abs(moved[:, 24:] - base[:, 24:]).max()) > 1e-4


def test_the_loss_is_the_weighted_cross_entropy_of_the_masked_positions():
    cfg = _cfg()
    params = _params(cfg)
    tokens, noise = _batch()
    logits = sdar_logits(params, tokens, noise, cfg)
    _, weight = noised_batch(tokens, noise, cfg)
    ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    want = jnp.sum(weight * ce) / tokens.size
    assert abs(float(sdar_loss(params, tokens, noise, cfg)[0]) - float(want)) < 1e-4


@pytest.mark.parametrize("remat", ["none", "layer"])
def test_every_way_of_replaying_gives_the_same_loss_and_gradients(remat):
    tokens, noise = _batch()
    out = {}
    for how in ("sublayer", remat):
        cfg = _cfg(remat=how)
        out[how] = jax.value_and_grad(cfg.loss)(_params(cfg), tokens, noise)
    assert abs(float(out["sublayer"][0]) - float(out[remat][0])) < 1e-6
    for a, b in zip(jax.tree.leaves(out["sublayer"][1]), jax.tree.leaves(out[remat][1])):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_every_leaf_takes_a_gradient_and_the_layers_are_stacked_over_periods():
    cfg = _cfg()
    params = _params(cfg)
    tokens, noise = _batch()
    grads = jax.grad(cfg.loss)(params, tokens, noise)
    for name, shape in layer_shapes(cfg).items():
        leaf = grads["periods"][LAYER][name]
        assert leaf.shape == (cfg.num_layers, 1) + shape
        for layer in range(cfg.num_layers):
            assert float(jnp.abs(leaf[layer]).max()) > 0, (name, layer)
    assert layer_shapes(cfg)["w_gate"] == (3, 64, 32) and layer_shapes(cfg)["router"] == (64, 8)
    # the mask id's row of the embedding is read (and the head never scores it as a target)
    assert float(jnp.abs(grads["embed"]["tok"][cfg.mask_id]).max()) > 0


def test_the_loads_are_counted_by_the_layers_that_ran():
    """The loss's second result: each layer's row is what its own router
    chose among the experts held (here from the layer's input, by hand), some
    pairs on experts that are not held, and every way of replaying counts
    alike."""
    from apex_tpu.transformer import sdar
    from apex_tpu.transformer.moe import route_softmax_top_k
    cfg = _cfg()
    params = _params(cfg)
    tokens, noise = _batch()
    loads = sdar_loss(params, tokens, noise, cfg)[1]["expert_loads"]
    assert loads.shape == (cfg.num_layers, cfg.experts_held[1]) and loads.dtype == jnp.int32
    x = jnp.take(params["embed"]["tok"], noised_batch(tokens, noise, cfg)[0], axis=0)
    first, count = cfg.experts_held
    for layer in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[layer, 0], params["periods"][LAYER])
        h = sdar._attention_sublayer(lp, x, cfg)
        m = sdar.rms_norm(h, lp["norm2"], cfg.norm_eps).reshape(-1, cfg.hidden)
        idx = np.asarray(route_softmax_top_k(m, lp["router"], cfg.top_k)[0])
        want = np.bincount(idx.reshape(-1), minlength=cfg.num_experts)[first:first + count]
        np.testing.assert_array_equal(loads[layer], want)
        assert 0 < want.sum() < idx.size                     # experts that are not held, too
        x, _ = sdar._experts_sublayer(lp, h, cfg)
    for remat in ("none", "layer"):
        np.testing.assert_array_equal(
            sdar_loss(params, tokens, noise, _cfg(remat=remat))[1]["expert_loads"], loads)


def test_what_the_config_refuses():
    with pytest.raises(ValueError, match="not a range"):
        _cfg(experts_held=(6, 3))
    with pytest.raises(ValueError, match="num_kv_heads"):
        _cfg(num_kv_heads=3)
    with pytest.raises(ValueError, match="mask_id"):
        _cfg(mask_id=VOCAB)
    with pytest.raises(ValueError, match="remat"):
        _cfg(remat="full")


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


def test_data_parallel_step_equals_the_one_device_step():
    cfg = _cfg()
    tokens, noise = _batch(rows=4)
    out = {}
    for dp in (1, 2):
        step, opt, _ = _step(cfg, dp=dp)
        params = _params(cfg)
        new, _, loss, counters = step(params, opt.init(params), tokens, noise)
        out[dp] = (float(loss), new, np.asarray(counters["expert_loads"]))
    assert abs(out[1][0] - out[2][0]) < 1e-5
    # the step's fourth result: each shard's loads, stacked over dp
    assert out[1][2].shape == (1, cfg.num_layers, 3) and out[2][2].shape == (2, cfg.num_layers, 3)
    np.testing.assert_array_equal(out[1][2][0], out[2][2].sum(0))
    for a, b in zip(jax.tree.leaves(out[1][1]), jax.tree.leaves(out[2][1])):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_train_step_carries_the_contracts_scopes():
    cfg = _cfg()
    step, opt, mesh = _step(cfg)
    assert "jit_train_step" in monitor_trace._PROGRAMS
    text = step.lower(*abstract_train_args(cfg, opt, mesh, 2, L)).as_text(debug_info=True)
    for scope in ("noise", "embed", "layer/pre_norm", "layer/attn/qkv", "layer/attn/qk_norm",
                  "layer/attn/rope", "layer/attn/core", "layer/attn/out", "layer/moe/route",
                  "layer/moe/dispatch", "layer/moe/experts", "layer/moe/combine",
                  "layer/residual", "final_norm", "lm_head_loss", "opt"):
        assert scope + "/" in text or scope + '"' in text, scope
