"""FSDP (ZeRO-3) + ParallelismPlan acceptance suite.

Gates: (1) the ParallelismPlan refuses bad axis names / indivisible
shapes / nonsense compositions at CONSTRUCTION; (2) the modeled
``hbm_params_bytes`` accounting shows the acceptance drop (≥1.8× vs the
DDP leg of the DDP+ZeRO-1 baseline at dp=2 on the GPT example — exactly
2.0× — and ≥1.8× vs the ZeRO-1 leg from dp=4 up; the replicated-params
term is what FSDP deletes, so the ZeRO-1 ratio grows with dp); (3)
on the 8-device virtual mesh:
FSDP == DDP+FusedAdam loss-curve parity over ≥5 GPT steps at dp=2
(measured BITWISE on the sim; asserted to 1e-5), the int8 weight-gather
codec within codec tolerance, a mid-run checkpoint save/restore
round-trip rejoining the curve exactly, and the compiled tp/fsdp
program's forward gather ring proven ≥0.5 hidden from its HLO
(``accounting.overlap_report`` — the PR-4 flagship contract in FSDP
position); (4) the sharded-checkpoint manifest path saves local shards
and refuses dp-degree / shard-shape skew.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.comm import CompressionConfig
from apex_tpu.fsdp import (
    FSDP,
    FSDPAdam,
    FSDPAdamState,
    LeafMeta,
    fsdp_step_wire_bytes,
    hbm_params_bytes,
    hbm_reduction,
    param_gather_wire_bytes,
)
from apex_tpu.parallel import ParallelismPlan
from apex_tpu.parallel.mesh import build_mesh


# ---------------------------------------------------------------------------
# ParallelismPlan validation (stock-safe): bad plans die at construction


def test_plan_presets_construct():
    for name in ("ddp", "zero1", "fsdp", "fsdp+tp"):
        plan = ParallelismPlan.preset(name)
        desc = plan.describe()
        assert plan.data in desc and "mesh:" in desc
    assert ParallelismPlan.preset("fsdp+tp").tp == 2
    assert ParallelismPlan.preset("fsdp+tp").overlap_comm


@pytest.mark.parametrize("bad", [
    dict(data="zzz"),
    dict(optimizer="sgd"),
    dict(dp_axis="rows"),  # not in the mesh vocabulary
    dict(tp=0),
    dict(pp=-2),
    dict(dp=0),
    dict(data="ddp", weight_gather=CompressionConfig("int8")),
    dict(data="fsdp", e5m2_allgather=True),
    dict(data="fsdp", optimizer="lamb"),
    dict(data="fsdp", compression=CompressionConfig("int8_ef")),
    dict(data="fsdp",
         weight_gather=CompressionConfig("int8", stochastic_rounding=True)),
    dict(fused_update="sometimes"),
])
def test_plan_refuses_bad_construction(bad):
    with pytest.raises(ValueError):
        ParallelismPlan(**bad)


def test_plan_refuses_unknown_preset():
    with pytest.raises(ValueError, match="preset"):
        ParallelismPlan.preset("fsdp+pp")


def test_plan_mesh_indivisible_fails_loudly():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="divisible"):
        ParallelismPlan.preset("fsdp", tp=n + 1).mesh()


def test_plan_component_cross_checks():
    with pytest.raises(ValueError, match="reduce-scatter"):
        ParallelismPlan.preset("fsdp").ddp()
    with pytest.raises(ValueError, match="not fsdp"):
        ParallelismPlan.preset("ddp").fsdp()


def test_plan_builds_the_right_optimizer():
    from apex_tpu.contrib.optimizers import (
        DistributedFusedAdam,
        DistributedFusedLAMB,
    )

    assert isinstance(ParallelismPlan.preset("zero1").build_optimizer(),
                      DistributedFusedAdam)
    assert isinstance(
        ParallelismPlan.preset("zero1", optimizer="lamb").build_optimizer(),
        DistributedFusedLAMB)
    assert isinstance(ParallelismPlan.preset("fsdp").build_optimizer(),
                      FSDPAdam)


def test_fsdp_engine_refuses_stateful_codecs():
    with pytest.raises(ValueError, match="error feedback"):
        FSDP(compression=CompressionConfig("int8_ef"))
    with pytest.raises(ValueError, match="stochastic"):
        FSDP(weight_gather=CompressionConfig(
            "int8", stochastic_rounding=True))


def test_fsdp_shard_multiple_is_lcm_of_codecs():
    f = FSDP(compression=CompressionConfig("int8", block_size=192),
             weight_gather=CompressionConfig("int8", block_size=256))
    assert f.shard_multiple == 768  # lcm(192, 256)
    assert FSDP().shard_multiple == 1


# ---------------------------------------------------------------------------
# the HBM acceptance accounting (stock-safe: pure shape arithmetic)


def _gpt_meta(dtype="float32"):
    """LeafMeta of the GPT example fixture (shapes only — no init)."""
    from apex_tpu.transformer.testing import GPTConfig

    cfg = GPTConfig(vocab_size=128, max_seq=32, hidden=64, num_layers=2,
                    num_heads=2, dtype=jnp.float32)
    h, f, L, v = cfg.hidden, cfg.ffn_hidden, cfg.num_layers, cfg.vocab_size
    leaf = lambda *s: LeafMeta(tuple(s), dtype)  # noqa: E731
    return {
        "embed": {"tok": leaf(v, h), "pos": leaf(cfg.max_seq, h)},
        "layers": {
            "ln1_w": leaf(L, h), "ln1_b": leaf(L, h),
            "qkv_kernel": leaf(L, h, 3 * h), "qkv_bias": leaf(L, 3 * h),
            "out_kernel": leaf(L, h, h), "out_bias": leaf(L, h),
            "ln2_w": leaf(L, h), "ln2_b": leaf(L, h),
            "fc1_kernel": leaf(L, h, f), "fc1_bias": leaf(L, f),
            "fc2_kernel": leaf(L, f, h), "fc2_bias": leaf(L, h),
        },
        "head": {"ln_w": leaf(h), "ln_b": leaf(h)},
    }


def test_hbm_drop_acceptance_gate():
    """THE acceptance assertion: per-chip param+grad+optimizer-state HBM
    for the GPT example at dp=2 drops ≥1.8× vs the DDP leg of the
    baseline pair (measured exactly 2.0×: fp32 params+grads+m+v replicated
    vs everything fp32 sharded), with the ZeRO-1 leg at 1.75× (its
    replicated params+grads are half the total at dp=2) crossing 1.8×
    from dp=4 (2.75×) and reaching 16.75× at dp=32."""
    meta = _gpt_meta()
    assert hbm_reduction(meta, world=2, baseline="ddp") >= 1.8
    assert abs(hbm_reduction(meta, world=2, baseline="ddp") - 2.0) < 1e-6
    z2 = hbm_reduction(meta, world=2, baseline="zero1")
    assert 1.7 <= z2 < 1.8  # honest: the zero1 win at dp=2 is 1.75x
    assert hbm_reduction(meta, world=4, baseline="zero1") >= 1.8
    assert hbm_reduction(meta, world=8, baseline="zero1") >= 2.7
    assert hbm_reduction(meta, world=32, baseline="zero1") >= 5.0


def test_hbm_breakdown_terms():
    meta = _gpt_meta()
    n = sum(m.size for m in jax.tree_util.tree_leaves(
        meta, is_leaf=lambda x: isinstance(x, LeafMeta)))
    ddp = hbm_params_bytes(meta, strategy="ddp", world=2)
    z = hbm_params_bytes(meta, strategy="zero1", world=2)
    f = hbm_params_bytes(meta, strategy="fsdp", world=2)
    # ddp fp32: params 4n + grads 4n + m+v 8n (no master at fp32)
    assert ddp["total"] == 16 * n
    # zero1 keeps replicated params+grads, shards the 12n fp32 state
    assert z["params_bytes"] == 4 * n and z["grads_bytes"] == 4 * n
    assert z["opt_state_bytes"] == pytest.approx(6 * n, rel=0.01)
    # fsdp: NO replicated params; state+grads all sharded
    assert f["params_bytes"] == 0
    assert f["total"] == pytest.approx(8 * n, rel=0.01)
    # the gather working set stays leaf-sized, not model-sized
    assert 0 < f["gather_workspace_bytes"] < 0.2 * ddp["total"]
    with pytest.raises(ValueError, match="strategy"):
        hbm_params_bytes(meta, strategy="zero3", world=2)


def test_plan_hbm_accounting_matches_module():
    meta = _gpt_meta()
    plan = ParallelismPlan.preset("fsdp")
    assert plan.hbm_params_bytes(meta, world=2) == hbm_params_bytes(
        meta, strategy="fsdp", world=2)


# ---------------------------------------------------------------------------
# wire-byte models (stock-safe)


def test_param_gather_ring_wire_byte_neutrality():
    """The fused ring moves EXACTLY the monolithic tiled all-gather's
    bytes: shard*(W-1) == full*(W-1)/W; backward adds the fp32 dW ring."""
    from apex_tpu.comm import (
        all_gather_wire_bytes,
        matmul_param_gather_wire_bytes,
    )

    shard, itemsize, w = 4096, 2, 8
    ring = matmul_param_gather_wire_bytes(shard, itemsize, w)
    mono = all_gather_wire_bytes(shard * w, itemsize, w)
    assert ring == mono == shard * itemsize * (w - 1)
    bwd = matmul_param_gather_wire_bytes(shard, itemsize, w, backward=True)
    assert bwd == ring + shard * 4 * (w - 1)
    assert matmul_param_gather_wire_bytes(shard, itemsize, 1) == 0.0


def test_fsdp_step_wire_model():
    meta = _gpt_meta()
    fp32 = fsdp_step_wire_bytes(meta, 8)
    int8 = fsdp_step_wire_bytes(
        meta, 8,
        compression=CompressionConfig("int8", min_elements=256),
        weight_gather=CompressionConfig("int8", min_elements=256),
        shard_multiple=256)
    assert 0 < int8 < fp32  # the codec must actually shrink the wire
    # remat replays the forward gather: one extra gather leg
    remat = fsdp_step_wire_bytes(meta, 8, remat_gathers=2)
    assert remat == fp32 + param_gather_wire_bytes(meta, 8)
    f = FSDP(weight_gather=CompressionConfig("int8", min_elements=256))
    assert f.gather_wire_bytes(meta, 8) < FSDP().gather_wire_bytes(meta, 8)


def test_regress_polarity_covers_fsdp_headliners():
    """The watch-stage gate actually covers the FSDP record: memory and
    wire growth regress, hidden_fraction/reduction shrink regress."""
    from apex_tpu.monitor.regress import classify_metric

    assert classify_metric("hbm_params_bytes_fsdp") == "lower"
    assert classify_metric("peak_hbm_bytes_zero1") == "lower"
    assert classify_metric("ring.exposed_bytes") == "lower"
    assert classify_metric("wire_bytes_fsdp") == "lower"
    assert classify_metric("step_ms_fsdp") == "lower"
    assert classify_metric("ring.hidden_fraction") == "higher"
    assert classify_metric("ring.hidden_bytes") == "higher"
    assert classify_metric("hbm_reduction_vs_zero1") == "higher"


# ---------------------------------------------------------------------------
# sharded-checkpoint manifest path (stock-safe: forced predicate on the
# single-process mesh, plus duck-typed fakes for the refusal ladder)


@pytest.fixture
def sharded_ckpt(monkeypatch, tmp_path):
    """Force the cross-process predicate for dp-sharded (64,) leaves so
    the per-shard path runs on this single-process mesh."""
    from apex_tpu.resilience import checkpoint as ck

    monkeypatch.setattr(
        ck, "_is_cross_process",
        lambda a: hasattr(a, "addressable_shards") and getattr(
            a, "shape", ()) == (64,))
    from jax.sharding import NamedSharding

    mesh = build_mesh(tp=1, pp=1, sp=1)
    x = jax.device_put(jnp.arange(64, dtype=jnp.float32),
                       NamedSharding(mesh, P("dp")))
    state = {"w": x, "b": jnp.ones((3,))}
    return ck, str(tmp_path), state, x


def test_sharded_checkpoint_round_trip(sharded_ckpt):
    ck, d, state, x = sharded_ckpt
    mgr = ck.CheckpointManager(d)
    mgr.save(state, 7, block=True)
    path = mgr.step_path(7)
    # local shards landed under the per-process shard dir, fingerprinted
    assert os.path.isdir(os.path.join(path, "shard-p0"))
    sm = json.load(open(os.path.join(path, "shard-p0", "manifest.json")))
    assert sm["process_count"] == 1 and len(sm["shards"]) == 8
    assert mgr.latest_valid() == path
    got, step = mgr.restore(target=state)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(x))
    assert got["w"].sharding == x.sharding  # rebound onto the LIVE layout


def test_sharded_checkpoint_refuses_dp_degree_skew(sharded_ckpt):
    ck, d, state, x = sharded_ckpt
    mgr = ck.CheckpointManager(d)
    mgr.save(state, 1, block=True)
    mp = os.path.join(mgr.step_path(1), "manifest.json")
    m = json.load(open(mp))
    (key,) = list(m["sharded"])
    m["sharded"][key]["dp_degree"] = 4
    json.dump(m, open(mp, "w"))
    # an explicit-path restore refuses loudly (dp degree 4 recorded, shard
    # dirs for processes 1-3 absent) ...
    with pytest.raises(ck.CheckpointError, match="dp degree"):
        mgr.restore(target=state, path=mgr.step_path(1))
    # ... and discovery skips it: every process reaches the same verdict,
    # so no rank restores state its peers do not have
    assert mgr.latest_valid() is None
    with pytest.raises(ck.CheckpointError, match="no valid checkpoint"):
        mgr.restore(target=state)


def test_sharded_checkpoint_refuses_shard_shape_skew(sharded_ckpt):
    """A template sliced differently (different dp degree -> different
    shard placement) is refused before any rebinding."""
    ck, d, state, x = sharded_ckpt
    mgr = ck.CheckpointManager(d)
    mgr.save(state, 1, block=True)
    from jax.sharding import NamedSharding

    mesh2 = build_mesh(tp=4, pp=1, sp=1)  # dp=2: 2 shards of 32, not 8x8
    y = jax.device_put(jnp.arange(64, dtype=jnp.float32),
                       NamedSharding(mesh2, P("dp")))
    with pytest.raises(ck.CheckpointError, match="skew"):
        mgr.restore(target={"w": y, "b": state["b"]})


def test_sharded_checkpoint_torn_shard_dir_is_invalid(sharded_ckpt):
    """A crash between process 0's publish and a peer's shard rename
    leaves the shard dir missing — verify() must call that torn, and
    latest_valid() must fall back to the previous good checkpoint."""
    import shutil

    ck, d, state, x = sharded_ckpt
    mgr = ck.CheckpointManager(d)
    mgr.save(state, 1, block=True)
    mgr.save(state, 2, block=True)
    shutil.rmtree(os.path.join(mgr.step_path(2), "shard-p0"))
    assert not mgr.verify(mgr.step_path(2))
    assert mgr.latest_valid() == mgr.step_path(1)


def test_sharded_multiwriter_save_refused(sharded_ckpt, monkeypatch):
    """process0_only=False on a multi-process sharded save is refused:
    every process would publish its own step dir holding only its own
    shard-p{K}, the last os.replace wins, and every save verifies torn."""
    ck, d, state, x = sharded_ckpt
    monkeypatch.setattr(ck, "_process_info", lambda: (0, 2))
    mgr = ck.CheckpointManager(d, process0_only=False)
    with pytest.raises(ck.CheckpointError, match="process0_only"):
        mgr.save(state, 1, block=True)
    assert mgr.latest_valid() is None  # nothing was written


def test_genuinely_non_addressable_still_refused():
    """The loud CheckpointError survives for leaves with no addressable
    replica-0 shard."""
    from apex_tpu.resilience import checkpoint as ck

    class _Shard:
        replica_id = 1  # only replicas of other processes' data

        def __init__(self):
            self.index = (slice(0, 4),)
            self.data = np.zeros(4)

    class _Fake:
        shape = (8,)
        dtype = np.float32
        is_fully_addressable = False
        is_fully_replicated = False
        addressable_shards = [_Shard()]

    with pytest.raises(ck.CheckpointError, match="non-addressable"):
        ck.state_dict({"x": _Fake()})


def test_state_dict_sharded_leaf_round_trip(monkeypatch):
    from apex_tpu.resilience import checkpoint as ck

    monkeypatch.setattr(
        ck, "_is_cross_process",
        lambda a: hasattr(a, "addressable_shards") and getattr(
            a, "shape", ()) == (64,))
    from jax.sharding import NamedSharding

    mesh = build_mesh(tp=1, pp=1, sp=1)
    x = jax.device_put(jnp.arange(64, dtype=jnp.float32),
                       NamedSharding(mesh, P("dp")))
    sd = ck.state_dict({"w": x})
    assert sd["leaves"]["0"]["__sharded__"]
    assert len(sd["leaves"]["0"]["shards"]) == 8
    back = ck.load_state_dict({"w": x}, sd)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(x))


# ---------------------------------------------------------------------------
# mesh-gated: the ring op, training parity, checkpoint rejoin, HLO gate


B, S = 8, 32


def _gpt_fixture():
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    cfg = GPTConfig(vocab_size=128, max_seq=S, hidden=64, num_layers=2,
                    num_heads=2, dtype=jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 128)
    return cfg, params, tok


def _mesh_dp(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} of the 8 virtual devices")
    return build_mesh(tp=1, pp=1, sp=1, devices=jax.devices()[:n])


def _state_specs(params):
    shard = jax.tree_util.tree_map(lambda _: P("dp"), params)
    return FSDPAdamState(count=P(), master=shard, mu=shard, nu=shard)


@pytest.mark.parametrize("bidirectional", [False, True])
def test_matmul_param_gather_matches_monolithic(bidirectional):
    """Against x @ all_gather(w): the summed value and dX/dW to fp-reorder
    tolerance (the ring adds the chunks' sums in its own order)."""
    from apex_tpu.comm import matmul_param_gather

    mesh = _mesh_dp(8)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (8, 4, 16), jnp.float32)
    w = jax.random.normal(ks[1], (16, 32), jnp.float32)
    cot = jax.random.normal(ks[2], (8, 4, 32), jnp.float32)

    def run(body):
        def loss(x, w, cot):
            def inner(x, w, cot):
                return lax.psum(jnp.sum(body(x[0], w) * cot[0]), "dp")

            return jax.shard_map(
                inner, mesh=mesh,
                in_specs=(P("dp"), P(None, "dp"), P("dp")),
                out_specs=P())(x, w, cot)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, w, cot)

    fused = lambda x, w: matmul_param_gather(  # noqa: E731
        x, w, axis_name="dp", bidirectional=bidirectional)
    mono = lambda x, w: jnp.dot(  # noqa: E731
        x, lax.all_gather(w, "dp", axis=1, tiled=True))
    vf, (gxf, gwf) = run(fused)
    vm, (gxm, gwm) = run(mono)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(vm),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gxf), np.asarray(gxm),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gwf), np.asarray(gwm),
                               rtol=2e-5, atol=1e-5)


def test_matmul_param_gather_sums_dx_over_the_axes_only_the_weight_varies_on():
    """A weight column-split over tp and fsdp-sharded over dp, activations
    replicated over tp: dX is the sum of the tp ranks' partials, as the
    monolithic program's; dW as before."""
    from apex_tpu.comm import matmul_param_gather

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=4, pp=1, sp=1)  # dp=2
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 256), jnp.float32)

    def run(body):
        def loss(x, w):
            def inner(x, ws):
                return lax.psum(jnp.sum(jnp.sin(body(x[0], ws))),
                                ("tp", "dp"))

            return jax.shard_map(
                inner, mesh=mesh,
                in_specs=(P("dp"), P(None, ("tp", "dp"))),
                out_specs=P())(x, w)

        return jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)

    gxf, gwf = run(lambda x, ws: matmul_param_gather(x, ws, axis_name="dp"))
    gxm, gwm = run(lambda x, ws: jnp.dot(
        x, lax.all_gather(ws, "dp", axis=1, tiled=True)))
    np.testing.assert_allclose(np.asarray(gxf), np.asarray(gxm),
                               rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gwf), np.asarray(gwm),
                               rtol=2e-5, atol=1e-5)


def _fsdp_gpt_losses(steps=6, weight_gather=None, compression=None,
                     ckpt_dir=None, lr=2e-3):
    """FSDP-trained loss curve on the GPT fixture at dp=2; optionally
    round-trips the FULL optimizer state through a CheckpointManager
    mid-run (the rejoin contract)."""
    from apex_tpu.transformer.testing import gpt_loss

    cfg, params, tok = _gpt_fixture()
    mesh = _mesh_dp(2)
    fsdp = FSDP(weight_gather=weight_gather, compression=compression)
    opt = FSDPAdam(fsdp=fsdp, lr=lr)
    meta = fsdp.meta(params)
    pspecs = jax.tree_util.tree_map(lambda _: P(), params)
    sspec = _state_specs(params)
    init = jax.jit(jax.shard_map(
        opt.init, mesh=mesh, in_specs=(pspecs,), out_specs=sspec,
        check_vma=False))
    state = init(params)

    def body(st, t):
        def loss_fn(master):
            return gpt_loss(fsdp.gather(master, meta), t, t, cfg)

        l, g = jax.value_and_grad(loss_fn)(st.master)
        st = opt.step(g, st)
        return st, lax.pmean(l, "dp")

    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(sspec, P("dp")),
        out_specs=(sspec, P()), check_vma=False))
    losses = []
    for i in range(steps):
        state, l = step(state, tok)
        losses.append(float(l))
        if ckpt_dir is not None and i == steps // 2:
            # the satellite contract: shard state survives the manifest
            # path exactly — the continued curve cannot drift
            from apex_tpu.resilience import CheckpointManager

            mgr = CheckpointManager(ckpt_dir)
            mgr.save(state, i + 1, block=True)
            fresh = jax.tree_util.tree_map(jnp.zeros_like, state)
            state, got_step = mgr.restore(target=fresh)
            assert got_step == i + 1
    return losses


def _ddp_gpt_losses(steps=6, lr=2e-3):
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.transformer.testing import gpt_loss

    cfg, params, tok = _gpt_fixture()
    mesh = _mesh_dp(2)
    opt = FusedAdam(lr=lr)
    opt_state = opt.init(params)
    ddp = DistributedDataParallel()

    def body(p, s, t):
        l, g = jax.value_and_grad(lambda p: gpt_loss(p, t, t, cfg))(p)
        g = ddp.average_gradients(g)
        updates, s = opt.update(g, s, p)
        return (jax.tree_util.tree_map(lambda p, u: p + u, p, updates), s,
                lax.pmean(l, "dp"))

    pspecs = jax.tree_util.tree_map(lambda _: P(), params)
    ospecs = jax.tree_util.tree_map(lambda _: P(), opt_state)
    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(pspecs, ospecs, P("dp")),
        out_specs=(pspecs, ospecs, P()), check_vma=False))
    losses = []
    p, s = params, opt_state
    for _ in range(steps):
        p, s, l = step(p, s, tok)
        losses.append(float(l))
    return losses


def test_fsdp_matches_ddp_loss_curve():
    """ACCEPTANCE: FSDP == DDP+FusedAdam over ≥5 GPT steps at dp=2.
    The shared Adam tail + exact gather/reduce-scatter make the curves
    bitwise on the sim; asserted to 1e-5 (fp-reorder headroom), plus
    training must actually progress."""
    base = _ddp_gpt_losses()
    fsdp = _fsdp_gpt_losses()
    assert len(fsdp) >= 5
    assert base[-1] < base[0] - 0.5, base
    np.testing.assert_allclose(fsdp, base, atol=1e-5)


def test_fsdp_int8_weight_gather_within_codec_tolerance():
    """int8 param-gather wire: the curve tracks the exact one within
    codec tolerance (measured ~1e-3 max divergence; 0.02 is margin) —
    the fp32 master stays exact, only the gathered copy is rounded."""
    base = _ddp_gpt_losses()
    int8 = _fsdp_gpt_losses(
        weight_gather=CompressionConfig("int8", min_elements=256))
    np.testing.assert_allclose(int8, base, atol=0.02)
    assert any(a != b for a, b in zip(int8, base)), \
        "the codec should actually round something"


def test_fsdp_int8_grad_reduce_within_tolerance():
    base = _ddp_gpt_losses()
    int8 = _fsdp_gpt_losses(
        compression=CompressionConfig("int8", min_elements=256))
    np.testing.assert_allclose(int8, base, atol=0.05)


def test_fsdp_int4_weight_gather_within_codec_tolerance():
    """The sub-8-bit FSDP wire: nibble-packed int4 param gathers (half
    the int8 gather bytes again) keep the curve within the ±7-code
    tolerance of the exact run — the fp32 master stays exact, only the
    gathered model-dtype copy is rounded, so the loss never drifts, it
    just wobbles inside the codec band."""
    base = _ddp_gpt_losses()
    int4 = _fsdp_gpt_losses(
        weight_gather=CompressionConfig("int4", block_size=128,
                                        min_elements=256))
    np.testing.assert_allclose(int4, base, atol=0.1)
    assert any(a != b for a, b in zip(int4, base)), \
        "the codec should actually round something"
    assert int4[-1] < int4[0] - 0.4, int4  # training still progresses


def test_fsdp_int4_grad_reduce_within_tolerance():
    base = _ddp_gpt_losses()
    int4 = _fsdp_gpt_losses(
        compression=CompressionConfig("int4", block_size=128,
                                      min_elements=256))
    np.testing.assert_allclose(int4, base, atol=0.15)
    assert int4[-1] < int4[0] - 0.4, int4


def test_fsdp_checkpoint_midrun_rejoins_exactly(tmp_path):
    """Mid-run save → zeroed state → restore: the continued curve is
    IDENTICAL to the uninterrupted run (shard-exact manifest path)."""
    plain = _fsdp_gpt_losses()
    rejoined = _fsdp_gpt_losses(ckpt_dir=str(tmp_path))
    np.testing.assert_array_equal(plain, rejoined)


def test_fsdp_adam_matches_fused_adam_singleleaf():
    """The shard optimizer is FusedAdam given the same grads (the ZeRO-1
    parity contract, now for the stage-3 optimizer)."""
    from apex_tpu.optimizers import FusedAdam

    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (13, 7)),
              "b": jax.random.normal(jax.random.fold_in(key, 1), (5,))}
    grads = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.fold_in(key, 2), x.shape)
        * 0.1, params)
    mesh = _mesh_dp(8)
    fsdp = FSDP()
    opt = FSDPAdam(fsdp=fsdp, lr=1e-2, weight_decay=0.01)
    meta = fsdp.meta(params)

    def run(p, g):
        st = opt.init(p)
        world = lax.axis_size("dp")
        for _ in range(3):
            def loss_fn(master):
                full = fsdp.gather(master, meta)
                # sum(g*p): grad of this IS g (dp-summed by the VJP)
                return lax.psum(
                    sum(jnp.vdot(a, b) for a, b in zip(
                        jax.tree_util.tree_leaves(full),
                        jax.tree_util.tree_leaves(g))), "dp") / world
            gs = jax.grad(loss_fn)(st.master)
            st = opt.step(gs, st)
        return fsdp.gather(st.master, meta)

    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    got = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(pspec, pspec), out_specs=pspec,
        check_vma=False))(params, grads)

    ref = FusedAdam(lr=1e-2, weight_decay=0.01)
    rs = ref.init(params)
    want = params
    for _ in range(3):
        upd, rs = ref.update(grads, rs, want)
        want = jax.tree_util.tree_map(lambda p, u: p + u, want, upd)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   atol=1e-6, err_msg=k)


def test_fsdp_step_records_metrics():
    from apex_tpu.monitor import Metrics

    params = {"w": jnp.ones((64, 8))}
    mesh = _mesh_dp(8)
    fsdp = FSDP()
    opt = FSDPAdam(fsdp=fsdp, lr=1e-2)
    meta = fsdp.meta(params)
    metrics = Metrics({"grad_norm": 0.0, "param_norm": 0.0,
                       "update_norm": 0.0, "param_gather_bytes": 0.0,
                       "comm_wire_bytes": 0.0, "hbm_params_bytes": 0.0})

    def run(p, m):
        st = opt.init(p)
        g = jax.grad(lambda s: lax.psum(
            jnp.sum(fsdp.gather(s, meta)["w"] ** 2), "dp"))(st.master)
        st, m = opt.step(g, st, metrics=m, meta=meta)
        return m

    got = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(jax.tree_util.tree_map(lambda _: P(),
                                                         params), P()),
        out_specs=P(), check_vma=False))(params, metrics)
    d = got.as_dict()
    assert d["grad_norm"] > 0 and d["param_norm"] > 0
    assert d["param_gather_bytes"] == param_gather_wire_bytes(meta, 8)
    assert d["hbm_params_bytes"] == hbm_params_bytes(
        meta, strategy="fsdp", world=8)["total"]
    assert d["comm_wire_bytes"] > d["param_gather_bytes"]


def test_flagship_tp_fsdp_gather_ring_proven_hidden():
    """ACCEPTANCE: the compiled tp/fsdp program's forward weight-gather
    rings are ≥0.5 hidden, proven from the HLO (the PR-4 flagship
    contract in FSDP position): a two-layer MLP whose weights are
    tp-column-split AND fsdp-sharded over dp on a dp=2 x tp=4 mesh."""
    from apex_tpu.comm import overlap_report

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(tp=4, pp=1, sp=1)  # dp=2
    fsdp = FSDP()
    d_in, d_h = 128, 256
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, d_in), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (d_in, d_h), jnp.float32)
    w2 = jax.random.normal(jax.random.PRNGKey(2), (d_h, d_in), jnp.float32)

    def loss(x, w1, w2):
        def body(x, w1s, w2s):
            # column-parallel entry over tp; its tp-local weight fsdp-
            # sharded over dp and gathered through the overlapped ring
            h = jax.nn.gelu(fsdp.linear(x[0], w1s))
            # row-parallel exit: the weight's gather dim is CONTRACTING,
            # so this leaf rides the plain dp all-gather (the non-ring
            # FSDP position), then the tp psum
            w2f = lax.all_gather(w2s, "dp", axis=0, tiled=True)
            y = lax.psum(jnp.dot(h, w2f), "tp")
            return lax.psum(jnp.sum(y * y), "dp")

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("dp"), P(None, ("tp", "dp")), P(("tp", "dp"))),
            out_specs=P())(x, w1, w2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(1, 2))).lower(
        x, w1, w2).compile()
    rep = overlap_report(compiled.as_text())
    assert rep.permutes > 0, f"no gather rings in the program: {rep}"
    assert rep.hidden >= 2, rep
    assert rep.hidden_fraction >= 0.5, rep


def test_plan_drives_fsdp_end_to_end():
    """The ParallelismPlan IS the wiring: preset('fsdp') -> mesh,
    engine, optimizer; one train step runs and shrinks the loss."""
    from apex_tpu.transformer.testing import gpt_loss

    cfg, params, tok = _gpt_fixture()
    plan = ParallelismPlan.preset("fsdp")
    mesh = plan.mesh(devices=jax.devices()[:2])
    fsdp = plan.fsdp()
    opt = plan.build_optimizer(lr=2e-3)
    meta = fsdp.meta(params)
    pspecs = jax.tree_util.tree_map(lambda _: P(), params)
    sspec = _state_specs(params)
    init = jax.jit(jax.shard_map(
        opt.init, mesh=mesh, in_specs=(pspecs,), out_specs=sspec,
        check_vma=False))

    def body(st, t):
        def loss_fn(master):
            return gpt_loss(fsdp.gather(master, meta), t, t, cfg)

        l, g = jax.value_and_grad(loss_fn)(st.master)
        return opt.step(g, st), lax.pmean(l, "dp")

    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(sspec, P("dp")),
        out_specs=(sspec, P()), check_vma=False))
    state = init(params)
    first = None
    for _ in range(3):
        state, l = step(state, tok)
        first = first if first is not None else float(l)
    assert float(l) < first
