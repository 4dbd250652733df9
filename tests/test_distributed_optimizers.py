"""ZeRO-style optimizer tests — ref tests/L0/run_optimizers/test_dist_adam.py:
the dp-sharded optimizer must produce the SAME parameters as the non-sharded
fused optimizer given the same gradients, while holding only 1/dp state."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.contrib.optimizers import (
    DistributedFusedAdam,
    DistributedFusedLAMB,
)
from apex_tpu.optimizers import FusedAdam, FusedLAMB
from apex_tpu.parallel.mesh import build_mesh


def _params_grads(key):
    p = {
        "w": jax.random.normal(key, (13, 7)),  # deliberately non-multiple of 8
        "b": jax.random.normal(jax.random.fold_in(key, 1), (5,)),
    }
    g = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, 2), x.shape) * 0.1,
        p)
    return p, g


def test_dist_adam_matches_fused_adam():
    params, grads = _params_grads(jax.random.PRNGKey(0))
    mesh = build_mesh(tp=1, pp=1, sp=1)  # dp=8
    opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01)

    def run(p, g):
        state = opt.init(p)
        for _ in range(3):
            p, state = opt.step(g, state, p)
        # state shards are 1/8 (padded) of each param
        assert state.mu["w"].shape == (12,)  # ceil(91/8)
        return p

    got = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params),) * 2,
        out_specs=jax.tree.map(lambda _: P(), params),
        check_vma=False,  # replicated-by-construction all-gather output
    ))(params, grads)

    ref_opt = FusedAdam(lr=1e-2, weight_decay=0.01)
    ref_state = ref_opt.init(params)
    want = params
    for _ in range(3):
        updates, ref_state = ref_opt.update(grads, ref_state, want)
        want = jax.tree.map(lambda p, u: p + u, want, updates)
    for k in ("w", "b"):
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), atol=1e-6, err_msg=k)


def test_dist_adam_sums_grads_over_dp():
    # different grads per dp rank: the reduce-scatter must average them
    params = {"w": jnp.zeros((8, 4))}
    mesh = build_mesh(tp=1, pp=1, sp=1)
    opt = DistributedFusedAdam(lr=1.0, betas=(0.0, 0.999), eps=1e-8,
                               weight_decay=0.0)

    per_rank_g = jnp.stack(
        [jnp.full((8, 4), float(i)) for i in range(8)])  # mean = 3.5

    def run(p, g):
        g = jax.tree.map(lambda x: x[0], g)  # my rank's grad
        state = opt.init(p)
        p, state = opt.step(g, state, p)
        return p

    got = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=({"w": P()}, {"w": P("dp")}),
        out_specs={"w": P()},
        check_vma=False,
    ))(params, {"w": per_rank_g})
    # beta1=0: update direction = sign-ish mhat/sqrt(vhat); with identical
    # entries everywhere the update must be identical too — and nonzero
    v = np.asarray(got["w"])
    assert np.allclose(v, v.flat[0])
    assert abs(v.flat[0]) > 0.1


def test_dist_lamb_matches_fused_lamb():
    params, grads = _params_grads(jax.random.PRNGKey(1))
    mesh = build_mesh(tp=1, pp=1, sp=1)
    opt = DistributedFusedLAMB(lr=1e-2, weight_decay=0.01,
                               max_grad_norm=None, grad_averaging=True)

    def run(p, g):
        state = opt.init(p)
        for _ in range(3):
            p, state = opt.step(g, state, p)
        return p

    got = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params),) * 2,
        out_specs=jax.tree.map(lambda _: P(), params),
        check_vma=False,
    ))(params, grads)

    ref_opt = FusedLAMB(lr=1e-2, weight_decay=0.01, max_grad_norm=0.0)
    ref_state = ref_opt.init(params)
    want = params
    for _ in range(3):
        updates, ref_state = ref_opt.update(grads, ref_state, want)
        want = jax.tree.map(lambda p, u: p + u, want, updates)
    for k in ("w", "b"):
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), atol=2e-6, err_msg=k)


def test_dist_adam_grad_clipping_and_scale():
    params = {"w": jnp.ones((4, 4))}
    big = {"w": jnp.full((4, 4), 100.0)}
    mesh = build_mesh(tp=1, pp=1, sp=1)
    opt = DistributedFusedAdam(lr=1e-2, max_grad_norm=1.0)

    def run(p, g):
        state = opt.init(p)
        p2, _ = opt.step(g, state, p, scale=jnp.asarray(2.0))
        return p2

    got = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=({"w": P()}, {"w": P()}),
        out_specs={"w": P()}, check_vma=False,
    ))(params, big)
    # huge grads clipped to norm 1 -> bounded first step
    delta = np.abs(np.asarray(got["w"]) - 1.0).max()
    assert 0 < delta < 0.05


def test_dist_adam_e5m2_allgather():
    """Ref e5m2_allgather: fp8-transport param all-gather. Masters stay
    fp32-exact (bit-compared against the uncompressed run — compression
    only touches the wire); the replicated params carry only the e5m2
    rounding of the model dtype (|rel| <= 2^-2 on normals)."""
    params, grads = _params_grads(jax.random.PRNGKey(3))
    mesh = build_mesh(tp=1, pp=1, sp=1)  # dp=8

    def run(e5m2):
        opt = DistributedFusedAdam(lr=1e-2, e5m2_allgather=e5m2)

        def body(p, g):
            state = opt.init(p)
            for _ in range(3):
                p, state = opt.step(g, state, p)
            return p, state.master

        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),) * 2,
            out_specs=(jax.tree.map(lambda _: P(), params),
                       jax.tree.map(lambda _: P("dp"), params)),
            check_vma=False,
        ))(params, grads)

    p_c, m_c = run(True)
    p_u, m_u = run(False)
    for k in ("w", "b"):
        # the sharded fp32 masters are bit-identical: compression only
        # touches the wire format of the gather
        np.testing.assert_array_equal(np.asarray(m_c[k]), np.asarray(m_u[k]),
                                      err_msg=f"master {k}")
        a, b = np.asarray(p_c[k], np.float32), np.asarray(p_u[k], np.float32)
        # e5m2 keeps 2 mantissa bits: worst-case relative step 25%
        np.testing.assert_allclose(a, b, rtol=0.25, atol=1e-6,
                                   err_msg=f"params {k}")
        assert np.any(a != b), "compression should actually round something"


@pytest.mark.parametrize("cls_name", ["adam", "lamb"])
def test_zero_fused_update_matches_unfused(cls_name):
    """fused_update='on' (the ops/fused_update.py Pallas tail) produces
    the same parameters as the per-op chain — the megakernel-PR gate for
    the ZeRO update tail. Tolerance is fp reassociation noise only."""
    params, grads = _params_grads(jax.random.PRNGKey(3))
    mesh = build_mesh(tp=1, pp=1, sp=1)  # dp=8

    def run(mode):
        cls = (DistributedFusedAdam if cls_name == "adam"
               else DistributedFusedLAMB)
        opt = cls(lr=1e-2, weight_decay=0.01, fused_update=mode)

        def body(p, g):
            state = opt.init(p)
            for _ in range(3):
                p, state = opt.step(g, state, p)
            return p

        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),) * 2,
            out_specs=jax.tree.map(lambda _: P(), params),
            check_vma=False,
        ))(params, grads)

    got, want = run("on"), run("off")
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=5e-6, atol=5e-7, err_msg=k)
