"""Minimal distributed-training example (ref ``examples/simple/distributed/
distributed_data_parallel.py``): a linear model trained over every device,
with the parallelism strategy picked by ONE declarative
``ParallelismPlan`` preset instead of hand-wired flags:

* ``--plan ddp``    — replicated params, bucketed-allreduce DDP (plus the
  full resilience wiring: in-graph anomaly guard, atomic auto-resumed
  checkpoints, SIGTERM save-and-exit, ``--chaos-step`` NaN injection);
* ``--plan zero1``  — ``DistributedFusedAdam``: dp-sharded optimizer
  state, grads reduce-scattered, params all-gathered by the optimizer;
* ``--plan fsdp``   — ``apex_tpu.fsdp``: parameters sharded too; the
  forward gathers on demand and the backward reduce-scatters gradients
  straight into shard layout;
* ``--plan fsdp+tp`` — the same FSDP engine on a dp×tp mesh (this toy
  model defines no tensor-parallel layers, so tp only replicates compute —
  the point is that the PLAN resolves the composed mesh; see
  ``tests/test_fsdp.py`` for fsdp+tp on the TP GPT).

Run directly; on a CPU-only machine set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to fake a mesh.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

if os.environ.get("JAX_PLATFORMS") == "cpu":
    from apex_tpu.utils.platform import pin_cpu_platform

    pin_cpu_platform(virtual_devices=8)

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor import Metrics
from apex_tpu.parallel import ParallelismPlan
from apex_tpu.parallel.mesh import DP_AXIS
from apex_tpu.resilience import (
    AnomalyGuard,
    CheckpointManager,
    GuardPolicy,
    PreemptionHandler,
    TrainSupervisor,
    chaos,
    replicated_spec,
)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--plan", default="ddp",
                    choices=["ddp", "zero1", "fsdp", "fsdp+tp"],
                    help="ParallelismPlan preset (replaces the old "
                         "hand-wired DDP/ZeRO knobs)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--checkpoint-dir", default="",
                    help="atomic checkpoints + auto-resume + SIGTERM save")
    ap.add_argument("--save-freq", type=int, default=50)
    ap.add_argument("--chaos-step", type=int, default=-1,
                    help="inject a NaN gradient at this step "
                         "(guard demo; --plan ddp only)")
    ap.add_argument("--elastic", action="store_true",
                    help="drive the sharded loop through TrainSupervisor "
                         "with an elastic checkpoint spec: checkpoints "
                         "restore at a DIFFERENT --plan dp degree (the "
                         "restart manifest names the legal ones); needs "
                         "--checkpoint-dir and a zero1/fsdp plan")
    return ap.parse_args(argv)


def _data():
    n = 128  # fixed global sample count (divisible by any dp in 1..8)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 8))
    true_w = jnp.arange(8.0)
    y = x @ true_w + 0.5
    return x, y, true_w


def _loss(params, x, y):
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _train_ddp(args, plan, mesh, params, x, y):
    """The original resilience-wired DDP loop, constructed from the plan."""
    ddp = plan.ddp()
    guard = AnomalyGuard(GuardPolicy(on_anomaly="skip", skip_budget=3))

    def body(params, gstate, metrics, x, y, it):
        grads = jax.grad(_loss)(ddp.replicate(params), x, y)
        grads = ddp.average_gradients(grads)
        if args.chaos_step >= 0:
            grads = chaos.inject_nonfinite(grads, it, args.chaos_step)
        proposed = jax.tree.map(lambda p, g: p - args.lr * g, params, grads)
        # guard: a non-finite grad never reaches the params — the bad step
        # is skipped (then rolled back / halted if it persists), and the
        # counters ride the Metrics pytree. axis_names makes both the flag
        # and the counters rank-uniform.
        bad, metrics = guard.check(grads=grads, metrics=metrics,
                                   axis_names=DP_AXIS)
        params, gstate, metrics = guard.apply(
            gstate, bad, proposed, params, metrics=metrics)
        return params, gstate, metrics

    gstate = guard.init(params)
    # pre-seed the counter names: the Metrics treedef stays fixed across
    # steps, so the jitted step never retraces (the monitor contract)
    metrics = Metrics({"anomalies_total": 0.0, "nonfinite_grads_total": 0.0,
                       "guard_skips_total": 0.0, "rollbacks_total": 0.0,
                       "guard_halted": 0.0})
    step = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(DP_AXIS), P(DP_AXIS), P()),
        out_specs=(P(), P(), P())))

    mgr = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir \
        else None
    pre = PreemptionHandler() if mgr is not None else None
    start = 0
    if mgr is not None and mgr.latest_valid() is not None:
        (params, gstate, metrics), start = mgr.restore(
            target=(params, gstate, metrics))
        print(f"=> auto-resumed at step {start}")

    for it in range(start, args.steps):
        params, gstate, metrics = step(params, gstate, metrics, x, y,
                                       jnp.asarray(it))
        guard.raise_if_halted(gstate)
        if pre is not None:
            save_at = pre.sync_save_step(it)
            if save_at is not None:
                mgr.save((params, gstate, metrics), save_at + 1, block=True)
                print(f"=> preempted: saved at step {save_at + 1}, exiting")
                # None params = "no final state to validate": main skips
                # the convergence assert on this clean save-and-exit path
                return None, metrics
        if mgr is not None and (it + 1) % args.save_freq == 0:
            mgr.save((params, gstate, metrics), it + 1)
    if mgr is not None:
        mgr.close()
    return params, metrics


def _train_sharded(args, plan, mesh, params, x, y):
    """zero1 / fsdp / fsdp+tp: the sharded-optimizer loops, built entirely
    from the plan (no strategy-specific wiring beyond the state specs)."""
    opt = plan.build_optimizer(lr=args.lr)
    pspecs = jax.tree.map(lambda _: P(), params)
    shard = jax.tree.map(lambda _: P(DP_AXIS), params)

    if plan.data == "fsdp":
        from apex_tpu.fsdp import FSDPAdamState

        fsdp = plan.fsdp()
        meta = fsdp.meta(params)
        sspec = FSDPAdamState(count=P(), master=shard, mu=shard, nu=shard)

        def init_fn(p):
            return opt.init(p)

        def body(st, x, y):
            def loss_fn(master):
                return _loss(fsdp.gather(master, meta), x, y)

            l, g = jax.value_and_grad(loss_fn)(st.master)
            st = opt.step(g, st)
            return st, lax.pmean(l, DP_AXIS)

        def final_fn(st):
            return fsdp.gather(st.master, meta)
    else:  # zero1
        from apex_tpu.contrib.optimizers.distributed_fused_adam import (
            DistAdamState,
        )

        sspec = (pspecs,
                 DistAdamState(count=P(), master=shard, mu=shard, nu=shard))

        def init_fn(p):
            return p, opt.init(p)

        def body(st, x, y):
            p, ostate = st
            l, g = jax.value_and_grad(_loss)(p, x, y)
            p, ostate = opt.step(g, ostate, p)
            return (p, ostate), lax.pmean(l, DP_AXIS)

        def final_fn(st):
            return st[0]

    init = jax.jit(jax.shard_map(
        init_fn, mesh=mesh, in_specs=(pspecs,), out_specs=sspec,
        check_vma=False))
    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(sspec, P(DP_AXIS), P(DP_AXIS)),
        out_specs=(sspec, P()), check_vma=False))
    finalize = jax.jit(jax.shard_map(
        final_fn, mesh=mesh, in_specs=(sspec,), out_specs=pspecs,
        check_vma=False))

    state = init(params)
    if args.elastic:
        return _run_elastic(args, plan, mesh, params, opt, state, step,
                            finalize, x, y)
    mgr = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir \
        else None
    start = 0
    if mgr is not None and mgr.latest_valid() is not None:
        state, start = mgr.restore(target=state)
        print(f"=> auto-resumed at step {start}")
    loss = None
    for it in range(start, args.steps):
        state, loss = step(state, x, y)
        if mgr is not None and (it + 1) % args.save_freq == 0:
            mgr.save(state, it + 1)
    if mgr is not None:
        mgr.close()
    if loss is None:
        print(f"=> nothing to run: resumed at step {start} "
              f">= --steps {args.steps}")
    else:
        print(f"final loss {float(loss):.6f}")
    return finalize(state), None


def _run_elastic(args, plan, mesh, params, opt, state, step, finalize, x, y):
    """The --elastic loop: TrainSupervisor + an elastic checkpoint spec.
    Saves are topology-portable — a later run with a different dp degree
    resumes from the restart manifest via the reshard path (the manifest's
    ``legal_resume_dp`` names the degrees that divide cleanly)."""
    dp = mesh.shape[DP_AXIS]
    # per-leaf reshard specs mirroring the state structure: the optimizer
    # knows its shard arithmetic; the replicated params tree (zero1's
    # first element) never reshards
    espec = opt.elastic_spec(params, dp)
    if plan.data != "fsdp":
        espec = (jax.tree.map(lambda _: replicated_spec(), params), espec)
    mgr = plan.checkpoint_manager(args.checkpoint_dir, allow_reshard=True)
    last = {}

    def step_fn(st, it):
        st, last["loss"] = step(st, x, y)
        return st

    sup = TrainSupervisor(step_fn, mgr, elastic=espec, dp_degree=dp,
                          save_freq=args.save_freq,
                          preemption=PreemptionHandler())
    start = 0
    info = TrainSupervisor.read_restart(args.checkpoint_dir)
    if info is not None or mgr.latest_valid() is not None:
        state, start = sup.resume(state)
        prev_dp = info.get("dp_degree") if info else dp
        print(f"=> elastic resume at step {start} "
              f"(checkpoint dp={prev_dp}, live dp={dp})")
    state, nxt = sup.run(state, start, max(0, args.steps - start))
    mgr.close()
    if sup.exited == "preempted":
        print(f"=> preempted: saved at step {nxt}, restart manifest "
              "written — rerun (any legal dp) to continue")
        return None, None
    if "loss" in last:
        print(f"final loss {float(last['loss']):.6f}")
    return finalize(state), None


def main(argv=None):
    args = parse_args(argv)
    plan = ParallelismPlan.preset(args.plan)
    if args.elastic and (plan.data == "ddp" or not args.checkpoint_dir):
        raise SystemExit("--elastic needs --checkpoint-dir and a sharded "
                         "plan (zero1/fsdp/fsdp+tp)")
    print(plan.describe())

    # TPU matmuls default to bf16 accumulation; this toy regression needs f32
    jax.config.update("jax_default_matmul_precision", "highest")
    mesh = plan.mesh()
    params = {"w": jnp.zeros((8,)), "b": jnp.zeros(())}
    x, y, true_w = _data()
    print("  modeled hbm_params_bytes:",
          {k: int(v) for k, v in plan.hbm_params_bytes(
              params, world=mesh.shape[DP_AXIS]).items()})

    if plan.data == "ddp":
        params, metrics = _train_ddp(args, plan, mesh, params, x, y)
        if metrics is not None:
            stats = metrics.as_dict()
            print(f"(anomalies={stats['anomalies_total']:.0f} "
                  f"skips={stats['guard_skips_total']:.0f})")
    else:
        params, _ = _train_sharded(args, plan, mesh, params, x, y)

    if params is None:
        return  # preempted: state saved for --resume, nothing to validate

    err = float(jnp.abs(params["w"] - true_w).max())
    print(f"w error after {args.steps} steps: {err:.4f}")
    assert err < 0.05


if __name__ == "__main__":
    main()
