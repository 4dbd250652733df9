"""ImageNet-style ResNet trainer — amp + DDP + SyncBN on the TPU mesh.

Reference: ``examples/imagenet/main_amp.py`` (543 LoC) — torchvision ResNet
under ``amp.initialize(opt_level=...)`` + apex DDP (+ ``--sync_bn``),
printing per-iteration loss and img/s; the L1 suite runs it twice with
``--deterministic`` and requires bitwise-equal loss curves
(``tests/L1/common/compare.py``).

TPU version: same knobs, synthetic data by default (no ImageNet in the
image); the train loop is one jitted step over a dp mesh. Run:

    python examples/imagenet/main_amp.py --arch resnet18 --iters 20 \
        --opt-level O2 --sync_bn --deterministic
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time
from typing import Any, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

if os.environ.get("JAX_PLATFORMS") == "cpu":
    from apex_tpu.utils.platform import pin_cpu_platform

    pin_cpu_platform(virtual_devices=8)

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.amp import frontend as amp
from apex_tpu.amp.autocast import autocast
from apex_tpu.models import ResNet18, ResNet50
from apex_tpu.models.resnet import make_norm
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import ParallelismPlan
from apex_tpu.parallel.mesh import DP_AXIS


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--plan", default="ddp",
                   choices=["ddp", "zero1", "fsdp", "fsdp+tp"],
                   help="ParallelismPlan preset. 'ddp' is the reference "
                        "recipe (SGD + amp, replicated params); 'zero1' / "
                        "'fsdp' switch to the sharded Adam optimizers "
                        "(DistributedFusedAdam / FSDPAdam — the sharded "
                        "families are Adam/LAMB) and run fp32 (O0). "
                        "'fsdp+tp' resolves the dp×tp mesh; the ResNet "
                        "defines no TP layers, so tp replicates compute")
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet18", "resnet50"])
    p.add_argument("-b", "--batch-size", type=int, default=64,
                   help="GLOBAL batch (split over dp)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--loss-scale", default=None,
                   help="'dynamic' or a float (default: policy preset)")
    p.add_argument("--keep-batchnorm-fp32", default=None)
    p.add_argument("--sync_bn", action="store_true",
                   help="cross-device SyncBatchNorm (ref --sync_bn)")
    p.add_argument("--deterministic", action="store_true")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--resume", default="", metavar="PATH",
                   help="path to a checkpoint to resume from (the "
                        "reference's --resume: restores model, optimizer, "
                        "amp and batch-norm state plus the iteration); "
                        "'auto' discovers the latest VALID checkpoint in "
                        "--checkpoint-dir (torn/corrupt ones are skipped)")
    p.add_argument("--checkpoint-dir", default="",
                   help="save the full train state here (end of run, plus "
                        "every --save-freq iters) — atomic, manifested "
                        "resilience.CheckpointManager checkpoints")
    p.add_argument("--save-freq", type=int, default=0,
                   help="checkpoint every N iters (0 = only at the end)")
    p.add_argument("--keep-last-n", type=int, default=3,
                   help="checkpoint retention (plus every --keep-every-k)")
    p.add_argument("--keep-every-k", type=int, default=0)
    p.add_argument("--async-save", action="store_true",
                   help="serialize checkpoints off the critical path")
    p.add_argument("--preempt-save", action="store_true",
                   help="on SIGTERM: save a checkpoint at the agreed step "
                        "and exit cleanly (requires --checkpoint-dir)")
    p.add_argument("--elastic", action="store_true",
                   help="drive the sharded loop through TrainSupervisor "
                        "with an elastic checkpoint spec: a checkpoint "
                        "saved here restores at a DIFFERENT --plan dp "
                        "degree (restart manifest names the legal ones); "
                        "needs --checkpoint-dir and a zero1/fsdp plan")
    return p.parse_args(argv)


# jitted-step cache keyed by every config knob the traced program depends
# on: repeat runs of one config (the L1 determinism double-run, the
# O0-vs-O2 comparison, baseline regeneration) reuse the SAME jit object and
# pay zero recompiles. Initial state is rebuilt per call (deterministic
# from the seed), so cached-step runs return identical losses.
_STEP_CACHE = {}


def _step_key(args):
    return (args.plan, args.arch, args.batch_size, args.image_size,
            args.num_classes, args.lr, args.momentum, args.weight_decay,
            args.opt_level, args.loss_scale, args.keep_batchnorm_fp32,
            args.sync_bn)


def train(args) -> List[float]:
    """Run the loop; returns the per-iteration loss list (the L1 contract)."""
    plan = ParallelismPlan.preset(args.plan)
    print(plan.describe())
    mesh = plan.mesh()
    dp = mesh.shape[DP_AXIS]
    if args.batch_size % dp != 0:
        raise ValueError(f"batch {args.batch_size} % dp {dp} != 0")

    arch = {"resnet18": ResNet18, "resnet50": ResNet50}[args.arch]
    model = arch(num_classes=args.num_classes,
                 norm=make_norm(sync_bn=args.sync_bn))

    rng = jax.random.PRNGKey(args.seed)
    sample = jnp.zeros((2, args.image_size, args.image_size, 3))
    variables = model.init(rng, sample, use_running_average=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    print("  modeled hbm_params_bytes:",
          {k: int(v)
           for k, v in plan.hbm_params_bytes(params, world=dp).items()})

    if plan.data != "ddp":
        if args.opt_level != "O0":
            raise SystemExit(
                f"--plan {args.plan} runs the sharded fp32 Adam loop; "
                "pass --opt-level O0 (this loop does not compose amp "
                "with FSDP)")
        return _train_sharded(args, plan, mesh, model, params, batch_stats)

    overrides = {}
    if args.loss_scale is not None:
        overrides["loss_scale"] = (
            "dynamic" if args.loss_scale == "dynamic"
            else float(args.loss_scale))
    if args.keep_batchnorm_fp32 is not None:
        overrides["keep_batchnorm_fp32"] = (
            args.keep_batchnorm_fp32 in ("True", "true", True))
    amp_state, policy = amp.initialize(params, args.opt_level, **overrides)

    tx = FusedSGD(lr=args.lr, momentum=args.momentum,
                  weight_decay=args.weight_decay)
    opt_state = tx.init(amp_state.master_params)
    ddp = plan.ddp()

    cached = _STEP_CACHE.get(_step_key(args))
    if cached is not None:
        return _run_loop(args, cached, amp_state, opt_state, batch_stats)

    # O1: per-op autocast transform around the model apply — whitelisted ops
    # (convs/matmuls) run in the compute dtype, reductions in fp32 (the ref's
    # monkey-patch casting; without this wrap O1 would train identically to
    # O0, params and inputs both being fp32)
    def apply_model(variables, images):
        return model.apply(variables, images, use_running_average=False,
                           mutable=["batch_stats"])

    if policy.compute_dtype is not None:
        apply_model = autocast(apply_model, policy.compute_dtype)

    def body(amp_state, opt_state, batch_stats, images, labels):
        def loss_fn(masters):
            model_p = ddp.replicate(amp.cast_params(
                masters, policy, amp_state.is_norm_param))
            logits, upd = apply_model(
                {"params": model_p, "batch_stats": batch_stats},
                amp.cast_inputs(images, policy))
            onehot = jax.nn.one_hot(labels, args.num_classes)
            loss = -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot, -1))
            return amp.scale_loss(loss, amp_state), (loss, upd["batch_stats"])

        grads, (loss, new_bs) = jax.grad(loss_fn, has_aux=True)(
            amp_state.master_params)
        grads = ddp.average_gradients(grads)
        new_amp, new_opt, _ = amp.apply_grads_with_optimizer(
            amp_state, grads, tx, opt_state)
        # Without --sync_bn each dp shard sees different batch stats (the
        # reference keeps per-rank stats and checkpoints rank 0's); here the
        # single program keeps their mean — a strictly better estimate.
        def pmean(s):
            if DP_AXIS not in jax.typeof(s).vma:
                s = jax.lax.pcast(s, DP_AXIS, to="varying")
            return jax.lax.pmean(s, DP_AXIS)

        new_bs = jax.tree_util.tree_map(pmean, new_bs)
        loss = pmean(loss)
        return new_amp, new_opt, new_bs, loss

    replicated = jax.tree_util.tree_map(lambda _: P(), amp_state)
    step = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(replicated,
                  jax.tree_util.tree_map(lambda _: P(), opt_state),
                  jax.tree_util.tree_map(lambda _: P(), batch_stats),
                  P(DP_AXIS), P(DP_AXIS)),
        out_specs=(replicated,
                   jax.tree_util.tree_map(lambda _: P(), opt_state),
                   jax.tree_util.tree_map(lambda _: P(), batch_stats),
                   P()),
    ))

    _STEP_CACHE[_step_key(args)] = step
    return _run_loop(args, step, amp_state, opt_state, batch_stats)


def _train_sharded(args, plan, mesh, model, params, batch_stats
                   ) -> List[float]:
    """zero1 / fsdp: the plan-built sharded-Adam loop (fp32). Replaces the
    old hand-threaded optimizer wiring with ``plan.build_optimizer``; the
    batch stats stay replicated and dp-meaned exactly like the ddp path."""
    from jax.sharding import PartitionSpec as P

    opt = plan.build_optimizer(lr=args.lr, weight_decay=args.weight_decay)
    pspecs = jax.tree_util.tree_map(lambda _: P(), params)
    bspecs = jax.tree_util.tree_map(lambda _: P(), batch_stats)
    shard = jax.tree_util.tree_map(lambda _: P(DP_AXIS), params)

    def loss_fn(model_p, bs, images, labels):
        logits, upd = model.apply(
            {"params": model_p, "batch_stats": bs}, images,
            use_running_average=False, mutable=["batch_stats"])
        onehot = jax.nn.one_hot(labels, args.num_classes)
        loss = -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot, -1))
        return loss, upd["batch_stats"]

    def pmean(s):
        if hasattr(jax, "typeof") and DP_AXIS not in jax.typeof(s).vma:
            s = jax.lax.pcast(s, DP_AXIS, to="varying")
        return jax.lax.pmean(s, DP_AXIS)

    if plan.data == "fsdp":
        from apex_tpu.fsdp import FSDPAdamState

        fsdp = plan.fsdp()
        meta = fsdp.meta(params)
        sspec = (FSDPAdamState(count=P(), master=shard, mu=shard, nu=shard),
                 bspecs)

        def init_fn(p, bs):
            return opt.init(p), bs

        def body(st, images, labels):
            ostate, bs = st

            def wrapped(master):
                return loss_fn(fsdp.gather(master, meta), bs, images,
                               labels)

            (loss, new_bs), g = jax.value_and_grad(
                wrapped, has_aux=True)(ostate.master)
            ostate = opt.step(g, ostate)
            new_bs = jax.tree_util.tree_map(pmean, new_bs)
            return (ostate, new_bs), pmean(loss)
    else:  # zero1
        from apex_tpu.contrib.optimizers.distributed_fused_adam import (
            DistAdamState,
        )

        sspec = (pspecs,
                 DistAdamState(count=P(), master=shard, mu=shard, nu=shard),
                 bspecs)

        def init_fn(p, bs):
            return p, opt.init(p), bs

        def body(st, images, labels):
            p, ostate, bs = st
            (loss, new_bs), g = jax.value_and_grad(
                loss_fn, has_aux=True)(p, bs, images, labels)
            p, ostate = opt.step(g, ostate, p)
            new_bs = jax.tree_util.tree_map(pmean, new_bs)
            return (p, ostate, new_bs), pmean(loss)

    init = jax.jit(jax.shard_map(
        init_fn, mesh=mesh, in_specs=(pspecs, bspecs), out_specs=sspec,
        check_vma=False))
    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(sspec, P(DP_AXIS), P(DP_AXIS)),
        out_specs=(sspec, P()), check_vma=False))
    state = init(params, batch_stats)

    if args.elastic:
        return _run_elastic_sharded(args, plan, mesh, opt, params, state,
                                    step)

    mgr = _make_manager(args) if args.checkpoint_dir else None
    state, start_it = _resolve_resume(args, mgr, state)

    losses = []
    data_rng = jax.random.PRNGKey(args.seed + 1)
    t0 = time.perf_counter()
    for it in range(start_it, args.iters):
        k = jax.random.fold_in(data_rng, it)
        images = jax.random.normal(
            k, (args.batch_size, args.image_size, args.image_size, 3))
        labels = jax.random.randint(
            jax.random.fold_in(k, 1), (args.batch_size,), 0,
            args.num_classes)
        state, loss = step(state, images, labels)
        losses.append(float(loss))
        if it % args.print_freq == 0 or it == args.iters - 1:
            dt = time.perf_counter() - t0
            ips = args.batch_size * (it - start_it + 1) / dt
            print(f"iter {it:4d}  loss {losses[-1]:.6f}  {ips:,.1f} img/s")
        if mgr is not None and (
                it == args.iters - 1
                or (args.save_freq and (it + 1) % args.save_freq == 0)):
            p = mgr.save(state, it + 1)
            print(f"=> saved checkpoint '{p}' (iter {it + 1})")
    if mgr is not None:
        mgr.close()
    return losses


def _run_elastic_sharded(args, plan, mesh, opt, params, state,
                         step) -> List[float]:
    """The --elastic sharded loop: TrainSupervisor drives the step with an
    elastic spec stamped into every checkpoint, so a preempted/killed run
    relaunched on a different slice (different dp degree, new --plan mesh)
    resumes through the reshard path — the restart manifest's
    ``legal_resume_dp`` names the degrees the shard arithmetic divides."""
    from apex_tpu.parallel.mesh import DP_AXIS
    from apex_tpu.resilience import (
        PreemptionHandler,
        TrainSupervisor,
        replicated_spec,
    )

    dp = mesh.shape[DP_AXIS]
    ospec = opt.elastic_spec(params, dp)
    repl = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda _: replicated_spec(), tree)
    # mirror the state tuples _train_sharded builds: batch stats (and
    # zero1's replicated param copy) never reshard
    if plan.data == "fsdp":
        espec = (ospec, repl(state[1]))
    else:
        espec = (repl(params), ospec, repl(state[2]))
    mgr = plan.checkpoint_manager(
        args.checkpoint_dir, allow_reshard=True,
        keep_last_n=args.keep_last_n, keep_every_k=args.keep_every_k,
        async_save=args.async_save)

    losses: List[float] = []
    data_rng = jax.random.PRNGKey(args.seed + 1)

    def step_fn(st, it):
        k = jax.random.fold_in(data_rng, it)
        images = jax.random.normal(
            k, (args.batch_size, args.image_size, args.image_size, 3))
        labels = jax.random.randint(
            jax.random.fold_in(k, 1), (args.batch_size,), 0,
            args.num_classes)
        st, loss = step(st, images, labels)
        losses.append(float(loss))
        if it % args.print_freq == 0 or it == args.iters - 1:
            print(f"iter {it:4d}  loss {losses[-1]:.6f}")
        return st

    sup = TrainSupervisor(
        step_fn, mgr, elastic=espec, dp_degree=dp,
        save_freq=args.save_freq or args.iters,
        preemption=PreemptionHandler() if args.preempt_save else None)
    start_it = 0
    info = TrainSupervisor.read_restart(args.checkpoint_dir)
    if info is not None or mgr.latest_valid() is not None:
        state, start_it = sup.resume(state)
        prev_dp = info.get("dp_degree") if info else dp
        print(f"=> elastic resume at iter {start_it} "
              f"(checkpoint dp={prev_dp}, live dp={dp})")
        if start_it >= args.iters:
            raise SystemExit(
                f"checkpoint is already at iter {start_it} >= --iters "
                f"{args.iters}; nothing to resume (raise --iters)")
    state, nxt = sup.run(state, start_it, args.iters - start_it)
    if sup.exited != "killed":
        mgr.save(state, nxt, elastic=espec)
    mgr.close()
    if sup.exited == "preempted":
        print(f"=> preempted at iter {nxt}; restart manifest written")
    return losses


def _make_manager(args):
    from apex_tpu.resilience import CheckpointManager

    return CheckpointManager(
        args.checkpoint_dir, keep_last_n=args.keep_last_n,
        keep_every_k=args.keep_every_k, async_save=args.async_save)


def _resolve_resume(args, mgr, state):
    """The resume contract shared by the ddp and sharded loops: restore
    the train state and continue at the saved iteration. The manager
    re-hangs the flat leaves on the LIVE treedef after verifying the
    manifest fingerprint + per-leaf checksums — a torn or revision-skewed
    checkpoint is refused, not mis-bound. ``--resume auto`` is a standing
    relaunch flag: no checkpoint yet (first launch, or all torn) means
    start fresh, not die."""
    from apex_tpu.resilience import CheckpointError

    start_it = 0
    if not args.resume:
        return state, start_it
    restore_mgr = mgr or _make_manager(args)
    if args.resume == "auto":
        if not args.checkpoint_dir:
            raise SystemExit("--resume auto needs --checkpoint-dir")
        path = restore_mgr.latest_valid()
    else:
        path = args.resume
    if path is None:
        print(f"=> no valid checkpoint in '{args.checkpoint_dir}' yet; "
              "starting fresh")
        return state, start_it
    try:
        state, start_it = restore_mgr.restore(target=state, path=path)
    except CheckpointError as e:
        raise SystemExit(f"=> {e}")
    print(f"=> loaded checkpoint '{path}' (resuming at iter {start_it})")
    if start_it >= args.iters:
        raise SystemExit(
            f"checkpoint is already at iter {start_it} >= --iters "
            f"{args.iters}; nothing to resume (raise --iters)")
    return state, start_it


def _run_loop(args, step, amp_state, opt_state, batch_stats) -> List[float]:
    from apex_tpu.resilience import PreemptionHandler

    state = (amp_state, opt_state, batch_stats)
    mgr = _make_manager(args) if args.checkpoint_dir else None
    state, start_it = _resolve_resume(args, mgr, state)
    amp_state, opt_state, batch_stats = state

    pre = None
    if args.preempt_save:
        if mgr is None:
            raise SystemExit("--preempt-save needs --checkpoint-dir")
        pre = PreemptionHandler()

    def save(state, it):
        p = mgr.save(state, it)
        print(f"=> saved checkpoint '{p}' (iter {it})")

    losses = []
    data_rng = jax.random.PRNGKey(args.seed + 1)
    t0 = time.perf_counter()
    for it in range(start_it, args.iters):
        k = jax.random.fold_in(data_rng, it)
        images = jax.random.normal(
            k, (args.batch_size, args.image_size, args.image_size, 3))
        labels = jax.random.randint(
            jax.random.fold_in(k, 1), (args.batch_size,), 0,
            args.num_classes)
        amp_state, opt_state, batch_stats, loss = step(
            amp_state, opt_state, batch_stats, images, labels)
        losses.append(float(loss))
        if it % args.print_freq == 0 or it == args.iters - 1:
            dt = time.perf_counter() - t0
            ips = args.batch_size * (it - start_it + 1) / dt
            print(f"iter {it:4d}  loss {losses[-1]:.6f}  {ips:,.1f} img/s")
        if pre is not None:
            save_at = pre.sync_save_step(it)
            if save_at is not None:
                # preemption: all processes agreed on this step — save
                # synchronously inside the grace window and stop
                p = mgr.save((amp_state, opt_state, batch_stats),
                             save_at + 1, block=True)
                print(f"=> saved checkpoint '{p}' (iter {save_at + 1})")
                mgr.close()
                print(f"=> preempted at iter {save_at}; exiting after save")
                return losses
        if mgr is not None and (
                it == args.iters - 1
                or (args.save_freq and (it + 1) % args.save_freq == 0)):
            save((amp_state, opt_state, batch_stats), it + 1)
    if mgr is not None:
        mgr.close()  # drain async saves before the process exits
    return losses


def main(argv=None):
    args = parse_args(argv)
    losses = train(args)
    print(f"final loss: {losses[-1]:.6f}")
    return losses


if __name__ == "__main__":
    main()
