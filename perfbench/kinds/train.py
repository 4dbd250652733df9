"""A training cell: the program's jitted step (forward, backward, FusedAdam)
driven with a fresh seeded batch every step.

The entry is ``bench.train_step_fn`` (what ``bench.build_train_step`` wraps)
over ``apex_tpu.parallel.mesh.build_mesh``; the weights are the benchmark's
own, from the seed. Set-up builds the one compiled step with its state and
drives it through the job's first three steps, through the window's own call
and feed; those steps are also the warm-up, and what they leave behind (each
loss, the first gradient as the optimizer got it, the parameters' change) is
what ``correct`` compares with the plain reference once the window has closed.
"""

from __future__ import annotations

import collections
import gc
import time
from typing import Any, Dict

import numpy as np

import counts
import reference
import traffic
import weights
from harness import Context, TraceWindow, compared, memory_by_device, memory_peak_bytes, span

CHECK_STEPS = 3          # the job's first steps, compared with the reference
TRACE_STEPS = 3          # steps inside the profiler's window of a traced run


def _program(ctx: Context):
    """(step, new_state, feed, make_p0, rows, seq): the jitted step, its
    state made from the seed (parameters and the optimizer's own zeros), the
    feed that places a step's batch, and the initial parameters made anew
    (for the change, and for the reference). All read ``ctx.seed`` when they
    are called."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import bench
    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.transformer.testing import GPTConfig, gpt_param_specs

    cfg, mix, job = ctx.config, ctx.mix, ctx.config["train"]
    dp, tp = int(mix["mesh"]["dp"]), int(mix["mesh"]["tp"])
    if dp * tp != ctx.chips:
        raise ValueError(f"mesh dp x tp = {dp * tp}, the cell asks for {ctx.chips} chips")
    gpt = GPTConfig(vocab_size=cfg["assumed"]["padded_vocab_size"],
                    max_seq=cfg["n_positions"], hidden=cfg["n_embd"],
                    num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                    dtype=jnp.dtype(cfg["assumed"]["param_dtype"]),
                    remat=bool(job["remat"]), remat_policy=job["remat_policy"])
    mesh = build_mesh(tp=tp, pp=1, sp=1, dp=dp, devices=ctx.devices)
    step, opt = bench.train_step_fn(gpt, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), gpt_param_specs(gpt))
    data = NamedSharding(mesh, P("dp"))
    rows, seq = int(mix["rows_per_chip"]) * dp, int(mix["seq"])

    def make_p0():
        return weights.make_params(cfg, ctx.seed, shardings)

    def feed(i: int):
        tok, tgt = traffic.train_batch(ctx.seed, i, rows, seq, cfg["vocab_size"])
        return jax.device_put(tok, data), jax.device_put(tgt, data)

    init_state = jax.jit(opt.init)

    def new_state():
        params = make_p0()
        return params, init_state(params)

    return step, new_state, feed, make_p0, rows, seq


def first_steps(ctx: Context, step, params, opt_state, feed, make_p0):
    """The job's first steps through the window's own call and feed. Returns
    the state as step 4 will take it, and what the steps left behind."""
    import jax

    n_head = ctx.config["n_head"]
    b1 = float(ctx.config["train"]["betas"][0])
    grad_norms = jax.jit(lambda mu: reference.leaf_norms(
        jax.tree.map(lambda m: m / (1.0 - b1), mu), n_head))
    grad_samples = jax.jit(lambda mu: reference.leaf_samples(
        jax.tree.map(lambda m: m / (1.0 - b1), mu), n_head))
    change_norms = jax.jit(lambda a, b: reference.change_norms(a, b, n_head))
    seen: Dict[str, Any] = {"losses": []}
    for i in range(1, CHECK_STEPS + 1):
        params, opt_state, loss = step(params, opt_state, *feed(i))
        seen["losses"].append(float(loss))
        if i == 1:      # Adam's first moment after one step is (1 - b1)·g
            seen["grad_norms"] = jax.device_get(grad_norms(opt_state.mu))
            seen["grad_samples"] = jax.device_get(grad_samples(opt_state.mu))
        if i == 2:      # the change the reference follows (two steps)
            seen["change_norms"] = jax.device_get(change_norms(params, make_p0()))
    return params, opt_state, seen


def compare(seen, ref, limits) -> list:
    """The numbers compared, each beside its limit."""
    rows = []
    g, leaf = reference.worst_leaf_gap(seen["grad_norms"], ref["grad_norms"])
    rows.append(compared("grad_norm_gap", g, limits["grad_norm_gap"]))
    rows[-1]["leaf"] = leaf
    # the gaps of norms read a bias and are blind to noise (an error at right
    # angles to the gradient changes its norm in the second order only), so
    # the gradient's relative error is read too, from evenly spaced elements
    worst, leaf, overall = reference.sampled_error(seen["grad_samples"], ref["grad_samples"])
    rows.append(compared("grad_error", overall, limits["grad_error"]))
    rows.append(compared("grad_error_worst_leaf", worst, limits["grad_error_worst_leaf"]))
    rows[-1]["leaf"] = leaf
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: left out of the change, by a rule on the gradient
    _, gr = reference.flatten_norms(ref["grad_norms"])
    keep = gr >= 1e-3 * float(np.median(gr))
    c, leaf = reference.worst_leaf_gap(seen["change_norms"], ref["change_norms"], keep)
    rows.append(compared("update_norm_gap", c, limits["update_norm_gap"]))
    rows[-1]["leaf"] = leaf
    rows[-1]["leaves_left_out"] = int((~keep).sum())
    return rows


def run_reference(ctx: Context, make_p0, n_rows: int, seq: int, **kw) -> Dict:
    import jax.numpy as jnp

    batches = []
    for i in range(1, CHECK_STEPS + 1):
        tok, tgt = traffic.train_batch(ctx.seed, i, n_rows, seq, ctx.config["vocab_size"])
        batches.append((jnp.asarray(tok), jnp.asarray(tgt)))
    return reference.train_reference(
        make_p0, batches, ctx.config["train"], ctx.config["n_head"],
        float(ctx.config["layer_norm_epsilon"]),
        int(ctx.mix["check"]["reference_rows_per_block"]), **kw)


def run(ctx: Context, limits) -> Dict[str, Any]:
    import jax

    step, new_state, feed, make_p0, rows, seq = _program(ctx)
    params, opt_state = new_state()
    params, opt_state, seen = first_steps(ctx, step, params, opt_state, feed, make_p0)
    setup_s = time.perf_counter() - ctx.t_process_start
    ctx.info(phase="set-up done", setup_s=setup_s, first_losses=seen["losses"],
             cache_hits=ctx.counters.cache_hits, cache_misses=ctx.counters.cache_misses)

    # -- the measured window ---------------------------------------------------
    # Steps are dispatched up to ``in_flight_steps`` ahead of the oldest loss
    # not yet read, as a loop that logs every few steps runs: a host that
    # stalls for a second or two (seen about once in a dozen runs on the
    # one-chip machines, PERF.md) then leaves the device fed. A step is
    # dispatched only while the queue, by the pace of the losses read so far,
    # would end inside the window, so the window closes on the last step's
    # loss, within a step short of ``--seconds``, with every step finished.
    depth = int(ctx.mix["in_flight_steps"])
    tracer = TraceWindow(ctx) if ctx.trace else None
    pending = collections.deque()
    ctx.counters.mark()
    t0 = time.perf_counter()
    i = done = CHECK_STEPS
    first_read = None           # (step, instant) of the first loss read
    step_s = 0.0                # seconds a step, from the first loss read to the last
    slowest = {"batch": 0.0, "dispatch": 0.0, "loss_read": 0.0}    # where a host stall sat
    while True:
        now = time.perf_counter() - t0
        room = now + (len(pending) + 1) * step_s <= ctx.seconds
        if pending and (len(pending) >= depth or not room):
            with span("hb.loss_read"):
                done, loss = pending.popleft()
                last_loss = float(loss)
            t1 = time.perf_counter()
            slowest["loss_read"] = max(slowest["loss_read"], t1 - t0 - now)
            if first_read is None:
                first_read = (done, t1)
            else:
                step_s = (t1 - first_read[1]) / (done - first_read[0])
            if tracer and tracer.active and done >= trace_until:
                tracer.stop()
            if tracer and not tracer.done and not tracer.active and done >= CHECK_STEPS + 2:
                tracer.start()
                trace_until = done + 2 + TRACE_STEPS
            continue
        if not room:
            break
        i += 1
        with span("hb.batch"):
            tok, tgt = feed(i)
        fed = time.perf_counter() - t0
        with span("hb.dispatch"):
            params, opt_state, loss = step(params, opt_state, tok, tgt)
        pending.append((i, loss))
        slowest["batch"] = max(slowest["batch"], fed - now)
        slowest["dispatch"] = max(slowest["dispatch"], time.perf_counter() - t0 - fed)
    if tracer:
        tracer.stop()
    ctx.counters.close()
    window_s = t1 - t0
    steps = done - CHECK_STEPS
    tokens = steps * rows * seq
    mem_peak = memory_peak_bytes(ctx)
    ctx.info(phase="window closed", steps=steps, window_s=window_s, last_loss=last_loss,
             step_s=step_s, slowest_host_s=slowest,
             compiles_in_window=ctx.counters.in_window,
             peak_bytes_in_use=memory_by_device(ctx))

    # -- the program's state is freed, then the reference runs -------------------
    del params, opt_state, step, loss, tok, tgt
    gc.collect()
    t_ref = time.perf_counter()
    ref = run_reference(ctx, make_p0, rows, seq)
    numbers = compare(seen, ref, limits)
    numbers.append(compared("compiles_in_window", ctx.counters.in_window, 0))
    # the steps' losses are printed and not compared: no control or fault
    # reads ten times what sound runs do (perfbench/limits, PERF.md)
    ctx.info(phase="reference done", reference_s=time.perf_counter() - t_ref,
             reference_losses=ref["losses"],
             loss_gap=max(abs(a - b) for a, b in zip(seen["losses"], ref["losses"])))

    return {
        "attempted": steps, "failed": 0, "numbers": numbers,
        "memory_peak_bytes": mem_peak,
        "end_to_end": {"train_tokens_per_s": tokens / window_s, "setup_s": setup_s},
        "facts": {"kind": "train", "tokens_per_s": tokens / window_s,
                  "rows": rows, "seq": seq, "chips": ctx.chips, "steps": steps,
                  "model": ctx.config, "peaks": ctx.peaks},
        "tracer": tracer,
    }


def readings(ctx: Context, seeds, control_seeds, fault_seeds) -> list:
    """For the limits: what the comparison reads on each of ``seeds`` (no
    window is needed), and on the first few what it reads with the reference
    computed on int8's and on fp8's grid put in the program's place (the
    controls) and with the fault planted there that leaves half of the batch
    out."""
    step, new_state, feed, make_p0, rows, seq = _program(ctx)
    loose = {k: float("inf") for k in ("grad_norm_gap", "update_norm_gap",
                                       "grad_error", "grad_error_worst_leaf")}
    out = []
    for seed in seeds:
        ctx.seed = int(seed)
        params, opt_state = new_state()
        params, opt_state, seen = first_steps(ctx, step, params, opt_state, feed, make_p0)
        del params, opt_state
        gc.collect()
        t = time.perf_counter()
        ref = run_reference(ctx, make_p0, rows, seq)
        rec = {"seed": int(seed), "reference_s": time.perf_counter() - t,
               "program": {n["name"]: n for n in compare(seen, ref, loose)},
               "losses": {"program": seen["losses"], "reference": ref["losses"]},
               "loss_gap": max(abs(a - b) for a, b in zip(seen["losses"], ref["losses"]))}
        if seed in control_seeds:
            for grid in ("int8", "fp8"):
                low = run_reference(ctx, make_p0, rows, seq, quant=grid)
                rec["control_" + grid] = {n["name"]: n for n in compare(low, ref, loose)}
        if seed in fault_seeds:
            half = run_reference(ctx, make_p0, rows, seq, rows=slice(0, rows // 2))
            rec["fault_half_batch"] = {n["name"]: n for n in compare(half, ref, loose)}
        ctx.info(**rec)
        out.append(rec)
    return out
