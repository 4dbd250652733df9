"""Measured per-op profile of the flagship GPT train step.

The round's MFU question — *which op eats the step time?* — answered by
``apex_tpu.monitor.report.step_report``: run the bench.py train step under
``jax.profiler``, join per-instruction measured time with HLO flops/bytes
AND bytes-on-wire, print the per-op table (stderr, human) plus ONE
machine-parseable JSON line (stdout — the ``bench_comm.py`` convention,
schema-stamped by ``monitor.sink.json_record``).

Run: ``python benchmarks/profile_step.py [--steps N] [--top N]`` on a
machine with a TPU (full bench shape). It measures, so it fails when the
default backend is not ``tpu``.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--remat", action="store_true",
                    help="profile the remat=dots config instead of no-remat")
    args = ap.parse_args()

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"profile_step.py measures the chip; the default "
                         f"backend is {backend!r}")

    import bench
    from apex_tpu.monitor import (
        gpt_analytic_flops_per_token,
        json_record,
        step_report,
    )
    from apex_tpu.pyprof import format_measured_table

    batch, seq = bench.BATCH, bench.SEQ
    # profile the lightest remat that fits: no-remat (the MFU operating
    # point) unless it OOMs, then selective-dots, then full. The probe runs
    # through
    # the same non-donating wrapper the profiler jits (wrapping the jitted
    # step inlines it WITHOUT donate_argnums, so repeated profiled calls
    # reuse the param buffers; same function object -> same jit cache
    # entry, so the probe's compile is the profiler's compile).
    tries = ([(True, "dots"), (True, "full")] if args.remat
             else [(False, "full"), (True, "dots"), (True, "full")])
    last = None
    for remat, policy in tries:
        cfg = bench.flagship_config(seq, remat=remat, remat_policy=policy)
        train_step, params, opt_state, tok, tgt = bench.build_train_step(
            cfg, batch, seq)

        # everything the step produces is returned — returning only the
        # loss would let XLA dead-code-eliminate the optimizer update
        def step(params, opt_state, tok, tgt, _ts=train_step):
            return _ts(params, opt_state, tok, tgt)

        try:
            out = jax.jit(step)(params, opt_state, tok, tgt)
            jax.block_until_ready(jax.tree.leaves(out)[0])
            args.remat = remat
            break
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise  # only device OOM drops a tier
            last = e
            print(f"# remat={remat}/{policy} failed "
                  f"({type(e).__name__}), trying next", flush=True)
    else:
        raise RuntimeError(f"no profiling config fit: {last}")

    from apex_tpu.utils.platform import device_peaks

    peak = device_peaks().bf16_flops_per_s
    n_params = sum(x.size for x in jax.tree.leaves(params))
    flops_step = gpt_analytic_flops_per_token(
        n_params, cfg.num_layers, cfg.hidden, seq) * batch * seq
    header = (f"flagship GPT step profile | "
              f"{jax.devices()[0].device_kind} | batch={batch} "
              f"seq={seq} remat={args.remat}")
    print(header, file=sys.stderr)
    rep = step_report(step, params, opt_state, tok, tgt,
                      steps=args.steps, depth=args.depth, peak_flops=peak,
                      analytic_flops_per_step=flops_step)
    # human table on stderr; the one-line contract owns stdout
    print(format_measured_table(
        {"rows": rep.pop("rows"), "unattributed": rep.pop("unattributed"),
         "total_ms_per_step": rep["step_time_ms"],
         "coverage_pct": rep["coverage_pct"]}, top=args.top),
        file=sys.stderr, flush=True)
    print(json_record(metric="gpt2_124m_step_profile", batch=batch, seq=seq,
                      remat=bool(args.remat), **rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
