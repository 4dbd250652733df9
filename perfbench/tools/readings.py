"""Readings for the comparison's limits, many seeds in one process.

    python3 perfbench/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--control 3] [--faults 3] [--seconds 12] [--set key=value ...]

Prints one line of readings a seed (see each kind's ``readings``). ``--set``
overrides a number of the traffic mix for this call alone (``rate_rps=0.9``):
the rate sweep is this tool with one seed a rate. Not part of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import harness  # noqa: E402
import run as runner  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--sweep", default=None,
                    help="key=v1,v2,...: one run of the first seed for each value")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    bench = runner._load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell, entry = runner.find_cell(bench, args.workload)
    config = runner._load_json(os.path.join(harness.ROOT, entry["file"]))
    mix = runner._load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    for kv in args.set:
        k, v = kv.split("=", 1)
        mix[k] = json.loads(v)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = harness.Context(cell=args.workload, config=config, mix=mix, chips=int(cell["chips"]),
                          seed=seeds[0], seconds=args.seconds, trace=False,
                          t_process_start=T0)
    harness.setup_jax(ctx)
    kind = runner._module(os.path.join(HERE, "kinds", mix["kind"] + ".py"), "kind")
    if args.sweep:
        key, values = args.sweep.split("=", 1)
        recs = []
        for v in values.split(","):
            mix[key] = json.loads(v)
            ctx.info(sweep=key, value=mix[key])
            recs += [dict(r, **{key: mix[key]}) for r in kind.readings(ctx, seeds[:1], [], [])]
    else:
        recs = kind.readings(ctx, seeds, seeds[:args.control], seeds[:args.faults])
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "mix": mix, "readings": recs}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
