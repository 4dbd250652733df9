"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration's file, its traffic
mix's file (``perfbench/traffic/<traffic>.json``), the runner of the mix's
``kind`` (``perfbench/kinds/<kind>.py``), the comparison's limits
(``perfbench/limits/<cell>.json``) and, in a traced run, one reader per
per-layer metric (``perfbench/metrics/<metric>.py``): all by name, so a new
cell, configuration or metric is new files and entries, and no edit here.

It measures the chip: with no TPU, or fewer chips than the cell asks for, it
exits with code 3 and prints no result. The last line of standard output is
the result; every line before it is a fact about the run.
"""

from __future__ import annotations

import time

_T_PROCESS_START = time.perf_counter()

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have: {sorted(cells)})")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_for(entries, cell: str):
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, bench_path: str = None) -> dict:
    """One run of one cell; the result line as a dict. ``bench_path`` names
    another ``BENCHMARK.json`` with its own data files beside it (the tests'
    toy cells); runners and metric readers are always this directory's."""
    bench_path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
    bench = _load_json(bench_path)
    base = os.path.dirname(os.path.abspath(bench_path))
    data = os.path.join(base, bench["paths"][0])
    cell, config_entry = find_cell(bench, workload)
    config = _load_json(os.path.join(base, config_entry["file"]))
    mix = _load_json(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    limits = _load_json(os.path.join(data, "limits", workload + ".json"))["limits"]
    ctx = harness.Context(cell=workload, config=config, mix=mix, chips=int(cell["chips"]),
                          seed=int(seed), seconds=float(seconds), trace=bool(trace),
                          t_process_start=_T_PROCESS_START, require_tpu=require_tpu)
    harness.setup_jax(ctx)
    kind = _module(os.path.join(HERE, "kinds", mix["kind"] + ".py"), "kind_" + mix["kind"])
    out = kind.run(ctx, limits)

    device = {"platform": ctx.devices[0].platform, "kind": ctx.devices[0].device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": all(n["ok"] for n in out["numbers"]),
              "attempted": out["attempted"], "failed": out["failed"], "metrics": {}}
    if not trace:
        for m in metrics_for(bench["end_to_end"], workload):
            value = out["end_to_end"][m["name"]]
            result["metrics"][m["name"]] = {"value": value if value == value else None,
                                            "unit": m["unit"]}
    else:
        tr = out["tracer"].reduce() if out.get("tracer") else None
        if tr is not None:
            import xplane

            summary = xplane.summary(tr)
            device["busy_s"] = summary.get("busy_s")
            device["window_s"] = summary.get("window_s")
            result["breakdown"] = summary.get("breakdown")
            out["facts"]["trace_summary"] = summary
        for m in metrics_for(bench["per_layer"], workload):
            reader = _module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                             "metric_" + m["name"].replace(".", "_"))
            value = reader.read(out["facts"], tr)
            if value is not None and value == value:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["device"] = device
    result["compared"] = {n["name"]: {k: v for k, v in n.items() if k != "name"}
                          for n in out["numbers"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 3
    # each number compared beside its limit: last on standard error, and last
    # in the result's line
    for name, n in result["compared"].items():
        print(f"compared {name}: {n['value']!r} limit {n['limit']!r} "
              f"{'ok' if n['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
