"""Spreads of a cell's runs, from the logs that ``chain.sh`` leaves.

    python3 perfbench/tools/spread.py chiprun_out/<dir> [<cell>]

For every cell and end-to-end metric: each set's median and spread (the
distance between the first and third quartile, ``statistics.quantiles``, over
the median), a set being the runs of one seed list in the order they were
made; and every number compared, with the largest value any run read.
"""
import glob
import json
import os
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    root = sys.argv[1]
    only = sys.argv[2] if len(sys.argv) > 2 else None
    runs = {}
    for path in sorted(glob.glob(os.path.join(root, "*.t0.log")), key=lambda p: p.split(".r")[-1]):
        with open(path) as f:
            lines = f.read().strip().splitlines()
        if not lines:
            continue
        try:
            res = json.loads(lines[-1])
        except ValueError:
            continue
        if "correct" not in res:
            continue
        cell = os.path.basename(path).split(".s")[0]
        if only and cell != only:
            continue
        runs.setdefault(cell, []).append((os.path.basename(path), res))
    for cell, rs in runs.items():
        print(f"== {cell}: {len(rs)} runs, correct {sum(r['correct'] for _, r in rs)}")
        half = len(rs) // 2
        sets = [rs[:half], rs[half:]] if len(rs) >= 8 else [rs]
        for name in rs[0][1]["metrics"]:
            row = []
            for s in sets:
                vals = [r["metrics"][name]["value"] for _, r in s]
                row.append((statistics.median(vals), spread(vals) if len(vals) >= 2 else float("nan"),
                            [round(v, 3) for v in vals]))
            widest = max(sp for _, sp, _ in row)
            print(f"  {name}: widest spread {widest:.5f}; " + "; ".join(
                f"set{i + 1} median {m:.4f} spread {sp:.5f} first {vals[0]} rest {vals[1:]}"
                for i, (m, sp, vals) in enumerate(row)))
        for name in rs[0][1]["compared"]:
            vals = [r["compared"][name]["value"] for _, r in rs]
            print(f"  compared {name}: max {max(vals)!r} over {len(vals)} runs")


if __name__ == "__main__":
    main()
