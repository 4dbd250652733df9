"""Measured per-op time attribution: trace parse + HLO cost join.

Reference capability: ``apex/pyprof/parse`` reads the nvprof/nsys SQLite
database into per-kernel records (``parse/kernel.py``: name, duration,
grid) and ``apex/pyprof/prof/output.py`` renders the joined
{op, time, flops, bytes} table. That answers the question static analysis
cannot: *which op eats the step time?*

TPU re-design: ``jax.profiler`` already writes a Chrome-trace JSON
(``*.trace.json.gz``) whose duration events on the device rows are named by
HLO instruction — the same names the compiled HLO text carries. So the
pipeline is: run the step under ``jax.profiler.trace`` → sum measured
durations per instruction name → join with the flops/bytes rows
:mod:`apex_tpu.pyprof.prof` computes from the compiled HLO → per-op
{name, scope, op, time, flops, bytes, MFU%, GB/s}. No SQLite, no kernel
string munging: the instruction name IS the join key on both sides.

Coverage is reported honestly: measured events that match no entry-
computation instruction (infeed, runtime bookkeeping) are kept as
unattributed rows, and ``coverage_pct`` says how much measured time the
join explained.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from apex_tpu.pyprof.prof import (
    _SKIP_OPS,
    _comp_flops,
    _conv_flops,
    _dot_flops,
    _nbytes,
    _parse_hlo,
    _scopes_of,
)


def load_trace_events(
    log_dir: str,
) -> Tuple[Dict[str, Tuple[float, int]], float]:
    """Parse the newest trace run under ``log_dir``.

    Returns ``({name: (dur_us, exec_count)}, total_us)`` summed over
    complete ('X') events — the count matters for ops inside compiled
    loops (scan-over-layers bodies execute once per layer per step).
    Device-row events are preferred when any process is a device (host
    rows duplicate dispatch-side spans of the same names); on the CPU
    backend everything rides the host row and all events count.
    """
    runs = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*")))
    if not runs:
        raise FileNotFoundError(f"no profile runs under {log_dir}")
    paths = glob.glob(os.path.join(runs[-1], "*.trace.json.gz"))
    if not paths:
        raise FileNotFoundError(f"no *.trace.json.gz in {runs[-1]}")

    events: List[dict] = []
    pid_names: Dict[int, str] = {}
    for p in paths:
        tr = json.loads(gzip.open(p, "rb").read())
        for e in tr.get("traceEvents", []):
            if e.get("ph") == "M" and e.get("name") == "process_name":
                pid_names[e["pid"]] = e.get("args", {}).get("name", "")
            elif e.get("ph") == "X" and "dur" in e:
                events.append(e)

    device_pids = {p for p, n in pid_names.items() if "/device:" in n}
    if device_pids:
        events = [e for e in events if e.get("pid") in device_pids]
        keep = lambda name: True  # noqa: E731 — device rows are op spans
    else:
        # host-only trace (CPU backend): thunk execution spans carry bare
        # HLO instruction names; dispatch/wait machinery carries pythonic
        # ("$file:line fn") or prose ("Wait for ...", "Foo::Bar") names
        # whose durations OVERLAP the op spans and would corrupt totals.
        keep = lambda name: (  # noqa: E731
            name and " " not in name and "::" not in name
            and not name.startswith("$") and not name.startswith("PjitFunction")
        )

    dur: Dict[str, Tuple[float, int]] = {}
    total = 0.0
    for e in events:
        name = e.get("name", "")
        if not keep(name):
            continue
        d = float(e["dur"])
        t, c = dur.get(name, (0.0, 0))
        dur[name] = (t + d, c + 1)
        total += d
    if len(device_pids) > 1:
        # every device row carries its own copy of an SPMD op's span;
        # report the per-device MEAN of both time AND exec count so
        # ms/step and the flops/bytes scaling downstream (MFU%, GB/s)
        # both describe one chip, not the sum over all chips (advisor r3)
        n = float(len(device_pids))
        dur = {k: (t / n, max(1, round(c / n))) for k, (t, c) in dur.items()}
        total /= n
    return dur, total


def measured_op_table(
    fn: Callable,
    *args: Any,
    steps: int = 3,
    log_dir: Optional[str] = None,
    depth: int = 2,
    peak_flops: Optional[float] = None,
    **kwargs: Any,
) -> Dict[str, Any]:
    """Run ``steps`` executions of ``jit(fn)(*args)`` under the profiler and
    join measured per-op time with HLO flops/bytes.

    Returns ``{rows, coverage_pct, total_ms_per_step, unattributed}``:

    * ``rows`` — one dict per entry-computation instruction that measured
      nonzero time: ``{name, scope, op, time_ms (per step), flops, bytes,
      mfu_pct, gbps, pct}``, sorted by time.
    * ``unattributed`` — measured device events matching no instruction
      (runtime spans), as ``{name, time_ms}``.
    * ``coverage_pct`` — % of measured device time the rows explain.
    """
    if peak_flops is None:
        # mfu_pct is against this device's published peak unless the
        # caller brings one (a device kind without an entry is an error)
        from apex_tpu.utils.platform import device_peaks

        peak_flops = device_peaks().bf16_flops_per_s
    jitted = jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    # warmup outside the trace so compilation never pollutes timing
    out = jitted(*args, **kwargs)
    jax.block_until_ready(out)

    owns_dir = log_dir is None
    if owns_dir:
        log_dir = tempfile.mkdtemp(prefix="apex_tpu_prof_")
    import time as _time

    jax.profiler.start_trace(log_dir)
    try:
        # wall clock spans dispatch -> fence only (NOT the profiler
        # start/stop, which writes trace files); per-op capture overhead
        # stays included, so the number errs slightly pessimistic
        t0 = _time.perf_counter()
        for _ in range(steps):
            out = jitted(*args, **kwargs)
        jax.block_until_ready(out)
        # host-read a leaf: a value transfer cannot return before the
        # device finishes, so the window ends when the work does
        leaves = jax.tree.leaves(out)
        if leaves:
            jax.device_get(leaves[0])
        wall_ms = (_time.perf_counter() - t0) / steps * 1e3
    finally:
        jax.profiler.stop_trace()

    dur_us, total_us = load_trace_events(log_dir)

    comps, _ = _parse_hlo(compiled.as_text())
    shapes = {i.name: i.type_str for instrs in comps.values() for i in instrs}

    # HLO instruction names are module-unique, so the join spans ALL
    # computations, not just entry — ops inside scan/while bodies (the
    # layer stack of any scan-over-layers model) emit their own trace
    # events per iteration. Container ops (while/call/conditional) are
    # excluded from rows: their spans COVER their bodies' spans and would
    # double-count the attributed total.
    containers = {n for n, rec in _scopes_of(comps).items()
                  if rec["container"]}
    all_instrs = {i.name: i for instrs in comps.values() for i in instrs}
    instr_by_name = {
        n: i for n, i in all_instrs.items()
        if i.op not in _SKIP_OPS and n not in containers
    }
    # container spans COVER their bodies' spans: drop them from the
    # denominator and the unattributed list, or coverage could never
    # approach 100% on loop-dominated (scan-over-layers) programs
    for n in containers & set(dur_us):
        total_us -= dur_us.pop(n)[0]

    rows: List[Dict[str, Any]] = []
    matched_us = 0.0
    matched_names = set()
    for name, (t_us, count) in dur_us.items():
        ins = instr_by_name.get(name)
        if ins is None:
            continue
        matched_names.add(name)
        matched_us += t_us
        if ins.op == "dot":
            flops = _dot_flops(ins, shapes)
        elif ins.op == "convolution":
            flops = _conv_flops(ins, shapes)
        elif ins.callee:
            flops = _comp_flops(ins.callee, comps, shapes)
        else:
            flops = 0.0
        byts = _nbytes(ins.type_str) + sum(
            _nbytes(shapes.get(o, "")) for o in ins.operands if o in shapes)
        # per-step totals: measured time and executions are summed over
        # all `steps` runs (and all loop iterations within each)
        execs_per_step = count / steps
        flops, byts = flops * execs_per_step, float(byts) * execs_per_step
        parts = [p for p in ins.op_name.split("/") if p] or ["<no-scope>"]
        if parts[0].startswith("jit("):
            parts = parts[1:] or ["<top>"]
        t_s = t_us / 1e6 / steps
        rows.append({
            "name": ins.name,
            "scope": "/".join(parts[:depth]) if parts else "<top>",
            "op": ins.op,
            "count_per_step": execs_per_step,
            "time_ms": t_s * 1e3,
            "flops": flops,
            "bytes": byts,
            "mfu_pct": 100.0 * flops / (t_s * peak_flops) if t_s else 0.0,
            "gbps": byts / t_s / 1e9 if t_s else 0.0,
        })

    rows.sort(key=lambda r: -r["time_ms"])
    total_row_ms = sum(r["time_ms"] for r in rows) or 1.0
    for r in rows:
        r["pct"] = 100.0 * r["time_ms"] / total_row_ms

    unattributed = sorted(
        ({"name": n, "time_ms": d / 1e3 / steps}
         for n, (d, _) in dur_us.items() if n not in matched_names),
        key=lambda r: -r["time_ms"])
    return {
        "rows": rows,
        "unattributed": unattributed,
        "coverage_pct": 100.0 * matched_us / total_us if total_us else 0.0,
        "total_ms_per_step": total_row_ms,
        # host wall clock around the profiled loop (includes trace + async
        # dispatch overhead): the honest step-time denominator when the
        # trace join is partial — attributed time understates the step by
        # 1/coverage, and an empty join leaves the 1.0ms sentinel above
        "wall_ms_per_step": wall_ms,
        "log_dir": log_dir,
        # the exact executable that was measured — downstream joins
        # (monitor.report: wire-byte pricing, cost analysis) read it instead
        # of paying a second lower+compile of the same program
        "compiled": compiled,
    }


def format_measured_table(result: Dict[str, Any], top: int = 25,
                          show_unattributed: int = 5) -> str:
    """Render the measured join like the reference's ``prof/output.py``."""
    rows = result["rows"]
    lines = [
        f"{'name':28s} {'scope':30s} {'op':14s} {'ms/step':>9s} "
        f"{'GFLOP':>9s} {'MB':>9s} {'MFU%':>6s} {'GB/s':>7s} {'%':>5s}",
        "-" * 124,
    ]
    for r in rows[:top]:
        lines.append(
            f"{r['name'][:28]:28s} {r['scope'][:30]:30s} {r['op'][:14]:14s} "
            f"{r['time_ms']:9.3f} {r['flops']/1e9:9.2f} {r['bytes']/1e6:9.1f} "
            f"{r['mfu_pct']:6.1f} {r['gbps']:7.1f} {r['pct']:5.1f}")
    rest = rows[top:]
    if rest:
        lines.append(f"(+{len(rest)} more rows, "
                     f"{sum(r['pct'] for r in rest):.1f}% of attributed time)")
    lines.append(
        f"ATTRIBUTED {result['total_ms_per_step']:.3f} ms/step | trace "
        f"coverage {result['coverage_pct']:.1f}%")
    un = result["unattributed"][:show_unattributed]
    if un:
        lines.append("unattributed device spans: " + ", ".join(
            f"{u['name'][:40]}={u['time_ms']:.3f}ms" for u in un))
    return "\n".join(lines)


def measured_report(fn: Callable, *args: Any, steps: int = 3, top: int = 25,
                    depth: int = 2, peak_flops: Optional[float] = None,
                    **kwargs: Any) -> str:
    """One command: measured per-op table for a jittable step (printed +
    returned). The measured analogue of :func:`apex_tpu.pyprof.report`."""
    table = format_measured_table(
        measured_op_table(fn, *args, steps=steps, depth=depth,
                          peak_flops=peak_flops, **kwargs), top=top)
    print(table)
    return table
