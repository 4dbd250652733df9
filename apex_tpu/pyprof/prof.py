"""Per-op / per-layer attribution report from compiled HLO.

Reference capability: ``apex/pyprof/parse`` + ``apex/pyprof/prof`` — walk a
captured profile, map each kernel back to its layer, attach FLOP/byte
estimates, and render a table (``prof/output.py``).

TPU re-design: the compiled HLO is the ground truth of what actually runs
after XLA fusion — no SQLite scraping needed. Each HLO instruction carries
``metadata={op_name="jit(f)/scope1/scope2/op"}`` where the scopes are
``jax.named_scope`` annotations (:func:`apex_tpu.pyprof.annotate`), so layer
attribution falls out of the same annotation API the reference wraps NVTX
for. FLOPs are computed from dot/convolution shapes (recursing into fusion
subcomputations), bytes from operand+result sizes, and each op gets a
roofline time estimate ``max(flops/peak, bytes/bandwidth)`` — the analogue
of the reference's per-op FLOP formula tables, with the compiler's fused
graph instead of tracing heuristics.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}

_INSTR_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*(?P<op>[\w\-]+)\(")
_SHAPE_RE = re.compile(r"(?P<dt>\w+)\[(?P<dims>[\d,]*)\]")
_META_RE = re.compile(r'metadata=\{[^}]*op_name="(?P<op_name>[^"]*)"')
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?(?P<callee>[\w.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{(?P<dims>[\d,]*)\}")


def _parse_shape(type_str: str) -> List[Tuple[str, List[int]]]:
    """'(bf16[2,3]{1,0}, f32[4])' or 'bf16[2,3]{1,0}' -> [(dtype, dims)...]"""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dims = [int(x) for x in m.group("dims").split(",") if x]
        out.append((m.group("dt"), dims))
    return out


def _nbytes(type_str: str) -> int:
    return sum(
        _DTYPE_BYTES.get(dt, 4) * int(np.prod(dims)) if dims
        else _DTYPE_BYTES.get(dt, 4)
        for dt, dims in _parse_shape(type_str))


@dataclass
class _Instr:
    name: str
    op: str
    type_str: str
    line: str
    op_name: str = ""
    callee: Optional[str] = None
    operands: List[str] = field(default_factory=list)


def _type_end(line: str, start: int) -> int:
    """Index just past the result type that starts at ``start``. A tuple
    type is matched by depth: a TPU layout carries parentheses of its own
    (``{1,0:T(8,128)(2,1)}``), inside tuples too."""
    if line[start] != "(":
        end = start
        while end < len(line) and not line[end].isspace():
            end += 1
        return end
    depth = 0
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(line)


def _parse_instr(line: str) -> Optional[_Instr]:
    head = _INSTR_HEAD_RE.match(line)
    if not head or head.end() >= len(line):
        return None
    t_end = _type_end(line, head.end())
    op = _OPCODE_RE.match(line, t_end)
    if not op:
        return None
    ins = _Instr(head.group("name"), op.group("op"),
                 line[head.end():t_end], line)
    meta = _META_RE.search(line)
    if meta:
        ins.op_name = meta.group("op_name")
    calls = _CALLS_RE.search(line)
    if calls:
        ins.callee = calls.group("callee")
    # operand names: %foo references inside the opcode's parentheses
    close = _type_end(line, op.end() - 1)
    ins.operands = re.findall(r"%([\w.\-]+)", line[op.end():close])
    return ins


def _parse_hlo(hlo_text: str) -> Tuple[Dict[str, List[_Instr]], str]:
    """-> ({computation_name: [instrs]}, entry_computation_name)."""
    comps: Dict[str, List[_Instr]] = {}
    entry = ""
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        header = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->", line)
        if header and not line.lstrip().startswith("ROOT"):
            cur = header.group(2)
            comps[cur] = []
            if header.group(1):
                entry = cur
            continue
        if cur is None:
            continue
        ins = _parse_instr(line)
        if ins is not None:
            comps[cur].append(ins)
    return comps, entry


# -- which scope each instruction belongs to ----------------------------------

_MOVE_OPS = frozenset((
    "copy", "transpose", "reshape", "bitcast", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "broadcast"))
_ASYNC_HALF_RE = re.compile(r"-(?:start|done)$")    # copy-start, slice-done
_MOVE_FILLERS = frozenset((
    "parameter", "tuple", "get-tuple-element", "constant"))
_CONTAINER_OPS = frozenset(("while", "call", "conditional"))
_SCALAR_INDEX_RE = re.compile(r"^(?:s|u)(?:8|16|32|64)\[\]|^pred\[\]")


def _moves_only(ins: _Instr, comps: Dict[str, List[_Instr]],
                seen: Optional[set] = None) -> bool:
    """True where the instruction moves data and computes nothing: one of
    the layout and slicing opcodes, or a fusion whose fused computation
    holds nothing but those (and parameters, tuples, constants and index
    arithmetic on scalars)."""
    if _ASYNC_HALF_RE.sub("", ins.op) in _MOVE_OPS:
        return True
    if ins.op != "fusion" or ins.callee not in comps:
        return False
    seen = seen if seen is not None else set()
    if ins.callee in seen:
        return False
    seen.add(ins.callee)
    moves = False
    for inner in comps[ins.callee]:
        if inner.op in _MOVE_FILLERS or _SCALAR_INDEX_RE.match(inner.type_str):
            continue
        if not _moves_only(inner, comps, seen):
            return False
        moves = True
    return moves


def instruction_scopes(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """``{instruction name: {"op_name", "opcode", "moves_only", "container",
    "operands"}}`` of a compiled module's text, for every instruction of
    every computation that is not itself fused into another (instruction
    names are unique in a module; a device trace names its events by them).

    ``op_name`` is the instruction's ``metadata`` path, where the scopes a
    program planted live (``monitor.trace.split_scope`` reads it).
    ``moves_only`` marks data movement (see :func:`_moves_only`).
    ``container`` marks ``while`` / ``call`` / ``conditional``, whose events
    span their bodies' events: a reader leaves them out or takes self
    time. ``operands`` lets a reader lend an unnamed instruction its
    neighbour's scope.
    """
    return _scopes_of(_parse_hlo(hlo_text)[0])


def _scopes_of(comps: Dict[str, List[_Instr]]) -> Dict[str, Dict[str, Any]]:
    fused = {i.callee for instrs in comps.values() for i in instrs
             if i.op == "fusion" and i.callee}
    table: Dict[str, Dict[str, Any]] = {}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for ins in instrs:
            table[ins.name] = {
                "op_name": ins.op_name,
                "opcode": ins.op,
                "moves_only": _moves_only(ins, comps),
                "container": ins.op in _CONTAINER_OPS,
                "operands": ins.operands,
            }
    return table


def _dot_flops(ins: _Instr, shapes: Dict[str, str]) -> float:
    out = _parse_shape(ins.type_str)
    out_elems = float(np.prod(out[0][1])) if out and out[0][1] else 1.0
    cdims = _CDIMS_RE.search(ins.line)
    csize = 1.0
    if cdims and ins.operands:
        lhs_type = shapes.get(ins.operands[0], "")
        lhs = _parse_shape(lhs_type)
        if lhs:
            dims = lhs[0][1]
            for d in (int(x) for x in cdims.group("dims").split(",") if x):
                if d < len(dims):
                    csize *= dims[d]
    return 2.0 * out_elems * csize


def _conv_flops(ins: _Instr, shapes: Dict[str, str]) -> float:
    # flops = 2 * out_elems * (kernel spatial * in_channels); estimate the
    # multiplier from the rhs (kernel) operand: prod(all dims) / out_channels
    out = _parse_shape(ins.type_str)
    out_elems = float(np.prod(out[0][1])) if out and out[0][1] else 1.0
    mult = 1.0
    if len(ins.operands) >= 2:
        k = _parse_shape(shapes.get(ins.operands[1], ""))
        if k and k[0][1]:
            kd = k[0][1]
            mult = float(np.prod(kd)) / max(kd[-1], 1)  # o is last by default
    return 2.0 * out_elems * mult


def _comp_flops(comp: str, comps: Dict[str, List[_Instr]],
                shapes: Dict[str, str], seen=None) -> float:
    if seen is None:
        seen = set()
    if comp in seen or comp not in comps:
        return 0.0
    seen.add(comp)
    total = 0.0
    for ins in comps[comp]:
        if ins.op == "dot":
            total += _dot_flops(ins, shapes)
        elif ins.op == "convolution":
            total += _conv_flops(ins, shapes)
        elif ins.callee:
            total += _comp_flops(ins.callee, comps, shapes, seen)
    return total


_SKIP_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all"}


def op_table(
    fn: Callable,
    *args: Any,
    depth: int = 2,
    peak_flops: Optional[float] = None,
    hbm_bandwidth: Optional[float] = None,
    **kwargs: Any,
) -> List[Dict[str, Any]]:
    """Per-op roofline attribution of a jittable function.

    Returns one row per executed HLO instruction of the entry computation
    (fusions counted whole, their inner dots attributed to them):
    ``{scope, op, flops, bytes, est_time_s, bound}``, aggregated up to
    ``depth`` segments of the ``named_scope`` path and sorted by estimated
    time. ``peak_flops`` / ``hbm_bandwidth`` default to the published peaks
    of the device JAX reports (``utils.platform.DEVICE_PEAKS``); pass
    measured numbers for a calibrated roofline.
    """
    if peak_flops is None or hbm_bandwidth is None:
        # an unknown device kind (the CPU included) raises: the roofline is
        # never taken against an assumed chip
        from apex_tpu.utils.platform import device_peaks

        peaks = device_peaks()
        if peak_flops is None:
            peak_flops = peaks.bf16_flops_per_s
        if hbm_bandwidth is None:
            hbm_bandwidth = peaks.hbm_bytes_per_s
    lowered = jax.jit(fn).lower(*args, **kwargs)
    hlo = lowered.compile().as_text()
    comps, entry = _parse_hlo(hlo)
    if not entry:
        entry = max(comps, key=lambda c: len(comps[c])) if comps else ""
    shapes = {i.name: i.type_str for instrs in comps.values() for i in instrs}

    rows: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for ins in comps.get(entry, []):
        if ins.op in _SKIP_OPS:
            continue
        if ins.op == "dot":
            flops = _dot_flops(ins, shapes)
        elif ins.op == "convolution":
            flops = _conv_flops(ins, shapes)
        elif ins.callee:
            flops = _comp_flops(ins.callee, comps, shapes)
        else:
            flops = 0.0
        byts = _nbytes(ins.type_str) + sum(
            _nbytes(shapes.get(o, "")) for o in ins.operands
            if o in shapes)
        # scope: drop the jit(...) prefix and the op leaf, keep `depth` segs
        parts = [p for p in ins.op_name.split("/") if p] or ["<no-scope>"]
        if parts[0].startswith("jit("):
            parts = parts[1:] or ["<top>"]
        scope = "/".join(parts[:depth]) if parts else "<top>"
        key = (scope, ins.op)
        row = rows.setdefault(key, {
            "scope": scope, "op": ins.op, "count": 0,
            "flops": 0.0, "bytes": 0.0})
        row["count"] += 1
        row["flops"] += flops
        row["bytes"] += float(byts)

    out = list(rows.values())
    for r in out:
        t_c = r["flops"] / peak_flops
        t_m = r["bytes"] / hbm_bandwidth
        r["est_time_s"] = max(t_c, t_m)
        r["bound"] = "compute" if t_c >= t_m else "memory"
    out.sort(key=lambda r: -r["est_time_s"])
    return out


def format_table(rows: List[Dict[str, Any]], top: int = 25) -> str:
    """Render like the reference's ``prof/output.py`` column table."""
    total_t = sum(r["est_time_s"] for r in rows) or 1.0
    lines = [
        f"{'scope':40s} {'op':18s} {'n':>4s} {'GFLOP':>10s} {'MB':>10s} "
        f"{'est_ms':>8s} {'%':>5s} {'bound':>7s}",
        "-" * 108,
    ]
    for r in rows[:top]:
        lines.append(
            f"{r['scope'][:40]:40s} {r['op'][:18]:18s} {r['count']:4d} "
            f"{r['flops']/1e9:10.2f} {r['bytes']/1e6:10.1f} "
            f"{r['est_time_s']*1e3:8.3f} "
            f"{100*r['est_time_s']/total_t:5.1f} {r['bound']:>7s}")
    rest = rows[top:]
    if rest:
        lines.append(
            f"(+{len(rest)} more rows, "
            f"{100*sum(r['est_time_s'] for r in rest)/total_t:.1f}% of est time)")
    lines.append(
        f"TOTAL est {total_t*1e3:.2f} ms | "
        f"{sum(r['flops'] for r in rows)/1e9:.1f} GFLOP | "
        f"{sum(r['bytes'] for r in rows)/1e6:.1f} MB")
    return "\n".join(lines)


def report(fn: Callable, *args: Any, depth: int = 2, top: int = 25,
           peak_flops: Optional[float] = None,
           hbm_bandwidth: Optional[float] = None,
           **kwargs: Any) -> str:
    """One-command per-op report for a jittable step (printed + returned)."""
    table = format_table(
        op_table(fn, *args, depth=depth, peak_flops=peak_flops,
                 hbm_bandwidth=hbm_bandwidth, **kwargs), top=top)
    print(table)
    return table
