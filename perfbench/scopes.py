"""Device time of a traced program by model scope and phase.

A device trace names an ``XLA Ops`` event by its instruction's text
(``%copy.49 = s32[...] copy(...)``) and says nothing of which part of the
model the instruction belongs to. The program does: it plants scopes
(``monitor.trace.span``) that ride each compiled instruction's ``op_name``,
registers each jitted program under its module's name, and
``monitor.trace.scope_table`` compiles the program again, from shapes, and
returns ``{instruction name: {"op_name", "opcode", "moves_only",
"container", "operands"}}``. ``monitor.trace.split_scope`` reads a phase
(``fwd`` / ``recompute`` / ``bwd`` / ``opt`` ...) and a scope
(``layer/attn/qkv``) out of an ``op_name``.

This module joins the two by instruction name, and proves the join: every
event's name and opcode must be in the table, or the table is of another
executable. Where the program has no registry (a tree from before the
scopes), :func:`table_for` returns ``None`` and so does every reader.

Everything below :func:`table_for` is arithmetic on plain lists and dicts,
checked by the tests on hand-made events and a recorded pair.
"""

from __future__ import annotations

import json
import re
import sys
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import xplane

Key = Tuple[str, str, bool]             # (phase, scope, moves_only)

NONE = "(none)"                         # no scope of its own, none to borrow
MATCH_FLOOR = 0.99                      # share of self time the table must name

_NAME = re.compile(r"^%?([\w.\-]+)\s*=\s*")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")


def parse_event(text: str) -> Tuple[str, str]:
    """``%copy.49 = s32[2]{0:T(128)} copy(s32[2]{0} %p)`` -> ``("copy.49",
    "copy")``. A tuple type is skipped by depth: a TPU layout carries
    parentheses of its own. ``("", "")`` where the text is no instruction."""
    head = _NAME.match(text)
    if not head or head.end() >= len(text):
        return "", ""
    i = head.end()
    if text[i] == "(":
        depth = 0
        while i < len(text):
            depth += (text[i] == "(") - (text[i] == ")")
            i += 1
            if depth == 0:
                break
    else:
        while i < len(text) and not text[i].isspace():
            i += 1
    op = _OPCODE.match(text, i)
    return (head.group(1), op.group(1)) if op else ("", "")


def _same_opcode(in_table: str, in_trace: str) -> bool:
    """The module's text writes an asynchronous slice as ``slice-start`` /
    ``slice-done``; the trace writes ``async-start`` / ``async-done``."""
    if in_table == in_trace:
        return True
    return in_trace.startswith("async-") and in_table.endswith(in_trace[5:])


def table_for(facts: Dict, module: str) -> Optional[Tuple[Dict[str, Dict], Callable]]:
    """The program's scope table of ``module`` at the run's shapes, with its
    ``split_scope``: ``(table, split_scope)``, kept in ``facts`` so that
    the readers of one run compile once. ``None`` where the program has no
    registry, or no program registered under that name."""
    memo = facts.setdefault("_scope_tables", {})
    if module not in memo:
        memo[module] = None
        try:
            from apex_tpu.monitor.trace import scope_table, split_scope
        except ImportError:
            return None
        table = scope_table(module, rows=facts["rows"], seq=facts["seq"])
        if table is not None:
            memo[module] = (table, split_scope)
    return memo[module]


def _resolve(table: Dict[str, Dict], split_scope: Callable
             ) -> Dict[str, Tuple[str, str, bool]]:
    """instruction -> (phase, scope, own): its own phase and scope where its
    ``op_name`` has a user scope; else, where it has no ``op_name`` at all,
    those of its first operand that has a scope, else of its first user
    (one hop, both from the table); else its own phase and ``(none)``."""
    own = {name: split_scope(rec["op_name"]) for name, rec in table.items()}
    users: Dict[str, List[str]] = {}
    for name, rec in table.items():
        for o in rec["operands"]:
            users.setdefault(o, []).append(name)
    out = {}
    for name, rec in table.items():
        phase, scope = own[name]
        if scope:
            out[name] = (phase, scope, True)
            continue
        if not rec["op_name"]:
            lent = next((own[n] for n in rec["operands"] + users.get(name, [])
                         if n in own and own[n][1]), None)
            if lent:
                out[name] = (lent[0], lent[1], False)
                continue
        out[name] = (phase, NONE, False)
    return out


def by_scope(trace, module: str, table_and_split) -> Optional[Dict]:
    """Self time of every ``XLA Ops`` event inside the executions of
    ``module`` that the trace holds whole, per execution, mean over devices:

    ``rows``       {(phase, scope, moves_only): seconds per step}
    ``step_s``     all self time per step (the step's busy time)
    ``own_s``      of it, in instructions with a user scope of their own
    ``matched``    share of self time whose event's name and opcode are in
                   the table
    ``missed``     the names that were not, by self time, ten at most

    ``None`` where there is no trace, no table or no whole execution."""
    if trace is None or table_and_split is None:
        return None
    table, split_scope = table_and_split
    resolved = _resolve(table, split_scope)
    rows: Dict[Key, float] = {}
    missed: Dict[str, float] = {}
    total = own = matched = 0.0
    runs_n = 0
    for dev in trace.devices.values():
        runs = xplane.whole_runs(xplane.module_runs(dev, module))
        events = xplane.events_within(dev.ops, xplane.as_intervals(runs))
        runs_n += len(runs)
        for text, _, ns in xplane.self_times(events):
            name, opcode = parse_event(text)
            rec = table.get(name)
            total += ns
            if rec is None or not _same_opcode(rec["opcode"], opcode):
                label = name or text[:60]
                missed[label] = missed.get(label, 0.0) + ns
                continue
            matched += ns
            phase, scope, is_own = resolved[name]
            own += ns if is_own else 0.0
            key = (phase or NONE, scope, bool(rec["moves_only"]))
            rows[key] = rows.get(key, 0.0) + ns
    if not runs_n or total <= 0:
        return None
    per = 1e9 * runs_n
    return {"rows": {k: v / per for k, v in rows.items()},
            "step_s": total / per, "own_s": own / per,
            "matched": matched / total,
            "missed": [[k, v / per] for k, v in
                       sorted(missed.items(), key=lambda kv: -kv[1])[:10]]}


def info(facts: Dict, **fields) -> None:
    """One line of facts on standard output, before the result's line."""
    argv = sys.argv
    cell = (argv[argv.index("--workload") + 1]
            if "--workload" in argv[:-1] else facts["model"].get("name"))
    print(json.dumps({"info": cell, **fields}), flush=True)


def train_step_scopes(facts: Dict, trace) -> Optional[Dict]:
    """:func:`by_scope` of ``jit_train_step`` for this run, once (kept in
    ``facts``), and only where the table is proved to be of the traced
    executable: under ``MATCH_FLOOR`` of self time matched, one ``info``
    line says which names missed and every reader gets ``None``."""
    if "_train_step_scopes" not in facts:
        facts["_train_step_scopes"] = None
        if (trace is not None and facts.get("kind") == "train"
                and any(xplane.module_runs(dev, "jit_train_step")
                        for dev in trace.devices.values())):
            got = by_scope(trace, "jit_train_step",
                           table_for(facts, "jit_train_step"))
            if got is not None and got["matched"] < MATCH_FLOOR:
                info(facts, scope_table="not of the traced executable",
                     matched_share=got["matched"], missed=got["missed"])
                got = None
            facts["_train_step_scopes"] = got
    return facts["_train_step_scopes"]


def share(got: Optional[Dict], pick: Callable[[Key], bool]) -> Optional[float]:
    """Percent of the step's self time in the rows that ``pick`` takes."""
    if got is None:
        return None
    return 100.0 * sum(v for k, v in got["rows"].items() if pick(k)) / got["step_s"]


def table_by_scope(got: Dict, top: int = 25) -> List[List]:
    """``[[phase, scope, seconds_per_step, of_which_moves_only], ...]``, the
    ``top`` largest."""
    merged: Dict[Tuple[str, str], List[float]] = {}
    for (phase, scope, moves), s in got["rows"].items():
        rec = merged.setdefault((phase, scope), [0.0, 0.0])
        rec[0] += s
        rec[1] += s if moves else 0.0
    ranked = sorted(merged.items(), key=lambda kv: -kv[1][0])[:top]
    return [[p, s, v[0], v[1]] for (p, s), v in ranked]


# -- collectives ------------------------------------------------------------------

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_COLLECTIVE_OPCODES = frozenset(
    c + suffix for c in COLLECTIVES for suffix in ("", "-start", "-done"))


def collective_seconds(events: Sequence[xplane.Event]) -> Dict[str, float]:
    """Nanoseconds of self time of collective instructions among ``events``
    (one device's ``XLA Ops`` line), by instruction name. On that line an
    operation's time is time the core itself spends in it: a ``-done``
    that waits for the links counts, transfers behind other operations do
    not."""
    out: Dict[str, float] = {}
    for text, _, ns in xplane.self_times(events):
        name, opcode = parse_event(text)
        if opcode in _COLLECTIVE_OPCODES and ns > 0:
            out[name] = out.get(name, 0.0) + ns
    return out
