"""Set-up read from the program's own host log (``monitor.trace.host_log``).

The program keeps, in memory, its host spans (``train_step``: one a call of
the step, with the call's index; ``scope_table``) and JAX's compile path
(``trace``, ``lower`` and ``compile`` records, a compile marked read from the
persistent cache or compiled), all on ``time.time()``'s clock. Set-up here is
the records that end before the step's (``CHECK_STEPS`` + 1)-th call starts:
``setup_s`` stops there, with one batch placed and one line printed between.
Seconds are unions of intervals, since a compile can sit inside a dispatch.

Where the program keeps no log (a tree from before it), :func:`setup`
returns ``None`` and so does every reader. Everything below :func:`records`
is arithmetic on plain records, which the tests check on hand-made logs.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Optional, Sequence

import scopes
from xplane import clip, total, union

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "kind_train_for_hostlog", os.path.join(HERE, "kinds", "train.py"))
_train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_train)
CHECK_STEPS = _train.CHECK_STEPS

STEP = "train_step"                     # the step's span and program name
COMPILE_PATH = ("trace", "lower", "compile")


def records() -> Optional[list]:
    try:
        from apex_tpu.monitor.trace import host_log
    except ImportError:
        return None
    return host_log()


def setup(log: Optional[Sequence] = None) -> Optional[Dict]:
    """``{"records", "first", "end"}``: the records of set-up, the instant
    the step's first call started and the instant its (``CHECK_STEPS`` +
    1)-th did (the end of set-up). ``None`` where there is no log or the step
    was not called that often."""
    log = records() if log is None else log
    if log is None:
        return None
    calls = [r for r in log if r.kind == "span" and r.name == STEP and r.call is not None]
    ends = [r.start for r in calls if r.call == CHECK_STEPS + 1]
    if not ends:
        return None
    end = max(ends)
    firsts = [r.start for r in calls if r.call == 1 and r.start < end]
    if not firsts:
        return None
    return {"records": [r for r in log if r.end < end], "first": max(firsts), "end": end}


def seconds(recs: Sequence, kinds: Sequence[str]) -> float:
    """Union of the intervals of ``recs`` of the given kinds."""
    return total(union((r.start, r.end) for r in recs if r.kind in kinds))


def steps_s(got: Dict) -> float:
    """From the step's first call to its (``CHECK_STEPS`` + 1)-th, less the
    compile path's union inside."""
    lo, hi = got["first"], got["end"]
    path = union((r.start, r.end) for r in got["records"] if r.kind in COMPILE_PATH)
    return (hi - lo) - total(clip(path, lo, hi))


def compiles(recs: Sequence, program: Optional[str] = None) -> List:
    return [r for r in recs if r.kind == "compile" and (program is None or r.program == program)]


def by_program(recs: Sequence) -> List[list]:
    """``[[program, trace_s, lower_s, compile_s, compiled, read, traced], ...]``
    by the sum of the three times, largest first: seconds are unions of a
    program's records of that kind, ``compiled`` and ``read`` count its
    compile records (read: a persistent cache hit inside), ``traced`` the
    passes its trace records fold."""
    rows: Dict[str, list] = {}
    for r in recs:
        if r.kind in COMPILE_PATH:
            rows.setdefault(r.program, []).append(r)
    out = []
    for program, rs in rows.items():
        times = [seconds(rs, (k,)) for k in COMPILE_PATH]
        cs = compiles(rs)
        out.append([program, *times, sum(not c.cached for c in cs),
                    sum(bool(c.cached) for c in cs),
                    sum(r.count for r in rs if r.kind == "trace")])
    return sorted(out, key=lambda row: -sum(row[1:4]))


def print_table(facts: Dict, got: Dict) -> None:
    """The line ``PERF.md`` §5 is written from: by program, and what the log
    covers of set-up (its compile path and the steps between, as a union)."""
    recs = got["records"]
    covered = union([(r.start, r.end) for r in recs if r.kind in COMPILE_PATH]
                    + [(got["first"], got["end"])])
    scopes.info(facts, setup_by_program=by_program(recs)[:25],
                setup_records=len(recs), setup_passes=sum(r.count for r in recs),
                setup_log_covered_s=total(covered),
                setup_log_span_s=got["end"] - min(r.start for r in recs))
