"""What the block-diffusion cell's readers share: whether a run is of that
family (by the configuration's ``model_type``, which no other family states),
its counters, and the device time of the routed layer's parts.

XLA rewrites ``lax.ragged_dot`` on the chip into a grouped product of its own
and names the instruction ``ragged-dot-*`` with no scope (``op_name`` is the
instruction's own name), so ``scopes.py`` files its time under ``(none)``.
:func:`moe_seconds` therefore reads the routed layer's time as the rows under
``layer/moe/*`` plus the self time of the events whose instruction is named
``ragged-dot*``, which it adds to ``experts``: whatever implements the grouped
product, its time is the experts'.
"""

from __future__ import annotations

from typing import Dict, Optional

import scopes
import xplane

MOE = "layer/moe"
PARTS = ("route", "dispatch", "experts", "combine")
GROUPED = "ragged-dot"
MODEL_TYPE = "sdar_moe"


def is_sdar(facts: Dict) -> bool:
    return facts.get("kind") == "train" and facts.get("model", {}).get("model_type") == MODEL_TYPE


def pairs_held(facts: Dict) -> Optional[float]:
    """(position, expert) pairs a step's layers held, from the kind's
    counters; ``None`` where the kind printed none."""
    return (facts.get("counters") or {}).get("pairs_held")


def grouped_seconds(trace, module: str = "jit_train_step") -> float:
    """Self time a step of the instructions named ``ragged-dot*``."""
    total_ns, runs_n = 0.0, 0
    for dev in trace.devices.values():
        runs = xplane.whole_runs(xplane.module_runs(dev, module))
        events = xplane.events_within(dev.ops, xplane.as_intervals(runs))
        runs_n += len(runs)
        for text, _, ns in xplane.self_times(events):
            name, _ = scopes.parse_event(text)
            if name.startswith(GROUPED):
                total_ns += ns
    return total_ns / runs_n / 1e9 if runs_n else 0.0


def moe_seconds(facts: Dict, trace) -> Optional[Dict[str, float]]:
    """Seconds a step under each part of ``layer/moe`` (every phase), the
    unscoped grouped products counted under ``experts``; ``step_s`` beside
    them. ``None`` where there is no scope table or no routed layer."""
    if "_moe_seconds" in facts:
        return facts["_moe_seconds"]
    facts["_moe_seconds"] = None
    got = scopes.train_step_scopes(facts, trace)
    if got is None:
        return None
    out = dict.fromkeys(PARTS, 0.0)
    seen = False
    for (_, scope, _), s in got["rows"].items():
        if scope == MOE or scope.startswith(MOE + "/"):
            seen = True
            part = scope[len(MOE) + 1:].split("/")[0]
            out[part if part in out else "dispatch"] += s
    if not seen:
        return None
    out["experts"] += grouped_seconds(trace)
    out["step_s"] = got["step_s"]
    facts["_moe_seconds"] = out
    return out
