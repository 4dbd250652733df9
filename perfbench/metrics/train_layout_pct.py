"""The share of the train step's device self time in instructions that move
data and compute nothing (``moves_only``: copies, transposes, reshapes,
slices, dynamic-update-slices, and fusions of nothing else), in any scope.

Before the result it prints the table ``PERF.md`` §5 is written from:
``{"info": <cell>, "by_scope": [[phase, scope, seconds_per_step,
of_which_moves_only], ... 25], "moves_only": {...}}``, the second key
splitting the moved time into ``attn/*`` by phase, ``scan_carry``,
``(none)`` and the rest."""
import scopes


def read(facts, trace):
    got = scopes.train_step_scopes(facts, trace)
    if got is None:
        return None
    moved = {}
    for (phase, scope, moves), s in got["rows"].items():
        if not moves:
            continue
        if "attn/" in scope:
            where = "attn/*." + phase
        elif scope in ("scan_carry", scopes.NONE):
            where = scope
        else:
            where = "rest"
        moved[where] = moved.get(where, 0.0) + s
    phases = {}
    for (phase, _, _), s in got["rows"].items():
        phases[phase] = phases.get(phase, 0.0) + s
    scopes.info(facts, by_scope=scopes.table_by_scope(got), moves_only=moved,
                by_phase=phases, step_busy_s=got["step_s"],
                matched_share=got["matched"], missed=got["missed"])
    return scopes.share(got, lambda key: key[2])
