"""Collective time the core itself spends, as a share of the train step:
self time on the core's own ``XLA Ops`` line of ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``, ``all-to-all``
and their ``-start`` / ``-done``, inside the whole traced steps, over those
steps' span (idle time between steps counts), on the device where it is
largest. A transfer that runs behind other operations is not on that line;
a ``-done`` that waits for it is. Prints which instructions they were."""
import scopes
import xplane


def read(facts, trace):
    if trace is None or facts.get("kind") != "train":
        return None
    worst, names = None, {}
    for dev in trace.devices.values():
        runs = xplane.whole_runs(xplane.module_runs(dev, "jit_train_step"))
        if not runs:
            continue
        span_ns = max(s + d for _, s, d in runs) - min(s for _, s, _ in runs)
        by_name = scopes.collective_seconds(
            xplane.events_within(dev.ops, xplane.as_intervals(runs)))
        pct = 100.0 * sum(by_name.values()) / span_ns
        if worst is None or pct > worst:
            worst = pct
            names = {k: v / 1e9 / len(runs) for k, v in by_name.items()}
    if worst is None or not names:
        return None     # one device, or a program with no collective
    table = scopes.table_for(facts, "jit_train_step")
    rows = []
    for name, s in sorted(names.items(), key=lambda kv: -kv[1])[:10]:
        rec = table[0].get(name) if table else None
        rows.append([name, s, *(table[1](rec["op_name"]) if rec else ("", ""))])
    scopes.info(facts, collectives=rows)
    return worst
