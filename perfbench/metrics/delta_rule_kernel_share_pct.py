"""How much of the delta rule's core its own kernels run: of the device self
time a step under the scope ``layer/linattn/core`` in every phase
(``scopes.py``), the percent in the kernels' own scopes below it
(``layer/linattn/core/delta_rule_fwd``: a ``pallas_call``'s ``name=`` is the
scope's last component, and the program's table says which instructions are
``pallas_call``s). The rest is what XLA still runs round them: layout changes,
casts, the decay's sum over a chunk. 0 where the core is XLA's fusions alone
(an ``einsum``'s equation is a scope below the core too, and no kernel);
nothing where the step has no such scope."""
import scopes

SCOPE = "layer/linattn/core"


def read(facts, trace):
    got = scopes.train_step_scopes(facts, trace)
    if got is None:
        return None
    table, split_scope = scopes.table_for(facts, "jit_train_step")
    kernels = {split_scope(rec["op_name"])[1] for rec in table.values()
               if rec["opcode"] == "custom-call" and rec["op_name"].endswith("/pallas_call")}
    under = [(scope, s) for (_, scope, _), s in got["rows"].items()
             if scope == SCOPE or scope.startswith(SCOPE + "/")]
    whole = sum(s for _, s in under)
    if whole <= 0:
        return None
    return 100.0 * sum(s for scope, s in under if scope in kernels) / whole
