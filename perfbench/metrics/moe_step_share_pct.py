"""The share of the train step's device self time in the routed layer
(``layer/moe/*``: the router, the dispatch, the experts' grouped products with
XLA's unscoped ``ragged-dot-*`` counted by name, the combine; every phase):
whether the mechanism does the work the cell is there for."""
import scopes_sdar


def read(facts, trace):
    got = scopes_sdar.moe_seconds(facts, trace)
    if got is None:
        return None
    return 100.0 * sum(got[p] for p in scopes_sdar.PARTS) / got["step_s"]
