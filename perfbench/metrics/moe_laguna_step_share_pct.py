"""The share of Laguna's train step (device self time) in the routed layer
(``layer/moe/*``: the router, the dispatch, the held experts' grouped
products with XLA's unscoped ``ragged-dot-*`` counted by name, the combine;
every phase; the gated shared expert, ``layer/shared/*``, is not in it). The
twin of ``moe_step_share_pct``."""
import scopes_laguna
import scopes_sdar


def read(facts, trace):
    got = scopes_laguna.moe_seconds(facts, trace)
    if got is None:
        return None
    return 100.0 * sum(got[p] for p in scopes_sdar.PARTS) / got["step_s"]
