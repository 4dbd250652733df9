"""The share of SmallThinker's train step (device self time) in the routed
layer (``layer/moe/*``, every phase: the router, whose span runs in the
attention sublayer ahead of the projections, the dispatch, the held
experts' grouped products with XLA's unscoped ``ragged-dot-*`` counted by
name, the combine). The twin of ``moe_step_share_pct``."""
import scopes_sdar
import scopes_smallthinker


def read(facts, trace):
    got = scopes_smallthinker.moe_seconds(facts, trace)
    if got is None:
        return None
    return 100.0 * sum(got[p] for p in scopes_sdar.PARTS) / got["step_s"]
