"""Of the routed layer's device time a step in the latent-attention decoder
(``layer/moe/*``), the share that is not the experts' products: routing,
sorting and laying out the pairs, gathering their rows and adding the results
back. The twin of ``moe_overhead_pct``."""
import scopes_dsv2
import scopes_sdar


def read(facts, trace):
    got = scopes_dsv2.moe_seconds(facts, trace)
    if got is None:
        return None
    whole = sum(got[p] for p in scopes_sdar.PARTS)
    return 100.0 * (whole - got["experts"]) / whole if whole > 0 else None
