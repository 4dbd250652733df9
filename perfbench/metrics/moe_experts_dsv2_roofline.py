"""The held experts' grouped products as a share of their roofline in the
latent-attention decoder's step: the least time the chip could take for them,
forward + backward (the larger of the held pairs' operations over the bf16
peak and the experts' weights plus the pairs' rows over the memory's peak:
``counts_dsv2``), over the device self time a step under
``layer/moe/experts`` in every phase, XLA's unscoped ``ragged-dot-*``
instructions counted there by name. The twin of ``moe_experts_roofline``."""
import counts_dsv2
import scopes_dsv2


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    pairs = scopes_dsv2.pairs_held(facts)
    if pairs is None:
        return None
    got = scopes_dsv2.moe_seconds(facts, trace)
    if got is None or got["experts"] <= 0:
        return None
    model, peaks = facts["model"], facts["peaks"]
    least = max(counts_dsv2.experts_flops_per_step(model, pairs) / peaks.bf16_flops_per_s,
                counts_dsv2.experts_bytes_per_step(model, pairs) / peaks.hbm_bytes_per_s)
    return 100.0 * least / got["experts"]
