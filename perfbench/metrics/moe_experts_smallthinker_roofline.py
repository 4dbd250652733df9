"""The held experts' ReLU-gated grouped products as a share of their
roofline in SmallThinker's step (hidden 2,560, width 768): the least time
the chip could take for them, forward + backward (the larger of the held
pairs' operations over the bf16 peak and the experts' weights plus the
pairs' rows over the memory's peak: ``counts_smallthinker``), over the
device self time a step under ``layer/moe/experts`` in every phase, XLA's
unscoped ``ragged-dot-*`` instructions counted there by name. The twin of
``moe_experts_roofline``."""
import counts_smallthinker
import scopes_smallthinker


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    pairs = scopes_smallthinker.pairs_held(facts)
    if pairs is None:
        return None
    got = scopes_smallthinker.moe_seconds(facts, trace)
    if got is None or got["experts"] <= 0:
        return None
    model, peaks = facts["model"], facts["peaks"]
    least = max(counts_smallthinker.experts_flops_per_step(model, pairs) / peaks.bf16_flops_per_s,
                counts_smallthinker.experts_bytes_per_step(model, pairs) / peaks.hbm_bytes_per_s)
    return 100.0 * least / got["experts"]
