"""Flash attention's share of its roofline on SmallThinker's full layers (28
query heads over 4 K/V heads of 128, no positional encoding, causal on the
listed schedule: 528 of 1,024 tiles a head at 16,384 positions).
Compute-bound: forward + backward operations of the causal pairs
(``counts_smallthinker.nope_attn_flops_per_step``: s(s+1)/2 a row a head at
512 operations a pair forward, x 3.5 for the pass) over the bf16 peak,
divided by the summed device time a step of ``flash_fwd``, ``flash_bwd_dq``
and ``flash_bwd_dkv`` under ``layer/attn/core`` (every phase). The twin of
``swa_attn_smallthinker_roofline``."""
import counts_smallthinker
import scopes_smallthinker


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    seconds = scopes_smallthinker.flash_seconds(facts, trace, scopes_smallthinker.ATTN)
    if seconds is None:
        return None
    flops = counts_smallthinker.nope_attn_flops_per_step(
        facts["model"], facts["rows"] // facts["chips"], facts["seq"])
    return 100.0 * flops / facts["peaks"].bf16_flops_per_s / seconds
