"""Flash attention's share of its roofline on SmallThinker's window layers
(28 query heads over 4 K/V heads of 128, rotary, the window's listed
schedule). Compute-bound: forward + backward operations of the pairs the
window lets through (``counts_smallthinker.swa_attn_flops_per_step``:
s·4,096 - 4,096·4,095/2 a row a head at 512 operations a pair forward, x 3.5
for the pass; a tile's pairs the mask hides are not credited, so another
tile size leaves the count standing) over the bf16 peak, divided by the
summed device time a step of ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` under ``layer/swa/core`` (every phase: a forward replayed
costs time and earns no credit)."""
import counts_smallthinker
import scopes_smallthinker


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    seconds = scopes_smallthinker.flash_seconds(facts, trace, scopes_smallthinker.SWA)
    if seconds is None:
        return None
    flops = counts_smallthinker.swa_attn_flops_per_step(
        facts["model"], facts["rows"] // facts["chips"], facts["seq"])
    return 100.0 * flops / facts["peaks"].bf16_flops_per_s / seconds
