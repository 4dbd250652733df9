"""Flash attention's share of its roofline on Laguna's full layers (48 query
heads over 8 K/V heads of 128, causal on the listed schedule: 136 of 256
tiles a head at 8,192 positions). Compute-bound: forward + backward
operations of the causal pairs (``counts_laguna.full_attn_flops_per_step``:
s(s+1)/2 a row a head at 512 operations a pair forward, x 3.5 for the pass)
over the bf16 peak, divided by the summed device time a step of
``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` under
``layer/attn/core`` (every phase). The twin of ``swa_attn_roofline``."""
import counts_laguna
import scopes_laguna


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    seconds = scopes_laguna.flash_seconds(facts, trace, scopes_laguna.ATTN)
    if seconds is None:
        return None
    flops = counts_laguna.full_attn_flops_per_step(
        facts["model"], facts["rows"] // facts["chips"], facts["seq"])
    return 100.0 * flops / facts["peaks"].bf16_flops_per_s / seconds
