"""Flash attention's share of its roofline in the hybrid decoder's train
step, where a head of 8,192 rows takes the streamed schedule. Compute-bound:
causal attention's forward + backward operations of the full layers held
(``counts_hybrid.full_attn_flops_per_step``) over the bf16 peak, divided by
the summed device time of ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` a step (the forward replayed under remat costs time and
earns no credit)."""
import counts_hybrid
import scopes_hybrid
import xplane

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if trace is None or not scopes_hybrid.is_hybrid(facts):
        return None
    seconds = xplane.kernel_seconds_per_run(trace, "jit_train_step", KERNELS)
    if not seconds:
        return None
    flops = counts_hybrid.full_attn_flops_per_step(
        facts["model"], facts["rows"] // facts["chips"], facts["seq"])
    return 100.0 * flops / facts["peaks"].bf16_flops_per_s / seconds
