"""The fused LM head + cross entropy's share of its roofline in
SmallThinker's train step, over the rows of the vocabulary held.
Compute-bound: 3 x 2·T·h·V operations
(``counts_smallthinker.lm_head_loss_flops_per_step``) over the bf16 peak,
divided by the summed device time of ``lm_head_fwd``, ``lm_head_bwd_dx`` and
``lm_head_bwd_dw`` a step. The twin of ``lm_head_loss_laguna_roofline``."""
import counts_smallthinker
import scopes_smallthinker
import xplane

KERNELS = ("lm_head_fwd", "lm_head_bwd_dx", "lm_head_bwd_dw")


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if trace is None or not scopes_smallthinker.is_smallthinker(facts):
        return None
    seconds = xplane.kernel_seconds_per_run(trace, "jit_train_step", KERNELS)
    if not seconds:
        return None
    flops = counts_smallthinker.lm_head_loss_flops_per_step(
        facts["model"], facts["rows"] // facts["chips"], facts["seq"])
    return 100.0 * flops / facts["peaks"].bf16_flops_per_s / seconds
