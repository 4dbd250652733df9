"""The fused LM head + cross entropy's share of its roofline in the train
step. Compute-bound: 3 x 2·T·h·V operations over the bf16 peak, divided by the
summed device time of ``lm_head_fwd``, ``lm_head_bwd_dx`` and
``lm_head_bwd_dw`` per step."""
import counts
import xplane

KERNELS = ("lm_head_fwd", "lm_head_bwd_dx", "lm_head_bwd_dw")


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if trace is None or facts.get("kind") != "train":
        return None
    seconds = xplane.kernel_seconds_per_run(trace, "jit_train_step", KERNELS)
    if seconds is None:
        return None
    flops = counts.lm_head_loss_flops_per_step(facts["model"], facts["rows"] // facts["chips"], facts["seq"])
    return 100.0 * flops / facts["peaks"].bf16_flops_per_s / seconds
