"""Flash attention's share of its roofline in the train step. Compute-bound:
causal attention's forward + backward operations from shapes over the bf16
peak, divided by the summed device time of ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` per step (the forward replayed under remat costs time and
earns no credit)."""
import counts
import xplane

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if trace is None or facts.get("kind") != "train":
        return None
    seconds = xplane.kernel_seconds_per_run(trace, "jit_train_step", KERNELS)
    if seconds is None:
        return None
    flops = counts.flash_attn_flops_per_step(facts["model"], facts["rows"] // facts["chips"], facts["seq"])
    return 100.0 * flops / facts["peaks"].bf16_flops_per_s / seconds
