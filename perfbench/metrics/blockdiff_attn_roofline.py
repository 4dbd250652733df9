"""Flash attention's share of its roofline under the block-diffusion mask
(the listed schedule: 288 of 1,024 tiles a head at 16,384 positions, grouped
heads). Compute-bound: forward + backward operations of the tiles that hold a
visible pair (``counts_sdar.attn_tile_flops_per_step``) over the bf16 peak,
divided by the summed device time of ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` a step (the forward replayed under remat costs time and
earns no credit)."""
import counts_sdar
import scopes_sdar
import xplane

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if trace is None or not scopes_sdar.is_sdar(facts):
        return None
    seconds = xplane.kernel_seconds_per_run(trace, "jit_train_step", KERNELS)
    if not seconds:
        return None
    flops = counts_sdar.attn_tile_flops_per_step(
        facts["model"], facts["rows"] // facts["chips"], facts["seq"])
    return 100.0 * flops / facts["peaks"].bf16_flops_per_s / seconds
