"""Flash attention's share of its roofline with keys of 192 over values of
128 (latent attention's expanded heads, causal, the streamed schedule: 528 of
1,024 tiles a head at 16,384 positions). Compute-bound: forward + backward
operations of the tiles on or under the diagonal at 2 x (192 + 128) a pair a
head forward (``counts_dsv2.attn_tile_flops_per_step``; nothing padded to the
lanes) over the bf16 peak, divided by the summed device time of
``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` a step (the forward
replayed under remat costs time and earns no credit)."""
import counts_dsv2
import scopes_dsv2
import xplane

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if trace is None or not scopes_dsv2.is_dsv2(facts):
        return None
    seconds = xplane.kernel_seconds_per_run(trace, "jit_train_step", KERNELS)
    if not seconds:
        return None
    flops = counts_dsv2.attn_tile_flops_per_step(
        facts["model"], facts["rows"] // facts["chips"], facts["seq"])
    return 100.0 * flops / facts["peaks"].bf16_flops_per_s / seconds
