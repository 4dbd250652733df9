"""The hybrid decoder's whole train step as a share of the chip's bf16 peak:
tokens of the steps that lie wholly inside the traced window, over the span
from the first such step's start to the last one's end on the device's clock,
x ``counts_hybrid.train_flops_per_token`` (6 a weight in a multiply-add, 6·h·s
a full layer, the delta rule in recurrent form) over the peak. Recomputation
is not credited."""
import counts_hybrid
import scopes_hybrid
import xplane


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if trace is None or not scopes_hybrid.is_hybrid(facts):
        return None
    shares = []
    for dev in trace.devices.values():
        runs = xplane.whole_runs(xplane.module_runs(dev, "jit_train_step"))
        if not runs:
            continue
        span_s = (max(s + d for _, s, d in runs) - min(s for _, s, _ in runs)) / 1e9
        tokens = len(runs) * (facts["rows"] // facts["chips"]) * facts["seq"]
        flops = tokens * counts_hybrid.train_flops_per_token(facts["model"], facts["seq"])
        shares.append(flops / span_s / facts["peaks"].bf16_flops_per_s)
    return 100.0 * sum(shares) / len(shares) if shares else None
