"""SmallThinker's whole train step as a share of the chip's bf16 peak: the
steps that lie wholly inside the traced window, over the span from the first
such step's start to the last one's end on the device's clock, x
``counts_smallthinker.train_flops_per_step`` (6 a weight a position for the
projections and the router, attention over the pairs the masks let through:
the full layer s(s+1)/2, the window layers s·4,096 - 4,096·4,095/2, at 28
heads and 512 operations a pair a head forward; the held experts over the
pairs the kind's counter says were held; the head) over the peak.
Recomputation is not credited."""
import counts_smallthinker
import scopes_smallthinker
import xplane


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    pairs = scopes_smallthinker.pairs_held(facts)
    if trace is None or not scopes_smallthinker.is_smallthinker(facts) or pairs is None:
        return None
    rows = facts["rows"] // facts["chips"]
    flops = counts_smallthinker.train_flops_per_step(facts["model"], rows, facts["seq"], pairs)
    shares = []
    for dev in trace.devices.values():
        runs = xplane.whole_runs(xplane.module_runs(dev, "jit_train_step"))
        if not runs:
            continue
        span_s = (max(s + d for _, s, d in runs) - min(s for _, s, _ in runs)) / 1e9
        shares.append(len(runs) * flops / span_s / facts["peaks"].bf16_flops_per_s)
    return 100.0 * sum(shares) / len(shares) if shares else None
