"""Laguna's whole train step as a share of the chip's bf16 peak: the steps
that lie wholly inside the traced window, over the span from the first such
step's start to the last one's end on the device's clock, x
``counts_laguna.train_flops_per_step`` (6 a weight a position for the
projections, the per-head gates, the dense and shared FFNs and the router,
attention over the pairs the masks let through: full layers s(s+1)/2 at 48
heads, window layers s·512 - 512·511/2 at 72 heads, 512 operations a pair a
head forward; the held experts over the pairs the kind's counter says were
held; the head) over the peak. Recomputation is not credited."""
import counts_laguna
import scopes_laguna
import xplane


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    pairs = scopes_laguna.pairs_held(facts)
    if trace is None or not scopes_laguna.is_laguna(facts) or pairs is None:
        return None
    rows = facts["rows"] // facts["chips"]
    flops = counts_laguna.train_flops_per_step(facts["model"], rows, facts["seq"], pairs)
    shares = []
    for dev in trace.devices.values():
        runs = xplane.whole_runs(xplane.module_runs(dev, "jit_train_step"))
        if not runs:
            continue
        span_s = (max(s + d for _, s, d in runs) - min(s for _, s, _ in runs)) / 1e9
        shares.append(len(runs) * flops / span_s / facts["peaks"].bf16_flops_per_s)
    return 100.0 * sum(shares) / len(shares) if shares else None
