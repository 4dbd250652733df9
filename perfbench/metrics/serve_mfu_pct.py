"""The serving model step's share of the chip's bf16 peak: (2N + 4·L·h·context)
for every token fed in the window, prefill and decode alike, counted by the
harness from the requests it sent, over window x peak."""


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if facts.get("kind") != "serve" or not facts.get("fed_flops"):
        return None
    return 100.0 * facts["fed_flops"] / (
        facts["window_s"] * facts["chips"] * facts["peaks"].bf16_flops_per_s)
