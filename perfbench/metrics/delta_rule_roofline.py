"""The gated delta rule's share of its roofline in the train step: the least
time the chip could take for every linear layer's core, forward + backward
(the larger of its recurrent-form operations over the bf16 peak and the bytes
of q, k, v, g, beta, o and their cotangents over the memory's peak:
``counts_hybrid``), over the device self time a step under the scope
``layer/linattn/core`` in every phase (``scopes.py``: a kernel, or XLA's
fusions, read alike; the core replayed under remat costs time and earns no
credit)."""
import counts_hybrid
import scopes
import scopes_hybrid

SCOPE = "layer/linattn/core"


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if not scopes_hybrid.is_hybrid(facts):
        return None
    got = scopes.train_step_scopes(facts, trace)
    if got is None:
        return None
    seconds = sum(s for (_, scope, _), s in got["rows"].items()
                  if scope == SCOPE or scope.startswith(SCOPE + "/"))
    if seconds <= 0:
        return None
    model, peaks = facts["model"], facts["peaks"]
    tokens = (facts["rows"] // facts["chips"]) * facts["seq"]
    layers = counts_hybrid.layers_held(model).count(counts_hybrid.LINEAR)
    least = max(counts_hybrid.delta_rule_flops_per_token(model) / peaks.bf16_flops_per_s,
                counts_hybrid.delta_rule_bytes_per_token(model) / peaks.hbm_bytes_per_s)
    return 100.0 * tokens * layers * least / seconds
