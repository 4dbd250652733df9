"""The optimizer's pass as a share of its memory roofline in the
latent-attention decoder's train step: the least time the chip's memory
allows for Adam over the parameters held (22 bytes a parameter: g and p read
in bf16, m and v read and written in float32, p written in bf16) over the
device self time a step under the scope ``opt`` and everything below it, in
every phase. The twin of ``adam_step_roofline`` over ``counts_dsv2.n_params``."""
import counts_dsv2
import scopes
import scopes_dsv2

SCOPE = "opt"
BYTES_PER_PARAM = 2 + 4 + 4 + 2 + 4 + 4 + 2     # read g, m, v, p; write m, v, p


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if not scopes_dsv2.is_dsv2(facts):
        return None
    got = scopes.train_step_scopes(facts, trace)
    if got is None:
        return None
    seconds = sum(s for (_, scope, _), s in got["rows"].items()
                  if scope == SCOPE or scope.startswith(SCOPE + "/"))
    if seconds <= 0:
        return None
    least = (counts_dsv2.n_params(facts["model"]) * BYTES_PER_PARAM
             / facts["peaks"].hbm_bytes_per_s)
    return 100.0 * least / seconds
