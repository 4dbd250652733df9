"""The optimizer's pass as a share of its memory roofline in the train step:
the least time the chip's memory allows for Adam over the parameters a chip
holds (22 bytes a parameter: g and p read in bf16, m and v read and written
in float32, p written in bf16: the state the configurations state) over the
device self time a step under the scope ``opt`` and everything below it, in
every phase (``scopes.py``: a kernel, or XLA's fusions, read alike). The
hybrid's few float32 leaves move more than 22 bytes, so the reading can only
err low; above 100, time under ``opt`` went missing."""
import counts
import counts_hybrid
import scopes

SCOPE = "opt"
BYTES_PER_PARAM = 2 + 4 + 4 + 2 + 4 + 4 + 2     # read g, m, v, p; write m, v, p


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    got = scopes.train_step_scopes(facts, trace)
    if got is None:
        return None
    seconds = sum(s for (_, scope, _), s in got["rows"].items()
                  if scope == SCOPE or scope.startswith(SCOPE + "/"))
    if seconds <= 0:
        return None
    model = facts["model"]
    family = counts_hybrid if "layer_types" in model else counts
    least = family.n_params(model) * BYTES_PER_PARAM / facts["peaks"].hbm_bytes_per_s
    return 100.0 * least / seconds
