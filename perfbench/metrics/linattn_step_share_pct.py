"""The share of the train step's device self time under ``layer/linattn/*``
(projections, convolutions, the delta rule's core, the gated norm, the output
projection; every phase): whether the mechanism does the work the cell is
there for."""
import scopes

SCOPE = "layer/linattn"


def read(facts, trace):
    got = scopes.train_step_scopes(facts, trace)
    if got is None or not any(k[1].startswith(SCOPE) for k in got["rows"]):
        return None
    return scopes.share(got, lambda key: key[1] == SCOPE or key[1].startswith(SCOPE + "/"))
