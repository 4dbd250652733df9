"""The share of the train step's device self time under ``layer/attn/*`` of
the latent-attention decoder (the query projection, the latent's down
projection, its norm and its expansion into heads, the rotation, the flash
kernels, the output projection; every phase): whether the mechanism does the
work the cell is there for."""
import scopes
import scopes_dsv2

SCOPE = "layer/attn"


def read(facts, trace):
    if not scopes_dsv2.is_dsv2(facts):
        return None
    got = scopes.train_step_scopes(facts, trace)
    if got is None or not any(k[1].startswith(SCOPE + "/kv_up") for k in got["rows"]):
        return None
    return scopes.share(got, lambda key: key[1] == SCOPE or key[1].startswith(SCOPE + "/"))
