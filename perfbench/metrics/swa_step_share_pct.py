"""The share of Laguna's train step (device self time, every phase) under
``layer/swa/*``: the sliding-window layers' attention sublayer (projections,
QK-norm, the rotation, the flash kernels on the window's listed schedule,
the per-head gate, the output projection). Whether the mechanism the cell is
there for does its share of the work."""
import scopes
import scopes_laguna


def read(facts, trace):
    if not scopes_laguna.is_laguna(facts):
        return None
    got = scopes.train_step_scopes(facts, trace)
    swa = scopes_laguna.SWA
    if got is None or not any(k[1].startswith(swa + "/") for k in got["rows"]):
        return None
    return scopes.share(got, lambda key: key[1] == swa or key[1].startswith(swa + "/"))
