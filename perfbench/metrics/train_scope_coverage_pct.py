"""The guard on every other scope metric: the share of the train step's
device self time (whole traced steps, mean over devices) whose instruction
carries a model scope of its own in its ``op_name`` (``layer/attn/qkv``,
``opt``, ``scan_carry`` ...), before any scope is lent by a neighbour."""
import scopes


def read(facts, trace):
    got = scopes.train_step_scopes(facts, trace)
    return None if got is None else 100.0 * got["own_s"] / got["step_s"]
