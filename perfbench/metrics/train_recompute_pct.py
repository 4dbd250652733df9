"""The share of the train step's device self time spent replaying the
forward inside the backward pass (phase ``recompute``: everything under
``jax.checkpoint``'s ``rematted_computation``, ``flash_fwd``'s second run
included). Time that earns no credit in ``train_step_mfu_pct``."""
import scopes


def read(facts, trace):
    return scopes.share(scopes.train_step_scopes(facts, trace),
                        lambda key: key[0] == "recompute")
