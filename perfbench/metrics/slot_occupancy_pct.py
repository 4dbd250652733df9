"""Occupied slots (decoding or mid-prefill) over all slots, the mean over the
window's engine steps (``engine.occupancy()`` after each)."""


def read(facts, trace):
    occ = facts.get("occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None
