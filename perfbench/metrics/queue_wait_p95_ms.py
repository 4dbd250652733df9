"""Submitted to admitted into a slot, on the engine's own clock (its
``admitted`` events), 95th percentile over the requests the window admitted."""
from harness import percentile


def read(facts, trace):
    q = facts.get("queue_ms")
    return percentile(q, 95) if q else None
