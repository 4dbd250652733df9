"""How late the load generator ran: due instant to the actual ``submit()``,
95th percentile over the window's requests, on the harness's clock."""
from harness import percentile


def read(facts, trace):
    late = facts.get("late_ms")
    return percentile(late, 95) if late else None
