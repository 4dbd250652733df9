"""The device's idle share of the traced window: 1 - the union of the
intervals in which an operation ran, on the device that idles most."""


def read(facts, trace):
    summary = facts.get("trace_summary")
    if not summary:
        return None
    return 100.0 * summary["idle_share_max"]
