"""The decode step's share of its roofline. It is bound by bandwidth: the
bytes a step must read (every block's weights and the head's table once, K
and V of every active slot's context, from the harness's own count of
lengths) over the chip's HBM bytes per second, divided by the ``decode``
program's device time; means over the traced window's steps. Counted for the
work and found by the program's name, so it reads the same whatever kernel
does the work."""
import counts
import xplane


def read(facts, trace):
    if facts.get("peaks") is None:     # no chip, no share of a peak
        return None
    if trace is None or not facts.get("ctx_sums"):
        return None
    lo, hi = facts["trace_span"]
    steps = [(c, n) for t, c, n in facts["ctx_sums"] if lo < t <= hi and n > 0]
    runs = [d for dev in trace.devices.values()
            for _, _, d in xplane.module_runs(dev, "jit_decode")]
    if not steps or not runs:
        return None
    need = sum(counts.decode_step_bytes(facts["model"], [c]) for c, _ in steps) / len(steps)
    least_s = need / facts["peaks"].hbm_bytes_per_s
    return 100.0 * least_s / (sum(runs) / len(runs) / 1e9)
