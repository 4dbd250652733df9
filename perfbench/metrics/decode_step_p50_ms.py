"""Device time of the engine's ``decode`` program per call, the median over
the calls in the traced window."""
import statistics

import xplane


def read(facts, trace):
    if trace is None:
        return None
    runs = [d for dev in trace.devices.values()
            for _, _, d in xplane.module_runs(dev, "jit_decode")]
    return statistics.median(runs) / 1e6 if runs else None
