"""Seconds from the start of the step's first call to the start of its
(``CHECK_STEPS`` + 1)-th, less the compile path inside: the set-up steps and
the comparison's reads between them."""
import hostlog


def read(facts, trace):
    got = hostlog.setup()
    if got is None:
        return None
    return hostlog.steps_s(got)
