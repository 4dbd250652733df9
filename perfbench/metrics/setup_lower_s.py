"""Seconds of set-up in which a program was being traced or lowered to a
module (``trace`` and ``lower`` records of the program's host log, every
program, as a union): paid on every run, warm or cold, kernels' payloads
included."""
import hostlog


def read(facts, trace):
    got = hostlog.setup()
    if got is None:
        return None
    return hostlog.seconds(got["records"], ("trace", "lower"))
