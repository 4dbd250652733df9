"""How many times set-up compiled the train step's program, or read it from
the persistent cache (its ``compile`` records in the program's host log)."""
import hostlog


def read(facts, trace):
    got = hostlog.setup()
    if got is None:
        return None
    return len(hostlog.compiles(got["records"], hostlog.STEP))
