"""How many programs set-up compiled with no persistent cache hit inside
(``compile`` records of the program's host log, every program): 0 on a warm
run; more marks a cold run, or a cache key that moved."""
import hostlog


def read(facts, trace):
    got = hostlog.setup()
    if got is None:
        return None
    return sum(not r.cached for r in hostlog.compiles(got["records"]))
