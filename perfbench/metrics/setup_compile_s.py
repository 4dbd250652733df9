"""Seconds of set-up in which a program was being compiled or read from the
persistent cache (``compile`` records of the program's host log, every
program, as a union).

Before the result it prints the table ``PERF.md`` §5 is written from:
``{"info": <cell>, "setup_by_program": [[program, trace_s, lower_s,
compile_s, compiled, read, traced], ... 25], "setup_records", "setup_passes",
"setup_log_covered_s", "setup_log_span_s"}`` (``hostlog.print_table``)."""
import hostlog


def read(facts, trace):
    got = hostlog.setup()
    if got is None:
        return None
    hostlog.print_table(facts, got)
    return hostlog.seconds(got["records"], ("compile",))
