"""Share of the traced window in which no operation ran on the chip."""
from bench import trace_reduce


def value(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * trace_reduce.idle_share(run.trace)
