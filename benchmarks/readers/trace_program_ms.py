"""Mean device time, in milliseconds, of one launch of the programs whose
name in the trace's `XLA Modules` line matches; nothing in a run that took
no trace or launched no such program."""

import trace_reduce


def read(run, match):
    if run.trace is None:
        return None
    seconds = trace_reduce.seconds_per_launch(run.trace["programs"], match)
    return None if seconds is None else 1e3 * seconds
