"""A number of trace_reduce.reduce()'s summary of the device trace; nothing
in a run that took no trace."""


def read(run, key, scale=1.0):
    value = (run.trace or {}).get(key)
    return None if value is None else scale * value
