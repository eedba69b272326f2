"""A number the harness takes itself around the program (a host clock around
a set-up phase, the child's CPU seconds, a count of compile events)."""


def read(run, key, scale=1.0):
    value = run.values.get(key)
    return None if value is None else scale * value
