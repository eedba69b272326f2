"""A count of the load generator's result line over the length of its
window, by the child's own clock: a rate over all the work and all the time
of the window."""


def read(run, count, scale=1.0):
    return scale * run.child[count] / run.child["window_s"]
