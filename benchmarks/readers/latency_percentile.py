"""A percentile, in milliseconds, of the time from send to answer of every
RPC answered in the window, at the caller. A failed RPC counts as the
timeout. Nothing answered, nothing read."""

import numpy as np


def read(run, q):
    latency = run.child["latency_s"]
    return 1e3 * float(np.percentile(latency, q)) if latency else None
