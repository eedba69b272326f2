"""prom_ratio over series that a program older than the metric does not
declare; nothing read there, and nothing refused.

run.py refuses a run whose daemon lacks a sample that a reader's `names()`
returns, so that a renamed counter fails and is not left out. The driver
also runs this benchmark, as the newest PR leaves it, over that PR's parent
commit, where a metric family the PR adds is missing by right. So this
reader hands run.py no names. What a rename would break is held on the CPU
instead: tests/test_benchmark_metrics.py checks `reads()` of every metric
file against a fresh `observability.Metrics()` of the tree it runs in."""

from readers import prom_ratio

reads = prom_ratio.names
read = prom_ratio.read
