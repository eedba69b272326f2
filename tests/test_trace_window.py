"""The traced window's cases (benchmarks/tests/test_trace_window.py) in tier-1.

`benchmarks/trace_reduce.py` and `run.Tracer` decide what every traced run
reports as `busy_s`, `device_idle_share` and `check_program_ms`; their cases
live with the benchmark, which `pytest tests/` does not collect and no PR but
a `benchmark` one may change. They are loaded from there by path and
re-exported, tests and fixtures, so that one copy of them runs here too.
`benchmarks/tests/test_benchmark.py` starts serving processes and stays out.
"""

import importlib.util
import os
import sys

_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "tests", "test_trace_window.py",
)
_FIXTURES = ("cpu_trace",)


def _load():
    spec = importlib.util.spec_from_file_location("benchmarks_test_trace_window", _PATH)
    module = importlib.util.module_from_spec(spec)
    path = list(sys.path)
    try:
        spec.loader.exec_module(module)  # puts benchmarks/ first on sys.path
    finally:
        sys.path[:] = path  # `run` and `trace_reduce` stay in sys.modules
    return {
        name: obj for name, obj in vars(module).items()
        if name.startswith("test_") or name in _FIXTURES
    }


globals().update(_load())
