"""The traced window: device events clipped to a window marked inside the
trace. Hand-made intervals on the CPU, no chip and no rates, and one CPU
profiler trace taken through run.Tracer's own code.

    python -m pytest benchmarks/tests/test_trace_window.py -q
"""

import os
import random
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH]

import run  # noqa: E402
import trace_reduce  # noqa: E402

CHIP = "/device:TPU:0"
WINDOW = (10.0, 13.0)


def planes(ops, modules=()):
    return {CHIP: {trace_reduce.OPS_LINE: list(ops),
                   trace_reduce.MODULES_LINE: list(modules)}}


def test_events_overhanging_both_edges_fill_the_window_and_no_more():
    summary = trace_reduce.reduce(
        planes([(9.96, 1.5, "a"), (11.46, 1.6, "b")]), WINDOW
    )
    assert summary["busy_s"] == summary["window_s"] == 3.0
    assert summary["idle_share"] == 0.0
    assert summary["event_span_s"] == pytest.approx(3.1)
    assert summary["idle_gaps"] == []


def test_idle_time_at_the_head_and_the_tail_of_the_window_counts():
    summary = trace_reduce.reduce(planes([(10.5, 2.0, "a")]), WINDOW)
    assert summary["busy_s"] == 2.0
    assert summary["idle_share"] == pytest.approx(1 / 3)
    assert summary["idle_gaps"] == [
        ["unattributed@0.000s", 0.5], ["unattributed@2.500s", 0.5],
    ]
    # the same events without a window: first to last event, nothing idle
    assert trace_reduce.reduce(planes([(10.5, 2.0, "a")]))["idle_share"] == 0.0


@pytest.mark.parametrize("cut, whole", [
    ((9.8, 0.4), 2),    # by the left edge
    ((12.9, 0.4), 2),   # by the right edge
    ((9.0, 5.0), 0),    # spans the whole window, and covers the other two
], ids=["left_edge", "right_edge", "spans_the_window"])
def test_a_launch_cut_by_the_window_is_counted_and_not_timed(cut, whole):
    inside = [(10.5, 0.4, "jit_check(1)"), (11.5, 0.4, "jit_check(1)")]
    modules = [(*cut, "jit_check(1)")] + (inside if whole else [])
    summary = trace_reduce.reduce(planes([(9.0, 5.0, "op")], modules), WINDOW)
    assert summary["launches_cut"] == 1
    launched = summary["programs"]
    if whole:
        assert launched == {"jit_check(1)": [2, 0.8]}
        assert trace_reduce.seconds_per_launch(launched, "check") == 0.4
    else:
        assert launched == {}


def test_a_program_with_only_cut_launches_reads_as_nothing():
    modules = [(9.9, 0.2, "jit_check(1)"), (12.95, 0.1, "jit_check(1)"),
               (11.0, 0.5, "jit_expand(2)"), (8.0, 1.0, "jit_check(1)")]
    summary = trace_reduce.reduce(planes([(9.0, 5.0, "op")], modules), WINDOW)
    assert summary["launches_cut"] == 2  # the launch before the window is neither
    assert summary["programs"] == {"jit_expand(2)": [1, 0.5]}
    run_ = type("Run", (), {"trace": summary})
    sys.path[:0] = [os.path.join(BENCH, "readers")]
    import trace_program_ms

    assert trace_program_ms.read(run_, "check") is None
    assert trace_program_ms.read(run_, "expand") == 500.0


def test_nested_events_clipped_at_an_edge_keep_self_times_within_busy():
    ops = [(9.0, 2.0, "while"), (9.5, 1.0, "a"), (10.6, 0.2, "cond"),
           (10.65, 0.1, "b"), (12.5, 1.0, "while"), (12.8, 0.5, "c")]
    summary = trace_reduce.reduce(planes(ops), WINDOW)
    assert summary["busy_s"] == pytest.approx(1.5)
    times = dict(summary["device_ops"])
    assert times == pytest.approx({"while": 0.3 + 0.3, "a": 0.5, "cond": 0.1,
                                   "b": 0.1, "c": 0.2})
    assert sum(times.values()) <= summary["busy_s"] + 1e-12


def nested(rng, start, end, depth=0):
    """Random events inside [start, end], nested or apart as a device's
    operations are: a loop's body lies inside the loop."""
    events, at = [], start
    while depth < 3:
        at += rng.expovariate(4.0) * (end - start)
        length = rng.expovariate(4.0) * (end - start)
        if at + length >= end:
            break
        events.append((at, length, f"op{depth}"))
        events += nested(rng, at, at + length, depth + 1)
        at += length
    return events


def test_random_intervals_never_give_busy_time_beyond_the_window():
    rng = random.Random(28)
    seen = 0
    while seen < 200:  # windows that held an operation
        ops = nested(rng, 0.0, 10.0) or [(5.0, 1.0, "op0")]
        w0 = rng.uniform(-1, 9)
        window = (w0, w0 + rng.uniform(0.01, 5))
        summary = trace_reduce.reduce(planes(ops), window)
        if summary is None:
            assert not trace_reduce.clip(ops, *window)
            continue
        seen += 1
        assert 0 < summary["busy_s"] <= summary["window_s"] == window[1] - window[0]
        assert 0.0 <= summary["idle_share"] < 1.0
        gaps = sum(seconds for _, seconds in summary["idle_gaps"])
        assert gaps <= summary["window_s"] - summary["busy_s"] + 1e-9
        assert sum(s for _, s in summary["device_ops"]) <= summary["busy_s"] + 1e-9
        run.device_times(summary)  # what run.py refuses never comes of a window


def test_no_operation_inside_the_window_is_nothing():
    assert trace_reduce.reduce(planes([(1.0, 2.0, "a"), (14.0, 1.0, "b")]), WINDOW) is None
    assert trace_reduce.reduce(planes([]), WINDOW) is None
    assert trace_reduce.reduce({}, WINDOW) is None


def test_of_several_chips_only_those_that_ran_in_the_window_count():
    summary = trace_reduce.reduce({
        CHIP: {trace_reduce.OPS_LINE: [(10.0, 1.5, "a")]},
        "/device:TPU:1": {trace_reduce.OPS_LINE: [(11.0, 3.0, "a")]},
        "/device:TPU:2": {trace_reduce.OPS_LINE: [(1.0, 2.0, "a")]},
    }, WINDOW)
    assert summary["busy_s"] == 1.75
    assert summary["idle_gaps"] == [["unattributed@0.000s", 1.0]]  # the fullest chip's


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """One trace taken by run.Tracer itself, on whatever device JAX has
    here: the CPU has no device plane, the host plane is what is looked at."""
    import jax.numpy as jnp

    out = tmp_path_factory.mktemp("out")
    saved, run.OUT = run.OUT, str(out)
    try:
        tracer = run.Tracer(seconds=1.0)  # a window of 0.5 s, 0.25 s in
        tracer.begin()
        jnp.arange(8).sum().block_until_ready()
        tracer._thread.join()
    finally:
        run.OUT = saved
    return tracer


def test_the_tracer_marks_one_window_of_the_asked_length(cpu_trace):
    assert cpu_trace.length == 0.5
    _, windows = trace_reduce.read_trace(cpu_trace.dir)
    ((start, end),) = windows
    assert 0.5 <= end - start < 0.6
    assert abs((end - start) - cpu_trace.host_window_s) < 0.002  # one clock or two


def test_a_trace_without_device_operations_or_without_the_mark_fails(
    cpu_trace, tmp_path
):
    with pytest.raises(run.BenchFailure, match="no operation ran on the device inside"):
        run.window_summary(cpu_trace.dir)
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))  # a session nobody marked
    jnp.arange(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    for trace_dir in (str(tmp_path), str(tmp_path / "no_trace_here")):
        with pytest.raises(run.BenchFailure, match="holds 0 bench.window events"):
            run.window_summary(trace_dir)
