"""Tests of the benchmark harness itself: no chip, no described topology.

    python -m pytest benchmarks/tests -q

Cases (a), (e) and the traced half of (a) share one rehearsal of each kind
(JAX_PLATFORMS=cpu, --tuples 20000), run through module-scoped fixtures.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH]

import loadgen  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from scrape import Scrape  # noqa: E402

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def rehearse(root: str, cell: str, trace: int, program=None) -> tuple[dict, str, dict]:
    """(the result line, the standard error, the phase lines by phase) of a
    rehearsal of run.py, or of `program`, a script that ends in run.main()."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    command = ["-c", program] if program else [os.path.join(root, "benchmarks", "run.py")]
    done = subprocess.run(
        [sys.executable, *command,
         "--workload", cell, "--seed", "3000000001", "--seconds", "2",
         "--trace", str(trace), "--tuples", "20000"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    phases = {line["phase"]: line for line in lines if "phase" in line}
    return lines[-1], done.stderr, phases


def read_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return read_bench()


@pytest.mark.parametrize("cell", read_bench()["workloads"], ids=lambda c: c["name"])
def test_a_cells_files_are_there(cell, bench):
    config = run.named(bench["configs"], cell["config"], "configuration")
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    assert os.path.isfile(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))


@pytest.mark.parametrize("metric", read_bench()["per_layer"], ids=lambda m: m["name"])
def test_a_layer_metric_names_cells_and_an_end_to_end_metric(metric, bench):
    cells = {cell["name"] for cell in bench["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    assert metric["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert os.path.isfile(os.path.join(BENCH, "metrics", metric["name"] + ".json"))


@pytest.mark.parametrize("metric", read_bench()["end_to_end"], ids=lambda m: m["name"])
def test_an_end_to_end_bound_lies_within_the_contracts(metric):
    assert 0 < metric["bound"] <= 0.25
    assert os.path.isfile(os.path.join(BENCH, "metrics", metric["name"] + ".json"))


@pytest.fixture(scope="module", params=[0, 1], ids=["end_to_end", "per_layer"])
def rehearsal(request, bench):
    cell = bench["workloads"][0]["name"]
    line, _, phases = rehearse(ROOT, cell, request.param)
    return request.param, line, phases


def test_rehearsal_prints_the_contracts_line(rehearsal, bench):
    """(a) exactly the contract's keys, correct, every end-to-end metric, and
    no device metric from a CPU run; (e) a traced run fails if a metric file
    reads a Prometheus name the daemon does not expose, so this passing run
    has found every one."""
    trace, line, _ = rehearsal
    assert list(line) == RESULT_KEYS  # what was compared comes last
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert all(number <= limit for number, limit in line["compared"].values())
    assert line["device"]["platform"] == "cpu"
    assert {"busy_s", "window_s"}.isdisjoint(line["device"])
    if trace:
        chip_only = {
            m["name"] for m in bench["per_layer"]
            if m["source"] == "device_trace" or m["layer"] == "device"
        } - {"compiles_in_window"}
        assert chip_only.isdisjoint(line["metrics"])
        assert {"server_rpc_ms", "checks_per_launch"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 or trace for m in line["metrics"].values())


def test_the_rehearsals_phase_lines_say_where_the_spread_comes_from(rehearsal):
    """The window's halves and quarters add up to the window, the longest
    time without an answer lies inside it, and the `judged` line carries the
    collector's full collections and the cores the serving process may use."""
    _, line, phases = rehearsal
    window, judged = phases["window"], phases["judged"]
    assert window["attempted"] == line["attempted"]
    for parts in (window["halves"], window["quarters"]):
        assert sum(part["rpcs"] for part in parts) == window["attempted"]
        assert sum(part["good_checks"] for part in parts) == window["good_checks"]
        assert all(part["p50_ms"] > 0 for part in parts)
    assert 0 < window["longest_gap_s"] < window["window_s"]
    assert 0 <= window["longest_gap_at_s"] < window["window_s"]
    assert judged["full_collections"] >= 0 <= judged["full_collection_s"]
    assert (judged["full_collections"] == 0) == (judged["full_collection_s"] == 0)
    assert judged["cpu_count"] >= len(judged["cpus_allowed"]) > 0


def test_a_windows_parts_add_up_and_a_planted_stall_is_its_longest_gap():
    """Ten answers a second for 8 s, none after 3.0 s and before 5.5 s."""
    answered_s = [t / 10 for t in range(1, 80) if not 30 < t < 55]
    latency = [0.1 + (0.2 if t > 5.4 else 0.0) for t in answered_s]
    good = [2048 if k % 9 else 2047 for k in range(len(answered_s))]
    halves = loadgen.window_parts(answered_s, 8.0, latency, good, 2)
    quarters = loadgen.window_parts(answered_s, 8.0, latency, good, 4)
    for parts in (halves, quarters):
        assert sum(part["rpcs"] for part in parts) == len(answered_s)
        assert sum(part["good_checks"] for part in parts) == sum(good)
    assert [part["rpcs"] for part in quarters] == [19, 11, 5, 20]
    assert [part["rpcs"] for part in halves] == [30, 25]
    assert halves[0]["p50_ms"] == pytest.approx(100.0)
    assert halves[1]["p50_ms"] == pytest.approx(300.0)
    gap_s, at_s = loadgen.longest_gap(answered_s, 8.0)
    assert (gap_s, at_s) == (pytest.approx(2.5), pytest.approx(3.0))
    # a stall at either end of the window counts, and so does an empty window
    assert loadgen.longest_gap([3.0, 3.5], 8.0) == (4.5, 3.5)
    assert loadgen.longest_gap([], 8.0) == (8.0, 0.0)
    assert loadgen.window_parts([], 8.0, [], [], 2) == [
        {"rpcs": 0, "good_checks": 0, "p50_ms": None}] * 2


def test_the_collectors_full_collections_are_counted_and_timed():
    import gc

    collections = run.Collections()
    try:
        gc.collect(0)
        assert (collections.count, collections.seconds) == (0, 0.0)
        gc.collect()
        gc.collect(2)
    finally:
        seen = collections.close()
    assert seen["full_collections"] == 2 and seen["full_collection_s"] > 0
    gc.collect()
    assert collections.count == 2  # closed: it counts no more


def test_a_new_cell_is_files_and_an_entry(tmp_path, bench):
    """(b) a configuration, a generator, a traffic mix and a layer metric
    added as files of their own, and entries in BENCHMARK.json, are found by
    name: run.py and loadgen.py are copied unchanged."""
    new = tmp_path / "benchmarks"
    shutil.copytree(BENCH, new, ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(new / "generators" / "drive.py", new / "generators" / "drive_b.py")
    config = json.loads((new / "configs" / "drive-1e6.json").read_text())
    config["generator"] = "drive_b"
    (new / "configs" / "other.json").write_text(json.dumps(config))
    traffic = json.loads((new / "traffic" / "view_checks.json").read_text())
    traffic.update(callers=8, think_ms=0)
    (new / "traffic" / "few_checks.json").write_text(json.dumps(traffic))
    shutil.copy(new / "metrics" / "queue_ms.json", new / "metrics" / "queue_b_ms.json")
    bench = json.loads(json.dumps(bench))
    bench["configs"].append({"name": "other", "file": "benchmarks/configs/other.json"})
    bench["workloads"].append({"name": "other.few", "config": "other",
                               "traffic": "few_checks", "chips": 1})
    bench["per_layer"].append({"name": "queue_b_ms", "unit": "ms",
                               "workloads": ["other.few"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    line, _, _ = rehearse(str(tmp_path), "other.few", 1)
    assert line["correct"] is True
    assert line["metrics"]["queue_b_ms"]["value"] > 0


OPS = [(0.0, 1.0, "a"), (0.5, 1.0, "b"), (3.0, 1.0, "a"), (3.2, 0.1, "c")]


def test_trace_reduction_on_hand_made_intervals():
    """(c) overlapping and nested intervals included."""
    assert trace_reduce.merge(OPS) == [(0.0, 1.5), (3.0, 4.0)]
    assert trace_reduce.busy_seconds(OPS) == 2.5
    assert trace_reduce.idle_share(OPS, 5.0) == 0.5
    assert trace_reduce.top_ops(OPS, 2) == [["a", 2.0], ["b", 1.0]]
    assert trace_reduce.idle_gaps(OPS, 0.0, 5.0, 2) == [(1.5, 1.5), (4.0, 1.0)]
    nested = [(0, 10, "while"), (1, 2, "a"), (3, 4, "cond"), (3.5, 1, "b")]
    assert trace_reduce.self_times(nested) == [
        (0, 4, "while"), (1, 2, "a"), (3, 3, "cond"), (3.5, 1, "b"),
    ]
    assert trace_reduce.clip(nested, 2.0, 4.0) == [
        (2.0, 2.0, "while"), (2.0, 1.0, "a"), (3.0, 1.0, "cond"), (3.5, 0.5, "b"),
    ]
    launches = [(0, 2.0, "p"), (2, 4.0, "p"), (6, 9.0, "p")]
    assert trace_reduce.whole_launches(launches, 1.0, 7.0) == ([(2, 4.0, "p")], 2)
    assert trace_reduce.whole_launches(launches, 3.0, 5.0) == ([], 1)
    launched = trace_reduce.programs(
        [(0, 2.0, "jit_check(1)"), (2, 4.0, "jit_check(1)"), (6, 9.0, "jit_expand(2)")]
    )
    assert trace_reduce.seconds_per_launch(launched, "check") == 3.0
    assert trace_reduce.seconds_per_launch(launched, "filter") is None


def test_reduce_reports_busy_window_and_breakdown():
    planes = {"/device:TPU:0": {
        trace_reduce.OPS_LINE: OPS,
        trace_reduce.MODULES_LINE: [(0.0, 1.5, "jit_check(7)"), (3.0, 1.0, "jit_check(7)")],
    }}
    summary = trace_reduce.reduce(planes, window=(0.0, 5.0))
    assert summary["window_s"] == 5.0 and summary["event_span_s"] == 4.0
    assert summary["busy_s"] == 2.5 and summary["idle_share"] == 0.5
    assert summary["programs"] == {"jit_check(7)": [2, 2.5]}
    assert summary["launches_cut"] == 0
    assert summary["idle_gaps"][0][1] == 1.5
    cut = trace_reduce.reduce(planes, window=(1.0, 3.5))
    assert cut["busy_s"] == 1.0 and cut["window_s"] == 2.5
    assert cut["programs"] == {} and cut["launches_cut"] == 2
    # without a window: from the first to the last event, no launch cut
    whole = trace_reduce.reduce(planes)
    assert whole["window_s"] == 4.0 and whole["busy_s"] == 2.5
    assert whole["programs"] == {"jit_check(7)": [2, 2.5]}
    assert trace_reduce.reduce({"/device:TPU:0": {trace_reduce.OPS_LINE: []}}) is None


def test_busy_time_beyond_the_window_is_refused():
    """The contract refuses a traced line unless 0 < busy_s <= window_s; so
    does run.py, naming both numbers (PR 27's first traced deep run)."""
    assert run.device_times({"busy_s": 2.5, "window_s": 3.0}) == {
        "busy_s": 2.5, "window_s": 3.0,
    }
    for busy_s, window_s in ((3.04432, 3.00087), (0.0, 3.0)):
        with pytest.raises(run.BenchFailure) as failure:
            run.device_times({"busy_s": busy_s, "window_s": window_s})
        assert repr(busy_s) in str(failure.value)
        assert repr(window_s) in str(failure.value)


def test_a_failure_ends_the_command_with_code_1():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "no.such",
         "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 1 and done.stdout == ""
    assert "FAILED: BENCHMARK.json has no workload named 'no.such'" in done.stderr


def test_an_unknown_device_kind_is_an_error():
    """(d)"""
    assert run.device_peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.BenchFailure, match="TPU v9"):
        run.device_peak("TPU v9")


def test_a_renamed_counter_is_not_known():
    """(e), the negative: the check a traced run makes on every metric file."""
    scrape = Scrape(
        "# TYPE keto_tpu_checks_total counter\n"
        'keto_tpu_checks_total{path="device"} 3\n'
        "# TYPE keto_tpu_host_fallback_total counter\n"
    )
    assert scrape.knows("keto_tpu_checks_total")
    assert scrape.knows("keto_tpu_host_fallback_total")  # declared, never counted
    assert not scrape.knows("keto_tpu_checks_renamed_total")


@pytest.mark.parametrize("doctored, correct, failed", [
    ({}, True, 0),
    ({"wrong_checks": 1, "wrong_rpcs": 1}, False, 1),
    ({"errors": 2}, True, 2),
], ids=["clean", "wrong_answer", "rpc_errors"])
def test_a_wrong_answer_makes_the_run_incorrect(doctored, correct, failed):
    """(f) a wrong answer in the child's result line makes `correct` false
    and counts in `failed`; so do a reference mismatch, a failed device
    batch and an open breaker."""
    child = {"attempted": 10, "errors": 0, "wrong_rpcs": 0, "wrong_checks": 0,
             "callers_stuck": 0, **doctored}
    assert run.verdict(child, 0, {}, 0.0) == (correct, failed)
    assert run.verdict(child, 1, {}, 0.0)[0] is False
    assert run.verdict(child, 0, {"device": 1.0}, 0.0)[0] is False
    assert run.verdict(child, 0, {}, 1.0)[0] is False


ONE_ANSWER_ALTERED = """
import sys
sys.path[:0] = [%r]
import run
from keto_tpu.engine import definitions, tpu_engine

whole = tpu_engine.TPUCheckEngine.check_batch


def altered(self, *args, **kwargs):
    results = list(whole(self, *args, **kwargs))
    results[-1] = (definitions.RESULT_NOT_MEMBER if results[-1].allowed
                   else definitions.RESULT_IS_MEMBER)
    return results


tpu_engine.TPUCheckEngine.check_batch = altered
sys.exit(run.main(sys.argv[1:]))
""" % BENCH


def test_an_answer_altered_where_it_is_produced_makes_the_run_incorrect(bench):
    """(f) through a whole rehearsal: the engine under the daemon flips the
    last of every batch's 2,048 answers, and the run says so, in `correct`,
    in `failed`, in what it compared and on its last lines of stderr."""
    cell = bench["workloads"][0]["name"]
    line, stderr, _ = rehearse(ROOT, cell, 0, program=ONE_ANSWER_ALTERED)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    wrong, limit = line["compared"]["wrong_checks"]
    assert wrong == line["attempted"] > limit == 0
    assert stderr.strip().splitlines()[-4].startswith(f"compared wrong_checks: {wrong} ")


COMPILES_ONCE = """
import sys
sys.path[:0] = [%r]
import jax
import run

whole = run.serve


def serve(config, cols):
    if "--again" not in sys.argv:  # as if the check program had been compiled
        jax.monitoring.record_event(run.CACHE_WRITE_EVENT)
    return whole(config, cols)


run.serve = serve
sys.exit(run.serve_once())
""" % BENCH


def test_a_process_that_compiled_is_started_again(bench, capfd):
    """The window is served by a process that read every program from the
    compile cache: one that compiled and cached a program ends before any
    traffic and is started once more, and `setup_s` covers both set-ups."""
    cell = bench["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    code = ("import sys; sys.path.insert(0, %r); import run; "
            "sys.exit(run.supervise(sys.argv[1:], [sys.executable, '-c', %r]))"
            % (BENCH, COMPILES_ONCE))
    done = subprocess.run(
        [sys.executable, "-c", code, "--workload", cell, "--seed", "3000000002",
         "--seconds", "2", "--trace", "0", "--tuples", "20000"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
    serving = [l for l in lines if l.get("phase") == "serving"]
    assert [(l["programs_compiled"], l["again"]) for l in serving] == [
        (1, False), (0, True),
    ]
    assert sum("correct" in l for l in lines) == 1 and lines[-1]["correct"] is True
    one_setup = serving[1]["bulk_load_s"] + serving[1]["snapshot_build_s"]
    assert lines[-1]["metrics"]["setup_s"]["value"] > 2 * one_setup


def test_a_process_that_compiles_every_time_is_not_started_a_third_time(capfd):
    always = [sys.executable, "-c", "import sys; print('started'); sys.exit(%d)" % run.AGAIN]
    assert run.supervise([], always) == 1
    assert capfd.readouterr().out.count("started") == 2
    assert run.supervise([], [sys.executable, "-c", "import sys; sys.exit(3)"]) == 3


def test_the_load_generator_stays_off_jax():
    code = ("import sys; sys.path.insert(0, %r); import loadgen; "
            "import keto_tpu.api.client; assert 'jax' not in sys.modules" % BENCH)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
