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

import run  # noqa: E402
import trace_reduce  # noqa: E402
from scrape import Scrape  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(root: str, cell: str, trace: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3000000001", "--seconds", "2",
         "--trace", str(trace), "--tuples", "20000"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=[0, 1], ids=["end_to_end", "per_layer"])
def rehearsal(request, bench):
    cell = bench["workloads"][0]["name"]
    return request.param, rehearse(ROOT, cell, request.param)


def test_rehearsal_prints_the_contracts_line(rehearsal, bench):
    """(a) exactly the contract's keys, correct, every end-to-end metric, and
    no device metric from a CPU run; (e) a traced run fails if a metric file
    reads a Prometheus name the daemon does not expose, so this passing run
    has found every one."""
    trace, line = rehearsal
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
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
    line = rehearse(str(tmp_path), "other.few", 1)
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
    summary = trace_reduce.reduce(planes, window_s=5.0)
    assert summary["busy_s"] == 2.5 and summary["idle_share"] == 0.5
    assert summary["programs"] == {"jit_check(7)": [2, 2.5]}
    assert summary["idle_gaps"][0][1] == 1.5
    assert trace_reduce.reduce({"/device:TPU:0": {trace_reduce.OPS_LINE: []}}) is None


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


def test_the_load_generator_stays_off_jax():
    code = ("import sys; sys.path.insert(0, %r); import loadgen; "
            "import keto_tpu.api.client; assert 'jax' not in sys.modules" % BENCH)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
