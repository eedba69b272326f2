"""The benchmark's data files against the program they read, on the CPU.

benchmarks/run.py refuses a traced run whose daemon does not declare a
sample a metric's reader names, but that is a chip run that prints nothing.
Here every metric of BENCHMARK.json is held to the tree it is committed
with: its file and reader exist, and every Prometheus sample the reader
reads is declared by a fresh `observability.Metrics()`, with the labels the
file asks for. A renamed counter, label or stage fails here first.
"""

import json
import os
import sys

import pytest

from keto_tpu.observability import (
    CHECK_STAGES,
    DEVICE_FEED_STATES,
    EDGE_PHASES,
    MIRROR_BUILD_PHASES,
    Metrics,
)
from keto_tpu.observability_workload import FOLD_WHERE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

# the label values a fresh registry cannot show, because a labelled series
# appears with its first sample: the program's own vocabularies
LABEL_VALUES = {
    ("keto_tpu_check_stage_duration_seconds", "stage"): set(CHECK_STAGES),
    ("keto_tpu_check_stage_cpu_seconds", "stage"): set(CHECK_STAGES),
    ("keto_tpu_grpc_edge_seconds", "phase"): set(EDGE_PHASES),
    ("keto_tpu_device_feed_seconds", "state"): set(DEVICE_FEED_STATES),
    ("keto_tpu_workload_fold_seconds", "where"): set(FOLD_WHERE),
    ("keto_tpu_mirror_build_seconds", "phase"): set(MIRROR_BUILD_PHASES),
}
SAMPLE_SUFFIXES = ("_total", "_sum", "_count", "_bucket", "")


def read_json(path):
    with open(path) as f:
        return json.load(f)


BENCHMARK = read_json(os.path.join(ROOT, "BENCHMARK.json"))
METRICS = [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]


@pytest.fixture(scope="module")
def declared():
    """{family: its label names} of what a fresh Metrics() exports."""
    metrics = Metrics()
    labels = {
        c._name: set(c._labelnames)
        for c in vars(metrics).values()
        if hasattr(c, "_labelnames")
    }
    exported = {family.name for family in metrics.registry.collect()}
    assert exported and exported <= set(labels)
    return {family: labels[family] for family in exported}


@pytest.fixture(scope="module")
def load_reader():
    sys.path.insert(0, BENCH)
    try:
        from workload import load_module

        yield lambda name: load_module("readers", name)
    finally:
        sys.path.remove(BENCH)


def family_of(sample: str, declared: dict) -> str | None:
    for suffix in SAMPLE_SUFFIXES:
        if suffix and not sample.endswith(suffix):
            continue
        family = sample[: len(sample) - len(suffix)] if suffix else sample
        if family in declared:
            return family
    return None


def test_every_metric_is_named_once():
    assert len(METRICS) == len(set(METRICS))


@pytest.mark.parametrize("name", METRICS)
def test_metric_file_reads_what_the_program_declares(name, declared, load_reader):
    spec = read_json(os.path.join(BENCH, "metrics", name + ".json"))
    reader = load_reader(spec["reader"])
    assert callable(reader.read)
    args = spec["args"]
    # `names` is what run.py checks on the chip; `reads` is the same list
    # from a reader that must stay silent on a program older than its
    # metric (readers/prom_ratio_optional.py)
    samples = set()
    for listed in ("names", "reads"):
        samples |= set(getattr(reader, listed, lambda args: ())(args))
    for sample in samples:
        assert family_of(sample, declared), f"{name} reads {sample}"
    for series in args.get("num", []) + args.get("den", []):
        family = family_of(series["name"], declared)
        for label, value in series.get("labels", {}).items():
            assert label in declared[family], (name, series)
            known = LABEL_VALUES.get((family, label))
            assert known is None or value in known, (name, series)
