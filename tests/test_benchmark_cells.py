"""A benchmark configuration against the plain reference, small, on the CPU.

On the chip a run compares 32 sampled answers with `engine/reference.py`
and every answer with the generator's construction truth
(benchmarks/run.py). Here the configuration's own generator, generator
parameters and daemon configuration make a store of 20,000 tuples, and every
check of one of the cell's 2,048-item batches is held to both.
"""

import os
import sys

import pytest

from keto_tpu.config import Config
from keto_tpu.registry import Registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
TUPLES = 20_000


@pytest.fixture(scope="module")
def workload_module():
    sys.path.insert(0, BENCH)
    try:
        import workload

        yield workload
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("seed", [3000003951, 11])
@pytest.mark.parametrize("config_name", ["drive-chip-share"])
def test_a_batch_of_the_cell_equals_reference_and_truth(
    workload_module, config_name, seed
):
    config = workload_module.read_json(
        os.path.join(BENCH, "configs", config_name + ".json")
    )
    traffic = workload_module.read_json(
        os.path.join(BENCH, "traffic", "batch_checks.json")
    )
    workload = workload_module.Workload(config, traffic, seed, TUPLES)
    registry = Registry(Config(config["serve_config"]))
    registry.relation_tuple_manager().bulk_load(workload.columns())
    engine = registry.check_engine()
    assert engine._ensure_state().snapshot.n_tuples == TUPLES

    queries, truth, _ = workload.request(0)
    assert len(queries) == traffic["items"] == 2048
    assert {q.relation for q in queries} == {"view"}
    assert 0.4 < truth.mean() < 0.6  # half of the queries allowed
    answers = [result.allowed for result in engine.check_batch(queries)]
    reference = [
        engine.reference.check_relation_tuple(q, 0).allowed for q in queries
    ]
    assert answers == reference
    assert answers == truth.tolist()
    assert not registry.metrics().host_fallback_total.collect()[0].samples
