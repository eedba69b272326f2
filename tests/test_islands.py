"""The AND/NOT island combine (engine/islands.py) and its place in a
check launch.

The combine evaluates each program's circuit once over all of that
program's instances, as arrays of leaf bits, level by level, innermost
first. Here it is held to the plain loop it replaced (one island at a
time, in reverse allocation order, a stack machine each) on seeded random
circuits with nested islands, and the engine that uses it to the
reference on Keto's rewrite cases. The launch's tracing is held to what a
launch with islands did: `keto_tpu_island_instances_total` moves by the
islands it allocated, and stage `islands`, not `device_wait`, holds the
combine's time.
"""

import random
import time

import numpy as np
import pytest

from keto_tpu.config import Config
from keto_tpu.engine import islands
from keto_tpu.engine.kernel import estimate_step_gather_bytes
from keto_tpu.engine.snapshot import (
    CIRC_AND,
    CIRC_FALSE,
    CIRC_LEAF,
    CIRC_NOT,
    CIRC_OR,
)
from keto_tpu.engine.tpu_engine import TPUCheckEngine
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.observability import FlightRecorder, Metrics
from keto_tpu.storage import MemoryManager

from test_reference_engine import REWRITE_CASES, REWRITE_NAMESPACES, REWRITE_TUPLES


def eval_circuit_by_stack(ops, leaves) -> bool:
    """One instance's circuit, a bool at a time."""
    stack = []
    for op in ops:
        if op[0] == CIRC_LEAF:
            stack.append(bool(leaves[op[1]]))
        elif op[0] == CIRC_FALSE:
            stack.append(False)
        elif op[0] == CIRC_NOT:
            stack[-1] = not stack[-1]
        else:
            b = stack.pop()
            stack[-1] = (stack[-1] and b) if op[0] == CIRC_AND else (stack[-1] or b)
    return stack[-1]


def combine_by_loop(ctx_hit, isl_parent, isl_pid, n_isl, circuits, n_queries, K):
    """The oracle: every island from the last allocated to the first, so
    a nested island (the higher index) resolves before its parent reads
    the leaf it feeds."""
    for i in range(n_isl - 1, -1, -1):
        base = n_queries + i * K
        if eval_circuit_by_stack(circuits[int(isl_pid[i])], ctx_hit[base : base + K]):
            ctx_hit[int(isl_parent[i])] = True
    return ctx_hit[:n_queries]


def random_circuit(rng: random.Random, K: int) -> tuple:
    """A postfix circuit of AND, OR and NOT over leaves 0..K-1."""

    def emit(depth):
        roll = rng.random()
        if depth >= 3 or roll < 0.35:
            return [(CIRC_LEAF, rng.randrange(K))]
        if roll < 0.45:
            return [(CIRC_FALSE,)] if rng.random() < 0.2 else emit(depth + 1) + [(CIRC_NOT,)]
        combine = CIRC_AND if roll < 0.75 else CIRC_OR
        ops = emit(depth + 1)
        for _ in range(rng.randrange(1, 3)):
            ops += emit(depth + 1) + [(combine,)]
        return ops

    return tuple(emit(0))


@pytest.mark.parametrize("seed", range(24))
def test_array_combine_equals_the_loop(seed):
    rng = random.Random(seed)
    K = rng.randrange(1, 9)
    B = rng.randrange(1, 40)
    n_isl = rng.randrange(1, 60)
    circuits = {pid: random_circuit(rng, K) for pid in rng.sample(range(100), 4)}
    pids = list(circuits)
    isl_pid = np.array([rng.choice(pids) for _ in range(n_isl)], np.int32)
    # an island hangs under a query's root ctx or, nested, under a leaf ctx
    # of an island allocated before it
    isl_parent = np.array([
        rng.randrange(B) if i == 0 or rng.random() < 0.4
        else B + rng.randrange(i) * K + rng.randrange(K)
        for i in range(n_isl)
    ], np.int32)
    ctx_hit = np.array([rng.random() < 0.4 for _ in range(B + n_isl * K)])
    want = combine_by_loop(ctx_hit.copy(), isl_parent, isl_pid, n_isl, circuits, B, K)
    got = islands.combine_islands(
        ctx_hit.copy(), isl_parent, isl_pid, n_isl, circuits, B, K
    )
    assert got.tolist() == want.tolist()


def test_nesting_levels_follow_the_parents():
    # islands 0 and 1 under queries; 2 under island 0's leaf 1; 3 under 2
    B, K = 3, 2
    parent = np.array([0, 2, B + 0 * K + 1, B + 2 * K + 0])
    assert islands.nesting_levels(parent, B, K).tolist() == [0, 0, 1, 2]


def rewrite_engine(metrics=None, flightrec=None):
    cfg = Config({"limit": {"max_read_depth": 100}})
    cfg.set_namespaces(REWRITE_NAMESPACES)
    m = MemoryManager()
    m.write_relation_tuples([RelationTuple.from_string(s) for s in REWRITE_TUPLES])
    return TPUCheckEngine(m, cfg, metrics=metrics, flightrec=flightrec)


ISLAND_CASES = [
    (q, want) for q, want in REWRITE_CASES if q.startswith(("acl:", "resource:"))
]


@pytest.fixture(scope="module")
def island_batch():
    """Keto's acl and resource cases as one launch, reference beside it."""
    engine = rewrite_engine()
    queries = [RelationTuple.from_string(q) for q, _ in ISLAND_CASES]
    got = engine.check_batch(queries, 100)
    ref = [engine.reference.check_relation_tuple(q, 100) for q in queries]
    assert engine.stats["host_checks"] == 0
    return {q: (g, r) for (q, _), g, r in zip(ISLAND_CASES, got, ref)}


@pytest.mark.parametrize("query,expected", ISLAND_CASES)
def test_engine_equals_reference_on_the_island_cases(island_batch, query, expected):
    got, ref = island_batch[query]
    assert got.membership == ref.membership
    assert got.allowed == expected


def islands_counted(metrics: Metrics) -> float:
    return metrics.registry.get_sample_value("keto_tpu_island_instances_total")


def stage_seconds(metrics: Metrics, stage: str) -> tuple[float, float]:
    """(count, sum) of one stage's histogram."""
    child = metrics.check_stage_duration.labels(stage)
    samples = {s.name: s.value for s in child.collect()[0].samples}
    return (
        samples["keto_tpu_check_stage_duration_seconds_count"],
        samples["keto_tpu_check_stage_duration_seconds_sum"],
    )


def test_a_launch_with_islands_counts_them_and_times_the_combine(monkeypatch):
    metrics = Metrics()
    recorder = FlightRecorder(capacity=8)
    engine = rewrite_engine(metrics, recorder)
    # warm: the compile stays out of the readings below
    engine.check_batch([RelationTuple.from_string("acl:document#access@bob")], 100)

    pause = 0.2
    combine = islands.combine_islands

    def slow_combine(*args):
        time.sleep(pause)
        return combine(*args)

    monkeypatch.setattr(islands, "combine_islands", slow_combine)
    before_isl = islands_counted(metrics)
    before_stage = stage_seconds(metrics, "islands")
    before_wait = stage_seconds(metrics, "device_wait")
    # one island an item: each query's root task runs an AND program
    queries = [
        RelationTuple.from_string(s)
        for s in ("acl:document#access@alice", "acl:document#access@mallory",
                  "resource:topsecret#delete@mark")
    ]
    assert [r.allowed for r in engine.check_batch(queries, 100)] == [True, False, True]
    entry = recorder.entries()[-1]
    assert entry["n_isl"] == 3
    assert islands_counted(metrics) - before_isl == 3
    count, seconds = stage_seconds(metrics, "islands")
    assert count - before_stage[0] == 1
    assert seconds - before_stage[1] >= pause
    # device_wait is the readback, and no longer holds the combine
    wait_count, wait_s = stage_seconds(metrics, "device_wait")
    assert wait_count - before_wait[0] == 1
    assert wait_s - before_wait[1] < pause
    assert entry["stage_ms"]["islands"] >= pause * 1e3


def test_a_launch_without_islands_moves_neither():
    metrics = Metrics()
    engine = rewrite_engine(metrics)
    queries = [RelationTuple.from_string(s) for s in (
        "group:editors#member@mark", "resource:topsecret#read@mike",
    )]
    assert [r.allowed for r in engine.check_batch(queries, 100)] == [True, True]
    assert islands_counted(metrics) == 0
    assert stage_seconds(metrics, "islands")[0] == 0
    assert stage_seconds(metrics, "device_wait")[0] == 1


# The launch statics of the cells that compile no island: the rehearsal's
# (20,000 tuples) and drive-chip-share's probe depths on the chip. Their
# gather estimate, and with it `check_program_roofline`, is pinned.
MONOTONE_STATICS = {
    "rehearsal": ({"dh_probes": 7, "rh_probes": 8}, 9371648),
    "drive-chip-share": ({"dh_probes": 12, "rh_probes": 13}, 11468800),
}


@pytest.mark.parametrize("name", sorted(MONOTONE_STATICS))
def test_gather_estimate_without_islands_is_unchanged(name):
    probes, pinned = MONOTONE_STATICS[name]
    cfg = dict(K=2, frontier_cap=8192, n_island_cap=0, has_delta=False, **probes)
    assert estimate_step_gather_bytes(cfg) == pinned
    # the island variant's scatters: one int32 a task for each of the two
    # instance tables
    islanded = estimate_step_gather_bytes({**cfg, "n_island_cap": 4096})
    assert islanded - pinned == 2 * 8192 * 4
