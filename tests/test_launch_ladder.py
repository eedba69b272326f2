"""The launch ladder: a check launch is sized to its batch.

`tpu_engine._BUCKETS` is every power of two from 16 to 16,384, so a batch
of n items runs in the smallest power of two >= n (floor 16) and under half
of a launch's static shape is padding. The frontier gets one, two or four
slots a query slot, between `max(B, 64)` and `min(frontier_cap, max(4 * B,
64))`: the narrowest that held what the bucket's recent launches asked for,
two before any. Held here, on the CPU, as counts: the shapes the engine
hands `check_kernel_packed`, the verdicts against the reference engine, a
fan-out that overflows the narrowed frontier and stays on the device
through a second launch at the widest, and one that overflows that too and
is replayed exactly on the host.
"""

from __future__ import annotations

import numpy as np
import pytest

from keto_tpu.config import Config
from keto_tpu.engine import kernel, tpu_engine
from keto_tpu.engine.tpu_engine import TPUCheckEngine
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.namespace.ast import (
    ComputedSubjectSet,
    Relation,
    SubjectSetRewrite,
    TupleToSubjectSet,
)
from keto_tpu.observability import Metrics
from keto_tpu.storage import MemoryManager

FRONTIER_CAP = 1 << 14  # the engine's default

# files under folders, `view` through a computed userset and a
# tuple-to-userset: the drive cells' two children a step, at a toy size
FOLDERS, FILES = 8, 64
NAMESPACES = [
    Namespace(name="files", relations=[
        Relation(name="owner"),
        Relation(name="parent"),
        Relation(name="view", subject_set_rewrite=SubjectSetRewrite(children=[
            ComputedSubjectSet(relation="owner"),
            TupleToSubjectSet(
                relation="parent", computed_subject_set_relation="view"
            ),
        ])),
    ]),
]
TUPLES = [f"files:d{d}#owner@u{d}" for d in range(FOLDERS)] + [
    f"files:f{f}#parent@(files:d{f % FOLDERS}#...)" for f in range(FILES)
]


def engine_for(namespaces, tuples, **kwargs):
    cfg = Config({"limit": {"max_read_depth": 5}})
    cfg.set_namespaces(namespaces)
    manager = MemoryManager()
    manager.write_relation_tuples(
        [RelationTuple.from_string(s) for s in tuples]
    )
    return TPUCheckEngine(manager, cfg, **kwargs)


def launches_of(engine, queries, monkeypatch):
    """(results, [(qpack's shape, frontier_cap)] of every check launch)."""
    seen = []
    launch = kernel.check_kernel_packed

    def recorded(tables, qpack, **statics):
        seen.append((tuple(qpack.shape), statics["frontier_cap"]))
        return launch(tables, qpack, **statics)

    monkeypatch.setattr(kernel, "check_kernel_packed", recorded)
    return engine.check_batch(queries), seen


def frontier_of(B: int, slots: int, cap: int = FRONTIER_CAP) -> int:
    """The frontier rung of `slots` slots a query slot of bucket B."""
    return min(cap, max(4 * B, 64), max(B, 64, slots * B))


def expected_widths(n: int, largest: int = FRONTIER_CAP) -> list[int]:
    """Smallest power of two >= n, floor 16; more than the largest bucket
    is cut along it and each slice sized alone."""
    return [
        max(16, 1 << (m - 1).bit_length())
        for m in [largest] * (n // largest) + [n % largest] if m
    ]


def test_the_ladder_is_every_power_of_two():
    assert tpu_engine._BUCKETS == tuple(1 << k for k in range(4, 15))
    # the frontier-peak histogram carries the ladder's edges, so a peak's
    # distance to its launch's cap stays readable at every rung
    edges = Metrics().launch_frontier_peak._upper_bounds
    assert set(tpu_engine._BUCKETS) <= set(edges)
    assert 4 * tpu_engine._BUCKETS[-1] in edges


@pytest.fixture(scope="module")
def engine():
    return engine_for(NAMESPACES, TUPLES)


@pytest.mark.parametrize(
    "n", [1, 16, 17, 32, 33, 1025, 2048, 2049, 4096, 5000, 16384, 20000]
)
def test_a_launch_is_sized_to_its_batch(n, engine, monkeypatch):
    queries = [
        RelationTuple.from_string(
            # every other one by the next folder's owner, who may not
            f"files:f{i % FILES}#view@u{(i + i % 2) % FOLDERS}"
        )
        for i in range(n)
    ]
    before = dict(engine.stats)
    results, launches = launches_of(engine, queries, monkeypatch)
    widths = expected_widths(n)
    # two children a query slot at most: every launch at two frontier
    # slots a query slot, the rung it starts at and the one it keeps
    assert launches == [((7, B), frontier_of(B, 2)) for B in widths]
    # the reference engine's verdict, asked once a distinct query
    reference = {
        str(q): engine.reference.check_is_member(q) for q in queries[:2 * FILES]
    }
    got = [r.allowed for r in results]
    assert got == [reference[str(q)] for q in queries]
    assert got == [i % 2 == 0 for i in range(n)]
    # two live tasks a query: nothing leaves the device while the frontier
    # has two slots a query slot; a full 16,384 bucket under the default
    # cap has one, at its widest, and what overflows is replayed (the
    # cap's rule, as ever)
    replayed = engine.stats["host_checks"] - before["host_checks"]
    assert (replayed > 0) == (2 * widths[0] > FRONTIER_CAP)
    assert engine.stats["device_checks"] + engine.stats["host_checks"] == (
        before["device_checks"] + before["host_checks"] + n
    )


def test_a_capped_engine_splits_along_its_largest_bucket(monkeypatch):
    engine = engine_for(NAMESPACES, TUPLES, frontier_cap=128)
    queries = [
        RelationTuple.from_string(f"files:f{i % FILES}#view@u{i % FOLDERS}")
        for i in range(300)
    ]
    results, launches = launches_of(engine, queries, monkeypatch)
    # buckets stop at the cap: 128 + 128 + 44 in a 64, every frontier 128
    assert launches == [((7, 128), 128), ((7, 128), 128), ((7, 64), 128)]
    assert all(r.allowed for r in results)


# groups of nested groups: a `member` check of a group has FAN_OUT children
# a step. Three lie between the two frontier slots a query slot that a
# launch starts at and the four of the widest rung; six lie beyond both
GROUPS = 32
FAN_NAMESPACES = [Namespace(name="g", relations=[Relation(name="member")])]


def fan_engine(fan_out: int, metrics: Metrics):
    tuples = [
        f"g:top{t}#member@(g:sub{t}_{s}#member)"
        for t in range(GROUPS) for s in range(fan_out)
    ] + [f"g:sub{t}_{fan_out - 1}#member@u{t}" for t in range(GROUPS)]
    return engine_for(FAN_NAMESPACES, tuples, metrics=metrics)


def fan_queries():
    return [
        RelationTuple.from_string(f"g:top{t}#member@u{t if t % 2 else 'x'}")
        for t in range(GROUPS)
    ]


def sample(metrics: Metrics, name: str) -> float:
    return metrics.registry.get_sample_value(name) or 0.0


def test_a_fan_out_of_three_stays_on_the_device_through_a_retry(monkeypatch):
    metrics = Metrics()
    engine = fan_engine(3, metrics)
    queries = fan_queries()
    results, launches = launches_of(engine, queries, monkeypatch)
    # 32 queries fill a 32 bucket: 64 frontier slots for 96 children, so
    # the queries whose children lie past slot 64 are launched again at
    # 128, where all of theirs fit
    assert launches == [((7, 32), 64), ((7, 32), 128)]
    flagged = sum(1 for t in range(GROUPS) if 3 * t + 3 > 64)
    assert sample(metrics, "keto_tpu_frontier_retry_total") == flagged == 11
    assert engine.stats["host_checks"] == 0
    assert engine.stats["device_checks"] == GROUPS
    got = [r.allowed for r in results]
    assert got == [engine.reference.check_is_member(q) for q in queries]
    assert got == [t % 2 == 1 for t in range(GROUPS)]
    # both launches paid for their frontier: 64 + 128 slots for 2 x 32
    assert sample(metrics, "keto_tpu_launch_frontier_slots_total") == 192
    assert sample(metrics, "keto_tpu_launch_bucket_slots_total") == 64
    assert sample(metrics, "keto_tpu_launch_demand_peak_count") == 2
    # the bucket's next launch goes straight to the widest rung
    results, launches = launches_of(engine, queries, monkeypatch)
    assert launches == [((7, 32), 128)]
    assert [r.allowed for r in results] == got
    assert sample(metrics, "keto_tpu_frontier_retry_total") == 11
    assert engine.stats["host_checks"] == 0


def test_a_fan_out_over_four_slots_a_query_is_replayed_exactly(monkeypatch):
    metrics = Metrics()
    engine = fan_engine(6, metrics)
    queries = fan_queries()
    results, launches = launches_of(engine, queries, monkeypatch)
    # 32 queries fill a 32 bucket: 64 frontier slots for 192 children,
    # then 128 for the 132 of the 22 queries past slot 64
    assert launches == [((7, 32), 64), ((7, 32), 128)]
    assert sample(metrics, "keto_tpu_frontier_retry_total") == 22
    assert engine.stats["host_cause"].get("frontier_overflow", 0) > 0
    assert engine.stats["host_checks"] == sum(
        engine.stats["host_cause"].values()
    )
    got = [r.allowed for r in results]
    assert got == [engine.reference.check_is_member(q) for q in queries]
    assert got == [t % 2 == 1 for t in range(GROUPS)]
    assert metrics.registry.get_sample_value(
        "keto_tpu_launch_padding_waste_sum"
    ) == 0.0


def test_an_invalid_query_slot_is_inert():
    """A retry clears `valid` of the queries it does not launch again and
    leaves their object and relation: such a slot neither probes nor
    expands, computed hops included, so it asks for no frontier."""
    engine = engine_for(NAMESPACES, TUPLES)
    state = engine._ensure_state()
    queries = [
        RelationTuple.from_string(f"files:f{i}#view@u0") for i in range(16)
    ]
    qpack = kernel.pack_queries(
        *engine._encode_queries(state, queries, 16, 5, False, None)
    )
    qpack[6] = 0
    cfg = kernel.kernel_static_config(
        state.snapshot, 5, 64, has_delta=state.has_delta
    )
    out = np.asarray(kernel.check_kernel_packed(state.tables, qpack, **cfg))
    stats = kernel.launch_stats_dict(
        kernel.unpack_results(out, 16, 0, state.snapshot.K)[-1]
    )
    assert stats["steps"] == 1
    assert stats["live_sum"] == stats["edge_rows"] == stats["demand_max"] == 0


def test_the_widest_rung_is_taken_without_a_retry_where_no_narrower_is():
    """A 16 bucket's rungs all clamp to 64, and an engine with
    auto_frontier off launches at its cap: neither ever retries."""
    metrics = Metrics()
    engine = fan_engine(6, metrics)
    assert engine._launch_frontier(16) == (64, 64)
    assert engine._launch_frontier(2048) == (4096, 8192)
    fixed = engine_for(NAMESPACES, TUPLES, auto_frontier=False)
    assert fixed._launch_frontier(2048) == (FRONTIER_CAP, FRONTIER_CAP)
