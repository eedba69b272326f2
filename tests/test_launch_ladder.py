"""The launch ladder: a check launch is sized to its batch.

`tpu_engine._BUCKETS` is every power of two from 16 to 16,384, so a batch
of n items runs in the smallest power of two >= n (floor 16) and under half
of a launch's static shape is padding; the frontier gets four slots a query
slot (`min(frontier_cap, max(4 * B, 64))`). Held here, on the CPU, as counts:
the shapes the engine hands `check_kernel_packed`, the verdicts against the
reference engine, and the one thing the finer ladder can cost, a fan-out
that overflows the smaller frontier and is replayed exactly on the host.
"""

from __future__ import annotations

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
    assert launches == [
        ((7, B), min(FRONTIER_CAP, max(4 * B, 64))) for B in widths
    ]
    # the reference engine's verdict, asked once a distinct query
    reference = {
        str(q): engine.reference.check_is_member(q) for q in queries[:2 * FILES]
    }
    got = [r.allowed for r in results]
    assert got == [reference[str(q)] for q in queries]
    assert got == [i % 2 == 0 for i in range(n)]
    # two live tasks a query: nothing leaves the device while the frontier
    # has two slots a query slot; a full 16,384 bucket under the default
    # cap has one, and what overflows is replayed (the cap's rule, as ever)
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


# groups of six nested groups: a `member` check of a group has six children
# a step, between the four frontier slots a query slot that every launch
# gets and the eight that a half-full bucket enjoyed under a x4 ladder
FAN_OUT, GROUPS = 6, 32
FAN_NAMESPACES = [Namespace(name="g", relations=[Relation(name="member")])]
FAN_TUPLES = [
    f"g:top{t}#member@(g:sub{t}_{s}#member)"
    for t in range(GROUPS) for s in range(FAN_OUT)
] + [f"g:sub{t}_{FAN_OUT - 1}#member@u{t}" for t in range(GROUPS)]


def test_a_fan_out_over_four_slots_a_query_is_replayed_exactly(monkeypatch):
    metrics = Metrics()
    engine = engine_for(FAN_NAMESPACES, FAN_TUPLES, metrics=metrics)
    queries = [
        RelationTuple.from_string(f"g:top{t}#member@u{t if t % 2 else 'x'}")
        for t in range(GROUPS)
    ]
    results, launches = launches_of(engine, queries, monkeypatch)
    # 32 queries fill a 32 bucket: 128 frontier slots for 192 children
    assert launches == [((7, 32), 128)]
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
