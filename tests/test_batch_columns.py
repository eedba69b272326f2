"""A BatchCheck as columns: the gRPC handler reads the request into
`ketoapi.CheckColumns`, the engine launches from them, and an item becomes
a RelationTuple only where the host oracle has to answer it.

The two ways into the engine (columns off the wire, a list of tuples) are
held to each other and to `engine/reference.py` on one mixed batch, for a
dict-vocabulary store (the scalar encoder) and an ArrayMap one (the
vectorized encoder, what every benchmark cell runs); the per-tuple view
encoding is the loop the column encoders are compared with."""

import numpy as np
import pytest

from keto_tpu.api.descriptors import pb
from keto_tpu.api.grpc_server import _Services
from keto_tpu.api.messages import tuple_to_proto
from keto_tpu.config import Config
from keto_tpu.engine.snapshot import ArrayMap, encode_query_batch
from keto_tpu.errors import NamespaceNotFoundError
from keto_tpu.ketoapi import CheckColumns, RelationTuple
from keto_tpu.registry import Registry
from keto_tpu.storage.columns import TupleColumns

NAMESPACES = [
    {
        "name": "videos",
        "relations": [
            {"name": "owner"},
            {
                "name": "view",
                "rewrite": {
                    "operation": "or",
                    "children": [{"type": "computed_subject_set", "relation": "owner"}],
                },
            },
        ],
    },
    {"name": "groups", "relations": [{"name": "member"}]},
]
NIL_SUBJECT = "subject is not allowed to be nil"


def ts(*strs):
    return [RelationTuple.from_string(s) for s in strs]


BASE = ts(
    *(f"videos:v{i}#owner@user{i}" for i in range(24)),
    "videos:v1#owner@(groups:g#member)",
    "groups:g#member@user1",
)
# written after the engine's first build: these names live in the overlay
LATE = ts(
    "videos:late#owner@newbie",
    "videos:v2#owner@(groups:late#member)",
    "groups:late#member@newbie",
)
# the mixed batch: (item as the canonical string, or None for a nil
# subject; what the handler must refuse it with, or None if it launches)
MIXED = [
    ("videos:v3#view@user3", None),                   # plain, allowed
    ("videos:v3#view@user4", None),                   # plain, denied
    ("videos:v1#owner@(groups:g#member)", None),      # subject set, allowed
    ("videos:v2#owner@(groups:g#member)", None),      # subject set, denied
    (None, NIL_SUBJECT),
    ("nope:x#r@user1", NamespaceNotFoundError("nope").message),
    ("videos:v1#owner@(nope:g#member)", NamespaceNotFoundError("nope").message),
    ("videos:ghost#view@user1", None),                # unknown object
    ("videos:v1#nosuch@user1", None),                 # unknown relation
    ("videos:late#view@newbie", None),                # overlay node and subject
    ("videos:v5#owner@newbie", None),                 # base node, overlay subject
    ("videos:v2#owner@(groups:late#member)", None),   # overlay subject set
    ("videos:v3#view@user3", None),                   # a duplicate
    ("videos:v4#view@", None),                        # the empty subject id
] + [(f"videos:v{i % 24}#view@user{i % 7}", None) for i in range(50)]
assert len(MIXED) == 64


def wire_request(items, max_depth=0):
    req = pb.BatchCheckRequest(max_depth=max_depth)
    for item in items:
        if item is None:
            req.tuples.add(namespace="videos", object="v1", relation="view")
        else:
            req.tuples.append(tuple_to_proto(RelationTuple.from_string(item)))
    # through the wire form, as a served request arrives
    return pb.BatchCheckRequest.FromString(req.SerializeToString())


class Served:
    """One registry on the CPU engine over `dsn`, its gRPC handlers, and
    the mixed batch answered once through `batch_check`."""

    def __init__(self, dsn: str):
        self.registry = Registry(Config({
            "dsn": dsn, "check": {"engine": "tpu"}, "namespaces": NAMESPACES,
        }))
        manager = self.registry.relation_tuple_manager()
        if dsn == "columnar":
            manager.bulk_load(TupleColumns.from_tuples(BASE))
        else:
            manager.write_relation_tuples(BASE)
        self.services = _Services(self.registry)
        self.engine = self.registry.check_engine()
        assert self.engine.check_batch(BASE[:1])[0].allowed  # the base build
        manager.write_relation_tuples(LATE)
        self.builds = self.engine.stats["snapshot_builds"]
        built, replayed = self.built(), self.engine.stats["host_checks"]
        self.response = self.services.batch_check(
            wire_request([item for item, _ in MIXED]), None
        )
        self.built_by_mixed = self.built() - built
        self.replayed_of_mixed = self.engine.stats["host_checks"] - replayed
        self.causes_of_mixed = dict(self.engine.stats["host_cause"])

    def built(self) -> float:
        return self.registry.metrics().check_batch_tuples_built_total._value.get()

    def close(self):
        self.engine.stop_push_refresh()


@pytest.fixture(scope="module", params=["memory", "columnar"])
def served(request):
    s = Served(request.param)
    yield s
    s.close()


def launched_tuples():
    return ts(*(item for item, refused in MIXED if refused is None))


def test_the_store_runs_the_encoder_it_should(served):
    snap = served.engine._ensure_state().snapshot
    columnar = served.registry.config.dsn == "columnar"
    assert isinstance(snap.obj_slots, ArrayMap) == columnar
    # the late names ride the overlay, no rebuild took them into the base
    assert served.engine.stats["snapshot_builds"] == served.builds
    assert snap.encode_node("videos", "late", "owner") is None


def test_batch_check_equals_check_batch_on_the_tuples(served):
    """The same results, error strings and order as the tuple way in."""
    want = iter(served.engine.check_batch(launched_tuples()))
    got = [(r.allowed, r.error) for r in served.response.results]
    assert len(got) == len(MIXED)
    for (item, refused), (allowed, error) in zip(MIXED, got):
        if refused is not None:
            assert (allowed, error) == (False, refused), item
            continue
        res = next(want)
        if res.error is not None:
            assert (allowed, error) == (False, str(res.error)), item
        else:
            assert (allowed, error) == (res.allowed, ""), item
    assert served.response.snaptoken


def test_batch_check_equals_the_reference(served):
    reference = served.engine.reference
    for (item, refused), got in zip(MIXED, served.response.results):
        if refused is not None:
            continue
        want = reference.check_relation_tuple(RelationTuple.from_string(item), 0)
        if want.error is not None:
            assert (got.allowed, got.error) == (False, str(want.error)), item
        else:
            assert (got.allowed, got.error) == (want.allowed, ""), item
    answers = {item: r.allowed for (item, _), r in zip(MIXED, served.response.results)}
    assert answers["videos:v3#view@user3"] and not answers["videos:v3#view@user4"]
    assert answers["videos:late#view@newbie"]
    assert answers["videos:v2#owner@(groups:late#member)"]
    assert served.response.results[8].error  # the unknown relation


def test_only_the_replayed_items_were_built(served):
    """Of the 61 launched items four need the host oracle: the unknown
    object, the unknown relation, and the two on videos:v2#owner, a row
    that a late subject-set edge made dirty. The rest build nothing, the
    rows patched from the overlay among them."""
    assert served.built_by_mixed == served.replayed_of_mixed == 4
    assert served.causes_of_mixed == {"unindexed": 2, "dirty_row": 2}


def per_tuple_encoding(view, tuples, B):
    """The loop the column encoders replace: one view lookup a tuple."""
    q_obj = np.zeros(B, np.int32)
    q_rel = np.zeros(B, np.int32)
    q_skind = np.zeros(B, np.int32)
    q_sa = np.full(B, -2, np.int32)
    q_sb = np.zeros(B, np.int32)
    q_valid = np.zeros(B, bool)
    for i, t in enumerate(tuples):
        node = view.encode_node(t.namespace, t.object, t.relation)
        if node is None:
            continue
        q_obj[i], q_rel[i] = node
        subject = view.encode_subject(t)
        if subject is not None:
            q_skind[i], q_sa[i], q_sb[i] = subject
        q_valid[i] = True
    return q_obj, q_rel, q_skind, q_sa, q_sb, q_valid


@pytest.mark.parametrize("form", ["tuples", "columns", "wire"])
@pytest.mark.parametrize("encoder", ["vectorized", "engine"])
def test_the_encoders_agree_array_for_array(served, form, encoder):
    tuples = launched_tuples()
    state = served.engine._ensure_state()
    B = 64
    want = per_tuple_encoding(state.view, tuples, B)
    assert want[-1].sum() == len(tuples) - 2  # ghost and nosuch stay invalid
    if form == "tuples":
        items = tuples
    elif form == "columns":
        items = CheckColumns.of(tuples)
    else:
        items, refused = served.services._batch_check_columns(
            wire_request([item for item, _ in MIXED]).tuples
        )
        assert sorted(refused) == [4, 5, 6]
        items = items.take([i for i in range(len(MIXED)) if i not in refused])
    if encoder == "vectorized":
        got = encode_query_batch(state.view, items, B)
    else:  # whichever the engine picks for this store: scalar on a dict
        q = served.engine._encode_queries(state, items, B, 5, False, None)
        assert (q[2] == 5).all()
        got = q[:2] + q[3:]
    valid = want[-1]
    for name, w, g in zip(("obj", "rel", "skind", "sa", "sb", "valid"), want, got):
        # a row without a node never runs, and only the loop leaves its
        # subject columns at the sentinel
        assert w.dtype == g.dtype and (w == g)[valid | (name == "valid")].all(), name


def test_columns_read_as_the_tuples_they_stand_for():
    tuples = launched_tuples()
    cols = CheckColumns.of(tuples)
    assert len(cols) == len(tuples) and list(cols) == tuples
    assert cols[3] == tuples[3] and cols.tuple_at(2) == tuples[2]
    assert list(cols[10:20]) == tuples[10:20]
    assert isinstance(cols[10:20], CheckColumns)
    assert list(cols.take([5, 2, 2])) == [tuples[5], tuples[2], tuples[2]]
    assert cols.subjects() == [t.subject for t in tuples]
    assert CheckColumns.of(cols) is cols
    plain = CheckColumns.of(tuples[:2])
    assert plain.subjects() == ["user3", "user4"]


class TestWhatABatchBuilds:
    """keto_tpu_check_batch_tuples_built_total: a batch that the device
    answers whole builds no RelationTuple, one with three unknown objects
    builds those three."""

    N = 2048

    @pytest.fixture(scope="class")
    def big(self):
        registry = Registry(Config({
            "dsn": "columnar", "check": {"engine": "tpu"},
            "namespaces": NAMESPACES,
        }))
        registry.relation_tuple_manager().bulk_load(TupleColumns.from_tuples(
            ts(*(f"videos:v{i}#owner@user{i % 97}" for i in range(self.N)))
        ))
        yield registry, _Services(registry)
        registry.check_engine().stop_push_refresh()

    def ask(self, big, items, monkeypatch):
        registry, services = big
        calls = []
        tuple_at = CheckColumns.tuple_at
        monkeypatch.setattr(
            CheckColumns, "tuple_at",
            lambda self, i: calls.append(i) or tuple_at(self, i),
        )
        counter = registry.metrics().check_batch_tuples_built_total._value
        before = counter.get()
        resp = services.batch_check(wire_request(items), None)
        assert len(resp.results) == len(items)
        assert not any(r.error for r in resp.results)
        return resp, counter.get() - before, calls

    def test_a_clean_batch_builds_nothing(self, big, monkeypatch):
        items = [f"videos:v{i}#view@user{(i + i % 2) % 97}" for i in range(self.N)]
        resp, built, calls = self.ask(big, items, monkeypatch)
        assert built == 0 and calls == []
        assert [r.allowed for r in resp.results] == [
            i % 97 == (i + i % 2) % 97 for i in range(self.N)
        ]
        assert big[0].check_engine().stats["host_checks"] == 0

    def test_three_unknown_objects_build_three(self, big, monkeypatch):
        items = [f"videos:v{i}#view@user{i % 97}" for i in range(self.N)]
        for i in (7, 700, 2047):
            items[i] = f"videos:ghost{i}#view@user1"
        resp, built, calls = self.ask(big, items, monkeypatch)
        assert built == 3 and calls == [7, 700, 2047]
        assert [i for i, r in enumerate(resp.results) if not r.allowed] == [
            7, 700, 2047
        ]

    def test_the_host_engine_builds_every_item(self):
        registry = Registry(Config({
            "dsn": "memory", "check": {"engine": "host"},
            "namespaces": NAMESPACES,
        }))
        registry.relation_tuple_manager().write_relation_tuples(BASE)
        counter = registry.metrics().check_batch_tuples_built_total._value
        resp = _Services(registry).batch_check(
            wire_request(["videos:v3#view@user3", "videos:v3#view@user4"]), None
        )
        assert [r.allowed for r in resp.results] == [True, False]
        assert counter.get() == 2
