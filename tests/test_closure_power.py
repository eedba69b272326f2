"""On-device GraphBLAS closure powering (engine/closure_power.py).

The contract under test is BIT-IDENTITY: the device kernel — frontier ×
adjacency as bit-packed boolean matmul, 32 sources per uint32 lane —
must produce byte-for-byte the same ClosureBuild as the numpy host
builder on every topology the host suite pins: deep chains, cycles,
AND/NOT islands, rel-not-found poison, depth caps, row-cap overflow,
arbitrary wave decompositions. Identity (not just answer-equality)
is what lets `closure.powering = "device"` share the host's checkpoint
cache, dirty-refresh merge, and differential oracle unchanged.

Rides the host suite's topologies: see tests/test_closure.py.
"""

import os

import numpy as np
import pytest

from test_closure import (
    DEPTH,
    TestBuilderVsOracle,
    deep_namespaces,
    deep_queries,
    deep_tuples,
    make_engine,
)

from keto_tpu.engine.closure import extract_graph, power_closure
from keto_tpu.engine.closure_power import (
    PoweringUnsupported,
    power_closure_device,
)
from keto_tpu.engine.reference import ReferenceEngine
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.namespace.ast import (
    ComputedSubjectSet,
    InvertResult,
    Operator,
    Relation,
    SubjectSetRewrite,
)

BUILD_FIELDS = (
    "covered_keys", "ent_obj", "ent_rel", "ent_skind",
    "ent_sa", "ent_sb", "ent_req",
)


def _operands(engine):
    state = engine._ensure_state()
    graph = extract_graph(state.snapshot)
    assert graph is not None
    return graph, state.snapshot, state.base_version


def _assert_identical(host_build, device_build):
    for field in BUILD_FIELDS:
        assert np.array_equal(
            getattr(host_build, field), getattr(device_build, field)
        ), field
    assert host_build.n_nodes == device_build.n_nodes
    assert host_build.vocab_fp == device_build.vocab_fp
    assert host_build.n_entries == device_build.n_entries


def _both(engine, max_depth=None, max_set_rows=64, sources=None):
    graph, snap, base_version = _operands(engine)
    depth = engine.config.max_read_depth() if max_depth is None else max_depth
    hb = power_closure(graph, snap, depth, max_set_rows, base_version,
                       sources=sources)
    db, record = power_closure_device(
        graph, snap, depth, max_set_rows, base_version, sources=sources
    )
    _assert_identical(hb, db)
    return hb, db, record


class TestBitIdentity:
    """Every ClosureBuild array the host builder emits, the kernel must
    emit byte-for-byte — including entry ORDER (p_src-major lexsort),
    which the wave decomposition must preserve."""

    def test_deep_chains(self):
        tuples, _ = deep_tuples()
        hb, db, record = _both(make_engine(tuples))
        assert len(db.covered_keys) > 0
        assert record["steps"] > 0 and record["waves"] >= 1

    @pytest.mark.parametrize("depth", [1, 2, 5])
    def test_depth_caps(self, depth):
        # req > max_depth entries must drop identically; the kernel's
        # loop runs one level PAST the subject horizon for poison, the
        # same as the host's
        tuples, _ = deep_tuples()
        _both(make_engine(tuples), max_depth=depth)

    @pytest.mark.parametrize("msr", [1, 3])
    def test_row_cap_overflow(self, msr):
        # sources whose reach or subject set outgrows max_set_rows drop
        # out of coverage on BOTH builders, at the same rows
        tuples, _ = deep_tuples()
        hb, db, _ = _both(make_engine(tuples), max_set_rows=msr)
        graph, _, _ = _operands(make_engine(tuples))
        assert len(db.covered_keys) < len(graph.universe)

    def test_cycles_min_depth(self):
        ns = [Namespace(name="g", relations=[Relation(name="member")])]
        tuples = [
            RelationTuple.from_string("g:x#member@(g:y#member)"),
            RelationTuple.from_string("g:y#member@(g:x#member)"),
            RelationTuple.from_string("g:x#member@alice"),
        ]
        _both(make_engine(tuples, namespaces=ns, max_depth=8))

    def test_island_poison(self):
        ns = [Namespace(name="acl", relations=[
            Relation(name="allow"), Relation(name="deny"),
            Relation(name="access", subject_set_rewrite=SubjectSetRewrite(
                operation=Operator.AND,
                children=[
                    ComputedSubjectSet(relation="allow"),
                    InvertResult(child=ComputedSubjectSet(relation="deny")),
                ])),
            Relation(name="group"),
        ])]
        tuples = [
            RelationTuple.from_string("acl:d#allow@u1"),
            RelationTuple.from_string("acl:g#group@(acl:d#access)"),
            RelationTuple.from_string("acl:h#group@u2"),
        ]
        _both(make_engine(tuples, namespaces=ns, max_depth=6))

    def test_relation_not_found_poison(self):
        ns = [Namespace(name="cfg", relations=[Relation(name="member")])]
        tuples = [
            RelationTuple.from_string("cfg:a#member@(cfg:b#ghost)"),
            RelationTuple.from_string("cfg:b#ghost@u1"),
        ]
        _both(make_engine(tuples, namespaces=ns, max_depth=6))

    def test_subset_sources(self):
        # the dirty-refresh path powers an explicit source subset
        tuples, _ = deep_tuples()
        engine = make_engine(tuples)
        graph, _, _ = _operands(engine)
        sources = graph.universe[:: 3]
        _both(engine, sources=sources)

    def test_forced_multi_wave(self, monkeypatch):
        # a zero scratch budget forces the range bisection all the way
        # down: many tiny waves must still concatenate into the host's
        # global entry order
        monkeypatch.setenv("KETO_CLOSURE_POWER_MB", "0")
        tuples, _ = deep_tuples()
        hb, db, record = _both(make_engine(tuples))
        assert record["waves"] > 1

    def test_unsupported_depth_raises(self):
        tuples, _ = deep_tuples()
        graph, snap, base_version = _operands(make_engine(tuples))
        with pytest.raises(PoweringUnsupported):
            power_closure_device(graph, snap, 101, 64, base_version)


class TestDeviceVsOracle:
    """Device-powered indexes against the EXACT host closure oracle
    (`reference.closure_subjects`) — the same per-node subject-set and
    req-depth decode the host builder suite pins, now decoding entries
    the kernel materialized."""

    _compare_node = TestBuilderVsOracle._compare_node

    def test_deep_chain(self):
        tuples, _ = deep_tuples()
        engine = make_engine(tuples, powering="device")
        assert engine.closure_ensure_built()
        assert engine.closure_index().stats["device_builds"] >= 1
        for f in (0, 3, DEPTH - 1):
            self._compare_node(engine, "deep", f"c0f{f}", "viewer")
        self._compare_node(engine, "deep", f"c1f{DEPTH}", "owner")

    def test_cycles(self):
        ns = [Namespace(name="g", relations=[Relation(name="member")])]
        tuples = [
            RelationTuple.from_string("g:x#member@(g:y#member)"),
            RelationTuple.from_string("g:y#member@(g:x#member)"),
            RelationTuple.from_string("g:x#member@alice"),
        ]
        engine = make_engine(tuples, namespaces=ns, max_depth=8,
                             powering="device")
        assert engine.closure_ensure_built()
        self._compare_node(engine, "g", "x", "member")
        self._compare_node(engine, "g", "y", "member")

    def test_island_poison(self):
        ns = [Namespace(name="acl", relations=[
            Relation(name="allow"), Relation(name="deny"),
            Relation(name="access", subject_set_rewrite=SubjectSetRewrite(
                operation=Operator.AND,
                children=[
                    ComputedSubjectSet(relation="allow"),
                    InvertResult(child=ComputedSubjectSet(relation="deny")),
                ])),
            Relation(name="group"),
        ])]
        tuples = [
            RelationTuple.from_string("acl:d#allow@u1"),
            RelationTuple.from_string("acl:g#group@(acl:d#access)"),
            RelationTuple.from_string("acl:h#group@u2"),
        ]
        engine = make_engine(tuples, namespaces=ns, max_depth=6,
                             powering="device")
        assert engine.closure_ensure_built()
        self._compare_node(engine, "acl", "d", "access")
        self._compare_node(engine, "acl", "g", "group")
        self._compare_node(engine, "acl", "h", "group")

    def test_relation_not_found_poison(self):
        ns = [Namespace(name="cfg", relations=[Relation(name="member")])]
        tuples = [
            RelationTuple.from_string("cfg:a#member@(cfg:b#ghost)"),
            RelationTuple.from_string("cfg:b#ghost@u1"),
        ]
        engine = make_engine(tuples, namespaces=ns, max_depth=6,
                             powering="device")
        assert engine.closure_ensure_built()
        self._compare_node(engine, "cfg", "a", "member")
        self._compare_node(engine, "cfg", "b", "ghost")


class TestEngineDevicePowering:
    """closure.powering = "device" end to end: the engine's builds and
    dirty refreshes route through the kernel, answers stay differential
    against the host oracle, and the routing is OBSERVABLE."""

    def test_build_routes_through_kernel(self):
        tuples, owners = deep_tuples()
        engine = make_engine(tuples, powering="device")
        assert engine.closure_ensure_built()
        idx = engine.closure_index()
        assert idx.powering == "device"
        assert idx.stats["device_builds"] >= 1
        assert idx.stats["device_fallbacks"] == 0
        assert idx.stats["power_steps"] > 0
        oracle = ReferenceEngine(engine.manager, engine.config)
        queries = deep_queries(owners)
        for q, res in zip(queries, engine.check_batch(queries)):
            assert res.membership == oracle.check_relation_tuple(q).membership
        assert engine.stats.get("closure_hits", 0) > 0

    def test_device_equals_host_engine_builds(self):
        tuples, _ = deep_tuples()
        host_eng = make_engine(tuples, powering="host")
        dev_eng = make_engine(tuples, powering="device")
        assert host_eng.closure_ensure_built()
        assert dev_eng.closure_ensure_built()
        with host_eng.closure_index()._mu:
            hb = host_eng.closure_index()._build
        with dev_eng.closure_index()._mu:
            db = dev_eng.closure_index()._build
        _assert_identical(hb, db)

    def test_mesh_parity(self):
        from keto_tpu.parallel import default_mesh

        tuples, owners = deep_tuples()
        queries = deep_queries(owners)
        engine = make_engine(tuples, mesh=default_mesh(8),
                             powering="device")
        assert engine.closure_ensure_built()
        assert engine.closure_index().stats["device_builds"] >= 1
        off = make_engine(tuples, closure=False, mesh=default_mesh(8))
        for q, a, b in zip(queries, engine.check_batch(queries),
                           off.check_batch(queries)):
            assert a.membership == b.membership, str(q)
        assert engine.stats.get("closure_hits", 0) > 0

    def test_interleaved_writes_refresh_through_kernel(self):
        import random

        tuples, owners = deep_tuples()
        engine = make_engine(tuples, powering="device")
        oracle = ReferenceEngine(engine.manager, engine.config)
        assert engine.closure_ensure_built()
        idx = engine.closure_index()
        builds0 = idx.stats["device_builds"]
        rng = random.Random(5)
        wrong = 0
        for r in range(12):
            c = rng.randrange(len(owners))
            engine.manager.write_relation_tuples([RelationTuple.from_string(
                f"deep:c{c}f{rng.randrange(DEPTH + 1)}#owner@w{r}"
            )])
            if r % 3 == 2:
                engine.closure_ensure_built()
            qs = deep_queries(owners, n=8, seed=r) + [
                RelationTuple.from_string(f"deep:c{c}f0#viewer@w{r}")
            ]
            for q, res in zip(qs, engine.check_batch(qs)):
                if res.membership != oracle.check_relation_tuple(q).membership:
                    wrong += 1
        assert wrong == 0
        # the dirty refreshes re-powered through the kernel, not host
        assert idx.stats["device_builds"] > builds0
        assert idx.stats["device_fallbacks"] == 0

    def test_default_powering_is_host(self):
        tuples, _ = deep_tuples()
        engine = make_engine(tuples)
        assert engine.closure_ensure_built()
        idx = engine.closure_index()
        assert idx.powering == "host"
        assert idx.stats["device_builds"] == 0

    def test_device_failure_falls_back_to_host(self, monkeypatch):
        # any kernel failure costs the speedup, never correctness: the
        # powering lands via the host builder and the fallback is
        # counted where dashboards can see it
        import keto_tpu.engine.closure_power as cp

        def boom(*a, **k):
            raise RuntimeError("injected device loss")

        monkeypatch.setattr(cp, "power_closure_device", boom)
        tuples, owners = deep_tuples()
        engine = make_engine(tuples, powering="device")
        assert engine.closure_ensure_built()
        idx = engine.closure_index()
        assert idx.stats["device_builds"] == 0
        assert idx.stats["device_fallbacks"] >= 1
        oracle = ReferenceEngine(engine.manager, engine.config)
        queries = deep_queries(owners)
        for q, res in zip(queries, engine.check_batch(queries)):
            assert res.membership == oracle.check_relation_tuple(q).membership


class TestObservability:
    """The kernel's footprint and launches surface where every other
    kernel's do: hbm_snapshot, the flight recorder, and metrics."""

    def test_hbm_snapshot_carries_power_family(self):
        tuples, _ = deep_tuples()
        engine = make_engine(tuples, powering="device")
        assert engine.closure_ensure_built()
        snap = engine.hbm_snapshot()
        fam = snap["buffers"]["closure_power"]
        assert fam and all(v > 0 for v in fam.values())
        assert set(fam) == {"adjacency_pack", "bit_matrix", "scratch"}
        assert snap["totals"]["closure_power"] == sum(fam.values())

    def _engine(self, **kwargs):
        from keto_tpu.config import Config
        from keto_tpu.engine.tpu_engine import TPUCheckEngine
        from keto_tpu.storage import MemoryManager

        tuples, _ = deep_tuples()
        cfg = Config({
            "limit": {"max_read_depth": DEPTH + 4},
            "closure": {"enabled": True, "powering": "device"},
        })
        cfg.set_namespaces(deep_namespaces())
        m = MemoryManager()
        m.write_relation_tuples(tuples)
        return TPUCheckEngine(m, cfg, frontier_cap=4096, **kwargs)

    def test_flightrec_power_launch_entries(self):
        from keto_tpu.observability import FlightRecorder

        fr = FlightRecorder(capacity=32)
        engine = self._engine(flightrec=fr)
        assert engine.closure_ensure_built()
        entries = [e for e in fr.entries() if e["kind"] == "closure_power"]
        assert entries, [e["kind"] for e in fr.entries()]
        for e in entries:
            assert e["steps"] > 0
            assert e["adjacency_bytes"] > 0 and e["scratch_bytes"] > 0
            assert 0 < e["occupancy"] <= 1
            assert "launch_id" in e

    def test_power_metrics_counted(self):
        from keto_tpu.observability import Metrics

        metrics = Metrics()
        engine = self._engine(metrics=metrics)
        assert engine.closure_ensure_built()
        text = metrics.export().decode()
        assert "keto_tpu_closure_power_builds_total 1.0" in text
        assert "keto_tpu_closure_power_steps_total" in text
        assert "keto_tpu_closure_power_bytes" in text


class TestSyncBudget:
    """The kernel's whole device->host budget is ONE packed readback
    (level plane + per-source summary + stats vector) at resolve; the
    ketolint host-sync pass enforces annotation and this pins the COUNT
    so a second sync can't slip in as 'just one more'."""

    def test_sync_annotation_count_pinned(self):
        src = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "keto_tpu", "engine", "closure_power.py",
        )
        with open(src) as fh:
            text = fh.read()
        assert text.count("allow[host-sync]") == 1

    def test_ketolint_green(self):
        from keto_tpu.analysis.lint import lint_paths
        from keto_tpu.analysis.source_scan import (
            iter_py_files,
            package_root,
            repo_root,
        )

        findings = lint_paths(iter_py_files(package_root()), None, repo_root())
        assert [f for f in findings if f.rule == "host-sync"] == []


class TestCheckpointLayoutVersioning:
    """Checkpoints record the table layout they were packed under: a
    snapshot a CPU process once built one slot to a bucket must NOT
    warm-start an engine probing bucket rows (the packed hash tables are
    physically different)."""

    def _small_snapshot(self):
        tuples, _ = deep_tuples(n_chains=2)
        engine = make_engine(tuples, closure=False)
        return engine._ensure_state().snapshot

    def test_layout_mismatch_rejected(self, tmp_path):
        from test_checkpoint import forge_retired_layout

        from keto_tpu.engine.checkpoint import (
            checkpoint_info,
            load_snapshot,
            save_snapshot,
        )

        snap = self._small_snapshot()
        path = str(tmp_path / "ckpt")
        save_snapshot(snap, path)

        info = checkpoint_info(path)
        assert info["table_layout"] == "bucketized"
        assert info["loadable"]
        assert load_snapshot(path) is not None

        forge_retired_layout(path)
        info = checkpoint_info(path)
        assert info["table_layout"] == "compact"
        assert not info["loadable"]
        assert load_snapshot(path) is None
