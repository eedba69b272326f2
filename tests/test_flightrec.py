"""Engine flight recorder: kernel launch counters, ring semantics,
launch-id correlation, HBM accounting, bench summary schema, and the
zero-additional-device-syncs guard.

The differential counter tests pin the kernel's on-device introspection
(STAT_*: iterations used, frontier sums, live task-steps, probe hits,
gathered candidate rows, dedupe survivors) against an independent HOST
step-walk oracle that mirrors the batched BFS bookkeeping for monotone
configs — on the three canonical graph shapes: flat (resolves in one
step), deep-20 (iterations track the chain), and a cycle (terminates
inside the step budget with no host replay).
"""

from __future__ import annotations

import ast
import logging
import re
from pathlib import Path

import pytest

from keto_tpu.config import Config
from keto_tpu.engine.tpu_engine import TPUCheckEngine
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.namespace.ast import (
    ComputedSubjectSet,
    Relation,
    SubjectSetRewrite,
    TupleToSubjectSet,
)
from keto_tpu.observability import (
    FlightRecorder,
    Metrics,
    RequestTrace,
    finish_request_telemetry,
    next_launch_id,
    summarize_launches,
)
from keto_tpu.storage import MemoryManager

WILDCARD = "..."


def make_engine(namespaces, tuples, max_depth=5, frontier_cap=64,
                flightrec=None, metrics=None):
    cfg = Config({"limit": {"max_read_depth": max_depth}})
    cfg.set_namespaces(namespaces)
    m = MemoryManager()
    m.write_relation_tuples([RelationTuple.from_string(s) for s in tuples])
    return TPUCheckEngine(
        m, cfg, frontier_cap=frontier_cap, auto_frontier=False,
        flightrec=flightrec, metrics=metrics,
    )


# -- host step-walk oracle ----------------------------------------------------


def kernel_walk_oracle(namespaces, tuples, query: str, max_depth: int,
                       bucket: int, step_cap: int) -> dict:
    """Independent reimplementation of the batched BFS's per-step
    bookkeeping for monotone (union-only) configs: one task is
    (object, relation, remaining depth); per step every live task
    direct-probes (depth >= 1), then expands its subject-set CSR row
    (children at depth-1, wildcard-relation edges skipped), COMPUTED
    instructions (same depth), and TTU instructions (row of the TTU
    relation, children carry the computed relation at depth-1); the
    candidate set dedupes on (object, relation) keeping the deepest.
    Counter semantics mirror engine/kernel.py STAT_*: frontier_sum
    counts the padded bucket at step 1 (the seed frontier is B tasks),
    live_sum counts only genuinely-live tasks, edge_rows counts valid
    pre-dedupe candidates, dedupe_kept the admitted survivors,
    demand_max the largest candidate count of a step before the
    wildcard-relation edges are skipped."""
    q = RelationTuple.from_string(query)
    direct: set[tuple] = set()
    rows: dict[tuple, list[tuple]] = {}
    for s in tuples:
        t = RelationTuple.from_string(s)
        key = (t.namespace, t.object, t.relation)
        if t.subject_set is not None:
            ss = t.subject_set
            rows.setdefault(key, []).append(
                (ss.namespace, ss.object, ss.relation)
            )
            direct.add(key + (("set", ss.namespace, ss.object, ss.relation),))
        else:
            direct.add(key + (("id", t.subject_id),))
    rewrites: dict[tuple, list] = {}
    for ns in namespaces:
        for rel in ns.relations or ():
            srw = rel.subject_set_rewrite
            if srw is None:
                continue
            for child in srw.children:
                if isinstance(child, ComputedSubjectSet):
                    rewrites.setdefault((ns.name, rel.name), []).append(
                        ("computed", child.relation)
                    )
                elif isinstance(child, TupleToSubjectSet):
                    rewrites.setdefault((ns.name, rel.name), []).append(
                        ("ttu", child.relation,
                         child.computed_subject_set_relation)
                    )
    if q.subject_set is not None:
        subject = ("set", q.subject_set.namespace, q.subject_set.object,
                   q.subject_set.relation)
    else:
        subject = ("id", q.subject_id)

    frontier = [(q.namespace, q.object, q.relation, max_depth)]
    counters = dict(steps=0, frontier_sum=0, frontier_max=0, live_sum=0,
                    probe_hits=0, edge_rows=0, dedupe_kept=0, demand_max=0)
    n_tasks = bucket  # the seed frontier is the padded bucket
    resolved = False
    while counters["steps"] < step_cap and n_tasks > 0 and not resolved:
        counters["steps"] += 1
        counters["frontier_sum"] += n_tasks
        counters["frontier_max"] = max(counters["frontier_max"], n_tasks)
        hits = sum(
            1 for (ns, obj, rel, depth) in frontier
            if depth >= 1 and (ns, obj, rel, subject) in direct
        )
        counters["probe_hits"] += hits
        if hits:
            resolved = True
        live = 0 if resolved else len(frontier)
        counters["live_sum"] += live
        children: list[tuple] = []
        skipped = 0
        if not resolved:
            for (ns, obj, rel, depth) in frontier:
                if depth >= 1:
                    for (cns, cobj, crel) in rows.get((ns, obj, rel), ()):
                        if crel != WILDCARD:
                            children.append((cns, cobj, crel, depth - 1))
                        else:
                            skipped += 1
                for instr in rewrites.get((ns, rel), ()):
                    if instr[0] == "computed":
                        children.append((ns, obj, instr[1], depth))
                    elif depth >= 1:  # ttu
                        for (cns, cobj, _r) in rows.get(
                            (ns, obj, instr[1]), ()
                        ):
                            children.append((cns, cobj, instr[2], depth - 1))
        counters["edge_rows"] += len(children)
        counters["demand_max"] = max(
            counters["demand_max"], len(children) + skipped
        )
        best: dict[tuple, int] = {}
        for (cns, cobj, crel, cdepth) in children:
            key = (cns, cobj, crel)
            best[key] = max(best.get(key, -1), cdepth)
        frontier = [(k[0], k[1], k[2], d) for k, d in best.items()]
        n_tasks = len(frontier)
        counters["dedupe_kept"] += n_tasks
    counters["member"] = resolved
    return counters


def launch_counters(engine, flightrec, query: str) -> dict:
    before = len(flightrec.entries())
    res = engine.check_batch([RelationTuple.from_string(query)])
    entries = flightrec.entries()
    assert len(entries) == before + 1
    entry = entries[-1]
    assert entry["kind"] == "check"
    entry["member"] = res[0].allowed
    return entry


FLAT_NS = [Namespace(name="doc", relations=[Relation(name="owner")])]
FLAT_TUPLES = [f"doc:d{i}#owner@u{i}" for i in range(20)]

DEEP = 20
DEEP_NS = [Namespace(name="deep", relations=[
    Relation(name="owner"),
    Relation(name="parent"),
    Relation(name="viewer", subject_set_rewrite=SubjectSetRewrite(children=[
        ComputedSubjectSet(relation="owner"),
        TupleToSubjectSet(relation="parent",
                          computed_subject_set_relation="viewer"),
    ])),
])]
DEEP_TUPLES = [
    f"deep:f{i}#parent@(deep:f{i + 1}#{WILDCARD})" for i in range(DEEP)
] + [f"deep:f{DEEP}#owner@alice"]

CYCLE_NS = [Namespace(name="g", relations=[Relation(name="member")])]
CYCLE_TUPLES = [
    "g:x#member@(g:y#member)",
    "g:y#member@(g:x#member)",
    "g:x#member@alice",
]


class TestCounterDifferential:
    """Device counters == the host step-walk oracle on known graphs."""

    def _compare(self, namespaces, tuples, query, max_depth):
        fr = FlightRecorder(capacity=16)
        engine = make_engine(
            namespaces, tuples, max_depth=max_depth, flightrec=fr,
            frontier_cap=128,
        )
        entry = launch_counters(engine, fr, query)
        assert engine.stats["host_checks"] == 0, "fixture must stay on device"
        want = kernel_walk_oracle(
            namespaces, tuples, query, max_depth,
            bucket=entry["bucket"], step_cap=entry["step_cap"],
        )
        assert entry["member"] == want["member"]
        for key in ("steps", "frontier_sum", "frontier_max", "live_sum",
                    "probe_hits", "edge_rows", "dedupe_kept", "demand_max"):
            assert entry[key] == want[key], (
                f"{key}: device={entry[key]} oracle={want[key]} "
                f"(entry={entry}, oracle={want})"
            )
        return entry, want

    def test_flat_hit_resolves_in_one_step(self):
        entry, _ = self._compare(FLAT_NS, FLAT_TUPLES, "doc:d3#owner@u3", 5)
        assert entry["steps"] == 1
        assert entry["probe_hits"] == 1

    def test_flat_miss_terminates_without_exploration(self):
        entry, _ = self._compare(FLAT_NS, FLAT_TUPLES, "doc:d3#owner@nobody", 5)
        assert entry["steps"] == 1
        assert entry["probe_hits"] == 0
        assert entry["edge_rows"] == 0

    def test_deep20_iterations_track_the_chain(self):
        entry, _ = self._compare(
            DEEP_NS, DEEP_TUPLES, "deep:f0#viewer@alice", DEEP + 4
        )
        assert entry["member"] is True
        # one TTU descent per step: the walk reaches f20's owner row
        # after DEEP + 1 steps — this is the flat-vs-deep contrast the
        # acceptance bar calls non-degenerate
        assert entry["steps"] >= DEEP
        assert entry["edge_rows"] >= DEEP

    def test_deep20_miss_explores_whole_chain(self):
        entry, _ = self._compare(
            DEEP_NS, DEEP_TUPLES, "deep:f0#viewer@mallory", DEEP + 4
        )
        assert entry["member"] is False
        assert entry["steps"] >= DEEP

    def test_cycle_terminates_inside_step_budget(self):
        entry, _ = self._compare(
            CYCLE_NS, CYCLE_TUPLES, "g:y#member@mallory", 8
        )
        assert entry["member"] is False
        assert entry["steps"] <= entry["step_cap"]
        # the cycle walks x<->y until depth drains: more than one step,
        # but the frontier never grows past one live task per step
        assert entry["steps"] > 1
        assert entry["frontier_max"] == entry["bucket"]

    def test_cycle_hit_through_the_loop(self):
        entry, _ = self._compare(CYCLE_NS, CYCLE_TUPLES, "g:y#member@alice", 8)
        assert entry["member"] is True
        assert entry["steps"] == 2  # y -> x, then x's direct probe hits

    def test_demand_is_the_candidate_total_before_the_frontier_cut(self):
        """STAT_DEMAND_MAX against a hand count: 8 queries of a group
        with 6 nested groups ask for 48 children in their first step, and
        a frontier of 16 holds 16 of them. The cut flags 6 of the 8 (the
        first two have their 12 within the 16) for host replay."""
        namespaces = [Namespace(name="g", relations=[Relation(name="member")])]
        tuples = [
            f"g:top{t}#member@(g:sub{t}_{s}#member)"
            for t in range(8) for s in range(6)
        ]
        fr = FlightRecorder(capacity=4)
        engine = make_engine(
            namespaces, tuples, flightrec=fr, frontier_cap=16
        )
        engine.check_batch([
            RelationTuple.from_string(f"g:top{t}#member@u") for t in range(8)
        ])
        (entry,) = fr.entries()
        assert entry["frontier_cap"] == 16
        assert entry["demand_max"] == 8 * 6
        assert entry["edge_rows"] == 16
        assert entry["host_causes"] == {"frontier_overflow": 6}

    def test_gather_bytes_scale_with_iterations(self):
        fr = FlightRecorder(capacity=16)
        engine = make_engine(
            DEEP_NS, DEEP_TUPLES, max_depth=DEEP + 4, flightrec=fr,
            frontier_cap=128,
        )
        flat_fr = FlightRecorder(capacity=16)
        flat_engine = make_engine(
            FLAT_NS, FLAT_TUPLES, max_depth=5, flightrec=flat_fr,
            frontier_cap=128,
        )
        deep_e = launch_counters(engine, fr, "deep:f0#viewer@alice")
        flat_e = launch_counters(flat_fr and flat_engine, flat_fr,
                                 "doc:d1#owner@u1")
        assert deep_e["gather_bytes_est"] > flat_e["gather_bytes_est"]


class TestRingSemantics:
    def test_ring_bounded_and_ordered(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.record({"kind": "check", "launch_id": next_launch_id(),
                       "i": i})
        entries = fr.entries()
        assert len(entries) == 4
        assert [e["i"] for e in entries] == [6, 7, 8, 9]
        ids = [e["launch_id"] for e in entries]
        assert ids == sorted(ids)

    def test_disabled_records_nothing_but_ids_advance(self):
        fr = FlightRecorder(enabled=False)
        a = next_launch_id()
        fr.record({"kind": "check"})
        b = next_launch_id()
        assert fr.entries() == []
        assert b > a

    def test_engine_skips_recording_when_disabled(self):
        fr = FlightRecorder(enabled=False)
        engine = make_engine(FLAT_NS, FLAT_TUPLES, flightrec=fr)
        engine.check_batch([RelationTuple.from_string("doc:d1#owner@u1")])
        assert fr.entries() == []

    def test_list_launch_ids_advance_while_disabled(self):
        # the expand/list legs allocate their launch id BEFORE the
        # kernel dispatch, unconditionally — ids must advance while
        # recording is off (same contract as check launches) so logs
        # from an enable/disable boundary stay correlatable
        fr = FlightRecorder(enabled=False)
        engine = make_engine(FLAT_NS, FLAT_TUPLES, flightrec=fr)
        a = next_launch_id()
        engine.list_objects_batch([("doc", "owner", "u1")])
        b = next_launch_id()
        assert fr.entries() == []
        assert b > a + 1  # the leg consumed at least one id in between

    def test_dump_counts_and_returns_entries(self):
        m = Metrics()
        fr = FlightRecorder(capacity=8, metrics=m)
        fr.record({"kind": "check", "launch_id": 1})
        entries = fr.dump("device")
        assert len(entries) == 1
        text = m.export().decode()
        assert 'keto_tpu_flightrec_dumps_total{reason="device"} 1.0' in text

    def test_dump_disabled_is_silent_noop(self, caplog):
        # a disabled recorder has an empty ring by construction: an
        # empty-tail WARNING + dump count per batch failure would be
        # pure noise (batch-failed counters already count the failures)
        m = Metrics()
        fr = FlightRecorder(enabled=False, capacity=8, metrics=m)
        with caplog.at_level("WARNING", logger="keto_tpu"):
            assert fr.dump("device") == []
        assert "flight recorder dump" not in caplog.text
        # no counted dump: HELP/TYPE lines remain, sample lines don't
        assert "keto_tpu_flightrec_dumps_total{" not in m.export().decode()

    def test_context_provider_stamps_entries(self):
        fr = FlightRecorder(capacity=8)
        fr.context_providers.append(lambda: {"breaker": "open"})
        fr.record({"kind": "check"})
        assert fr.entries()[0]["breaker"] == "open"


class TestFailurePaths:
    def test_device_failure_dumps_and_error_carries_launch_id(self):
        from keto_tpu import faults
        from keto_tpu.api.batcher import classify_engine_error

        fr = FlightRecorder(capacity=8)
        engine = make_engine(FLAT_NS, FLAT_TUPLES, flightrec=fr)
        engine.check_batch([RelationTuple.from_string("doc:d1#owner@u1")])
        faults.set_fault("device_launch", error="device died")
        try:
            with pytest.raises(Exception) as ei:
                engine.check_batch_submit(
                    [RelationTuple.from_string("doc:d1#owner@u1")]
                )
        finally:
            faults.clear()
        lid = getattr(ei.value, "launch_id", None)
        assert isinstance(lid, int)
        err = classify_engine_error(ei.value, None, "engine")
        assert f"launch={lid}" in str(err)
        assert err.launch_id == lid

    def test_submit_preserves_already_stamped_launch_id(self, monkeypatch):
        # split ('multi') batches recurse into check_batch_submit per
        # slice; a failing slice stamps ITS launch id (the one with a
        # ring entry) and the parent wrapper must not clobber it with
        # the parent id, which is never recorded
        engine = make_engine(FLAT_NS, FLAT_TUPLES)

        def slice_failed(*a, **k):
            e = RuntimeError("slice died")
            e.launch_id = 12345
            raise e

        monkeypatch.setattr(
            engine, "_check_batch_submit_inner", slice_failed
        )
        with pytest.raises(RuntimeError) as ei:
            engine.check_batch_submit(
                [RelationTuple.from_string("doc:d1#owner@u1")]
            )
        assert ei.value.launch_id == 12345

    def test_batcher_dumps_on_device_failure(self):
        from keto_tpu import faults
        from keto_tpu.api.batcher import CheckBatcher

        m = Metrics()
        fr = FlightRecorder(capacity=8, metrics=m)
        engine = make_engine(FLAT_NS, FLAT_TUPLES, flightrec=fr)
        engine.check_batch([RelationTuple.from_string("doc:d1#owner@u1")])
        b = CheckBatcher(engine, window_s=0.001, flightrec=fr, metrics=m)
        faults.set_fault("device_launch", error="device died")
        try:
            # graceful degradation: the rider still answers correctly
            res = b.check(RelationTuple.from_string("doc:d1#owner@u1"))
            assert res.allowed is True
        finally:
            faults.clear()
            b.close()
        text = m.export().decode()
        assert 'keto_tpu_flightrec_dumps_total{reason="device"} 1.0' in text


class TestLaunchIdCorrelation:
    def test_riders_collect_launch_ids(self):
        fr = FlightRecorder(capacity=8)
        engine = make_engine(FLAT_NS, FLAT_TUPLES, flightrec=fr)
        rt = RequestTrace()
        handle = engine.check_batch_submit(
            [RelationTuple.from_string("doc:d1#owner@u1")], telemetry=[rt]
        )
        engine.check_batch_resolve(handle)
        assert len(rt.launch_ids) == 1
        assert rt.launch_ids[0] == fr.entries()[-1]["launch_id"]
        assert rt.ctx.trace_id in fr.entries()[-1]["trace_ids"]

    def test_slow_query_log_includes_launch_ids(self, caplog):
        rt = RequestTrace()
        rt.add_stage("device_wait", 0.2)
        rt.launch_ids.append(777)
        with caplog.at_level(logging.WARNING, logger="keto_tpu"):
            finish_request_telemetry(
                None, 0, "http", "GET /check", rt, "OK", 0.25
            )
        slow = [r for r in caplog.records if "slow request" in r.getMessage()]
        assert slow and "launch_ids=[777]" in slow[0].getMessage()

    def test_request_log_includes_launch_ids(self, caplog):
        rt = RequestTrace()
        rt.add_stage("device_wait", 0.01)
        rt.launch_ids.append(42)
        with caplog.at_level(logging.INFO, logger="keto_tpu"):
            finish_request_telemetry(
                None, None, "http", "GET /check", rt, "OK", 0.02
            )
        reqs = [r for r in caplog.records
                if r.getMessage() == "request handled"]
        assert reqs and getattr(reqs[0], "launch_ids") == [42]


class TestHbmSnapshot:
    def test_structure_and_staleness(self):
        engine = make_engine(FLAT_NS, FLAT_TUPLES)
        assert engine.hbm_snapshot() == {"built": False}
        engine.check_batch([RelationTuple.from_string("doc:d1#owner@u1")])
        snap = engine.hbm_snapshot()
        assert snap["built"] is True
        assert snap["total_bytes"] > 0
        assert snap["totals"]["check"] > 0
        assert snap["buffers"]["check"]["dh_pack"] > 0
        assert snap["staleness_versions"] == 0
        # a write the mirror has not folded yet shows as staleness
        engine.manager.write_relation_tuples(
            [RelationTuple.from_string("doc:new#owner@u0")]
        )
        assert engine.hbm_snapshot()["staleness_versions"] == 1

    def test_labeled_gauges_refresh(self):
        m = Metrics()
        engine = make_engine(FLAT_NS, FLAT_TUPLES, metrics=m)
        engine.check_batch([RelationTuple.from_string("doc:d1#owner@u1")])
        engine.hbm_snapshot()
        text = m.export().decode()
        assert re.search(
            r'keto_tpu_hbm_table_bytes\{buffer="check"\} [1-9]', text
        )


class TestBenchSummaryGolden:
    """bench.py's launch_telemetry schema: pinned key set so the BENCH
    json contract can't drift silently."""

    GOLDEN_KEYS = {
        "launches", "iterations_mean", "iterations_p95", "step_cap",
        "frontier_peak_max", "live_task_steps_mean",
        "gather_bytes_per_check", "edge_rows_per_check",
        "padding_waste_mean",
    }

    def test_schema(self):
        entries = [
            {"kind": "check", "steps": 2, "step_cap": 11, "n": 8,
             "bucket": 16, "occupancy": 0.5, "frontier_max": 16,
             "frontier_sum": 20, "live_sum": 9, "gather_bytes_est": 1000,
             "edge_rows": 4, "dedupe_kept": 4},
            {"kind": "check", "steps": 4, "step_cap": 11, "n": 16,
             "bucket": 16, "occupancy": 1.0, "frontier_max": 30,
             "frontier_sum": 60, "live_sum": 33, "gather_bytes_est": 3000,
             "edge_rows": 12, "dedupe_kept": 10},
            {"kind": "expand", "steps": 9},  # non-check entries excluded
        ]
        s = summarize_launches(entries)
        assert set(s) == self.GOLDEN_KEYS
        assert s["launches"] == 2
        assert s["iterations_mean"] == 3.0
        assert s["iterations_p95"] == 4
        assert s["frontier_peak_max"] == 30
        assert s["gather_bytes_per_check"] == round(4000 / 24, 1)
        assert s["padding_waste_mean"] == 0.25

    def test_empty_window(self):
        assert summarize_launches([]) == {}
        assert summarize_launches([{"kind": "expand"}]) == {}


class TestNoAdditionalDeviceSyncs:
    """The counters ride the EXISTING resolve readback: the batched
    check hot path must carry exactly the annotated sync points it had
    before this feature (ketolint's host-sync pass enforces annotation;
    this pins the COUNT so an extra annotated sync can't slip in as
    'just one more')."""

    # (function, expected allow[host-sync] count): the submit phase has
    # ZERO syncs; resolve carries ONE packed readback, on one device and
    # on a mesh alike (the stats vector rides it), plus the
    # batch_corrupt fault point's — that is the whole device->host budget
    EXPECTED = {
        "_check_batch_submit_inner": 0,
        "check_batch_submit": 0,
        "check_batch_resolve_v": 0,
        "_check_batch_resolve_v_inner": 2,
        # the frontier retry: ONE packed readback of the second launch
        # of a batch whose queries overflowed a narrowed frontier
        "_frontier_retry": 1,
        # the closure fast path (engine/closure_kernel.py) keeps the
        # same budget shape: zero syncs at submit, ONE packed readback
        # at resolve carrying verdicts + causes + the stats vector
        "_closure_batch_resolve_v": 1,
    }

    def test_sync_annotation_count_pinned(self):
        src = Path("keto_tpu/engine/tpu_engine.py").read_text()
        tree = ast.parse(src)
        lines = src.splitlines()
        counts = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef) and node.name in self.EXPECTED:
                body = "\n".join(
                    lines[node.lineno - 1 : node.end_lineno]
                )
                counts[node.name] = body.count("allow[host-sync]")
        assert counts == self.EXPECTED

    def test_ketolint_host_sync_pass_green(self):
        from keto_tpu.analysis.lint import lint_paths
        from keto_tpu.analysis.source_scan import (
            iter_py_files,
            package_root,
            repo_root,
        )

        findings = lint_paths(
            iter_py_files(package_root()), None, repo_root()
        )
        assert [f for f in findings if f.rule == "host-sync"] == []


class TestConfigKeys:
    def test_flightrec_keys_validate_and_apply(self):
        from keto_tpu.registry import Registry

        cfg = Config({
            "dsn": "memory",
            "observability": {"flightrec": {"enabled": True, "capacity": 7}},
        })
        reg = Registry(cfg)
        fr = reg.flight_recorder()
        assert fr.enabled is True
        assert fr.capacity == 7

    def test_flightrec_disabled(self):
        from keto_tpu.registry import Registry

        cfg = Config({
            "dsn": "memory",
            "observability": {"flightrec": {"enabled": False}},
        })
        fr = Registry(cfg).flight_recorder()
        assert fr.enabled is False

    def test_bad_capacity_rejected(self):
        with pytest.raises(Exception):
            Config({
                "dsn": "memory",
                "observability": {"flightrec": {"capacity": 0}},
            })
