"""Leopard closure subsystem differential suite (engine/closure.py +
engine/closure_kernel.py + keto_tpu/closure).

The contract under test: a closure-enabled engine answers EXACTLY like a
closure-disabled one (which the rest of the suite already pins against
the reference), at any depth, on any store, under interleaved writes
forcing the index to lag — a lagging/dirty/uncovered index falls back
(observable in the cause counters), it never answers stale."""

import random

import numpy as np
import pytest

from keto_tpu.config import Config
from keto_tpu.engine.definitions import Membership
from keto_tpu.engine.reference import ReferenceEngine
from keto_tpu.engine.tpu_engine import TPUCheckEngine
from keto_tpu.ketoapi import CheckColumns, RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.namespace.ast import (
    ComputedSubjectSet,
    InvertResult,
    Operator,
    Relation,
    SubjectSetRewrite,
    TupleToSubjectSet,
)
from keto_tpu.registry import Registry
from keto_tpu.storage import MemoryManager

DEPTH = 9


def deep_namespaces():
    return [Namespace(name="deep", relations=[
        Relation(name="owner"),
        Relation(name="parent"),
        Relation(name="viewer", subject_set_rewrite=SubjectSetRewrite(
            children=[
                ComputedSubjectSet(relation="owner"),
                TupleToSubjectSet(
                    relation="parent",
                    computed_subject_set_relation="viewer",
                ),
            ]
        )),
    ])]


def deep_tuples(n_chains=6, n_users=8, seed=3):
    rng = random.Random(seed)
    tuples, owners = [], {}
    for c in range(n_chains):
        for i in range(DEPTH):
            tuples.append(RelationTuple.from_string(
                f"deep:c{c}f{i}#parent@(deep:c{c}f{i + 1}#...)"
            ))
        owner = f"u{rng.randrange(n_users)}"
        owners[c] = owner
        tuples.append(RelationTuple.from_string(
            f"deep:c{c}f{DEPTH}#owner@{owner}"
        ))
    return tuples, owners


def make_engine(tuples, namespaces=None, max_depth=DEPTH + 4, store=None,
                closure=True, mesh=None, **cfg_extra):
    cfg = Config({
        "limit": {"max_read_depth": max_depth},
        "closure": {"enabled": closure, **cfg_extra},
    })
    cfg.set_namespaces(namespaces or deep_namespaces())
    m = store if store is not None else MemoryManager()
    m.write_relation_tuples(tuples)
    return TPUCheckEngine(m, cfg, frontier_cap=4096, mesh=mesh)


def deep_queries(owners, n=64, n_users=8, seed=11):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        c = rng.randrange(len(owners))
        f = rng.randrange(DEPTH)
        sub = owners[c] if i % 2 == 0 else f"u{rng.randrange(n_users)}"
        out.append(RelationTuple.from_string(f"deep:c{c}f{f}#viewer@{sub}"))
    return out


class TestBuilderVsOracle:
    """The powering product equals the exact host closure oracle —
    per-node subject sets AND per-entry minimum required depths."""

    def _compare_node(self, engine, ns, obj, rel):
        state = engine._ensure_state()
        snap = state.snapshot
        idx = engine.closure_index()
        with idx._mu:
            build = idx._build
            graph = idx._graph
        oracle_ok, oracle = ReferenceEngine(
            engine.manager, engine.config
        ).closure_subjects(ns, obj, rel, 0)
        node = snap.encode_node(ns, obj, rel)
        assert node is not None
        key = node[0] * graph.R + node[1]
        covered = key in build.covered_keys
        if not oracle_ok:
            assert not covered, f"{ns}:{obj}#{rel} covers a non-monotone walk"
            return
        if not covered:
            return  # builder may under-cover (caps); never over-cover
        mask = (
            build.ent_obj.astype(np.int64) * graph.R + build.ent_rel
        ) == key
        got = {}
        subj_by_id = {v: k for k, v in snap.subj_ids.items()}
        slot_names = {v: k for k, v in snap.obj_slots.items()}
        rel_names = {v: k for k, v in snap.rel_ids.items()}
        ns_names = {v: k for k, v in snap.ns_ids.items()}
        for sk, sa, sb, rq in zip(
            build.ent_skind[mask], build.ent_sa[mask],
            build.ent_sb[mask], build.ent_req[mask],
        ):
            if sk == 0:
                got[("id", subj_by_id[int(sa)])] = int(rq)
            else:
                nsid, obj_name = slot_names[int(sa)]
                got[
                    ("set", ns_names[nsid], obj_name, rel_names[int(sb)])
                ] = int(rq)
        assert got == oracle, f"{ns}:{obj}#{rel}: {got} != {oracle}"

    def test_deep_chain_sets_and_depths(self):
        tuples, _ = deep_tuples()
        engine = make_engine(tuples)
        assert engine.closure_ensure_built()
        for f in (0, 3, DEPTH - 1):
            self._compare_node(engine, "deep", f"c0f{f}", "viewer")
        self._compare_node(engine, "deep", f"c1f{DEPTH}", "owner")

    def test_cycles_terminate_with_min_depth(self):
        ns = [Namespace(name="g", relations=[Relation(name="member")])]
        tuples = [
            RelationTuple.from_string("g:x#member@(g:y#member)"),
            RelationTuple.from_string("g:y#member@(g:x#member)"),
            RelationTuple.from_string("g:x#member@alice"),
        ]
        engine = make_engine(tuples, namespaces=ns, max_depth=8)
        assert engine.closure_ensure_built()
        self._compare_node(engine, "g", "x", "member")
        self._compare_node(engine, "g", "y", "member")

    def test_island_poison_blocks_coverage(self):
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
        engine = make_engine(tuples, namespaces=ns, max_depth=6)
        assert engine.closure_ensure_built()
        self._compare_node(engine, "acl", "d", "access")  # island
        self._compare_node(engine, "acl", "g", "group")  # reaches island
        self._compare_node(engine, "acl", "h", "group")  # clean

    def test_relation_not_found_poison(self):
        # a data relation inside a CONFIGURED namespace errors in the
        # reference; any node reaching it must stay uncovered
        ns = [Namespace(name="cfg", relations=[Relation(name="member")])]
        tuples = [
            RelationTuple.from_string("cfg:a#member@(cfg:b#ghost)"),
            RelationTuple.from_string("cfg:b#ghost@u1"),
        ]
        engine = make_engine(tuples, namespaces=ns, max_depth=6)
        assert engine.closure_ensure_built()
        self._compare_node(engine, "cfg", "a", "member")
        self._compare_node(engine, "cfg", "b", "ghost")

    @pytest.mark.parametrize("dsn", ["sqlite", "columnar"])
    def test_store_parity(self, dsn, tmp_path):
        if dsn == "sqlite":
            from keto_tpu.storage.sqlite import SQLPersister

            store = SQLPersister(f"sqlite://{tmp_path}/closure.db")
        else:
            from keto_tpu.storage.columnar import ColumnarStore

            store = ColumnarStore()
        tuples, _ = deep_tuples()
        engine = make_engine(tuples, store=store)
        assert engine.closure_ensure_built()
        self._compare_node(engine, "deep", "c0f0", "viewer")


class TestCheckParity:
    """closure-on answers == closure-off answers == host oracle, at
    every requested depth, on single-device and mesh engines."""

    def _assert_parity(self, mesh=None):
        tuples, owners = deep_tuples()
        queries = deep_queries(owners)
        on = make_engine(tuples, mesh=mesh)
        assert on.closure_ensure_built()
        off = make_engine(tuples, closure=False, mesh=mesh)
        oracle = ReferenceEngine(off.manager, off.config)
        for depth in (0, 1, 3, DEPTH + 2):
            r_on = on.check_batch(queries, depth)
            r_off = off.check_batch(queries, depth)
            for q, a, b in zip(queries, r_on, r_off):
                assert a.membership == b.membership, (str(q), depth)
                want = oracle.check_relation_tuple(q, depth)
                assert a.membership == want.membership, (str(q), depth)
        assert on.stats.get("closure_hits", 0) > 0
        return on

    def test_single_device_parity_all_depths(self):
        engine = self._assert_parity()
        # the full-depth leg must resolve entirely on the closure
        fallbacks = engine.stats.get("closure_fallback", {})
        assert fallbacks.get("uncovered", 0) == 0, fallbacks

    def test_mesh_parity(self):
        from keto_tpu.parallel import default_mesh

        self._assert_parity(mesh=default_mesh(8))

    def test_unknown_vocabulary_rides_fallback(self):
        tuples, _ = deep_tuples()
        engine = make_engine(tuples)
        assert engine.closure_ensure_built()
        res = engine.check_batch([
            RelationTuple.from_string("deep:c0f0#viewer@martian"),
            RelationTuple.from_string("nowhere:x#y@alice"),
        ])
        assert all(r.membership == Membership.NOT_MEMBER for r in res)

    @pytest.mark.parametrize("as_columns", [False, True])
    def test_mixed_batch_splits_and_merges_in_order(self, as_columns):
        # covered nodes + an uncovered (island) namespace in ONE batch:
        # resolved verdicts and BFS-leftover verdicts must interleave
        # back into request order; a batch that arrives as CheckColumns
        # hands its leftover rows on as columns
        ns = deep_namespaces() + [Namespace(name="acl", relations=[
            Relation(name="allow"), Relation(name="deny"),
            Relation(name="access", subject_set_rewrite=SubjectSetRewrite(
                operation=Operator.AND,
                children=[
                    ComputedSubjectSet(relation="allow"),
                    InvertResult(child=ComputedSubjectSet(relation="deny")),
                ])),
        ])]
        tuples, owners = deep_tuples()
        tuples = tuples + [
            RelationTuple.from_string("acl:d#allow@u1"),
            RelationTuple.from_string("acl:e#allow@u2"),
            RelationTuple.from_string("acl:e#deny@u2"),
        ]
        engine = make_engine(tuples, namespaces=ns)
        assert engine.closure_ensure_built()
        batch = [
            RelationTuple.from_string(f"deep:c0f0#viewer@{owners[0]}"),
            RelationTuple.from_string("acl:d#access@u1"),
            RelationTuple.from_string("deep:c1f0#viewer@nobody"),
            RelationTuple.from_string("acl:e#access@u2"),
        ]
        res = engine.check_batch(CheckColumns.of(batch) if as_columns else batch)
        assert [r.membership for r in res] == [
            Membership.IS_MEMBER, Membership.IS_MEMBER,
            Membership.NOT_MEMBER, Membership.NOT_MEMBER,
        ]
        assert engine.stats.get("closure_fallback", {}).get("uncovered", 0) >= 2


class TestChurn:
    """Interleaved writes force the index to lag: zero wrong answers,
    and the fallback -> catch-up -> hit transitions are observable."""

    def test_write_then_check_is_never_stale(self):
        tuples, owners = deep_tuples()
        engine = make_engine(tuples)
        oracle = ReferenceEngine(engine.manager, engine.config)
        assert engine.closure_ensure_built()
        rng = random.Random(5)
        wrong = 0
        for r in range(20):
            c = rng.randrange(len(owners))
            engine.manager.write_relation_tuples([RelationTuple.from_string(
                f"deep:c{c}f{rng.randrange(DEPTH + 1)}#owner@w{r}"
            )])
            qs = deep_queries(owners, n=8, seed=r) + [
                RelationTuple.from_string(f"deep:c{c}f0#viewer@w{r}")
            ]
            for q, res in zip(qs, engine.check_batch(qs)):
                want = oracle.check_relation_tuple(q)
                if res.membership != want.membership:
                    wrong += 1
        assert wrong == 0
        # churn must have produced BOTH hits and dirty fallbacks
        assert engine.stats.get("closure_hits", 0) > 0
        assert engine.stats.get("closure_fallback", {}).get("dirty", 0) > 0

    def test_refresh_reads_proportional_to_dirty_set(self):
        """The ROADMAP item 3 scale fix: a dirty refresh must fetch only
        the dirty nodes' consulting regions (indexed per-object reads),
        NOT re-read the whole store per pass — on a many-chain topology
        a one-chain perturbation reads ~one chain's rows."""
        tuples, owners = deep_tuples(n_chains=24)
        engine = make_engine(tuples)
        oracle = ReferenceEngine(engine.manager, engine.config)
        assert engine.closure_ensure_built()
        idx = engine.closure_index()
        store_rows = len(tuples)
        # perturb ONE chain, then refresh
        engine.manager.write_relation_tuples([
            RelationTuple.from_string(f"deep:c3f{DEPTH}#owner@fresh")
        ])
        assert engine.closure_ensure_built()
        assert idx.stats.get("scoped_refreshes", 0) == 1
        rows = idx.stats.get("refresh_rows_read", 0)
        # one chain is DEPTH parent edges + owners — far under the
        # 24-chain store (the old full read would count store_rows)
        assert 0 < rows <= 3 * (DEPTH + 2), (rows, store_rows)
        assert rows < store_rows / 4
        # and the refreshed index answers the overlay-era subject right
        res = engine.check_batch([
            RelationTuple.from_string("deep:c3f0#viewer@fresh")
        ])
        want = oracle.check_relation_tuple(
            RelationTuple.from_string("deep:c3f0#viewer@fresh")
        )
        assert res[0].membership == want.membership

    def test_scoped_refresh_marks_future_writes(self):
        """After a region-scoped refresh installs the MERGED dependency
        graph, a write at an object only the refreshed rows reach must
        still dirty its ancestors (under-marking would serve stale
        covered answers)."""
        tuples, owners = deep_tuples(n_chains=4)
        engine = make_engine(tuples)
        oracle = ReferenceEngine(engine.manager, engine.config)
        assert engine.closure_ensure_built()
        # extend chain 1 with an overlay-era tail object, refresh it in
        engine.manager.write_relation_tuples([
            RelationTuple.from_string(
                f"deep:c1f{DEPTH}#parent@(deep:newtail#...)"
            ),
            RelationTuple.from_string("deep:newtail#owner@tailowner"),
        ])
        assert engine.closure_ensure_built()
        q = RelationTuple.from_string("deep:c1f0#viewer@tailowner")
        res = engine.check_batch([q])[0]
        assert res.membership == Membership.IS_MEMBER
        # now write at the overlay-era object: the merged dependency
        # graph must mark chain 1 dirty, and answers stay oracle-exact
        engine.manager.delete_relation_tuples([
            RelationTuple.from_string("deep:newtail#owner@tailowner")
        ])
        assert engine.closure_ensure_built()
        res = engine.check_batch([q])[0]
        want = oracle.check_relation_tuple(q)
        assert res.membership == want.membership
        assert want.membership == Membership.NOT_MEMBER

    def test_held_tail_lag_gating(self):
        # lag budget 0: the submit path may never catch up inline, so a
        # lagging index must refuse (cause=lag) and answers ride BFS
        tuples, owners = deep_tuples()
        engine = make_engine(tuples, lag_budget_versions=0)
        oracle = ReferenceEngine(engine.manager, engine.config)
        assert engine.closure_ensure_built()
        q_hit = RelationTuple.from_string(f"deep:c0f0#viewer@{owners[0]}")
        engine.check_batch([q_hit])
        assert engine.stats.get("closure_hits", 0) == 1
        engine.manager.write_relation_tuples([
            RelationTuple.from_string("deep:c0f9#owner@late")
        ])
        res = engine.check_batch([
            RelationTuple.from_string("deep:c0f0#viewer@late")
        ])
        assert res[0].membership == Membership.IS_MEMBER  # BFS, never stale
        assert engine.stats["closure_fallback"].get("lag", 0) == 1
        # maintenance (closure_ensure_built = catch-up + incremental
        # dirty refresh) restores hits for BOTH the untouched chain and
        # the freshly-written one — including the overlay-era subject
        # the base snapshot has no id for
        assert engine.closure_ensure_built()
        idx = engine.closure_index()
        assert idx.stats.get("refreshes", 0) >= 1
        assert idx.describe()["dirty_nodes"] == 0
        hits0 = engine.stats["closure_hits"]
        queries = [
            RelationTuple.from_string(f"deep:c1f0#viewer@{owners[1]}"),
            q_hit,
            RelationTuple.from_string("deep:c0f0#viewer@late"),
        ]
        res = engine.check_batch(queries)
        assert engine.stats["closure_hits"] == hits0 + 3
        for q, r in zip(queries, res):
            assert r.membership == oracle.check_relation_tuple(q).membership
        assert res[2].membership == Membership.IS_MEMBER

    def test_overlay_relation_edges_stay_dirty_not_wrong(self):
        # an edge whose subject-set RELATION is overlay-era (no base id)
        # cannot be keyed into the closure graph: the refresh must keep
        # the consulting region dirty (BFS fallback, correct answers)
        # instead of covering a node whose rows it silently dropped
        tuples, owners = deep_tuples()
        engine = make_engine(tuples)
        oracle = ReferenceEngine(engine.manager, engine.config)
        assert engine.closure_ensure_built()
        engine.manager.write_relation_tuples([
            RelationTuple.from_string("deep:c0f5#parent@(other:x#g)"),
            RelationTuple.from_string("other:x#g@newbie"),
        ])
        assert engine.closure_ensure_built()  # catch-up + refresh pass
        # expand-subject traverses the overlay-relation set: member via
        # deep:c0f5#parent -> (other:x#g) -> direct @newbie
        q = RelationTuple.from_string("deep:c0f5#parent@newbie")
        res = engine.check_batch([q])
        want = oracle.check_relation_tuple(q)
        assert res[0].membership == want.membership
        assert res[0].membership == Membership.IS_MEMBER
        # the touched chain stayed dirty (rows unrepresentable in the
        # base-strided graph); untouched chains refreshed back to hits
        assert engine.stats["closure_fallback"].get("dirty", 0) >= 1
        hits0 = engine.stats.get("closure_hits", 0)
        engine.check_batch([
            RelationTuple.from_string(f"deep:c1f0#viewer@{owners[1]}")
        ])
        assert engine.stats.get("closure_hits", 0) == hits0 + 1

    def test_write_at_refreshed_overlay_object_still_marks(self):
        # the post-refresh marking hole: an edge to a NEW object is
        # refreshed into the closure rows (and its marks cleared); a
        # LATER write at that object must still mark the ancestors —
        # the refresh installs its content graph + overlay encoder so
        # the base snapshot's inability to encode the object does not
        # silently skip the op
        tuples, owners = deep_tuples()
        engine = make_engine(tuples)
        oracle = ReferenceEngine(engine.manager, engine.config)
        assert engine.closure_ensure_built()
        # extend chain 0 with a brand-new tail object (base rel "...")
        engine.manager.write_relation_tuples([
            RelationTuple.from_string(
                f"deep:c0f{DEPTH}#parent@(deep:c0tail#...)"
            )
        ])
        assert engine.closure_ensure_built()  # refresh consumes marks
        assert engine.closure_index().describe()["dirty_nodes"] == 0
        # now write AT the new object: base snapshot has no id for it
        engine.manager.write_relation_tuples([
            RelationTuple.from_string("deep:c0tail#owner@phantom")
        ])
        assert engine.closure_ensure_built()
        q = RelationTuple.from_string("deep:c0f0#viewer@phantom")
        res = engine.check_batch([q])
        want = oracle.check_relation_tuple(q)
        assert res[0].membership == want.membership
        assert res[0].membership == Membership.IS_MEMBER

    def test_empty_store_cold_start_gains_coverage(self):
        # a server can start over an EMPTY store (bulk load arrives
        # later): the initial index is empty and the base snapshot can
        # encode nothing — maintenance must still power the written
        # graph into coverage (encoder advanced to the overlay view +
        # dirty refresh), not stay closure-less until compaction
        engine = make_engine([])  # empty store, closure on
        assert engine.closure_ensure_built()
        tuples, owners = deep_tuples(n_chains=2)
        engine.manager.write_relation_tuples(tuples)
        assert engine.closure_ensure_built()  # mark under view + refresh
        q = RelationTuple.from_string(f"deep:c0f0#viewer@{owners[0]}")
        res = engine.check_batch([q])
        assert res[0].membership == Membership.IS_MEMBER
        assert engine.stats.get("closure_hits", 0) == 1, (
            engine.stats.get("closure_fallback"),
            engine.closure_index().describe(),
        )

    def test_dirty_marks_transitive_ancestors_only(self):
        tuples, owners = deep_tuples()
        engine = make_engine(tuples)
        assert engine.closure_ensure_built()
        engine.manager.write_relation_tuples([
            RelationTuple.from_string("deep:c2f5#owner@noob")
        ])
        assert engine.closure_index().catch_up(
            engine.manager, engine.manager.version()
        )
        idx = engine.closure_index()
        state = engine._ensure_state()
        snap = state.snapshot
        with idx._mu:
            dirty = set(idx._dirty)
            R = idx._graph.R
        def key(obj, rel):
            node = snap.encode_node("deep", obj, rel)
            return node[0] * R + node[1]
        # ancestors of the changed node (same chain, heads through f5)
        for f in (0, 3, 5):
            assert key(f"c2f{f}", "viewer") in dirty
        # other chains untouched
        assert key("c3f0", "viewer") not in dirty


class TestMaintainer:
    def _registry(self, tmp_path):
        cfg = Config({
            "dsn": "memory",
            "limit": {"max_read_depth": DEPTH + 4},
            "closure": {"enabled": True},
        })
        cfg.set_namespaces(deep_namespaces())
        reg = Registry(cfg)
        tuples, owners = deep_tuples()
        reg.relation_tuple_manager().write_relation_tuples(tuples)
        return reg, owners

    def test_tailer_applies_watch_events(self, tmp_path):
        reg, owners = self._registry(tmp_path)
        engine = reg.check_engine()
        maint = reg.closure_maintainer()
        reg.watch_hub()  # write hooks live
        maint.step()  # initial powering
        assert not engine.closure_index().needs_rebuild()
        reg.relation_tuple_manager().write_relation_tuples([
            RelationTuple.from_string("deep:c0f9#owner@tailed")
        ])
        maint.step()
        idx = engine.closure_index()
        assert idx.lag_versions(
            reg.relation_tuple_manager().version()
        ) == 0
        # the step both applied the event (dirty marking) and ran the
        # incremental refresh that re-powered the marked nodes
        assert idx.stats.get("refreshes", 0) >= 1
        assert idx.describe()["dirty_nodes"] == 0
        res = engine.check_batch([
            RelationTuple.from_string("deep:c0f0#viewer@tailed")
        ])
        assert res[0].membership == Membership.IS_MEMBER
        assert engine.stats.get("closure_hits", 0) >= 1

    def test_background_thread_keeps_index_fresh(self, tmp_path):
        import time as _time

        reg, owners = self._registry(tmp_path)
        engine = reg.check_engine()
        maint = reg.closure_maintainer()
        maint.poll_interval = 0.05
        maint.start()
        try:
            manager = reg.relation_tuple_manager()
            manager.write_relation_tuples([
                RelationTuple.from_string("deep:c1f9#owner@bg")
            ])
            deadline = _time.monotonic() + 5
            idx = engine.closure_index()
            while _time.monotonic() < deadline:
                if (
                    not idx.needs_rebuild()
                    and idx.lag_versions(manager.version()) == 0
                ):
                    break
                _time.sleep(0.02)
            assert idx.lag_versions(manager.version()) == 0
            res = engine.check_batch([
                RelationTuple.from_string("deep:c1f0#viewer@bg")
            ])
            assert res[0].membership == Membership.IS_MEMBER
        finally:
            maint.stop()

    def test_held_maintainer_never_answers_stale(self, tmp_path):
        reg, owners = self._registry(tmp_path)
        # budget 0 disables the inline catch-up: held maintainer = pure lag
        reg.config.set("closure.lag_budget_versions", 0)
        engine = reg.check_engine()
        maint = reg.closure_maintainer()
        maint.step()
        maint.hold()
        maint.start()
        try:
            reg.relation_tuple_manager().write_relation_tuples([
                RelationTuple.from_string("deep:c0f9#owner@held")
            ])
            res = engine.check_batch([
                RelationTuple.from_string("deep:c0f0#viewer@held")
            ])
            assert res[0].membership == Membership.IS_MEMBER
            assert engine.stats["closure_fallback"].get("lag", 0) >= 1
            maint.release()
            import time as _time

            idx = engine.closure_index()
            deadline = _time.monotonic() + 5
            while _time.monotonic() < deadline:
                if idx.lag_versions(
                    reg.relation_tuple_manager().version()
                ) == 0:
                    break
                _time.sleep(0.02)
            assert idx.lag_versions(
                reg.relation_tuple_manager().version()
            ) == 0
        finally:
            maint.stop()


class TestVersionGating:
    def test_snapshot_rebuild_invalidates_index(self):
        tuples, owners = deep_tuples()
        engine = make_engine(tuples)
        assert engine.closure_ensure_built()
        engine.invalidate()
        # a config-fingerprint-stable rebuild produces a new snapshot
        # object with a new version: the old index must refuse
        from keto_tpu.engine.closure import CAUSE_STALE_SNAPSHOT

        state = engine._ensure_state()
        view, cause = engine.closure_index().view_for(state)
        assert view is None and cause == CAUSE_STALE_SNAPSHOT
        # ...and re-powering restores service
        assert engine.closure_ensure_built()
        view, cause = engine.closure_index().view_for(state)
        assert view is not None

    def test_dirty_overflow_goes_stale_not_wrong(self):
        from keto_tpu.engine import closure as closure_mod

        tuples, owners = deep_tuples()
        engine = make_engine(tuples)
        oracle = ReferenceEngine(engine.manager, engine.config)
        assert engine.closure_ensure_built()
        idx = engine.closure_index()
        old = closure_mod.DIRTY_COMPACT_THRESHOLD
        closure_mod.DIRTY_COMPACT_THRESHOLD = 1
        try:
            engine.manager.write_relation_tuples([
                RelationTuple.from_string("deep:c0f9#owner@burst"),
                RelationTuple.from_string("deep:c1f9#owner@burst"),
            ])
            idx.catch_up(engine.manager, engine.manager.version())
            assert idx.needs_rebuild()
            q = RelationTuple.from_string("deep:c0f0#viewer@burst")
            res = engine.check_batch([q])
            assert (
                res[0].membership
                == oracle.check_relation_tuple(q).membership
            )
            assert engine.stats["closure_fallback"].get(
                "stale_snapshot", 0
            ) >= 1
        finally:
            closure_mod.DIRTY_COMPACT_THRESHOLD = old


class TestObservability:
    def test_hbm_snapshot_breaks_out_closure_families(self):
        tuples, _ = deep_tuples()
        engine = make_engine(tuples)
        assert engine.closure_ensure_built()
        engine.check_batch([
            RelationTuple.from_string("deep:c0f0#viewer@u1")
        ])
        snap = engine.hbm_snapshot()
        assert "closure" in snap["buffers"]
        assert "closure_delta" in snap["buffers"]
        assert snap["buffers"]["closure"].get("ch_pack", 0) > 0
        assert snap["buffers"]["closure"].get("cc_pack", 0) > 0
        assert snap["buffers"]["closure_delta"].get("cd_pack", 0) > 0
        assert snap["totals"]["closure"] > 0

    def test_flightrec_closure_launch_entries(self):
        from keto_tpu.observability import FlightRecorder

        tuples, owners = deep_tuples()
        fr = FlightRecorder(capacity=16)
        cfg = Config({
            "limit": {"max_read_depth": DEPTH + 4},
            "closure": {"enabled": True},
        })
        cfg.set_namespaces(deep_namespaces())
        m = MemoryManager()
        m.write_relation_tuples(tuples)
        engine = TPUCheckEngine(m, cfg, frontier_cap=4096, flightrec=fr)
        assert engine.closure_ensure_built()
        queries = deep_queries(owners, n=8)
        engine.check_batch(queries)
        entries = [e for e in fr.entries() if e["kind"] == "closure"]
        assert entries, [e["kind"] for e in fr.entries()]
        e = entries[-1]
        # the stats vector rides the packed readback like every kernel:
        # ONE step regardless of the chain depth is the whole point
        assert e["steps"] == 1
        assert e["step_cap"] == 1
        assert e["n"] == len(queries)
        assert e["closure_resolved"] == len(queries)
        assert e["gather_bytes_est"] > 0
        assert "launch_id" in e

    def test_closure_metrics_registered_and_counted(self):
        from keto_tpu.observability import Metrics

        metrics = Metrics()
        tuples, owners = deep_tuples()
        cfg = Config({
            "limit": {"max_read_depth": DEPTH + 4},
            "closure": {"enabled": True},
        })
        cfg.set_namespaces(deep_namespaces())
        m = MemoryManager()
        m.write_relation_tuples(tuples)
        engine = TPUCheckEngine(m, cfg, frontier_cap=4096, metrics=metrics)
        assert engine.closure_ensure_built()
        engine.check_batch(deep_queries(owners, n=8))
        text = metrics.export().decode()
        assert "keto_tpu_closure_hits_total 8.0" in text
        assert "keto_tpu_closure_lag_versions 0.0" in text
        assert "keto_tpu_closure_builds_total 1.0" in text


class TestPersistence:
    def test_closure_checkpoint_roundtrip(self, tmp_path):
        tuples, owners = deep_tuples()
        engine = make_engine(tuples)
        # enable the cache dir via config BEFORE the index exists
        engine.config.set("check.mirror_cache", str(tmp_path))
        assert engine.closure_ensure_built()
        idx = engine.closure_index()
        assert idx.cache_path is not None
        import os

        assert os.path.exists(idx.cache_path)
        # a fresh engine over the same store+config loads, not powers
        engine2 = make_engine([], store=engine.manager)
        engine2.config.set("check.mirror_cache", str(tmp_path))
        assert engine2.closure_ensure_built()
        assert engine2.closure_index().stats["cache_loads"] == 1
        res = engine2.check_batch([
            RelationTuple.from_string(f"deep:c0f0#viewer@{owners[0]}")
        ])
        assert res[0].membership == Membership.IS_MEMBER
        assert engine2.stats.get("closure_hits", 0) == 1

    def test_cache_rejected_when_depth_limit_changes(self, tmp_path):
        # the persisted product was trimmed to the powering depth; a
        # restart with a RAISED limit.max_read_depth must re-power, not
        # serve the shallow build's definitive negatives
        tuples, owners = deep_tuples()
        engine = make_engine(tuples, max_depth=4)
        engine.config.set("check.mirror_cache", str(tmp_path))
        assert engine.closure_ensure_built()
        deep_engine = make_engine([], store=engine.manager,
                                  max_depth=DEPTH + 4)
        deep_engine.config.set("check.mirror_cache", str(tmp_path))
        assert deep_engine.closure_ensure_built()
        assert deep_engine.closure_index().stats["cache_loads"] == 0
        q = RelationTuple.from_string(f"deep:c0f0#viewer@{owners[0]}")
        res = deep_engine.check_batch([q])
        assert res[0].membership == Membership.IS_MEMBER
        assert deep_engine.stats.get("closure_hits", 0) == 1

    def test_torn_closure_checkpoint_degrades_to_powering(self, tmp_path):
        from keto_tpu.engine.checkpoint import load_closure

        p = tmp_path / "closure-default.npz"
        p.write_bytes(b"PK\x03\x04 torn")
        assert load_closure(str(p)) is None


class TestConfigKeys:
    def test_schema_validates_and_applies(self):
        cfg = Config({
            "dsn": "memory",
            "closure": {
                "enabled": True,
                "max_set_rows": 128,
                "lag_budget_versions": 7,
            },
        })
        reg = Registry(cfg)
        engine = reg.check_engine()
        assert engine.closure_enabled is True
        idx = engine.closure_index()
        assert idx.max_set_rows == 128
        assert idx.lag_budget_versions == 7

    def test_unknown_closure_key_rejected(self):
        from keto_tpu.config import ConfigError

        with pytest.raises(ConfigError):
            Config({"dsn": "memory", "closure": {"bogus": 1}})

    def test_disabled_by_default(self):
        engine = make_engine([], closure=False)
        assert engine.closure_enabled is False
        engine2 = TPUCheckEngine(MemoryManager(), Config({"dsn": "memory"}))
        assert engine2.closure_enabled is False


class TestRowCap:
    def test_oversized_sets_fall_back_not_wrong(self):
        # one node fanning out to many subjects with max_set_rows below
        # the fanout: uncovered, answers still correct via BFS
        ns = [Namespace(name="big", relations=[Relation(name="member")])]
        tuples = [
            RelationTuple.from_string(f"big:hub#member@u{i}")
            for i in range(32)
        ]
        engine = make_engine(
            tuples, namespaces=ns, max_depth=6, max_set_rows=8
        )
        assert engine.closure_ensure_built()
        res = engine.check_batch([
            RelationTuple.from_string("big:hub#member@u3"),
            RelationTuple.from_string("big:hub#member@nobody"),
        ])
        assert res[0].membership == Membership.IS_MEMBER
        assert res[1].membership == Membership.NOT_MEMBER
        assert engine.stats["closure_fallback"].get("uncovered", 0) == 2
