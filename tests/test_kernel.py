"""Differential tests: the batched BFS kernel against the exact host
reference engine, on the ported fixture sets and randomized graphs.
Runs on the virtual CPU backend (conftest.py); the same code path runs
on TPU."""

import os
import random

import pytest

from keto_tpu.config import Config
from keto_tpu.engine import Membership, ReferenceEngine
from keto_tpu.engine.snapshot import build_snapshot
from keto_tpu.engine.tpu_engine import TPUCheckEngine
from keto_tpu.ketoapi import RelationTuple, SubjectSet
from keto_tpu.namespace import Namespace
from keto_tpu.namespace.ast import (
    ComputedSubjectSet,
    Relation,
    SubjectSetRewrite,
    TupleToSubjectSet,
)
from keto_tpu.storage import MemoryManager

from test_reference_engine import (
    REWRITE_CASES,
    REWRITE_NAMESPACES,
    REWRITE_TUPLES,
)


def make_tpu_engine(namespaces, tuples, max_depth=5):
    cfg = Config({"limit": {"max_read_depth": max_depth}})
    cfg.set_namespaces(namespaces)
    m = MemoryManager()
    m.write_relation_tuples([RelationTuple.from_string(s) for s in tuples])
    return TPUCheckEngine(m, cfg)


@pytest.fixture(scope="module")
def rewrite_tpu_engine():
    # one snapshot build + kernel compile for all 20 fixture cases
    return make_tpu_engine(REWRITE_NAMESPACES, REWRITE_TUPLES, max_depth=100)


class TestSnapshot:
    def test_build_and_encode(self):
        tuples = [
            RelationTuple.from_string("n:o#r@u"),
            RelationTuple.from_string("n:o#r@(n:o2#r2)"),
        ]
        snap = build_snapshot(tuples, [Namespace(name="n")])
        assert snap.n_tuples == 2
        node = snap.encode_node("n", "o", "r")
        assert node is not None
        assert snap.encode_node("missing", "o", "r") is None
        assert snap.encode_subject(tuples[0]) == (0, snap.subj_ids["u"], 0)
        skind, sa, sb = snap.encode_subject(tuples[1])
        assert skind == 1

    def test_hash_table_holds_all_edges(self):
        # build a snapshot with enough edges to force collisions
        tuples = [
            RelationTuple.from_string(f"n:o{i % 97}#r{i % 11}@u{i}")
            for i in range(2000)
        ]
        snap = build_snapshot(tuples, [])
        assert (snap.dh_val != -1).sum() == 2000


class TestKernelDifferential:
    @pytest.mark.skipif(
        not os.path.isdir(
            "/root/reference/contrib/cat-videos-example/relation-tuples"
        ),
        reason="reference checkout with the cat-videos fixture not present",
    )
    def test_cat_videos(self):
        import glob
        import json

        tuples = []
        for f in sorted(
            glob.glob(
                "/root/reference/contrib/cat-videos-example/relation-tuples/*.json"
            )
        ):
            d = json.load(open(f))
            d.pop("$schema", None)
            tuples.append(str(RelationTuple.from_dict(d)))
        e = make_tpu_engine([Namespace(name="videos")], tuples)
        queries = [
            "videos:/cats/1.mp4#view@*",
            "videos:/cats/1.mp4#view@cat lady",
            "videos:/cats/2.mp4#view@cat lady",
            "videos:/cats/2.mp4#view@john",
            "videos:/cats#view@cat lady",
            "videos:/cats#owner@cat lady",
            "videos:/cats/1.mp4#owner@cat lady",
        ]
        rts = [RelationTuple.from_string(q) for q in queries]
        got = e.check_batch(rts)
        want = [e.reference.check_relation_tuple(t, 0) for t in rts]
        for q, g, w in zip(queries, got, want):
            assert g.membership == w.membership, q
        # all these are monotone: the device must have answered them
        assert e.stats["host_checks"] == 0

    @pytest.mark.parametrize("query,expected", REWRITE_CASES)
    def test_rewrite_fixtures(self, rewrite_tpu_engine, query, expected):
        res = rewrite_tpu_engine.check_batch(
            [RelationTuple.from_string(query)], 100
        )[0]
        assert res.error is None
        assert (res.membership == Membership.IS_MEMBER) == expected, query

    def test_and_not_islands_run_on_device(self):
        """AND/NOT rewrites execute as device islands (VERDICT round-1
        item 4): every REWRITE_CASE — including acl's AND + NOT(deny) and
        resource's AND(owner, TTU) — answers from the kernel, matching
        the exact host engine. The ONLY host replay allowed is the
        unknown-object query (object absent from graph + vocab — the
        documented exact-host path, unrelated to islands)."""
        unknown_vocab = {"doc:another_doc#viewer@user"}
        e = make_tpu_engine(REWRITE_NAMESPACES, REWRITE_TUPLES, max_depth=100)
        rts = [RelationTuple.from_string(q) for q, _ in REWRITE_CASES]
        got = e.check_batch(rts, 100)
        for (q, expected), g in zip(REWRITE_CASES, got):
            assert g.error is None, q
            assert (g.membership == Membership.IS_MEMBER) == expected, q
        assert e.stats["host_checks"] == len(unknown_vocab)
        assert e.stats["device_checks"] == len(rts) - len(unknown_vocab)

    def test_deep_chain_topology(self):
        # the reference benchmark's "deep" namespace (bench_test.go:56-86)
        max_depth = 32
        namespaces = [
            Namespace(
                name="deep",
                relations=[
                    Relation(name="owner"),
                    Relation(name="parent"),
                    Relation(
                        name="editor",
                        subject_set_rewrite=SubjectSetRewrite(
                            children=[ComputedSubjectSet(relation="owner")]
                        ),
                    ),
                    Relation(
                        name="viewer",
                        subject_set_rewrite=SubjectSetRewrite(
                            children=[
                                ComputedSubjectSet(relation="editor"),
                                TupleToSubjectSet(
                                    relation="parent",
                                    computed_subject_set_relation="viewer",
                                ),
                            ]
                        ),
                    ),
                ],
            )
        ]
        tuples = ["deep:deep_file#parent@(deep:folder_1#...)"]
        for i in range(1, max_depth):
            tuples.append(f"deep:folder_{i}#parent@(deep:folder_{i + 1}#...)")
        for d in (2, 4, 8, 16, 32):
            tuples.append(f"deep:folder_{d}#owner@user_{d}")
        e = make_tpu_engine(namespaces, tuples, max_depth=100 * max_depth)
        for d in (2, 4, 8, 16, 32):
            q = RelationTuple.from_string(f"deep:deep_file#viewer@user_{d}")
            res = e.check_batch([q], 2 * d)[0]
            ref = e.reference.check_relation_tuple(q, 2 * d)
            assert res.membership == ref.membership, f"depth {d}"
            assert res.membership == Membership.IS_MEMBER
        # not enough depth: reference and kernel agree on the miss
        q = RelationTuple.from_string("deep:deep_file#viewer@user_32")
        res = e.check_batch([q], 3)[0]
        assert res.membership == Membership.NOT_MEMBER
        assert e.stats["host_checks"] == 0

    def test_wide_union_topology(self):
        # the reference benchmark's wide namespace (bench_test.go:19-46)
        width = 40
        relations = [Relation(name="editor")]
        children = []
        for i in range(width):
            relations.append(Relation(name=f"relation-{i}"))
            children.append(ComputedSubjectSet(relation=f"relation-{i}"))
        children.append(ComputedSubjectSet(relation="editor"))
        relations.append(
            Relation(name="viewer", subject_set_rewrite=SubjectSetRewrite(children=children))
        )
        ns = Namespace(name="wide", relations=relations)
        e = make_tpu_engine([ns], ["wide:file#editor@user"], max_depth=80)
        q = RelationTuple.from_string("wide:file#viewer@user")
        res = e.check_batch([q], 80)[0]
        assert res.membership == Membership.IS_MEMBER
        # width exceeds the instruction cap K=8: korrectly host-flagged
        assert e.stats["host_checks"] == 1

    def test_circular_graph(self):
        e = make_tpu_engine(
            [Namespace(name="n")],
            [
                "n:a#r@(n:b#r)",
                "n:b#r@(n:c#r)",
                "n:c#r@(n:a#r)",
                "n:c#r@deep-user",
            ],
            max_depth=10,
        )
        for q, want in [
            ("n:a#r@deep-user", True),
            ("n:b#r@deep-user", True),
            ("n:a#r@nobody", False),
        ]:
            res = e.check_batch([RelationTuple.from_string(q)], 10)[0]
            assert (res.membership == Membership.IS_MEMBER) == want, q

    def test_subject_set_query_subject(self):
        # query whose subject is itself a subject set: direct probe must
        # match subject-set edges exactly
        e = make_tpu_engine(
            [Namespace(name="n")],
            ["n:o#r@(n:o2#r2)"],
        )
        q = RelationTuple.make("n", "o", "r", SubjectSet("n", "o2", "r2"))
        assert e.check_batch([q])[0].membership == Membership.IS_MEMBER
        q2 = RelationTuple.make("n", "o", "r", SubjectSet("n", "o2", "other"))
        assert e.check_batch([q2])[0].membership == Membership.NOT_MEMBER

    def test_randomized_differential(self):
        rng = random.Random(42)
        n_objects = 30
        n_users = 10
        relations = ["r0", "r1", "r2"]
        namespaces = [
            Namespace(
                name="rnd",
                relations=[
                    Relation(name="r0"),
                    Relation(name="r1"),
                    Relation(
                        name="r2",
                        subject_set_rewrite=SubjectSetRewrite(
                            children=[
                                ComputedSubjectSet(relation="r0"),
                                TupleToSubjectSet(
                                    relation="r1",
                                    computed_subject_set_relation="r2",
                                ),
                            ]
                        ),
                    ),
                ],
            )
        ]
        for trial in range(5):
            tuples = set()
            for _ in range(120):
                obj = f"o{rng.randrange(n_objects)}"
                rel = rng.choice(relations)
                if rng.random() < 0.45:
                    sub = f"(rnd:o{rng.randrange(n_objects)}#{rng.choice(relations)})"
                else:
                    sub = f"u{rng.randrange(n_users)}"
                tuples.add(f"rnd:{obj}#{rel}@{sub}")
            # generous depth so visited-pruning order effects vanish
            e = make_tpu_engine(namespaces, sorted(tuples), max_depth=12)
            queries = []
            for _ in range(64):
                queries.append(
                    RelationTuple.from_string(
                        f"rnd:o{rng.randrange(n_objects)}#"
                        f"{rng.choice(relations)}@u{rng.randrange(n_users)}"
                    )
                )
            got = e.check_batch(queries, 12)
            for q, g in zip(queries, got):
                ref = e.reference.check_relation_tuple(q, 12)
                assert g.membership == ref.membership, f"trial {trial}: {q}"

    def test_read_your_writes(self):
        cfg = Config({"limit": {"max_read_depth": 5}})
        cfg.set_namespaces([Namespace(name="n")])
        m = MemoryManager()
        e = TPUCheckEngine(m, cfg)
        q = RelationTuple.from_string("n:o#r@u")
        assert e.check_batch([q])[0].membership == Membership.NOT_MEMBER
        m.write_relation_tuples([q])
        assert e.check_batch([q])[0].membership == Membership.IS_MEMBER
        m.delete_relation_tuples([q])
        assert e.check_batch([q])[0].membership == Membership.NOT_MEMBER
        # the delta overlay serves read-your-writes without rebuilds
        assert e.stats["snapshot_builds"] == 1

    def test_large_batch_spans_buckets(self):
        tuples = [f"n:o{i}#r@u{i}" for i in range(50)]
        e = make_tpu_engine([Namespace(name="n")], tuples)
        queries = [RelationTuple.from_string(f"n:o{i}#r@u{i}") for i in range(50)]
        queries += [RelationTuple.from_string(f"n:o{i}#r@u{i + 1}") for i in range(50)]
        got = e.check_batch(queries)
        assert all(r.membership == Membership.IS_MEMBER for r in got[:50])
        assert all(r.membership == Membership.NOT_MEMBER for r in got[50:])


class TestReviewRegressions:
    def test_data_only_relation_in_configured_namespace_errors(self):
        # reference: namespace has a relation config, queried relation not
        # declared -> error (engine.go:219-228). A directly-matching tuple
        # instead wins the OR race (one legal schedule) -> IsMember.
        e = make_tpu_engine(
            [Namespace(name="n", relations=[Relation(name="known")])],
            ["n:o#rogue@u"],
        )
        # direct hit: both paths say IsMember, no error
        hit = e.check_batch([RelationTuple.from_string("n:o#rogue@u")])[0]
        assert hit.membership == Membership.IS_MEMBER and hit.error is None
        # miss: the undeclared relation surfaces as an error on both paths
        res = e.check_batch([RelationTuple.from_string("n:o#rogue@v")])[0]
        ref = e.reference.check_relation_tuple(
            RelationTuple.from_string("n:o#rogue@v")
        )
        assert res.error is not None and ref.error is not None
        assert type(res.error) is type(ref.error)

    def test_namespace_config_change_invalidates_snapshot(self):
        cfg = Config({"limit": {"max_read_depth": 5}})
        cfg.set_namespaces([
            Namespace(name="n", relations=[Relation(name="owner"), Relation(name="editor")])
        ])
        m = MemoryManager()
        m.write_relation_tuples([RelationTuple.from_string("n:o#owner@u")])
        e = TPUCheckEngine(m, cfg)
        q = RelationTuple.from_string("n:o#editor@u")
        assert e.check_batch([q])[0].membership == Membership.NOT_MEMBER
        # add a rewrite (editor includes owner) WITHOUT any tuple write
        cfg.set_namespaces([
            Namespace(
                name="n",
                relations=[
                    Relation(name="owner"),
                    Relation(
                        name="editor",
                        subject_set_rewrite=SubjectSetRewrite(
                            children=[ComputedSubjectSet(relation="owner")]
                        ),
                    ),
                ],
            )
        ])
        assert e.check_batch([q])[0].membership == Membership.IS_MEMBER

    def test_step_exhaustion_falls_back_to_host(self):
        # interleaved computed+TTU chain: ~2 BFS steps per level; depth
        # clamp 100 over 60 levels exceeds the kernel step budget, which
        # must flag needs_host instead of silently denying
        ns = Namespace(
            name="d",
            relations=[
                Relation(name="owner"),
                Relation(name="parent"),
                Relation(
                    name="w",
                    subject_set_rewrite=SubjectSetRewrite(
                        children=[
                            ComputedSubjectSet(relation="owner"),
                            TupleToSubjectSet(
                                relation="parent",
                                computed_subject_set_relation="v",
                            ),
                        ]
                    ),
                ),
                Relation(
                    name="v",
                    subject_set_rewrite=SubjectSetRewrite(
                        children=[ComputedSubjectSet(relation="w")]
                    ),
                ),
            ],
        )
        levels = 60
        tuples = ["d:f0#parent@(d:f1#...)"]
        for i in range(1, levels):
            tuples.append(f"d:f{i}#parent@(d:f{i + 1}#...)")
        tuples.append(f"d:f{levels}#owner@user")
        e = make_tpu_engine([ns], tuples, max_depth=100)
        q = RelationTuple.from_string("d:f0#v@user")
        res = e.check_batch([q], 100)[0]
        ref = e.reference.check_relation_tuple(q, 100)
        assert res.membership == ref.membership == Membership.IS_MEMBER
        assert e.stats["host_checks"] == 1  # exhaustion was flagged

    def test_small_frontier_cap_splits_batches(self):
        e = TPUCheckEngine(
            MemoryManager(),
            _cfg_with([Namespace(name="n")]),
            frontier_cap=16,
        )
        queries = [RelationTuple.from_string(f"n:o{i}#r@u") for i in range(40)]
        res = e.check_batch(queries)
        assert len(res) == 40
        assert all(r.membership == Membership.NOT_MEMBER for r in res)


def _cfg_with(namespaces):
    cfg = Config({"limit": {"max_read_depth": 5}})
    cfg.set_namespaces(namespaces)
    return cfg


class TestIslands:
    """Device-island semantics: AND/NOT full-evaluation islands
    (engine/snapshot.py _compile_rewrite + engine/islands.py combine)
    differentially against the exact host engine."""

    def _engine(self, namespaces, tuples, max_depth=8):
        return make_tpu_engine(namespaces, tuples, max_depth=max_depth)

    def test_nested_not_not(self):
        from keto_tpu.namespace.ast import InvertResult

        ns = [Namespace(name="n", relations=[
            Relation(name="a"),
            Relation(name="dbl", subject_set_rewrite=SubjectSetRewrite(children=[
                InvertResult(child=InvertResult(
                    child=ComputedSubjectSet(relation="a"))),
            ])),
        ])]
        e = self._engine(ns, ["n:x#a@u1"])
        cases = ["n:x#dbl@u1", "n:x#dbl@u2"]
        got = e.check_batch([RelationTuple.from_string(c) for c in cases])
        for c, g in zip(cases, got):
            ref = e.reference.check_relation_tuple(RelationTuple.from_string(c), 0)
            assert g.membership == ref.membership, c
        assert e.stats["host_checks"] == 0

    def test_nested_islands_along_ttu_chain(self):
        """view = owner | ttu(parent, view); owner = granted & not(revoked):
        every folder hop spawns a nested island under the previous one."""
        from keto_tpu.namespace.ast import InvertResult, Operator

        ns = [Namespace(name="f", relations=[
            Relation(name="granted"),
            Relation(name="revoked"),
            Relation(name="parent"),
            Relation(name="owner", subject_set_rewrite=SubjectSetRewrite(
                operation=Operator.AND,
                children=[
                    ComputedSubjectSet(relation="granted"),
                    InvertResult(child=ComputedSubjectSet(relation="revoked")),
                ])),
            Relation(name="view", subject_set_rewrite=SubjectSetRewrite(children=[
                ComputedSubjectSet(relation="owner"),
                TupleToSubjectSet(relation="parent",
                                  computed_subject_set_relation="view"),
            ])),
        ])]
        tuples = [
            "f:root#granted@alice",
            "f:root#granted@bob",
            "f:root#revoked@bob",
            "f:mid#parent@(f:root#...)",
            "f:leaf#parent@(f:mid#...)",
            "f:leaf#granted@carol",
        ]
        e = self._engine(ns, tuples, max_depth=10)
        cases = [
            "f:leaf#view@alice",   # root grant propagates down
            "f:leaf#view@bob",     # revoked at root: denied everywhere
            "f:leaf#view@carol",   # direct grant on the leaf
            "f:mid#view@carol",    # carol has nothing above the leaf
            "f:root#owner@bob",    # AND + NOT island at the root itself
        ]
        got = e.check_batch([RelationTuple.from_string(c) for c in cases], 10)
        for c, g in zip(cases, got):
            ref = e.reference.check_relation_tuple(RelationTuple.from_string(c), 10)
            assert g.membership == ref.membership, c
        assert e.stats["host_checks"] == 0

    def test_depth_exhaustion_under_not_matches_reference(self):
        """not(deep-chain) where the chain exceeds max_depth: the
        reference collapses the exhausted branch to NotMember and the NOT
        flips it to ALLOWED — the device must reproduce exactly that
        (deliberate parity, however security-questionable)."""
        from keto_tpu.namespace.ast import InvertResult, Operator

        ns = [Namespace(name="d", relations=[
            Relation(name="deny"),
            Relation(name="link"),
            Relation(name="denied_deep", subject_set_rewrite=SubjectSetRewrite(
                children=[
                    ComputedSubjectSet(relation="deny"),
                    TupleToSubjectSet(relation="link",
                                      computed_subject_set_relation="denied_deep"),
                ])),
            Relation(name="ok", subject_set_rewrite=SubjectSetRewrite(children=[
                InvertResult(child=ComputedSubjectSet(relation="denied_deep")),
            ])),
        ])]
        chain = 6
        tuples = [f"d:n{i}#link@(d:n{i+1}#...)" for i in range(chain)]
        tuples.append(f"d:n{chain}#deny@mallory")
        for depth in (3, chain + 3):  # exhausted vs fully explored
            e = self._engine(ns, tuples, max_depth=depth)
            for sub in ("mallory", "alice"):
                q = RelationTuple.from_string(f"d:n0#ok@{sub}")
                g = e.check_batch([q], depth)[0]
                ref = e.reference.check_relation_tuple(q, depth)
                assert g.membership == ref.membership, (depth, sub)
            assert e.stats["host_checks"] == 0

    def test_randomized_differential_with_islands(self):
        """Random graphs whose relation rewrites include AND and NOT
        nodes (acyclic in relation space so the reference terminates)."""
        from keto_tpu.namespace.ast import InvertResult, Operator

        rng = random.Random(1234)
        n_objects, n_users = 24, 8
        rel_names = [f"r{i}" for i in range(6)]

        def random_rewrite(i):
            # children may only reference strictly higher relation ids
            higher = rel_names[i + 1 :]
            if not higher or rng.random() < 0.3:
                return None

            def leaf():
                r = rng.choice(higher)
                if rng.random() < 0.5:
                    return ComputedSubjectSet(relation=r)
                return TupleToSubjectSet(
                    relation=rng.choice(rel_names),
                    computed_subject_set_relation=r,
                )

            def node(budget):
                roll = rng.random()
                if budget <= 0 or roll < 0.45:
                    return leaf()
                if roll < 0.6:
                    return InvertResult(child=node(budget - 1))
                op = Operator.AND if rng.random() < 0.5 else Operator.OR
                return SubjectSetRewrite(
                    operation=op,
                    children=[node(budget - 1) for _ in range(rng.randrange(2, 4))],
                )

            rw = node(2)
            if not isinstance(rw, SubjectSetRewrite):
                rw = SubjectSetRewrite(children=[rw])
            return rw

        for trial in range(4):
            relations = [
                Relation(name=r, subject_set_rewrite=random_rewrite(i))
                for i, r in enumerate(rel_names)
            ]
            namespaces = [Namespace(name="rnd", relations=relations)]
            tuples = set()
            for _ in range(150):
                obj = f"o{rng.randrange(n_objects)}"
                rel = rng.choice(rel_names)
                if rng.random() < 0.4:
                    sub = f"(rnd:o{rng.randrange(n_objects)}#{rng.choice(rel_names)})"
                else:
                    sub = f"u{rng.randrange(n_users)}"
                tuples.add(f"rnd:{obj}#{rel}@{sub}")
            e = make_tpu_engine(namespaces, sorted(tuples), max_depth=10)
            queries = [
                RelationTuple.from_string(
                    f"rnd:o{rng.randrange(n_objects)}#"
                    f"{rng.choice(rel_names)}@u{rng.randrange(n_users)}"
                )
                for _ in range(64)
            ]
            got = e.check_batch(queries, 10)
            # cyclic random graphs: the reference's shared visited-set
            # makes pruned traversal order-dependent (the Go original is
            # racy there — goroutine scheduling decides); the kernel
            # implements the deterministic pruning-free semantics, so
            # that's the oracle (same choice as test_sharded)
            oracle = ReferenceEngine(e.manager, e.config, visited_pruning=False)
            for q, g in zip(queries, got):
                ref = oracle.check_relation_tuple(q, 10)
                assert g.membership == ref.membership, f"trial {trial}: {q}"


class TestHostFallbackCauses:
    """VERDICT r2 item 7: host fallback must be observable by cause —
    "host because AND/NOT overflow" distinguishable from "host because
    error" — via stats["host_cause"] and the labeled Prometheus counter."""

    def test_rewrite_cap_pinned(self):
        # a union rewrite with > rewrite_instr_cap children compiles to
        # FLAG_HOST_ONLY (snapshot.py _compile); its queries host-replay
        # with cause "rewrite_cap" and still return exact verdicts
        K = 8  # TPUCheckEngine default rewrite_instr_cap
        rels = [Relation(name=f"r{i}") for i in range(K + 1)]
        wide = Relation(
            name="wide",
            subject_set_rewrite=SubjectSetRewrite(
                children=[
                    ComputedSubjectSet(relation=f"r{i}") for i in range(K + 1)
                ]
            ),
        )
        ns = Namespace(name="w", relations=rels + [wide])
        e = make_tpu_engine([ns], [f"w:o#r{K}@alice"])  # hit via LAST branch
        got = e.check_batch(
            [
                RelationTuple.from_string("w:o#wide@alice"),
                RelationTuple.from_string("w:o#wide@bob"),
            ]
        )
        assert got[0].membership == Membership.IS_MEMBER
        assert got[1].membership == Membership.NOT_MEMBER
        assert e.stats["host_checks"] == 2
        assert e.stats["host_cause"] == {"rewrite_cap": 2}

    def test_relation_not_found_cause(self):
        e = make_tpu_engine(
            [Namespace(name="n", relations=[Relation(name="known")])],
            ["n:o#rogue@u"],
        )
        res = e.check_batch([RelationTuple.from_string("n:o#rogue@v")])[0]
        assert res.error is not None
        assert e.stats["host_cause"] == {"relation_not_found": 1}

    def test_unindexed_cause(self):
        e = make_tpu_engine([Namespace(name="n")], ["n:o#r@u"])
        e.check_batch([RelationTuple.from_string("ghost:o#r@u")])
        assert e.stats["host_cause"] == {"unindexed": 1}

    def test_island_overflow_cause(self):
        # one query fanning out (via TTU) to more AND/NOT islands than
        # island_cap = 2*B can hold: exact verdict via host replay,
        # cause "island_overflow" — the capacity cliff the cause split
        # exists to expose
        from keto_tpu.namespace.ast import InvertResult, Operator

        n_docs = 40  # > island_cap (2 * bucket16 = 32)
        ns = Namespace(
            name="acl",
            relations=[
                Relation(name="allow"),
                Relation(name="deny"),
                Relation(name="parent"),
                Relation(
                    name="access",
                    subject_set_rewrite=SubjectSetRewrite(
                        operation=Operator.AND,
                        children=[
                            ComputedSubjectSet(relation="allow"),
                            InvertResult(
                                child=ComputedSubjectSet(relation="deny")
                            ),
                        ],
                    ),
                ),
                Relation(
                    name="super",
                    subject_set_rewrite=SubjectSetRewrite(
                        children=[
                            TupleToSubjectSet(
                                relation="parent",
                                computed_subject_set_relation="access",
                            )
                        ]
                    ),
                ),
            ],
        )
        tuples = [f"acl:root#parent@(acl:doc{i}#...)" for i in range(n_docs)]
        tuples.append(f"acl:doc{n_docs - 1}#allow@alice")
        e = make_tpu_engine([ns], tuples)
        res = e.check_batch([RelationTuple.from_string("acl:root#super@alice")])
        assert res[0].membership == Membership.IS_MEMBER
        assert e.stats["host_cause"] == {"island_overflow": 1}

    def test_prometheus_counter_labels(self):
        from keto_tpu.observability import Metrics

        K = 8
        rels = [Relation(name=f"r{i}") for i in range(K + 1)]
        wide = Relation(
            name="wide",
            subject_set_rewrite=SubjectSetRewrite(
                children=[
                    ComputedSubjectSet(relation=f"r{i}") for i in range(K + 1)
                ]
            ),
        )
        cfg = Config({"limit": {"max_read_depth": 5}})
        cfg.set_namespaces([Namespace(name="w", relations=rels + [wide])])
        m = MemoryManager()
        m.write_relation_tuples([RelationTuple.from_string("w:o#r0@u")])
        metrics = Metrics()
        e = TPUCheckEngine(m, cfg, metrics=metrics)
        e.check_batch([RelationTuple.from_string("w:o#wide@u")] * 2)
        text = metrics.export().decode()
        assert 'keto_tpu_host_fallback_total{cause="rewrite_cap"} 2.0' in text


class TestBoundedLoop:
    """kernel.bounded_loop is the one loop of every BFS kernel, a counted
    fori_loop whose body is a cond, and kernel.covering_segments the one
    scan-form segment map: each is held to the plain construct it stands
    for, and the check and expand kernels to engine/reference.py across
    an early exit."""

    @pytest.mark.parametrize(
        "start,stop,max_steps",
        [(0, 5, 8), (0, 8, 8), (0, 20, 8), (3, 3, 8), (0, 5, 0)],
        ids=["early-exit", "exact", "capped", "never-true", "no-steps"],
    )
    def test_is_a_capped_while_loop(self, start, stop, max_steps):
        import jax
        import jax.numpy as jnp

        from keto_tpu.engine.kernel import bounded_loop

        def cond_fn(st):
            return st[0] < stop

        def step_fn(st):
            return st[0] + 1, st[1] * 2 + st[0]

        want, steps = (start, 1), 0
        while steps < max_steps and want[0] < stop:
            want, steps = (want[0] + 1, want[1] * 2 + want[0]), steps + 1
        got = jax.jit(
            lambda i, acc: bounded_loop(cond_fn, step_fn, (i, acc), max_steps)
        )(jnp.int32(start), jnp.int32(1))
        assert (int(got[0]), int(got[1])) == want

    @pytest.mark.parametrize("seed", range(4))
    def test_covering_segments_is_the_binary_search(self, seed):
        """Empty segments, a work list cut short at F and one that ends
        before F: slot j's segment is the one searchsorted names."""
        import numpy as np

        from keto_tpu.engine.kernel import covering_segments

        rng = np.random.default_rng(seed)
        F, n_seg = 64, 48
        counts = rng.integers(0, 4 + 2 * seed, n_seg).astype(np.int32)
        counts[rng.random(n_seg) < 0.4] = 0
        offsets = (np.cumsum(counts) - counts).astype(np.int32)
        seg, j = covering_segments(offsets, counts, F)
        assert list(np.asarray(j)) == list(range(F))
        live = np.arange(F) < counts.sum()
        want = np.searchsorted(offsets, np.arange(F), side="right") - 1
        assert np.asarray(seg)[live].tolist() == want[live].tolist()
        assert ((np.asarray(seg) >= 0) & (np.asarray(seg) < n_seg)).all()

    def test_early_exit_matches_reference(self):
        """A batch that resolves in ~2 of its budgeted steps: the trips
        the cond passes through must not perturb the verdicts."""
        ns = [Namespace(name="n", relations=[Relation(name="r")])]
        tuples = [f"n:o{i}#r@u{i}" for i in range(64)]
        queries = [
            RelationTuple.from_string(f"n:o{i}#r@u{i % 3}") for i in range(64)
        ]
        e = make_tpu_engine(ns, tuples)
        got = [r.allowed for r in e.check_batch(queries)]
        want = [
            e.reference.check_relation_tuple(q, 0).membership
            == Membership.IS_MEMBER
            for q in queries
        ]
        assert got == want and sum(want) == 3
        assert e.stats["host_checks"] == 0

    def test_expand_kernel_matches_reference(self):
        """The expand kernel shares bounded_loop: a tree that is complete
        after two of its four budgeted steps."""
        ns = [Namespace(name="n", relations=[
            Relation(name="r"), Relation(name="g"),
        ])]
        tuples = (
            [f"n:o#r@(n:m{i}#g)" for i in range(4)]
            + [f"n:m{i}#g@u{j}" for i in range(4) for j in range(3)]
        )
        e = make_tpu_engine(ns, tuples)
        sub = SubjectSet("n", "o", "r")
        device = e.expand_batch([sub], 4)[0]
        assert str(device) == str(e.reference.expand(sub, 4))
        assert e.stats["host_expands"] == 0
