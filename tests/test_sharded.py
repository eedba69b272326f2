"""Multi-chip differential tests: the shard_map SPMD kernel on a virtual
8-device CPU mesh (conftest.py) against the single-chip kernel and the
exact host reference engine."""

import random

import numpy as np
import pytest

from keto_tpu.config import Config
from keto_tpu.engine import Membership
from keto_tpu.engine.kernel import unpack_results
from keto_tpu.engine.tpu_engine import TPUCheckEngine
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.namespace.ast import (
    ComputedSubjectSet,
    Relation,
    SubjectSetRewrite,
    TupleToSubjectSet,
)
from keto_tpu.parallel import build_sharded_snapshot, default_mesh
from keto_tpu.storage import MemoryManager

from test_reference_engine import (
    REWRITE_CASES,
    REWRITE_NAMESPACES,
    REWRITE_TUPLES,
)


def make_mesh_engine(namespaces, tuples, max_depth=5, n_devices=8):
    cfg = Config({"limit": {"max_read_depth": max_depth}})
    cfg.set_namespaces(namespaces)
    m = MemoryManager()
    m.write_relation_tuples([RelationTuple.from_string(s) for s in tuples])
    return TPUCheckEngine(m, cfg, mesh=default_mesh(n_devices))


def drive_smoke_case():
    """chip_smoke's drive store at 20,000 tuples and its 2,048-item batch."""
    import chip_smoke
    from keto_tpu.storage.columnar import ColumnarStore

    drive = chip_smoke.build_drive(chip_smoke.DEFAULT_SEED, 20_000)
    store = ColumnarStore()
    store.bulk_load(drive.cols)
    queries, _ = chip_smoke.view_queries(drive, np.random.default_rng(1), 2048)
    return store, chip_smoke.drive_config(serve=False), queries, 0


def rewrite_case():
    """test_islands_on_mesh_no_host_replay's store and queries: AND/NOT
    islands allocated on the device."""
    cfg = Config({"limit": {"max_read_depth": 100}})
    cfg.set_namespaces(REWRITE_NAMESPACES)
    m = MemoryManager()
    m.write_relation_tuples([RelationTuple.from_string(s) for s in REWRITE_TUPLES])
    return m, cfg, [RelationTuple.from_string(q) for q, _ in REWRITE_CASES], 100


@pytest.mark.parametrize(
    "case, islands",
    [(drive_smoke_case, False), (rewrite_case, True)],
    ids=["drive-smoke", "rewrite-islands"],
)
def test_packed_sharded_program_equals_packed_one_device_program(case, islands):
    """The sharded check program's one result vector and
    check_kernel_packed's, at the same statics (F 8,192), read back the
    same n_isl, ctx_hit[:B] and needs_host, slot for slot."""
    manager, cfg, queries, depth = case()
    read = {}
    for mesh in (None, default_mesh(4)):
        engine = TPUCheckEngine(
            manager, cfg, mesh=mesh, frontier_cap=8192, auto_frontier=False
        )
        handle = engine.check_batch_submit(queries, depth)
        _, outputs, meta = handle
        ctx_hit, needs_host, _, _, n_isl, _ = unpack_results(
            np.asarray(outputs), meta["B"], meta["island_cap"],
            meta["state"].snapshot.K,
        )
        engine.check_batch_resolve(handle)
        read[mesh is None] = (
            (meta["B"], meta["island_cap"], meta["launch_cap"], meta["step_cap"]),
            n_isl, ctx_hit[: meta["B"]], needs_host,
        )
    (statics, n_isl, ctx_hit, needs_host) = read[True]
    assert statics[2] == 8192
    assert read[False][:2] == (statics, n_isl)
    assert (n_isl > 0) == islands
    np.testing.assert_array_equal(read[False][2], ctx_hit)
    np.testing.assert_array_equal(read[False][3], needs_host)


class TestShardedSnapshot:
    def test_shards_partition_edges(self):
        tuples = [
            RelationTuple.from_string(f"n:o{i}#r@u{i % 7}") for i in range(300)
        ] + [
            RelationTuple.from_string(f"n:o{i}#r@(n:o{(i + 1) % 50}#r)")
            for i in range(50)
        ]
        snap = build_sharded_snapshot(tuples, [Namespace(name="n")], n_shards=8)
        assert snap.sharded["dh_obj"].shape[0] == 8
        # every direct edge is in exactly one shard
        total = sum(
            int((snap.sharded["dh_val"][s] != -1).sum()) for s in range(8)
        )
        assert total == 350
        # all shards share one capacity (stacked) and the probe max
        assert snap.sharded["dh_obj"].ndim == 2
        assert snap.dh_probes >= 1

    def test_csr_rows_padded_consistently(self):
        tuples = [
            RelationTuple.from_string(f"n:o{i}#r@(n:q{j}#r)")
            for i in range(20)
            for j in range(i % 5 + 1)
        ]
        snap = build_sharded_snapshot(tuples, [Namespace(name="n")], n_shards=4)
        rp = snap.sharded["row_ptr"]
        assert rp.shape[0] == 4
        for s in range(4):
            # row_ptr monotone; padded tail repeats the terminal offset
            assert (np.diff(rp[s]) >= 0).all()


class TestShardedDifferential:
    @pytest.fixture(scope="class")
    def rewrite_engine(self):
        return make_mesh_engine(REWRITE_NAMESPACES, REWRITE_TUPLES, max_depth=100)

    @pytest.mark.parametrize("query,expected", REWRITE_CASES)
    def test_rewrite_fixtures(self, rewrite_engine, query, expected):
        res = rewrite_engine.check_batch([RelationTuple.from_string(query)], 100)[0]
        assert res.error is None
        assert (res.membership == Membership.IS_MEMBER) == expected, query

    def test_deep_chain_crosses_shards(self):
        # parent chains hash objects onto different shards: every hop is
        # a cross-shard all-gather merge
        namespaces = [
            Namespace(
                name="deep",
                relations=[
                    Relation(name="owner"),
                    Relation(name="parent"),
                    Relation(
                        name="viewer",
                        subject_set_rewrite=SubjectSetRewrite(
                            children=[
                                ComputedSubjectSet(relation="owner"),
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
        depth = 16
        tuples = ["deep:f0#parent@(deep:f1#...)"]
        for i in range(1, depth):
            tuples.append(f"deep:f{i}#parent@(deep:f{i + 1}#...)")
        tuples.append(f"deep:f{depth}#owner@alice")
        e = make_mesh_engine(namespaces, tuples, max_depth=64)
        q = RelationTuple.from_string("deep:f0#viewer@alice")
        res = e.check_batch([q], 64)[0]
        assert res.membership == Membership.IS_MEMBER
        assert e.stats["host_checks"] == 0
        miss = RelationTuple.from_string("deep:f0#viewer@bob")
        assert e.check_batch([miss], 64)[0].membership == Membership.NOT_MEMBER

    def test_randomized_differential_vs_reference(self):
        rng = random.Random(7)
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
        relations = ["r0", "r1", "r2"]
        for trial in range(3):
            tuples = set()
            for _ in range(150):
                obj = f"o{rng.randrange(40)}"
                rel = rng.choice(relations)
                if rng.random() < 0.45:
                    sub = f"(rnd:o{rng.randrange(40)}#{rng.choice(relations)})"
                else:
                    sub = f"u{rng.randrange(12)}"
                tuples.add(f"rnd:{obj}#{rel}@{sub}")
            e = make_mesh_engine(namespaces, sorted(tuples), max_depth=12)
            queries = [
                RelationTuple.from_string(
                    f"rnd:o{rng.randrange(40)}#{rng.choice(relations)}"
                    f"@u{rng.randrange(12)}"
                )
                for _ in range(64)
            ]
            got = e.check_batch(queries, 12)
            # cyclic random graphs: the reference's visited-set pruning can
            # miss members the kernel finds; the no-pruning oracle is the
            # exact fixpoint both kernels must match (engine/reference.py)
            from keto_tpu.engine import ReferenceEngine

            oracle = ReferenceEngine(e.manager, e.config, visited_pruning=False)
            for q, g in zip(queries, got):
                ref = oracle.check_relation_tuple(q, 12)
                assert g.membership == ref.membership, f"trial {trial}: {q}"

    def test_islands_on_mesh_no_host_replay(self):
        """AND/NOT islands under shard_map: island allocation is derived
        from replicated tables so every shard builds the identical island
        state; the whole REWRITE_CASES set answers on-device (the one
        unknown-object query is the documented exact-host path)."""
        e = make_mesh_engine(REWRITE_NAMESPACES, REWRITE_TUPLES, max_depth=100)
        rts = [RelationTuple.from_string(q) for q, _ in REWRITE_CASES]
        got = e.check_batch(rts, 100)
        for (q, expected), g in zip(REWRITE_CASES, got):
            assert (g.membership == Membership.IS_MEMBER) == expected, q
        assert e.stats["host_checks"] == 1  # doc:another_doc (unknown vocab)

    def test_expand_sharded_differential(self):
        """Expand under a mesh uses the SHARDED full CSR (VERDICT round-1
        item 6: previously one device held everything). Trees must match
        the host reference exactly, with zero host replays for clean
        queries."""
        from keto_tpu.ketoapi import SubjectSet

        namespaces = [Namespace(name="g")]
        tuples = [f"g:root#member@u{i}" for i in range(9)]
        tuples += [f"g:root#member@(g:team{i}#member)" for i in range(4)]
        for i in range(4):
            tuples += [f"g:team{i}#member@t{i}_{j}" for j in range(3)]
        # a deeper chain crossing shards
        tuples += [
            "g:deep#member@(g:mid#member)",
            "g:mid#member@(g:leafgrp#member)",
            "g:leafgrp#member@bottom",
        ]
        e = make_mesh_engine(namespaces, tuples, max_depth=10)
        subs = [
            SubjectSet("g", "root", "member"),
            SubjectSet("g", "deep", "member"),
            SubjectSet("g", "team2", "member"),
            SubjectSet("g", "nothing", "member"),  # nil tree
        ]
        for d in (2, 3, 6):
            got = e.expand_batch(subs, d)
            for sub, tree in zip(subs, got):
                want = e.reference.expand(sub, d)
                if want is None:
                    assert tree is None, (sub, d)
                else:
                    assert tree is not None, (sub, d)
                    assert tree.to_dict() == want.to_dict(), (sub, d)

    def test_expand_sharded_read_your_writes(self):
        from keto_tpu.ketoapi import SubjectSet

        e = make_mesh_engine([Namespace(name="g")], ["g:r#m@a"], max_depth=6)
        sub = SubjectSet("g", "r", "m")
        t1 = e.expand_batch([sub], 3)[0]
        assert {c.tuple.subject_id for c in t1.children} == {"a"}
        e.manager.write_relation_tuples(
            [RelationTuple.from_string("g:r#m@b")]
        )
        t2 = e.expand_batch([sub], 3)[0]
        want = e.reference.expand(sub, 3)
        assert t2.to_dict() == want.to_dict()
        assert {c.tuple.subject_id for c in t2.children} == {"a", "b"}

    def test_read_your_writes_on_mesh(self):
        cfg = Config({"limit": {"max_read_depth": 5}})
        cfg.set_namespaces([Namespace(name="n")])
        m = MemoryManager()
        e = TPUCheckEngine(m, cfg, mesh=default_mesh(8))
        q = RelationTuple.from_string("n:o#r@u")
        assert e.check_batch([q])[0].membership == Membership.NOT_MEMBER
        m.write_relation_tuples([q])
        assert e.check_batch([q])[0].membership == Membership.IS_MEMBER


class TestShardedColumnar:
    """Columnar store + device mesh (round-3 VERDICT item 3): the
    vectorized columnar ingest must feed the sharded snapshot — these
    two were mutually exclusive in round 2 (the engine silently fell
    back to per-tuple ingest under a mesh)."""

    def test_columnar_store_builds_sharded_snapshot(self):
        from keto_tpu.storage.columnar import ColumnarStore
        from keto_tpu.storage.columns import TupleColumns

        cfg = Config({"limit": {"max_read_depth": 100}})
        cfg.set_namespaces(REWRITE_NAMESPACES)
        store = ColumnarStore()
        store.bulk_load(TupleColumns.from_tuples(
            [RelationTuple.from_string(s) for s in REWRITE_TUPLES]
        ))
        e = TPUCheckEngine(store, cfg, mesh=default_mesh(8))
        rts = [RelationTuple.from_string(q) for q, _ in REWRITE_CASES]
        got = e.check_batch(rts, 100)
        for (q, expected), g in zip(REWRITE_CASES, got):
            assert g.error is None, q
            assert (g.membership == Membership.IS_MEMBER) == expected, q
        # the mesh path must have built a SHARDED snapshot from columns
        state = e._state
        assert state.sharded is not None
        assert state.sharded.n_shards == 8
        # only the one unknown-object query replays on host
        assert e.stats["host_checks"] == 1

    def test_columnar_mesh_randomized_differential(self):
        from keto_tpu.storage.columnar import ColumnarStore
        from keto_tpu.storage.columns import TupleColumns

        rng = random.Random(99)
        ns = [Namespace(name="g", relations=[
            Relation(name="r0"),
            Relation(name="r1"),
            Relation(name="r2", subject_set_rewrite=SubjectSetRewrite(children=[
                ComputedSubjectSet(relation="r0"),
                TupleToSubjectSet(relation="r1",
                                  computed_subject_set_relation="r2"),
            ])),
        ])]
        tup = set()
        for _ in range(600):
            obj = f"o{rng.randrange(80)}"
            rel = rng.choice(["r0", "r1", "r2"])
            if rng.random() < 0.4:
                sub = f"(g:o{rng.randrange(80)}#{rng.choice(['r0', 'r1', 'r2'])})"
            else:
                sub = f"u{rng.randrange(16)}"
            tup.add(f"g:{obj}#{rel}@{sub}")
        cfg = Config({"limit": {"max_read_depth": 8}})
        cfg.set_namespaces(ns)
        store = ColumnarStore()
        store.bulk_load(TupleColumns.from_tuples(
            [RelationTuple.from_string(s) for s in sorted(tup)]
        ))
        e = TPUCheckEngine(store, cfg, mesh=default_mesh(8))
        queries = [RelationTuple.from_string(
            f"g:o{rng.randrange(80)}#{rng.choice(['r0', 'r1', 'r2'])}"
            f"@u{rng.randrange(16)}"
        ) for _ in range(64)]
        got = e.check_batch(queries, 8)
        for q, g in zip(queries, got):
            want = e.reference.check_relation_tuple(q, 8)
            assert g.membership == want.membership, q.to_string()

    def test_columnar_mesh_expand_differential(self):
        """The expand state under columnar+mesh builds through the
        vectorized sharded CSR (no per-tuple Python) and must produce
        the exact host trees."""
        from keto_tpu.ketoapi import SubjectSet
        from keto_tpu.storage.columnar import ColumnarStore
        from keto_tpu.storage.columns import TupleColumns

        rng = random.Random(41)
        tuples = []
        for r in range(16):
            for _ in range(3):
                tuples.append(RelationTuple.from_string(
                    f"role:r{r}#member@u{rng.randrange(10)}"
                ))
            if r:
                tuples.append(RelationTuple.from_string(
                    f"role:r{r}#member@(role:r{rng.randrange(r)}#member)"
                ))
        cfg = Config({"limit": {"max_read_depth": 5}})
        cfg.set_namespaces([Namespace(name="role")])
        store = ColumnarStore()
        store.bulk_load(TupleColumns.from_tuples(tuples))
        e = TPUCheckEngine(store, cfg, mesh=default_mesh(8))
        subs = [SubjectSet("role", f"r{i}", "member") for i in range(16)]
        trees = e.expand_batch(subs, 5)
        for s, t in zip(subs, trees):
            want = e.reference.expand(s, 5)
            got = t.to_dict() if t is not None else None
            assert got == (want.to_dict() if want is not None else None), s

    def test_columnar_mesh_read_your_writes(self):
        """Writes after a columnar bulk load under a mesh ride the
        replicated delta overlay, not a rebuild."""
        from keto_tpu.storage.columnar import ColumnarStore
        from keto_tpu.storage.columns import TupleColumns

        cfg = Config({"limit": {"max_read_depth": 5}})
        cfg.set_namespaces([Namespace(name="n")])
        store = ColumnarStore()
        store.bulk_load(TupleColumns.from_tuples(
            [RelationTuple.from_string("n:o#r@u")]
        ))
        e = TPUCheckEngine(store, cfg, mesh=default_mesh(8))
        q0 = RelationTuple.from_string("n:o#r@u")
        assert e.check_batch([q0])[0].membership == Membership.IS_MEMBER
        builds_before = e.stats["snapshot_builds"]
        q = RelationTuple.from_string("n:o2#r@u")
        store.write_relation_tuples([q])
        assert e.check_batch([q])[0].membership == Membership.IS_MEMBER
        assert e.stats["snapshot_builds"] == builds_before


class TestMeshCapacityBoundaries:
    """VERDICT r2 item 8: pin behavior near the dedupe index-bit limit
    (kernel.py dedupe_phase) and prove the sharding is correct past the
    8-device mesh the rest of the suite uses."""

    def test_dedupe_at_28_bit_boundary_traces(self):
        # G = 2^28 candidates (e.g. 16 shards x 16M frontier) needs
        # exactly 28 index bits: the largest legal configuration. Traced
        # via eval_shape so no memory is allocated.
        import functools

        import jax
        import jax.numpy as jnp

        from keto_tpu.engine.kernel import Expansion, dedupe_phase

        G = 1 << 28
        cand = Expansion(
            q=jax.ShapeDtypeStruct((G,), jnp.int32),
            ctx=jax.ShapeDtypeStruct((G,), jnp.int32),
            obj=jax.ShapeDtypeStruct((G,), jnp.int32),
            rel=jax.ShapeDtypeStruct((G,), jnp.int32),
            depth=jax.ShapeDtypeStruct((G,), jnp.int32),
            valid=jax.ShapeDtypeStruct((G,), jnp.bool_),
        )
        out = jax.eval_shape(
            functools.partial(dedupe_phase, F=1 << 14, n_queries=4096), cand
        )
        assert out[0].shape == (1 << 14,)

    def test_dedupe_past_28_bits_fails_loud(self):
        # one bit past the limit must raise (silent priority truncation
        # would corrupt dedupe winners), naming the remedy
        import functools

        import jax
        import jax.numpy as jnp
        import pytest as _pytest

        from keto_tpu.engine.kernel import Expansion, dedupe_phase

        G = 1 << 29
        cand = Expansion(
            q=jax.ShapeDtypeStruct((G,), jnp.int32),
            ctx=jax.ShapeDtypeStruct((G,), jnp.int32),
            obj=jax.ShapeDtypeStruct((G,), jnp.int32),
            rel=jax.ShapeDtypeStruct((G,), jnp.int32),
            depth=jax.ShapeDtypeStruct((G,), jnp.int32),
            valid=jax.ShapeDtypeStruct((G,), jnp.bool_),
        )
        with _pytest.raises(ValueError, match="frontier_cap"):
            jax.eval_shape(
                functools.partial(dedupe_phase, F=1 << 14, n_queries=4096),
                cand,
            )

    def test_16_shard_differential_subprocess(self):
        # the suite's mesh is 8 virtual devices (conftest); a 16-shard
        # run needs its own backend, so it executes in a subprocess with
        # xla_force_host_platform_device_count=16
        import json
        import os
        import subprocess
        import sys

        script = r"""
import json, os, random, sys
sys.path.insert(0, os.environ["KETO_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")
from keto_tpu.config import Config
from keto_tpu.engine import Membership
from keto_tpu.engine.kernel import unpack_results
from keto_tpu.engine.tpu_engine import TPUCheckEngine
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.namespace.ast import (
    ComputedSubjectSet, Relation, SubjectSetRewrite, TupleToSubjectSet,
)
from keto_tpu.parallel import default_mesh
from keto_tpu.storage import MemoryManager

assert len(jax.devices()) == 16, jax.devices()
rng = random.Random(77)
ns = [Namespace(name="g", relations=[
    Relation(name="r0"),
    Relation(name="r1"),
    Relation(name="r2", subject_set_rewrite=SubjectSetRewrite(children=[
        ComputedSubjectSet(relation="r0"),
        TupleToSubjectSet(relation="r1", computed_subject_set_relation="r2"),
    ])),
])]
tup = set()
for _ in range(400):
    obj = f"o{rng.randrange(60)}"
    rel = rng.choice(["r0", "r1", "r2"])
    if rng.random() < 0.4:
        sub = f"(g:o{rng.randrange(60)}#{rng.choice(['r0','r1','r2'])})"
    else:
        sub = f"u{rng.randrange(12)}"
    tup.add(f"g:{obj}#{rel}@{sub}")
cfg = Config({"limit": {"max_read_depth": 8}})
cfg.set_namespaces(ns)
m = MemoryManager()
m.write_relation_tuples([RelationTuple.from_string(s) for s in sorted(tup)])
e = TPUCheckEngine(m, cfg, mesh=default_mesh(16))
queries = [RelationTuple.from_string(
    f"g:o{rng.randrange(60)}#{rng.choice(['r0','r1','r2'])}@u{rng.randrange(12)}"
) for _ in range(64)]
got = e.check_batch(queries, 8)
mismatch = sum(
    1 for q, g in zip(queries, got)
    if g.membership != e.reference.check_relation_tuple(q, 8).membership
)
print(json.dumps({
    "devices": len(jax.devices()), "mismatches": mismatch,
    "host_checks": e.stats["host_checks"],
}))
"""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        env["KETO_REPO"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=600, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["devices"] == 16
        assert rec["mismatches"] == 0
