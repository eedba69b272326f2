"""benchmarks/generators/rewrites.py: Keto's rewrite namespaces at a size.

Its counts are fixed by the size alone, for every seed, so no seed
compiles another program; the columns hold what the construction says;
and every denied `delete` or `access` item is denied by its circuit's last
leaf: flipped in a store of its own, that leaf turns the answer.
"""

import os
import sys

import numpy as np
import pytest

from keto_tpu.config import Config
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.registry import Registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEEDS = [0, 11, 3000003951, 2**31 + 5]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import workload
        from generators import rewrites

        yield workload, rewrites
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def config(bench):
    workload, _ = bench
    return workload.read_json(os.path.join(BENCH, "configs", "rewrites-4e6.json"))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_counts_do_not_depend_on_the_seed(bench, config, seed):
    _, rewrites = bench
    truth = rewrites.Truth(config["generator_params"], seed, config["tuples"])
    assert (truth.n_users, truth.n_groups, truth.n_objects, truth.n_filler) == (
        40_000, 10_000, 480_000, 0
    )
    assert truth.n_targets == 5 * 480_000
    assert truth.members.shape == (10_000, 12)
    assert truth.allow.shape == (480_000, 4)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_columns_hold_the_construction(bench, config, seed):
    _, rewrites = bench
    tuples = 20_003  # leaves 3 co-owners to make the count exact
    truth = rewrites.Truth(config["generator_params"], seed, tuples)
    cols = rewrites.columns(truth)
    assert len(cols) == tuples and truth.n_filler == 3

    def rows(ns, rel):
        return (cols.ns == ns) & (cols.rel == rel)

    # every user in exactly one of the 4 levels, the levels as even as can be
    level = rows("level", "member")
    assert sorted(cols.sobj[level]) == sorted(f"u{u}" for u in range(truth.n_users))
    assert set(np.unique(cols.obj[level], return_counts=True)[1]) == {truth.n_users // 4}
    # groups of 12 distinct users, 3 from each level
    group = rows("group", "member")
    assert group.sum() == 12 * truth.n_groups
    for g in range(truth.n_groups):
        members = truth.members[g]
        assert len(set(members)) == 12
        assert np.bincount(truth.level_of[members], minlength=4).tolist() == [3] * 4
    # a resource: an owner group, one direct owner (a co-owner on the first
    # three), a level; a document: 4 distinct allowed users, one denied
    owner = rows("resource", "owner")
    assert (cols.skind[owner] == 1).sum() == truth.n_objects
    assert (cols.skind[owner] == 0).sum() == truth.n_objects + 3
    assert rows("resource", "level").sum() == truth.n_objects
    assert rows("acl", "allow").sum() == 4 * truth.n_objects
    assert all(len(set(a)) == 4 for a in truth.allow)
    deny = rows("acl", "deny")
    assert cols.sobj[deny].tolist() == [
        f"u{truth.allow[i, truth.deny[i]]}" for i in range(truth.n_objects)
    ]
    assert not (cols.ns == "doc").any()


@pytest.fixture(scope="module")
def served(bench, config):
    """A store of the configuration at 20,000 tuples, its engine and one of
    the cell's batches; the store is this test's own, to be written."""
    workload, _ = bench
    traffic = workload.read_json(os.path.join(BENCH, "traffic", "batch_checks.json"))
    wl = workload.Workload(config, traffic, 3000003951, 20_000)
    registry = Registry(Config(config["serve_config"]))
    registry.relation_tuple_manager().bulk_load(wl.columns())
    queries, allowed, _ = wl.request(0)
    return registry, wl.truth, queries, allowed


def last_leaf(truth, query) -> str:
    """The tuple that decides the circuit's last leaf of a denied item:
    the level membership `delete` lacks, the deny that `access` has."""
    i = int(query.object[1:])
    if query.relation == "delete":
        return f"level:l{truth.level[i]}#member@{query.subject_id}"
    return f"acl:d{i}#deny@{query.subject_id}"


@pytest.mark.parametrize("relation", ["delete", "access"])
def test_a_denied_item_is_denied_by_its_last_leaf(served, relation):
    registry, truth, queries, allowed = served
    manager = registry.relation_tuple_manager()
    reference = registry.check_engine().reference
    denied = [q for q, a in zip(queries, allowed) if not a and q.relation == relation]
    assert len(denied) > 100
    for query in denied[:12]:
        assert not reference.check_relation_tuple(query, 0).allowed
        leaf = RelationTuple.from_string(last_leaf(truth, query))
        flip, undo = (
            (manager.write_relation_tuples, manager.delete_relation_tuples)
            if relation == "delete"
            else (manager.delete_relation_tuples, manager.write_relation_tuples)
        )
        flip([leaf])
        try:
            assert reference.check_relation_tuple(query, 0).allowed, str(query)
        finally:
            undo([leaf])
        assert not reference.check_relation_tuple(query, 0).allowed


def test_the_mix_follows_the_targets(served):
    _, _, queries, allowed = served
    share = {
        rel: sum(q.relation == rel for q in queries) / len(queries)
        for rel in ("delete", "access", "read")
    }
    assert share["delete"] == pytest.approx(0.4, abs=0.05)
    assert share["access"] == pytest.approx(0.4, abs=0.05)
    assert share["read"] == pytest.approx(0.2, abs=0.05)
    assert 0.4 < allowed.mean() < 0.6
