"""Differential correctness tier for the attached TPU.

Runs the differential fixture sets on the chip: the same code paths the
CPU suite exercises, now with the TPU compiler's and the device's
semantics. The sets are cat-videos, deep-chain-32 (through the BFS kernel
and again through the device-built closure index), the AND/NOT island
fixtures, a randomized differential sweep and an expand differential,
each compared against the exact host reference engine.

`chip_smoke.py` runs `main()` as its phase 5, in the process that holds
the chip. Standalone, on a machine with a chip:

    python tools/tpu_test_tier.py

Prints one JSON line per fixture set plus a final summary line
{"tier": "tpu", "device", "sets", "cases", "failures"}; exit 0 iff
failures == 0. Anything but a TPU is refused before any work: only
chip_smoke.py's CPU rehearsal calls `main(require_tpu=False)`.
"""

from __future__ import annotations

import json
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"
))


# the upstream cat-videos example (contrib/cat-videos-example)
CAT_VIDEOS_TUPLES = [
    "videos:/cats/1.mp4#owner@(videos:/cats#owner)",
    "videos:/cats/1.mp4#view@(videos:/cats/1.mp4#owner)",
    "videos:/cats/1.mp4#view@*",
    "videos:/cats/2.mp4#owner@(videos:/cats#owner)",
    "videos:/cats/2.mp4#view@(videos:/cats/2.mp4#owner)",
    "videos:/cats#owner@cat lady",
    "videos:/cats#view@(videos:/cats#owner)",
]


def engine_for(namespaces, tuples, max_depth=5, **planes):
    """A TPUCheckEngine over a fresh in-memory store holding `tuples`;
    `planes` are further config sections (closure=...)."""
    from keto_tpu.config import Config
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.ketoapi import RelationTuple
    from keto_tpu.storage import MemoryManager

    cfg = Config({"limit": {"max_read_depth": max_depth}, **planes})
    cfg.set_namespaces(namespaces)
    m = MemoryManager()
    m.write_relation_tuples([RelationTuple.from_string(s) for s in tuples])
    return TPUCheckEngine(m, cfg)


def deep_chain(depth: int):
    """(namespaces, tuples, cases) of one parent chain of `depth` hops with
    an owner at its end (the bench_test.go:56-86 topology)."""
    from keto_tpu.namespace import Namespace
    from keto_tpu.namespace.ast import (
        ComputedSubjectSet,
        Relation,
        SubjectSetRewrite,
        TupleToSubjectSet,
    )

    namespaces = [Namespace(name="deep", relations=[
        Relation(name="owner"),
        Relation(name="parent"),
        Relation(name="viewer", subject_set_rewrite=SubjectSetRewrite(children=[
            ComputedSubjectSet(relation="owner"),
            TupleToSubjectSet(relation="parent",
                              computed_subject_set_relation="viewer"),
        ])),
    ])]
    tuples = [
        f"deep:f{i}#parent@(deep:f{i + 1}#...)" for i in range(depth)
    ] + [f"deep:f{depth}#owner@alice"]
    cases = [
        ("deep:f0#viewer@alice", True),
        ("deep:f0#viewer@bob", False),
        (f"deep:f{depth}#owner@alice", True),
    ]
    return namespaces, tuples, cases


def main(require_tpu: bool = True) -> int:
    import jax

    device = jax.devices()[0]
    if require_tpu and device.platform != "tpu":
        print(json.dumps({
            "tier": "tpu", "error": f"no TPU (found {device.platform})",
        }))
        return 2

    from keto_tpu.engine import Membership
    from keto_tpu.ketoapi import RelationTuple
    from keto_tpu.namespace import Namespace
    from keto_tpu.namespace.ast import (
        ComputedSubjectSet,
        Relation,
        SubjectSetRewrite,
        TupleToSubjectSet,
    )

    total_cases = 0
    total_failures = 0
    sets = 0

    def report(name, cases, failures, extra=None):
        nonlocal total_cases, total_failures, sets
        sets += 1
        total_cases += cases
        total_failures += failures
        line = {"set": name, "cases": cases, "failures": failures}
        line.update(extra or {})
        print(json.dumps(line), flush=True)

    # ---- cat-videos (the reference's own example fixture) ----------------
    e = engine_for([Namespace(name="videos")], CAT_VIDEOS_TUPLES)
    queries = [
        "videos:/cats/1.mp4#view@*",
        "videos:/cats/1.mp4#view@cat lady",
        "videos:/cats/2.mp4#view@cat lady",
        "videos:/cats/2.mp4#view@john",
        "videos:/cats#owner@cat lady",
    ]
    rts = [RelationTuple.from_string(q) for q in queries]
    got = e.check_batch(rts)
    fails = sum(
        1
        for t, g in zip(rts, got)
        if g.membership != e.reference.check_relation_tuple(t, 0).membership
    )
    report("cat-videos", len(rts), fails, {"host_checks": e.stats["host_checks"]})

    # ---- deep chain, depth 32 (bench_test.go:56-86 topology) -------------
    depth = 32
    namespaces, tuples, cases = deep_chain(depth)
    e = engine_for(namespaces, tuples, max_depth=2 * depth)
    got = e.check_batch(
        [RelationTuple.from_string(c) for c, _ in cases], 2 * depth
    )
    fails = sum(
        1
        for (c, want), g in zip(cases, got)
        if (g.membership == Membership.IS_MEMBER) != want
    )
    report("deep-chain-32", len(cases), fails,
           {"host_checks": e.stats["host_checks"]})

    # ---- the same chain through the Leopard closure index ----------------
    # built by the device powering kernel and answered by the closure
    # probe: the builder falls back to the host builder when the device
    # fails (engine/closure.py), so a fallback or a batch the probe did
    # not answer counts as a failure beside the verdicts
    e = engine_for(
        namespaces, tuples, max_depth=2 * depth,
        closure={"enabled": True, "powering": "device"},
    )
    built = e.closure_ensure_built()
    got = e.check_batch(
        [RelationTuple.from_string(c) for c, _ in cases], 2 * depth
    )
    fails = sum(
        1
        for (c, want), g in zip(cases, got)
        if (g.membership == Membership.IS_MEMBER) != want
    )
    index = e.closure_index().stats
    on_device = (
        built and index["device_builds"] > 0
        and index["device_fallbacks"] == 0
        and e.stats.get("closure_hits", 0) == len(cases)
    )
    report("closure-deep-chain-32", len(cases), fails + (not on_device), {
        "device_builds": index["device_builds"],
        "device_fallbacks": index["device_fallbacks"],
        "power_steps": index["power_steps"],
        "closure_hits": e.stats.get("closure_hits", 0),
    })

    # ---- AND/NOT islands (ported rewrites_test fixtures) -----------------
    from test_reference_engine import (
        REWRITE_CASES,
        REWRITE_NAMESPACES,
        REWRITE_TUPLES,
    )

    e = engine_for(REWRITE_NAMESPACES, REWRITE_TUPLES, max_depth=100)
    rts = [RelationTuple.from_string(q) for q, _ in REWRITE_CASES]
    got = e.check_batch(rts, 100)
    fails = sum(
        1
        for (q, want), g in zip(REWRITE_CASES, got)
        if (g.membership == Membership.IS_MEMBER) != want
    )
    report("rewrites+islands", len(rts), fails,
           {"host_checks": e.stats["host_checks"]})

    # ---- randomized differential -----------------------------------------
    rng = random.Random(99)
    namespaces = [Namespace(name="rnd", relations=[
        Relation(name="r0"),
        Relation(name="r1"),
        Relation(name="r2", subject_set_rewrite=SubjectSetRewrite(children=[
            ComputedSubjectSet(relation="r0"),
            TupleToSubjectSet(relation="r1",
                              computed_subject_set_relation="r2"),
        ])),
    ])]
    rels = ["r0", "r1", "r2"]
    tup = set()
    for _ in range(200):
        obj = f"o{rng.randrange(40)}"
        rel = rng.choice(rels)
        if rng.random() < 0.4:
            sub = f"(rnd:o{rng.randrange(40)}#{rng.choice(rels)})"
        else:
            sub = f"u{rng.randrange(10)}"
        tup.add(f"rnd:{obj}#{rel}@{sub}")
    e = engine_for(namespaces, sorted(tup), max_depth=12)
    from keto_tpu.engine import ReferenceEngine

    oracle = ReferenceEngine(e.manager, e.config, visited_pruning=False)
    queries = [
        RelationTuple.from_string(
            f"rnd:o{rng.randrange(40)}#{rng.choice(rels)}@u{rng.randrange(10)}"
        )
        for _ in range(128)
    ]
    got = e.check_batch(queries, 12)
    fails = sum(
        1
        for q, g in zip(queries, got)
        if g.membership != oracle.check_relation_tuple(q, 12).membership
    )
    report("randomized-differential", len(queries), fails)

    # ---- expand differential (device BFS gather vs exact host trees) -----
    from keto_tpu.ketoapi import SubjectSet

    namespaces = [
        Namespace(name="role", relations=[Relation(name="member")]),
    ]
    tup = set()
    for r in range(24):
        for _ in range(3):
            tup.add(f"role:r{r}#member@u{rng.randrange(12)}")
        if r and rng.random() < 0.6:
            tup.add(f"role:r{r}#member@(role:r{rng.randrange(r)}#member)")
    e = engine_for(namespaces, sorted(tup), max_depth=6)
    subs = [
        SubjectSet(namespace="role", object=f"r{rng.randrange(24)}",
                   relation="member")
        for _ in range(32)
    ]
    trees = e.expand_batch(subs, 6)
    fails = 0
    for s, t in zip(subs, trees):
        want = e.reference.expand(s, 6)
        got_d = t.to_dict() if t is not None else None
        want_d = want.to_dict() if want is not None else None
        if got_d != want_d:
            fails += 1
    report("expand-differential", len(subs), fails,
           {"host_expands": e.stats.get("host_expands", 0)})

    print(json.dumps({
        "tier": "tpu", "device": str(device), "sets": sets,
        "cases": total_cases, "failures": total_failures,
    }))
    return 0 if total_failures == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
