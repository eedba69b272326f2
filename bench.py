"""Benchmark: batched Check() throughput on the device engine.

Reproduces BASELINE.md config 2 (batched Check over a cat-videos-style
topology: ~10k tuples, owner/parent/viewer userset rewrite, concurrent
checks riding one device batch) plus the served-path procedure of
BASELINE.md ("served QPS via gRPC load ... p50/p95/p99"): a real daemon
(gRPC mux + micro-batcher) hammered by concurrent client threads.

The reference publishes no numbers (SURVEY.md §6) and no Go toolchain
exists in this image, so `vs_baseline` is reported against the
north-star target of 1,000,000 Check()/sec (BASELINE.json metric) —
vs_baseline = 1.0 means the Zanzibar-paper-class goal is met.

The device: a run that finds no TPU exits non-zero. `--platform cpu` is
the caller's explicit choice to bench XLA's CPU backend instead (counts
and control flow, never a device rate); nothing falls back to it. Every
record names the device it ran on. A failure after start-up still emits
the one JSON line, with `error` set, and exit code 1.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Flags:
  --platform {auto,tpu,cpu}   auto/tpu (default auto): the attached TPU
                              or exit 2; cpu: the CPU backend, by choice
  --skip-serve                skip the served-path (gRPC) section
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

import numpy as np

NORTH_STAR_QPS = 1_000_000.0

N_FOLDERS = 64
FILES_PER_FOLDER = 120
N_USERS = 512
# KETO_BENCH_BATCH: the engine leg's batch size. A launch has a fixed
# cost, so a bigger batch spreads it over more checks until compute
# dominates. Unset, bench.main() picks the batch per platform: on tpu it
# calibrates between 16384 and 32768 (_calibrate_batch), on cpu it uses
# 4096 (big batches only add latency there). Importers that never run
# main() (profile_kernel) see the 4096 default.
_BATCH_FROM_ENV = "KETO_BENCH_BATCH" in os.environ
BATCH = int(os.environ.get("KETO_BENCH_BATCH", 4096))
# expand leg batch: same per-platform logic (launch amortization on
# tpu; measured sweep: 256 -> 2.97k trees/s, 1024 -> 6.3k, 4096 flat),
# resolved in main() beside BATCH
_EXPAND_FROM_ENV = "KETO_BENCH_EXPAND_BATCH" in os.environ
EXPAND_BATCH = int(os.environ.get("KETO_BENCH_EXPAND_BATCH", 256))
ROUNDS = 20

# KETO_BENCH_SERVE_CLIENTS: concurrent closed-loop clients in the
# served phase. A closed loop caps the offered load at in-flight clients
# over launch latency however well the batcher coalesces, so showing
# batch amortization needs more clients where a launch takes longer.
SERVE_THREADS = int(os.environ.get("KETO_BENCH_SERVE_CLIENTS", 32))
SERVE_SECONDS = 8.0
# batch-check RPC leg (keto_tpu extension surface): few clients, big
# batches — the serving-plane shape that can actually feed the device
# engine (one check per RPC caps offered load at clients/RTT; a batch
# RPC carries thousands per round-trip)
SERVE_BATCH_SIZE = int(os.environ.get("KETO_BENCH_SERVE_BATCH", 2048))
SERVE_BATCH_CLIENTS = int(os.environ.get("KETO_BENCH_SERVE_BATCH_CLIENTS", 4))
# reverse-reachability leg (ListObjects/ListSubjects, bench_reverse):
# batch of concurrent enumerations per device launch
LIST_BATCH = int(os.environ.get("KETO_BENCH_LIST_BATCH", 256))


def build_dataset():
    from keto_tpu.ketoapi import RelationTuple
    from keto_tpu.namespace import Namespace
    from keto_tpu.namespace.ast import (
        ComputedSubjectSet,
        Relation,
        SubjectSetRewrite,
        TupleToSubjectSet,
    )

    namespaces = [
        Namespace(
            name="videos",
            relations=[
                Relation(name="owner"),
                Relation(name="parent"),
                Relation(
                    name="view",
                    subject_set_rewrite=SubjectSetRewrite(
                        children=[
                            ComputedSubjectSet(relation="owner"),
                            TupleToSubjectSet(
                                relation="parent",
                                computed_subject_set_relation="view",
                            ),
                        ]
                    ),
                ),
            ],
        )
    ]
    rng = random.Random(1234)
    tuples = []
    owners: dict[str, str] = {}
    for d in range(N_FOLDERS):
        owner = f"user{rng.randrange(N_USERS)}"
        owners[f"/d{d}"] = owner
        tuples.append(RelationTuple.from_string(f"videos:/d{d}#owner@{owner}"))
        for f in range(FILES_PER_FOLDER):
            obj = f"/d{d}/v{f}.mp4"
            tuples.append(
                RelationTuple.from_string(f"videos:{obj}#parent@(videos:/d{d}#...)")
            )
            if rng.random() < 0.25:
                u = f"user{rng.randrange(N_USERS)}"
                tuples.append(RelationTuple.from_string(f"videos:{obj}#owner@{u}"))
                owners[obj] = u
    # query mix: half hits (folder owner sees nested file), half misses
    queries = []
    for i in range(BATCH):
        d = rng.randrange(N_FOLDERS)
        obj = f"/d{d}/v{rng.randrange(FILES_PER_FOLDER)}.mp4"
        if i % 2 == 0:
            sub = owners[f"/d{d}"]
        else:
            sub = f"user{rng.randrange(N_USERS)}"
        queries.append(RelationTuple.from_string(f"videos:{obj}#view@{sub}"))
    return namespaces, tuples, queries


def _calibrate_batch(candidates) -> dict:
    """Short pipelined burst per candidate batch size on the flagship
    dataset; returns {"best": B, "rates": {B: qps}}. Separate engines
    (frontier scales with the batch) — each pays one XLA compile, then 8
    pipelined launches measure the steady rate."""
    from keto_tpu.config import Config
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.storage import MemoryManager

    namespaces, tuples, queries = build_dataset()
    cfg = Config({"limit": {"max_read_depth": 5}})
    cfg.set_namespaces(namespaces)
    manager = MemoryManager()
    manager.write_relation_tuples(tuples)
    rates: dict = {}
    for B in candidates:
        engine = TPUCheckEngine(manager, cfg, frontier_cap=2 * B)
        qs = [queries[i % len(queries)] for i in range(B)]
        engine.check_batch(qs)  # compile + warm
        n, window = 8, 4
        t0 = time.perf_counter()
        handles = []
        for _ in range(n):
            handles.append(engine.check_batch_submit(qs))
            if len(handles) > window:
                engine.check_batch_resolve(handles.pop(0))
        for h in handles:
            engine.check_batch_resolve(h)
        rates[B] = round(n * B / (time.perf_counter() - t0), 1)
    best = max(rates, key=rates.get)
    return {"best": best, "rates": {str(k): v for k, v in rates.items()}}


def bench_kernel(namespaces, tuples, queries) -> dict:
    """Device-kernel path: warm-up (snapshot build + XLA compile) is kept
    out of the timed region.

    Throughput is measured PIPELINED: all ROUNDS batches are launched
    via check_batch_submit before any resolves — jax dispatch is async,
    so the device overlaps compute with result readback, exactly as a
    loaded server keeps multiple device batches in flight (one batch at
    a time is bound by the launch-to-readback latency). Per-batch
    LATENCY is reported separately from blocked single-batch rounds."""
    from keto_tpu.config import Config
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.storage import MemoryManager

    from keto_tpu.observability import FlightRecorder, summarize_launches

    cfg = Config({"limit": {"max_read_depth": 5}})
    cfg.set_namespaces(namespaces)
    manager = MemoryManager()
    manager.write_relation_tuples(tuples)
    # frontier cap 2×batch: smallest cap that keeps this workload fully
    # on-device (overflow would flag host replay); per-step cost scales
    # with the cap, so oversizing it halves throughput
    flightrec = FlightRecorder(capacity=4 * ROUNDS)
    engine = TPUCheckEngine(
        manager, cfg, frontier_cap=2 * BATCH, flightrec=flightrec
    )

    warm0 = time.perf_counter()
    engine.check_batch(queries)
    warmup_s = time.perf_counter() - warm0
    assert engine.stats["host_checks"] == 0, "bench workload must stay on device"

    # pipelined throughput with BOUNDED depth: a sliding window of 8
    # in-flight batches (each handle holds device buffers, so the queue
    # is bounded; 8 hides the launch-to-readback latency)
    depth_cap = 8
    t0 = time.perf_counter()
    handles: list = []
    for i in range(ROUNDS):
        handles.append(engine.check_batch_submit(queries))
        if len(handles) > depth_cap:
            engine.check_batch_resolve(handles.pop(0))
    for h in handles:
        engine.check_batch_resolve(h)
    wall = time.perf_counter() - t0
    qps = ROUNDS * BATCH / wall

    # blocked per-batch latency (what one isolated batch costs)
    latencies = []
    for _ in range(5):
        s = time.perf_counter()
        engine.check_batch(queries)
        latencies.append(time.perf_counter() - s)
    lat = np.array(latencies) * 1e3
    p50b = float(np.percentile(lat, 50))
    p95b = float(np.percentile(lat, 95))

    # BASELINE config 1: single-check latency floor (one blocked check,
    # smallest bucket — what an unloaded caller sees end-to-end through
    # the engine, including any device round-trip)
    engine.check_batch(queries[:1])  # small-bucket compile warm-up
    single = []
    for i in range(20):
        s = time.perf_counter()
        engine.check_batch([queries[i % len(queries)]])
        single.append(time.perf_counter() - s)
    return {
        "value": round(qps, 1),
        "warmup_s": round(warmup_s, 2),
        "p50_batch_ms": round(p50b, 2),
        "p95_batch_ms": round(p95b, 2),
        # amortized device cost per check at steady state (pipelined)
        "per_check_us_pipelined": round(wall * 1e6 / (ROUNDS * BATCH), 3),
        "single_check_p50_ms": round(
            float(np.percentile(np.array(single) * 1e3, 50)), 2
        ),
        # per-launch device introspection aggregates (flight recorder):
        # mean/p95 iterations, gather bytes/check, padding waste — the
        # droop-hypothesis evidence captured with every BENCH record
        "launch_telemetry": summarize_launches(flightrec.entries()),
    }


def bench_config3_islands() -> dict:
    """BASELINE config 3: rewrite-heavy namespace with AND + NOT (the
    island path). Round 1 flagged these host-only; they now run on
    device — this measures that."""
    from keto_tpu.config import Config
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.ketoapi import RelationTuple
    from keto_tpu.namespace import Namespace
    from keto_tpu.namespace.ast import (
        ComputedSubjectSet,
        InvertResult,
        Operator,
        Relation,
        SubjectSetRewrite,
    )
    from keto_tpu.storage import MemoryManager

    n_docs, n_users = 3000, 512
    ns = [Namespace(name="acl", relations=[
        Relation(name="allow"),
        Relation(name="deny"),
        Relation(name="access", subject_set_rewrite=SubjectSetRewrite(
            operation=Operator.AND,
            children=[
                ComputedSubjectSet(relation="allow"),
                InvertResult(child=ComputedSubjectSet(relation="deny")),
            ])),
    ])]
    rng = random.Random(5)
    tuples = []
    for d in range(n_docs):
        for _ in range(3):
            tuples.append(RelationTuple.from_string(
                f"acl:doc{d}#allow@u{rng.randrange(n_users)}"
            ))
        if rng.random() < 0.3:
            tuples.append(RelationTuple.from_string(
                f"acl:doc{d}#deny@u{rng.randrange(n_users)}"
            ))
    queries = [
        RelationTuple.from_string(
            f"acl:doc{rng.randrange(n_docs)}#access@u{rng.randrange(n_users)}"
        )
        for _ in range(BATCH)
    ]
    cfg = Config({"limit": {"max_read_depth": 5}})
    cfg.set_namespaces(ns)
    m = MemoryManager()
    m.write_relation_tuples(tuples)
    engine = TPUCheckEngine(m, cfg, frontier_cap=2 * BATCH)
    engine.check_batch(queries)  # warm-up/compile
    rounds = 5
    t0 = time.perf_counter()
    handles = [engine.check_batch_submit(queries) for _ in range(rounds)]
    for h in handles:
        engine.check_batch_resolve(h)
    wall = time.perf_counter() - t0
    return {
        "islands_qps": round(rounds * BATCH / wall, 1),
        "islands_host_checks": engine.stats["host_checks"],
    }


def bench_config3_expand() -> dict:
    """BASELINE config 3: Expand() trees on an RBAC role-chain rewrite
    namespace (the rewrites_test.go:20-100 topology class: documents
    whose viewer ⊇ editor ⊇ owner via computed-subject-set rewrites,
    editors granted through role groups whose member sets nest other
    roles). Expand engine parity: internal/expand/engine.go:35-104."""
    from keto_tpu.config import Config
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.ketoapi import RelationTuple, SubjectSet
    from keto_tpu.namespace import Namespace
    from keto_tpu.namespace.ast import (
        ComputedSubjectSet,
        Relation,
        SubjectSetRewrite,
    )
    from keto_tpu.storage import MemoryManager

    n_docs, n_roles, n_users = 2000, 64, 512
    ns = [
        Namespace(name="role", relations=[Relation(name="member")]),
        Namespace(name="doc", relations=[
            Relation(name="owner"),
            Relation(name="editor", subject_set_rewrite=SubjectSetRewrite(
                children=[ComputedSubjectSet(relation="owner")]
            )),
            Relation(name="viewer", subject_set_rewrite=SubjectSetRewrite(
                children=[ComputedSubjectSet(relation="editor")]
            )),
        ]),
    ]
    rng = random.Random(7)
    tuples = []
    # role hierarchy: each role has direct members and may nest one role
    for r in range(n_roles):
        for _ in range(4):
            tuples.append(RelationTuple.from_string(
                f"role:r{r}#member@u{rng.randrange(n_users)}"
            ))
        if r and rng.random() < 0.5:
            tuples.append(RelationTuple.from_string(
                f"role:r{r}#member@(role:r{rng.randrange(r)}#member)"
            ))
    for d in range(n_docs):
        tuples.append(RelationTuple.from_string(
            f"doc:d{d}#owner@u{rng.randrange(n_users)}"
        ))
        tuples.append(RelationTuple.from_string(
            f"doc:d{d}#editor@(role:r{rng.randrange(n_roles)}#member)"
        ))
        if rng.random() < 0.3:
            tuples.append(RelationTuple.from_string(
                f"doc:d{d}#viewer@u{rng.randrange(n_users)}"
            ))
    cfg = Config({"limit": {"max_read_depth": 6}})
    cfg.set_namespaces(ns)
    m = MemoryManager()
    m.write_relation_tuples(tuples)
    engine = TPUCheckEngine(m, cfg)
    exp_batch = EXPAND_BATCH
    # expand the role member sets: real tuple fanout (direct members +
    # nested roles), the "who holds this role" question — expand follows
    # STORED subject-set edges, not rewrites (engine.go:35-104), so doc
    # viewer sets (rewrite-derived) would expand to leaves
    subjects = [
        SubjectSet(namespace="role", object=f"r{rng.randrange(n_roles)}",
                   relation="member")
        for _ in range(exp_batch)
    ]
    # frontier/edge caps scale with the batch: the fixed defaults
    # (frontier 1024, edges 4096) fit ~256 of these trees, and an
    # overflow silently turns the excess into host replays (the leg
    # then measures the host). pool_cap stays at the engine's auto
    # default, which already scales with the batch (32x the bucket —
    # larger than any explicit value we'd pass here).
    ecaps = dict(
        frontier_cap=max(1024, 4 * exp_batch),
        edge_cap=max(4096, 16 * exp_batch),
    )
    trees = engine.expand_batch(subjects, 6, **ecaps)  # warm-up/compile
    n_nodes = sum(_tree_size(t) for t in trees if t is not None)
    host_after_warmup = engine.stats.get("host_expands", 0)
    rounds = 5
    lat = []
    t0 = time.perf_counter()
    for _ in range(rounds):
        s = time.perf_counter()
        engine.expand_batch(subjects, 6, **ecaps)
        lat.append(time.perf_counter() - s)
    wall = time.perf_counter() - t0
    return {
        "expand_qps": round(rounds * exp_batch / wall, 1),
        "expand_batch": exp_batch,
        "expand_p50_batch_ms": round(float(np.percentile(np.array(lat) * 1e3, 50)), 2),
        "expand_tree_nodes_avg": round(n_nodes / max(len(trees), 1), 1),
        # timed-region fallbacks only (warm-up batch excluded)
        "expand_host": engine.stats.get("host_expands", 0) - host_after_warmup,
    }


def bench_reverse(namespaces, tuples) -> dict:
    """Reverse-reachability workload (engine/reverse_kernel.py): the
    subject-centric inverse of the flagship check bench. ListObjects asks
    "which videos can this user view?" for LIST_BATCH random users over
    the cat-videos topology (reverse BFS over the transposed mirror);
    ListSubjects asks "who can view this video?" over random files of
    the same topology (forward enumeration over the full-edge CSR +
    rewrites: the owner computed-set and the parent-folder TTU both
    traverse per query). Caps are sized so the workload stays on device —
    a fallback would silently measure the O(candidates x check) host
    oracle instead."""
    import random as _random

    from keto_tpu.config import Config
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.storage import MemoryManager

    rng = _random.Random(11)
    cfg = Config({"limit": {"max_read_depth": 5}})
    cfg.set_namespaces(namespaces)
    m = MemoryManager()
    m.write_relation_tuples(tuples)
    engine = TPUCheckEngine(m, cfg)
    B = LIST_BATCH
    lo_queries = [
        ("videos", "view", f"user{rng.randrange(N_USERS)}") for _ in range(B)
    ]
    ls_queries = [
        (
            "videos",
            f"/d{rng.randrange(N_FOLDERS)}/v{rng.randrange(FILES_PER_FOLDER)}.mp4",
            "view",
        )
        for _ in range(B)
    ]
    caps = dict(
        frontier_cap=max(16384, 4 * B),
        result_cap=2048,
        pool_cap=64 * B,
    )
    out: dict = {"list_batch": B}
    rounds = 5

    t0 = time.perf_counter()
    engine.list_objects_batch(lo_queries, 5, **caps)  # build + compile
    out["listobjects_warmup_s"] = round(time.perf_counter() - t0, 2)
    host0 = engine.stats.get("host_list_objects", 0)
    t0 = time.perf_counter()
    for _ in range(rounds):
        res = engine.list_objects_batch(lo_queries, 5, **caps)
    wall = time.perf_counter() - t0
    out["listobjects_qps"] = round(rounds * B / wall, 1)
    out["listobjects_avg_results"] = round(
        sum(len(r) for r in res) / max(len(res), 1), 1
    )
    # timed-region fallbacks only (device-exactness health signal)
    out["listobjects_host"] = engine.stats.get("host_list_objects", 0) - host0

    t0 = time.perf_counter()
    engine.list_subjects_batch(ls_queries, 5, **caps)
    out["listsubjects_warmup_s"] = round(time.perf_counter() - t0, 2)
    host0 = engine.stats.get("host_list_subjects", 0)
    t0 = time.perf_counter()
    for _ in range(rounds):
        res = engine.list_subjects_batch(ls_queries, 5, **caps)
    wall = time.perf_counter() - t0
    out["listsubjects_qps"] = round(rounds * B / wall, 1)
    out["listsubjects_avg_results"] = round(
        sum(len(r) for r in res) / max(len(res), 1), 1
    )
    out["listsubjects_host"] = engine.stats.get("host_list_subjects", 0) - host0
    return out


def bench_filter() -> dict:
    """Bulk ACL filter leg (engine/filter_kernel.py): one subject, a
    10k-object candidate column, one device ride — vs the pipelined
    check_batch baseline on the SAME (subject, object) pairs. The
    acceptance bar is >=10x lower per-object cost than the pipelined
    per-Check ride (the motivation's "10k independent Check rides").

    Three arms over one ~10k-object cat-videos topology:
      - filter/frontier: closure off — the shared-frontier reverse walk
        expands the subject's reachable set ONCE and intersects the
        whole candidate column (the structural win: the walk explores
        the SUBJECT's world, not 10k objects' ancestries).
      - filter/closure: Leopard fast path — every covered candidate is
        one batched membership gather.
      - check_batch baselines, closure off AND on, pipelined exactly
        like bench_kernel.
    Verdict equality between the two filter arms is asserted, plus a
    random-sample differential vs the host oracle (the full differential
    lives in tests/test_filter.py + tools/filter_correctness.py)."""
    import random as _random

    from keto_tpu.config import Config
    from keto_tpu.engine.reference import ReferenceEngine
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.ketoapi import RelationTuple
    from keto_tpu.observability import FlightRecorder, summarize_launches
    from keto_tpu.storage import MemoryManager

    namespaces, _, _ = build_dataset()
    # a >=10k-object candidate universe: 84 folders x 120 files
    rng = _random.Random(77)
    n_folders, files_per_folder = 84, 120
    tuples = []
    owners: dict[str, str] = {}
    for d in range(n_folders):
        owner = f"user{rng.randrange(N_USERS)}"
        owners[f"/d{d}"] = owner
        tuples.append(RelationTuple.from_string(f"videos:/d{d}#owner@{owner}"))
        for f in range(files_per_folder):
            obj = f"/d{d}/v{f}.mp4"
            tuples.append(RelationTuple.from_string(
                f"videos:{obj}#parent@(videos:/d{d}#...)"
            ))
    n_objects = int(os.environ.get("KETO_BENCH_FILTER_OBJECTS", 10000))
    candidates = [
        f"/d{rng.randrange(n_folders)}/v{rng.randrange(files_per_folder)}.mp4"
        for _ in range(n_objects)
    ]
    # the filtering subject owns one folder: ~1.2% hit rate, the sparse
    # search-result shape (most candidates are other people's documents)
    subject = owners["/d0"]

    cfg = Config({
        "limit": {"max_read_depth": 5},
        "closure": {"enabled": True},
        "filter": {"chunk_size": 16384},
    })
    cfg.set_namespaces(namespaces)
    m = MemoryManager()
    m.write_relation_tuples(tuples)
    rounds = 5
    out: dict = {"filter_objects": n_objects}

    def _filter_arm(closure: bool, prefix: str):
        flightrec = FlightRecorder(capacity=64)
        engine = TPUCheckEngine(m, cfg, flightrec=flightrec)
        engine.closure_enabled = closure
        if closure:
            engine.closure_ensure_built()
        verdicts = engine.filter_batch(
            "videos", "view", subject, candidates, chunk_size=16384
        )  # build + compile
        host0 = engine.stats.get("filter_host", 0)
        t0 = time.perf_counter()
        for _ in range(rounds):
            verdicts = engine.filter_batch(
                "videos", "view", subject, candidates, chunk_size=16384
            )
        wall = time.perf_counter() - t0
        out[f"{prefix}_objects_per_sec"] = round(rounds * n_objects / wall, 1)
        out[f"{prefix}_per_object_us"] = round(
            wall / (rounds * n_objects) * 1e6, 3
        )
        out[f"{prefix}_host"] = engine.stats.get("filter_host", 0) - host0
        kind = "filter_closure" if closure else "filter"
        out[f"{prefix}_launch_telemetry"] = summarize_launches(
            flightrec.entries(), kind=kind
        )
        return verdicts, engine

    frontier_verdicts, _ = _filter_arm(False, "filter_frontier")
    closure_verdicts, _ = _filter_arm(True, "filter_closure")
    assert frontier_verdicts == closure_verdicts, (
        "filter arms disagree — differential bug"
    )
    out["filter_allowed"] = sum(frontier_verdicts)
    # random-sample differential vs the exact host oracle
    oracle = ReferenceEngine(m, cfg)
    sample = rng.sample(range(n_objects), 200)
    want = oracle.filter_objects(
        "videos", "view", subject, [candidates[i] for i in sample]
    )
    got = [frontier_verdicts[i] for i in sample]
    out["filter_oracle_sample_mismatches"] = sum(
        1 for a, b in zip(got, want) if a != b
    )

    # headline metric: the closure-arm throughput (the steady serving
    # shape — a warm Leopard index); the frontier arm is the
    # closure-cold contrast
    out["filter_objects_per_sec"] = out["filter_closure_objects_per_sec"]

    # pipelined check_batch baselines on the SAME pairs
    check_tuples = [
        RelationTuple.from_string(f"videos:{obj}#view@{subject}")
        for obj in candidates
    ]

    def _check_arm(closure: bool, prefix: str):
        engine = TPUCheckEngine(m, cfg, frontier_cap=2 * BATCH)
        engine.closure_enabled = closure
        if closure:
            engine.closure_ensure_built()
        engine.check_batch(check_tuples)  # compile + warm
        t0 = time.perf_counter()
        handles = [
            engine.check_batch_submit(check_tuples) for _ in range(rounds)
        ]
        results = None
        for h in handles:
            results = engine.check_batch_resolve(h)
        wall = time.perf_counter() - t0
        out[f"{prefix}_objects_per_sec"] = round(rounds * n_objects / wall, 1)
        out[f"{prefix}_per_object_us"] = round(
            wall / (rounds * n_objects) * 1e6, 3
        )
        return results

    check_results = _check_arm(False, "checkbatch")
    _check_arm(True, "checkbatch_closure")
    from keto_tpu.engine.definitions import Membership

    check_verdicts = [
        r.error is None and r.membership == Membership.IS_MEMBER
        for r in check_results
    ]
    assert check_verdicts == frontier_verdicts, (
        "check_batch and filter disagree — differential bug"
    )

    # the acceptance ratio: per-object cost of the pipelined per-Check
    # ride over the filter ride (>= 10 is the bar). Both filter arms
    # are ratioed so the artifact shows the closure-warm AND
    # closure-cold story; the closure-on check contrast sits beside it.
    out["filter_per_object_us"] = out["filter_closure_per_object_us"]
    out["filter_vs_checkbatch_per_object"] = round(
        out["checkbatch_per_object_us"] / out["filter_closure_per_object_us"],
        2,
    )
    out["filter_frontier_vs_checkbatch_per_object"] = round(
        out["checkbatch_per_object_us"] / out["filter_frontier_per_object_us"],
        2,
    )
    return out


def bench_watch(n_events: int = 2000, n_subs: int = 4) -> dict:
    """Watch-subsystem leg (keto_tpu/watch): one writer churning
    single-tuple transactions against N live subscribers on the
    in-process hub — the event-consumer workload (cache sync, audit,
    replication) end to end minus the wire. Reports aggregate delivered
    changes/sec across subscribers and the p95 write-commit-to-delivery
    lag; resets must be 0 (the buffer is sized for the churn)."""
    import threading as _threading

    from keto_tpu.ketoapi import RelationTuple
    from keto_tpu.storage import MemoryManager
    from keto_tpu.watch import WatchHub

    manager = MemoryManager()
    hub = WatchHub(manager, poll_interval=0.05, buffer=n_events + 16)
    write_ts: list[float] = [0.0] * (n_events + 1)
    lags: list[list[float]] = [[] for _ in range(n_subs)]
    resets = [0]

    def consume(i: int) -> None:
        sub = hub.subscribe("default")
        try:
            seen = 0
            while seen < n_events:
                event = sub.get(timeout=10.0)
                if event is None:
                    return  # stalled: the partial lag sample still reports
                if event.is_reset:
                    resets[0] += 1
                    continue
                now = time.perf_counter()
                lags[i].append(now - write_ts[event.version])
                seen += len(event.changes)
        finally:
            sub.close()

    threads = [
        _threading.Thread(target=consume, args=(i,), daemon=True)
        for i in range(n_subs)
    ]
    for t in threads:
        t.start()
    time.sleep(0.05)  # subscribers parked on their buffers
    t0 = time.perf_counter()
    for v in range(1, n_events + 1):
        write_ts[v] = time.perf_counter()
        manager.write_relation_tuples(
            [RelationTuple("videos", f"w{v}", "owner", subject_id="writer")]
        )
    for t in threads:
        t.join(timeout=30)
    wall = time.perf_counter() - t0
    all_lags = sorted(lag for per_sub in lags for lag in per_sub)
    delivered = len(all_lags)
    p95 = all_lags[int(0.95 * (delivered - 1))] if delivered else 0.0
    return {
        "watch_subscribers": n_subs,
        "watch_churn_events": n_events,
        "watch_events_per_sec": round(delivered / wall, 1),
        "watch_p95_lag_ms": round(p95 * 1e3, 3),
        "watch_resets": resets[0],
    }


def _tree_size(tree) -> int:
    if tree is None:
        return 0
    return 1 + sum(_tree_size(c) for c in (tree.children or ()))


def _deep_dataset():
    """The depth-20 drive topology (scaled bench_test.go:56-86 'deep'
    namespace) shared by the deep leg and the closure A/B leg."""
    from keto_tpu.config import Config
    from keto_tpu.ketoapi import RelationTuple
    from keto_tpu.namespace import Namespace
    from keto_tpu.namespace.ast import (
        ComputedSubjectSet,
        Relation,
        SubjectSetRewrite,
        TupleToSubjectSet,
    )
    from keto_tpu.storage import MemoryManager

    depth, n_chains, n_users = 20, 200, 128
    ns = [Namespace(name="deep", relations=[
        Relation(name="owner"),
        Relation(name="parent"),
        Relation(name="viewer", subject_set_rewrite=SubjectSetRewrite(children=[
            ComputedSubjectSet(relation="owner"),
            TupleToSubjectSet(relation="parent",
                              computed_subject_set_relation="viewer"),
        ])),
    ])]
    rng = random.Random(6)
    tuples = []
    owners = {}
    for c in range(n_chains):
        for i in range(depth):
            tuples.append(RelationTuple.from_string(
                f"deep:c{c}f{i}#parent@(deep:c{c}f{i + 1}#...)"
            ))
        owner = f"u{rng.randrange(n_users)}"
        owners[c] = owner
        tuples.append(RelationTuple.from_string(f"deep:c{c}f{depth}#owner@{owner}"))
    queries = []
    for i in range(BATCH):
        c = rng.randrange(n_chains)
        sub = owners[c] if i % 2 == 0 else f"u{rng.randrange(n_users)}"
        queries.append(RelationTuple.from_string(f"deep:c{c}f0#viewer@{sub}"))
    cfg = Config({
        "limit": {"max_read_depth": depth + 4},
        "closure": {"enabled": True},
    })
    cfg.set_namespaces(ns)
    m = MemoryManager()
    m.write_relation_tuples(tuples)
    return m, cfg, queries


def _closure_stats_record(engine, prefix: str) -> dict:
    """The closure observability fields every closure-bearing leg
    records: hit ratio over the leg's window, per-cause fallbacks, and
    the index lag at capture time."""
    hits = engine.stats.get("closure_hits", 0)
    fallbacks = dict(engine.stats.get("closure_fallback", {}))
    total = hits + sum(fallbacks.values())
    idx = engine.closure_index()
    return {
        f"{prefix}_hit_ratio": round(hits / total, 4) if total else 0.0,
        f"{prefix}_fallback_total": fallbacks,
        f"{prefix}_lag_versions": idx.lag_versions(
            engine.manager.version(nid=engine.nid)
        ),
    }


def bench_config4_deep(closure: bool = True) -> dict:
    """BASELINE config 4: depth-20 recursive Check. With `closure` (the
    default serving shape for this leg) the Leopard index answers the
    chains in one probe step — deep20_qps is then read against the flat
    leg's value (acceptance: within 1.5x); closure=False measures the
    raw BFS kernel (the flight-recorder A/B's iteration contrast)."""
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.observability import FlightRecorder, summarize_launches

    m, cfg, queries = _deep_dataset()
    flightrec = FlightRecorder(capacity=64)
    engine = TPUCheckEngine(
        m, cfg, frontier_cap=2 * BATCH, flightrec=flightrec
    )
    engine.closure_enabled = closure
    if closure:
        engine.closure_ensure_built()
    engine.check_batch(queries)
    rounds = 5
    t0 = time.perf_counter()
    handles = [engine.check_batch_submit(queries) for _ in range(rounds)]
    for h in handles:
        engine.check_batch_resolve(h)
    wall = time.perf_counter() - t0
    out = {
        "deep20_qps": round(rounds * BATCH / wall, 1),
        "deep20_host_checks": engine.stats["host_checks"],
        "deep20_closure": closure,
        # BFS iterations sit near the chain depth — the flat leg's
        # launch_telemetry is the non-degeneracy contrast; with closure
        # on, check-kind launches only happen for fallbacks
        "deep20_launch_telemetry": summarize_launches(flightrec.entries()),
    }
    if closure:
        out.update(_closure_stats_record(engine, "closure"))
        # the closure launches' own telemetry: iterations_mean must sit
        # at 1.0 regardless of chain depth — THE contrast the subsystem
        # exists for (the BFS arm's deep20 telemetry shows ~chain depth)
        out["deep20_closure_launch_telemetry"] = summarize_launches(
            flightrec.entries(), kind="closure"
        )
    return out


def bench_flightrec_ab() -> dict:
    """Counter-overhead A/B (acceptance leg, CPU-runnable): batched check
    QPS with the flight recorder ON vs OFF on the SAME engine and
    compiled kernel (the kernel's stats accumulation is always compiled
    in — the A/B isolates the host-side recording layer), recorder
    toggled every call so drift hits both arms. Also proves the counters
    are
    non-degenerate: iterations_used differs between the flat flagship
    workload and the deep-20 chain workload, and gather bytes move with
    table size/fanout (probe depths and edge rows both track the graph).
    """
    from keto_tpu.config import Config
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.ketoapi import RelationTuple
    from keto_tpu.observability import FlightRecorder, summarize_launches
    from keto_tpu.storage import MemoryManager

    namespaces, tuples, queries = build_dataset()
    cfg = Config({"limit": {"max_read_depth": 5}})
    cfg.set_namespaces(namespaces)
    manager = MemoryManager()
    manager.write_relation_tuples(tuples)
    fr_on = FlightRecorder(capacity=1024)
    engine = TPUCheckEngine(
        manager, cfg, frontier_cap=2 * BATCH, flightrec=fr_on
    )
    for _ in range(6):  # compile + ramp (shared by both arms)
        engine.check_batch(queries)

    # per-call alternation: the bench box is shared and coarse burst
    # rates swing 2x, so the arms must interleave at the finest grain —
    # one synchronous batch per sample, recorder toggled every call, and
    # the verdict read from MEDIANS over many samples (adjacent samples
    # see the same ambient load; the median discards the noise spikes).
    # Sync calls are also the honest sensitivity: pipelining would hide
    # recording cost behind the next batch's device time.
    fr_off = FlightRecorder(enabled=False)
    on_t: list = []
    off_t: list = []
    for i in range(120):
        engine.flightrec = fr_off if i % 2 == 0 else fr_on
        t0 = time.perf_counter()
        engine.check_batch(queries)
        dt = time.perf_counter() - t0
        (off_t if i % 2 == 0 else on_t).append(dt)
    med_on = sorted(on_t)[len(on_t) // 2]
    med_off = sorted(off_t)[len(off_t) // 2]
    qps_on = BATCH / med_on
    qps_off = BATCH / med_off
    on_vs_off = med_off / med_on
    n_pairs = len(on_t)
    flat = summarize_launches(fr_on.entries())
    small_probes = {
        "dh_probes": engine._ensure_state().snapshot.dh_probes,
        "rh_probes": engine._ensure_state().snapshot.rh_probes,
    }

    # deep-20 contrast: iterations must track the chain depth (closure
    # OFF — this leg measures the BFS kernel's counters, and a closure
    # hit would answer in one step by design)
    deep = bench_config4_deep(closure=False).get("deep20_launch_telemetry", {})

    # table-size contrast: the same drive topology at ~1e6 tuples
    # (vectorized columnar build — the scale tier's ingest path; a
    # MemoryManager write at this size is minutes of host dict churn).
    # Probe-chain growth is bucket-quantized (one bucket row = one 256 B
    # gather regardless of chain occupancy), so small growth is free
    # until a chain crosses a bucket boundary: measured here, the
    # direct-probe chain goes ~6 probes (9.7k tuples) -> ~10 (1e6),
    # crossing the 8-slot bucket — the probe phase physically gathers
    # one extra bucket row per task-step and the per-check gather-bytes
    # estimate must move with it
    from keto_tpu.storage.columnar import ColumnarStore
    from tools.scale_bench import synth_columns

    cols_l, f_names, owner_names, files_per = synth_columns(
        1_000_000, N_USERS, seed=7
    )
    n_folders = len(f_names)
    n_files = n_folders * files_per
    # synth_columns concatenates owner rows first, parent rows after;
    # the parent rows' objects are the file names
    file_names = cols_l.obj[n_folders:]
    store_l = ColumnarStore()
    store_l.bulk_load(cols_l)
    cfg_l = Config({"limit": {"max_read_depth": 5}})
    cfg_l.set_namespaces(namespaces)  # identical namespace config
    queries_l = [
        RelationTuple.from_string(
            f"videos:{file_names[i]}#view@"
            f"{owner_names[i // files_per] if i % 2 == 0 else 'nobody'}"
        )
        for i in np.random.default_rng(11).integers(0, n_files, BATCH)
    ]
    fr_l = FlightRecorder(capacity=64)
    engine_l = TPUCheckEngine(
        store_l, cfg_l, frontier_cap=2 * BATCH, flightrec=fr_l
    )
    engine_l.check_batch(queries_l)
    engine_l.check_batch(queries_l)
    large = summarize_launches(fr_l.entries())
    large_probes = {
        "dh_probes": engine_l._ensure_state().snapshot.dh_probes,
        "rh_probes": engine_l._ensure_state().snapshot.rh_probes,
    }

    return {
        "metric": "flightrec_ab",
        "ab_batch": BATCH,
        "flightrec_on_qps": round(qps_on, 1),
        "flightrec_off_qps": round(qps_off, 1),
        "on_vs_off": round(on_vs_off, 4),
        "ab_samples_per_arm": n_pairs,
        "small_tuples": len(tuples),
        "large_tuples": int(n_folders + n_files),
        "small_probe_depths": small_probes,
        "large_probe_depths": large_probes,
        "flat_launch_telemetry": flat,
        "deep20_launch_telemetry": deep,
        "large_table_launch_telemetry": large,
    }


def bench_closure_ab() -> dict:
    """Leopard-closure A/B (acceptance leg, CPU-runnable): the deep-20
    workload with the closure index ON vs OFF on the SAME engine and
    store, toggled per call so ambient-load drift hits both arms
    (medians over many synchronous samples — the --ab-flightrec
    protocol). Every ON sample's verdicts are compared against the OFF
    arm's reference answers: the record carries the mismatch count,
    which must be zero. The flat flagship workload rides along as the
    contrast leg — the acceptance bar reads deep20-ON against flat."""
    from keto_tpu.config import Config
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.storage import MemoryManager

    m, cfg, queries = _deep_dataset()
    engine = TPUCheckEngine(m, cfg, frontier_cap=2 * BATCH)
    engine.closure_enabled = False
    t0 = time.perf_counter()
    engine.closure_ensure_built()
    build_s = time.perf_counter() - t0
    engine.check_batch(queries)  # BFS compile + ramp
    engine.closure_enabled = True
    engine.check_batch(queries)  # closure compile + ramp
    engine.closure_enabled = False
    expected = [r.membership for r in engine.check_batch(queries)]

    on_t: list = []
    off_t: list = []
    mismatches = 0
    for i in range(60):
        engine.closure_enabled = i % 2 == 1
        t0 = time.perf_counter()
        res = engine.check_batch(queries)
        dt = time.perf_counter() - t0
        (on_t if i % 2 == 1 else off_t).append(dt)
        if i % 2 == 1:
            mismatches += sum(
                1 for r, want in zip(res, expected) if r.membership != want
            )
    med_on = sorted(on_t)[len(on_t) // 2]
    med_off = sorted(off_t)[len(off_t) // 2]

    # flat contrast on the flagship dataset: the acceptance denominator
    namespaces, tuples, flat_queries = build_dataset()
    fcfg = Config({"limit": {"max_read_depth": 5}})
    fcfg.set_namespaces(namespaces)
    fm = MemoryManager()
    fm.write_relation_tuples(tuples)
    fengine = TPUCheckEngine(fm, fcfg, frontier_cap=2 * BATCH)
    fengine.check_batch(flat_queries)
    flat_t: list = []
    for _ in range(20):
        t0 = time.perf_counter()
        fengine.check_batch(flat_queries)
        flat_t.append(time.perf_counter() - t0)
    flat_qps = BATCH / sorted(flat_t)[len(flat_t) // 2]

    idx = engine.closure_index().describe()
    return {
        "metric": "closure_ab",
        "ab_batch": BATCH,
        "closure_on_deep20_qps": round(BATCH / med_on, 1),
        "closure_off_deep20_qps": round(BATCH / med_off, 1),
        "on_vs_off": round(med_off / med_on, 4),
        "flat_qps": round(flat_qps, 1),
        # the acceptance ratio: deep chains within 1.5x of flat checks
        "deep20_vs_flat": round((BATCH / med_on) / flat_qps, 4),
        "ab_samples_per_arm": len(on_t),
        "verdict_mismatches": mismatches,
        "closure_covered_nodes": idx["covered_nodes"],
        "closure_entries": idx["entries"],
        "closure_build_s": round(build_s, 3),
        **_closure_stats_record(engine, "closure"),
    }


def _deep_columns(n_chains: int, depth: int = 20, n_users: int = 128,
                  seed: int = 9, n_direct: int = 0):
    """The deep-20 drive topology at COLUMNAR scale: the same
    chain-of-parents shape as `_deep_dataset`, but synthesized as numpy
    string columns and bulk-loaded (a MemoryManager write at 1e6 rows
    is minutes of host dict churn). Chosen over `synth_columns`' flat
    videos topology because the closure powers the DEEP universe.

    `n_direct` appends that many direct viewer grants on random chain
    nodes: they thicken the powered subject sets (real closure content)
    WITHOUT adding universe nodes, so the tuple count can hit a target
    (1e6) while the interesting-node universe — ~2 nodes per chain
    object — stays inside MAX_CLOSURE_NODES."""
    from keto_tpu.storage.columns import TupleColumns, concat_columns

    rng = np.random.default_rng(seed)
    n_par = n_chains * depth
    chain = np.repeat(np.arange(n_chains), depth)
    level = np.tile(np.arange(depth), n_chains)
    stem = np.char.add(np.char.add("c", chain.astype("U8")), "f")
    obj = np.char.add(stem, level.astype("U3"))
    sobj = np.char.add(stem, (level + 1).astype("U3"))
    par = TupleColumns(
        ns=np.full(n_par, "deep", dtype="U4"),
        obj=obj,
        rel=np.full(n_par, "parent", dtype="U6"),
        skind=np.ones(n_par, dtype=np.int8),
        sns=np.full(n_par, "deep", dtype="U4"),
        sobj=sobj,
        srel=np.full(n_par, "...", dtype="U3"),
    )
    tails = np.char.add(
        np.char.add("c", np.arange(n_chains).astype("U8")),
        "f" + str(depth),
    )
    owner_names = np.char.add(
        "u", rng.integers(0, n_users, n_chains).astype("U8")
    )
    own = TupleColumns(
        ns=np.full(n_chains, "deep", dtype="U4"),
        obj=tails,
        rel=np.full(n_chains, "owner", dtype="U5"),
        skind=np.zeros(n_chains, dtype=np.int8),
        sns=np.full(n_chains, "", dtype="U1"),
        sobj=owner_names,
        srel=np.full(n_chains, "", dtype="U1"),
    )
    parts = [own, par]
    if n_direct:
        dc = rng.integers(0, n_chains, n_direct)
        dl = rng.integers(0, depth + 1, n_direct)
        dobj = np.char.add(
            np.char.add(np.char.add("c", dc.astype("U8")), "f"),
            dl.astype("U3"),
        )
        dusers = np.char.add(
            "u", rng.integers(0, n_users, n_direct).astype("U8")
        )
        parts.append(TupleColumns(
            ns=np.full(n_direct, "deep", dtype="U4"),
            obj=dobj,
            rel=np.full(n_direct, "viewer", dtype="U6"),
            skind=np.zeros(n_direct, dtype=np.int8),
            sns=np.full(n_direct, "", dtype="U1"),
            sobj=dusers,
            srel=np.full(n_direct, "", dtype="U1"),
        ))
    return concat_columns(parts), owner_names


def _powering_context(target_tuples: int):
    """Build the deep columnar store once and extract the powering
    operands (graph + base snapshot) that both powering legs share."""
    from keto_tpu.config import Config
    from keto_tpu.engine.closure import extract_graph
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.storage.columnar import ColumnarStore

    depth = 20
    # the universe runs ~2 interesting nodes per chain object; cap the
    # chain population so it stays under MAX_CLOSURE_NODES with slack,
    # and make up the tuple-count target with direct viewer grants
    max_chains = 960_000 // (2 * (depth + 1))
    n_chains = max(1, min(target_tuples // (depth + 1), max_chains))
    n_direct = max(0, target_tuples - n_chains * (depth + 1))
    cols, _ = _deep_columns(n_chains, depth, n_direct=n_direct)
    store = ColumnarStore()
    store.bulk_load(cols)
    m, cfg, _ = _deep_dataset()  # only for the namespace config
    del m
    engine = TPUCheckEngine(store, cfg, frontier_cap=BATCH)
    t0 = time.perf_counter()
    state = engine._ensure_state()
    snapshot_s = time.perf_counter() - t0
    graph = extract_graph(state.snapshot)
    assert graph is not None, "deep topology must fit the closure caps"
    meta = {
        "tuples": int(cols.obj.shape[0]),
        "chains": n_chains,
        "depth": depth,
        "closure_nodes": int(graph.universe.shape[0]),
        "closure_edges": int(graph.e_dst.shape[0]),
        "snapshot_build_s": round(snapshot_s, 3),
        "max_depth": cfg.max_read_depth(),
    }
    return graph, state.snapshot, state.base_version, meta


def _build_sweep_entry(msr: int, build, rec: dict) -> dict:
    return {
        "max_set_rows": msr,
        "build_s": round(rec["build_s"], 3),
        "covered_nodes": int(build.covered_keys.shape[0]),
        "entries": int(build.ent_obj.shape[0]),
        "waves": rec["waves"],
        "steps": rec["steps"],
        "lanes": rec["lanes"],
        "hbm_bytes": {k: int(v) for k, v in rec["hbm"].items()},
        "hbm_total_bytes": int(sum(rec["hbm"].values())),
    }


def bench_closure_build(context=None, msrs=(4, 64, 4096)) -> dict:
    """Device-powering build leg: GraphBLAS closure powering over the
    deep topology at ~1e6 tuples, swept across `closure.max_set_rows` —
    the knob that trades coverage for index size. Records build seconds
    plus the packed-adjacency / bit-matrix / scratch HBM footprint the
    kernel actually reserved (the numbers `hbm_snapshot` accounts live
    under the closure_power family)."""
    from keto_tpu.engine.closure_power import power_closure_device

    target = int(os.environ.get("KETO_BENCH_CLOSURE_TUPLES", "1000000"))
    graph, snap, base_version, meta = (
        context if context is not None else _powering_context(target)
    )
    sweep = []
    for msr in msrs:
        build, rec = power_closure_device(
            graph, snap, meta["max_depth"], msr, base_version
        )
        sweep.append(_build_sweep_entry(msr, build, rec))
    return {"metric": "closure_build", **meta, "sweep": sweep}


def bench_powering_ab() -> dict:
    """Host-vs-device powering A/B (the --ab-closure protocol applied
    to the BUILDER): the same graph and snapshot powered by the numpy
    host builder and the bit-packed device kernel, compared field by
    field. The device contract is bit-identity — covered sets, entry
    rows, AND first-discovery req depths must match exactly — so every
    mismatch field must read zero. The max_set_rows sweep rides along
    as the build-cost curve."""
    from keto_tpu.engine.closure import power_closure
    from keto_tpu.engine.closure_power import power_closure_device

    target = int(os.environ.get("KETO_BENCH_CLOSURE_TUPLES", "1000000"))
    ctx = _powering_context(target)
    graph, snap, base_version, meta = ctx
    msr = 4096

    t0 = time.perf_counter()
    hb = power_closure(graph, snap, meta["max_depth"], msr, base_version)
    host_s = time.perf_counter() - t0
    db, rec = power_closure_device(
        graph, snap, meta["max_depth"], msr, base_version
    )

    covered_mismatches = int(
        np.setxor1d(hb.covered_keys, db.covered_keys).shape[0]
    )
    fields = ("ent_obj", "ent_rel", "ent_skind", "ent_sa", "ent_sb")
    exact = all(
        np.array_equal(getattr(hb, f), getattr(db, f)) for f in fields
    )
    if exact:
        subject_mm = 0
        req_mm = int(np.count_nonzero(hb.ent_req != db.ent_req))
    else:
        # identity failed somewhere: count as SETS so the record says
        # how wrong, not just that ordering differed
        def rows(b):
            m = np.ascontiguousarray(np.stack(
                [getattr(b, f).astype(np.int64) for f in fields], axis=1
            ))
            return m.view([("", np.int64)] * len(fields)).ravel()

        hv, dv = rows(hb), rows(db)
        subject_mm = int(
            np.setdiff1d(hv, dv).shape[0] + np.setdiff1d(dv, hv).shape[0]
        )
        hs, hi = np.unique(hv, return_index=True)
        pos = np.searchsorted(hs, dv)
        pos = np.clip(pos, 0, len(hs) - 1)
        hit = hs[pos] == dv
        req_mm = int(np.count_nonzero(
            hb.ent_req[hi[pos[hit]]] != db.ent_req[np.flatnonzero(hit)]
        ))

    return {
        "metric": "powering_ab",
        **meta,
        "max_set_rows": msr,
        "host_build_s": round(host_s, 3),
        "device_build_s": round(rec["build_s"], 3),
        "host_vs_device": round(host_s / max(rec["build_s"], 1e-9), 4),
        "covered_nodes": int(db.covered_keys.shape[0]),
        "entries": int(db.ent_obj.shape[0]),
        "subject_set_mismatches": subject_mm,
        "req_depth_mismatches": req_mm,
        "covered_key_mismatches": covered_mismatches,
        "device_waves": rec["waves"],
        "device_steps": rec["steps"],
        "device_lanes": rec["lanes"],
        "device_hbm_bytes": {k: int(v) for k, v in rec["hbm"].items()},
        # the A/B's own device build IS the sweep's top point — one
        # fewer multi-minute powering on the 1-core bench host
        "build_sweep": bench_closure_build(context=ctx, msrs=(4, 64))
        ["sweep"] + [_build_sweep_entry(msr, db, rec)],
    }


def bench_grpc_echo_ceiling(seconds: float = 3.0, n_threads: int = 32) -> dict:
    """The HOST PLATFORM's gRPC ceiling: a zero-logic echo server and
    closed-loop clients, all in this process tree. On the 1-core bench
    host (os.sched_getaffinity = {0}) this measures what ANY gRPC
    serve + load pair can possibly do here — served_qps should be read
    against it, not against absolute targets set for multi-core hosts."""
    import threading
    from concurrent import futures as _futures

    import grpc

    def handler(request, context):
        return request

    h = grpc.method_handlers_generic_handler("echo.Echo", {
        "Ping": grpc.unary_unary_rpc_method_handler(
            handler,
            request_deserializer=lambda b: b,
            response_serializer=lambda b: b,
        ),
    })
    server = grpc.server(_futures.ThreadPoolExecutor(max_workers=16))
    server.add_generic_rpc_handlers((h,))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        count = [0]
        lock = threading.Lock()
        stop_at = time.monotonic() + seconds

        def worker():
            ch = grpc.insecure_channel(f"127.0.0.1:{port}")
            ping = ch.unary_unary(
                "/echo.Echo/Ping",
                request_serializer=lambda b: b,
                response_deserializer=lambda b: b,
            )
            n = 0
            while time.monotonic() < stop_at:
                ping(b"x", timeout=10)
                n += 1
            ch.close()
            with lock:
                count[0] += n

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        return {"echo_ceiling_qps": round(count[0] / wall, 1)}
    finally:
        server.stop(0)


def _stage_summary(metrics) -> dict:
    """Mean per-stage serving ms from the check_stage_duration histogram
    (observability.CHECK_STAGES): the BENCH json's stage-attributable
    record — future trajectory entries can say WHERE p95 moved (queue
    wait vs padding vs dispatch vs device wait vs host replay), not just
    that it moved."""
    sums: dict = {}
    counts: dict = {}
    for fam in metrics.check_stage_duration.collect():
        for s in fam.samples:
            if s.name.endswith("_sum"):
                sums[s.labels["stage"]] = s.value
            elif s.name.endswith("_count"):
                counts[s.labels["stage"]] = s.value
    return {
        stage: round(1e3 * sums.get(stage, 0.0) / n, 3)
        for stage, n in counts.items()
        if n
    }


def bench_served(namespaces, tuples, queries, serve_workers: int = 1) -> dict:
    """Served path per BASELINE.md: a real daemon (direct gRPC listener +
    batcher + device engine) under concurrent gRPC clients; per-REQUEST
    latency percentiles, not per-batch. The direct listener (serve.read.
    grpc) skips the cmux-parity byte splice — the muxed port remains the
    wire-parity default, this is the measured high-throughput path.
    `serve_workers` >= 2 runs the replica group (api/replica.py): the
    record then carries per-worker QPS/occupancy so 1-vs-N comparisons
    are first-class in the artifact."""
    import os as _os
    import threading

    from keto_tpu.api import ReadClient, open_channel
    from keto_tpu.api.daemon import Daemon
    from keto_tpu.config import Config
    from keto_tpu.registry import Registry

    def make_daemon(aio: bool) -> Daemon:
        grpc_cfg = {"host": "127.0.0.1", "port": 0}
        if aio:
            grpc_cfg["aio"] = True
        cfg = Config(
            {
                "dsn": "memory",
                # pipeline depth 8: where the launch-to-readback latency
                # exceeds batch compute, served throughput scales with
                # launched-but-unresolved batches in flight
                "check": {"engine": "tpu", "pipeline_depth": 8},
                "limit": {"max_read_depth": 5},
                "serve": {
                    "read": {"host": "127.0.0.1", "port": 0,
                             "grpc": grpc_cfg},
                    "write": {"host": "127.0.0.1", "port": 0},
                    "metrics": {"host": "127.0.0.1", "port": 0},
                    "check": {"workers": max(int(serve_workers), 1)},
                },
            }
        )
        cfg.set_namespaces(namespaces)
        registry = Registry(cfg)
        registry.relation_tuple_manager().write_relation_tuples(tuples)
        d = Daemon(registry)
        d.start()
        return d

    daemon = make_daemon(aio=False)
    try:
        addr = f"127.0.0.1:{daemon.read_grpc_port}"
        # warm every bucket size the load phase can hit (single checks ride
        # the smallest padded bucket; batcher-coalesced groups the next one
        # up) so XLA compiles land before the timed window, not inside it
        engine = daemon.registry.check_engine()
        engine.check_batch(queries[:1])
        engine.check_batch(queries[: min(SERVE_THREADS + 1, len(queries))])
        warm = ReadClient(open_channel(addr))
        warm.check(queries[0], timeout=300)
        warm.close()

        def load_phase(n_threads: int, seconds: float, qs=None) -> dict:
            # `qs` narrows the key set: the repeated-key (hot) leg passes
            # a handful of queries so the serve-side check cache's hit
            # path is what gets measured
            qs = queries if qs is None else qs
            stop_at = time.monotonic() + seconds
            lock = threading.Lock()
            all_lat: list[float] = []
            last_done: list[float] = []
            errors = [0]

            def worker(seed: int) -> None:
                rng = random.Random(seed)
                client = ReadClient(open_channel(addr))
                lat: list[float] = []
                n_err = 0
                done = 0.0
                try:
                    while time.monotonic() < stop_at:
                        q = qs[rng.randrange(len(qs))]
                        s = time.perf_counter()
                        try:
                            client.check(q, timeout=30)
                        except Exception:
                            n_err += 1
                            continue
                        done = time.perf_counter()
                        lat.append(done - s)
                finally:
                    client.close()
                    with lock:
                        all_lat.extend(lat)
                        errors[0] += n_err
                        if done:
                            last_done.append(done)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=worker, args=(i,), daemon=True)
                for i in range(n_threads)
            ]
            for t in threads:
                t.start()
            # join without timeout: every request carries a 30s gRPC
            # deadline, so workers terminate; joining fully also means no
            # thread can still be mutating all_lat below
            for t in threads:
                t.join()
            if not all_lat:
                return {"error": "no successful served requests"}
            # wall = issue window start -> last request completion (NOT
            # the join time, which would fold straggler drain into the
            # denominator)
            wall = max(last_done) - t0
            lat_ms = np.array(all_lat) * 1e3
            return {
                "qps": round(len(all_lat) / wall, 1),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
                "p95_ms": round(float(np.percentile(lat_ms, 95)), 2),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
                "errors": errors[0],
            }

        def batch_load_phase(n_threads: int, batch: int, seconds: float) -> dict:
            """Batch-RPC load: every request carries `batch` checks
            (BatchCheckService), so a handful of closed-loop clients
            offer n_threads * batch checks per round-trip — the serving
            shape that can saturate the device engine (a single-check
            client fleet is offered-load-starved: clients/launch-RTT)."""
            stop_at = time.monotonic() + seconds
            lock = threading.Lock()
            rpc_lat: list[float] = []
            checks = [0]
            last_done: list[float] = []
            errors = [0]

            def worker(seed: int) -> None:
                rng = random.Random(seed)
                client = ReadClient(open_channel(addr))
                lat: list[float] = []
                n_checks = 0
                n_err = 0
                done = 0.0
                # pre-slice a rotation of query windows so the client
                # side isn't building fresh lists per RPC
                qn = len(queries)
                try:
                    while time.monotonic() < stop_at:
                        start = rng.randrange(qn)
                        qs = [
                            queries[(start + j) % qn] for j in range(batch)
                        ]
                        s = time.perf_counter()
                        try:
                            client.check_batch(qs, timeout=60)
                        except Exception:
                            n_err += 1
                            continue
                        done = time.perf_counter()
                        lat.append(done - s)
                        n_checks += batch
                finally:
                    client.close()
                    with lock:
                        rpc_lat.extend(lat)
                        checks[0] += n_checks
                        errors[0] += n_err
                        if done:
                            last_done.append(done)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=worker, args=(i,), daemon=True)
                for i in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if not rpc_lat:
                return {"error": "no successful batch RPCs"}
            wall = max(last_done) - t0
            lat_ms = np.array(rpc_lat) * 1e3
            return {
                "qps": round(checks[0] / wall, 1),
                "rpc_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
                "rpc_p95_ms": round(float(np.percentile(lat_ms, 95)), 2),
                "errors": errors[0],
            }

        # low-concurrency phase first: the latency-respecting operating
        # point (p95 < 10 ms on the 1-core host); then the throughput
        # phase at full closed-loop concurrency
        low = load_phase(8, SERVE_SECONDS / 2)
        high = load_phase(SERVE_THREADS, SERVE_SECONDS)
        # repeated-key (hot) phase: a handful of keys hammered by every
        # client — the serve-side check cache's operating point (Zanzibar
        # §3 hot spots). cache_hit_ratio is measured over exactly this
        # window so the cold phases don't dilute it.
        cache = daemon.registry.check_cache()
        cache_before = cache.stats() if cache is not None else None
        hot = load_phase(SERVE_THREADS, SERVE_SECONDS / 2, qs=queries[:4])
        hot_hit_ratio = None
        if cache_before is not None:
            after = cache.stats()
            hits = after["hit"] - cache_before["hit"]
            lookups = (
                hits
                + after["miss"] - cache_before["miss"]
                + after["stale"] - cache_before["stale"]
            )
            if lookups:
                hot_hit_ratio = round(hits / lookups, 4)
        # batch-RPC phase: warm the batch bucket first
        engine.check_batch(queries[:SERVE_BATCH_SIZE])
        batch_phase = batch_load_phase(
            SERVE_BATCH_CLIENTS, SERVE_BATCH_SIZE, SERVE_SECONDS
        )
        # per-stage serving breakdown accumulated across all phases
        stage_ms = _stage_summary(daemon.registry.metrics())
        # served-path launch telemetry: the daemon's process-wide flight
        # recorder saw every device batch the load phases produced
        from keto_tpu.observability import summarize_launches

        served_launches = summarize_launches(
            daemon.registry.flight_recorder().entries()
        )
        # workload observatory snapshot over the same phases: top-key
        # concentration + live SLO burn state ride the artifact, so a
        # committed bench leg also says WHAT traffic shape it measured
        workload_snapshot = None
        obs = daemon.registry.workload_observatory()
        if obs is not None and obs.enabled:
            hk = obs.hotkeys(top=5)
            workload_snapshot = {
                "hotkey_top_share": {
                    kind: payload["top_share"]
                    for kind, payload in hk["kinds"].items()
                },
                "slo": {
                    name: {
                        "burn_short": o["burn_short"],
                        "fast_burn": o["fast_burn"],
                    }
                    for name, o in obs.slo_status().get(
                        "objectives", {}
                    ).items()
                },
            }
        # replica mode: the per-worker answered-checks breakdown (the
        # plain-int twin of worker_checks_total) — 1-vs-N comparisons
        # read occupancy skew straight from the artifact
        worker_breakdown = None
        if daemon._group is not None:
            group = daemon._group
            counts = {
                str(w.worker_id): int(w.checks_answered)
                for w in group.workers
            }
            total = sum(counts.values()) or 1
            worker_breakdown = {
                "checks": counts,
                "occupancy": {
                    k: round(v / total, 4) for k, v in counts.items()
                },
                "hedge_stats": group.stats()["hedge"],
            }
    finally:
        daemon.stop()

    # asyncio plane (serve.read.grpc.aio): same workload, the no-handoff
    # server architecture — recorded beside the threaded number
    aio = None
    try:
        daemon = make_daemon(aio=True)
        try:
            addr = f"127.0.0.1:{daemon.read_grpc_port}"
            engine = daemon.registry.check_engine()
            engine.check_batch(queries[:1])
            engine.check_batch(queries[: min(SERVE_THREADS + 1, len(queries))])
            warm = ReadClient(open_channel(addr))
            warm.check(queries[0], timeout=300)
            warm.close()
            aio = load_phase(SERVE_THREADS, SERVE_SECONDS / 2)
        finally:
            daemon.stop()
    except Exception as e:  # the aio leg must never sink the bench line
        aio = {"error": f"{type(e).__name__}: {e}"}

    out = {
        "host_cores": len(_os.sched_getaffinity(0)),
        # 1-vs-N replica comparisons are first-class in the artifact:
        # every served leg records how many workers answered it
        "serve_workers": max(int(serve_workers), 1),
    }
    if worker_breakdown is not None:
        out["served_worker_breakdown"] = worker_breakdown
    if stage_ms:
        out["served_stage_ms"] = stage_ms
    if served_launches:
        out["served_launch_telemetry"] = served_launches
    if workload_snapshot is not None:
        out["served_workload"] = workload_snapshot
    # each phase reports independently: a wedge between phases must not
    # discard the completed phase's measurement
    if "error" in low:
        out["served_c8_error"] = low["error"]
    else:
        out["served_c8_qps"] = low["qps"]
        out["served_c8_p95_ms"] = low["p95_ms"]
        out["served_c8_errors"] = low["errors"]
    if "error" in high:
        out["served_error"] = high["error"]
    else:
        out.update({
            "served_qps": high["qps"],
            "served_clients": SERVE_THREADS,
            "served_p50_ms": high["p50_ms"],
            "served_p95_ms": high["p95_ms"],
            "served_p99_ms": high["p99_ms"],
            "served_errors": high["errors"],
        })
    # repeated-key leg: the check-cache hit path under load
    if "error" in hot:
        out["served_hot_error"] = hot["error"]
    else:
        out["served_hot_qps"] = hot["qps"]
        out["served_hot_p95_ms"] = hot["p95_ms"]
        out["served_hot_errors"] = hot["errors"]
    if hot_hit_ratio is not None:
        out["cache_hit_ratio"] = hot_hit_ratio
    if "error" in batch_phase:
        out["served_batch_error"] = batch_phase["error"]
    else:
        out.update({
            "served_batch_qps": batch_phase["qps"],
            "served_batch_size": SERVE_BATCH_SIZE,
            "served_batch_clients": SERVE_BATCH_CLIENTS,
            "served_batch_rpc_p50_ms": batch_phase["rpc_p50_ms"],
            "served_batch_rpc_p95_ms": batch_phase["rpc_p95_ms"],
            "served_batch_errors": batch_phase["errors"],
        })
    if aio is not None:
        if "error" in aio:
            out["served_aio_error"] = aio["error"]
        else:
            out["served_aio_qps"] = aio["qps"]
            out["served_aio_p95_ms"] = aio["p95_ms"]
    # the echo ceiling runs even when a served phase wedged: every leg
    # that DID complete gets its served_vs_echo_ceiling ratio (before
    # PR 4 only the full-concurrency leg of an all-green run carried it)
    out.update(bench_grpc_echo_ceiling())
    ceiling = out.get("echo_ceiling_qps")
    if ceiling:
        for leg, ratio_key in (
            ("served_qps", "served_vs_echo_ceiling"),
            ("served_c8_qps", "served_c8_vs_echo_ceiling"),
            ("served_hot_qps", "served_hot_vs_echo_ceiling"),
            ("served_aio_qps", "served_aio_vs_echo_ceiling"),
            ("served_batch_qps", "served_batch_vs_echo_ceiling"),
        ):
            if out.get(leg):
                out[ratio_key] = round(out[leg] / ceiling, 3)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", choices=("auto", "tpu", "cpu"), default="auto")
    ap.add_argument("--skip-serve", action="store_true")
    ap.add_argument(
        "--serve-workers", type=int,
        default=int(os.environ.get("KETO_BENCH_SERVE_WORKERS", 1)),
        help="replica serve workers for the served legs "
             "(serve.check.workers; 1 = the single-stack daemon) — the "
             "BENCH json records serve_workers + the per-worker "
             "QPS/occupancy breakdown so 1-vs-N compares in-artifact",
    )
    ap.add_argument(
        "--ab-flightrec", action="store_true",
        help="run ONLY the flight-recorder counter-overhead A/B leg "
             "(recorder on vs off QPS + non-degeneracy contrasts) and "
             "print its JSON record",
    )
    ap.add_argument(
        "--ab-filter", action="store_true",
        help="run ONLY the BatchFilter leg (10k-object filter vs the "
             "pipelined check_batch baseline, closure-warm and "
             "closure-cold arms, per-object cost ratio + launch "
             "telemetry) and print its JSON record",
    )
    ap.add_argument(
        "--ab-closure", action="store_true",
        help="run ONLY the Leopard-closure A/B leg (deep-20 QPS with "
             "the closure index on vs off, verdict-equality checked, "
             "plus the flat-contrast acceptance ratio) and print its "
             "JSON record",
    )
    ap.add_argument(
        "--ab-powering", action="store_true",
        help="run ONLY the closure-powering A/B leg (host numpy builder "
             "vs the on-device bit-packed GraphBLAS kernel over the "
             "~1e6-tuple deep topology: bit-identity mismatch counts, "
             "build seconds, and the max_set_rows HBM sweep) and print "
             "its JSON record",
    )
    args = ap.parse_args()

    if args.platform == "cpu":
        # the caller's explicit choice; nothing else selects the CPU
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    platform = jax.devices()[0].platform
    if args.platform != "cpu" and platform != "tpu":
        print(
            f"bench: no TPU (jax.devices() reports {platform!r}); "
            "pass --platform cpu to bench the CPU backend by choice",
            file=sys.stderr,
        )
        return 2

    global BATCH, EXPAND_BATCH
    if not _EXPAND_FROM_ENV and platform == "tpu":
        EXPAND_BATCH = 1024
    if not _BATCH_FROM_ENV and platform == "tpu":
        # a short pipelined burst at each candidate picks the batch that
        # amortizes the launch best. ~1 compile + ~8 launches per candidate.
        BATCH = _calibrate_batch((16384, 32768))["best"]

    record: dict = {
        "metric": "batched_check_qps",
        "value": 0.0,
        "unit": "checks/sec",
        "vs_baseline": 0.0,
        "batch": BATCH,
    }
    try:
        if args.ab_flightrec:
            ab = bench_flightrec_ab()
            ab["device"] = str(jax.devices()[0])
            print(json.dumps(ab))
            return 0

        if args.ab_closure:
            ab = bench_closure_ab()
            ab["device"] = str(jax.devices()[0])
            print(json.dumps(ab))
            return 0

        if args.ab_filter:
            ab = bench_filter()
            ab["device"] = str(jax.devices()[0])
            print(json.dumps(ab))
            return 0

        if args.ab_powering:
            ab = bench_powering_ab()
            ab["device"] = str(jax.devices()[0])
            print(json.dumps(ab))
            return 0

        namespaces, tuples, queries = build_dataset()
        record["tuples"] = len(tuples)

        kernel = bench_kernel(namespaces, tuples, queries)
        record["value"] = kernel.pop("value")
        record["vs_baseline"] = round(record["value"] / NORTH_STAR_QPS, 4)
        record.update(kernel)

        record.update(bench_config3_islands())
        record.update(bench_config3_expand())
        record.update(bench_config4_deep())
        record.update(bench_reverse(namespaces, tuples))
        record.update(bench_filter())
        record.update(bench_watch())

        if not args.skip_serve:
            record.update(
                bench_served(
                    namespaces, tuples, queries,
                    serve_workers=args.serve_workers,
                )
            )

        record["device"] = str(jax.devices()[0])
        print(json.dumps(record))
        return 0
    except Exception as err:  # never a bare traceback: one JSON line, always
        import traceback

        record["error"] = f"{type(err).__name__}: {err}"[:400]
        record["error_site"] = traceback.format_exc().strip().splitlines()[-3:-1]
        print(json.dumps(record))
        return 1


if __name__ == "__main__":
    sys.exit(main())
