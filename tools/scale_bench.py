"""Scale proof: columnar ingest + snapshot build + checks at 1e7 tuples.

The stepping stone to BASELINE config 5 (1e8 @ v5e-8): generates a
drive-style graph (folders with owners, files with parent edges — the
cat-videos topology scaled) ENTIRELY as numpy columns, bulk-loads the
ColumnarStore, times the device-mirror build, and differentially
spot-checks the engine against construction ground truth plus the exact
host reference engine on sampled queries.

    python tools/scale_bench.py [--tuples 10000000] [--platform cpu]

Without `--platform cpu` a run that finds no TPU exits 2.

Prints one JSON line:
  {"tuples", "ingest_s", "snapshot_build_s", "device_table_bytes",
   "check_batch_s", "check_qps", "spot_checks", "spot_failures",
   "ref_spot_checks", "ref_spot_failures", "device"}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def synth_columns(n_target: int, n_users: int, seed: int = 7):
    """Drive-style topology as pure numpy columns: ~n_target tuples of
    which ~1% are folder owners and ~99% file->folder parent edges."""
    from keto_tpu.storage.columns import TupleColumns, concat_columns

    files_per = 80
    n_folders = max(1, n_target // (files_per + 1))
    rng = np.random.default_rng(seed)

    folders = np.arange(n_folders)
    f_names = np.char.add("/d", folders.astype("U10"))
    owners = np.char.add("u", (rng.integers(0, n_users, n_folders)).astype("U10"))

    own = TupleColumns(
        ns=np.full(n_folders, "videos", "U6"),
        obj=f_names,
        rel=np.full(n_folders, "owner", "U6"),
        skind=np.zeros(n_folders, np.int8),
        sns=np.full(n_folders, "", "U1"),
        sobj=owners,
        srel=np.full(n_folders, "", "U1"),
    )
    n_files = n_folders * files_per
    parent_names = np.repeat(f_names, files_per)
    file_names = np.char.add(
        np.char.add(parent_names, "/v"),
        np.tile(np.arange(files_per), n_folders).astype("U3"),
    )
    par = TupleColumns(
        ns=np.full(n_files, "videos", "U6"),
        obj=file_names,
        rel=np.full(n_files, "parent", "U6"),
        skind=np.ones(n_files, np.int8),
        sns=np.full(n_files, "videos", "U6"),
        sobj=parent_names,
        srel=np.full(n_files, "...", "U3"),
    )
    cols = concat_columns([own, par])
    return cols, f_names, owners, files_per


def synth_rbac_columns(n_roles: int, n_users: int, seed: int = 23):
    """RBAC role-membership overlay for the expand leg (VERDICT r03 weak
    item 6: expand had never been measured over 1e7-scale tables): each
    role holds 12 direct user members plus 2 nested-role subject sets,
    so a depth-4 expand assembles ~40-100-node trees. At the default
    n_roles=1000 this adds ~0.14% to a 1e7 dataset — build timings stay
    comparable with the r03 artifacts."""
    from keto_tpu.storage.columns import TupleColumns

    rng = np.random.default_rng(seed)
    members_per = 12
    nested_per = 2
    n_direct = n_roles * members_per
    role_of = np.repeat(np.arange(n_roles), members_per)
    direct = TupleColumns(
        ns=np.full(n_direct, "rbac", "U4"),
        obj=np.char.add("role", role_of.astype("U7")),
        rel=np.full(n_direct, "member", "U6"),
        skind=np.zeros(n_direct, np.int8),
        sns=np.full(n_direct, "", "U1"),
        sobj=np.char.add(
            "u", rng.integers(0, n_users, n_direct).astype("U10")
        ),
        srel=np.full(n_direct, "", "U1"),
    )
    n_nest = n_roles * nested_per
    parent_role = np.repeat(np.arange(n_roles), nested_per)
    # nest only into HIGHER role ids: the membership graph stays acyclic
    child_role = np.minimum(
        parent_role + 1 + rng.integers(0, 97, n_nest), n_roles - 1
    )
    nested = TupleColumns(
        ns=np.full(n_nest, "rbac", "U4"),
        obj=np.char.add("role", parent_role.astype("U7")),
        rel=np.full(n_nest, "member", "U6"),
        skind=np.ones(n_nest, np.int8),
        sns=np.full(n_nest, "rbac", "U4"),
        sobj=np.char.add("role", child_role.astype("U7")),
        srel=np.full(n_nest, "member", "U6"),
    )
    from keto_tpu.storage.columns import concat_columns

    return concat_columns([direct, nested])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tuples", type=int, default=10_000_000)
    ap.add_argument("--users", type=int, default=100_000)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--ref-samples", type=int, default=32)
    ap.add_argument("--platform", choices=("auto", "cpu"), default="auto")
    ap.add_argument(
        "--expand-roles", type=int, default=1000,
        help="RBAC roles overlaid for the expand leg (0 disables both "
        "the overlay and the expand measurements)",
    )
    ap.add_argument("--expand-batch", type=int, default=256)
    ap.add_argument(
        "--mesh", type=int, default=0,
        help="shard the build over an N-device mesh (with --platform cpu "
        "this forces N virtual host devices — the 1e7 sharded-columnar "
        "proof for BASELINE config 5)",
    )
    args = ap.parse_args()
    if args.platform == "cpu":
        # the caller's explicit choice; nothing else selects the CPU
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.mesh:
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags
                    + f" --xla_force_host_platform_device_count={args.mesh}"
                ).strip()

    import jax

    found = jax.devices()[0].platform
    if args.platform != "cpu" and found != "tpu":
        print(
            f"scale_bench: no TPU (jax.devices() reports {found!r}); "
            "pass --platform cpu to run on the CPU backend by choice",
            file=sys.stderr,
        )
        return 2

    from keto_tpu.config import Config
    from keto_tpu.engine import Membership
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.ketoapi import RelationTuple
    from keto_tpu.namespace import Namespace
    from keto_tpu.namespace.ast import (
        ComputedSubjectSet,
        Relation,
        SubjectSetRewrite,
        TupleToSubjectSet,
    )
    from keto_tpu.storage.columnar import ColumnarStore

    record: dict = {"tuples": 0}
    t0 = time.perf_counter()
    cols, f_names, owners, files_per = synth_columns(args.tuples, args.users)
    if args.expand_roles:
        from keto_tpu.storage.columns import concat_columns

        cols = concat_columns(
            [cols, synth_rbac_columns(args.expand_roles, args.users)]
        )
    record["tuples"] = len(cols)
    record["column_bytes"] = cols.nbytes()

    store = ColumnarStore()
    store.bulk_load(cols)
    record["ingest_s"] = round(time.perf_counter() - t0, 2)

    ns = [Namespace(name="videos", relations=[
        Relation(name="owner"),
        Relation(name="parent"),
        Relation(name="view", subject_set_rewrite=SubjectSetRewrite(children=[
            ComputedSubjectSet(relation="owner"),
            TupleToSubjectSet(relation="parent",
                              computed_subject_set_relation="view"),
        ])),
    ]), Namespace(name="rbac", relations=[Relation(name="member")])]
    cfg = Config({"limit": {"max_read_depth": 5}})
    cfg.set_namespaces(ns)
    mesh = None
    if args.mesh:
        from keto_tpu.parallel import default_mesh

        mesh = default_mesh(args.mesh)
        # default_mesh truncates to the devices that exist — record and
        # index by the ACTUAL shard count, not the requested one
        record["mesh_devices"] = int(mesh.devices.size)
    # frontier sized to the query batch like bench.py: the BFS frontier
    # routinely exceeds B (TTU fan-out), and an overflow silently turns
    # the whole batch into host-oracle replays — at --batch 16384 the
    # engine's 1<<14 default measured the HOST, not the chip. The floor
    # keeps small --batch runs at least at the engine default.
    engine = TPUCheckEngine(
        store, cfg, mesh=mesh, frontier_cap=max(1 << 14, 2 * args.batch)
    )

    # snapshot build (timed separately from XLA compile: run a 1-query
    # warm-up AFTER grabbing the build time via _ensure_state)
    t0 = time.perf_counter()
    state = engine._ensure_state()
    record["snapshot_build_s"] = round(time.perf_counter() - t0, 2)
    if mesh is not None:
        # account from the DEVICE arrays (the engine releases the raw
        # host columns during placement); shards are equal-capacity by
        # construction, so per-shard = global sharded bytes / n_shards
        sharded_tables, replicated_tables = state.tables
        n_shards = state.sharded.n_shards
        sharded_bytes = int(sum(v.nbytes for v in sharded_tables.values()))
        replicated_bytes = int(
            sum(v.nbytes for v in replicated_tables.values())
        )
        record["n_shards"] = n_shards
        record["per_shard_bytes"] = sharded_bytes // n_shards
        record["replicated_bytes_per_device"] = replicated_bytes
        # per-device HBM = its shard + a full replicated copy; the total
        # across the mesh pays replicated_bytes on EVERY device
        record["per_device_bytes"] = (
            sharded_bytes // n_shards + replicated_bytes
        )
        record["device_table_bytes"] = (
            sharded_bytes + n_shards * replicated_bytes
        )
    else:
        record["device_table_bytes"] = int(
            sum(
                np.asarray(v).nbytes
                for v in state.snapshot.device_arrays().values()
            )
        )

    # query batch with construction ground truth: half owner-hits
    rng = np.random.default_rng(11)
    B = args.batch
    fi = rng.integers(0, len(f_names), B)
    vi = rng.integers(0, files_per, B)
    hit = rng.random(B) < 0.5
    subs = np.where(hit, owners[fi], np.char.add("nobody", fi.astype("U10")))
    queries = [
        RelationTuple.from_string(
            f"videos:{f_names[fi[i]]}/v{vi[i]}#view@{subs[i]}"
        )
        for i in range(B)
    ]
    # ground truth: owner sees every file in the folder; "nobodyX" never
    # owns anything (the owner vocab is uN)
    want = hit

    # warm the ACTUAL bucket (a [:1] warm-up leaves the B-sized bucket's
    # XLA compile inside the timed region — it cost ~3 s and was 96% of
    # the round-2/3 "scale collapse" at 1e7)
    got = engine.check_batch(queries)
    rounds = 5
    t0 = time.perf_counter()
    handles = [engine.check_batch_submit(queries) for _ in range(rounds)]
    for h in handles:
        engine.check_batch_resolve(h)
    wall = time.perf_counter() - t0
    record["check_batch_s"] = round(wall / rounds, 3)
    record["check_qps"] = round(rounds * B / wall, 1)

    fails = sum(
        1
        for g, w in zip(got, want)
        if (g.membership == Membership.IS_MEMBER) != bool(w)
    )
    record["spot_checks"] = B
    record["spot_failures"] = fails
    record["host_checks"] = engine.stats["host_checks"]

    # exact reference engine on a sample (paginated store reads)
    ref_fails = 0
    for i in rng.integers(0, B, args.ref_samples):
        ref = engine.reference.check_relation_tuple(queries[int(i)], 0)
        if (ref.membership == Membership.IS_MEMBER) != bool(want[int(i)]):
            ref_fails += 1
    record["ref_spot_checks"] = args.ref_samples
    record["ref_spot_failures"] = ref_fails

    # expand leg (VERDICT r03 weak item 6): RBAC trees assembled over the
    # full-scale columnar tier — device subgraph gather + host DFS
    # assembly, with the per-tree host cost and needs_host rate recorded
    expand_fails = 0
    if args.expand_roles:
        from keto_tpu.ketoapi import SubjectSet

        Be = args.expand_batch
        roles = rng.integers(0, args.expand_roles, Be)
        subjects = [
            SubjectSet("rbac", f"role{int(r)}", "member") for r in roles
        ]
        # pool sized for ~100-node trees across the whole batch (the
        # serve default expects ~10); overflow host-replays, which is
        # exact but would dominate the timing
        pool_cap = 128 * Be
        t0 = time.perf_counter()
        trees = engine.expand_batch(subjects, max_depth=4, frontier_cap=8192, pool_cap=pool_cap)
        record["expand_warm_s"] = round(time.perf_counter() - t0, 2)

        def tree_nodes(tr):
            if tr is None:
                return 0
            n = 1
            for c in tr.children or ():
                n += tree_nodes(c)
            return n

        sizes = [tree_nodes(tr) for tr in trees]
        rounds_e = 3
        t0 = time.perf_counter()
        for _ in range(rounds_e):
            engine.expand_batch(subjects, max_depth=4, frontier_cap=8192, pool_cap=pool_cap)
        wall_e = time.perf_counter() - t0
        record["expand_batch"] = Be
        record["expand_qps"] = round(rounds_e * Be / wall_e, 1)
        record["expand_ms_per_tree"] = round(
            wall_e / (rounds_e * Be) * 1e3, 3
        )
        record["expand_tree_nodes_avg"] = round(
            float(np.mean(sizes)), 1
        )
        record["expand_host"] = engine.stats.get("host_expands", 0)
        # differential: one sampled tree against the exact host engine
        i0 = int(rng.integers(0, Be))
        ref_tree = engine.reference.expand(subjects[i0], 4)
        if tree_nodes(ref_tree) != sizes[i0]:
            expand_fails += 1
        record["expand_ref_mismatch"] = expand_fails

    record["device"] = str(jax.devices()[0])
    print(json.dumps(record))
    return 0 if fails == 0 and ref_fails == 0 and expand_fails == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
