"""1e8 north-star, stage 1: shard-streamed build + one-shard HBM proof.

Round 3's two 1e8 attempts OOM-killed the 128 GB host because emulating
8 devices in one address space holds every shard's tables (plus the XLA
runtime's copies) at once. This tool does what a real v5e-8 deployment
does — each chip holds ONE shard — without needing 8 chips:

  --phase build   (CPU, ~30 min): synth 1e8 drive-topology tuples in
      chunks, run the shared vectorized ingest (columnar_encode), FREE
      the string columns, then build each of the 8 shards' edge tables
      one at a time at equal capacities, pack them into the device row
      layout, stream each to disk (raw .npy), and free it before the
      next — peak RSS is one shard, not eight. Also pre-encodes a
      query batch with construction ground truth (owner hit/miss on
      shard-0 objects) so the TPU phase needs no vocabulary in memory.

  --phase tpu     (one real chip): load shard 0 + the replicated
      tables, device_put onto the TPU (the real HBM residency test —
      ~3.6 GB projected per chip at 1e8), run check_kernel_packed on
      the pre-encoded queries, and compare against ground truth.

Single-shard scope: only queries whose OBJECT lives on shard 0 are
dispatched, and the drive graph resolves folder-owner checks with one
direct probe — fully shard-local. TTU view checks span shards (file
row on one, folder owner on another) and are exactly what the 8-chip
mesh kernel's all_gather handles (tests/test_sharded.py); they are out
of scope for a one-chip residency proof.

Artifacts: SCALE_1e8_BUILD_r04.json (build phase),
SCALE_1e8_TPU_r04.json (tpu phase). Shard files land in
--out (default /tmp/keto_1e8_shards), ~2.6 GB per shard.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_SHARDS = 8


def _namespaces():
    from keto_tpu.namespace import Namespace
    from keto_tpu.namespace.ast import (
        ComputedSubjectSet,
        Relation,
        SubjectSetRewrite,
        TupleToSubjectSet,
    )

    return [Namespace(name="videos", relations=[
        Relation(name="owner"),
        Relation(name="parent"),
        Relation(name="view", subject_set_rewrite=SubjectSetRewrite(children=[
            ComputedSubjectSet(relation="owner"),
            TupleToSubjectSet(relation="parent",
                              computed_subject_set_relation="view"),
        ])),
    ])]


def build_phase(args) -> int:
    from tools.scale_bench import synth_columns
    from keto_tpu.engine.kernel import pack_raw_tables
    from keto_tpu.engine.snapshot import (
        build_edge_tables,
        columnar_encode,
        table_capacity,
    )
    from keto_tpu.parallel.sharding import shard_of_objslot
    from keto_tpu.storage.columns import concat_columns

    os.makedirs(args.out, exist_ok=True)
    record: dict = {"phase": "build", "n_shards": N_SHARDS}
    t_all = time.perf_counter()

    # -- synth in chunks (one giant synth would double-buffer ~46 GB) ----
    t0 = time.perf_counter()
    chunks = []
    per = args.tuples // 8
    for i in range(8):
        c, _, _, _ = synth_columns(per, args.users, seed=100 + i)
        # distinct folder namespace per chunk (synth reuses /dN names):
        # prefix both the object and subject-set-object columns so the
        # 1e8 graph is 1e8 DISTINCT tuples, not 8 copies of 1.25e7
        c.obj = np.char.add(f"/c{i}", c.obj)
        is_set = c.skind == 1
        sobj = c.sobj.astype(f"U{c.sobj.dtype.itemsize // 4 + 4}")
        sobj[is_set] = np.char.add(f"/c{i}", c.sobj[is_set])
        c.sobj = sobj
        chunks.append(c)
    cols = concat_columns(chunks)
    del chunks
    gc.collect()
    record["tuples"] = len(cols)
    record["column_bytes"] = int(cols.nbytes())
    record["synth_s"] = round(time.perf_counter() - t0, 1)
    print(json.dumps({"step": "synth", **record}), flush=True)

    t0 = time.perf_counter()
    snap, (t_obj, t_rel, t_skind, t_sa, t_sb) = columnar_encode(
        cols, _namespaces(), K=8, version=1
    )
    record["encode_s"] = round(time.perf_counter() - t0, 1)
    # ground-truth query material BEFORE freeing columns: folder owner
    # rows are the first len/81-ish rows per chunk; recover pairs from
    # the encoded arrays instead (skind==0 rows are owner edges)
    del cols
    gc.collect()
    print(json.dumps({"step": "encode", "encode_s": record["encode_s"]}),
          flush=True)

    # -- equal shard capacities ------------------------------------------
    shard = shard_of_objslot(t_obj, N_SHARDS)
    counts = np.bincount(shard, minlength=N_SHARDS)
    set_counts = np.bincount(
        shard[t_skind == 1], minlength=N_SHARDS
    )
    dh_cap = max(table_capacity(int(c)) for c in counts)
    rh_cap = max(table_capacity(int(c)) for c in set_counts)
    record["edges_per_shard"] = counts.tolist()
    record["dh_cap"] = int(dh_cap)
    record["rh_cap"] = int(rh_cap)

    # -- queries with ground truth (shard 0 owner rows) ------------------
    rng = np.random.default_rng(5)
    own_rows = np.flatnonzero((t_skind == 0) & (shard == 0))
    pick = rng.choice(own_rows, size=args.batch, replace=True)
    hit = rng.random(args.batch) < 0.5
    q_obj = t_obj[pick].astype(np.int32)
    q_rel = t_rel[pick].astype(np.int32)
    q_sa = np.where(hit, t_sa[pick], -2).astype(np.int32)  # -2: no match
    qpack = np.stack([
        q_obj, q_rel, np.full(args.batch, 5, np.int32),
        np.zeros(args.batch, np.int32), q_sa,
        np.zeros(args.batch, np.int32),
        np.ones(args.batch, np.int32),
    ]).astype(np.int32)
    np.save(os.path.join(args.out, "qpack.npy"), qpack)
    np.save(os.path.join(args.out, "want.npy"), hit)

    # rewrite-bearing leg (VERDICT r4 item 3: the 1e8 capture had only
    # direct probes): VIEW on the same folder objects exercises the
    # compiled computed-subject-set instruction + rh span probes + the
    # full deny-exhaustion path at 1e8 table scale. Ground truth stays
    # constructible: restrict to folders with NO parent row, where
    # view == owner exactly (the TTU branch finds no row).
    view_rel = snap.rel_ids["view"]
    parent_rel = snap.rel_ids["parent"]
    parent_objs = np.unique(t_obj[t_rel == parent_rel])
    vq = ~np.isin(q_obj, parent_objs)
    qpack_view = qpack.copy()
    qpack_view[1] = view_rel
    qpack_view[6] = vq.astype(np.int32)  # only parent-free rows valid
    np.save(os.path.join(args.out, "qpack_view.npy"), qpack_view)
    np.save(os.path.join(args.out, "want_view.npy"), hit & vq)
    record["view_queries"] = int(vq.sum())

    # -- per-shard build, stream, free -----------------------------------
    shard_bytes = 0
    build_s = []
    for s in range(N_SHARDS):
        t0 = time.perf_counter()
        m = shard == s
        tables = build_edge_tables(
            t_obj[m], t_rel[m], t_skind[m], t_sa[m], t_sb[m],
            dh_min_cap=dh_cap, rh_min_cap=rh_cap,
        )
        probes = {
            "dh_probes": int(tables.pop("dh_probes")),
            "rh_probes": int(tables.pop("rh_probes")),
        }
        packed = pack_raw_tables(tables)
        if s == 0:
            record["shard0_probes"] = probes
        out = os.path.join(args.out, f"shard{s}.npz")
        # uncompressed: int32 hash tables barely compress and the write
        # must not dominate the build
        np.savez(out, **packed)
        nbytes = int(sum(v.nbytes for v in packed.values()))
        shard_bytes = max(shard_bytes, nbytes)
        del tables, packed
        gc.collect()
        build_s.append(round(time.perf_counter() - t0, 1))
        print(json.dumps({"step": "shard", "shard": s,
                          "build_s": build_s[-1],
                          "bytes": nbytes, **probes}), flush=True)

    # -- replicated tables + statics -------------------------------------
    from keto_tpu.engine.kernel import pack_instr_table

    arrays = snap.device_arrays()
    repl = {k: arrays[k] for k in (
        "objslot_ns", "ns_has_config", "prog_flags",
    )}
    repl["instr_pack"] = pack_instr_table(
        arrays["instr_kind"], arrays["instr_rel"], arrays["instr_rel2"]
    )
    np.savez(os.path.join(args.out, "replicated.npz"), **repl)
    statics = {
        "K": snap.K,
        "n_config_rels": snap.n_config_rels,
        "wildcard_rel": snap.wildcard_rel,
        "n_tuples": int(len(t_obj)),
        "dh_probes": record["shard0_probes"]["dh_probes"],
        "rh_probes": record["shard0_probes"]["rh_probes"],
        "batch": args.batch,
    }
    with open(os.path.join(args.out, "statics.json"), "w") as f:
        json.dump(statics, f)

    record["per_shard_build_s"] = build_s
    record["per_shard_bytes"] = shard_bytes
    record["replicated_bytes"] = int(sum(v.nbytes for v in repl.values()))
    record["per_device_bytes"] = shard_bytes + record["replicated_bytes"]
    record["total_s"] = round(time.perf_counter() - t_all, 1)
    print(json.dumps(record), flush=True)
    return 0


def tpu_phase(args) -> int:
    import jax

    from keto_tpu.engine.delta import empty_delta_tables
    from keto_tpu.engine.kernel import (
        check_kernel_packed,
        device_tables,
        pack_delta_tables,
    )

    record: dict = {"phase": "tpu"}
    with open(os.path.join(args.out, "statics.json")) as f:
        st = json.load(f)
    dev = jax.devices()[0]
    record["device"] = str(dev)
    if dev.platform != "tpu":
        print(json.dumps({**record, "error": "not a TPU device"}))
        return 2

    t0 = time.perf_counter()
    shard = dict(np.load(os.path.join(args.out, "shard0.npz")))
    repl = dict(np.load(os.path.join(args.out, "replicated.npz")))
    record["load_s"] = round(time.perf_counter() - t0, 1)

    tables_np = {**shard, **repl, **pack_delta_tables(empty_delta_tables())}
    host_bytes = int(sum(v.nbytes for v in tables_np.values()))
    t0 = time.perf_counter()
    # the probe tables are stored as bucket rows and placed row-major
    tables = device_tables(tables_np)
    jax.block_until_ready(list(tables.values()))
    record["device_put_s"] = round(time.perf_counter() - t0, 1)
    record["device_table_bytes"] = host_bytes
    del tables_np, shard, repl
    gc.collect()
    try:
        stats = dev.memory_stats()
        record["hbm_bytes_in_use"] = int(stats.get("bytes_in_use", 0))
        record["hbm_limit_bytes"] = int(
            stats.get("bytes_limit", stats.get("bytes_reservable_limit", 0))
        )
    except Exception:
        pass

    qpack = np.load(os.path.join(args.out, "qpack.npy"))
    want = np.load(os.path.join(args.out, "want.npy"))
    B = st["batch"]
    statics = dict(
        K=st["K"], dh_probes=st["dh_probes"], rh_probes=st["rh_probes"],
        max_steps=5 + st["n_config_rels"] + 4,
        wildcard_rel=st["wildcard_rel"],
        n_config_rels=max(st["n_config_rels"], 1),
        frontier_cap=2 * B, n_island_cap=0, has_delta=False,
    )
    t0 = time.perf_counter()
    flat = np.asarray(check_kernel_packed(tables, qpack, **statics))
    record["first_launch_s"] = round(time.perf_counter() - t0, 1)
    got = flat[1 : 1 + B].astype(bool)
    needs_host = flat[1 + B : 1 + 2 * B]
    fails = int((got != want).sum())
    record["spot_checks"] = int(B)
    record["spot_failures"] = fails
    record["needs_host"] = int((needs_host > 0).sum())

    # pipelined steady-state rate at this table size (window 8)
    rounds = 16
    t0 = time.perf_counter()
    pending = []
    for _ in range(rounds):
        pending.append(check_kernel_packed(tables, qpack, **statics))
        if len(pending) > 8:
            np.asarray(pending.pop(0))
    for h in pending:
        np.asarray(h)
    wall = time.perf_counter() - t0
    record["check_qps"] = round(rounds * B / wall, 1)
    record["n_tuples"] = st["n_tuples"]

    # rewrite-bearing leg (computed-subject-set via the view relation)
    vq_path = os.path.join(args.out, "qpack_view.npy")
    if os.path.exists(vq_path):
        qpack_v = np.load(vq_path)
        want_v = np.load(os.path.join(args.out, "want_view.npy"))
        valid_v = qpack_v[6].astype(bool)
        flat = np.asarray(check_kernel_packed(tables, qpack_v, **statics))
        got_v = flat[1 : 1 + B].astype(bool)
        nh_v = flat[1 + B : 1 + 2 * B]
        record["view_spot_checks"] = int(valid_v.sum())
        record["view_spot_failures"] = int(
            ((got_v != want_v) & valid_v & (nh_v == 0)).sum()
        )
        record["view_needs_host"] = int(((nh_v > 0) & valid_v).sum())
        t0 = time.perf_counter()
        pending = []
        for _ in range(rounds):
            pending.append(check_kernel_packed(tables, qpack_v, **statics))
            if len(pending) > 8:
                np.asarray(pending.pop(0))
        for h in pending:
            np.asarray(h)
        record["view_check_qps"] = round(
            rounds * B / (time.perf_counter() - t0), 1
        )
        fails += record["view_spot_failures"]
    print(json.dumps(record), flush=True)
    return 0 if fails == 0 else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=("build", "tpu"), required=True)
    ap.add_argument("--tuples", type=int, default=100_000_000)
    ap.add_argument("--users", type=int, default=1_000_000)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--out", default="/tmp/keto_1e8_shards")
    args = ap.parse_args()
    if args.phase == "build":
        # the build is pure host numpy, but importing the kernel module
        # creates a jnp scalar, which initializes the default backend.
        # A chip belongs to one process at a time and the build needs
        # none: keep it on the CPU BEFORE any keto_tpu import.
        import jax

        jax.config.update("jax_platforms", "cpu")
        return build_phase(args)
    return tpu_phase(args)


if __name__ == "__main__":
    sys.exit(main())
