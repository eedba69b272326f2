#!/usr/bin/env python3
"""chip_smoke.py: serve a drive store from the chip through the daemon,
and prove that the chip answered and not a host fallback.

    python chip_smoke.py            one chip: load, serve, compare, count
    python chip_smoke.py --mesh4    four chips: the sharded path only

One process owns the chip: it builds the store, starts the daemon the way
`keto-tpu serve` does, and drives it through the gRPC clients (which stay
off JAX) from threads of its own. It starts no child process.

It prints one JSON line per phase and, as its last line, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as `jax.devices()` reports it. Any phase that raises, one
answer that differs from the oracle, or one device failure that a host
fallback covered up ends the run with a non-zero exit and no such line.

The daemon keeps answering when the device fails (api/batcher.py,
engine/tpu_engine.py): right for a product, and exactly what would let a
chip path that never compiled pass a correctness smoke from the host. So
the counters that record those fallbacks are read and must be zero.

On a machine without a TPU the script refuses to run. The one exception
is the rehearsal of /opt/skills/guides/on-chip-measurement section 2: the
caller sets JAX_PLATFORMS=cpu AND names a rehearsal size with --tuples
(and, for --mesh4, gives the CPU four devices through XLA_FLAGS). With no
arguments anything but a TPU is refused before any work is done.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
import urllib.parse
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# tools/ holds the drive topology (scale_bench) and the differential tier
sys.path[:0] = [ROOT, os.path.join(ROOT, "tools")]

# The round size the smoke, the compile tests and the benchmark's two
# configurations share: the probe tables hold 8n slots rounded up to a
# power of two, 0.80 GB on the chip as 64-lane bucket rows. No launch
# copies them any more (ROADMAP S3, done), so a larger store is a matter
# of memory and of a new configuration (R1), not of this constant.
DEFAULT_TUPLES = 1_000_000
DEFAULT_SEED = 7
RPC_TIMEOUT_S = 600.0
EXPAND_DEPTH = 4
SINGLES = 384  # concurrent single checks: enough for the batcher to batch
BATCH = 2048  # items of one BatchCheck RPC
REF_SAMPLES = 32  # answers per verb also compared with engine/reference.py


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def emit(phase: str, t0: float, **fields) -> None:
    line = {"phase": phase, "seconds": round(time.perf_counter() - t0, 3)}
    line.update(fields)
    print(json.dumps(line), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- phase 1: the device -------------------------------------------------------


def phase_device(args) -> dict:
    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    rehearsal = device["platform"] != "tpu"
    if rehearsal and not (
        os.environ.get("JAX_PLATFORMS") == "cpu" and args.tuples is not None
    ):
        raise SmokeFailure(
            f"no TPU: jax.devices() reports {device}. A CPU run is only a "
            "rehearsal: set JAX_PLATFORMS=cpu and pass --tuples"
        )
    want = 4 if args.mesh4 else 1
    require(
        device["count"] >= want,
        f"{want} device(s) needed, jax.devices() has {device['count']}",
    )
    emit("device", t0, rehearsal=rehearsal, **device)
    return device


# -- the drive store -----------------------------------------------------------


@dataclass
class Drive:
    """The scale_bench drive topology plus its construction ground truth:
    folder i (`f_names[i]`) is owned by `owners[i]` (and by `co<i>` for
    i < n_co), holds `files_per` files `<folder>/v<j>`, and `view` on a
    file or folder is exactly ownership of the folder."""

    cols: object
    f_names: np.ndarray
    owners: np.ndarray
    n_co: int
    files_per: int
    n_roles: int

    def owners_of(self, folder: int) -> list[str]:
        out = [str(self.owners[folder])]
        if folder < self.n_co:
            out.append(f"co{folder}")
        return sorted(out)

    def visible_to(self, user: str) -> list[str]:
        """Sorted objects `user` can view: each owned folder and its files."""
        folders = [int(i) for i in np.flatnonzero(self.owners == user)]
        if user.startswith("co") and user[2:].isdigit() and int(user[2:]) < self.n_co:
            folders.append(int(user[2:]))
        out = []
        for i in folders:
            name = str(self.f_names[i])
            out.append(name)
            out.extend(f"{name}/v{j}" for j in range(self.files_per))
        return sorted(out)


def build_drive(seed: int, tuples: int) -> Drive:
    """`tuples` relation tuples in all: folder owners and file->folder
    parent edges (tools/scale_bench.synth_columns), the RBAC overlay that
    gives expand real trees (synth_rbac_columns), and co-owners on the
    first few folders to make the count exact."""
    from scale_bench import synth_columns, synth_rbac_columns

    from keto_tpu.storage.columnar import _identity_keys
    from keto_tpu.storage.columns import TupleColumns, concat_columns

    n_users = max(100, tuples // 100)
    n_roles = max(16, min(1000, tuples // 1000))
    rbac = synth_rbac_columns(n_roles, n_users, seed=seed + 16)
    # a role may draw one member twice: keep the tuples the store will keep
    rbac = rbac.take(np.sort(np.unique(_identity_keys(rbac), return_index=True)[1]))
    drive, f_names, owners, files_per = synth_columns(
        tuples - len(rbac), n_users, seed=seed
    )
    n_co = tuples - len(rbac) - len(drive)
    require(0 <= n_co <= len(f_names), f"cannot make {tuples} tuples exact")
    co = TupleColumns(
        ns=np.full(n_co, "videos", "U6"),
        obj=f_names[:n_co],
        rel=np.full(n_co, "owner", "U6"),
        skind=np.zeros(n_co, np.int8),
        sns=np.full(n_co, "", "U1"),
        sobj=np.char.add("co", np.arange(n_co).astype("U10")),
        srel=np.full(n_co, "", "U1"),
    )
    cols = concat_columns([drive, rbac, co])
    return Drive(cols, f_names, owners, n_co, files_per, n_roles)


def drive_config(serve: bool):
    from keto_tpu.config import Config
    from keto_tpu.namespace import Namespace
    from keto_tpu.namespace.ast import (
        ComputedSubjectSet,
        Relation,
        SubjectSetRewrite,
        TupleToSubjectSet,
    )

    values: dict = {"dsn": "columnar"}
    if serve:
        loopback = {"host": "127.0.0.1", "port": 0}
        values["serve"] = {
            "read": dict(loopback), "write": dict(loopback),
            "metrics": dict(loopback),
        }
    cfg = Config(values)
    cfg.set_namespaces([
        Namespace(name="videos", relations=[
            Relation(name="owner"),
            Relation(name="parent"),
            Relation(name="view", subject_set_rewrite=SubjectSetRewrite(
                children=[
                    ComputedSubjectSet(relation="owner"),
                    TupleToSubjectSet(
                        relation="parent",
                        computed_subject_set_relation="view",
                    ),
                ]
            )),
        ]),
        Namespace(name="rbac", relations=[Relation(name="member")]),
    ])
    return cfg


def view_queries(drive: Drive, rng, n: int):
    """n distinct `view` checks on files, about half of them allowed, with
    the answers the construction fixes: an owner of the folder sees every
    file in it, and `nobody<i>` owns nothing."""
    from keto_tpu.ketoapi import RelationTuple

    folder = rng.integers(0, len(drive.f_names), n)
    file = rng.integers(0, drive.files_per, n)
    want = rng.random(n) < 0.5
    queries = []
    for i in range(n):
        subject = (
            drive.owners[folder[i]] if want[i] else f"nobody{i}-{folder[i]}"
        )
        queries.append(RelationTuple.from_string(
            f"videos:{drive.f_names[folder[i]]}/v{file[i]}#view@{subject}"
        ))
    return queries, [bool(w) for w in want]


def tree_dict(tree):
    return None if tree is None else tree.to_dict()


def device_memory() -> dict | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats:
        return None
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size")
    return {k: int(stats[k]) for k in keep if k in stats}


def cache_entries(cache_dir: str) -> set[str]:
    return {
        os.path.basename(p) for p in glob.glob(os.path.join(cache_dir, "*-cache"))
    }


# -- phase 2: load and serve ---------------------------------------------------


def phase_load(drive_seconds: float, drive: Drive):
    """The store behind Registry + Daemon as `keto-tpu serve` composes them
    (cli.cmd_serve), on loopback ports, every default plane on."""
    from keto_tpu import native
    from keto_tpu.api.daemon import Daemon
    from keto_tpu.registry import Registry

    t0 = time.perf_counter()
    registry = Registry(drive_config(serve=True))
    registry.relation_tuple_manager().bulk_load(drive.cols)
    bulk_load_s = time.perf_counter() - t0
    daemon = Daemon(registry)
    daemon.start()
    t1 = time.perf_counter()
    engine = registry.check_engine()
    state = engine._ensure_state()
    snapshot_build_s = time.perf_counter() - t1
    emit(
        "load", t0,
        tuples=len(drive.cols),
        snapshot_tuples=int(state.snapshot.n_tuples),
        synth_s=round(drive_seconds, 3),
        bulk_load_s=round(bulk_load_s, 3),
        snapshot_build_s=round(snapshot_build_s, 3),
        snapshot_hbm_bytes=int(
            scrape(daemon).value("keto_tpu_snapshot_hbm_bytes")
        ),
        table_shapes={
            k: list(v.shape) for k, v in state.tables.items()
            if k in ("dh_pack", "rh_pack", "e_pack")
        },
        native_encoder_loaded=native._load() is not None,
        memory_stats=device_memory(),
    )
    require(
        state.snapshot.n_tuples == len(drive.cols),
        "the device mirror does not hold every loaded tuple",
    )
    return daemon, engine


class Scrape:
    """One read of GET /metrics/prometheus."""

    def __init__(self, text: str):
        from prometheus_client.parser import text_string_to_metric_families

        self.samples = [
            sample
            for family in text_string_to_metric_families(text)
            for sample in family.samples
        ]

    def by_label(self, name: str, label: str) -> dict:
        return {
            s.labels.get(label, ""): s.value for s in self.samples if s.name == name
        }

    def value(self, name: str) -> float:
        return sum(s.value for s in self.samples if s.name == name)


def http_get(port: int, path: str) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=RPC_TIMEOUT_S
    ) as resp:
        return resp.read().decode()


def scrape(daemon) -> Scrape:
    return Scrape(http_get(daemon.metrics_port, "/metrics/prometheus"))


# -- phase 4: nothing hid the device -------------------------------------------


def check_device_served(
    daemon, engine, stage: str, flat_checks: int = 0, replays_ok: bool = False
) -> None:
    """Fail unless the device answered: no failed batch, the breaker closed
    and never moved, and no query replayed on the host. `flat_checks` also
    demands that many checks counted on the device path. `replays_ok` is
    for reads of freshly written rows, which the engine may replay by
    design (cause `dirty_row`): their by-cause counts are printed only."""
    t0 = time.perf_counter()
    s = scrape(daemon)
    failed = s.by_label("keto_tpu_check_batch_failed_total", "cause")
    transitions = s.by_label("keto_tpu_breaker_transitions_total", "to")
    breaker = s.value("keto_tpu_breaker_state")
    fallback = s.by_label("keto_tpu_host_fallback_total", "cause")
    paths = s.by_label("keto_tpu_checks_total", "path")
    filtered = s.by_label("keto_tpu_filter_objects_total", "path")
    host_legs = {
        k: int(v) for k, v in engine.stats.items()
        if k in ("host_checks", "host_expands", "host_list_objects",
                 "host_list_subjects", "filter_host")
    }
    emit(
        "counters", t0, after=stage,
        check_batch_failed_total=failed,
        breaker_state=breaker,
        breaker_transitions_total=transitions,
        host_fallback_total=fallback,
        checks_total=paths,
        filter_objects_total=filtered,
        engine_host_replays=host_legs,
        engine_host_causes=dict(engine.stats.get("host_cause", {})),
    )
    require(
        not any(failed.values()),
        f"device batches failed and the host answered for them: {failed}",
    )
    require(
        breaker == 0 and not any(transitions.values()),
        f"the device breaker moved: state={breaker} {transitions}",
    )
    require(
        paths.get("device", 0) >= flat_checks,
        f"{flat_checks} flat checks sent, the device path counted {paths}",
    )
    if not replays_ok:
        require(
            not any(fallback.values()) and not paths.get("host")
            and not any(host_legs.values()),
            "queries built for the device were replayed on the host: "
            f"{fallback} {paths} {host_legs}",
        )


def check_launch_kinds(daemon) -> None:
    """Every verb left launches of its own kernel in the flight recorder."""
    t0 = time.perf_counter()
    launches, check_ms = {}, {}
    for kind in ("check", "expand", "list_objects", "list_subjects", "filter"):
        doc = json.loads(
            http_get(daemon.metrics_port, f"/admin/flightrec?kind={kind}")
        )
        entries = doc.get("entries", [])
        launches[kind] = len(entries)
        if kind == "check":
            for bucket in sorted({e["bucket"] for e in entries}):
                rung = [e for e in entries if e["bucket"] == bucket]
                check_ms[bucket] = {
                    "launches": len(rung),
                    "frontier_cap": sorted({e["frontier_cap"] for e in rung}),
                    **{
                        f"{ms}_median": float(np.median([e[ms] for e in rung]))
                        for ms in ("wall_ms", "device_ms")
                    },
                }
    emit("launches", t0, flightrec_entries=launches,
         check_launch_by_bucket=check_ms)
    require(
        all(launches.values()),
        f"a verb never launched its kernel: {launches}",
    )


# -- phase 3: requests ---------------------------------------------------------


def phase_requests(args, drive: Drive, daemon, engine) -> None:
    from keto_tpu.api.client import ReadClient, WriteClient, open_channel
    from keto_tpu.ketoapi import RelationTuple, SubjectSet

    rng = np.random.default_rng(args.seed + 1)
    reference = engine.reference
    read = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
    write = WriteClient(open_channel(f"127.0.0.1:{daemon.write_port}"))
    mismatches: list[str] = []

    def compare(what: str, got, want) -> None:
        if got != want:
            mismatches.append(f"{what}: got {got!r}, want {want!r}")

    def settle() -> None:
        require(not mismatches, "\n".join(mismatches[:10]))

    def compare_with_reference(what: str, queries, got, sample: int) -> None:
        for i in rng.choice(len(queries), min(sample, len(queries)), replace=False):
            ref = reference.check_relation_tuple(queries[int(i)], 0)
            compare(f"{what} vs reference {queries[int(i)]}", got[int(i)], ref.allowed)

    # single checks: one alone (it pays the first compile), then a few
    # hundred at once so that the batcher forms real batches
    t0 = time.perf_counter()
    singles, want_singles = view_queries(drive, rng, 1 + SINGLES)
    got_first = read.check(singles[0], timeout=RPC_TIMEOUT_S)
    first_check_s = time.perf_counter() - t0
    before = scrape(daemon)
    t1 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=64) as pool:
        got_rest = list(pool.map(
            lambda q: read.check(q, timeout=RPC_TIMEOUT_S), singles[1:]
        ))
    concurrent_s = time.perf_counter() - t1
    after = scrape(daemon)
    got_singles = [got_first] + got_rest
    compare("single checks", got_singles, want_singles)
    compare_with_reference("single check", singles, got_singles, REF_SAMPLES)
    launches = (
        after.value("keto_tpu_check_batch_size_count")
        - before.value("keto_tpu_check_batch_size_count")
    )
    emit(
        "single_checks", t0, n=len(singles),
        first_check_s=round(first_check_s, 3),
        concurrent_s=round(concurrent_s, 3),
        launches=int(launches),
        mean_batch=round(SINGLES / max(launches, 1), 1),
        mismatches=len(mismatches),
    )
    settle()
    require(launches < SINGLES, "the batcher formed no batch")
    # the batcher answers from the host when a launch fails, so right
    # answers say nothing yet: read the counters before going on
    check_device_served(daemon, engine, "single checks", flat_checks=len(singles))

    # one 2,048-item BatchCheck RPC, then a second with other items
    t0 = time.perf_counter()
    batch, want_batch = view_queries(drive, rng, 2 * BATCH)
    got_batch = read.check_batch(batch[: BATCH], timeout=RPC_TIMEOUT_S)
    first_batch_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    got_batch += read.check_batch(batch[BATCH :], timeout=RPC_TIMEOUT_S)
    second_batch_s = time.perf_counter() - t1
    compare("batch errors", [e for _, e in got_batch if e], [])
    got_batch = [allowed for allowed, _ in got_batch]
    compare("batch checks", got_batch, want_batch)
    compare_with_reference("batch check", batch, got_batch, REF_SAMPLES)
    emit(
        "check_batch", t0, n=len(batch),
        first_batch_s=round(first_batch_s, 3),
        second_batch_s=round(second_batch_s, 3),
        mismatches=len(mismatches),
    )
    settle()

    n_flat = len(singles) + len(batch)
    check_device_served(daemon, engine, "flat checks", flat_checks=n_flat)

    # one REST GET on the read port
    t0 = time.perf_counter()
    q = singles[0]
    body = json.loads(http_get(
        daemon.read_port,
        "/relation-tuples/check/openapi?" + urllib.parse.urlencode({
            "namespace": q.namespace, "object": q.object,
            "relation": q.relation, "subject_id": q.subject_id,
        }),
    ))
    compare("REST check", body.get("allowed"), want_singles[0])
    emit("rest_check", t0, allowed=body.get("allowed"))

    # expand: RBAC role trees against the reference's trees
    t0 = time.perf_counter()
    nodes = 0
    first_expand_s = None
    for role in rng.choice(drive.n_roles, 8, replace=False):
        subject = SubjectSet("rbac", f"role{int(role)}", "member")
        got = tree_dict(read.expand(subject, EXPAND_DEPTH, timeout=RPC_TIMEOUT_S))
        if first_expand_s is None:
            first_expand_s = time.perf_counter() - t0
        want = tree_dict(reference.expand(subject, EXPAND_DEPTH))
        compare(f"expand {subject}", got, want)
        nodes += json.dumps(want).count('"type"')
    emit("expand", t0, n=8, tree_nodes=nodes,
         first_expand_s=round(first_expand_s, 3), mismatches=len(mismatches))

    # list-objects, every page: two owners and a subject that owns nothing
    t0 = time.perf_counter()
    users = [str(drive.owners[int(i)]) for i in rng.integers(0, len(drive.owners), 2)]
    if drive.n_co:
        users.append("co0")
    users.append("nobody-at-all")
    listed = 0
    first_list_s = None
    for user in users:
        got, token = [], ""
        while True:
            page, token, _ = read.list_objects(
                "videos", "view", user, page_token=token, timeout=RPC_TIMEOUT_S
            )
            if first_list_s is None:
                first_list_s = time.perf_counter() - t0
            got.extend(page)
            if not token:
                break
        compare(f"list_objects {user}", got, drive.visible_to(user))
        listed += len(got)
    emit("list_objects", t0, n=len(users), objects=listed,
         first_list_s=round(first_list_s, 3), mismatches=len(mismatches))

    # list-subjects: a file's viewers are its folder's owners
    t0 = time.perf_counter()
    folders = [0] + [int(i) for i in rng.integers(0, len(drive.f_names), 3)]
    first_list_s = None
    for folder in folders:
        got, _, _ = read.list_subjects(
            "videos", f"{drive.f_names[folder]}/v1", "view", timeout=RPC_TIMEOUT_S
        )
        if first_list_s is None:
            first_list_s = time.perf_counter() - t0
        compare(f"list_subjects folder {folder}", got, drive.owners_of(folder))
    emit("list_subjects", t0, n=len(folders),
         first_list_s=round(first_list_s, 3), mismatches=len(mismatches))

    # filter: one owner against the files of their folder, of another
    # folder, and names that do not exist
    t0 = time.perf_counter()
    home, other = (int(i) for i in rng.choice(len(drive.f_names), 2, replace=False))
    owner = str(drive.owners[home])
    candidates, want_filter = [], []
    for folder in (home, other):
        name = str(drive.f_names[folder])
        seen = str(drive.owners[folder]) == owner
        candidates.append(name)
        want_filter.append(seen)
        for j in range(drive.files_per):
            candidates.extend([f"{name}/v{j}", f"{name}/ghost{j}"])
            want_filter.extend([seen, False])
    order = rng.permutation(len(candidates))
    candidates = [candidates[i] for i in order]
    want_allowed = [candidates[k] for k, i in enumerate(order) if want_filter[i]]
    got_allowed, _ = read.filter(
        "videos", "view", owner, candidates, timeout=RPC_TIMEOUT_S
    )
    compare("filter", got_allowed, want_allowed)
    sample = [candidates[int(i)] for i in rng.choice(len(candidates), REF_SAMPLES)]
    ref_verdicts = reference.filter_objects("videos", "view", owner, sample)
    compare(
        "filter vs reference",
        [c in set(got_allowed) for c in sample], ref_verdicts,
    )
    emit("filter", t0, candidates=len(candidates), allowed=len(got_allowed),
         mismatches=len(mismatches))
    settle()

    check_device_served(daemon, engine, "expand, list, filter", flat_checks=n_flat)
    check_launch_kinds(daemon)

    # a write, then a check pinned to its snaptoken: read your write
    t0 = time.perf_counter()
    folder = str(drive.f_names[int(rng.integers(0, len(drive.f_names)))])
    newcomer = RelationTuple.from_string(f"videos:{folder}/v3#view@smoke-writer")
    compare("check before the write", read.check(newcomer, timeout=RPC_TIMEOUT_S), False)
    tokens = write.transact(
        insert=[RelationTuple.from_string(f"videos:{folder}#owner@smoke-writer")],
        timeout=RPC_TIMEOUT_S,
    )
    require(bool(tokens and tokens[0]), "transact returned no snaptoken")
    t1 = time.perf_counter()
    allowed, _ = read.check_with_token(
        newcomer, snaptoken=tokens[0], timeout=RPC_TIMEOUT_S
    )
    pinned_check_s = time.perf_counter() - t1
    compare("pinned check after the write", allowed, True)
    emit("read_your_write", t0, snaptoken=tokens[0],
         pinned_check_s=round(pinned_check_s, 3), mismatches=len(mismatches))
    settle()

    check_device_served(
        daemon, engine, "read your write", flat_checks=n_flat, replays_ok=True
    )
    read.close()
    write.close()


# -- phase 5: the differential tier --------------------------------------------


def phase_tier(rehearsal: bool) -> None:
    import tpu_test_tier

    t0 = time.perf_counter()
    rc = tpu_test_tier.main(require_tpu=not rehearsal)
    emit("tier", t0, rc=rc)
    require(rc == 0, f"tools/tpu_test_tier.py returned {rc}")


# -- the four-chip path --------------------------------------------------------


def device_ids(tables: dict) -> dict:
    return {k: sorted(d.id for d in v.devices()) for k, v in tables.items()}


def run_mesh4(args, drive: Drive) -> None:
    """The sharded path and what it is compared with, and no other phase:
    one check batch and one expand batch through TPUCheckEngine(mesh=...)
    against the same batches on a single-device engine and the oracle."""
    from keto_tpu.engine.tpu_engine import TPUCheckEngine
    from keto_tpu.ketoapi import SubjectSet
    from keto_tpu.parallel import default_mesh
    from keto_tpu.storage.columnar import ColumnarStore

    rng = np.random.default_rng(args.seed + 2)
    t0 = time.perf_counter()
    store = ColumnarStore()
    store.bulk_load(drive.cols)
    cfg = drive_config(serve=False)
    mesh = default_mesh(4)
    sharded = TPUCheckEngine(store, cfg, mesh=mesh)
    state = sharded._ensure_state()
    sharded_tables, replicated_tables = state.tables
    holders = device_ids(sharded_tables)
    emit(
        "mesh4_build", t0,
        mesh_devices=[d.id for d in mesh.devices.flat],
        n_shards=int(state.sharded.n_shards),
        sharded_table_devices=holders,
        sharded_table_shapes={k: list(v.shape) for k, v in sharded_tables.items()},
        per_device_bytes=int(
            sum(v.nbytes for v in sharded_tables.values()) // 4
            + sum(v.nbytes for v in replicated_tables.values())
        ),
    )
    require(
        all(len(ids) == 4 for ids in holders.values()),
        f"a sharded table is not on four devices: {holders}",
    )

    t0 = time.perf_counter()
    single = TPUCheckEngine(store, cfg)
    queries, want = view_queries(drive, rng, BATCH)
    got_sharded = [r.allowed for r in sharded.check_batch(queries)]
    sharded_s = time.perf_counter() - t0
    got_single = [r.allowed for r in single.check_batch(queries)]
    diff_single = sum(a != b for a, b in zip(got_sharded, got_single))
    diff_truth = sum(a != b for a, b in zip(got_sharded, want))
    diff_ref = sum(
        got_sharded[int(i)]
        != single.reference.check_relation_tuple(queries[int(i)], 0).allowed
        for i in rng.choice(len(queries), REF_SAMPLES, replace=False)
    )
    emit(
        "mesh4_check", t0, n=len(queries), sharded_s=round(sharded_s, 3),
        differ_from_single_device=diff_single, differ_from_truth=diff_truth,
        differ_from_reference=diff_ref,
        host_checks={"sharded": sharded.stats["host_checks"],
                     "single": single.stats["host_checks"]},
    )
    require(
        not (diff_single or diff_truth or diff_ref),
        "the sharded check batch differs",
    )
    require(
        sharded.stats["host_checks"] == 0,
        f"the sharded check batch was replayed on the host: {sharded.stats}",
    )

    t0 = time.perf_counter()
    subjects = [
        SubjectSet("rbac", f"role{int(r)}", "member")
        for r in rng.choice(drive.n_roles, 16, replace=False)
    ]
    trees_sharded = [tree_dict(t) for t in sharded.expand_batch(subjects, EXPAND_DEPTH)]
    trees_single = [tree_dict(t) for t in single.expand_batch(subjects, EXPAND_DEPTH)]
    trees_ref = [
        tree_dict(single.reference.expand(s, EXPAND_DEPTH)) for s in subjects
    ]
    csr_holders = device_ids(sharded._state.expand_tables[0])
    emit(
        "mesh4_expand", t0, n=len(subjects),
        differ_from_single_device=sum(
            a != b for a, b in zip(trees_sharded, trees_single)
        ),
        differ_from_reference=sum(
            a != b for a, b in zip(trees_sharded, trees_ref)
        ),
        sharded_csr_devices=csr_holders,
        host_expands={"sharded": sharded.stats.get("host_expands", 0),
                      "single": single.stats.get("host_expands", 0)},
        memory_stats=device_memory(),
    )
    require(
        trees_sharded == trees_single == trees_ref,
        "the sharded expand batch differs",
    )
    require(
        all(len(ids) == 4 for ids in csr_holders.values()),
        f"a sharded expand table is not on four devices: {csr_holders}",
    )
    require(
        not sharded.stats.get("host_expands", 0),
        "the sharded expand batch was replayed on the host",
    )


# -- main ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=DEFAULT_SEED)
    ap.add_argument(
        "--tuples", type=int, default=None,
        help=f"relation tuples in all (default {DEFAULT_TUPLES:,})",
    )
    ap.add_argument(
        "--mesh4", action="store_true",
        help="run the sharded path on four devices, and nothing else",
    )
    args = ap.parse_args(argv)

    # before anything is printed: without the program there is no run
    from keto_tpu.compile_cache import ensure_compile_cache

    t_start = time.perf_counter()
    device = phase_device(args)
    rehearsal = device["platform"] != "tpu"
    tuples = args.tuples or DEFAULT_TUPLES

    cache_dir = ensure_compile_cache()
    cache_before = cache_entries(cache_dir)

    t0 = time.perf_counter()
    drive = build_drive(args.seed, tuples)
    drive_seconds = time.perf_counter() - t0

    if args.mesh4:
        run_mesh4(args, drive)
    else:
        daemon, engine = phase_load(drive_seconds, drive)
        try:
            phase_requests(args, drive, daemon, engine)
            peak = device_memory()
        finally:
            daemon.stop()
        phase_tier(rehearsal)
        emit("memory", t_start, after_serving=peak, at_end=device_memory())

    cache_after = cache_entries(cache_dir)
    emit(
        "compile_cache", t_start, dir=cache_dir,
        entries_before=len(cache_before), entries_after=len(cache_after),
        # the programs this run compiled and kept: on a warm cache only
        # those that sit at JAX's one-second threshold for keeping an entry
        added=sorted(name.rsplit("-", 2)[0] for name in cache_after - cache_before),
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        code = 1
    sys.exit(code)
