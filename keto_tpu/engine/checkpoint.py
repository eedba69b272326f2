"""Device-mirror checkpointing: GraphSnapshot save/restore.

The TPU analog of "checkpoint/resume" (SURVEY.md §5.4): the reference has
none in-engine (durability = the SQL store; snaptokens are stubbed), and
here too the authoritative state is the tuple store — what's worth
persisting is the COMPILED mirror. At 1e8 edges the hash-table/CSR build
is minutes of host work; a warm restart should `mmap` it back instead.

Format: one `.npz` (all int32 arrays, vocabularies as fixed-width
unicode arrays sorted by id) + metadata. A checkpoint is valid for
exactly one (store_version, config fingerprint) pair — the engine
compares `version` before trusting it, so a stale file is just ignored
(the delta overlay then covers any writes since the snapshot's base the
usual way).
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import zlib
from typing import Optional
from zipfile import BadZipFile

# every way a torn/corrupt/bit-rotted checkpoint file can surface from
# np.load: OSError (fs), KeyError (missing member), ValueError (format),
# EOFError (truncated member data), BadZipFile (mangled zip structure),
# zlib.error (deflate stream corrupted in place — bit rot with an intact
# central directory). Loading must DEGRADE on all of them, never raise
# through Daemon.start or the check path.
_TORN_FILE_ERRORS = (
    OSError, KeyError, ValueError, EOFError, BadZipFile, zlib.error,
)

import numpy as np

from .snapshot import GraphSnapshot

FORMAT_VERSION = 4  # v4: the meta vector ends in a layout code that
# names where the builder PLACED keys. There is one placement today
# (snapshot.probe_slot's bucket sequence, code 0); v4 files written by
# CPU processes before the layouts were merged carry code 1 (one slot a
# bucket, classic double hashing), and probing those tables with today's
# sequence would mis-answer every lookup, so any other code is refused
# like a version mismatch. (The shape a pack is stored in,
# kernel.as_bucket_rows, is made from these columns at upload and is no
# part of the file.)
# v3: bucketized probe sequence (snapshot.probe_slot) — v2 files hold
# tables built with the old (h1 + j*h2) slot layout and would mis-probe;
# a version mismatch just triggers a rebuild.
# v2: island circuits (AND/NOT device programs)

# layout code riding last in the meta vector (v4+): the one this process
# writes and reads, and the retired one, kept so a refusal can name it
_LAYOUT_CODE = 0
_LAYOUT_NAMES = {_LAYOUT_CODE: "bucketized", 1: "compact"}
_LAYOUT = _LAYOUT_NAMES[_LAYOUT_CODE]

# vocabularies larger than this reload as ArrayMaps, not Python dicts
_ARRAY_VOCAB_THRESHOLD = 200_000

_ARRAY_FIELDS = (
    "objslot_ns", "ns_has_config",
    "dh_obj", "dh_rel", "dh_skind", "dh_sa", "dh_sb", "dh_val",
    "rh_obj", "rh_rel", "rh_row",
    "row_ptr", "e_obj", "e_rel",
    "instr_kind", "instr_rel", "instr_rel2", "prog_flags",
)
_INT_FIELDS = (
    "n_config_rels", "wildcard_rel", "dh_probes", "rh_probes",
    "K", "version", "n_tuples",
)


def mirror_cache_path(cache_dir: str, nid: str) -> str:
    """THE naming contract for a network's mirror checkpoint file —
    shared by the engine's persist/load path and the daemon's cold-start
    recovery audit, so the audit can never drift into probing a name
    the engine stopped writing."""
    return os.path.join(cache_dir, f"mirror-{nid}.npz")


def stable_fingerprint(obj) -> int:
    """Process-stable 63-bit fingerprint of a JSON-able value (unlike
    Python's hash(), which is salted per process for strings)."""
    payload = json.dumps(obj, sort_keys=True, default=str).encode()
    return int.from_bytes(hashlib.sha256(payload).digest()[:8], "big") >> 1


def _names_by_id(d, n: int) -> np.ndarray:
    from .snapshot import ArrayMap

    if isinstance(d, ArrayMap):
        return np.asarray(d.keys_by_id_str_array(), dtype="U")
    out = [""] * n
    for name, i in d.items():
        out[i] = name
    return np.array(out, dtype="U")


def save_snapshot(snapshot: GraphSnapshot, path: str) -> None:
    """Atomic write of the snapshot to `path` (an .npz file). ArrayMap
    vocabularies (the columnar builder's) serialize via their vectorized
    id-ordered key arrays — never a per-entry Python loop at 1e7+."""
    from .snapshot import _SEP, ArrayMap

    n_obj = len(snapshot.obj_slots)
    if isinstance(snapshot.obj_slots, ArrayMap):
        keys_by_id = snapshot.obj_slots.keys_by_id_str_array()
        parts = np.char.partition(keys_by_id, _SEP)
        obj_ns = parts[:, 0].astype(np.int32)
        obj_names = parts[:, 2]
    else:
        obj_ns = np.zeros(n_obj, dtype=np.int32)
        obj_names = [""] * n_obj
        for (ns, obj), slot in snapshot.obj_slots.items():
            obj_ns[slot] = ns
            obj_names[slot] = obj
    payload = {k: getattr(snapshot, k) for k in _ARRAY_FIELDS}
    payload.update(
        {
            "meta": np.array(
                [FORMAT_VERSION]
                + [int(getattr(snapshot, k)) for k in _INT_FIELDS]
                + [_LAYOUT_CODE],
                dtype=np.int64,
            ),
            "ns_names": _names_by_id(snapshot.ns_ids, len(snapshot.ns_ids)),
            "rel_names": _names_by_id(snapshot.rel_ids, len(snapshot.rel_ids)),
            "obj_ns": obj_ns,
            "obj_names": np.array(obj_names, dtype="U"),
            "subj_names": _names_by_id(snapshot.subj_ids, len(snapshot.subj_ids)),
            # island circuits are tiny host-side tuples: JSON round-trip
            "island_circuits": np.array(
                [
                    json.dumps(
                        {str(k): list(v) for k, v in snapshot.island_circuits.items()}
                    )
                ],
                dtype="U",
            ),
        }
    )
    from .. import faults as _faults

    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **payload)
            # crash-ordering contract (tools/crash_smoke.py): the temp
            # file's BYTES must be on disk before the rename can publish
            # its NAME — without this fsync a crash shortly after
            # os.replace can surface a renamed-but-empty file, the one
            # torn state load_snapshot's fallback cannot distinguish
            # from a legitimately empty write
            f.flush()
            os.fsync(f.fileno())
        # crash point: temp durable, rename not yet issued — restart
        # must see the OLD checkpoint (or none) plus a stray .npz.tmp
        _faults.inject("checkpoint_pre_rename")
        os.replace(tmp, path)
        # the rename itself is made durable by fsyncing the DIRECTORY
        # (POSIX: a dir entry update is data of the directory file)
        try:
            dfd = os.open(d, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass  # platforms without dir fsync: rename atomicity remains
        # crash point: fully published — restart must load THIS file or
        # (version mismatch) ignore it, never see a torn one
        _faults.inject("checkpoint_post_rename")
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


CLOSURE_FORMAT_VERSION = 2  # v2: meta carries (max_depth, max_set_rows)
# — the powering parameters; a v1 file would be trusted under limits it
# was not powered at, so a version mismatch just re-powers.

_CLOSURE_ARRAYS = (
    "covered_keys", "ent_obj", "ent_rel", "ent_skind", "ent_sa", "ent_sb",
    "ent_req",
)


def closure_cache_path(cache_dir: str, nid: str) -> str:
    """Naming contract for a network's Leopard closure checkpoint —
    lives beside the mirror checkpoint so a warm restart restores both
    (the closure file is valid for exactly one snapshot version; the
    graph structures the maintainer needs re-extract from the restored
    snapshot, only the expensive powering product is persisted)."""
    return os.path.join(cache_dir, f"closure-{nid}.npz")


def save_closure(build, path: str) -> None:
    """Atomic, fsync-ordered write of one ClosureBuild's powering
    product (engine/closure.py). Same crash-ordering discipline as
    save_snapshot: bytes durable before the rename publishes the name."""
    payload = {k: np.asarray(getattr(build, k)) for k in _CLOSURE_ARRAYS}
    payload["meta"] = np.array(
        [
            CLOSURE_FORMAT_VERSION,
            int(build.snapshot_version),
            int(build.base_version),
            int(build.n_nodes),
            int(build.n_entries),
            int(build.vocab_fp),
            int(build.max_depth),
            int(build.max_set_rows),
        ],
        dtype=np.int64,
    )
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        try:
            dfd = os.open(d, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load_closure(path: str):
    """Load a persisted ClosureBuild; None when missing / torn /
    incompatible — the maintainer then re-powers from the snapshot,
    exactly as if no checkpoint existed."""
    from .closure import ClosureBuild

    try:
        with np.load(path, allow_pickle=False) as z:
            meta = z["meta"]
            # length check FIRST: a corrupt empty meta would raise
            # IndexError (not in _TORN_FILE_ERRORS) out of meta[0]
            if len(meta) != 8 or int(meta[0]) != CLOSURE_FORMAT_VERSION:
                return None
            arrays = {k: z[k] for k in _CLOSURE_ARRAYS}
            return ClosureBuild(
                snapshot_version=int(meta[1]),
                base_version=int(meta[2]),
                n_nodes=int(meta[3]),
                n_entries=int(meta[4]),
                vocab_fp=int(meta[5]),
                max_depth=int(meta[6]),
                max_set_rows=int(meta[7]),
                **arrays,
            )
    except _TORN_FILE_ERRORS:
        return None


def checkpoint_info(path: str) -> Optional[dict]:
    """Cheap checkpoint metadata probe for the cold-start recovery
    audit (api/daemon.py): reads ONLY the tiny `meta` array out of the
    zip — no vocabulary/CSR deserialization. Returns None when the file
    is missing; a dict with ``loadable: False`` when it exists but is
    torn/corrupt/incompatible (the states load_snapshot degrades to a
    rebuild on)."""
    if not os.path.exists(path):
        return None
    try:
        with np.load(path, allow_pickle=False) as z:
            meta = z["meta"]
            info = {
                "format_version": int(meta[0]),
                "loadable": int(meta[0]) == FORMAT_VERSION,
            }
            if len(meta) == len(_INT_FIELDS) + 2:
                info.update(
                    {k: int(meta[i + 1]) for i, k in enumerate(_INT_FIELDS)}
                )
                layout = _LAYOUT_NAMES.get(int(meta[-1]))
                info["table_layout"] = layout
                # a checkpoint of another layout exists but cannot be
                # probed — its tables' keys live in other slots
                if layout != _LAYOUT:
                    info["loadable"] = False
            else:
                info["loadable"] = False
            return info
    except _TORN_FILE_ERRORS:
        return {"loadable": False}


def restore_snapshot(path: str) -> Optional[GraphSnapshot]:
    """STRICT restore for callers that asked for this checkpoint by name
    (the HA follower's cold start, api/follower.py) instead of probing
    an optional cache:

      - missing or torn/corrupt file -> None (recover by rebuilding —
        a crash mid-publish must never wedge a restart);
      - intact but incompatible (format version or table layout) ->
        typed CheckpointIncompatibleError, because the file the caller
        explicitly wants CANNOT be honored by this process and silently
        rebuilding would hide an operational mistake (e.g. a cache dir
        left behind by a build that placed keys differently).

    load_snapshot keeps the old degrade-to-None contract for the
    engine's opportunistic warm-start probe."""
    from ..errors import CheckpointIncompatibleError

    info = checkpoint_info(path)
    if info is None:
        return None
    if not info.get("loadable"):
        fmt = info.get("format_version")
        if fmt is not None and fmt != FORMAT_VERSION:
            raise CheckpointIncompatibleError(
                debug=(
                    f"checkpoint {path} is format v{fmt}, this process "
                    f"reads v{FORMAT_VERSION}"
                )
            )
        layout = info.get("table_layout")
        if layout is not None and layout != _LAYOUT:
            raise CheckpointIncompatibleError(
                debug=(
                    f"checkpoint {path} was built under the {layout!r} "
                    f"table layout; this process probes "
                    f"{_LAYOUT!r} — its tables would mis-answer"
                )
            )
        return None  # torn/corrupt: recover cleanly via rebuild
    return load_snapshot(path)


def load_snapshot(path: str) -> Optional[GraphSnapshot]:
    """Load a snapshot; None when missing/corrupt/incompatible — a torn
    or truncated file (crash mid-write on a filesystem without the
    fsync ordering save_snapshot now enforces, or a stray partial copy)
    degrades to the same rebuild path as a missing one, never an error
    through Daemon.start."""
    try:
        with np.load(path, allow_pickle=False) as z:
            meta = z["meta"]
            if int(meta[0]) != FORMAT_VERSION:
                return None
            if len(meta) != len(_INT_FIELDS) + 2 or (
                int(meta[-1]) != _LAYOUT_CODE
            ):
                # layout mismatch: the tables were built for ANOTHER
                # probe sequence — loading them would mis-probe every
                # key, so degrade to a rebuild like any incompatibility
                return None
            ints = {k: int(meta[i + 1]) for i, k in enumerate(_INT_FIELDS)}
            arrays = {k: z[k] for k in _ARRAY_FIELDS}
            ns_names = z["ns_names"]
            rel_names = z["rel_names"]
            obj_ns = z["obj_ns"]
            obj_names = z["obj_names"]
            subj_names = z["subj_names"]
            circuits = {
                int(k): tuple(tuple(op) for op in v)
                for k, v in json.loads(str(z["island_circuits"][0])).items()
            }
    except _TORN_FILE_ERRORS:
        return None
    # big vocabs reload as ArrayMaps (sorted keys + explicit id values):
    # rebuilding 1e7-entry Python dicts would pay the exact memory/CPU
    # wall the columnar builder exists to avoid — defeating warm restart
    if len(obj_names) > _ARRAY_VOCAB_THRESHOLD:
        from .snapshot import (
            ArrayMap,
            _compose_keys,
            _decode_obj_key,
            _encode_obj_key,
        )

        composite = _compose_keys(obj_ns.astype(np.int64), obj_names)
        order = np.argsort(composite, kind="stable")
        obj_slots = ArrayMap(
            composite[order],
            encode=_encode_obj_key,
            decode=_decode_obj_key,
            values=order,
        )
    else:
        obj_slots = {
            (int(obj_ns[i]), str(obj_names[i])): i for i in range(len(obj_names))
        }
    if len(subj_names) > _ARRAY_VOCAB_THRESHOLD:
        from .snapshot import ArrayMap

        order = np.argsort(subj_names, kind="stable")
        subj_ids = ArrayMap(subj_names[order], values=order)
    else:
        subj_ids = {str(n): i for i, n in enumerate(subj_names)}
    return GraphSnapshot(
        island_circuits=circuits,
        ns_ids={str(n): i for i, n in enumerate(ns_names)},
        rel_ids={str(n): i for i, n in enumerate(rel_names)},
        obj_slots=obj_slots,
        subj_ids=subj_ids,
        **arrays,
        **ints,
    )
