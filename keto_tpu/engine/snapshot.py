"""Graph snapshot compiler: relation tuples + namespace configs → device
arrays for the batched BFS check kernel.

This replaces the reference's SQL-roundtrip-per-edge traversal
(internal/check/engine.go:109-141, one paginated SELECT per node) with an
HBM-resident mirror:

  - dictionary encoding: namespaces, relation strings, scoped objects
    ((ns, object) pairs → dense int32 "object slots") and plain subject
    ids each get dense int32 vocabularies — the TPU analog of the
    reference's UUID mapping (internal/persistence/sql/uuid_mapping.go)
  - direct-edge hash table: open-addressing, double-hashed, 32-bit keys
    (obj_slot, rel, subject) for O(1) existence probes (the reference's
    checkDirect single-row SELECT, engine.go:148-177)
  - subject-set CSR: per (obj_slot, rel) row of subject-set edges for
    frontier expansion (the reference's paginated n:obj#rel@* scan,
    engine.go:109-141); rows are addressed through a second hash table
  - rewrite programs: each namespace relation's userset-rewrite AST
    compiled to ≤ K flat instructions {COMPUTED(rel'), TTU(rel, rel')}
    executed per task inside the kernel. The monotone (pure-union)
    fragment runs on device; AND/NOT islands and oversized programs are
    flagged host_only and re-evaluated exactly by the ReferenceEngine
    (mirroring the reference's synchronous checkInverted islands,
    internal/check/rewrites.go:142-159)

All arrays are int32 (TPU-native); hashes are uint32 murmur3 finalizers.
Everything is built vectorized in numpy so 1e8-edge ingest stays feasible.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from ..ketoapi import CheckColumns, RelationTuple
from ..namespace import ast
from ..namespace.definitions import Namespace
from .definitions import WILDCARD_RELATION

EMPTY = np.int32(-1)

# rewrite instruction kinds
INSTR_NONE = 0
INSTR_COMPUTED = 1
INSTR_TTU = 2

# per-(ns,rel) flags
FLAG_HOST_ONLY = 1  # rewrite exceeds the instruction/circuit caps
FLAG_CONFIG_MISSING = 2  # namespace declares relations but not this one
FLAG_ISLAND = 4  # rewrite has AND/NOT: full-evaluation island on device

# island circuit op codes (host-side combine; see engine/islands.py)
CIRC_FALSE = "false"
CIRC_LEAF = "leaf"
CIRC_NOT = "not"
CIRC_AND = "and"
CIRC_OR = "or"

# circuit length cap: a rewrite tree compiling past this goes host_only
CIRCUIT_CAP = 48

_GOLDEN = np.uint32(0x9E3779B9)


def mix32(x: np.ndarray) -> np.ndarray:
    """murmur3 fmix32, vectorized over uint32."""
    x = np.asarray(x, dtype=np.uint32).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def hash_combine(*parts: np.ndarray) -> np.ndarray:
    h = np.zeros_like(np.asarray(parts[0], dtype=np.uint32)) + _GOLDEN
    for p in parts:
        h = mix32(h ^ np.asarray(p, dtype=np.uint32))
    return h


def slots_per_bucket(n_key_cols: int) -> int:
    """Open-addressing bucket size by table kind: every bucket is one
    256-byte gather row (64 int32 lanes —
    the measured cost of a random row-gather is constant in row width up
    to at least 256 B, tools/microbench_gather_layout.py), so 2-key pair
    tables (4-int packed entries) hold 16 slots per bucket and 5-key
    edge tables (8-int entries) hold 8. The deeper pair buckets matter:
    at the build load factor a bucket holds ~2 keys on average and the
    MAX occupancy (which is the probe limit under the bucketized
    sequence) reaches 9-14 on real tables — 16 slots keep that inside
    ONE gathered bucket row."""
    return 16 if n_key_cols <= 2 else 8


def probe_slot(h1, h2, j, cap: int, spb: int = 8):
    """Slot index for probe number `j` (0-based, slot units) of a key
    with hashes (h1, h2) in a power-of-two table of `cap` >= spb slots,
    with `spb` slots per bucket (see slots_per_bucket).

    THE open-addressing probe sequence — builders (numpy + native C++),
    host-side probes (engine/compact.py) and the device kernel
    (engine/kernel.py) must all agree on it. Bucketized: probes fill the
    spb consecutive slots of bucket (h1 + (j//spb)*h2) before double-
    hash-stepping to the next bucket, so the device kernel fetches ONE
    256-byte bucket row per spb slots of probe depth — the gather-volume
    cost model (tools/microbench_gather_layout.py: row cost is constant
    in row width 32-256 B, so a bucket row costs the same as one slot
    row and cuts probe gathers ~P-fold).

    Vectorized over numpy uint32 arrays (h1/h2/j broadcast)."""
    sh = np.uint32(spb.bit_length() - 1)  # log2(spb); spb is 8 or 16
    bmask = np.uint32(cap // spb - 1)
    jb = np.asarray(j, dtype=np.uint32) >> sh
    js = np.asarray(j, dtype=np.uint32) & np.uint32(spb - 1)
    return ((h1 + jb * h2) & bmask) * np.uint32(spb) + js


def pad_headroom(n: int, quantum: int = 1024) -> int:
    """Array length for n entries plus delta headroom. Vocab-dependent
    device arrays (objslot_ns, ns_has_config) are sized to a quantum
    boundary so a delta that introduces new object slots or namespaces
    keeps the array shape (no XLA recompile) until growth crosses the
    next quantum."""
    return ((n // quantum) + 2) * quantum


def hash_table_capacity(n: int, min_capacity: int = 64) -> int:
    """Power-of-two capacity at load factor ≤ 0.25 for n entries.

    Probe LIMITS (the max over all entries) multiply every probe
    gather's width in the kernel, so sparseness buys throughput
    directly: at load 0.5 the bench tables build with dh/rh probe
    limits 8/12; at 0.25 they drop to 5/6 and batched check QPS rises
    29% (CPU, measured round 3) for 2x table bytes. A further doubling
    gains ~2% — 0.25 is the knee."""
    # floor 64: the bucketized probe sequence (probe_slot) needs at
    # least BUCKET slots and a power-of-two bucket count
    cap = max(min_capacity, 64)
    while cap < 4 * n:
        cap *= 2
    return cap


def table_capacity(n: int, min_capacity: int = 64) -> int:
    """Capacity rule shared by the builder AND the sharded equal-capacity
    seed estimates (a mismatched seed makes every sharded build run
    twice through the grow-retry loop). ALL bucketized tables run half
    the classic 4n sizing: the probe limit IS the max bucket occupancy,
    and cap=8n keeps chains inside one bucket (bench tables: dh probes
    8 -> 5, rh 14 -> 9). Fixed-capacity callers (the delta overlay's
    static shapes, where occupancy is tiny and shape stability is the
    contract) pass boost_load=False to _build_hash_table instead."""
    cap = hash_table_capacity(n, min_capacity)
    if cap < 8 * n:
        # bucketized tables run HALF the classic load: the probe limit
        # IS the max bucket occupancy, so average occupancy ~1 (8-slot
        # edge buckets) / ~2 (16-slot pair buckets) keeps chains inside
        # one bucket on TPU and the CPU fallback's probe volume near the
        # double-hashing era's. 2x bytes; at 1e8 that is ~5.8 GB/device
        # of a v5e's 16 GB.
        cap *= 2
    return cap


def _build_hash_table(
    keys: tuple[np.ndarray, ...], values: np.ndarray, min_capacity: int = 64,
    boost_load: bool = True,
) -> tuple[np.ndarray, ...]:
    """Build an open-addressing table (double hashing, power-of-two size,
    load ≤ 0.25 per hash_table_capacity). Returns (slot arrays for each
    key column..., value array, probe_limit). Insertion is vectorized:
    per probe round, first-comer wins a slot via np.unique; the rest
    advance to their next probe slot.
    """
    n = len(values)
    cap = (
        table_capacity(n, min_capacity)
        if boost_load
        else hash_table_capacity(n, min_capacity)
    )
    h1_all = hash_combine(*keys)
    h2_all = mix32(h1_all ^ _GOLDEN) | np.uint32(1)  # odd stride, pow2 table
    while True:
        # native round-based builder when available (keto_tpu/native):
        # bit-identical winner rule, no per-round argsort (the sort was
        # ~25% of 5e7 per-shard builds)
        from ..native import build_probe_table

        spb = slots_per_bucket(len(keys))
        native = build_probe_table(
            h1_all, h2_all, keys, values, cap, int(EMPTY), spb
        )
        if native is not None:
            n_cols, n_vals, max_probes = native
            if max_probes >= 1:
                return (*n_cols, n_vals, max_probes)
            cap *= 2  # pathological clustering: grow and retry
            continue
        table_keys = [np.full(cap, EMPTY, dtype=np.int32) for _ in keys]
        table_vals = np.full(cap, EMPTY, dtype=np.int32)
        h1 = h1_all
        h2 = h2_all
        mask = np.uint32(cap - 1)
        pending = np.arange(n)
        probe = np.zeros(n, dtype=np.uint32)
        max_probes = 0
        while len(pending):
            max_probes += 1
            if max_probes > 64:
                break  # extremely clustered: grow and retry
            slots = probe_slot(
                h1[pending], h2[pending], probe[pending], cap, spb
            )
            if max_probes == 1:
                free = np.ones(len(pending), dtype=bool)  # empty table
            else:
                free = table_vals[slots] == EMPTY
            # among pending rows probing the same free slot, lowest index
            # wins: one stable sort by slot, then first-of-run — NOT
            # np.unique, which would re-sort the already-sorted slots
            # (the double sort was ~25% of the 5e7 per-shard builds)
            order = np.argsort(slots[free], kind="stable")
            free_idx = pending[free][order]
            free_slots = slots[free][order]
            if len(free_slots):
                first = np.concatenate(
                    [[0], np.flatnonzero(free_slots[1:] != free_slots[:-1]) + 1]
                )
            else:
                first = np.array([], dtype=np.int64)
            uniq_slots = free_slots[first]
            winners = free_idx[first]
            table_vals[uniq_slots] = values[winners]
            for col, key in zip(table_keys, keys):
                col[uniq_slots] = key[winners]
            placed = np.zeros(n, dtype=bool)
            placed[winners] = True
            lost = pending[~placed[pending]]
            probe[lost] += 1
            pending = lost
        if not len(pending):
            return (*table_keys, table_vals, max(max_probes, 1))
        cap *= 2  # grow on pathological clustering


def encode_edge_arrays(
    tuples: Sequence[RelationTuple],
    ns_ids: dict[str, int],
    rel_ids: dict[str, int],
    obj_slots: dict[tuple[int, str], int],
    subj_ids: dict[str, int],
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Encode tuples to (obj, rel, skind, sa, sb) int32 arrays under a
    pre-built vocabulary (every name must already be registered)."""
    n_t = len(tuples)
    t_obj = np.zeros(n_t, dtype=np.int32)
    t_rel = np.zeros(n_t, dtype=np.int32)
    t_skind = np.zeros(n_t, dtype=np.int32)
    t_sa = np.zeros(n_t, dtype=np.int32)
    t_sb = np.zeros(n_t, dtype=np.int32)
    for i, t in enumerate(tuples):
        n = ns_ids[t.namespace]
        t_obj[i] = obj_slots[(n, t.object)]
        t_rel[i] = rel_ids[t.relation]
        if t.subject_set is not None:
            s = t.subject_set
            t_skind[i] = 1
            t_sa[i] = obj_slots[(ns_ids[s.namespace], s.object)]
            t_sb[i] = rel_ids[s.relation]
        else:
            t_sa[i] = subj_ids[t.subject_id or ""]
    return t_obj, t_rel, t_skind, t_sa, t_sb


def group_rows_csr(
    key_obj: np.ndarray,
    key_rel: np.ndarray,
    payloads: tuple[np.ndarray, ...],
    min_capacity: int = 64,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int, np.ndarray, tuple]:
    """Group edges by (obj, rel) into a CSR addressed through a row hash
    table. Stable within a row (original order preserved). Returns
    (rh_obj, rh_rel, rh_row, rh_probes, row_ptr, sorted_payloads).
    Shared by the check kernel's subject-set CSR and the expand kernel's
    full-edge CSR so the probe-sensitive row-index construction has one
    implementation."""
    n = len(key_obj)
    if n:
        order = np.lexsort((np.arange(n), key_rel, key_obj))
        key_obj, key_rel = key_obj[order], key_rel[order]
        payloads = tuple(p[order] for p in payloads)
        row_change = np.empty(n, dtype=bool)
        row_change[0] = True
        row_change[1:] = (key_obj[1:] != key_obj[:-1]) | (
            key_rel[1:] != key_rel[:-1]
        )
        row_starts = np.flatnonzero(row_change)
        row_ptr = np.append(row_starts, n).astype(np.int32)
        rh_obj, rh_rel, rh_row, rh_probes = _build_hash_table(
            (key_obj[row_starts], key_rel[row_starts]),
            np.arange(len(row_starts), dtype=np.int32),
            min_capacity=min_capacity,
        )
    else:
        cap = max(min_capacity, 64)
        row_ptr = np.zeros(1, dtype=np.int32)
        rh_obj = np.full(cap, EMPTY, np.int32)
        rh_rel = np.full(cap, EMPTY, np.int32)
        rh_row = np.full(cap, EMPTY, np.int32)
        rh_probes = 1
    return rh_obj, rh_rel, rh_row, rh_probes, row_ptr, payloads


def build_edge_tables(
    t_obj: np.ndarray,
    t_rel: np.ndarray,
    t_skind: np.ndarray,
    t_sa: np.ndarray,
    t_sb: np.ndarray,
    dh_min_cap: int = 64,
    rh_min_cap: int = 64,
) -> dict:
    """Direct-edge hash table + subject-set CSR from encoded edge arrays.

    `dh_min_cap`/`rh_min_cap` force minimum table capacities so multiple
    shards of one graph can be built with identical shapes and stacked
    along a device axis (the slot sequence of an open-addressing probe
    depends on capacity, so stacked tables must share it).
    """
    n_t = len(t_obj)
    # direct-edge hash table over all edges (plain and subject-set)
    dh = _build_hash_table(
        (t_obj, t_rel, t_skind, t_sa, t_sb),
        np.ones(n_t, dtype=np.int32),
        min_capacity=dh_min_cap,
    )
    dh_obj, dh_rel, dh_skind, dh_sa, dh_sb, dh_val, dh_probes = dh

    # subject-set CSR grouped by (obj, rel); wildcard-relation subject sets
    # are kept (TTU traverses them; the kernel filters them for the
    # expand-subject slot)
    is_set = t_skind == 1
    rh_obj, rh_rel, rh_row, rh_probes, row_ptr, (e_obj, e_rel) = group_rows_csr(
        t_obj[is_set],
        t_rel[is_set],
        (t_sa[is_set].astype(np.int32), t_sb[is_set].astype(np.int32)),
        min_capacity=rh_min_cap,
    )

    return {
        "dh_obj": dh_obj, "dh_rel": dh_rel, "dh_skind": dh_skind,
        "dh_sa": dh_sa, "dh_sb": dh_sb, "dh_val": dh_val,
        "dh_probes": dh_probes,
        "rh_obj": rh_obj, "rh_rel": rh_rel, "rh_row": rh_row,
        "rh_probes": rh_probes,
        "row_ptr": row_ptr, "e_obj": e_obj, "e_rel": e_rel,
    }


_SEP = "\x1f"


class ArrayMap:
    """Sorted-numpy-backed replacement for the big vocab dicts.

    At 1e7+ object slots a Python dict costs GBs and seconds of
    insertion loop; this keeps the sorted unique key array from
    np.unique (slot id == sorted position) and answers .get() with one
    searchsorted. Encode/decode adapt composite keys ((ns_id, obj) <->
    "ns_id\\x1fobj"). Implements the dict surface the snapshot/delta/
    checkpoint code uses: get, in, len, items.

    Keys may be a unicode (U) or UTF-8 bytes (S) array: the columnar
    scale path stores S — 4x smaller and memcmp-fast, and UTF-8 byte
    order equals code-point order, so sortedness semantics match. The
    str<->bytes adaptation happens HERE, at the per-query boundary."""

    def __init__(
        self, sorted_keys: np.ndarray, encode=None, decode=None, values=None
    ):
        # values=None means the id IS the sorted position (the columnar
        # builder's slot assignment); an explicit array supports key
        # orders that differ from id order (checkpoint reload)
        self._keys = sorted_keys
        self._is_bytes = sorted_keys.dtype.kind == "S"
        self._values = values
        self._by_id: Optional[np.ndarray] = None  # lazy id -> raw key
        self._encode = encode or (lambda k: k)
        self._decode = decode or (lambda s: s)

    def keys_by_id_array(self) -> np.ndarray:
        """Raw (encoded) keys ordered by id — one vectorized inverse
        permutation, cached. The reverse-lookup primitive for decoders
        and checkpoint writes (never per-entry Python loops)."""
        if self._by_id is None:
            if self._values is None:
                self._by_id = self._keys
            else:
                inv = np.empty(len(self._keys), dtype=np.int64)
                inv[np.asarray(self._values, dtype=np.int64)] = np.arange(
                    len(self._keys), dtype=np.int64
                )
                self._by_id = self._keys[inv]
        return self._by_id

    def _raw_to_str(self, raw) -> str:
        return (
            bytes(raw).decode("utf-8") if self._is_bytes else str(raw)
        )

    def keys_by_id_str_array(self) -> np.ndarray:
        """keys_by_id_array as a U array regardless of key dtype — the
        checkpoint writer's boundary (vectorized decode, no per-entry
        Python)."""
        arr = self.keys_by_id_array()
        if self._is_bytes:
            arr = np.char.decode(arr, "utf-8")
        return arr

    def key_by_id(self, i: int):
        """Decoded key for one id (O(1) after the cached inverse)."""
        return self._decode(self._raw_to_str(self.keys_by_id_array()[i]))

    def get(self, key, default=None):
        k = self._encode(key)
        if self._is_bytes:
            k = k.encode("utf-8")
        i = int(np.searchsorted(self._keys, k))
        if i < len(self._keys) and self._keys[i] == k:
            return int(self._values[i]) if self._values is not None else i
        return default

    def __contains__(self, key) -> bool:
        return self.get(key) is not None

    def __len__(self) -> int:
        return len(self._keys)

    def items(self):
        for i, k in enumerate(self._keys):
            v = int(self._values[i]) if self._values is not None else i
            yield self._decode(self._raw_to_str(k)), v

    def merged_with(self, new_items: dict) -> "ArrayMap":
        """New ArrayMap with `new_items` (decoded key -> id) inserted;
        EXISTING ids are preserved, so the merged map must carry an
        explicit value array (sorted position no longer equals id).
        One O(n + k log k) sorted insert — the incremental-compaction
        vocab path (engine/compact.py)."""
        if not new_items:
            return self
        enc = [self._encode(k) for k in new_items]
        if self._is_bytes:
            new_keys = np.array([e.encode("utf-8") for e in enc], dtype="S")
        else:
            new_keys = np.array(enc, dtype="U")
        new_vals = np.fromiter(
            new_items.values(), dtype=np.int64, count=len(new_items)
        )
        order = np.argsort(new_keys)
        new_keys, new_vals = new_keys[order], new_vals[order]
        base_keys = self._keys
        # np.insert silently truncates values longer than the array's
        # fixed itemsize — widen first
        if new_keys.dtype.itemsize > base_keys.dtype.itemsize:
            base_keys = base_keys.astype(new_keys.dtype)
        else:
            new_keys = new_keys.astype(base_keys.dtype)
        base_vals = (
            np.arange(len(base_keys), dtype=np.int64)
            if self._values is None
            else np.asarray(self._values, dtype=np.int64)
        )
        pos = np.searchsorted(base_keys, new_keys)
        keys = np.insert(base_keys, pos, new_keys)
        vals = np.insert(base_vals, pos, new_vals)
        return ArrayMap(
            keys, encode=self._encode, decode=self._decode, values=vals
        )


def _encode_obj_key(key) -> str:
    ns_id, obj = key
    return f"{ns_id}{_SEP}{obj}"


def _decode_obj_key(s: str):
    ns, _, obj = s.partition(_SEP)
    return (int(ns), obj)


def _compose_keys(ns_ids_arr: np.ndarray, objs: np.ndarray) -> np.ndarray:
    """Vectorized "%d\\x1f%s" composite keys (the ns_id prefix contains
    no separator, so the first separator always delimits correctly)."""
    return np.char.add(
        np.char.add(ns_ids_arr.astype("U11"), _SEP), objs.astype("U")
    )


def _sorted_unique_encode(
    keys: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(sorted uniques, first-occurrence indices, per-row sorted ranks)
    of a fixed-width S-dtype key array — np.unique + searchsorted
    semantics, computed by the native hash-dedupe path when available
    (keto_tpu/native: O(n) dedupe + sort of the uniques only; ~5x over
    np.unique's whole-array comparison sort, the dominant cost of the
    1e8 encode phase)."""
    from ..native import sorted_unique_encode

    return sorted_unique_encode(keys)


def _compose_keys_bytes(ns_ids_arr: np.ndarray, objs: np.ndarray) -> np.ndarray:
    """UTF-8 bytes (S dtype) composite keys: 4x smaller than U and
    memcmp-comparable — the sort/unique/searchsorted pipeline over 1e7+
    keys is string-compare bound (measured: np.unique over U keys was
    60% of the 1e7 sharded build). UTF-8 byte order equals code-point
    order, so sorting/uniqueness match the U pipeline exactly.

    Byte-for-byte the same "%d\\x1fobj" keys np.char.add built, but
    assembled by slice-assignment into one uint8 buffer, grouped by
    DISTINCT ns_id (namespaces are few; np.char.add's per-element
    _vec_string passes were ~35% of the 1e7 columnar build)."""
    n = len(objs)
    if n == 0:
        return np.array([], dtype="S1")
    obj_s = _encode_utf8(objs)
    ow = obj_s.dtype.itemsize
    ids = np.asarray(ns_ids_arr, dtype=np.int64)
    uniq = np.unique(ids)
    if len(uniq) > 256:  # pathological namespace count: one pass beats
        return np.char.add(  # thousands of per-group slice assignments
            np.char.add(ids.astype("S11"), _SEP.encode()), obj_s
        )
    prefixes = {int(u): f"{int(u)}{_SEP}".encode() for u in uniq}
    total = max(len(p) for p in prefixes.values()) + ow
    buf = np.zeros((n, total), dtype=np.uint8)
    ob = np.ascontiguousarray(obj_s).view(np.uint8).reshape(n, ow)
    for u, p in prefixes.items():
        rows = np.flatnonzero(ids == u)
        pw = len(p)
        buf[rows, :pw] = np.frombuffer(p, dtype=np.uint8)
        buf[rows, pw : pw + ow] = ob[rows]
    return buf.view(f"S{total}").ravel()


def _encode_utf8(arr: np.ndarray) -> np.ndarray:
    """U -> S (utf-8). ASCII fast path: a U array is UCS-4, so for pure-
    ASCII content the utf-8 bytes are just the low byte of each code
    point — one vectorized narrowing cast instead of numpy's per-element
    _vec_string encode (measured 0.29 s/1e6 keys; the cast is ~20x
    faster, and real authorization-model names are overwhelmingly
    ASCII). Trailing NULs match np.char.encode's S-padding semantics."""
    if arr.dtype.kind != "U":
        arr = arr.astype("U")
    n = len(arr)
    if n == 0:
        return np.array([], dtype="S1")
    w = arr.dtype.itemsize // 4
    cp = np.ascontiguousarray(arr).view(np.uint32).reshape(n, w)
    if cp.max(initial=0) < 128:
        return np.ascontiguousarray(cp.astype(np.uint8)).view(f"S{w}").ravel()
    return np.char.encode(arr, "utf-8")


def _queries_like(keys: np.ndarray, queries_u: np.ndarray) -> np.ndarray:
    """Convert a U query array to the key array's dtype — the ONE place
    query/vocab dtype matching happens (numpy compares S vs U arrays
    elementwise-False without erroring, so a missed conversion would
    silently drop every row)."""
    return _encode_utf8(queries_u) if keys.dtype.kind == "S" else queries_u


def _compose_keys_like(
    keys: np.ndarray, ns_ids_arr: np.ndarray, objs: np.ndarray
) -> np.ndarray:
    """Composite queries in the key array's dtype (the composite twin of
    _queries_like): composing directly in S avoids materializing the
    4x-larger U composite first."""
    if keys.dtype.kind == "S":
        return _compose_keys_bytes(ns_ids_arr, objs)
    return _compose_keys(ns_ids_arr, objs)


def _sorted_lookup(keys_sorted, vals_sorted, queries, default=-1):
    """Vectorized map lookup: queries -> vals via binary search.
    vals_sorted=None means the value IS the sorted position (ArrayMap's
    columnar form) — no materialized arange over a 1e7-entry vocab."""
    n = len(keys_sorted)
    if n == 0:
        return np.full(len(queries), default, dtype=np.int32)
    idx = np.clip(np.searchsorted(keys_sorted, queries), 0, n - 1)
    ok = keys_sorted[idx] == queries
    vals = idx if vals_sorted is None else vals_sorted[idx]
    return np.where(ok, vals, default).astype(np.int32)


@dataclass
class GraphSnapshot:
    """Immutable device-ready mirror of one network's relation graph."""

    # vocabularies for query encoding: plain dicts from the object-path
    # builder, ArrayMaps from the columnar builder (same .get interface)
    ns_ids: dict[str, int]
    rel_ids: dict[str, int]
    obj_slots: dict  # (ns_id, object) -> slot (dict or ArrayMap)
    subj_ids: dict  # plain subject string -> id (dict or ArrayMap)
    n_config_rels: int  # rel ids < this may have rewrite programs
    wildcard_rel: int  # rel id of "..."

    # obj_slot -> ns_id
    objslot_ns: np.ndarray
    # ns_id -> 1 iff the namespace declares a non-empty relation config
    # (then any undeclared relation visited there is an engine error)
    ns_has_config: np.ndarray

    # direct-edge hash table: key (obj, rel, skind, sa, sb) -> 1
    dh_obj: np.ndarray
    dh_rel: np.ndarray
    dh_skind: np.ndarray
    dh_sa: np.ndarray
    dh_sb: np.ndarray
    dh_val: np.ndarray
    dh_probes: int

    # row hash table: key (obj, rel) -> row index
    rh_obj: np.ndarray
    rh_rel: np.ndarray
    rh_row: np.ndarray
    rh_probes: int

    # subject-set CSR
    row_ptr: np.ndarray  # [n_rows + 1]
    e_obj: np.ndarray  # [n_edges] subject-set object slot
    e_rel: np.ndarray  # [n_edges] subject-set relation id

    # rewrite programs, dense [n_ns * n_config_rels, K]; K is the
    # EFFECTIVE max instruction/leaf count over all programs (not the
    # build-time cap) — the kernel's expansion-slot count S = K + 1
    # scales every per-step gather, so it must stay tight
    instr_kind: np.ndarray
    instr_rel: np.ndarray
    instr_rel2: np.ndarray
    prog_flags: np.ndarray  # [n_ns * n_config_rels]
    K: int

    # island programs: pid -> postfix circuit over leaf values (host-side
    # combine, engine/islands.py); empty for monotone-only configs
    island_circuits: dict = field(default_factory=dict)

    version: int = 0
    n_tuples: int = 0

    # edge-array slots orphaned by incremental-compaction row rewrites
    # (engine/compact.py); past GARBAGE_FRACTION the engine rebuilds.
    # Not persisted by checkpoints — a reloaded mirror undercounts, which
    # only delays (never corrupts) the amortizing rebuild.
    merge_garbage: int = 0

    # lazy per-snapshot cache of _map_sorted_arrays results (sorted key/
    # value arrays per vocab — rebuilt per batch they cost O(V log V)
    # string sorting on the serve hot path; the snapshot is immutable)
    _vocab_cache: dict = field(
        default_factory=dict, repr=False, compare=False
    )

    # -- query encoding helpers ----------------------------------------------

    def encode_node(self, namespace: str, obj: str, relation: str):
        """(obj_slot, rel_id) or None if unknown to the graph+config."""
        ns_id = self.ns_ids.get(namespace)
        if ns_id is None:
            return None
        slot = self.obj_slots.get((ns_id, obj))
        rel = self.rel_ids.get(relation)
        if slot is None or rel is None:
            return None
        return slot, rel

    def encode_subject(self, t: RelationTuple):
        """(skind, sa, sb) or None if the subject never occurs in the data."""
        if t.subject_set is not None:
            s = t.subject_set
            ns_id = self.ns_ids.get(s.namespace)
            if ns_id is None:
                return None
            slot = self.obj_slots.get((ns_id, s.object))
            rel = self.rel_ids.get(s.relation)
            if slot is None or rel is None:
                return None
            return 1, slot, rel
        sid = self.subj_ids.get(t.subject_id or "")
        if sid is None:
            return None
        return 0, sid, 0

    def prog_index(self, ns_id: int, rel_id: int) -> int:
        if rel_id >= self.n_config_rels:
            return -1
        return ns_id * self.n_config_rels + rel_id

    def device_arrays(self) -> dict[str, np.ndarray]:
        """The arrays the kernel closes over (ready for jnp.asarray)."""
        return {
            "objslot_ns": self.objslot_ns,
            "ns_has_config": self.ns_has_config,
            "dh_obj": self.dh_obj, "dh_rel": self.dh_rel,
            "dh_skind": self.dh_skind, "dh_sa": self.dh_sa,
            "dh_sb": self.dh_sb, "dh_val": self.dh_val,
            "rh_obj": self.rh_obj, "rh_rel": self.rh_rel, "rh_row": self.rh_row,
            "row_ptr": self.row_ptr, "e_obj": self.e_obj, "e_rel": self.e_rel,
            "instr_kind": self.instr_kind, "instr_rel": self.instr_rel,
            "instr_rel2": self.instr_rel2, "prog_flags": self.prog_flags,
        }


def _is_monotone(rw: ast.SubjectSetRewrite) -> bool:
    if rw.operation != ast.Operator.OR:
        return False
    for child in rw.children:
        if isinstance(child, ast.SubjectSetRewrite):
            if not _is_monotone(child):
                return False
        elif isinstance(child, ast.InvertResult):
            return False
        elif not isinstance(
            child, (ast.ComputedSubjectSet, ast.TupleToSubjectSet)
        ):
            return False
    return True


def _compile_rewrite(
    rewrite: Optional[ast.SubjectSetRewrite], rel_ids: dict[str, int], K: int
) -> tuple[list[tuple[int, int, int]], Optional[tuple], int]:
    """Compile a rewrite AST for device execution.

    Returns (instructions, circuit, flags):
      - pure-union (monotone) trees flatten to <= K inline instructions
        executed in the BFS itself (children inherit the task's ctx):
        circuit None, flags 0
      - trees containing AND/NOT compile to a full-evaluation ISLAND
        (the data-parallel form of the reference's synchronous and/or/
        checkInverted, internal/check/binop.go:38-70, rewrites.go:95-159):
        the instructions become the island's LEAF sub-checks (each leaf
        accumulates hits in its own ctx) and `circuit` is a postfix
        boolean program over the leaf values, combined on host after the
        BFS converges (engine/islands.py). Two-valued logic is exact
        here: every or/and in the reference collapses Unknown to
        NotMember (binop.go or/and, checkgroup consumer), so Unknown
        never changes a check verdict — depth-exhaustion inside a branch
        is NotMember for that branch, exactly as the reference reports
      - trees exceeding the instruction/circuit caps: flags
        FLAG_HOST_ONLY (exact host replay)
    """
    if rewrite is None:
        return [], None, 0

    if _is_monotone(rewrite):
        instrs: list[tuple[int, int, int]] = []

        def walk(rw: ast.SubjectSetRewrite) -> None:
            for child in rw.children:
                if isinstance(child, ast.ComputedSubjectSet):
                    instrs.append((INSTR_COMPUTED, rel_ids[child.relation], 0))
                elif isinstance(child, ast.TupleToSubjectSet):
                    instrs.append(
                        (
                            INSTR_TTU,
                            rel_ids[child.relation],
                            rel_ids[child.computed_subject_set_relation],
                        )
                    )
                else:
                    walk(child)

        walk(rewrite)
        if len(instrs) > K:
            return [], None, FLAG_HOST_ONLY
        return instrs, None, 0

    # non-monotone: island leaves + postfix circuit
    leaves: list[tuple[int, int, int]] = []
    leaf_index: dict[tuple[int, int, int], int] = {}
    ops: list[tuple] = []
    ok = True

    def leaf(key: tuple[int, int, int]) -> None:
        k = leaf_index.get(key)
        if k is None:
            k = len(leaves)
            leaf_index[key] = k
            leaves.append(key)
        ops.append((CIRC_LEAF, k))

    def emit(node) -> None:
        nonlocal ok
        if isinstance(node, ast.ComputedSubjectSet):
            leaf((INSTR_COMPUTED, rel_ids[node.relation], 0))
        elif isinstance(node, ast.TupleToSubjectSet):
            leaf(
                (
                    INSTR_TTU,
                    rel_ids[node.relation],
                    rel_ids[node.computed_subject_set_relation],
                )
            )
        elif isinstance(node, ast.InvertResult):
            emit(node.child)
            ops.append((CIRC_NOT,))
        elif isinstance(node, ast.SubjectSetRewrite):
            if not node.children:
                # or([]) = and([]) = NotMember (binop.go:16-18,:39-41)
                ops.append((CIRC_FALSE,))
                return
            combine = CIRC_AND if node.operation == ast.Operator.AND else CIRC_OR
            for i, child in enumerate(node.children):
                emit(child)
                if i:
                    ops.append((combine,))
        else:
            ok = False

    emit(rewrite)
    if not ok or len(leaves) > K or len(ops) > CIRCUIT_CAP:
        return [], None, FLAG_HOST_ONLY
    return leaves, tuple(ops), FLAG_ISLAND


def build_snapshot(
    tuples: Sequence[RelationTuple],
    namespaces: Sequence[Namespace],
    K: int = 8,
    version: int = 0,
    with_edge_tables: bool = True,
) -> GraphSnapshot:
    """`with_edge_tables=False` builds only the vocabulary + rewrite
    programs (placeholder edge tables): the sharded builder re-builds the
    edge tables per shard and would otherwise pay the global O(edges)
    hash-table construction twice."""
    # ---- vocabularies -------------------------------------------------------
    ns_ids: dict[str, int] = {}
    rel_ids: dict[str, int] = {}
    obj_slots: dict[tuple[int, str], int] = {}
    subj_ids: dict[str, int] = {}

    def ns_id(name: str) -> int:
        return ns_ids.setdefault(name, len(ns_ids))

    def rel_id(name: str) -> int:
        return rel_ids.setdefault(name, len(rel_ids))

    def obj_slot(ns: int, obj: str) -> int:
        return obj_slots.setdefault((ns, obj), len(obj_slots))

    def subj_id(s: str) -> int:
        return subj_ids.setdefault(s, len(subj_ids))

    _register_config_vocab(namespaces, ns_id, rel_id)
    n_config_rels = len(rel_ids)

    for t in tuples:
        n = ns_id(t.namespace)
        obj_slot(n, t.object)
        rel_id(t.relation)
        if t.subject_set is not None:
            s = t.subject_set
            sn = ns_id(s.namespace)
            obj_slot(sn, s.object)
            rel_id(s.relation)
        else:
            subj_id(t.subject_id or "")

    n_ns = max(len(ns_ids), 1)
    n_objslots = max(len(obj_slots), 1)

    objslot_ns = np.zeros(pad_headroom(n_objslots), dtype=np.int32)
    for (ns, _obj), slot in obj_slots.items():
        objslot_ns[slot] = ns
    ns_has_config = np.zeros(pad_headroom(n_ns, 64), dtype=np.int32)
    for ns in namespaces:
        if ns.relations:
            ns_has_config[ns_ids[ns.name]] = 1

    # ---- edges --------------------------------------------------------------
    n_t = len(tuples)
    if with_edge_tables:
        t_obj, t_rel, t_skind, t_sa, t_sb = encode_edge_arrays(
            tuples, ns_ids, rel_ids, obj_slots, subj_ids
        )
        tables = build_edge_tables(t_obj, t_rel, t_skind, t_sa, t_sb)
    else:
        z = np.zeros(0, dtype=np.int32)
        tables = build_edge_tables(z, z, z, z, z)
    dh_obj, dh_rel, dh_skind, dh_sa, dh_sb = (
        tables["dh_obj"], tables["dh_rel"], tables["dh_skind"],
        tables["dh_sa"], tables["dh_sb"],
    )
    dh_val, dh_probes = tables["dh_val"], tables["dh_probes"]
    rh_obj, rh_rel, rh_row = tables["rh_obj"], tables["rh_rel"], tables["rh_row"]
    rh_probes = tables["rh_probes"]
    row_ptr = tables["row_ptr"]
    e_obj, e_rel = tables["e_obj"], tables["e_rel"]

    # ---- rewrite programs ---------------------------------------------------
    (
        instr_kind, instr_rel, instr_rel2, prog_flags, K_eff, island_circuits,
    ) = _build_programs(namespaces, ns_ids, rel_ids, n_config_rels, n_ns, K)

    return GraphSnapshot(
        ns_ids=ns_ids,
        rel_ids=rel_ids,
        obj_slots=obj_slots,
        subj_ids=subj_ids,
        n_config_rels=n_config_rels,
        wildcard_rel=rel_ids[WILDCARD_RELATION],
        objslot_ns=objslot_ns,
        ns_has_config=ns_has_config,
        dh_obj=dh_obj, dh_rel=dh_rel, dh_skind=dh_skind,
        dh_sa=dh_sa, dh_sb=dh_sb, dh_val=dh_val, dh_probes=dh_probes,
        rh_obj=rh_obj, rh_rel=rh_rel, rh_row=rh_row, rh_probes=rh_probes,
        row_ptr=row_ptr, e_obj=e_obj, e_rel=e_rel,
        instr_kind=instr_kind, instr_rel=instr_rel, instr_rel2=instr_rel2,
        prog_flags=prog_flags, K=K_eff,
        island_circuits=island_circuits,
        version=version, n_tuples=n_t,
    )


def _build_programs(namespaces, ns_ids, rel_ids, n_config_rels, n_ns, K):
    """Compile every namespace relation's rewrite into the dense program
    tables; shared by the object-path and columnar builders. Two passes
    so the stored K is the EFFECTIVE max program length (per-step kernel
    cost scales with K)."""
    NR = n_ns * max(n_config_rels, 1)
    compiled: dict[int, tuple] = {}
    missing_flags: list[int] = []
    for ns in namespaces:
        nsid = ns_ids[ns.name]
        if not ns.relations:
            continue
        declared = {rel.name for rel in ns.relations}
        # any (ns, rel) not declared is an engine error when visited
        # (ref: internal/check/engine.go:219-228)
        for rel_name, rid in rel_ids.items():
            if rid >= n_config_rels:
                continue
            if rel_name not in declared:
                missing_flags.append(nsid * n_config_rels + rid)
        for rel in ns.relations:
            rid = rel_ids[rel.name]
            pidx = nsid * n_config_rels + rid
            compiled[pidx] = _compile_rewrite(rel.subject_set_rewrite, rel_ids, K)

    K_eff = max([len(instrs) for instrs, _, _ in compiled.values()] + [1])
    instr_kind = np.zeros((NR, K_eff), dtype=np.int32)
    instr_rel = np.zeros((NR, K_eff), dtype=np.int32)
    instr_rel2 = np.zeros((NR, K_eff), dtype=np.int32)
    prog_flags = np.zeros(NR, dtype=np.int32)
    island_circuits: dict[int, tuple] = {}
    for pidx in missing_flags:
        prog_flags[pidx] |= FLAG_CONFIG_MISSING
    for pidx, (instrs, circuit, cflags) in compiled.items():
        prog_flags[pidx] |= cflags
        if circuit is not None:
            island_circuits[pidx] = circuit
        for k, (kind, a, b) in enumerate(instrs):
            instr_kind[pidx, k] = kind
            instr_rel[pidx, k] = a
            instr_rel2[pidx, k] = b
    return instr_kind, instr_rel, instr_rel2, prog_flags, K_eff, island_circuits


def _register_config_vocab(namespaces, ns_id, rel_id) -> None:
    """Config-referenced relations first, so rewrite-capable rel ids are
    dense in [0, n_config_rels) and the program table stays small."""
    rel_id(WILDCARD_RELATION)
    for ns in namespaces:
        ns_id(ns.name)
        for rel in ns.relations:
            rel_id(rel.name)
            if rel.subject_set_rewrite is not None:
                for _kind, a, b in _walk_rewrite_relations(rel.subject_set_rewrite):
                    rel_id(a)
                    if b:
                        rel_id(b)


def columnar_encode(
    cols,
    namespaces: Sequence[Namespace],
    K: int = 8,
    version: int = 0,
) -> tuple[GraphSnapshot, tuple[np.ndarray, ...]]:
    """Columnar vocabulary build + edge-array encoding: every per-tuple
    operation is a numpy primitive (np.unique factorization +
    searchsorted joins), no Python loop over tuples — the path that
    makes 1e7..1e8-edge ingest feasible (round-1 VERDICT item 3; the
    reference's load generator tops out at 1e6 via CLI,
    scripts/create-many-tuples.sh).

    `cols` is a storage.columns.TupleColumns. Vocabulary ids differ from
    build_snapshot's insertion order (sorted-unique instead), which is
    semantically irrelevant: ids never leave the engine. Big vocabs
    (object slots, subjects) become ArrayMaps instead of dicts.

    Returns (snapshot-with-placeholder-edge-tables, encoded edge arrays
    (t_obj, t_rel, t_skind, t_sa, t_sb)) so the single-device builder
    and the per-shard builder (parallel/sharding.py) share one
    vectorized ingest path."""
    from ..storage.columns import TupleColumns  # noqa: F401 (doc anchor)

    ns_ids: dict[str, int] = {}
    rel_ids: dict[str, int] = {}
    _register_config_vocab(
        namespaces,
        lambda name: ns_ids.setdefault(name, len(ns_ids)),
        lambda name: rel_ids.setdefault(name, len(rel_ids)),
    )
    n_config_rels = len(rel_ids)

    is_set = cols.skind == 1
    n_t = len(cols)

    # data namespaces/relations join the small dicts in sorted order,
    # and every row is factorized in the same pass: ONE sorted-unique
    # encode per name family replaces the np.unique over the full
    # columns plus four per-row sorted lookups (the names are few; the
    # rows are 1e7+ — rank->id is then a tiny int-array gather)
    def factorize(d: dict, own: np.ndarray, sub: np.ndarray):
        all_names = _encode_utf8(np.concatenate([own, sub[is_set]]))
        uniq, _, codes = _sorted_unique_encode(all_names)
        for name in uniq:
            d.setdefault(name.decode("utf-8"), len(d))
        rank_to_id = np.array(
            [d[name.decode("utf-8")] for name in uniq], dtype=np.int32
        )
        own_ids = rank_to_id[codes[: len(own)]]
        sub_ids = np.zeros(len(sub), dtype=np.int32)
        sub_ids[is_set] = rank_to_id[codes[len(own):]]
        return own_ids, sub_ids

    t_ns, s_ns = factorize(ns_ids, cols.ns, cols.sns)
    t_rel, s_rel = factorize(rel_ids, cols.rel, cols.srel)

    # object slots: sorted-unique composite (ns_id, object) keys; the
    # slot id IS the sorted position, so encoding = one searchsorted.
    # All big-string work runs on UTF-8 bytes (S): same sort order as U,
    # 4x less data through the sort — the build's dominant cost
    own_keys = _compose_keys_bytes(t_ns, cols.obj)
    set_keys = _compose_keys_bytes(s_ns[is_set], cols.sobj[is_set])
    all_keys = np.concatenate([own_keys, set_keys])
    all_ns = np.concatenate([t_ns, s_ns[is_set]])
    if len(all_keys):
        uniq_keys, first_idx, all_codes = _sorted_unique_encode(all_keys)
    else:
        uniq_keys, first_idx, all_codes = (
            np.array([], dtype="S1"), np.array([], dtype=np.int64),
            np.array([], dtype=np.int32),
        )
    obj_slots = ArrayMap(uniq_keys, encode=_encode_obj_key, decode=_decode_obj_key)
    t_obj = all_codes[: len(own_keys)]
    sa_set = all_codes[len(own_keys):]

    plain = ~is_set
    if plain.any():
        subj_keys, _, sa_plain = _sorted_unique_encode(
            _encode_utf8(cols.sobj[plain])
        )
    else:
        subj_keys = np.array([], "S1")
        sa_plain = np.array([], dtype=np.int32)
    subj_ids = ArrayMap(subj_keys)

    t_skind = cols.skind.astype(np.int32)
    t_sa = np.zeros(n_t, dtype=np.int32)
    t_sb = np.zeros(n_t, dtype=np.int32)
    t_sa[is_set] = sa_set
    t_sb[is_set] = s_rel[is_set]
    t_sa[plain] = sa_plain

    z = np.zeros(0, dtype=np.int32)
    tables = build_edge_tables(z, z, z, z, z)

    n_ns = max(len(ns_ids), 1)
    objslot_ns = np.zeros(pad_headroom(max(len(uniq_keys), 1)), dtype=np.int32)
    if len(uniq_keys):
        objslot_ns[: len(uniq_keys)] = all_ns[first_idx]
    ns_has_config = np.zeros(pad_headroom(n_ns, 64), dtype=np.int32)
    for ns in namespaces:
        if ns.relations:
            ns_has_config[ns_ids[ns.name]] = 1

    (
        instr_kind, instr_rel, instr_rel2, prog_flags, K_eff, island_circuits,
    ) = _build_programs(namespaces, ns_ids, rel_ids, n_config_rels, n_ns, K)

    snap = GraphSnapshot(
        ns_ids=ns_ids,
        rel_ids=rel_ids,
        obj_slots=obj_slots,
        subj_ids=subj_ids,
        n_config_rels=n_config_rels,
        wildcard_rel=rel_ids[WILDCARD_RELATION],
        objslot_ns=objslot_ns,
        ns_has_config=ns_has_config,
        dh_obj=tables["dh_obj"], dh_rel=tables["dh_rel"],
        dh_skind=tables["dh_skind"], dh_sa=tables["dh_sa"],
        dh_sb=tables["dh_sb"], dh_val=tables["dh_val"],
        dh_probes=tables["dh_probes"],
        rh_obj=tables["rh_obj"], rh_rel=tables["rh_rel"],
        rh_row=tables["rh_row"], rh_probes=tables["rh_probes"],
        row_ptr=tables["row_ptr"], e_obj=tables["e_obj"],
        e_rel=tables["e_rel"],
        instr_kind=instr_kind, instr_rel=instr_rel, instr_rel2=instr_rel2,
        prog_flags=prog_flags, K=K_eff,
        island_circuits=island_circuits,
        version=version, n_tuples=n_t,
    )
    return snap, (t_obj, t_rel, t_skind, t_sa, t_sb)


def build_snapshot_columnar(
    cols,
    namespaces: Sequence[Namespace],
    K: int = 8,
    version: int = 0,
) -> GraphSnapshot:
    """Single-device columnar snapshot: vectorized ingest + one global
    set of edge tables (see columnar_encode for the scale rationale)."""
    import dataclasses

    snap, (t_obj, t_rel, t_skind, t_sa, t_sb) = columnar_encode(
        cols, namespaces, K=K, version=version
    )
    tables = build_edge_tables(t_obj, t_rel, t_skind, t_sa, t_sb)
    return dataclasses.replace(
        snap,
        dh_obj=tables["dh_obj"], dh_rel=tables["dh_rel"],
        dh_skind=tables["dh_skind"], dh_sa=tables["dh_sa"],
        dh_sb=tables["dh_sb"], dh_val=tables["dh_val"],
        dh_probes=tables["dh_probes"],
        rh_obj=tables["rh_obj"], rh_rel=tables["rh_rel"],
        rh_row=tables["rh_row"], rh_probes=tables["rh_probes"],
        row_ptr=tables["row_ptr"], e_obj=tables["e_obj"],
        e_rel=tables["e_rel"],
    )


def _map_sorted_arrays(mapping, composite: bool = False):
    """(sorted_keys, values) numpy arrays from a vocab dict or ArrayMap,
    ready for _sorted_lookup. `composite` encodes dict keys of the
    (ns_id, object) form into the ArrayMap's "ns\\x1fobj" string form."""
    if isinstance(mapping, ArrayMap):
        keys = mapping._keys
        # None value array = id IS the sorted position (_sorted_lookup
        # handles it without materializing an arange over the vocab)
        vals = (
            None
            if mapping._values is None
            else np.asarray(mapping._values, dtype=np.int64)
        )
        return keys, vals
    if composite:
        items = [
            (f"{ns}{_SEP}{obj}", v) for (ns, obj), v in mapping.items()
        ]
    else:
        items = list(mapping.items())
    if not items:
        return np.array([], dtype="U1"), np.array([], dtype=np.int64)
    keys = np.array([k for k, _ in items], dtype="U")
    vals = np.array([v for _, v in items], dtype=np.int64)
    order = np.argsort(keys)
    return keys[order], vals[order]


def _vocab_arrays(snap: GraphSnapshot, name: str, mapping, composite=False):
    """Per-snapshot cached _map_sorted_arrays (the snapshot is
    immutable; rebuilding the dict-vocab sorted arrays per batch costs
    O(V log V) string sorting on the serve hot path)."""
    cached = snap._vocab_cache.get(name)
    if cached is None:
        cached = _map_sorted_arrays(mapping, composite=composite)
        snap._vocab_cache[name] = cached
    return cached


def _lookup_name_columns(
    snap: GraphSnapshot, ns_a, obj_a, rel_a, is_set, sns_a, sobj_a, srel_a
):
    """Vectorized base-vocab lookups over U name columns — the ONE
    pipeline shared by encode_edge_columns (expand-CSR builds) and
    encode_query_batch (check query encoding). Unknown namespaces
    compose to "-1\\x1f..." which matches nothing; query arrays convert
    to the vocab key dtype via _queries_like/_compose_keys_like.

    Returns (t_ns, t_rel, t_obj, s_ns, s_rel, s_slot, sid), all int32
    with -1 for not-in-base."""
    ns_keys, ns_vals = _vocab_arrays(snap, "ns", snap.ns_ids)
    rel_keys, rel_vals = _vocab_arrays(snap, "rel", snap.rel_ids)
    obj_keys, obj_vals = _vocab_arrays(snap, "obj", snap.obj_slots, True)
    subj_keys, subj_vals = _vocab_arrays(snap, "subj", snap.subj_ids)

    t_ns = _sorted_lookup(ns_keys, ns_vals, ns_a)
    t_rel = _sorted_lookup(rel_keys, rel_vals, rel_a)
    t_obj = _sorted_lookup(
        obj_keys, obj_vals, _compose_keys_like(obj_keys, t_ns, obj_a)
    )
    s_ns = np.where(is_set, _sorted_lookup(ns_keys, ns_vals, sns_a), -1)
    s_rel = np.where(is_set, _sorted_lookup(rel_keys, rel_vals, srel_a), -1)
    s_slot = _sorted_lookup(
        obj_keys, obj_vals, _compose_keys_like(obj_keys, s_ns, sobj_a)
    )
    sid = _sorted_lookup(subj_keys, subj_vals, _queries_like(subj_keys, sobj_a))
    return t_ns, t_rel, t_obj, s_ns, s_rel, s_slot, sid


def encode_edge_columns(cols, snapshot: GraphSnapshot):
    """Vectorized (t_obj, t_rel, t_skind, t_sa, t_sb, keep) encoding of
    TupleColumns under an EXISTING snapshot's vocabularies — the scale
    path for expand-state builds (no per-tuple Python). Names unknown to
    the snapshot drop via `keep`: that matches build_full_csr's
    view-skip semantics, because any tuple written after the base
    snapshot rides the delta overlay and its (obj, rel) row is
    dirty-flagged, which routes the affected queries to exact host
    replay regardless of CSR contents."""
    is_set = np.asarray(cols.skind) == 1
    _, t_rel, t_obj, _, s_rel, s_slot, sa_plain = _lookup_name_columns(
        snapshot,
        cols.ns.astype("U"), cols.obj, cols.rel.astype("U"),
        is_set, cols.sns.astype("U"), cols.sobj, cols.srel.astype("U"),
    )

    t_skind = np.asarray(cols.skind, dtype=np.int32)
    t_sa = np.where(is_set, s_slot, sa_plain).astype(np.int32)
    t_sb = np.where(is_set, np.maximum(s_rel, 0), 0).astype(np.int32)
    subject_ok = np.where(
        is_set, (s_slot != -1) & (s_rel != -1), sa_plain != -1
    )
    keep = (t_obj != -1) & (t_rel != -1) & subject_ok
    return t_obj, t_rel, t_skind, t_sa, t_sb, keep


def _encode_nodes(view, ns_l, obj_l, rel_l, present):
    """Vectorized base lookups + overlay-dict node patch — the node-half
    shared by encode_query_batch and encode_node_batch (ONE copy of the
    overlay-fallback invariant: resolve ns, then rel, then the slot
    keyed on the resolved ns — an overlay-era namespace can only own
    overlay-era objects, so no big-vocab scalar lookups happen here).

    Returns (slot, rel, valid) arrays of length n."""
    snap = view.snapshot
    ns_keys, ns_vals = _vocab_arrays(snap, "ns", snap.ns_ids)
    rel_keys, rel_vals = _vocab_arrays(snap, "rel", snap.rel_ids)
    obj_keys, obj_vals = _vocab_arrays(snap, "obj", snap.obj_slots, True)
    t_ns = _sorted_lookup(ns_keys, ns_vals, np.asarray(ns_l, dtype="U"))
    t_rel = _sorted_lookup(rel_keys, rel_vals, np.asarray(rel_l, dtype="U"))
    t_obj = _sorted_lookup(
        obj_keys, obj_vals,
        _compose_keys_like(obj_keys, t_ns, np.asarray(obj_l, dtype="U")),
    )
    valid = present & (t_ns != -1) & (t_rel != -1) & (t_obj != -1)
    ov = view.overlay
    if ov is not None:
        for i in np.flatnonzero(present & ~valid):
            i = int(i)
            ns = int(t_ns[i])
            if ns == -1:
                ns = ov.ns_ids.get(ns_l[i], -1)
            rel = int(t_rel[i])
            if rel == -1:
                rel = ov.rel_ids.get(rel_l[i], -1)
            slot = int(t_obj[i])
            if slot == -1 and ns != -1:
                slot = ov.obj_slots.get((ns, obj_l[i]), -1)
            if ns != -1 and rel != -1 and slot != -1:
                t_obj[i], t_rel[i], valid[i] = slot, rel, True
    return t_obj, t_rel, valid


def encode_object_column(view, ns_id: int, objects):
    """Vectorized candidate-object encoding for a FIXED namespace — the
    BatchFilter shape: one (namespace, relation), thousands of objects.
    One composed-key binary search over the object vocab (the ns/rel
    lookups encode_node_batch pays per row are constants here), then an
    overlay-dict patch for post-base names. Returns (slots, valid),
    both numpy ([n] int32, [n] bool)."""
    snap = view.snapshot
    n = len(objects)
    if isinstance(snap.obj_slots, ArrayMap):
        # big-vocab path: one composed-key binary search over the
        # sorted key array
        obj_keys, obj_vals = _vocab_arrays(snap, "obj", snap.obj_slots, True)
        obj_a = np.asarray(objects, dtype="U")
        ns_arr = np.full(n, ns_id, dtype=np.int32)
        slots = _sorted_lookup(
            obj_keys, obj_vals, _compose_keys_like(obj_keys, ns_arr, obj_a)
        )
    else:
        # dict-vocab path: direct dict lookups beat the numpy string
        # pipeline here — the U-array conversion alone costs more than
        # 10k dict probes (measured on the 10k-object filter leg)
        get = snap.obj_slots.get
        slots = np.fromiter(
            (get((ns_id, o), -1) for o in objects),
            dtype=np.int64, count=n,
        )
    valid = slots != -1
    ov = view.overlay
    if ov is not None and ov.obj_slots and not valid.all():
        for i in np.flatnonzero(~valid):
            slot = ov.obj_slots.get((ns_id, objects[int(i)]))
            if slot is not None:
                slots[i] = slot
                valid[i] = True
    return slots.astype(np.int32), valid


def encode_node_batch(view, triples, B: int):
    """Vectorized (namespace, object, relation) -> (obj_slot, rel_id)
    encoding for B node queries (the expand path's analog of
    encode_query_batch: per-subject scalar ArrayMap lookups cost ~1 ms
    each at 1e7 vocab). `triples[i]` is (ns, obj, rel) or None (row
    stays invalid). Returns (q_obj, q_rel, q_valid)."""
    n = len(triples)
    ns_l = [""] * n
    obj_l = [""] * n
    rel_l = [""] * n
    present = np.zeros(n, dtype=bool)
    for i, tr in enumerate(triples):
        if tr is None:
            continue
        ns_l[i], obj_l[i], rel_l[i] = tr
        present[i] = True

    t_obj, t_rel, valid = _encode_nodes(view, ns_l, obj_l, rel_l, present)
    q_obj = np.zeros(B, dtype=np.int32)
    q_rel = np.zeros(B, dtype=np.int32)
    q_valid = np.zeros(B, dtype=bool)
    q_obj[:n] = np.where(valid, t_obj, 0)
    q_rel[:n] = np.where(valid, t_rel, 0)
    q_valid[:n] = valid
    return q_obj, q_rel, q_valid


def encode_query_batch(view, tuples, B: int):
    """Vectorized batch query encoding against an ArrayMap-vocab
    snapshot: ONE composed-key searchsorted per column for the whole
    batch instead of 2-3 scalar ArrayMap.get calls per query — at 1e7
    vocab the per-query path costs ~1 ms each and dominated
    check_batch (engine 988 checks/s vs 77k/s for the kernel alone,
    measured round 3). Queries the base vocab can't resolve are
    re-encoded per-query through `view` (the delta overlay may know
    names written after the base snapshot); exact same semantics as the
    per-tuple loop.

    `tuples` is a CheckColumns (a served BatchCheck's items, which
    never were tuples) or a run of RelationTuples, whose columns are
    taken here; either way the encoder reads columns and builds no
    tuple.

    Returns (q_obj, q_rel, q_skind, q_sa, q_sb, q_valid) arrays of
    length B (tail rows beyond len(tuples) stay invalid)."""
    snap = view.snapshot
    cols = CheckColumns.of(tuples)
    n = len(cols)
    ns_l, obj_l, rel_l, skind_l, sns_l, sobj_l, srel_l = cols.columns()

    is_set = np.asarray(skind_l, dtype=np.int32) == 1
    # node half: shared vectorized base lookups + overlay node patch
    node_obj, node_rel, node_valid = _encode_nodes(
        view, ns_l, obj_l, rel_l, np.ones(n, dtype=bool)
    )
    # subject half: base lookups over the subject columns
    ns_keys, ns_vals = _vocab_arrays(snap, "ns", snap.ns_ids)
    rel_keys, rel_vals = _vocab_arrays(snap, "rel", snap.rel_ids)
    obj_keys, obj_vals = _vocab_arrays(snap, "obj", snap.obj_slots, True)
    subj_keys, subj_vals = _vocab_arrays(snap, "subj", snap.subj_ids)
    sobj_arr = np.asarray(sobj_l, dtype="U")
    s_ns = np.where(
        is_set, _sorted_lookup(ns_keys, ns_vals, np.asarray(sns_l, "U")), -1
    )
    s_rel = np.where(
        is_set, _sorted_lookup(rel_keys, rel_vals, np.asarray(srel_l, "U")), -1
    )
    s_slot = _sorted_lookup(
        obj_keys, obj_vals, _compose_keys_like(obj_keys, s_ns, sobj_arr)
    )
    sid = _sorted_lookup(subj_keys, subj_vals, _queries_like(subj_keys, sobj_arr))

    set_ok = is_set & (s_slot != -1) & (s_rel != -1)
    plain_ok = ~is_set & (sid != -1)

    q_obj = np.zeros(B, dtype=np.int32)
    q_rel = np.zeros(B, dtype=np.int32)
    q_skind = np.zeros(B, dtype=np.int32)
    q_sa = np.full(B, -2, dtype=np.int32)  # sentinel: matches nothing
    q_sb = np.zeros(B, dtype=np.int32)
    q_valid = np.zeros(B, dtype=bool)
    q_obj[:n] = np.where(node_valid, node_obj, 0)
    q_rel[:n] = np.where(node_valid, node_rel, 0)
    q_valid[:n] = node_valid
    q_skind[:n] = np.where(set_ok, 1, 0)
    q_sa[:n] = np.where(set_ok, s_slot, np.where(plain_ok, sid, -2))
    q_sb[:n] = np.where(set_ok, s_rel, 0)

    ov = view.overlay
    if ov is not None:
        # subject-only overlay patch (the node half was patched inside
        # _encode_nodes): still SMALL-dict lookups only — the base
        # verdict for every subject component is already known
        unresolved = np.flatnonzero(node_valid & ~(set_ok | plain_ok))
        for i in unresolved:
            i = int(i)
            if is_set[i]:
                sns = int(s_ns[i])
                if sns == -1:
                    sns = ov.ns_ids.get(sns_l[i], -1)
                srl = int(s_rel[i])
                if srl == -1:
                    srl = ov.rel_ids.get(srel_l[i], -1)
                ssl = int(s_slot[i])
                if ssl == -1 and sns != -1:
                    ssl = ov.obj_slots.get((sns, sobj_l[i]), -1)
                if sns != -1 and srl != -1 and ssl != -1:
                    q_skind[i], q_sa[i], q_sb[i] = 1, ssl, srl
                else:
                    q_skind[i], q_sa[i], q_sb[i] = 0, -2, 0
            else:
                sv = int(sid[i])
                if sv == -1:
                    sv = ov.subj_ids.get(sobj_l[i], -1)
                if sv != -1:
                    q_skind[i], q_sa[i], q_sb[i] = 0, sv, 0
                else:
                    q_skind[i], q_sa[i], q_sb[i] = 0, -2, 0
    return q_obj, q_rel, q_skind, q_sa, q_sb, q_valid


# -- transposed (reverse-reachability) mirror ---------------------------------
#
# The forward tables answer "expand from (obj, rel)"; the reverse subsystem
# (engine/reverse_kernel.py) walks the SAME graph backwards — "which
# (obj, rel) nodes can reach this subject?" — over a transposed twin of the
# forward layout, built from the same encoded edge arrays:
#
#   - reverse-edge CSR: subject-set edges grouped by their SUBJECT object
#     slot (key (sa, 0)), payload (parent obj, parent rel, edge sb).
#     Reverse-BFS expansion gathers one row per reached node and inverts
#     checkExpandSubject (sb == task rel) and TTU traversal (row relation
#     matches an inverted TTU instruction) per edge.
#   - reverse-seed CSR: ALL direct edges grouped by their full subject key
#     (sa, reverse_subject_tag(skind, sb)) with payload (obj, rel) — the
#     per-query seed frontier is exactly the nodes whose direct probe the
#     forward kernel would hit for that subject.
#   - inverted rewrite programs: for every monotone rewrite instruction
#     "(ns, rel_p) reaches rel_c via COMPUTED/TTU", one entry keyed by
#     rel_c so a reverse task (obj, rel_c) can enumerate its rewrite
#     predecessors. Non-monotone programs compile to POISON entries
#     (reaching their leaf relations host-flags the query), and any NOT in
#     the config disables the device path entirely (NOT-members are not
#     reverse-enumerable: "NOT deny" is a member exactly when no deny path
#     exists for the subject, which a reachability walk cannot observe).
#
# Same open-addressing/probe discipline as the forward tables
# (slots_per_bucket keyed off the key-column count), so the device kernel's
# bucketized row gathers serve both directions unchanged.

# reverse-instruction kinds (rinstr_kind lanes)
RINSTR_NONE = 0
RINSTR_COMPUTED = 1  # pred (task obj, rel_p) at the SAME depth, ns-gated
RINSTR_TTU = 2  # pred (edge obj, rel_p) at depth-1 when edge rel == rel_t
RINSTR_POISON = 3  # island program pulls from this rel: host-flag the query

# inverted-entry cap per target relation: a rel_c referenced by more
# rewrite instructions than this gets one POISON row instead (host
# fallback), mirroring the forward K/CIRCUIT caps' exactness contract
RINSTR_CAP = 16


# plain/set discriminator stride in reverse_subject_tag: a FIXED constant
# (not the relation-vocab size) so builders, the delta's reverse-dirty
# entries, and query encoding can never disagree on the tag basis across
# vocab growth (a retained mirror patched through a compaction keeps
# serving while the vocab grows). Relation ids are dense small ints —
# far below this.
_REVERSE_TAG_STRIDE = 1 << 20


def reverse_subject_tag(skind, sb):
    """Second key column of the reverse-seed CSR: disambiguates plain
    subject ids from subject-set slots sharing an int (subject vocabs
    overlap numerically). Vectorized over numpy arrays. Tag 0 is
    reserved (the delta reverse-dirty table uses it for row-level
    entries)."""
    return (
        np.asarray(skind, dtype=np.int32) * np.int32(_REVERSE_TAG_STRIDE)
        + np.asarray(sb, dtype=np.int32)
        + np.int32(1)
    )


def build_reverse_tables(
    t_obj: np.ndarray,
    t_rel: np.ndarray,
    t_skind: np.ndarray,
    t_sa: np.ndarray,
    t_sb: np.ndarray,
) -> dict:
    """Transposed twin of build_edge_tables from the SAME encoded edge
    arrays: reverse-edge CSR (subject-set edges by subject slot) +
    reverse-seed CSR (all edges by full subject key)."""
    is_set = np.asarray(t_skind) == 1
    rvh_obj, _rvh_rel, rvh_row, rvh_probes, rv_row_ptr, (
        rv_pobj, rv_prel, rv_sb,
    ) = group_rows_csr(
        t_sa[is_set].astype(np.int32),
        np.zeros(int(is_set.sum()), dtype=np.int32),
        (
            t_obj[is_set].astype(np.int32),
            t_rel[is_set].astype(np.int32),
            t_sb[is_set].astype(np.int32),
        ),
    )
    tags = reverse_subject_tag(t_skind, t_sb)
    rsh_obj, rsh_tag, rsh_row, rsh_probes, rs_row_ptr, (rs_obj, rs_rel) = (
        group_rows_csr(
            t_sa.astype(np.int32),
            tags,
            (t_obj.astype(np.int32), t_rel.astype(np.int32)),
        )
    )
    return {
        "rvh_obj": rvh_obj, "rvh_rel": _rvh_rel, "rvh_row": rvh_row,
        "rvh_probes": rvh_probes, "rv_row_ptr": rv_row_ptr,
        "rv_pobj": rv_pobj, "rv_prel": rv_prel, "rv_sb": rv_sb,
        "rsh_obj": rsh_obj, "rsh_tag": rsh_tag, "rsh_row": rsh_row,
        "rsh_probes": rsh_probes, "rs_row_ptr": rs_row_ptr,
        "rs_obj": rs_obj, "rs_rel": rs_rel,
    }


def _walk_rewrite_leaves(rw: ast.SubjectSetRewrite, has_not: bool = False):
    """Yield (kind, relation, relation2, under_not) for every leaf of a
    rewrite tree, including leaves inside AND/NOT islands (unlike
    _compile_rewrite, which drops oversized programs — the INVERTED table
    must see every leaf to know when a reverse walk enters a program's
    pull range)."""
    for child in rw.children:
        if isinstance(child, ast.ComputedSubjectSet):
            yield ("computed", child.relation, "", has_not)
        elif isinstance(child, ast.TupleToSubjectSet):
            yield (
                "ttu", child.relation, child.computed_subject_set_relation,
                has_not,
            )
        elif isinstance(child, ast.SubjectSetRewrite):
            yield from _walk_rewrite_leaves(child, has_not)
        elif isinstance(child, ast.InvertResult):
            sub = child.child
            if isinstance(sub, ast.SubjectSetRewrite):
                yield from _walk_rewrite_leaves(sub, True)
            elif isinstance(sub, ast.ComputedSubjectSet):
                yield ("computed", sub.relation, "", True)
            elif isinstance(sub, ast.TupleToSubjectSet):
                yield (
                    "ttu", sub.relation, sub.computed_subject_set_relation,
                    True,
                )


def build_reverse_programs(
    namespaces, ns_ids: dict, rel_ids: dict, n_config_rels: int,
    cap: int = RINSTR_CAP,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int, bool]:
    """Invert every namespace relation's rewrite for reverse-BFS.

    Returns (rinstr_kind, rinstr_relp, rinstr_relt, rinstr_ns) dense
    [n_config_rels, RK] tables keyed by TARGET relation rel_c, the
    effective RK, and `host_all`:

      - monotone programs invert exactly: COMPUTED(rel_c) in (ns, rel_p)
        -> entry (RINSTR_COMPUTED, rel_p, 0, ns) under rel_c;
        TTU(rel_t, rel_c) -> (RINSTR_TTU, rel_p, rel_t, ns). Oversized
        monotone programs (forward FLAG_HOST_ONLY) invert fine — reverse
        traversal evaluates one entry per step, not a K-bounded program.
      - AND-island programs emit POISON entries under each leaf's rel_c:
        a member of the island implies EVERY leaf sub-check is a member,
        so the reverse walk is guaranteed to reach a leaf relation node
        and trip the poison before the island's members could be missed.
        COMPUTED poisons are ns-gated (the leaf shares the island's
        object); TTU poisons use ns = -1 (their leaf objects live in
        arbitrary namespaces).
      - any NOT => host_all=True: NOT-members exist precisely where NO
        path exists, which reverse reachability cannot enumerate; the
        engine routes every reverse query to the host oracle.
      - more than `cap` entries under one rel_c => that row collapses to
        a single any-ns POISON (cause-coded fallback, never truncation).
    """
    per_target: dict[int, list[tuple[int, int, int, int]]] = {}
    host_all = False
    for ns in namespaces:
        nsid = ns_ids[ns.name]
        for rel in ns.relations:
            rw = rel.subject_set_rewrite
            if rw is None:
                continue
            rel_p = rel_ids[rel.name]
            monotone = _is_monotone(rw)
            for kind, a, b, under_not in _walk_rewrite_leaves(rw):
                if under_not:
                    host_all = True
                if kind == "computed":
                    rel_c, rel_t = rel_ids[a], 0
                    ekind = RINSTR_COMPUTED if monotone else RINSTR_POISON
                    ens = nsid
                else:
                    rel_c, rel_t = rel_ids[b], rel_ids[a]
                    ekind = RINSTR_TTU if monotone else RINSTR_POISON
                    ens = nsid if monotone else -1
                per_target.setdefault(rel_c, []).append(
                    (ekind, rel_p, rel_t, ens)
                )
    # dedupe (shared sub-rewrites register identical entries) + cap
    for rel_c, entries in per_target.items():
        uniq = list(dict.fromkeys(entries))
        if len(uniq) > cap:
            uniq = [(RINSTR_POISON, 0, 0, -1)]
        per_target[rel_c] = uniq
    RK = max([len(v) for v in per_target.values()] + [1])
    NR = max(n_config_rels, 1)
    rinstr_kind = np.zeros((NR, RK), dtype=np.int32)
    rinstr_relp = np.zeros((NR, RK), dtype=np.int32)
    rinstr_relt = np.zeros((NR, RK), dtype=np.int32)
    rinstr_ns = np.zeros((NR, RK), dtype=np.int32)
    for rel_c, entries in per_target.items():
        for k, (ekind, rel_p, rel_t, ens) in enumerate(entries):
            rinstr_kind[rel_c, k] = ekind
            rinstr_relp[rel_c, k] = rel_p
            rinstr_relt[rel_c, k] = rel_t
            rinstr_ns[rel_c, k] = ens
    return rinstr_kind, rinstr_relp, rinstr_relt, rinstr_ns, RK, host_all


def _walk_rewrite_relations(rw: ast.SubjectSetRewrite):
    """Yield (kind, relation, relation2) for every leaf referenced by a
    rewrite tree (used only to pre-register relation names in the vocab)."""
    for child in rw.children:
        if isinstance(child, ast.ComputedSubjectSet):
            yield ("computed", child.relation, "")
        elif isinstance(child, ast.TupleToSubjectSet):
            yield ("ttu", child.relation, child.computed_subject_set_relation)
        elif isinstance(child, ast.SubjectSetRewrite):
            yield from _walk_rewrite_relations(child)
        elif isinstance(child, ast.InvertResult):
            sub = child.child
            if isinstance(sub, ast.SubjectSetRewrite):
                yield from _walk_rewrite_relations(sub)
            elif isinstance(sub, ast.ComputedSubjectSet):
                yield ("computed", sub.relation, "")
            elif isinstance(sub, ast.TupleToSubjectSet):
                yield ("ttu", sub.relation, sub.computed_subject_set_relation)
