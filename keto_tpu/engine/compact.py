"""Incremental compaction: fold pending write ops into the base mirror.

When the fixed-shape delta overlay overflows (engine/delta.py:
DELTA_COMPACT_THRESHOLD), the engine previously had one move: a FULL
snapshot rebuild — O(edges) store ingest + sort/unique + hash-table
construction, minutes at 1e7+ tuples (SCALE_5e7_r03.json: 738 s build).
This module provides the middle path: merge the pending ops into COPIES
of the base snapshot's tables, touching only affected slots/rows.

The reference never needs this — every check re-queries SQL
(internal/check/engine.go:54-80) so "the graph" is always current; the
immutable-device-mirror design trades that for kernel throughput and
pays here (SURVEY §7 "mutable graph vs immutable device buffers").

How each table merges:

  - direct-edge hash table (dh_*): open addressing with value-liveness.
    Inserts claim empty slots along their probe chain (first-free is
    safe: entries are never REMOVED, so an existing key can never live
    beyond a free slot — tombstones keep their key and only zero the
    value, chains never break). Deletes set val=0 in place; the kernel's
    packed-row probe already gathers the value lane, so honoring
    `val == 1` as liveness costs nothing (kernel.probe_phase).
  - subject-set CSR (rh_* / row_ptr / e_*): affected (obj, rel) rows are
    REWRITTEN AT THE TAIL of the edge arrays; the row hash entry is
    repointed at the new row, the old span becomes garbage. Unaffected
    rows (the overwhelming majority) are untouched. Garbage is tracked
    on the snapshot (merge_garbage) and a full rebuild triggers once it
    passes GARBAGE_FRACTION of the edge arrays — classic log-structured
    amortization.
  - vocabularies: names first seen in the merged ops append AFTER the
    base ids (ArrayMap.merged_with / dict update), exactly like the
    delta overlay's VocabOverlay, so existing encodings stay valid.

Cost: O(ops · affected-row-size) numpy work plus one memcpy per table
(bandwidth-bound, sub-second per GB) — vs minutes for the full rebuild.
The merged snapshot is a NEW BASE (empty delta, has_delta=False);
probe limits may grow by a step, costing at most one XLA recompile.

The merge returns None (caller falls back to full rebuild) when the ops
batch is too large a fraction of the graph, the hash tables would pass
MAX_LOAD occupancy, probing would exceed MAX_PROBES, or accumulated CSR
garbage passes GARBAGE_FRACTION.

Future work: after a merge the engine re-uploads the
full table set; the deltas are actually tiny (op slots in the hash
tables + the CSR tail), so a jitted device-side scatter
(`dh_pack.at[slots // 8, ...].set(rows)`: the pack is stored as bucket
rows, kernel.as_bucket_rows) could cut the post-merge upload from
O(tables) to O(ops) — it needs headroom-padded edge arrays so the CSR
tail append keeps shapes static, and slot tracking through
_hash_insert. Worth it once the post-merge upload shows up in a profile
under write churn; the host-side merge (this module) is the part that
was minutes.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from ..ketoapi import RelationTuple
from .snapshot import (
    EMPTY,
    _GOLDEN,
    ArrayMap,
    GraphSnapshot,
    _build_hash_table,
    _lookup_name_columns,
    hash_combine,
    mix32,
    probe_slot,
    slots_per_bucket,
)

# merge only while the ops batch is a small fraction of the graph — past
# this a rebuild costs comparably and resets load/garbage for free
MAX_OPS_FRACTION = 8  # ops <= n_tuples / MAX_OPS_FRACTION
MIN_OPS_CAP = 65536  # floor so small graphs still merge
MAX_PROBES = 32  # probe-limit ceiling after insertion; under the
# bucketized sequence (snapshot.probe_slot) the kernel pays one gathered
# bucket row per slots_per_bucket slots, so chains up to one-two buckets
# are cheap — past this, rebuild at proper capacity
MAX_LOAD = 0.40  # occupancy ceiling (tables build at 0.25; tombstones
# and merged inserts erode sparseness, which probe limits pay for)
GARBAGE_FRACTION = 0.25  # rewritten-row garbage that forces a rebuild
GARBAGE_FLOOR = 65536  # edges; below this, garbage is noise (small CSRs
# would otherwise trip the fraction on their first rewritten row)


class MergeFallback(Exception):
    """Merge not applicable/beneficial — caller does a full rebuild."""


def _hash_insert(
    key_cols: list[np.ndarray],
    val_col: np.ndarray,
    new_keys: tuple[np.ndarray, ...],
    new_vals: np.ndarray,
    base_probes: int,
) -> int:
    """Vectorized upsert of (new_keys -> new_vals) into an occupied
    open-addressing table (arrays are caller-owned copies, mutated in
    place). Existing keys update their value; new keys claim the first
    free slot on their probe chain (safe — see module docstring).
    new_keys must be deduplicated. Returns the table's new probe limit;
    raises MergeFallback past MAX_PROBES."""
    n = len(new_vals)
    if n == 0:
        return base_probes
    cap = len(val_col)
    h1 = hash_combine(*new_keys)
    h2 = mix32(h1 ^ _GOLDEN) | np.uint32(1)
    pending = np.arange(n)
    probe = np.zeros(n, dtype=np.uint32)
    max_probes = base_probes
    while len(pending):
        depth = int(probe[pending].min()) + 1
        if depth > MAX_PROBES:
            raise MergeFallback("probe limit exceeded on merge insert")
        slots = probe_slot(
            h1[pending], h2[pending], probe[pending], cap,
            slots_per_bucket(len(new_keys)),
        ).astype(np.int64)
        match = np.ones(len(pending), dtype=bool)
        for col, k in zip(key_cols, new_keys):
            match &= col[slots] == k[pending]
        if match.any():
            val_col[slots[match]] = new_vals[pending[match]]
            max_probes = max(max_probes, int(probe[pending[match]].max()) + 1)
        free = (key_cols[0][slots] == EMPTY) & ~match
        if free.any():
            # among pending rows probing the same free slot, first wins
            order = np.argsort(slots[free], kind="stable")
            idx = pending[free][order]
            fslots = slots[free][order]
            uniq, first = np.unique(fslots, return_index=True)
            winners = idx[first]
            for col, k in zip(key_cols, new_keys):
                col[uniq] = k[winners]
            val_col[uniq] = new_vals[winners]
            max_probes = max(max_probes, int(probe[winners].max()) + 1)
            placed = np.zeros(n, dtype=bool)
            placed[winners] = True
            placed[pending[match]] = True
            rest = pending[~placed[pending]]
        else:
            rest = pending[~match]
        probe[rest] += 1
        pending = rest
    return max_probes


def _rehash_table(
    key_cols: list[np.ndarray],
    val_col: np.ndarray,
    new_keys: tuple[np.ndarray, ...],
    new_vals: np.ndarray,
    drop_zero_vals: bool,
) -> tuple[list[np.ndarray], np.ndarray, int]:
    """Rebuild an open-addressing table from its own (live) entries plus
    `new_keys -> new_vals`, growing capacity as needed. Pure int32
    sort/hash work — the expensive parts of a FULL rebuild (store
    ingest, string vocab sort/unique) never run. New entries win over
    existing ones on key collision (last-op-wins); with
    `drop_zero_vals`, value-0 rows (delete tombstones) are dropped
    entirely — a fresh table needs no masking entries.

    Safe to call on a table _hash_insert partially mutated: mutated
    slots only ever hold op data that `new_keys/new_vals` re-supply.
    Returns (key_cols, val_col, probe_limit)."""
    live = np.flatnonzero(
        (key_cols[0] != EMPTY) & ((val_col != 0) if drop_zero_vals else True)
    )
    all_keys = [
        np.concatenate([nk, col[live]]).astype(np.int32)
        for nk, col in zip(new_keys, key_cols)
    ]
    all_vals = np.concatenate(
        [new_vals, val_col[live]]
    ).astype(np.int32)
    # dedupe keeping the FIRST occurrence — new entries are first
    stacked = np.stack(all_keys, axis=1)
    _, first = np.unique(stacked, axis=0, return_index=True)
    keep = np.sort(first)
    all_keys = [c[keep] for c in all_keys]
    all_vals = all_vals[keep]
    if drop_zero_vals:
        alive = all_vals != 0
        all_keys = [c[alive] for c in all_keys]
        all_vals = all_vals[alive]
    built = _build_hash_table(tuple(all_keys), all_vals, min_capacity=64)
    *cols, vals, probes = built
    return list(cols), vals, probes


def _host_row_lookup(
    rh_obj: np.ndarray, rh_rel: np.ndarray, rh_row: np.ndarray,
    probes: int, obj: int, rel: int,
) -> int:
    """Scalar host-side probe of the (obj, rel) -> row hash table
    (the numpy twin of kernel._pair_key_probe). -1 when absent."""
    cap = len(rh_obj)
    o = np.asarray([obj], dtype=np.int32)
    r = np.asarray([rel], dtype=np.int32)
    h1 = hash_combine(o, r)
    h2 = mix32(h1 ^ _GOLDEN) | np.uint32(1)
    for p in range(probes):
        # array (not scalar) arithmetic: uint32 wraparound is the point,
        # and numpy only warns about it on the scalar path
        slot = int(probe_slot(h1, h2, np.uint32(p), cap, slots_per_bucket(2))[0])
        if rh_obj[slot] == obj and rh_rel[slot] == rel:
            return int(rh_row[slot])
        if rh_obj[slot] == EMPTY:
            return -1
    return -1


def patch_csr(
    rh_cols: tuple[np.ndarray, np.ndarray, np.ndarray],
    rh_probes: int,
    row_ptr: np.ndarray,
    payloads: tuple[np.ndarray, ...],
    per_row: dict,
) -> tuple[tuple, int, np.ndarray, tuple, int]:
    """Rewrite the affected rows of a hash-addressed CSR at the tail.

    `per_row` maps (obj, rel) -> {"ins": [payload-tuples], "del":
    set(payload-tuples)}. Returns (new rh_cols, new rh_probes, new
    row_ptr, new payloads, garbage_edges). All returned arrays are fresh
    copies; inputs are never mutated (concurrent readers hold them)."""
    rh_obj, rh_rel, rh_row = (np.array(c) for c in rh_cols)
    n_rows = len(row_ptr) - 1
    tail: list[tuple[np.ndarray, ...]] = []
    new_row_keys: list[tuple[int, int]] = []
    new_row_ids: list[int] = []
    ends: list[int] = []
    garbage = 0
    pos = int(row_ptr[-1])
    next_row = n_rows
    for (obj, rel), ch in per_row.items():
        row = _host_row_lookup(rh_obj, rh_rel, rh_row, rh_probes, obj, rel)
        if row >= 0:
            lo, hi = int(row_ptr[row]), int(row_ptr[row + 1])
            base = tuple(p[lo:hi] for p in payloads)
            garbage += hi - lo
        else:
            base = tuple(p[0:0] for p in payloads)
        # drop deleted payload rows from the base span
        if ch["del"] and len(base[0]):
            stacked = list(zip(*(c.tolist() for c in base)))
            keep = np.array(
                [t not in ch["del"] for t in stacked], dtype=bool
            )
            base = tuple(c[keep] for c in base)
        # append inserts not already present (the dh table dedupes the
        # edge itself; the CSR row must not carry duplicates either)
        if ch["ins"]:
            existing = set(zip(*(c.tolist() for c in base))) if len(
                base[0]
            ) else set()
            fresh = [t for t in ch["ins"] if t not in existing]
        else:
            fresh = []
        cols = tuple(
            np.concatenate(
                [base[i], np.array([t[i] for t in fresh], dtype=np.int32)]
            ).astype(np.int32)
            for i in range(len(payloads))
        )
        tail.append(cols)
        pos += len(cols[0])
        ends.append(pos)
        # uniform for new and rewritten rows: the hash upsert below
        # either inserts the key or repoints the existing entry at the
        # tail row — last-write-wins on the value either way
        new_row_keys.append((obj, rel))
        new_row_ids.append(next_row)
        next_row += 1

    new_payloads = tuple(
        np.concatenate([payloads[i]] + [t[i] for t in tail]).astype(np.int32)
        for i in range(len(payloads))
    )
    new_row_ptr = np.concatenate(
        [row_ptr, np.array(ends, dtype=np.int32)]
    ).astype(np.int32)
    keys = np.array(new_row_keys, dtype=np.int32).reshape(-1, 2)
    key_tuple = (keys[:, 0].copy(), keys[:, 1].copy())
    vals = np.array(new_row_ids, dtype=np.int32)
    n_live = int(np.count_nonzero(rh_obj != EMPTY))
    if n_live + len(vals) > MAX_LOAD * len(rh_row):
        rh_cols2, rh_row, new_probes = _rehash_table(
            [rh_obj, rh_rel], rh_row, key_tuple, vals, drop_zero_vals=False
        )
        rh_obj, rh_rel = rh_cols2
    else:
        try:
            new_probes = _hash_insert(
                [rh_obj, rh_rel], rh_row, key_tuple, vals, rh_probes
            )
        except MergeFallback:
            # pathological clustering: rebuild the (small) row table
            rh_cols2, rh_row, new_probes = _rehash_table(
                [rh_obj, rh_rel], rh_row, key_tuple, vals,
                drop_zero_vals=False,
            )
            rh_obj, rh_rel = rh_cols2
    return (rh_obj, rh_rel, rh_row), new_probes, new_row_ptr, new_payloads, garbage


def encode_ops(
    snapshot: GraphSnapshot, ops: Sequence[tuple[str, RelationTuple]]
):
    """Vectorized op encoding under the base vocab + appended new names.

    Returns (encoded int32 [n, 5] (obj, rel, skind, sa, sb), is_insert
    bool [n], overlay) where overlay is a delta.VocabOverlay carrying the
    new vocabulary entries and the extended objslot_ns / ns_has_config.
    Scalar per-op vocab lookups cost ~1 ms each at 1e7 vocab (round-3
    finding behind encode_query_batch); ops ride the same one-searchsorted
    -per-column pipeline."""
    from .delta import build_vocab_overlay

    overlay = build_vocab_overlay(snapshot, ops)
    n = len(ops)
    ns_l = np.empty(n, dtype=object)
    obj_l = np.empty(n, dtype=object)
    rel_l = np.empty(n, dtype=object)
    sns_l = np.empty(n, dtype=object)
    sobj_l = np.empty(n, dtype=object)
    srel_l = np.empty(n, dtype=object)
    skind = np.zeros(n, dtype=np.int32)
    is_insert = np.zeros(n, dtype=bool)
    for i, (op, t) in enumerate(ops):
        ns_l[i], obj_l[i], rel_l[i] = t.namespace, t.object, t.relation
        is_insert[i] = op == "insert"
        if t.subject_set is not None:
            s = t.subject_set
            skind[i] = 1
            sns_l[i], sobj_l[i], srel_l[i] = s.namespace, s.object, s.relation
        else:
            sns_l[i], sobj_l[i], srel_l[i] = "", t.subject_id or "", ""
    is_set = skind == 1
    t_ns, t_rel, t_obj, s_ns, s_rel, s_slot, sid = _lookup_name_columns(
        snapshot,
        ns_l.astype("U"), obj_l.astype("U"), rel_l.astype("U"),
        is_set, sns_l.astype("U"), sobj_l.astype("U"), srel_l.astype("U"),
    )
    # names the base vocab can't resolve were just assigned overlay ids
    sa = np.where(is_set, s_slot, sid).astype(np.int32)
    sb = np.where(is_set, np.maximum(s_rel, 0), 0).astype(np.int32)
    unresolved = (
        (t_ns == -1) | (t_rel == -1) | (t_obj == -1) | (sa == -1)
        | (is_set & (s_rel == -1))
    )
    def _ns_of(name):
        return overlay.ns_ids.get(name, snapshot.ns_ids.get(name))

    def _rel_of(name):
        return overlay.rel_ids.get(name, snapshot.rel_ids.get(name))

    def _slot_of(ns_id, obj):
        key = (ns_id, obj)
        return overlay.obj_slots.get(key, snapshot.obj_slots.get(key))

    for i in np.flatnonzero(unresolved):
        i = int(i)
        _op, t = ops[i]
        ns = int(t_ns[i]) if t_ns[i] != -1 else _ns_of(t.namespace)
        if t_rel[i] == -1:
            t_rel[i] = _rel_of(t.relation)
        if t_obj[i] == -1:
            t_obj[i] = _slot_of(ns, t.object)
        if t.subject_set is not None:
            s = t.subject_set
            if s_rel[i] == -1:
                sb[i] = _rel_of(s.relation)
            if sa[i] == -1:
                s_ns_i = int(s_ns[i]) if s_ns[i] != -1 else _ns_of(s.namespace)
                sa[i] = _slot_of(s_ns_i, s.object)
        elif sa[i] == -1:
            sa[i] = overlay.subj_ids.get(
                t.subject_id or "", snapshot.subj_ids.get(t.subject_id or "")
            )
    enc = np.stack(
        [t_obj, t_rel, skind, sa, sb], axis=1
    ).astype(np.int32)
    return enc, is_insert, overlay


def _merged_vocab(mapping, new_items: dict, composite: bool = False):
    """Base vocab + appended entries: dicts copy-update, ArrayMaps merge
    sorted (existing ids preserved — see ArrayMap.merged_with)."""
    if not new_items:
        return mapping
    if isinstance(mapping, ArrayMap):
        return mapping.merged_with(new_items)
    out = dict(mapping)
    out.update(new_items)
    return out


def merge_ops_into_snapshot(
    snapshot: GraphSnapshot,
    ops: Sequence[tuple[str, RelationTuple]],
    version: int,
    with_encoded: bool = False,
):
    """The merge driver: a NEW GraphSnapshot with `ops` folded in, or
    None when a full rebuild is the better (or only correct) move.
    The input snapshot is never mutated — concurrent readers hold it.
    `with_encoded` additionally returns the deduped encoded ops
    (snapshot, enc_u [n,5] int32, ins_u bool) so the engine can patch
    the expand full-CSR with the same op set."""

    def _ret(snap, enc_u=None, ins_u=None):
        return (snap, enc_u, ins_u) if with_encoded else snap

    n_ops = len(ops)
    if n_ops == 0:
        return _ret(None)
    if n_ops > max(MIN_OPS_CAP, snapshot.n_tuples // MAX_OPS_FRACTION):
        return _ret(None)
    try:
        enc, is_insert, overlay = encode_ops(snapshot, ops)
    except (KeyError, TypeError):
        return _ret(None)  # inconsistent op stream — rebuild from the store

    # last-op-wins per exact edge key (same contract as the delta overlay)
    rev = np.arange(n_ops - 1, -1, -1)
    _, first = np.unique(enc[rev], axis=0, return_index=True)
    keep = rev[first]
    enc_u = enc[keep]
    ins_u = is_insert[keep]

    # -- direct-edge table: upsert with value-liveness -----------------------
    # In-place insert while occupancy stays sparse (the 1e7+ fast path —
    # no O(cap) rehash); a table that can't absorb the batch rehash-grows
    # from its own int arrays instead (still no store re-ingest / string
    # vocab work — the parts that make a full rebuild minutes).
    dh_cols = [
        np.array(snapshot.dh_obj), np.array(snapshot.dh_rel),
        np.array(snapshot.dh_skind), np.array(snapshot.dh_sa),
        np.array(snapshot.dh_sb),
    ]
    dh_val = np.array(snapshot.dh_val)
    dh_keys = tuple(enc_u[:, i].copy() for i in range(5))
    dh_vals = ins_u.astype(np.int32)
    occupied = int(np.count_nonzero(snapshot.dh_obj != EMPTY))
    if occupied + len(enc_u) > MAX_LOAD * len(dh_val):
        dh_cols, dh_val, dh_probes = _rehash_table(
            dh_cols, dh_val, dh_keys, dh_vals, drop_zero_vals=True
        )
    else:
        try:
            dh_probes = _hash_insert(
                dh_cols, dh_val, dh_keys, dh_vals, snapshot.dh_probes
            )
        except MergeFallback:
            dh_cols, dh_val, dh_probes = _rehash_table(
                dh_cols, dh_val, dh_keys, dh_vals, drop_zero_vals=True
            )

    # -- subject-set CSR: rewrite affected rows at the tail ------------------
    per_row: dict = {}
    set_rows = enc_u[enc_u[:, 2] == 1]
    set_ins = ins_u[enc_u[:, 2] == 1]
    for (obj, rel, _sk, sa, sb), ins in zip(set_rows.tolist(), set_ins.tolist()):
        ch = per_row.setdefault((obj, rel), {"ins": [], "del": set()})
        if ins:
            ch["ins"].append((sa, sb))
            ch["del"].discard((sa, sb))
        else:
            ch["del"].add((sa, sb))
            ch["ins"] = [t for t in ch["ins"] if t != (sa, sb)]
    if per_row:
        try:
            (rh_obj, rh_rel, rh_row), rh_probes, row_ptr, (e_obj, e_rel), garbage = (
                patch_csr(
                    (snapshot.rh_obj, snapshot.rh_rel, snapshot.rh_row),
                    snapshot.rh_probes,
                    snapshot.row_ptr,
                    (snapshot.e_obj, snapshot.e_rel),
                    per_row,
                )
            )
        except MergeFallback:
            return _ret(None)
    else:
        rh_obj, rh_rel, rh_row = snapshot.rh_obj, snapshot.rh_rel, snapshot.rh_row
        rh_probes = snapshot.rh_probes
        row_ptr, e_obj, e_rel = snapshot.row_ptr, snapshot.e_obj, snapshot.e_rel
        garbage = 0

    total_garbage = snapshot.merge_garbage + garbage
    if total_garbage > max(GARBAGE_FLOOR, GARBAGE_FRACTION * len(e_obj)):
        return _ret(None)

    # live-edge delta: inserts that were absent minus deletes that were live
    # (approximated from op counts; exactness only matters for the load
    # gate above, which measures occupancy directly)
    n_tuples = snapshot.n_tuples + int(ins_u.sum()) - int((~ins_u).sum())

    return _ret(GraphSnapshot(
        ns_ids=_merged_vocab(snapshot.ns_ids, overlay.ns_ids),
        rel_ids=_merged_vocab(snapshot.rel_ids, overlay.rel_ids),
        obj_slots=_merged_vocab(snapshot.obj_slots, overlay.obj_slots, True),
        subj_ids=_merged_vocab(snapshot.subj_ids, overlay.subj_ids),
        n_config_rels=snapshot.n_config_rels,
        wildcard_rel=snapshot.wildcard_rel,
        objslot_ns=overlay.objslot_ns,
        ns_has_config=overlay.ns_has_config,
        dh_obj=dh_cols[0], dh_rel=dh_cols[1], dh_skind=dh_cols[2],
        dh_sa=dh_cols[3], dh_sb=dh_cols[4], dh_val=dh_val,
        dh_probes=dh_probes,
        rh_obj=rh_obj, rh_rel=rh_rel, rh_row=rh_row, rh_probes=rh_probes,
        row_ptr=row_ptr, e_obj=e_obj, e_rel=e_rel,
        instr_kind=snapshot.instr_kind, instr_rel=snapshot.instr_rel,
        instr_rel2=snapshot.instr_rel2, prog_flags=snapshot.prog_flags,
        K=snapshot.K,
        island_circuits=snapshot.island_circuits,
        version=version,
        n_tuples=max(n_tuples, 0),
        merge_garbage=total_garbage,
    ), enc_u, ins_u)
