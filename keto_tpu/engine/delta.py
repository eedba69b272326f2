"""Delta overlay: incremental device-mirror refresh without full rebuilds.

The reference gets read-your-writes for free (every check re-queries SQL);
the device mirror previously paid a full snapshot rebuild on any write.
This module implements the SURVEY §7 hard-part — "delta overlay searched
alongside compacted CSR":

  - the store's bounded change log (MemoryManager/SQLitePersister
    .changes_since) feeds pending (op, tuple) pairs since the snapshot's
    base version
  - pending ops compile to two FIXED-CAPACITY device hash tables:
      * delta direct-edge table keyed (obj, rel, skind, sa, sb) with
        value 1 (insert) / 0 (delete tombstone), last-op-wins — the check
        kernel ORs delta-inserts into its probe and masks tombstoned main-
        table hits
      * dirty-row tables keyed (obj, rel): rows whose subject-set edge
        list changed (check/TTU expansion) and rows with ANY change
        (expand kernel); a task touching a dirty row flags its query for
        exact host replay
  - capacities are compile-time constants (DELTA_CAPACITY / DIRTY_CAPACITY
    at <=1/4 load) and the vocab-dependent objslot_ns / ns_has_config
    arrays carry headroom padding (snapshot.pad_headroom), so delta
    refreshes keep every array shape and probe static — no XLA
    recompilation on the write path until vocab growth crosses a padding
    quantum (then exactly one recompile at the new shape)
  - the base GraphSnapshot stays IMMUTABLE: vocabulary entries first seen
    in a delta live in a VocabOverlay (new entries only) combined with the
    base through SnapshotView — concurrent readers holding the previous
    view/tables stay internally consistent
  - past DELTA_COMPACT_THRESHOLD pending ops (or a truncated change log,
    or any namespace-config change) the engine compacts: full rebuild,
    empty overlay
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from ..ketoapi import RelationTuple
from .snapshot import EMPTY, GraphSnapshot, _build_hash_table

# Fixed table shapes sized for hash_table_capacity's load factor (0.25:
# cap = next pow2 >= 4n). Each op contributes one dd entry and at most
# one distinct dirty (obj, rel) row, so BOTH tables must hold
# 4 * DELTA_COMPACT_THRESHOLD = 8192 — at the old 4096 a batch touching
# >1024 distinct rows would spuriously force a full compaction.
DELTA_CAPACITY = 8192
DIRTY_CAPACITY = 8192
# reverse-dirty table (engine/reverse_kernel.py): each op contributes up
# to TWO distinct entries (its subject's seed key + its subject slot's
# reverse row), so 4 * 2 * DELTA_COMPACT_THRESHOLD keeps a full-threshold
# batch inside the fixed shape
RDIRTY_CAPACITY = 16384
DELTA_COMPACT_THRESHOLD = 2048
DELTA_PROBES = 8  # static probe unroll; a build needing deeper probing
# signals compaction instead of growing the fixed-shape table

DIRTY_FOR_EXPAND = 1
DIRTY_FOR_CHECK = 2


class DeltaOverflow(Exception):
    """Pending deltas exceed the fixed overlay capacity: compact."""


@dataclass
class VocabOverlay:
    """Vocabulary entries added by pending deltas (new names only) plus
    replacement copies of the small vocab-dependent device arrays."""

    ns_ids: dict[str, int]
    rel_ids: dict[str, int]
    obj_slots: dict[tuple[int, str], int]
    subj_ids: dict[str, int]
    objslot_ns: np.ndarray  # full array: base entries + overlay entries
    ns_has_config: np.ndarray


class SnapshotView:
    """Immutable (base snapshot, overlay) pair with the snapshot's query-
    encoding interface; the engine hands one consistent view + table dict
    to every batch."""

    def __init__(self, snapshot: GraphSnapshot, overlay: Optional[VocabOverlay] = None):
        self.snapshot = snapshot
        self.overlay = overlay

    def _lookup(self, base: dict, extra_name: str, key):
        v = base.get(key)
        if v is None and self.overlay is not None:
            v = getattr(self.overlay, extra_name).get(key)
        return v

    def ns_id(self, name: str) -> Optional[int]:
        return self._lookup(self.snapshot.ns_ids, "ns_ids", name)

    def rel_id(self, name: str) -> Optional[int]:
        return self._lookup(self.snapshot.rel_ids, "rel_ids", name)

    def obj_slot(self, ns_id: int, obj: str) -> Optional[int]:
        return self._lookup(self.snapshot.obj_slots, "obj_slots", (ns_id, obj))

    def subj_id(self, s: str) -> Optional[int]:
        return self._lookup(self.snapshot.subj_ids, "subj_ids", s)

    def encode_node(self, namespace: str, obj: str, relation: str):
        ns = self.ns_id(namespace)
        if ns is None:
            return None
        slot = self.obj_slot(ns, obj)
        rel = self.rel_id(relation)
        if slot is None or rel is None:
            return None
        return slot, rel

    def encode_subject(self, t: RelationTuple):
        s = t.subject_set
        if s is not None:
            return self.encode_subject_fields(1, s.namespace, s.object, s.relation)
        return self.encode_subject_fields(0, "", t.subject_id or "", "")

    def encode_subject_fields(self, skind, sns: str, sobj: str, srel: str):
        """encode_subject on a row of CheckColumns: a subject set's three
        names where `skind` is set, else the plain id in `sobj`."""
        if skind:
            ns = self.ns_id(sns)
            if ns is None:
                return None
            slot = self.obj_slot(ns, sobj)
            rel = self.rel_id(srel)
            if slot is None or rel is None:
                return None
            return 1, slot, rel
        sid = self.subj_id(sobj)
        if sid is None:
            return None
        return 0, sid, 0


def _fixed_capacity_table(keys, values, capacity: int):
    """_build_hash_table with a hard shape: raises DeltaOverflow when the
    build needs more capacity or deeper probing than the statics allow."""
    # boost_load=False: these shapes are STATIC (DELTA_CAPACITY /
    # DIRTY_CAPACITY compile into the kernel); the load boost would
    # grow a full-threshold batch past the fixed shape and force the
    # spurious compaction the capacity was sized to prevent
    built = _build_hash_table(
        keys, values, min_capacity=capacity, boost_load=False
    )
    *cols, probes = built
    if cols[0].shape[0] != capacity or probes > DELTA_PROBES:
        raise DeltaOverflow
    return cols


def empty_delta_tables() -> dict[str, np.ndarray]:
    return {
        "dd_obj": np.full(DELTA_CAPACITY, EMPTY, np.int32),
        "dd_rel": np.full(DELTA_CAPACITY, EMPTY, np.int32),
        "dd_skind": np.full(DELTA_CAPACITY, EMPTY, np.int32),
        "dd_sa": np.full(DELTA_CAPACITY, EMPTY, np.int32),
        "dd_sb": np.full(DELTA_CAPACITY, EMPTY, np.int32),
        "dd_val": np.full(DELTA_CAPACITY, EMPTY, np.int32),
        "dirty_obj": np.full(DIRTY_CAPACITY, EMPTY, np.int32),
        "dirty_rel": np.full(DIRTY_CAPACITY, EMPTY, np.int32),
        "dirty_val": np.full(DIRTY_CAPACITY, EMPTY, np.int32),
        # reverse-dirty: keyed (subject slot/id, reverse_subject_tag) for
        # seed staleness, (subject slot, 0) for reverse-row staleness
        "rd_obj": np.full(RDIRTY_CAPACITY, EMPTY, np.int32),
        "rd_tag": np.full(RDIRTY_CAPACITY, EMPTY, np.int32),
        "rd_val": np.full(RDIRTY_CAPACITY, EMPTY, np.int32),
    }


def build_vocab_overlay(
    snapshot: GraphSnapshot, ops: Sequence[tuple[str, RelationTuple]]
) -> VocabOverlay:
    """Collect names first seen in the delta (base dicts untouched).
    Relations get data-only ids (>= n_config_rels); config relations can
    only change via a config reload, which always compacts."""
    ns_new: dict[str, int] = {}
    rel_new: dict[str, int] = {}
    slot_new: dict[tuple[int, str], int] = {}
    subj_new: dict[str, int] = {}
    base = snapshot

    def ns_id(name: str) -> int:
        v = base.ns_ids.get(name)
        if v is None:
            v = ns_new.setdefault(name, len(base.ns_ids) + len(ns_new))
        return v

    def rel_id(name: str) -> None:
        if name not in base.rel_ids:
            rel_new.setdefault(name, len(base.rel_ids) + len(rel_new))

    def obj_slot(ns: int, obj: str) -> None:
        key = (ns, obj)
        if key not in base.obj_slots:
            slot_new.setdefault(key, len(base.obj_slots) + len(slot_new))

    for _op, t in ops:
        n = ns_id(t.namespace)
        obj_slot(n, t.object)
        rel_id(t.relation)
        if t.subject_set is not None:
            s = t.subject_set
            obj_slot(ns_id(s.namespace), s.object)
            rel_id(s.relation)
        elif (t.subject_id or "") not in base.subj_ids:
            subj_new.setdefault(
                t.subject_id or "", len(base.subj_ids) + len(subj_new)
            )

    from .snapshot import pad_headroom

    objslot_ns = snapshot.objslot_ns
    ns_has_config = snapshot.ns_has_config
    if slot_new:
        # keep the base (headroom-padded) shape while the new slots fit,
        # so the refreshed tables don't trigger an XLA recompile
        total = len(base.obj_slots) + len(slot_new)
        size = max(len(snapshot.objslot_ns), pad_headroom(total))
        objslot_ns = np.zeros(size, dtype=np.int32)
        objslot_ns[: len(snapshot.objslot_ns)] = snapshot.objslot_ns
        for (ns, _obj), slot in slot_new.items():
            objslot_ns[slot] = ns
    if ns_new:
        # namespaces first seen in tuples have no config by definition
        n_ns = len(base.ns_ids) + len(ns_new)
        size = max(len(snapshot.ns_has_config), pad_headroom(n_ns, 64))
        ns_has_config = np.zeros(size, dtype=np.int32)
        ns_has_config[: len(snapshot.ns_has_config)] = snapshot.ns_has_config
    return VocabOverlay(
        ns_ids=ns_new,
        rel_ids=rel_new,
        obj_slots=slot_new,
        subj_ids=subj_new,
        objslot_ns=objslot_ns,
        ns_has_config=ns_has_config,
    )


def build_delta_tables(
    view: SnapshotView, ops: Sequence[tuple[str, RelationTuple]]
) -> dict[str, np.ndarray]:
    """Compile pending ops to the fixed-shape overlay tables under an
    overlay-aware view. Raises DeltaOverflow when the overlay can't hold
    them (compact)."""
    if len(ops) > DELTA_COMPACT_THRESHOLD:
        raise DeltaOverflow

    # last-op-wins on the exact edge key
    from .snapshot import reverse_subject_tag

    last: dict[tuple[int, int, int, int, int], int] = {}
    dirty_ss: set[tuple[int, int]] = set()
    dirty_all: set[tuple[int, int]] = set()
    # reverse-mirror staleness (engine/reverse_kernel.py): a changed edge
    # invalidates its SUBJECT's seed row (any op) and, for subject-set
    # edges, the subject slot's reverse-edge row
    rdirty: set[tuple[int, int]] = set()
    for op, t in ops:
        obj, rel = view.encode_node(t.namespace, t.object, t.relation)
        skind, sa, sb = view.encode_subject(t)
        if skind == 1:
            dirty_ss.add((obj, rel))
            rdirty.add((sa, 0))
        dirty_all.add((obj, rel))
        rdirty.add((sa, int(reverse_subject_tag(skind, sb))))
        last[(obj, rel, skind, sa, sb)] = 1 if op == "insert" else 0

    tables = empty_delta_tables()
    if last:
        keys = np.array(list(last.keys()), dtype=np.int32).T
        vals = np.array(list(last.values()), dtype=np.int32)
        cols = _fixed_capacity_table(tuple(keys), vals, DELTA_CAPACITY)
        (
            tables["dd_obj"], tables["dd_rel"], tables["dd_skind"],
            tables["dd_sa"], tables["dd_sb"], tables["dd_val"],
        ) = cols
    if dirty_all:
        # one table, value = bitmask: 1 dirty-for-expand (any change),
        # 2 dirty-for-check (subject-set row change)
        marks = {k: DIRTY_FOR_EXPAND for k in dirty_all}
        for k in dirty_ss:
            marks[k] |= DIRTY_FOR_CHECK
        keys = np.array(list(marks.keys()), dtype=np.int32).T
        vals = np.array(list(marks.values()), dtype=np.int32)
        cols = _fixed_capacity_table(tuple(keys), vals, DIRTY_CAPACITY)
        tables["dirty_obj"], tables["dirty_rel"], tables["dirty_val"] = cols
    if rdirty:
        keys = np.array(sorted(rdirty), dtype=np.int32).T
        vals = np.ones(len(rdirty), dtype=np.int32)
        cols = _fixed_capacity_table(tuple(keys), vals, RDIRTY_CAPACITY)
        tables["rd_obj"], tables["rd_tag"], tables["rd_val"] = cols
    return tables
