"""Leopard closure index: per-nid transitive-closure sets on device.

Zanzibar's Leopard set index (PAPER.md §3.2) answers deep recursive
checks as a set intersection instead of a per-level BFS: precompute, for
every (object, relation) node, the transitive closure of subjects that
reach it through the monotone rewrite fragment, keep the sets fresh from
the changelog, and answer Check() with one membership probe. Here the
closure is computed as sparse boolean matrix powering (min-plus over the
required-depth semiring) on the HOST over the snapshot's existing
forward mirrors, and the materialized product R·D — reachability times
direct-edge incidence — is packed into the same bucketized hash-table
layout every other device table uses, so a closure hit costs ONE
gather+membership probe regardless of chain depth (engine/
closure_kernel.py).

Correctness contract (the version-gating proof, docs §5k):

  - a closure answer is returned ONLY when (a) the index was built from
    the SAME immutable base snapshot the serving state wraps
    (`snapshot_version` equality — vocabulary ids never alias across
    rebuilds), (b) the index's `synced_version` has reached the state's
    `covered_version` (every committed write since the base has been
    folded into the dirty overlay), and (c) the query's node is covered
    and not dirty. Anything else — lag, unbuilt index, uncovered node,
    dirty node, unknown vocabulary — falls back to the BFS kernel with a
    cause-coded counter. A lagging index degrades latency, never answers.
  - "covered" means the powering proved the node's ENTIRE reachable
    region is monotone (no AND/NOT islands, no host-only rewrites, no
    config-missing/relation-not-found error semantics) and its closure
    set fits `closure.max_set_rows`; covered nodes answer positives AND
    negatives definitively, with exact per-entry minimum required depth
    (`req`), so depth-limited checks gate on the same value the BFS
    kernel's depth bookkeeping would compute.
  - incremental freshness marks DIRTY nodes instead of re-powering: an
    op's change sites are its same-object consulting relations
    (per-namespace `consult` map), and every transitive ancestor over
    the TRANSPOSED dependency CSR is marked. Pending-edge inserts need
    no special casing: any path through a pending edge has an all-base
    prefix to that edge's source, which was marked when the edge's own
    op was applied (induction over ops in version order).
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

from .snapshot import (
    EMPTY,
    FLAG_CONFIG_MISSING,
    FLAG_HOST_ONLY,
    FLAG_ISLAND,
    GraphSnapshot,
    INSTR_COMPUTED,
    INSTR_TTU,
    _build_hash_table,
)

# fixed-shape dirty-node overlay table (the closure twin of the delta
# overlay's dirty_pack): capacity sized so churn bursts mark thousands of
# ancestors before forcing a re-power; probes share DELTA_PROBES
CDIRTY_CAPACITY = 16384
from .delta import DELTA_PROBES  # noqa: E402  (shared probe depth)

# past this many dirty nodes the maintainer re-powers instead of
# accumulating fallbacks (the overlay table is 1/4-loaded at this count)
DIRTY_COMPACT_THRESHOLD = CDIRTY_CAPACITY // 4

# hard ceiling on the node universe: a graph whose interesting-node set
# exceeds this serves without a closure index (counted, never an error)
MAX_CLOSURE_NODES = 1 << 20

DEFAULT_MAX_SET_ROWS = 4096
DEFAULT_LAG_BUDGET = 64

# host-side fallback causes (no launch happened); the kernel-side causes
# (uncovered / dirty / invalid) are defined in engine/closure_kernel.py.
# A DISABLED engine skips the gate entirely and counts nothing.
CAUSE_UNBUILT = "unbuilt"
CAUSE_STALE_SNAPSHOT = "stale_snapshot"
CAUSE_LAG = "lag"


def _expand_spans(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenate ranges [starts[i], starts[i]+counts[i]) — the CSR
    row-expansion primitive (vectorized; no per-row Python loop)."""
    if len(starts) == 0 or counts.sum() == 0:
        return np.zeros(0, dtype=np.int64)
    reps = np.repeat(starts.astype(np.int64), counts)
    total = int(counts.sum())
    offs = np.arange(total, dtype=np.int64)
    base = np.repeat(np.cumsum(counts) - counts, counts)
    return reps + (offs - base)


@dataclass
class ClosureGraph:
    """Extracted + 0-cost-folded structure of one base snapshot: the
    cost-1 edge CSR (computed rewrites folded away), the folded direct-
    subject incidence, per-node base poison, the TRANSPOSED dependency
    CSR for dirty marking, and the per-namespace consult map. Everything
    is keyed by int64 node keys obj * R + rel."""

    R: int  # rel-id stride of the composite node key
    n_obj: int
    # folded cost-1 edges, sorted+grouped by source key
    e_src_keys: np.ndarray  # [n_src] unique source keys, sorted
    e_ptr: np.ndarray  # [n_src + 1]
    e_dst: np.ndarray  # [n_edges] dst node keys
    # folded direct-subject incidence, sorted+grouped by node key
    d_node_keys: np.ndarray  # [n_dn] unique node keys, sorted
    d_ptr: np.ndarray  # [n_dn + 1]
    d_skind: np.ndarray
    d_sa: np.ndarray
    d_sb: np.ndarray
    # per-(ns, rel) base poison, folded through the 0-cost closure
    fpoison: np.ndarray  # [n_ns, n_rels] bool
    # transposed dependency CSR (edges + self-consult image) for the
    # maintainer's ancestor BFS
    t_dst_keys: np.ndarray  # unique dependency targets, sorted
    t_ptr: np.ndarray
    t_src: np.ndarray  # predecessor node keys
    # per-ns consult map: consult[ns][x] = sorted rel ids r with x in
    # consult_rels(r) — an op at row (o, x) makes sites {(o, r)}
    consult: list  # list[dict[int, np.ndarray]]
    # candidate closure sources (the "interesting" universe)
    universe: np.ndarray  # sorted unique node keys
    # slot -> ns under the vocabulary this graph was encoded with (the
    # overlay-extended array for refresh-era content)
    objslot_ns: np.ndarray = None


@dataclass
class ClosureBuild:
    """One powering product over a ClosureGraph (immutable)."""

    snapshot_version: int
    base_version: int
    covered_keys: np.ndarray  # sorted node keys proven covered
    # closure entries: (node obj, node rel, skind, sa, sb) -> min req depth
    ent_obj: np.ndarray
    ent_rel: np.ndarray
    ent_skind: np.ndarray
    ent_sa: np.ndarray
    ent_sb: np.ndarray
    ent_req: np.ndarray
    n_nodes: int = 0
    n_entries: int = 0
    build_s: float = 0.0
    # id-assignment fingerprint (snapshot_vocab_fp): the persisted-cache
    # validity key beyond snapshot_version — see _load_cached
    vocab_fp: int = 0
    # the parameters this product was powered AT: entries were trimmed
    # to req <= max_depth and coverage judged under max_set_rows, so a
    # cache is only valid for a config demanding the same pair (a
    # RAISED depth limit over a shallow build would serve wrong
    # definitive negatives)
    max_depth: int = 0
    max_set_rows: int = 0


def _rel_closure0(n_rels: int, comp_edges: list[tuple[int, int]]) -> list[set]:
    """0-cost (computed-rewrite) closure over one namespace's relation
    graph: closure0[r] = {r} ∪ every rel reachable through computed
    instructions at the same depth. Tiny (n_config_rels bounded)."""
    closure = [{r} for r in range(n_rels)]
    adj: dict[int, set[int]] = {}
    for a, b in comp_edges:
        adj.setdefault(a, set()).add(b)
    changed = True
    while changed:
        changed = False
        for r in range(n_rels):
            add = set()
            for m in closure[r]:
                add |= adj.get(m, set())
            if not add <= closure[r]:
                closure[r] |= add
                changed = True
    return closure


def snapshot_vocab_fp(snapshot: GraphSnapshot) -> int:
    """Fingerprint binding a snapshot's ID ASSIGNMENT, not just its
    (store version, config) pair: closure entries live in encoded-id
    space, and a rebuild could in principle re-derive ids in a different
    order under the same version — a persisted closure trusted on
    version alone would then alias ids into wrong answers. The direct
    edge tables hash every encoded id in play, so identical bytes imply
    an identical encoding."""
    import hashlib

    h = hashlib.sha256()
    for a in (
        snapshot.dh_obj, snapshot.dh_rel, snapshot.dh_skind,
        snapshot.dh_sa, snapshot.dh_sb, snapshot.objslot_ns,
    ):
        h.update(np.ascontiguousarray(a).tobytes())
    return int.from_bytes(h.digest()[:8], "big") >> 1


def extract_graph(
    snapshot: GraphSnapshot,
    content: Optional[tuple] = None,
    objslot_ns: Optional[np.ndarray] = None,
) -> Optional[ClosureGraph]:
    """Pull the powering operands out of a base snapshot's host mirrors.
    Returns None when the graph exceeds the closure's structural limits
    (node-key overflow / universe cap) — the engine then serves without
    an index, exactly as if closure were disabled.

    `content` overrides the snapshot-table extraction with explicit
    encoded edge arrays (t_obj, t_rel, t_skind, t_sa, t_sb) — the mesh
    path's source (a sharded base carries only vocabulary) and the
    incremental dirty refresh's. `objslot_ns` overrides the slot->ns
    array for content encoded under an OVERLAY view (overlay slots sit
    past the base array; mis-attributing their namespace would corrupt
    poison/fold decisions)."""
    slot_ns = (
        objslot_ns if objslot_ns is not None else snapshot.objslot_ns
    )
    # the node-key stride is the BASE relation count: every build and
    # refresh of one index must key identically (merged entries mix),
    # so overlay-era relation ids — which would alias past the stride —
    # are filtered out by _store_content before content reaches here
    R = max(len(snapshot.rel_ids), 1)
    n_obj = max(len(snapshot.obj_slots), 1)
    if max(n_obj, len(slot_ns)) * R >= (1 << 31):
        return None
    n_cfg = snapshot.n_config_rels
    n_ns = max(len(snapshot.ns_ids), 1)
    W = snapshot.wildcard_rel

    def key(obj, rel):
        return obj.astype(np.int64) * R + rel.astype(np.int64)

    # -- per-namespace rewrite structure (programs are object-independent)
    instr_kind = snapshot.instr_kind
    instr_rel = snapshot.instr_rel
    instr_rel2 = snapshot.instr_rel2
    closure0: list[list[set]] = []
    ttu_by_rel: list[list[list[tuple[int, int]]]] = []  # [ns][r] -> [(trel, crel)]
    for ns in range(n_ns):
        comp = []
        ttus: list[list[tuple[int, int]]] = [[] for _ in range(R)]
        for r in range(n_cfg):
            pid = ns * n_cfg + r
            if pid >= len(instr_kind):
                continue
            for k in range(snapshot.K):
                ik = int(instr_kind[pid][k])
                if ik == INSTR_COMPUTED:
                    comp.append((r, int(instr_rel[pid][k])))
                elif ik == INSTR_TTU:
                    ttus[r].append((int(instr_rel[pid][k]), int(instr_rel2[pid][k])))
        c0 = _rel_closure0(R, comp)
        closure0.append(c0)
        # fold TTU lists through the 0-closure: T(r) = union over r' in
        # closure0(r) of ttus[r']
        folded: list[list[tuple[int, int]]] = []
        for r in range(R):
            t: list[tuple[int, int]] = []
            for m in c0[r]:
                t.extend(ttus[m])
            folded.append(t)
        ttu_by_rel.append(folded)

    # -- per-(ns, rel) base poison, folded through closure0
    poison0 = np.zeros((n_ns, R), dtype=bool)
    has_cfg = snapshot.ns_has_config[:n_ns].astype(bool)
    for ns in range(n_ns):
        for r in range(R):
            if r < n_cfg:
                pid = ns * n_cfg + r
                flags = int(snapshot.prog_flags[pid]) if pid < len(
                    snapshot.prog_flags
                ) else 0
                if flags & (FLAG_HOST_ONLY | FLAG_CONFIG_MISSING | FLAG_ISLAND):
                    poison0[ns, r] = True
            elif has_cfg[ns]:
                # data relation inside a configured namespace: the
                # reference's relation-not-found error (engine.go:219-228)
                poison0[ns, r] = True
    fpoison = np.zeros((n_ns, R), dtype=bool)
    for ns in range(n_ns):
        for r in range(R):
            fpoison[ns, r] = any(poison0[ns, m] for m in closure0[ns][r])

    # -- raw content: direct edges + CSR rows
    if content is not None:
        t_obj, t_rel, t_skind, t_sa, t_sb = (
            np.asarray(a, dtype=np.int32) for a in content
        )
        d_obj, d_rel, d_skind, d_sa, d_sb = t_obj, t_rel, t_skind, t_sa, t_sb
        # group the subject-set rows into a local CSR (the builder's twin
        # of build_edge_tables' grouping, minus the hash table)
        is_set = t_skind == 1
        s_obj, s_rel = t_obj[is_set], t_rel[is_set]
        e_payload_obj, e_payload_rel = t_sa[is_set], t_sb[is_set]
        if len(s_obj):
            order = np.lexsort((np.arange(len(s_obj)), s_rel, s_obj))
            s_obj, s_rel = s_obj[order], s_rel[order]
            e_payload_obj = e_payload_obj[order]
            e_payload_rel = e_payload_rel[order]
            change = np.empty(len(s_obj), dtype=bool)
            change[0] = True
            change[1:] = (s_obj[1:] != s_obj[:-1]) | (s_rel[1:] != s_rel[:-1])
            starts = np.flatnonzero(change)
            r_obj = s_obj[starts]
            r_rel = s_rel[starts]
            r_start = starts.astype(np.int64)
            r_count = np.append(starts[1:], len(s_obj)) - starts
        else:
            r_obj = np.zeros(0, np.int32)
            r_rel = np.zeros(0, np.int32)
            r_start = np.zeros(0, np.int64)
            r_count = np.zeros(0, np.int64)
    else:
        dmask = snapshot.dh_val == 1
        d_obj = snapshot.dh_obj[dmask]
        d_rel = snapshot.dh_rel[dmask]
        d_skind = snapshot.dh_skind[dmask]
        d_sa = snapshot.dh_sa[dmask]
        d_sb = snapshot.dh_sb[dmask]

        rmask = snapshot.rh_row != EMPTY
        r_obj = snapshot.rh_obj[rmask]
        r_rel = snapshot.rh_rel[rmask]
        r_row = snapshot.rh_row[rmask]
        row_ptr = snapshot.row_ptr
        r_start = row_ptr[r_row]
        r_count = row_ptr[r_row + 1] - r_start
        e_payload_obj = snapshot.e_obj
        e_payload_rel = snapshot.e_rel
    r_ns = slot_ns[np.clip(r_obj, 0, len(slot_ns) - 1)]
    d_ns = slot_ns[np.clip(d_obj, 0, len(slot_ns) - 1)]

    # overlay-era namespaces (content encoded under a view whose overlay
    # added them): no config by definition — trivial 0-closure, no
    # rewrites, never poisoned. Extending the per-ns structures keeps
    # their rows in the fold instead of silently dropping them.
    n_ns_total = n_ns
    for arr in (r_ns, d_ns):
        if len(arr):
            n_ns_total = max(n_ns_total, int(arr.max()) + 1)
    if n_ns_total > n_ns:
        trivial_c0 = [{r} for r in range(R)]
        trivial_ttu: list[list[tuple[int, int]]] = [[] for _ in range(R)]
        for _ in range(n_ns, n_ns_total):
            closure0.append(trivial_c0)
            ttu_by_rel.append(trivial_ttu)
        fpoison = np.pad(fpoison, ((0, n_ns_total - n_ns), (0, 0)))
        n_ns = n_ns_total

    # -- fold content to parent relations: P0(ns, x) = {r : x in closure0(r)}
    p0: list[dict[int, np.ndarray]] = []
    consult: list[dict[int, np.ndarray]] = []
    for ns in range(n_ns):
        inv: dict[int, list[int]] = {}
        cons: dict[int, set[int]] = {}
        for r in range(R):
            for m in closure0[ns][r]:
                inv.setdefault(m, []).append(r)
                cons.setdefault(m, set()).add(r)
            for trel, _crel in ttu_by_rel[ns][r]:
                cons.setdefault(trel, set()).add(r)
        p0.append({x: np.array(sorted(v), dtype=np.int64) for x, v in inv.items()})
        consult.append(
            {x: np.array(sorted(v), dtype=np.int64) for x, v in cons.items()}
        )

    def fold_sources(objs, rels, nss, fold_map):
        """(obj, x) content rows -> one output row per (obj, parent rel)
        pair, returned as (row_index, parent_rel) arrays."""
        out_idx: list[np.ndarray] = []
        out_rel: list[np.ndarray] = []
        for ns in range(n_ns):
            m = nss == ns
            if not m.any():
                continue
            idx = np.flatnonzero(m)
            for x, parents in fold_map[ns].items():
                mm = idx[rels[idx] == x]
                if len(mm) == 0:
                    continue
                out_idx.append(np.repeat(mm, len(parents)))
                out_rel.append(np.tile(parents, len(mm)))
        if not out_idx:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        return np.concatenate(out_idx), np.concatenate(out_rel)

    # folded direct incidence: (o, r) owns direct subject s when some
    # x in closure0(r) has the raw direct edge (o, x, s)
    fd_idx, fd_rel = fold_sources(d_obj, d_rel, d_ns, p0)
    fd_key = d_obj[fd_idx].astype(np.int64) * R + fd_rel
    fd_skind = d_skind[fd_idx]
    fd_sa = d_sa[fd_idx]
    fd_sb = d_sb[fd_idx]

    # folded expand-subject edges: rows (o, x) expand from (o, r) for
    # r in P0(x); children (e_obj, e_rel), wildcard-relation sets skipped
    fe_idx, fe_rel = fold_sources(r_obj, r_rel, r_ns, p0)
    src_keys_rows = r_obj[fe_idx].astype(np.int64) * R + fe_rel
    epos = _expand_spans(r_start[fe_idx], r_count[fe_idx])
    esrc = np.repeat(src_keys_rows, r_count[fe_idx])
    edst_obj = e_payload_obj[epos] if len(epos) else np.zeros(0, np.int32)
    edst_rel = e_payload_rel[epos] if len(epos) else np.zeros(0, np.int32)
    keep = edst_rel != W
    e1_src = esrc[keep]
    e1_dst = key(edst_obj[keep], edst_rel[keep])

    # folded TTU edges: rows (o, trel) jump from (o, r) for every
    # (trel, crel) in T(r); children (e_obj, crel) — wildcard sets kept
    tt_src: list[np.ndarray] = []
    tt_dst: list[np.ndarray] = []
    for ns in range(n_ns):
        m = r_ns == ns
        if not m.any():
            continue
        idx = np.flatnonzero(m)
        pairs: dict[int, list[tuple[int, int]]] = {}
        for r in range(R):
            for trel, crel in ttu_by_rel[ns][r]:
                pairs.setdefault(trel, []).append((r, crel))
        for trel, rcs in pairs.items():
            rows = idx[r_rel[idx] == trel]
            if len(rows) == 0:
                continue
            pos = _expand_spans(r_start[rows], r_count[rows])
            robj = np.repeat(r_obj[rows].astype(np.int64), r_count[rows])
            cobj = e_payload_obj[pos].astype(np.int64)
            for r, crel in rcs:
                tt_src.append(robj * R + r)
                tt_dst.append(cobj * R + crel)
    if tt_src:
        e1_src = np.concatenate([e1_src] + tt_src)
        e1_dst = np.concatenate([e1_dst] + tt_dst)

    # -- group edges by source (forward CSR) and by dst (transposed CSR)
    def group(keys, vals):
        if len(keys) == 0:
            return (
                np.zeros(0, np.int64), np.zeros(1, np.int64),
                np.zeros(0, np.int64),
            )
        order = np.argsort(keys, kind="stable")
        k = keys[order]
        v = vals[order]
        uniq, starts = np.unique(k, return_index=True)
        ptr = np.append(starts, len(k)).astype(np.int64)
        return uniq, ptr, v

    e_src_keys, e_ptr, e_dst = group(e1_src, e1_dst)
    t_dst_keys, t_ptr, t_src = group(e1_dst, e1_src)

    dk_keys, d_ptr, d_order = group(fd_key, np.arange(len(fd_key), dtype=np.int64))
    fd_skind = fd_skind[d_order] if len(d_order) else fd_skind
    fd_sa = fd_sa[d_order] if len(d_order) else fd_sa
    fd_sb = fd_sb[d_order] if len(d_order) else fd_sb

    # -- universe: every node whose folded structure is non-trivial
    universe = np.unique(
        np.concatenate([e_src_keys, dk_keys])
    )
    if len(universe) > MAX_CLOSURE_NODES:
        return None
    return ClosureGraph(
        R=R, n_obj=n_obj,
        e_src_keys=e_src_keys, e_ptr=e_ptr, e_dst=e_dst,
        d_node_keys=dk_keys, d_ptr=d_ptr,
        d_skind=fd_skind, d_sa=fd_sa, d_sb=fd_sb,
        fpoison=fpoison,
        t_dst_keys=t_dst_keys, t_ptr=t_ptr, t_src=t_src,
        consult=consult,
        universe=universe,
        objslot_ns=slot_ns,
    )


def _lookup_spans(sorted_keys: np.ndarray, ptr: np.ndarray, queries: np.ndarray):
    """(starts, counts) of each query key's group in a grouped CSR
    (zero-count for absent keys)."""
    if len(sorted_keys) == 0 or len(queries) == 0:
        z = np.zeros(len(queries), dtype=np.int64)
        return z, z
    pos = np.searchsorted(sorted_keys, queries)
    pos_c = np.clip(pos, 0, len(sorted_keys) - 1)
    hit = sorted_keys[pos_c] == queries
    starts = np.where(hit, ptr[pos_c], 0)
    counts = np.where(hit, ptr[np.clip(pos_c + 1, 0, len(ptr) - 1)] - ptr[pos_c], 0)
    return starts, counts


def node_poison_keys(graph: ClosureGraph, keys: np.ndarray) -> np.ndarray:
    """Per-node folded base poison: key (o, r) is poisoned when the
    0-cost-folded (ns(o), r) cell is — relation-not-found and userset
    operators the closure cannot represent (AND/NOT islands). Shared by
    the host builder and the device powering kernel so both judge
    coverage from the identical mask."""
    obj = (keys // graph.R).astype(np.int64)
    rel = (keys % graph.R).astype(np.int64)
    ns = graph.fpoison.shape[0]
    slot_ns = graph.objslot_ns
    nss = slot_ns[np.clip(obj, 0, len(slot_ns) - 1)]
    nss = np.clip(nss, 0, ns - 1)
    return graph.fpoison[nss, np.clip(rel, 0, graph.fpoison.shape[1] - 1)]


def power_closure(
    graph: ClosureGraph,
    snapshot: GraphSnapshot,
    max_depth: int,
    max_set_rows: int,
    base_version: int,
    sources: Optional[np.ndarray] = None,
) -> ClosureBuild:
    """Multi-source level-synchronous powering: reach(src) grows one
    cost-1 edge per round (0-cost computed hops were folded into the
    edges at extraction), tracking first-discovery level = exact minimum
    distance. Sources whose reach or subject set outgrows
    `max_set_rows`, or that reach a poisoned node, drop out of coverage
    — their queries stay on the BFS kernel.

    `sources` overrides the powered node set (the incremental dirty
    refresh re-powers ONLY the perturbed nodes); a source with no
    content in `graph` legitimately covers with an EMPTY set — every
    membership is then a definitive NOT_MEMBER."""
    t0 = time.perf_counter()
    R = graph.R
    srcs = np.asarray(sources, dtype=np.int64) if sources is not None \
        else graph.universe
    n_src = len(srcs)
    build = ClosureBuild(
        snapshot_version=snapshot.version,
        base_version=base_version,
        covered_keys=np.zeros(0, np.int64),
        ent_obj=np.zeros(0, np.int32), ent_rel=np.zeros(0, np.int32),
        ent_skind=np.zeros(0, np.int32), ent_sa=np.zeros(0, np.int32),
        ent_sb=np.zeros(0, np.int32), ent_req=np.zeros(0, np.int32),
        n_nodes=n_src,
        vocab_fp=snapshot_vocab_fp(snapshot),
        max_depth=int(max_depth),
        max_set_rows=int(max_set_rows),
    )
    if n_src == 0:
        build.build_s = time.perf_counter() - t0
        return build

    uncovered = np.zeros(n_src, dtype=bool)

    # reach pairs as (src_index << 32) | dst_key with dst_key < 2^31
    def pair(src_idx, dst):
        return (src_idx.astype(np.int64) << 32) | dst.astype(np.int64)

    seen = pair(np.arange(n_src, dtype=np.int64), srcs)
    order = np.argsort(seen)
    seen = seen[order]
    seen_level = np.zeros(n_src, dtype=np.int32)[order]
    f_src = np.arange(n_src, dtype=np.int64)
    f_dst = srcs.copy()
    level = 0
    # BFS one level PAST the subject horizon (dist <= max_depth, while
    # entries need dist <= max_depth - 1): error/island semantics fire at
    # a node reached with remaining depth 0 — the reference raises
    # relation-not-found BEFORE its depth guard cuts recursion — so
    # poison must propagate from that extra ring; the req <= max_depth
    # filter below trims the subject entries it contributes.
    while len(f_src) and level < max_depth:
        starts, counts = _lookup_spans(graph.e_src_keys, graph.e_ptr, f_dst)
        pos = _expand_spans(starts, counts)
        n_src_rep = np.repeat(f_src, counts)
        n_dst = graph.e_dst[pos] if len(pos) else np.zeros(0, np.int64)
        if len(n_dst) == 0:
            break
        cand = pair(n_src_rep, n_dst)
        cand, first = np.unique(cand, return_index=True)
        n_src_rep = n_src_rep[first]
        n_dst = n_dst[first]
        # drop pairs already seen (seen stays sorted)
        ins = np.searchsorted(seen, cand)
        ins_c = np.clip(ins, 0, len(seen) - 1)
        fresh = ~((len(seen) > 0) & (seen[ins_c] == cand))
        cand, n_src_rep, n_dst = cand[fresh], n_src_rep[fresh], n_dst[fresh]
        if len(cand) == 0:
            break
        level += 1
        seen = np.concatenate([seen, cand])
        seen_level = np.concatenate(
            [seen_level, np.full(len(cand), level, dtype=np.int32)]
        )
        order = np.argsort(seen, kind="stable")
        seen = seen[order]
        seen_level = seen_level[order]
        # per-source reach cap: oversized sources leave coverage and stop
        # expanding (their remaining frontier entries are dropped)
        counts_per_src = np.bincount(
            (seen >> 32).astype(np.int64), minlength=n_src
        )
        over = counts_per_src > max_set_rows
        if over.any():
            uncovered |= over
            live = ~uncovered[n_src_rep]
            n_src_rep, n_dst = n_src_rep[live], n_dst[live]
        f_src, f_dst = n_src_rep, n_dst

    r_src = (seen >> 32).astype(np.int64)
    r_dst = (seen & 0xFFFFFFFF).astype(np.int64)

    # poison propagation: any reachable poisoned node uncovers the source
    if len(r_dst):
        bad = node_poison_keys(graph, r_dst)
        if bad.any():
            uncovered[np.unique(r_src[bad])] = True

    # subject product R·D: join reach pairs with the folded direct sets
    starts, counts = _lookup_spans(graph.d_node_keys, graph.d_ptr, r_dst)
    pos = _expand_spans(starts, counts)
    p_src = np.repeat(r_src, counts)
    p_req = np.repeat(seen_level + 1, counts)  # direct probe costs +1
    if len(pos):
        p_skind = graph.d_skind[pos]
        p_sa = graph.d_sa[pos]
        p_sb = graph.d_sb[pos]
        # dedupe (src, subject triple) keeping the MIN required depth:
        # lexsort with req as the fastest key, then first-of-group wins
        order = np.lexsort((p_req, p_sb, p_sa, p_skind, p_src))
        p_src, p_req = p_src[order], p_req[order]
        p_skind, p_sa, p_sb = p_skind[order], p_sa[order], p_sb[order]
        first = np.ones(len(p_src), dtype=bool)
        first[1:] = ~(
            (p_src[1:] == p_src[:-1])
            & (p_skind[1:] == p_skind[:-1])
            & (p_sa[1:] == p_sa[:-1])
            & (p_sb[1:] == p_sb[:-1])
        )
        p_src, p_req = p_src[first], p_req[first]
        p_skind, p_sa, p_sb = p_skind[first], p_sa[first], p_sb[first]
        # entries needing more depth than the global clamp can never be
        # demanded (effective depth <= max_depth)
        fits = p_req <= max_depth
        p_src, p_req = p_src[fits], p_req[fits]
        p_skind, p_sa, p_sb = p_skind[fits], p_sa[fits], p_sb[fits]
        per_src = np.bincount(p_src, minlength=n_src)
        uncovered |= per_src > max_set_rows
    else:
        p_src = np.zeros(0, np.int64)
        p_req = np.zeros(0, np.int32)
        p_skind = p_sa = p_sb = np.zeros(0, np.int32)

    covered_idx = np.flatnonzero(~uncovered)
    covered_keys = srcs[covered_idx]
    keep = ~uncovered[p_src] if len(p_src) else np.zeros(0, dtype=bool)
    p_src, p_req = p_src[keep], p_req[keep]
    p_skind, p_sa, p_sb = p_skind[keep], p_sa[keep], p_sb[keep]
    node_keys = srcs[p_src]
    build.covered_keys = np.sort(covered_keys)
    build.ent_obj = (node_keys // R).astype(np.int32)
    build.ent_rel = (node_keys % R).astype(np.int32)
    build.ent_skind = p_skind.astype(np.int32)
    build.ent_sa = p_sa.astype(np.int32)
    build.ent_sb = p_sb.astype(np.int32)
    build.ent_req = p_req.astype(np.int32)
    build.n_entries = len(p_req)
    build.build_s = time.perf_counter() - t0
    return build


def pack_closure_tables(build: ClosureBuild, R: int) -> tuple[dict, int, int]:
    """Device tables for the closure kernel: `cc_pack` (node covered
    flags, pair-keyed), `ch_pack` (closure membership entries keyed like
    the direct-edge table, value = min required depth). Returns
    (host tables dict, cc_probes, ch_probes); the dirty overlay table
    (`cd_pack`) is built separately — it changes per sync, these are
    immutable per build."""
    from .kernel import pack_edge_table, pack_pair_table

    cov_obj = (build.covered_keys // R).astype(np.int32)
    cov_rel = (build.covered_keys % R).astype(np.int32)
    if len(cov_obj):
        cc_obj, cc_rel, cc_val, cc_probes = _build_hash_table(
            (cov_obj, cov_rel), np.ones(len(cov_obj), dtype=np.int32)
        )
    else:
        cc_obj = np.full(64, EMPTY, np.int32)
        cc_rel = np.full(64, EMPTY, np.int32)
        cc_val = np.full(64, EMPTY, np.int32)
        cc_probes = 1
    if len(build.ent_obj):
        ch = _build_hash_table(
            (
                build.ent_obj, build.ent_rel, build.ent_skind,
                build.ent_sa, build.ent_sb,
            ),
            build.ent_req.astype(np.int32),
        )
        ch_obj, ch_rel, ch_skind, ch_sa, ch_sb, ch_val, ch_probes = ch
    else:
        ch_obj = np.full(64, EMPTY, np.int32)
        ch_rel = np.full(64, EMPTY, np.int32)
        ch_skind = np.full(64, EMPTY, np.int32)
        ch_sa = np.full(64, EMPTY, np.int32)
        ch_sb = np.full(64, EMPTY, np.int32)
        ch_val = np.full(64, EMPTY, np.int32)
        ch_probes = 1
    tables = {
        "cc_pack": pack_pair_table(cc_obj, cc_rel, cc_val),
        "ch_pack": pack_edge_table(
            ch_obj, ch_rel, ch_skind, ch_sa, ch_sb, ch_val
        ),
    }
    return tables, cc_probes, ch_probes


def empty_dirty_table() -> np.ndarray:
    from .kernel import pack_pair_table

    e = np.full(CDIRTY_CAPACITY, EMPTY, np.int32)
    return pack_pair_table(e, e, e)


def build_dirty_table(dirty_keys: np.ndarray, R: int) -> Optional[np.ndarray]:
    """Fixed-shape dirty-node pair table; None when the dirty set no
    longer fits the static capacity/probes (the index then reports
    itself wholly stale until the maintainer re-powers)."""
    from .delta import _fixed_capacity_table
    from .delta import DeltaOverflow
    from .kernel import pack_pair_table

    if len(dirty_keys) == 0:
        return empty_dirty_table()
    if len(dirty_keys) * 4 > CDIRTY_CAPACITY:
        return None
    obj = (dirty_keys // R).astype(np.int32)
    rel = (dirty_keys % R).astype(np.int32)
    try:
        cols = _fixed_capacity_table(
            (obj, rel), np.ones(len(obj), dtype=np.int32), CDIRTY_CAPACITY
        )
    except DeltaOverflow:
        return None
    return pack_pair_table(*cols)


class ClosureView:
    """One consistent, lock-free handle the submit path captures: device
    tables + static probe depths, valid for exactly one (snapshot,
    synced-version) generation."""

    __slots__ = (
        "tables", "cc_probes", "ch_probes", "has_dirty", "snapshot_version",
        "synced_version", "R",
    )

    def __init__(self, tables, cc_probes, ch_probes, has_dirty,
                 snapshot_version, synced_version, R):
        self.tables = tables
        self.cc_probes = cc_probes
        self.ch_probes = ch_probes
        self.has_dirty = has_dirty
        self.snapshot_version = snapshot_version
        self.synced_version = synced_version
        self.R = R


class ClosureIndex:
    """Per-engine Leopard index: one build (closure tables on device) +
    a dirty-node overlay kept fresh from the changelog by the
    maintenance plane (keto_tpu/closure). All public methods are
    thread-safe; store reads NEVER happen under the index lock."""

    def __init__(
        self,
        nid: str,
        max_set_rows: int = DEFAULT_MAX_SET_ROWS,
        lag_budget_versions: int = DEFAULT_LAG_BUDGET,
        metrics=None,
        cache_path: Optional[str] = None,
        powering: str = "host",
        flightrec=None,
    ):
        self.nid = nid
        self.max_set_rows = int(max_set_rows)
        self.lag_budget_versions = int(lag_budget_versions)
        self.metrics = metrics
        self.cache_path = cache_path
        # "host" (numpy builder, the differential oracle) or "device"
        # (GraphBLAS bit-packed powering, engine/closure_power.py); the
        # device path falls back to host on any failure — counted,
        # never wrong
        self.powering = str(powering)
        self.flightrec = flightrec
        # last device build's buffer estimate — the hbm_snapshot()
        # `closure_power` family (powering scratch is transient, so this
        # reports the high-water shape of the most recent build)
        self._power_hbm: dict = {}
        self._mu = threading.Lock()
        self._graph: Optional[ClosureGraph] = None
        self._build: Optional[ClosureBuild] = None
        self._view: Optional[ClosureView] = None
        self._dirty: set[int] = set()
        self._synced_version = -1
        self._stale = False  # dirty overflow / RESET: rebuild required
        self._snapshot: Optional[GraphSnapshot] = None
        # the encoder (base snapshot or, after a refresh, the overlay
        # view the refresh content was read under) that op nodes encode
        # through for dirty marking — it must cover every object the
        # CURRENT graph's edges can reach, or a write at a
        # refreshed-into-existence object would mark nothing while the
        # installed rows already include paths to it
        self._encoder = None
        # bumped by every apply_changes: the refresh install aborts when
        # marks landed after its re-mark read (they would be wiped by
        # the dirty subtraction while synced advanced past them)
        self._marks_gen = 0
        self.stats = {
            "builds": 0, "applied_ops": 0, "dirty_nodes": 0,
            "cache_loads": 0, "rebuild_pending": 0,
            "device_builds": 0, "device_fallbacks": 0,
            "power_waves": 0, "power_steps": 0,
        }

    def _power(
        self, graph: ClosureGraph, snap, max_depth: int,
        base_version: int, sources=None,
    ) -> ClosureBuild:
        """Route one powering through the configured builder. The device
        kernel honors the exact host contract (bit-identical builds);
        any device-path failure — unsupported shape, compile error,
        device loss — falls back to the host builder for THIS powering
        and is counted, so `closure.powering = "device"` can never cost
        correctness, only the speedup."""
        if self.powering == "device":
            from .closure_power import (
                PoweringUnsupported,
                power_closure_device,
            )

            try:
                build, record = power_closure_device(
                    graph, snap, max_depth, self.max_set_rows,
                    base_version, sources=sources,
                    flightrec=self.flightrec, nid=self.nid,
                )
            except PoweringUnsupported as exc:
                logger.warning(
                    "device powering unsupported (%s); host fallback", exc
                )
            except Exception:
                logger.exception("device powering failed; host fallback")
            else:
                self.stats["device_builds"] += 1
                self.stats["power_waves"] += record["waves"]
                self.stats["power_steps"] += record["steps"]
                self._power_hbm = dict(record["hbm"])
                if self.metrics is not None:
                    self.metrics.closure_power_builds_total.inc()
                    self.metrics.closure_power_steps_total.inc(
                        record["steps"]
                    )
                    self.metrics.closure_power_bytes.set(
                        sum(record["hbm"].values())
                    )
                return build
            self.stats["device_fallbacks"] += 1
        return power_closure(
            graph, snap, max_depth, self.max_set_rows, base_version,
            sources=sources,
        )

    # -- build / rebuild -------------------------------------------------------

    def ensure_for(self, state, manager, max_depth: int) -> bool:
        """Build (or reuse) the index for `state`'s base snapshot, then
        fold in every committed op between the snapshot's base version
        and the state's covered version. Returns readiness. Called by
        the maintenance plane and by tests/bench — NEVER on the check
        submit path (a powering there would stall a batch)."""
        snap = state.snapshot
        with self._mu:
            # identity, not version: a rebuild under the same (store
            # version, config) pair could in principle re-derive
            # vocabulary ids in a different order, and closure entries
            # live in id space — the persisted-cache path re-validates
            # with snapshot_vocab_fp instead
            same_snapshot = (
                self._build is not None and self._snapshot is snap
            )
            current = same_snapshot and not self._stale
            # thrash guard: a STALE index over an UNCHANGED base snapshot
            # cannot be fixed by re-powering — the powering reads the
            # same base, then catch_up re-marks the same oversized dirty
            # set (or re-hits the same truncated changelog) and staleness
            # returns. The engine's own compaction (delta overflow /
            # truncated log) is what produces a fresher base; until it
            # does, the index stays stale and checks ride the BFS kernel.
            stuck = same_snapshot and self._stale
        if current:
            # advance the op encoder to the engine's CURRENT overlay
            # view (a superset of whatever the graph was installed
            # with): ops at objects first seen after the base — which
            # the base snapshot cannot encode — then mark their own
            # sites, and the dirty refresh powers them into coverage.
            # Without this, a server started over an empty/small store
            # would stay closure-less until the next compaction.
            view = getattr(state, "view", None)
            if view is not None:
                with self._mu:
                    if self._snapshot is snap:
                        self._encoder = view
        if not current and not stuck:
            content = None
            if getattr(state, "sharded", None) is not None:
                # mesh path: the sharded base snapshot carries only
                # vocabulary (its edge tables live per-shard), so the
                # builder reads the store and encodes under the base
                # vocabulary. The store may be AHEAD of the state; the
                # catch_up below ancestor-marks EVERY op since the base
                # version, so content the serving state has not seen yet
                # (including skipped-unencodable rows) can only route to
                # a fallback, never into an answer.
                content, _skipped = self._store_content(manager, snap)
            self._rebuild(snap, state.base_version, max_depth, content)
        return self.catch_up(manager, state.covered_version)

    def _store_content(self, manager, encoder):
        """Encoded (obj, rel, skind, sa, sb) arrays from the live store
        under `encoder`'s vocabulary (a SnapshotView for overlay-aware
        encoding, or the bare base snapshot). Returns (content,
        skipped_sites): rows mentioning names the encoder cannot resolve
        are dropped from content, and every droppable row whose NODE
        side does encode is reported — the caller must keep those
        regions dirty (a refresh from content missing their rows would
        silently flip a covered node's answer)."""
        cols = [[], [], [], [], []]
        skipped: set[tuple[int, int]] = set()
        # node keys are strided by the BASE relation count: overlay-era
        # relation ids would alias past it, so rows carrying them route
        # to the skip/keep-dirty path instead of into content. The
        # encoder is either the base GraphSnapshot or a SnapshotView
        # wrapping it.
        base = getattr(encoder, "snapshot", encoder)
        R = max(len(base.rel_ids), 1)
        for t in manager.all_relation_tuples(nid=self.nid):
            node = encoder.encode_node(t.namespace, t.object, t.relation)
            subj = encoder.encode_subject(t)
            if node is not None and node[1] >= R:
                # unkeyable row node: any predecessor reaches it through
                # an edge row reported (or included) under ITS key
                continue
            if (
                node is None
                or subj is None
                or (subj[0] == 1 and subj[2] >= R)
            ):
                if node is not None:
                    skipped.add((int(node[0]), int(node[1])))
                # node-side-unencodable rows are only reachable through
                # a pending edge whose own (node-encodable) row is
                # either present or itself reported here
                continue
            cols[0].append(node[0])
            cols[1].append(node[1])
            cols[2].append(subj[0])
            cols[3].append(subj[1])
            cols[4].append(subj[2])
        return (
            tuple(np.array(c, dtype=np.int32) for c in cols),
            skipped,
        )

    # -- region-scoped refresh reads (the ROADMAP item 3 scale fix) -----------

    def _decode_slots(self, encoder, slots) -> Optional[dict]:
        """slot -> (ns_name, obj_name) for exactly the requested slots,
        or None when any fails to decode (full-read fallback). Dict
        vocabs pay one pass over obj_slots.items() — no store reads and
        no per-tuple encode, cheap against the O(store) read this
        replaces; ArrayMap vocabs decode each slot in O(1)."""
        base = getattr(encoder, "snapshot", encoder)
        overlay = getattr(encoder, "overlay", None)
        ns_names = {v: k for k, v in base.ns_ids.items()}
        if overlay is not None:
            ns_names.update({v: k for k, v in overlay.ns_ids.items()})
        want = set(int(s) for s in slots)
        out: dict[int, tuple[str, str]] = {}

        def _take(ns_id, obj_name, slot):
            ns = ns_names.get(int(ns_id))
            if ns is not None:
                out[int(slot)] = (ns, obj_name)

        base_slots = base.obj_slots
        if hasattr(base_slots, "key_by_id"):  # ArrayMap
            n_base = len(base_slots)
            for slot in want:
                if 0 <= slot < n_base:
                    ns_id, obj_name = base_slots.key_by_id(slot)
                    _take(ns_id, obj_name, slot)
        else:
            for (ns_id, obj_name), slot in base_slots.items():
                if slot in want:
                    _take(ns_id, obj_name, slot)
        if overlay is not None:
            for (ns_id, obj_name), slot in overlay.obj_slots.items():
                if slot in want:
                    _take(ns_id, obj_name, slot)
        if len(out) != len(want):
            return None
        return out

    def _region_content(self, manager, encoder, dirty_objs: dict,
                        budget_objs: int):
        """Indexed region walk: fetch ONLY the dirty nodes' consulting
        regions via per-object `get_relation_tuples` queries, following
        subject-set children — every node the powering can reach from a
        refresh source lives at an object the walk visits (folded cost-1
        edges always target a row's subject-set object at the same
        source object). Returns (content, skipped_sites, rows_read), or
        None when the walk outgrows `budget_objs` distinct objects (the
        full-read fallback stays exact, just slower).

        The same encode/skip discipline as _store_content: rows whose
        node side encodes but whose subject cannot are reported as
        skipped sites (their regions stay dirty), node-unkeyable rows
        drop silently (reachable only through an edge whose own op
        marks)."""
        from ..ketoapi import RelationQuery

        base = getattr(encoder, "snapshot", encoder)
        R = max(len(base.rel_ids), 1)
        cols = [[], [], [], [], []]
        skipped: set[tuple[int, int]] = set()
        rows = 0
        visited: set[tuple[str, str]] = set(dirty_objs.values())
        frontier = set(visited)
        while frontier:
            nxt: set[tuple[str, str]] = set()
            for ns_name, obj_name in frontier:
                page = ""
                while True:
                    tuples, page = manager.get_relation_tuples(
                        RelationQuery(namespace=ns_name, object=obj_name),
                        page_token=page, page_size=2048, nid=self.nid,
                    )
                    for t in tuples:
                        rows += 1
                        if t.subject_set is not None:
                            nxt.add(
                                (t.subject_set.namespace, t.subject_set.object)
                            )
                        node = encoder.encode_node(
                            t.namespace, t.object, t.relation
                        )
                        subj = encoder.encode_subject(t)
                        if node is not None and node[1] >= R:
                            continue
                        if (
                            node is None
                            or subj is None
                            or (subj[0] == 1 and subj[2] >= R)
                        ):
                            if node is not None:
                                skipped.add((int(node[0]), int(node[1])))
                            continue
                        cols[0].append(node[0])
                        cols[1].append(node[1])
                        cols[2].append(subj[0])
                        cols[3].append(subj[1])
                        cols[4].append(subj[2])
                    if not page:
                        break
            frontier = nxt - visited
            visited |= frontier
            if len(visited) > budget_objs:
                return None
        content = tuple(np.array(c, dtype=np.int32) for c in cols)
        return content, skipped, rows

    def _refresh_content(self, manager, encoder, dirty_keys):
        """(content, skipped_sites, scoped) for one dirty refresh:
        region-scoped store reads when the dirty set decodes and its
        regions fit the walk budget — cost proportional to the dirty
        set, not the store — else the full _store_content read. The
        refresh's correctness protocol is identical either way; `scoped`
        tells the caller to MERGE (not replace) the dependency graph,
        since a region graph only covers the walked neighborhood."""
        # dirty keys are obj * R + rel: regions are per OBJECT
        R = self._graph_R(encoder)
        slots = sorted({int(k) // R for k in dirty_keys})
        budget = max(4096, 4 * self.max_set_rows)
        if getattr(manager, "get_relation_tuples", None) is not None:
            decoded = self._decode_slots(encoder, slots)
            if decoded is not None:
                region = self._region_content(
                    manager, encoder, decoded, budget
                )
                if region is not None:
                    content, skipped, rows = region
                    self.stats["refresh_rows_read"] = (
                        self.stats.get("refresh_rows_read", 0) + rows
                    )
                    self.stats["scoped_refreshes"] = (
                        self.stats.get("scoped_refreshes", 0) + 1
                    )
                    return content, skipped, True
        content, skipped = self._store_content(manager, encoder)
        self.stats["refresh_rows_read"] = (
            self.stats.get("refresh_rows_read", 0) + len(content[0])
        )
        self.stats["full_refresh_reads"] = (
            self.stats.get("full_refresh_reads", 0) + 1
        )
        return content, skipped, False

    @staticmethod
    def _graph_R(encoder) -> int:
        base = getattr(encoder, "snapshot", encoder)
        return max(len(base.rel_ids), 1)

    @staticmethod
    def _merge_dependency(old: ClosureGraph, region: ClosureGraph) -> ClosureGraph:
        """Dependency graph for future dirty marking after a
        region-scoped refresh: the UNION of the old transposed CSR and
        the region's. The refreshed rows may reach objects the base-era
        structures cannot even express, so their dependency edges must
        join; edges the region re-read no longer contains stay — for
        MARKING, over-marking is conservative (costs a re-power),
        under-marking would silently serve stale covered answers.
        Everything else (consult maps, poison, R) is per-namespace
        program structure — identical in both graphs up to overlay-era
        trivial extensions, so the longer wins."""
        import dataclasses

        def pairs(g: ClosureGraph) -> np.ndarray:
            if len(g.t_src) == 0:
                return np.zeros((0, 2), dtype=np.int64)
            counts = np.diff(g.t_ptr)
            dst = np.repeat(g.t_dst_keys, counts)
            return np.stack([dst, g.t_src], axis=1)

        allp = np.concatenate([pairs(old), pairs(region)], axis=0)
        if len(allp):
            allp = np.unique(allp, axis=0)
            dst = allp[:, 0]
            src = allp[:, 1]
            uniq, starts = np.unique(dst, return_index=True)
            ptr = np.append(starts, len(dst)).astype(np.int64)
        else:
            uniq = np.zeros(0, np.int64)
            ptr = np.zeros(1, np.int64)
            src = np.zeros(0, np.int64)
        objslot_ns = (
            old.objslot_ns
            if len(old.objslot_ns) >= len(region.objslot_ns)
            else region.objslot_ns
        )
        consult = (
            region.consult
            if len(region.consult) >= len(old.consult)
            else old.consult
        )
        fpoison = (
            region.fpoison
            if region.fpoison.shape[0] >= old.fpoison.shape[0]
            else old.fpoison
        )
        return dataclasses.replace(
            old, t_dst_keys=uniq, t_ptr=ptr, t_src=src,
            objslot_ns=objslot_ns, consult=consult, fpoison=fpoison,
        )

    def _rebuild(self, snap: GraphSnapshot, base_version: int,
                 max_depth: int, content=None) -> None:
        graph = extract_graph(snap, content)
        build = None
        powered = False
        if graph is not None:
            build = self._load_cached(snap, base_version, max_depth)
            if build is None:
                build = self._power(graph, snap, max_depth, base_version)
                self._persist(build)
                powered = True
                # counted only for REAL powerings: the metric (and the
                # maintainer's rebuild stat derived from it) exists to
                # spot thrash, and a warm-restart cache load is not one
                self.stats["builds"] += 1
        tables = None
        cc_probes = ch_probes = 1
        if build is not None:
            tables, cc_probes, ch_probes = pack_closure_tables(build, graph.R)
        with self._mu:
            self._graph = graph
            self._build = build
            self._snapshot = snap
            self._encoder = snap
            self._dirty = set()
            self._stale = graph is None or build is None
            self._synced_version = (
                build.base_version if build is not None else -1
            )
            self._view = None
            if build is not None and tables is not None:
                from .kernel import device_table, device_tables

                dev = device_tables(tables)
                dev["cd_pack"] = device_table(empty_dirty_table())
                self._view = ClosureView(
                    dev, cc_probes, ch_probes, False,
                    build.snapshot_version, self._synced_version, graph.R,
                )
        if self.metrics is not None and build is not None:
            if powered:
                self.metrics.closure_builds_total.inc()
            self.metrics.closure_entries.set(build.n_entries)

    # -- freshness -------------------------------------------------------------

    def catch_up(self, manager, through_version: int) -> bool:
        """Fold committed ops (synced, through_version] into the dirty
        overlay by reading the store changelog. Store read happens
        OUTSIDE the index lock. Returns readiness at through_version."""
        with self._mu:
            if self._build is None or self._stale:
                return False
            synced = self._synced_version
        if synced >= through_version:
            return True
        changes_since = getattr(manager, "changes_since", None)
        if changes_since is None:
            return False
        ops = changes_since(synced, nid=self.nid)
        if ops is None:
            # truncated changelog: the gap is unrecoverable incrementally
            self.mark_stale()
            return False
        return self.apply_changes(ops, through_version)

    def apply_changes(self, changes, through_version: int) -> bool:
        """Mark the transitive ancestors of every change's consult sites
        dirty, then advance synced_version. `changes` is a sequence of
        (op, RelationTuple); versions <= synced are assumed already
        applied (idempotent — re-marking dirty nodes is harmless)."""
        with self._mu:
            build = self._build
            graph = self._graph
            encoder = self._encoder or self._snapshot
            if build is None or graph is None or self._stale:
                return False
            if through_version <= self._synced_version:
                # already folded: everything at or below synced is
                # either refreshed into the rows or still marked — a
                # replayed watch event must not re-dirty nodes a refresh
                # just cleared
                return True
        sites: list[int] = []
        for _op, t in changes:
            # encode through the graph's OWN encoder (the base snapshot,
            # or the overlay view the last refresh installed): a write
            # at an object the refreshed rows already reach must mark —
            # under the base snapshot alone it would silently skip
            node = encoder.encode_node(t.namespace, t.object, t.relation)
            if node is None or node[1] >= graph.R:
                # names outside the encoder (or unkeyable overlay rels):
                # any influence on a covered node flows through an edge
                # whose own (node-encodable) op marks — and whose region
                # a refresh keeps dirty via its skipped-site report
                continue
            obj, rel = node
            slot_ns = graph.objslot_ns
            ns = int(slot_ns[obj]) if obj < len(slot_ns) else 0
            cons = graph.consult[ns].get(rel) if ns < len(graph.consult) else None
            rels = set(cons.tolist()) if cons is not None else set()
            rels.add(rel)  # the changed node is always its own site
            for r in rels:
                sites.append(int(obj) * graph.R + int(r))
        new_dirty = self._ancestors(graph, sites)
        with self._mu:
            if self._build is not build or self._stale:
                return False
            self._marks_gen += 1
            self._dirty |= new_dirty
            self.stats["applied_ops"] += len(changes)
            self.stats["dirty_nodes"] = len(self._dirty)
            if len(self._dirty) > DIRTY_COMPACT_THRESHOLD:
                self._stale = True
                self.stats["rebuild_pending"] += 1
                return False
            cd = build_dirty_table(
                np.fromiter(self._dirty, dtype=np.int64, count=len(self._dirty)),
                graph.R,
            )
            if cd is None:
                self._stale = True
                self.stats["rebuild_pending"] += 1
                return False
            from .kernel import device_table

            old = self._view
            tables = dict(old.tables) if old is not None else None
            if tables is None:
                return False
            tables["cd_pack"] = device_table(cd)
            self._synced_version = max(self._synced_version, through_version)
            self._view = ClosureView(
                tables, old.cc_probes, old.ch_probes, bool(self._dirty),
                old.snapshot_version, self._synced_version, old.R,
            )
            return True

    def refresh_dirty(self, manager, max_depth: int, view=None) -> bool:
        """INCREMENTAL maintenance, the not-rebuild-from-scratch half:
        re-power ONLY the dirty nodes from current store content and
        merge the fresh rows back — closure hits resume without paying
        an O(universe) powering or waiting for the engine's compaction.

        Race protocol (writes land while we work): catch up through v1
        first so the dirty set covers every committed op; read content
        (which may include ops PAST v1); re-read the version (v2) and
        ancestor-mark (v1, v2] — any node those late ops could affect is
        then freshly dirty, and only nodes NOT re-marked are refreshed.
        A node outside the re-marked set provably has identical closure
        at v1, at v2, and at content-read time, so installing its fresh
        rows and advancing synced to v2 can never answer ahead of the
        serving state. Called by the maintenance plane; store reads all
        happen OUTSIDE the index lock.

        `view` is the engine's current SnapshotView: content encodes
        through its OVERLAY so subjects/objects first seen after the
        base snapshot refresh correctly (overlay ids are exactly what
        queries encode to). Rows that still fail to encode keep their
        whole consulting region dirty via `skipped_sites` — a refresh
        can narrow the dirty set, never paper over missing rows."""
        with self._mu:
            build = self._build
            graph = self._graph
            snap = self._snapshot
            if (
                build is None or graph is None or self._stale
                or not self._dirty
            ):
                return False
        v1 = manager.version(nid=self.nid)
        if not self.catch_up(manager, v1):
            return False
        with self._mu:
            if self._build is not build or self._stale:
                return False
            dirty_before = set(self._dirty)
        encoder = view if view is not None else snap
        # region-scoped read (the ROADMAP item 3 scale fix): fetch only
        # the dirty nodes' consulting regions via indexed per-object
        # queries — refresh cost proportional to the dirty set, not the
        # store; oversized/undecodable regions fall back to a full read
        content, skipped_sites, scoped = self._refresh_content(
            manager, encoder, dirty_before
        )
        v2 = manager.version(nid=self.nid)
        if v2 != v1:
            changes_since = getattr(manager, "changes_since", None)
            ops2 = (
                changes_since(v1, nid=self.nid)
                if changes_since is not None else None
            )
            if ops2 is None:
                self.mark_stale()
                return False
            self.apply_changes(ops2, v2)
        with self._mu:
            if self._build is not build or self._stale:
                return False
            remarked = self._dirty - dirty_before
            marks_gen = self._marks_gen
        # regions whose rows could not be encoded stay dirty: expand the
        # skipped sites through the consult map + transposed ancestors
        # exactly like a live op's change sites
        if skipped_sites:
            sites: list[int] = []
            # namespace attribution through the GRAPH's overlay-extended
            # slot array (exactly like apply_changes): a skipped row at
            # a post-base object would otherwise fall back to ns 0 and
            # consult the wrong map, under-marking its region
            slot_ns_arr = graph.objslot_ns
            for obj, rel in skipped_sites:
                ns = (
                    int(slot_ns_arr[obj])
                    if obj < len(slot_ns_arr) else 0
                )
                cons = (
                    graph.consult[ns].get(rel)
                    if ns < len(graph.consult) else None
                )
                rels = set(cons.tolist()) if cons is not None else set()
                rels.add(rel)
                for r in rels:
                    sites.append(int(obj) * graph.R + int(r))
            remarked |= self._ancestors(graph, sites)
        refresh = dirty_before - remarked
        if not refresh:
            return False
        slot_ns = (
            view.overlay.objslot_ns
            if view is not None and view.overlay is not None
            else None
        )
        g2 = extract_graph(snap, content, objslot_ns=slot_ns)
        if g2 is None:
            self.mark_stale()
            return False
        keys = np.array(sorted(refresh), dtype=np.int64)
        fresh = self._power(
            g2, snap, max_depth, build.base_version, sources=keys
        )
        merged = self._merge_refresh(build, graph, keys, fresh)
        tables, cc_probes, ch_probes = pack_closure_tables(merged, graph.R)
        from .kernel import device_table, device_tables

        dev = device_tables(tables)
        with self._mu:
            if self._build is not build or self._stale:
                return False
            if self._marks_gen != marks_gen:
                # a concurrent catch-up marked nodes after our re-mark
                # read: installing would wipe those marks from the dirty
                # set while keeping the advanced synced version — abort;
                # the next maintenance pass retries over the fresh marks
                return False
            self._build = merged
            # the refresh content informs THE dependency graph and its
            # view becomes THE op encoder: future writes at objects the
            # refreshed rows now reach must mark their ancestors (the
            # base-era structures cannot even encode those objects). A
            # FULL-read graph replaces outright; a region-scoped graph
            # only covers the walked neighborhood, so its dependency
            # edges UNION into the old CSR (over-marking is safe,
            # dropping unwalked edges would under-mark)
            self._graph = (
                self._merge_dependency(graph, g2) if scoped else g2
            )
            self._encoder = encoder
            self._dirty -= refresh
            self._synced_version = max(self._synced_version, v2)
            cd = build_dirty_table(
                np.fromiter(
                    self._dirty, dtype=np.int64, count=len(self._dirty)
                ),
                graph.R,
            )
            if cd is None:
                self._stale = True
                return False
            dev["cd_pack"] = device_table(cd)
            self._view = ClosureView(
                dev, cc_probes, ch_probes, bool(self._dirty),
                merged.snapshot_version, self._synced_version, graph.R,
            )
            self.stats["dirty_nodes"] = len(self._dirty)
            self.stats["refreshes"] = self.stats.get("refreshes", 0) + 1
        if self.metrics is not None:
            self.metrics.closure_entries.set(merged.n_entries)
        return True

    @staticmethod
    def _merge_refresh(
        build: ClosureBuild, graph: ClosureGraph, keys: np.ndarray,
        fresh: ClosureBuild,
    ) -> ClosureBuild:
        """`build` with every row owned by `keys` replaced by `fresh`'s
        (coverage and entries both; a refreshed node may gain or lose
        coverage — row caps and poison were re-evaluated from current
        content)."""
        old_node_keys = (
            build.ent_obj.astype(np.int64) * graph.R + build.ent_rel
        )
        keep = ~np.isin(old_node_keys, keys)
        covered = np.union1d(
            np.setdiff1d(build.covered_keys, keys, assume_unique=False),
            fresh.covered_keys,
        )
        return ClosureBuild(
            snapshot_version=build.snapshot_version,
            base_version=build.base_version,
            covered_keys=covered,
            ent_obj=np.concatenate([build.ent_obj[keep], fresh.ent_obj]),
            ent_rel=np.concatenate([build.ent_rel[keep], fresh.ent_rel]),
            ent_skind=np.concatenate(
                [build.ent_skind[keep], fresh.ent_skind]
            ),
            ent_sa=np.concatenate([build.ent_sa[keep], fresh.ent_sa]),
            ent_sb=np.concatenate([build.ent_sb[keep], fresh.ent_sb]),
            ent_req=np.concatenate([build.ent_req[keep], fresh.ent_req]),
            n_nodes=build.n_nodes,
            n_entries=int(keep.sum()) + fresh.n_entries,
            vocab_fp=build.vocab_fp,
            max_depth=build.max_depth,
            max_set_rows=build.max_set_rows,
        )

    @staticmethod
    def _ancestors(graph: ClosureGraph, sites: list[int]) -> set[int]:
        """Reverse BFS over the transposed dependency CSR from every
        change site (sites are their own ancestors)."""
        out: set[int] = set(sites)
        frontier = np.array(sorted(out), dtype=np.int64)
        while len(frontier):
            starts, counts = _lookup_spans(
                graph.t_dst_keys, graph.t_ptr, frontier
            )
            pos = _expand_spans(starts, counts)
            preds = graph.t_src[pos] if len(pos) else np.zeros(0, np.int64)
            fresh = [p for p in np.unique(preds).tolist() if p not in out]
            out.update(fresh)
            frontier = np.array(fresh, dtype=np.int64)
        return out

    def mark_stale(self) -> None:
        """Changelog RESET / truncation: incremental maintenance lost the
        thread — the index refuses every query until re-powered."""
        with self._mu:
            self._stale = True

    # -- query-path view -------------------------------------------------------

    def view_for(self, state) -> tuple[Optional[ClosureView], Optional[str]]:
        """The consistent device view for one submit, or (None, cause).
        Lock-free reads of immutable view objects; never touches the
        store (the submit path must not pay a store read here — the
        maintenance plane owns catch-up)."""
        with self._mu:
            view = self._view
            stale = self._stale
            build = self._build
            snap_ref = self._snapshot
        if build is None:
            return None, CAUSE_UNBUILT
        if stale:
            return None, CAUSE_STALE_SNAPSHOT
        if view is None or snap_ref is not state.snapshot:
            # OBJECT identity, not version equality: entries live in the
            # build snapshot's id space, and only the very object the
            # serving state wraps is guaranteed to share it
            return None, CAUSE_STALE_SNAPSHOT
        if view.synced_version < state.covered_version:
            return None, CAUSE_LAG
        return view, None

    def lag_versions(self, store_version: int) -> int:
        with self._mu:
            synced = self._synced_version
        if synced < 0:
            return 0
        return max(0, store_version - synced)

    def needs_rebuild(self) -> bool:
        with self._mu:
            return self._stale or self._build is None

    def describe(self) -> dict:
        with self._mu:
            build = self._build
            return {
                "built": build is not None,
                "stale": self._stale,
                "synced_version": self._synced_version,
                "dirty_nodes": len(self._dirty),
                "covered_nodes": (
                    len(build.covered_keys) if build is not None else 0
                ),
                "entries": build.n_entries if build is not None else 0,
                **{k: v for k, v in self.stats.items()},
            }

    # -- persistence -----------------------------------------------------------

    def _persist(self, build: ClosureBuild) -> None:
        if self.cache_path is None or build is None:
            return
        from .checkpoint import save_closure

        try:
            save_closure(build, self.cache_path)
        except OSError:
            import logging

            logging.getLogger("keto_tpu").warning(
                "closure checkpoint write failed", exc_info=True
            )

    def _load_cached(self, snap: GraphSnapshot, base_version: int,
                     max_depth: int) -> Optional[ClosureBuild]:
        if self.cache_path is None:
            return None
        from .checkpoint import load_closure

        build = load_closure(self.cache_path)
        if build is None or build.snapshot_version != snap.version:
            return None
        if build.vocab_fp != snapshot_vocab_fp(snap):
            # same (store version, config) but a DIFFERENT id
            # assignment: trusting the file would alias closure entries
            # into other names' ids — re-power instead
            return None
        if (
            build.max_depth != int(max_depth)
            or build.max_set_rows != self.max_set_rows
        ):
            # powered under different limits: a raised max_read_depth
            # (entries/poison trimmed to the old ring) or a changed row
            # cap would make definitive answers wrong — re-power
            return None
        self.stats["cache_loads"] += 1
        build.base_version = base_version
        return build
