"""Batched TPU expand: device BFS subgraph gather + exact host assembly.

The reference's Expand is a sequential DFS issuing one paginated SQL query
per tree node (internal/expand/engine.go:35-104). Here the device walks
all B expand queries breadth-first in lockstep over a full-edge CSR
(subject-id leaves AND subject-set children, unlike the check kernel's
subject-set-only CSR) and emits every discovered edge into a bounded
per-query buffer; the host then runs the reference's exact DFS —
visited-set cycle cut (graph_utils.go), depth bookkeeping (restDepth<=1 ⇒
leaf, engine.go:74-77), nil-vs-leaf rules — over the device-gathered
adjacency, touching no store.

Expand applies NO userset rewrites (the reference's BuildTree only follows
stored tuples), so the kernel needs no rewrite programs.

Per step every live task (query, obj, rel, depth):
  1. looks up its full-CSR row and, when depth >= 2, appends the row's
     edges to the query's edge buffer (per-query bump allocation via a
     segmented scan over tasks sorted by query)
  2. enqueues subject-set children at depth-1 (>= 2) into the next
     frontier, deduped on (query, obj, rel) keeping the deepest instance —
     deepest-wins guarantees the host DFS always finds children for any
     node it first visits at an expandable depth
Buffer overflow or frontier overflow flags the query needs_host and the
engine facade re-runs it on the host ReferenceEngine.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ketoapi import RelationTuple, SubjectSet, Tree, TreeNodeType
from .kernel import N_LAUNCH_STATS, empty_launch_stats as _empty_stats
from .snapshot import EMPTY, GraphSnapshot


# -- full-edge CSR (host build) ------------------------------------------------


def build_full_csr(
    tuples: Sequence[RelationTuple], snapshot: GraphSnapshot, view=None
) -> dict[str, np.ndarray]:
    """Group ALL edges by (obj_slot, rel): subject-id leaves and
    subject-set children, in tuple order within a row. Encoding goes
    through `view` (base vocab + delta overlay) when given; tuples whose
    names the view doesn't know yet (written after the covered version)
    are skipped — their rows are either dirty-flagged or beyond this
    state's staleness horizon anyway."""
    from .delta import SnapshotView

    view = view or SnapshotView(snapshot)
    n_t = len(tuples)
    t_obj = np.zeros(n_t, dtype=np.int32)
    t_rel = np.zeros(n_t, dtype=np.int32)
    t_skind = np.zeros(n_t, dtype=np.int32)
    t_sa = np.zeros(n_t, dtype=np.int32)
    t_sb = np.zeros(n_t, dtype=np.int32)
    keep = np.zeros(n_t, dtype=bool)
    for i, t in enumerate(tuples):
        node = view.encode_node(t.namespace, t.object, t.relation)
        subject = view.encode_subject(t)
        if node is None or subject is None:
            continue
        t_obj[i], t_rel[i] = node
        t_skind[i], t_sa[i], t_sb[i] = subject
        keep[i] = True

    return full_csr_from_encoded(
        t_obj[keep], t_rel[keep], t_skind[keep], t_sa[keep], t_sb[keep]
    )


def full_csr_from_encoded(t_obj, t_rel, t_skind, t_sa, t_sb) -> dict:
    """Group pre-encoded full edges (subject-id leaves AND subject-set
    children) into the expand kernel's row-hash + CSR tables."""
    from .snapshot import group_rows_csr

    fh_obj, fh_rel, fh_row, fh_probes, row_ptr, (f_skind, f_sa, f_sb) = (
        group_rows_csr(t_obj, t_rel, (t_skind, t_sa, t_sb))
    )
    return {
        "fh_obj": fh_obj, "fh_rel": fh_rel, "fh_row": fh_row,
        "fh_probes": fh_probes,
        "f_row_ptr": row_ptr,
        "f_skind": f_skind,
        "f_sa": f_sa,
        "f_sb": f_sb,
    }


def columnar_subject_order(cols, keep):
    """Within-row child order for columnar CSR builds: the store's
    identity-key total order restricted to the subject fields (the
    (ns, obj, rel) prefix is constant within a CSR row). Matches the
    host oracle's paginated read order so device-assembled trees list
    children exactly as the reference engine does."""
    k = np.flatnonzero(np.asarray(keep))
    return k[np.lexsort((
        cols.srel[k], cols.sobj[k], cols.sns[k],
        np.asarray(cols.skind)[k],
    ))]


def build_full_csr_columnar(cols, snapshot: GraphSnapshot) -> dict:
    """build_full_csr from TupleColumns: vectorized encoding against the
    snapshot's vocabularies (engine/snapshot.py encode_edge_columns) —
    the columnar store's expand state never materializes per-tuple
    Python objects (the 1e7..1e8-scale requirement, mirroring the check
    path's columnar ingest)."""
    from .snapshot import encode_edge_columns

    t_obj, t_rel, t_skind, t_sa, t_sb, keep = encode_edge_columns(
        cols, snapshot
    )
    order = columnar_subject_order(cols, keep)
    return full_csr_from_encoded(
        t_obj[order], t_rel[order], t_skind[order], t_sa[order], t_sb[order]
    )


# -- device kernel -------------------------------------------------------------


def _row_lookup(tables, obj, rel, probes: int):
    from .kernel import _pair_key_probe

    return _pair_key_probe(tables, "fh", obj, rel, probes)


class _ExpandState(NamedTuple):
    t_q: jnp.ndarray  # [F]
    t_obj: jnp.ndarray  # [F]
    t_rel: jnp.ndarray  # [F]
    t_depth: jnp.ndarray  # [F]
    n_tasks: jnp.ndarray
    # edge buffer, flattened [B * E]
    eb_pobj: jnp.ndarray
    eb_prel: jnp.ndarray
    eb_skind: jnp.ndarray
    eb_sa: jnp.ndarray
    eb_sb: jnp.ndarray
    eb_count: jnp.ndarray  # [B]
    needs_host: jnp.ndarray  # [B]
    step: jnp.ndarray
    stats: jnp.ndarray  # [N_LAUNCH_STATS] launch introspection counters


@functools.partial(
    jax.jit,
    static_argnames=("fh_probes", "max_steps", "frontier_cap", "edge_cap"),
)
@jax.named_scope("keto.expand")
def expand_kernel(
    tables: dict,
    q_obj: jnp.ndarray,  # [B]
    q_rel: jnp.ndarray,  # [B]
    q_depth: jnp.ndarray,  # [B] clamped depths
    q_valid: jnp.ndarray,  # [B]
    *,
    fh_probes: int,
    max_steps: int,
    frontier_cap: int,
    edge_cap: int,
):
    """Returns (eb_pobj, eb_prel, eb_skind, eb_sa, eb_sb  [B*E],
    eb_count [B], root_has_children [B], needs_host [B],
    stats [N_LAUNCH_STATS])."""
    B = q_obj.shape[0]
    F = frontier_cap
    E = edge_cap
    n_edges = tables["f_skind"].shape[0]
    n_rows = tables["f_row_ptr"].shape[0] - 1

    def row_span(row):
        row_c = jnp.clip(row, 0, n_rows)
        start = tables["f_row_ptr"][row_c]
        end = tables["f_row_ptr"][jnp.minimum(row_c + 1, n_rows)]
        start = jnp.where(row == EMPTY, 0, start)
        length = jnp.where(row == EMPTY, 0, end - start)
        return start, length

    root_row = _row_lookup(tables, q_obj, q_rel, fh_probes)
    _, root_len = row_span(root_row)
    root_has_children = (root_len > 0) & q_valid

    # delta-overlay dirty roots: the CSR no longer reflects this row
    # (even root_has_children may be stale) -> exact host replay
    from .delta import DIRTY_FOR_EXPAND
    from .kernel import dirty_lookup

    init_needs_host = q_valid & (
        (dirty_lookup(tables, q_obj, q_rel) & DIRTY_FOR_EXPAND) != 0
    )

    def step_fn(st: _ExpandState) -> _ExpandState:
        idx = jnp.arange(F, dtype=jnp.int32)
        live = (idx < st.n_tasks) & ~st.needs_host[st.t_q]
        q, obj, rel, depth = st.t_q, st.t_obj, st.t_rel, st.t_depth

        row = _row_lookup(tables, obj, rel, fh_probes)
        start, length = row_span(row)
        # only depth >= 2 nodes expand (restDepth<=1 ⇒ leaf, engine.go:74-77)
        emit = live & (depth >= 2)
        # overlay-dirty rows: stale CSR contents -> host replay
        task_dirty = emit & (
            (dirty_lookup(tables, obj, rel) & DIRTY_FOR_EXPAND) != 0
        )
        needs_host_d = st.needs_host.at[q].max(task_dirty)
        emit = emit & ~task_dirty
        counts = jnp.where(emit, length, 0)

        # per-query bump allocation: sort tasks by query, segmented
        # exclusive scan of counts within each query
        order = jnp.argsort(q + jnp.where(live, 0, B))  # dead tasks last
        sq = q[order]
        scounts = counts[order]
        cum = jnp.cumsum(scounts) - scounts
        seg_first = jnp.concatenate(
            [jnp.ones(1, dtype=bool), sq[1:] != sq[:-1]]
        )
        seg_base = jnp.where(seg_first, cum, 0)
        seg_base = jax.lax.associative_scan(jnp.maximum, seg_base)
        within_q = cum - seg_base  # exclusive scan within query segment
        alloc = st.eb_count[sq] + within_q  # first edge slot for this task

        # unsort back to task order
        inv = jnp.zeros(F, dtype=jnp.int32).at[order].set(
            jnp.arange(F, dtype=jnp.int32)
        )
        alloc_t = alloc[inv]

        # overflow: any task whose row doesn't fit flags its query
        overflow = emit & ((alloc_t + counts) > E)
        needs_host = needs_host_d.at[q].max(overflow)
        emit = emit & ~overflow

        # scatter edges: one pass over the max row length via a bounded
        # segmented gather (total emitted this step <= F rows * row len,
        # flattened through a [F] work list like the check kernel)
        flat_counts = jnp.where(emit, counts, 0)
        offsets = jnp.cumsum(flat_counts) - flat_counts
        total = offsets[-1] + flat_counts[-1]
        j = jnp.arange(F * 4, dtype=jnp.int32)  # emission slots this step
        seg = jnp.searchsorted(offsets, j, side="right").astype(jnp.int32) - 1
        seg = jnp.clip(seg, 0, F - 1)
        within = j - offsets[seg]
        in_range = j < jnp.minimum(total, F * 4)
        e = jnp.clip(start[seg] + within, 0, max(n_edges - 1, 0))
        if n_edges:
            c_skind = tables["f_skind"][e]
            c_sa = tables["f_sa"][e]
            c_sb = tables["f_sb"][e]
        else:
            c_skind = jnp.zeros(F * 4, jnp.int32)
            c_sa = jnp.zeros(F * 4, jnp.int32)
            c_sb = jnp.zeros(F * 4, jnp.int32)

        dest_q = q[seg]
        dest = jnp.where(
            in_range, dest_q * E + alloc_t[seg] + within, B * E
        )  # out-of-bounds drops
        eb_pobj = st.eb_pobj.at[dest].set(obj[seg], mode="drop")
        eb_prel = st.eb_prel.at[dest].set(rel[seg], mode="drop")
        eb_skind = st.eb_skind.at[dest].set(c_skind, mode="drop")
        eb_sa = st.eb_sa.at[dest].set(c_sa, mode="drop")
        eb_sb = st.eb_sb.at[dest].set(c_sb, mode="drop")
        eb_count = st.eb_count.at[dest_q].add(
            jnp.where(in_range & emit[seg], 1, 0), mode="drop"
        )
        # rows longer than the F*4 emission budget truncate: flag them
        trunc = (offsets + flat_counts) > F * 4
        needs_host = needs_host.at[q].max(emit & trunc)

        # next frontier: subject-set children at depth-1 >= 2
        child_depth = depth[seg] - 1
        cand_valid = in_range & (c_skind == 1) & (child_depth >= 2) & emit[seg]
        from .kernel import Expansion, dedupe_phase

        # expand has no islands: every task rides its query's root ctx
        children = Expansion(
            q=dest_q, ctx=dest_q, obj=c_sa, rel=c_sb,
            depth=child_depth, valid=cand_valid,
        )
        nt_q, _nt_ctx, nt_obj, nt_rel, nt_depth, n_new, overflow_q = dedupe_phase(
            children, F, B
        )
        # dedupe reports int32 cause codes (shared with the check kernel);
        # the expand state keeps a boolean flag
        needs_host = needs_host | (overflow_q > 0)
        from .kernel import update_launch_stats

        # launch counters: edges emitted into the buffer this step stand
        # in for the check kernel's candidate-row count
        stats = update_launch_stats(
            st.stats,
            st.n_tasks,
            (live & (depth >= 0)).sum(),
            jnp.int32(0),
            (in_range & emit[seg]).sum(),
            n_new,
        )
        return _ExpandState(
            nt_q, nt_obj, nt_rel, nt_depth, n_new,
            eb_pobj, eb_prel, eb_skind, eb_sa, eb_sb,
            eb_count, needs_host, st.step + 1, stats,
        )

    pad = F - B
    init = _ExpandState(
        t_q=jnp.pad(jnp.arange(B, dtype=jnp.int32), (0, pad)),
        t_obj=jnp.pad(q_obj.astype(jnp.int32), (0, pad)),
        t_rel=jnp.pad(q_rel.astype(jnp.int32), (0, pad)),
        t_depth=jnp.where(
            jnp.pad(q_valid, (0, pad), constant_values=False),
            jnp.pad(q_depth.astype(jnp.int32), (0, pad)),
            -1,
        ),
        n_tasks=jnp.int32(B),
        eb_pobj=jnp.full(B * edge_cap, EMPTY, jnp.int32),
        eb_prel=jnp.full(B * edge_cap, EMPTY, jnp.int32),
        eb_skind=jnp.zeros(B * edge_cap, jnp.int32),
        eb_sa=jnp.zeros(B * edge_cap, jnp.int32),
        eb_sb=jnp.zeros(B * edge_cap, jnp.int32),
        eb_count=jnp.zeros(B, jnp.int32),
        needs_host=init_needs_host,
        step=jnp.int32(0),
        stats=_empty_stats(),
    )

    def cond_fn(st: _ExpandState):
        return (st.step < max_steps) & (st.n_tasks > 0)

    from .kernel import bounded_loop

    final = bounded_loop(cond_fn, step_fn, init, max_steps)
    return (
        final.eb_pobj, final.eb_prel, final.eb_skind, final.eb_sa, final.eb_sb,
        final.eb_count, root_has_children, final.needs_host, final.stats,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "fh_probes", "max_steps", "frontier_cap", "edge_cap", "pool_cap"
    ),
)
@jax.named_scope("keto.expand")
def expand_kernel_packed(
    tables: dict,
    qpack: jnp.ndarray,  # [4, B] int32: obj, rel, depth, valid
    *,
    fh_probes: int,
    max_steps: int,
    frontier_cap: int,
    edge_cap: int,
    pool_cap: int,
):
    """expand_kernel with single-buffer I/O and DEVICE-SIDE COMPACTION.

    The raw kernel's edge buffers are [B * edge_cap] with per-query
    strides — at the bench shapes (B=256, E=4096, 8.5-node trees) the
    readback is ~21 MB of 99.8% padding, moved in eight separate
    buffers. This wrapper gathers the used entries into a dense
    [pool_cap, 5] pool on device and returns ONE int32 vector:

        [ offsets (B+1) | root_has_children (B) | needs_host (B)
          | stats (N_LAUNCH_STATS) | pool rows (pool_cap * 5, row-major) ]

    Query i's edge records live at pool rows offsets[i]:offsets[i+1].
    Queries whose span would cross pool_cap are flagged needs_host
    (exact host replay — same overflow contract as edge_cap)."""
    B = qpack.shape[1]
    E = edge_cap
    eb = expand_kernel(
        tables,
        qpack[0], qpack[1], qpack[2], qpack[3].astype(bool),
        fh_probes=fh_probes, max_steps=max_steps,
        frontier_cap=frontier_cap, edge_cap=edge_cap,
    )
    eb_pobj, eb_prel, eb_skind, eb_sa, eb_sb, eb_count, root, needs, stats = eb
    counts = jnp.clip(eb_count, 0, E)
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    # pool slot j belongs to the query whose span contains j
    j = jnp.arange(pool_cap, dtype=jnp.int32)
    seg = (
        jnp.searchsorted(offs[1:], j, side="right").astype(jnp.int32)
    )
    seg_c = jnp.clip(seg, 0, B - 1)
    within = j - offs[seg_c]
    valid = (j < offs[B]) & (seg < B)
    src = jnp.clip(seg_c * E + within, 0, B * E - 1)
    pool = jnp.stack(
        [
            jnp.where(valid, col[src], EMPTY)
            for col in (eb_pobj, eb_prel, eb_skind, eb_sa, eb_sb)
        ],
        axis=1,
    )  # [pool_cap, 5]
    # a query whose span crosses the pool edge is truncated: host replay
    needs = needs | ((offs[1:] > pool_cap) & (counts > 0))
    # clamp offsets so hosts never index past the pool
    offs = jnp.minimum(offs, pool_cap)
    return jnp.concatenate([
        offs.astype(jnp.int32),
        root.astype(jnp.int32),
        needs.astype(jnp.int32),
        stats.astype(jnp.int32),
        pool.reshape(-1),
    ])


def unpack_expand_results(flat: np.ndarray, B: int, pool_cap: int):
    """Slice expand_kernel_packed's vector into (offsets[B+1], root[B]
    bool, needs_host[B] bool, pool columns (pobj, prel, skind, sa, sb)
    each [pool_cap], stats[N_LAUNCH_STATS])."""
    offs = flat[: B + 1]
    root = flat[B + 1 : 2 * B + 1].astype(bool)
    needs = flat[2 * B + 1 : 3 * B + 1].astype(bool)
    stats = flat[3 * B + 1 : 3 * B + 1 + N_LAUNCH_STATS]
    pool = flat[3 * B + 1 + N_LAUNCH_STATS :].reshape(pool_cap, 5)
    return offs, root, needs, (
        pool[:, 0], pool[:, 1], pool[:, 2], pool[:, 3], pool[:, 4]
    ), stats


# -- host assembly -------------------------------------------------------------


class _ChainLookup:
    """Two-level id -> name lookup: small overlay first, then base. Lets a
    delta refresh extend a decoder without copying the base dicts."""

    __slots__ = ("base", "extra")

    def __init__(self, base, extra):
        self.base = base
        self.extra = extra

    def __getitem__(self, key):
        v = self.extra.get(key)
        if v is None:
            return self.base[key]
        return v


class _ArrayIdLookup:
    """id -> decoded key over an ArrayMap (no dict materialization: at
    1e7+ slots inverting into a Python dict costs GBs and minutes —
    exactly what the columnar vocab path exists to avoid)."""

    __slots__ = ("_amap",)

    def __init__(self, amap):
        self._amap = amap

    def __getitem__(self, i):
        return self._amap.key_by_id(int(i))


# decoder memo bound: caches cover the serving hot set without letting a
# 1e7-vocab scan materialize the whole reverse vocabulary in Python
# (which the ArrayMap design exists to avoid)
_DECODER_MEMO_CAP = 200_000


class ExpandDecoder:
    """Reverse vocabularies for decoding device ids back to strings.

    subject_set()/subject_name() memoize per instance: tree assembly
    resolves the same hot (obj_slot, rel) pairs and subject ids across
    every tree of a batch (and across batches — the decoder lives on the
    engine state), and each uncached ArrayMap decode costs ~5-10 us of
    Python, which dominated the 1.34 ms/tree r04 assembly profile."""

    def __init__(self, snapshot: Optional[GraphSnapshot]):
        self._ss_memo: dict = {}
        self._subj_memo: dict = {}
        if snapshot is not None:
            from .snapshot import ArrayMap

            self.ns_names = {v: k for k, v in snapshot.ns_ids.items()}
            self.rel_names = {v: k for k, v in snapshot.rel_ids.items()}
            if isinstance(snapshot.obj_slots, ArrayMap):
                self.slot_to_obj = _ArrayIdLookup(snapshot.obj_slots)
            else:
                self.slot_to_obj = {v: k for k, v in snapshot.obj_slots.items()}
            if isinstance(snapshot.subj_ids, ArrayMap):
                self.subj_names = _ArrayIdLookup(snapshot.subj_ids)
            else:
                self.subj_names = {v: k for k, v in snapshot.subj_ids.items()}

    def extended(self, overlay) -> "ExpandDecoder":
        """Decoder view including a VocabOverlay's additions; O(overlay),
        the base reverse dicts are shared, not copied."""
        if overlay is None:
            return self
        d = ExpandDecoder(None)  # fresh memos: ids can remap per overlay
        d.ns_names = _ChainLookup(self.ns_names, {v: k for k, v in overlay.ns_ids.items()})
        d.rel_names = _ChainLookup(self.rel_names, {v: k for k, v in overlay.rel_ids.items()})
        d.slot_to_obj = _ChainLookup(
            self.slot_to_obj, {v: k for k, v in overlay.obj_slots.items()}
        )
        d.subj_names = _ChainLookup(
            self.subj_names, {v: k for k, v in overlay.subj_ids.items()}
        )
        return d

    def subject_set(self, obj_slot: int, rel: int) -> SubjectSet:
        key = (obj_slot, rel)
        ss = self._ss_memo.get(key)
        if ss is None:
            ns_id, obj = self.slot_to_obj[obj_slot]
            ss = SubjectSet(
                namespace=self.ns_names[ns_id],
                object=obj,
                relation=self.rel_names[rel],
            )
            if len(self._ss_memo) < _DECODER_MEMO_CAP:
                self._ss_memo[key] = ss
        return ss

    def subject_name(self, subj_id: int) -> str:
        name = self._subj_memo.get(subj_id)
        if name is None:
            name = self.subj_names[subj_id]
            if len(self._subj_memo) < _DECODER_MEMO_CAP:
                self._subj_memo[subj_id] = name
        return name


def assemble_tree(
    root: SubjectSet,
    root_slot: int,
    root_rel: int,
    depth: int,
    adjacency: dict[tuple[int, int], list[tuple[int, int, int]]],
    root_has_children: bool,
    decoder: ExpandDecoder,
) -> Optional[Tree]:
    """Exact reference DFS over the device-gathered adjacency:
    visited-set cycle cut, restDepth accounting, nil-vs-leaf rules
    (internal/expand/engine.go:35-104)."""
    visited: set[tuple[int, int]] = set()

    def subject_tuple(skind: int, sa: int, sb: int) -> RelationTuple:
        t = RelationTuple(namespace="", object="", relation="")
        if skind == 1:
            t.subject_set = decoder.subject_set(sa, sb)
        else:
            t.subject_id = decoder.subject_name(sa)
        return t

    def build(obj_slot: int, rel: int, rest: int) -> Optional[Tree]:
        key = (obj_slot, rel)
        if key in visited:
            return None  # cycle cut ⇒ nil ⇒ parent renders a leaf
        visited.add(key)
        children = adjacency.get(key)
        if not children:
            return None  # no matching tuples ⇒ nil
        node_tuple = RelationTuple(namespace="", object="", relation="")
        node_tuple.subject_set = decoder.subject_set(obj_slot, rel)
        node = Tree(type=TreeNodeType.UNION, tuple=node_tuple)
        if rest <= 1:
            node.type = TreeNodeType.LEAF
            return node
        for skind, sa, sb in children:
            child = build(sa, sb, rest - 1) if skind == 1 else None
            if child is None:
                child = Tree(
                    type=TreeNodeType.LEAF, tuple=subject_tuple(skind, sa, sb)
                )
            node.children.append(child)
        return node

    if depth <= 1:
        # the root expands nothing at restDepth<=1: leaf if its row is
        # non-empty, nil otherwise (engine.go:57-77)
        if not root_has_children:
            return None
        node_tuple = RelationTuple(namespace="", object="", relation="")
        node_tuple.subject_set = root
        return Tree(type=TreeNodeType.LEAF, tuple=node_tuple)
    return build(root_slot, root_rel, depth)


def decode_edge_buffer(
    eb_pobj, eb_prel, eb_skind, eb_sa, eb_sb, count: int, base: int
) -> dict[tuple[int, int], list[tuple[int, int, int]]]:
    """Edge records [base : base+count] → adjacency keyed by parent node,
    deduped preserving first-emission order (a node expanded at two BFS
    steps emits its row twice).

    Bulk .tolist() then a plain-int loop: converting numpy scalars one
    element at a time (int(arr[i]) x5 per record) cost ~3 us/record in
    the r04 assembly profile; tolist() converts the whole slice at
    ~50 ns/element and the loop then runs on machine ints."""
    adjacency: dict[tuple[int, int], list[tuple[int, int, int]]] = {}
    seen: set[tuple] = set()
    end = base + count
    rows = zip(
        eb_pobj[base:end].tolist(), eb_prel[base:end].tolist(),
        eb_skind[base:end].tolist(), eb_sa[base:end].tolist(),
        eb_sb[base:end].tolist(),
    )
    for rec in rows:
        if rec in seen:
            continue
        seen.add(rec)
        adjacency.setdefault((rec[0], rec[1]), []).append(rec[2:])
    return adjacency
