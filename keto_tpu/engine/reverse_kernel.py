"""Batched reverse-reachability kernels: ListObjects / ListSubjects.

The forward check kernel answers "may S do R on O?"; Zanzibar's hardest
production query family is the inverse — "which objects can this subject
reach?" (served there by the Leopard set index) and its dual "which
subjects reach this object?". Both are set-valued graph joins that batch
into the same bucketized-gather shape the check kernel runs (TrieJax /
GraphBLAS formulation: frontier expansion = batched sparse gather), so
they ride the identical bounded loop (engine/kernel.bounded_loop),
dedupe, and cause-coded host-fallback machinery.

ListObjects — reverse BFS over the TRANSPOSED mirror
(snapshot.build_reverse_tables / build_reverse_programs):

  seeds: the reverse-seed CSR row for the query's exact subject key —
    precisely the nodes whose direct probe the forward kernel would hit.
  per step, each frontier task (query, obj, rel, depth):
    1. flag_phase on the VISITED node (config-missing / relation-not-
       found / island / host-only programs host-flag the query, same
       codes as check) + reverse-dirty overlay probe (CAUSE_DIRTY)
    2. emit `obj` into the query's result pool when the node matches the
       query's (namespace, relation) filter and depth >= 0
    3. expand to PREDECESSORS: the reverse-edge CSR row keyed by `obj`
       inverts checkExpandSubject (edge sb == task rel, task rel not
       wildcard -> pred (edge obj, edge rel) at depth-1) and TTU
       instructions (inverted entry (ns, rel_p, rel_t) with edge rel ==
       rel_t and edge-obj namespace == ns -> pred (edge obj, rel_p) at
       depth-1); inverted COMPUTED entries add (obj, rel_p) at the SAME
       depth. POISON entries (AND-island leaf relations) host-flag the
       query instead of expanding — island members are not enumerable by
       pure-OR propagation.
    4. dedupe on (query, obj, rel) keeping the deepest remaining depth
       (kernel.dedupe_phase, unchanged).

  Exactness: device-exact on the monotone fragment; AND islands flag via
  poison entries (a member of an AND implies every leaf sub-check is a
  member, so the walk reaches a leaf relation before the island's
  members could be silently missed); any NOT in the config disables the
  device path entirely (snapshot.build_reverse_programs host_all) — NOT
  members exist exactly where NO path exists, which reachability cannot
  observe. Frontier/result/seed overflow, dirty rows, and step-budget
  exhaustion flag their query; the facade replays flagged queries on the
  exact host oracle (engine/reference.py list_objects).

ListSubjects — forward BFS from one (obj, rel) node over the full-edge
CSR (expand_kernel.build_full_csr: plain leaves AND subject-set
children) PLUS the compiled rewrite instructions (unlike Expand, which
follows stored tuples only): every visited node's plain-subject edges
are results when depth >= 1 (the forward direct probe's depth rule);
subject-set edges and COMPUTED/TTU instructions continue the walk with
check's exact depth bookkeeping. Same flag/fallback contract.

Both kernels use packed single-buffer I/O (one upload, one readback per
batch, see check_kernel_packed).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .delta import DELTA_PROBES, DIRTY_FOR_EXPAND
from .kernel import (
    CAUSE_DIRTY,
    CAUSE_FRONTIER_OVERFLOW,
    CAUSE_ISLAND_HOST,
    CAUSE_STEP_EXHAUSTED,
    Expansion,
    N_LAUNCH_STATS,
    _isolate,
    _multi_pair_key_probe,
    bounded_loop,
    covering_segments,
    dedupe_phase,
    empty_launch_stats,
    flag_phase,
    pack_instr_table,
    pack_row_table,
    pack_rh_span_table,
    program_lookup,
    update_launch_stats,
)
from .snapshot import (
    EMPTY,
    INSTR_COMPUTED,
    INSTR_TTU,
    RINSTR_COMPUTED,
    RINSTR_POISON,
    RINSTR_TTU,
    GraphSnapshot,
    build_reverse_programs,
    build_reverse_tables,
    reverse_subject_tag,
)


# -- host state builders (mirror expand_kernel.build_full_csr*) ----------------


def build_reverse_state(
    tuples: Sequence, snapshot: GraphSnapshot, namespaces, view=None
) -> dict:
    """Transposed mirror + inverted programs from per-tuple objects;
    tuples unknown to the view drop (their rows are reverse-dirty-flagged
    or beyond this state's staleness horizon, like build_full_csr)."""
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
    return _reverse_state_from_encoded(
        t_obj[keep], t_rel[keep], t_skind[keep], t_sa[keep], t_sb[keep],
        snapshot, namespaces,
    )


def build_reverse_state_columnar(cols, snapshot: GraphSnapshot, namespaces) -> dict:
    """Columnar twin: vectorized encoding against the snapshot vocab
    (no per-tuple Python on the 1e7+ ingest path)."""
    from .snapshot import encode_edge_columns

    t_obj, t_rel, t_skind, t_sa, t_sb, keep = encode_edge_columns(cols, snapshot)
    k = np.flatnonzero(keep)
    return _reverse_state_from_encoded(
        t_obj[k], t_rel[k], t_skind[k], t_sa[k], t_sb[k], snapshot, namespaces
    )


def _reverse_state_from_encoded(
    t_obj, t_rel, t_skind, t_sa, t_sb, snapshot: GraphSnapshot, namespaces
) -> dict:
    state = build_reverse_tables(t_obj, t_rel, t_skind, t_sa, t_sb)
    (
        rinstr_kind, rinstr_relp, rinstr_relt, rinstr_ns, RK, host_all,
    ) = build_reverse_programs(
        namespaces, snapshot.ns_ids, snapshot.rel_ids, snapshot.n_config_rels
    )
    state.update(
        rinstr_kind=rinstr_kind, rinstr_relp=rinstr_relp,
        rinstr_relt=rinstr_relt, rinstr_ns=rinstr_ns,
        RK=RK, host_all=host_all, garbage=0,
    )
    return state


def pack_rinstr_table(kind, relp, relt, ns) -> np.ndarray:
    """Interleave the inverted-instruction columns into [NR, RK*4] rows
    of (kind, rel_p, rel_t, ns) lanes — one row-gather per task."""
    NR, RK = kind.shape
    out = np.zeros((NR, RK, 4), dtype=np.int32)
    out[..., 0] = kind
    out[..., 1] = relp
    out[..., 2] = relt
    out[..., 3] = ns
    return out.reshape(NR, RK * 4)


def pack_reverse_tables(rnp: dict, snapshot: GraphSnapshot) -> dict:
    """Host reverse-state arrays -> the device table dict the reverse
    kernel closes over. Spans resolve into the row-hash value lanes at
    pack time (pack_rh_span_table) so row lookups ride the probe's own
    bucket-row fetch, exactly like the forward rh table."""
    return {
        "rvh_pack": pack_rh_span_table(
            rnp["rvh_obj"], rnp["rvh_rel"], rnp["rvh_row"], rnp["rv_row_ptr"]
        ),
        "rv_pack": pack_row_table(rnp["rv_pobj"], rnp["rv_prel"], rnp["rv_sb"]),
        "rsh_pack": pack_rh_span_table(
            rnp["rsh_obj"], rnp["rsh_tag"], rnp["rsh_row"], rnp["rs_row_ptr"]
        ),
        "rs_pack": np.stack(
            [np.asarray(rnp["rs_obj"]), np.asarray(rnp["rs_rel"])], axis=-1
        ).astype(np.int32),
        "rinstr_pack": pack_rinstr_table(
            rnp["rinstr_kind"], rnp["rinstr_relp"],
            rnp["rinstr_relt"], rnp["rinstr_ns"],
        ),
        "objslot_ns": np.asarray(snapshot.objslot_ns),
        "ns_has_config": np.asarray(snapshot.ns_has_config),
        "prog_flags": np.asarray(snapshot.prog_flags),
    }


def pack_subjects_tables(csr: dict, snapshot: GraphSnapshot) -> dict:
    """Full-edge CSR (expand_kernel.build_full_csr output) -> the
    list-subjects device tables: span-resolved fh row table + packed
    (skind, sa, sb) edge rows + the check kernel's instruction lanes."""
    return {
        "fsh_pack": pack_rh_span_table(
            csr["fh_obj"], csr["fh_rel"], csr["fh_row"], csr["f_row_ptr"]
        ),
        "fe_pack": pack_row_table(csr["f_skind"], csr["f_sa"], csr["f_sb"]),
        "instr_pack": pack_instr_table(
            snapshot.instr_kind, snapshot.instr_rel, snapshot.instr_rel2
        ),
        "objslot_ns": np.asarray(snapshot.objslot_ns),
        "ns_has_config": np.asarray(snapshot.ns_has_config),
        "prog_flags": np.asarray(snapshot.prog_flags),
    }


# -- shared device helpers -----------------------------------------------------


def _span_probe(tables, prefix: str, k1, k2, probes: int):
    """(start[F], len[F]) of the CSR row keyed (k1, k2) in a
    span-resolved pair table ({prefix}_pack); EMPTY rows -> len 0."""
    spans = _multi_pair_key_probe(
        tables, prefix, k1, k2[:, None], probes, n_vals=2
    )[:, 0, :]
    start = spans[..., 0]
    length = jnp.where(start < 0, 0, spans[..., 1] - start)
    return start, length


def _bump_emit(q, emit, counts_so_far, F: int, B: int):
    """Per-query bump allocation for <=1 emission per task: returns
    (slot_within_query[F]) for emitting tasks (garbage elsewhere). Same
    sort + segmented-scan construction as the expand kernel's edge
    buffer."""
    inc = emit.astype(jnp.int32)
    order = jnp.argsort(q + jnp.where(emit, 0, B))
    sq = q[order]
    scounts = inc[order]
    cum = jnp.cumsum(scounts) - scounts
    seg_first = jnp.concatenate([jnp.ones(1, dtype=bool), sq[1:] != sq[:-1]])
    seg_base = jnp.where(seg_first, cum, 0)
    seg_base = jax.lax.associative_scan(jnp.maximum, seg_base)
    within_q = cum - seg_base
    inv = jnp.zeros(F, dtype=jnp.int32).at[order].set(
        jnp.arange(F, dtype=jnp.int32)
    )
    return counts_so_far[q] + within_q[inv]


def _rd_lookup(tables, k1, k2):
    """Reverse-dirty probe: nonzero when the (key, tag) entry is marked
    in the delta's rd table (0 when clean)."""
    val = _multi_pair_key_probe(tables, "rd", k1, k2[:, None], DELTA_PROBES)[
        :, 0
    ]
    return jnp.maximum(val, 0)


# -- ListObjects: reverse BFS --------------------------------------------------


class _RevState(NamedTuple):
    t_q: jnp.ndarray  # [F]
    t_obj: jnp.ndarray  # [F]
    t_rel: jnp.ndarray  # [F]
    t_depth: jnp.ndarray  # [F] remaining depth (D - consumed)
    n_tasks: jnp.ndarray
    res_obj: jnp.ndarray  # [B * R] matched object slots (strided)
    res_count: jnp.ndarray  # [B]
    needs_host: jnp.ndarray  # [B] CAUSE_* code
    step: jnp.ndarray
    stats: jnp.ndarray  # [N_LAUNCH_STATS] launch introspection counters


_REVERSE_STATICS = (
    "rvh_probes", "rsh_probes", "RK", "max_steps", "wildcard_rel",
    "n_config_rels", "frontier_cap", "result_cap", "has_delta",
)


def _list_objects_impl(
    tables: dict,
    q_sa: jnp.ndarray,  # [B] subject id / subject-set object slot
    q_tag: jnp.ndarray,  # [B] reverse_subject_tag of the subject
    q_ns: jnp.ndarray,  # [B] target namespace id (result filter)
    q_rel: jnp.ndarray,  # [B] target relation id (result filter)
    q_depth: jnp.ndarray,  # [B] clamped max depth
    q_valid: jnp.ndarray,  # [B] bool
    *,
    rvh_probes: int,
    rsh_probes: int,
    RK: int,
    max_steps: int,
    wildcard_rel: int,
    n_config_rels: int,
    frontier_cap: int,
    result_cap: int,
    has_delta: bool,
):
    """Returns (res_obj [B*R], res_count [B], needs_host [B])."""
    B = q_sa.shape[0]
    F = frontier_cap
    R = result_cap
    S = 1 + RK  # expansion slots: reverse-ES row + inverted instructions
    n_redges = tables["rv_pack"].shape[0]
    n_sedges = tables["rs_pack"].shape[0]
    NCR = max(n_config_rels, 1)

    # -- seed: the reverse-seed CSR row for each query's subject key ----------
    s_start, s_len = _span_probe(tables, "rsh", q_sa, q_tag, rsh_probes)
    seed_counts = jnp.where(q_valid, s_len, 0)
    needs_host = jnp.zeros(B, dtype=jnp.int32)
    if has_delta:
        # the subject's direct-edge set changed since the base snapshot:
        # the seed row is stale either way (insert or tombstone)
        seed_dirty = q_valid & (_rd_lookup(tables, q_sa, q_tag) != 0)
        needs_host = jnp.where(seed_dirty, CAUSE_DIRTY, needs_host)
    offsets = jnp.cumsum(seed_counts) - seed_counts
    total = offsets[-1] + seed_counts[-1]
    # queries whose seed span crosses the frontier: host replay
    needs_host = jnp.maximum(
        needs_host,
        jnp.where(
            ((offsets + seed_counts) > F) & (seed_counts > 0),
            CAUSE_FRONTIER_OVERFLOW, 0,
        ).astype(jnp.int32),
    )
    seg, j = covering_segments(offsets, seed_counts, F)
    in_range = j < jnp.minimum(total, F)
    e = jnp.clip(s_start[seg] + (j - offsets[seg]), 0, max(n_sedges - 1, 0))
    if n_sedges:
        sp = _isolate(tables["rs_pack"][e])  # [F, 2] = (obj, rel)
        seed_obj, seed_rel = sp[:, 0], sp[:, 1]
    else:
        seed_obj = jnp.zeros(F, jnp.int32)
        seed_rel = jnp.zeros(F, jnp.int32)
    init = _RevState(
        t_q=jnp.where(in_range, seg, 0),
        t_obj=jnp.where(in_range, seed_obj, 0),
        # a direct hit consumes one depth unit (checkDirect runs at
        # restDepth-1), so seeds enter at D-1; emission requires >= 0
        t_rel=jnp.where(in_range, seed_rel, 0),
        t_depth=jnp.where(in_range, q_depth[seg] - 1, -1),
        n_tasks=jnp.minimum(total, F).astype(jnp.int32),
        res_obj=jnp.full(B * R, EMPTY, jnp.int32),
        res_count=jnp.zeros(B, jnp.int32),
        needs_host=needs_host,
        step=jnp.int32(0),
        stats=empty_launch_stats(),
    )

    def step_fn(st: _RevState) -> _RevState:
        idx = jnp.arange(F, dtype=jnp.int32)
        q, obj, rel, depth = st.t_q, st.t_obj, st.t_rel, st.t_depth
        live = (idx < st.n_tasks) & (st.needs_host[q] == 0)

        # 1. visited-node flags (same codes + exclusivity as check)
        prog = program_lookup(tables, obj, rel, live, n_config_rels=NCR)
        ns_t = prog[0]
        flagged = flag_phase(
            tables, obj, rel, live, n_config_rels=NCR, island_is_host=True,
            prog=prog,
        )
        needs_host = st.needs_host.at[q].max(flagged)
        if has_delta:
            zero = jnp.zeros_like(obj)
            row_dirty = live & (_rd_lookup(tables, obj, zero) != 0)
            needs_host = needs_host.at[q].max(
                jnp.where(row_dirty, CAUSE_DIRTY, 0).astype(jnp.int32)
            )

        # 2. result emission: the node matches its query's target filter
        match = (
            live
            & (rel == q_rel[q])
            & (ns_t == q_ns[q])
            & (depth >= 0)
        )
        alloc = _bump_emit(q, match, st.res_count, F, B)
        res_over = match & (alloc >= R)
        needs_host = needs_host.at[q].max(
            jnp.where(res_over, CAUSE_FRONTIER_OVERFLOW, 0).astype(jnp.int32)
        )
        emit = match & ~res_over
        dest = jnp.where(emit, q * R + alloc, B * R)
        res_obj = st.res_obj.at[dest].set(obj, mode="drop")
        res_count = st.res_count.at[q].add(emit.astype(jnp.int32))

        # 3. predecessor expansion -------------------------------------------
        # reverse-edge row keyed by the task's object slot
        zero = jnp.zeros_like(obj)
        rstart, rlen = _span_probe(tables, "rvh", obj, zero, rvh_probes)

        # inverted-instruction row keyed by the task's relation
        has_ri = live & (rel < NCR)
        ripack = _isolate(
            tables["rinstr_pack"][jnp.where(has_ri, rel, 0)]
        ).reshape(F, RK, 4)
        rik = jnp.where(has_ri[:, None], ripack[..., 0], 0)
        rip = ripack[..., 1]
        rit = ripack[..., 2]
        rin = ripack[..., 3]

        # POISON: an AND-island program pulls from this relation — its
        # members are not pure-OR-enumerable, so the query goes to host
        poison = live & jnp.any(
            (rik == RINSTR_POISON) & ((rin == -1) | (rin == ns_t[:, None])),
            axis=1,
        )
        needs_host = needs_host.at[q].max(
            jnp.where(poison, CAUSE_ISLAND_HOST, 0).astype(jnp.int32)
        )

        can_es = live & (depth >= 1) & (rel != wildcard_rel)
        is_rc = (rik == RINSTR_COMPUTED) & live[:, None] & (
            rin == ns_t[:, None]
        )
        is_rt = (rik == RINSTR_TTU) & (live & (depth >= 1))[:, None]
        counts = jnp.concatenate(
            [
                jnp.where(can_es, rlen, 0)[:, None],
                jnp.where(is_rc, 1, jnp.where(is_rt, rlen[:, None], 0)),
            ],
            axis=1,
        )  # [F, S]
        slot_kind = jnp.concatenate(
            [
                jnp.zeros((F, 1), jnp.int32),
                jnp.where(is_rc, 1, jnp.where(is_rt, 2, 0)),
            ],
            axis=1,
        )

        flat_counts = counts.reshape(-1)
        offsets = jnp.cumsum(flat_counts) - flat_counts
        total = offsets[-1] + flat_counts[-1]
        truncated = (offsets + flat_counts) > F
        seg_q = jnp.repeat(q, S, total_repeat_length=F * S)
        needs_host = needs_host.at[seg_q].max(
            jnp.where(
                truncated & (flat_counts > 0), CAUSE_FRONTIER_OVERFLOW, 0
            ).astype(jnp.int32)
        )

        seg, j = covering_segments(offsets, flat_counts, F)
        in_range = j < jnp.minimum(total, F)

        # ONE [F, 16] row-gather of the stacked per-(task, slot) source
        # matrix (same gather-volume lever as check's expand_phase)
        srcmat = jnp.stack(
            [
                jnp.broadcast_to(q[:, None], (F, S)),
                jnp.broadcast_to(obj[:, None], (F, S)),
                jnp.broadcast_to(rel[:, None], (F, S)),
                jnp.broadcast_to(depth[:, None], (F, S)),
                jnp.broadcast_to(rstart[:, None], (F, S)),
                slot_kind,
                jnp.concatenate([jnp.zeros((F, 1), jnp.int32), rip], axis=1),
                jnp.concatenate([jnp.zeros((F, 1), jnp.int32), rit], axis=1),
                jnp.concatenate(
                    [jnp.full((F, 1), -2, jnp.int32), rin], axis=1
                ),
                offsets.reshape(F, S),
                *(
                    jnp.zeros((F, S), jnp.int32)
                    for _ in range(6)
                ),  # pad to a 16-lane (64 B) gather row
            ],
            axis=-1,
        ).reshape(F * S, 16)
        src = _isolate(srcmat[seg])
        src_q = src[:, 0]
        src_obj = src[:, 1]
        src_rel = src[:, 2]
        src_depth = src[:, 3]
        src_start = src[:, 4]
        src_kind = src[:, 5]
        src_relp = src[:, 6]
        src_relt = src[:, 7]
        src_ns = src[:, 8]
        within = j - src[:, 9]

        e = jnp.clip(src_start + within, 0, max(n_redges - 1, 0))
        if n_redges:
            ep = _isolate(tables["rv_pack"][e])  # (p_obj, p_rel, e_sb, 0)
            p_obj, p_rel, e_sb = ep[:, 0], ep[:, 1], ep[:, 2]
        else:
            p_obj = jnp.zeros(F, jnp.int32)
            p_rel = jnp.zeros(F, jnp.int32)
            e_sb = jnp.zeros(F, jnp.int32)
        p_ns = tables["objslot_ns"][jnp.clip(p_obj, 0, None)]

        is_es = src_kind == 0
        is_c = src_kind == 1
        child_obj = jnp.where(is_c, src_obj, p_obj)
        child_rel = jnp.where(is_es, p_rel, src_relp)
        child_depth = jnp.where(is_c, src_depth, src_depth - 1)
        cond = jnp.where(
            is_es,
            e_sb == src_rel,
            is_c | ((p_rel == src_relt) & (p_ns == src_ns)),
        )
        children = Expansion(
            q=src_q, ctx=src_q, obj=child_obj, rel=child_rel,
            depth=child_depth, valid=in_range & cond,
        )
        nt_q, _nt_ctx, nt_obj, nt_rel, nt_depth, n_new, overflow_q = (
            dedupe_phase(children, F, B)
        )
        needs_host = jnp.maximum(needs_host, overflow_q)
        stats = update_launch_stats(
            st.stats,
            st.n_tasks,
            (live & (depth >= 0)).sum(),
            emit.sum(),
            children.valid.sum(),
            n_new,
        )
        return _RevState(
            nt_q, nt_obj, nt_rel, nt_depth, n_new,
            res_obj, res_count, needs_host, st.step + 1, stats,
        )

    def cond_fn(st: _RevState):
        return (
            (st.step < max_steps)
            & (st.n_tasks > 0)
            & ~jnp.all(st.needs_host > 0)
        )

    final = bounded_loop(cond_fn, step_fn, init, max_steps)
    # step budget ran out with live tasks: the walk did NOT finish —
    # those queries' enumerations may be incomplete (host replay)
    exhausted = (final.step >= max_steps) & (final.n_tasks > 0)
    live = jnp.arange(F, dtype=jnp.int32) < final.n_tasks
    needs_host = final.needs_host.at[final.t_q].max(
        jnp.where(exhausted & live, CAUSE_STEP_EXHAUSTED, 0).astype(jnp.int32)
    )
    return final.res_obj, final.res_count, needs_host, final.stats


@functools.partial(
    jax.jit, static_argnames=_REVERSE_STATICS + ("pool_cap",)
)
@jax.named_scope("keto.reverse")
def list_objects_kernel_packed(
    tables: dict,
    qpack: jnp.ndarray,  # [6, B] int32: sa, tag, ns, rel, depth, valid
    *,
    rvh_probes: int,
    rsh_probes: int,
    RK: int,
    max_steps: int,
    wildcard_rel: int,
    n_config_rels: int,
    frontier_cap: int,
    result_cap: int,
    pool_cap: int,
    has_delta: bool,
):
    """Single-buffer I/O + device-side compaction: ONE int32 vector
    [ offsets (B+1) | needs_host (B) | stats (N_LAUNCH_STATS) |
    pool rows (pool_cap) ]; query i's matched object slots live at
    pool[offsets[i]:offsets[i+1]] (may contain revisit duplicates — the
    host decoder dedupes)."""
    B = qpack.shape[1]
    R = result_cap
    res_obj, res_count, needs_host, stats = _list_objects_impl(
        tables,
        qpack[0], qpack[1], qpack[2], qpack[3], qpack[4],
        qpack[5].astype(bool),
        rvh_probes=rvh_probes, rsh_probes=rsh_probes, RK=RK,
        max_steps=max_steps, wildcard_rel=wildcard_rel,
        n_config_rels=n_config_rels, frontier_cap=frontier_cap,
        result_cap=result_cap, has_delta=has_delta,
    )
    counts = jnp.clip(res_count, 0, R)
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    j = jnp.arange(pool_cap, dtype=jnp.int32)
    seg = jnp.searchsorted(offs[1:], j, side="right").astype(jnp.int32)
    seg_c = jnp.clip(seg, 0, B - 1)
    within = j - offs[seg_c]
    valid = (j < offs[B]) & (seg < B)
    src = jnp.clip(seg_c * R + within, 0, B * R - 1)
    pool = jnp.where(valid, res_obj[src], EMPTY)
    needs_host = jnp.maximum(
        needs_host,
        jnp.where(
            (offs[1:] > pool_cap) & (counts > 0), CAUSE_FRONTIER_OVERFLOW, 0
        ).astype(jnp.int32),
    )
    offs = jnp.minimum(offs, pool_cap)
    return jnp.concatenate([offs, needs_host, stats.astype(jnp.int32), pool])


def unpack_list_results(flat: np.ndarray, B: int):
    """(offsets[B+1], needs_host[B] cause codes, pool values,
    stats[N_LAUNCH_STATS])."""
    offs = flat[: B + 1]
    needs = flat[B + 1 : 2 * B + 1]
    stats = flat[2 * B + 1 : 2 * B + 1 + N_LAUNCH_STATS]
    pool = flat[2 * B + 1 + N_LAUNCH_STATS :]
    return offs, needs, pool, stats


# -- ListSubjects: forward BFS with subject emission ---------------------------


class _SubState(NamedTuple):
    t_q: jnp.ndarray
    t_obj: jnp.ndarray
    t_rel: jnp.ndarray
    t_depth: jnp.ndarray
    n_tasks: jnp.ndarray
    res_sub: jnp.ndarray  # [B * R] plain subject ids (strided)
    res_count: jnp.ndarray  # [B]
    needs_host: jnp.ndarray  # [B] CAUSE_* code
    step: jnp.ndarray
    stats: jnp.ndarray  # [N_LAUNCH_STATS] launch introspection counters


_SUBJECTS_STATICS = (
    "K", "fsh_probes", "max_steps", "wildcard_rel", "n_config_rels",
    "frontier_cap", "result_cap", "has_delta",
)


def _list_subjects_impl(
    tables: dict,
    q_obj: jnp.ndarray,  # [B]
    q_rel: jnp.ndarray,  # [B]
    q_depth: jnp.ndarray,  # [B]
    q_valid: jnp.ndarray,  # [B]
    *,
    K: int,
    fsh_probes: int,
    max_steps: int,
    wildcard_rel: int,
    n_config_rels: int,
    frontier_cap: int,
    result_cap: int,
    has_delta: bool,
):
    """Returns (res_sub [B*R], res_count [B], needs_host [B])."""
    B = q_obj.shape[0]
    F = frontier_cap
    R = result_cap
    S = K + 1
    n_edges = tables["fe_pack"].shape[0]
    NCR = max(n_config_rels, 1)

    pad = F - B
    init = _SubState(
        t_q=jnp.pad(jnp.arange(B, dtype=jnp.int32), (0, pad)),
        t_obj=jnp.pad(q_obj.astype(jnp.int32), (0, pad)),
        t_rel=jnp.pad(q_rel.astype(jnp.int32), (0, pad)),
        t_depth=jnp.where(
            jnp.pad(q_valid, (0, pad), constant_values=False),
            jnp.pad(q_depth.astype(jnp.int32), (0, pad)),
            -1,
        ),
        n_tasks=jnp.int32(B),
        res_sub=jnp.full(B * R, EMPTY, jnp.int32),
        res_count=jnp.zeros(B, jnp.int32),
        needs_host=jnp.zeros(B, dtype=jnp.int32),
        step=jnp.int32(0),
        stats=empty_launch_stats(),
    )

    def step_fn(st: _SubState) -> _SubState:
        idx = jnp.arange(F, dtype=jnp.int32)
        q, obj, rel, depth = st.t_q, st.t_obj, st.t_rel, st.t_depth
        live = (idx < st.n_tasks) & (st.needs_host[q] == 0)

        prog = program_lookup(tables, obj, rel, live, n_config_rels=NCR)
        flagged = flag_phase(
            tables, obj, rel, live, n_config_rels=NCR, island_is_host=True,
            prog=prog,
        )
        needs_host = st.needs_host.at[q].max(flagged)
        _ns, has_prog, pid, _flags = prog

        # instruction lanes (COMPUTED / TTU), exactly like check
        ipack = _isolate(tables["instr_pack"][pid]).reshape(F, K, 4)
        ik = jnp.where(has_prog[:, None], ipack[..., 0], 0)
        ir = ipack[..., 1]
        ir2 = ipack[..., 2]

        # full-CSR spans for slot 0 (the task's own row: plain-subject
        # emission + subject-set children) and the TTU rows
        rels = jnp.concatenate([rel[:, None], ir], axis=1)  # [F, S]
        spans = _multi_pair_key_probe(
            tables, "fsh", obj, rels, fsh_probes, n_vals=2
        )
        starts = spans[..., 0]
        row_len = jnp.where(starts < 0, 0, spans[..., 1] - starts)

        can_row = live & (depth >= 1)
        is_comp = (ik == INSTR_COMPUTED) & can_row[:, None]
        is_ttu = (ik == INSTR_TTU) & can_row[:, None]

        if has_delta:
            dirty_vals = _multi_pair_key_probe(
                tables, "dirty", obj, rels, DELTA_PROBES
            )
            row_dirty = (jnp.maximum(dirty_vals, 0) & DIRTY_FOR_EXPAND) != 0
            dirty = (can_row & row_dirty[:, 0]) | jnp.any(
                is_ttu & row_dirty[:, 1:], axis=1
            )
            needs_host = needs_host.at[q].max(
                jnp.where(dirty, CAUSE_DIRTY, 0).astype(jnp.int32)
            )

        counts = jnp.concatenate(
            [
                jnp.where(can_row, row_len[:, 0], 0)[:, None],
                jnp.where(is_comp, 1, jnp.where(is_ttu, row_len[:, 1:], 0)),
            ],
            axis=1,
        )
        slot_kind = jnp.concatenate(
            [
                jnp.zeros((F, 1), jnp.int32),
                jnp.where(is_comp, 1, jnp.where(is_ttu, 2, 0)),
            ],
            axis=1,
        )

        flat_counts = counts.reshape(-1)
        offsets = jnp.cumsum(flat_counts) - flat_counts
        total = offsets[-1] + flat_counts[-1]
        truncated = (offsets + flat_counts) > F
        seg_q = jnp.repeat(q, S, total_repeat_length=F * S)
        needs_host = needs_host.at[seg_q].max(
            jnp.where(
                truncated & (flat_counts > 0), CAUSE_FRONTIER_OVERFLOW, 0
            ).astype(jnp.int32)
        )

        seg, j = covering_segments(offsets, flat_counts, F)
        in_range = j < jnp.minimum(total, F)

        srcmat = jnp.stack(
            [
                jnp.broadcast_to(q[:, None], (F, S)),
                jnp.broadcast_to(obj[:, None], (F, S)),
                jnp.broadcast_to(depth[:, None], (F, S)),
                starts,
                slot_kind,
                jnp.concatenate(
                    [
                        jnp.zeros((F, 1), jnp.int32),
                        # instruction child relation: COMPUTED swaps to
                        # ir at the same depth, TTU children carry ir2
                        jnp.where(ik == INSTR_COMPUTED, ir, ir2),
                    ],
                    axis=1,
                ),
                offsets.reshape(F, S),
                jnp.zeros((F, S), jnp.int32),
            ],
            axis=-1,
        ).reshape(F * S, 8)
        src = _isolate(srcmat[seg])
        src_q = src[:, 0]
        src_obj = src[:, 1]
        src_depth = src[:, 2]
        src_start = src[:, 3]
        src_kind = src[:, 4]
        src_crel = src[:, 5]
        within = j - src[:, 6]

        e = jnp.clip(src_start + within, 0, max(n_edges - 1, 0))
        if n_edges:
            ep = _isolate(tables["fe_pack"][e])  # (skind, sa, sb, 0)
            e_skind, e_sa, e_sb = ep[:, 0], ep[:, 1], ep[:, 2]
        else:
            e_skind = jnp.zeros(F, jnp.int32)
            e_sa = jnp.zeros(F, jnp.int32)
            e_sb = jnp.zeros(F, jnp.int32)

        is_row = src_kind == 0
        is_c = src_kind == 1
        is_t = src_kind == 2

        # result emission: plain-subject edges of the task's own row (the
        # batched analog of the direct probe hitting at depth >= 1)
        emit = in_range & is_row & (e_skind == 0)
        alloc = _bump_emit(src_q, emit, st.res_count, F, B)
        res_over = emit & (alloc >= R)
        needs_host = needs_host.at[src_q].max(
            jnp.where(res_over, CAUSE_FRONTIER_OVERFLOW, 0).astype(jnp.int32)
        )
        emit = emit & ~res_over
        dest = jnp.where(emit, src_q * R + alloc, B * R)
        res_sub = st.res_sub.at[dest].set(e_sa, mode="drop")
        res_count = st.res_count.at[src_q].add(emit.astype(jnp.int32))

        # children: subject-set edges (slot 0: their own sb relation,
        # wildcard-filtered like check; TTU rows: the instruction's
        # rel2) + COMPUTED relation swaps at the same depth
        child_obj = jnp.where(is_c, src_obj, e_sa)
        child_rel = jnp.where(is_row, e_sb, src_crel)
        child_depth = jnp.where(is_c, src_depth, src_depth - 1)
        cond = jnp.where(
            is_row,
            (e_skind == 1) & (e_sb != wildcard_rel),
            is_c | (e_skind == 1),
        )
        children = Expansion(
            q=src_q, ctx=src_q, obj=child_obj, rel=child_rel,
            depth=child_depth,
            valid=in_range & cond & (child_depth >= 1),
        )
        nt_q, _nt_ctx, nt_obj, nt_rel, nt_depth, n_new, overflow_q = (
            dedupe_phase(children, F, B)
        )
        needs_host = jnp.maximum(needs_host, overflow_q)
        stats = update_launch_stats(
            st.stats,
            st.n_tasks,
            (live & (depth >= 0)).sum(),
            emit.sum(),
            children.valid.sum(),
            n_new,
        )
        return _SubState(
            nt_q, nt_obj, nt_rel, nt_depth, n_new,
            res_sub, res_count, needs_host, st.step + 1, stats,
        )

    def cond_fn(st: _SubState):
        return (
            (st.step < max_steps)
            & (st.n_tasks > 0)
            & ~jnp.all(st.needs_host > 0)
        )

    final = bounded_loop(cond_fn, step_fn, init, max_steps)
    exhausted = (final.step >= max_steps) & (final.n_tasks > 0)
    live = jnp.arange(F, dtype=jnp.int32) < final.n_tasks
    needs_host = final.needs_host.at[final.t_q].max(
        jnp.where(exhausted & live, CAUSE_STEP_EXHAUSTED, 0).astype(jnp.int32)
    )
    return final.res_sub, final.res_count, needs_host, final.stats


@functools.partial(
    jax.jit, static_argnames=_SUBJECTS_STATICS + ("pool_cap",)
)
@jax.named_scope("keto.reverse")
def list_subjects_kernel_packed(
    tables: dict,
    qpack: jnp.ndarray,  # [4, B] int32: obj, rel, depth, valid
    *,
    K: int,
    fsh_probes: int,
    max_steps: int,
    wildcard_rel: int,
    n_config_rels: int,
    frontier_cap: int,
    result_cap: int,
    pool_cap: int,
    has_delta: bool,
):
    """Packed twin of list_objects_kernel_packed for the subjects leg:
    [ offsets (B+1) | needs_host (B) | stats (N_LAUNCH_STATS) |
    pool (pool_cap) ] of plain subject ids (revisit duplicates possible;
    host dedupes)."""
    B = qpack.shape[1]
    R = result_cap
    res_sub, res_count, needs_host, stats = _list_subjects_impl(
        tables,
        qpack[0], qpack[1], qpack[2], qpack[3].astype(bool),
        K=K, fsh_probes=fsh_probes, max_steps=max_steps,
        wildcard_rel=wildcard_rel, n_config_rels=n_config_rels,
        frontier_cap=frontier_cap, result_cap=result_cap,
        has_delta=has_delta,
    )
    counts = jnp.clip(res_count, 0, R)
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    j = jnp.arange(pool_cap, dtype=jnp.int32)
    seg = jnp.searchsorted(offs[1:], j, side="right").astype(jnp.int32)
    seg_c = jnp.clip(seg, 0, B - 1)
    within = j - offs[seg_c]
    valid = (j < offs[B]) & (seg < B)
    src = jnp.clip(seg_c * R + within, 0, B * R - 1)
    pool = jnp.where(valid, res_sub[src], EMPTY)
    needs_host = jnp.maximum(
        needs_host,
        jnp.where(
            (offs[1:] > pool_cap) & (counts > 0), CAUSE_FRONTIER_OVERFLOW, 0
        ).astype(jnp.int32),
    )
    offs = jnp.minimum(offs, pool_cap)
    return jnp.concatenate([offs, needs_host, stats.astype(jnp.int32), pool])


def decode_pool_slice(pool: np.ndarray, lo: int, hi: int) -> list[int]:
    """Ordered, deduplicated ids from one query's pool span (a node
    revisited at a deeper depth in a later step re-emits)."""
    seen: set[int] = set()
    out: list[int] = []
    for v in pool[lo:hi].tolist():
        if v in seen:
            continue
        seen.add(v)
        out.append(v)
    return out
