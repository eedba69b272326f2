"""Shared-frontier BatchFilter kernel: one subject, one candidate column.

Zanzibar's dominant production workload is search-result filtering — "of
these 10,000 candidate documents, which can this user see?" — which the
check path prices as 10k independent BFS walks. This kernel exploits
what that batch shape shares: ONE subject. It expands the subject's
reverse-reachable set ONCE (the same transposed-mirror walk the
ListObjects kernel runs, engine/reverse_kernel.py) and intersects every
frontier node against the whole candidate column instead of re-walking
per object — the TrieJax/GraphBLAS view of the join: frontier expansion
is a batched sparse gather, and the candidate intersection is one
binary search per visited node into the sorted candidate slot array.

Contract (the reverse kernel's discipline, applied to one walk):

  - seeds: the reverse-seed CSR row for the subject's exact key — the
    nodes whose direct probe the forward check kernel would hit; seeds
    enter at depth-1 (checkDirect runs at restDepth-1).
  - per step, each frontier task (obj, rel, depth):
      1. flag_phase on the visited node (island / host-only /
         config-missing / relation-not-found programs) + reverse-dirty
         overlay probe — any flag poisons the WHOLE walk's cause code:
         the walk is shared, so the engine host-replays every candidate
         the closure fast path did not already resolve. POISON inverted
         instructions (AND-island leaf relations) flag the same way —
         mirroring the reverse kernel's POISON discipline.
      2. candidate intersection: a task whose relation matches the
         query relation at depth >= 0 marks its object slot in the hit
         mask (searchsorted into the sorted candidate column — one
         [F]-wide binary search, no per-candidate work).
      3. predecessor expansion over the reverse-edge CSR + inverted
         instructions, identical to the ListObjects kernel.
      4. dedupe on (obj, rel) keeping the deepest remaining depth.
  - a CLEAN walk (cause 0) that drains its frontier is COMPLETE: hits
    are IS_MEMBER, unmarked candidates are definitive NOT_MEMBER —
    exactly the set the host oracle's N independent checks would admit.
  - any NOT in the config disables the device path entirely
    (snapshot.build_reverse_programs host_all, enforced by the engine
    before launch): NOT-members exist precisely where no path exists,
    which reachability cannot enumerate.

Packed single-buffer I/O like every other kernel: ONE int32 upload
[sa, tag, rel, depth, n_cand, cand_slots(C)] (candidates sorted
ascending, padded with INT32_MAX sentinels that no real slot equals)
and ONE readback [hit(C), cause(1), stats(N_LAUNCH_STATS)] with the
launch-stats vector riding the same transfer.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import (
    CAUSE_DIRTY,
    CAUSE_FRONTIER_OVERFLOW,
    CAUSE_ISLAND_HOST,
    CAUSE_STEP_EXHAUSTED,
    Expansion,
    N_LAUNCH_STATS,
    _isolate,
    bounded_loop,
    covering_segments,
    dedupe_phase,
    empty_launch_stats,
    flag_phase,
    program_lookup,
    update_launch_stats,
)
from .reverse_kernel import _rd_lookup, _span_probe
from .snapshot import RINSTR_COMPUTED, RINSTR_POISON, RINSTR_TTU

# sorted-candidate padding sentinel: real object slots are int32 node
# keys bounded far below this (extract-time overflow gates), so a
# frontier object can never equal it and padded lanes never match
CAND_PAD = np.int32(2**31 - 1)


class _FilterState(NamedTuple):
    t_obj: jnp.ndarray  # [F]
    t_rel: jnp.ndarray  # [F]
    t_depth: jnp.ndarray  # [F] remaining depth
    n_tasks: jnp.ndarray
    hit: jnp.ndarray  # [C] bool per candidate slot
    cause: jnp.ndarray  # scalar int32 CAUSE_* (0 = walk clean so far)
    step: jnp.ndarray
    stats: jnp.ndarray  # [N_LAUNCH_STATS]


_FILTER_STATICS = (
    "rvh_probes", "rsh_probes", "RK", "max_steps", "wildcard_rel",
    "n_config_rels", "frontier_cap", "has_delta",
)


def _filter_impl(
    tables: dict,
    q_sa: jnp.ndarray,  # scalar: subject id / subject-set object slot
    q_tag: jnp.ndarray,  # scalar: reverse_subject_tag of the subject
    q_rel: jnp.ndarray,  # scalar: target relation id
    q_depth: jnp.ndarray,  # scalar: clamped max depth
    n_cand: jnp.ndarray,  # scalar: real candidates (<= C)
    cand: jnp.ndarray,  # [C] sorted unique candidate object slots
    *,
    rvh_probes: int,
    rsh_probes: int,
    RK: int,
    max_steps: int,
    wildcard_rel: int,
    n_config_rels: int,
    frontier_cap: int,
    has_delta: bool,
):
    """Returns (hit [C] bool, cause scalar, stats)."""
    F = frontier_cap
    C = cand.shape[0]
    S = 1 + RK
    n_redges = tables["rv_pack"].shape[0]
    n_sedges = tables["rs_pack"].shape[0]
    NCR = max(n_config_rels, 1)

    # -- seed: the reverse-seed CSR row for the subject key -------------------
    s_start, s_len = _span_probe(
        tables, "rsh", q_sa[None], q_tag[None], rsh_probes
    )
    s_start, s_len = s_start[0], s_len[0]
    cause = jnp.int32(0)
    if has_delta:
        # the subject's direct-edge set changed since the base snapshot:
        # the seed row is stale either way (insert or tombstone)
        cause = jnp.where(
            _rd_lookup(tables, q_sa[None], q_tag[None])[0] != 0,
            CAUSE_DIRTY, cause,
        )
    cause = jnp.maximum(
        cause,
        jnp.where(s_len > F, CAUSE_FRONTIER_OVERFLOW, 0).astype(jnp.int32),
    )
    j = jnp.arange(F, dtype=jnp.int32)
    in_range = j < jnp.minimum(s_len, F)
    e = jnp.clip(s_start + j, 0, max(n_sedges - 1, 0))
    if n_sedges:
        sp = _isolate(tables["rs_pack"][e])  # [F, 2] = (obj, rel)
        seed_obj, seed_rel = sp[:, 0], sp[:, 1]
    else:
        seed_obj = jnp.zeros(F, jnp.int32)
        seed_rel = jnp.zeros(F, jnp.int32)
    init = _FilterState(
        t_obj=jnp.where(in_range, seed_obj, 0),
        t_rel=jnp.where(in_range, seed_rel, 0),
        # a direct hit consumes one depth unit (checkDirect runs at
        # restDepth-1), so seeds enter at D-1; marking requires >= 0
        t_depth=jnp.where(in_range, q_depth - 1, -1),
        n_tasks=jnp.minimum(s_len, F).astype(jnp.int32),
        hit=jnp.zeros(C, dtype=bool),
        cause=cause,
        step=jnp.int32(0),
        stats=empty_launch_stats(),
    )

    def step_fn(st: _FilterState) -> _FilterState:
        idx = jnp.arange(F, dtype=jnp.int32)
        obj, rel, depth = st.t_obj, st.t_rel, st.t_depth
        live = idx < st.n_tasks

        # 1. visited-node flags (same codes + exclusivity as check);
        # the walk is shared, so any per-task flag poisons the scalar
        prog = program_lookup(tables, obj, rel, live, n_config_rels=NCR)
        ns_t = prog[0]
        flagged = flag_phase(
            tables, obj, rel, live, n_config_rels=NCR, island_is_host=True,
            prog=prog,
        )
        cause = jnp.maximum(st.cause, flagged.max())
        if has_delta:
            zero = jnp.zeros_like(obj)
            row_dirty = live & (_rd_lookup(tables, obj, zero) != 0)
            cause = jnp.maximum(
                cause, jnp.where(row_dirty.any(), CAUSE_DIRTY, 0)
            )

        # 2. candidate intersection: one binary search per task into the
        # sorted candidate column; matching tasks scatter their slot's
        # hit bit (C stays on device — no per-candidate host work)
        match = live & (rel == q_rel) & (depth >= 0)
        pos = jnp.searchsorted(cand, obj).astype(jnp.int32)
        pos_c = jnp.clip(pos, 0, C - 1)
        found = match & (cand[pos_c] == obj)
        marks = found.astype(jnp.int32).sum()
        hit = st.hit.at[jnp.where(found, pos_c, C)].set(True, mode="drop")

        # 3. predecessor expansion (the ListObjects kernel's, single
        # query): reverse-edge CSR row keyed by the task's object slot
        zero = jnp.zeros_like(obj)
        rstart, rlen = _span_probe(tables, "rvh", obj, zero, rvh_probes)

        has_ri = live & (rel < NCR)
        ripack = _isolate(
            tables["rinstr_pack"][jnp.where(has_ri, rel, 0)]
        ).reshape(F, RK, 4)
        rik = jnp.where(has_ri[:, None], ripack[..., 0], 0)
        rip = ripack[..., 1]
        rit = ripack[..., 2]
        rin = ripack[..., 3]

        # POISON: an AND-island program pulls from this relation — its
        # members are not pure-OR-enumerable, so the walk goes to host
        poison = live & jnp.any(
            (rik == RINSTR_POISON) & ((rin == -1) | (rin == ns_t[:, None])),
            axis=1,
        )
        cause = jnp.maximum(
            cause, jnp.where(poison.any(), CAUSE_ISLAND_HOST, 0)
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
        cause = jnp.maximum(
            cause,
            jnp.where(
                (truncated & (flat_counts > 0)).any(),
                CAUSE_FRONTIER_OVERFLOW, 0,
            ),
        )

        seg, j2 = covering_segments(offsets, flat_counts, F)
        in_range = j2 < jnp.minimum(total, F)

        # ONE [F, 16] row-gather of the stacked per-(task, slot) source
        # matrix (same gather-volume lever as check's expand_phase)
        srcmat = jnp.stack(
            [
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
                    for _ in range(7)
                ),  # pad to a 16-lane (64 B) gather row
            ],
            axis=-1,
        ).reshape(F * S, 16)
        src = _isolate(srcmat[seg])
        src_obj = src[:, 0]
        src_rel = src[:, 1]
        src_depth = src[:, 2]
        src_start = src[:, 3]
        src_kind = src[:, 4]
        src_relp = src[:, 5]
        src_relt = src[:, 6]
        src_ns = src[:, 7]
        within = j2 - src[:, 8]

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
        zq = jnp.zeros(F, jnp.int32)
        children = Expansion(
            q=zq, ctx=zq, obj=child_obj, rel=child_rel,
            depth=child_depth, valid=in_range & cond,
        )
        _nt_q, _nt_ctx, nt_obj, nt_rel, nt_depth, n_new, overflow_q = (
            dedupe_phase(children, F, 1)
        )
        cause = jnp.maximum(cause, overflow_q[0])
        stats = update_launch_stats(
            st.stats,
            st.n_tasks,
            (live & (depth >= 0)).sum(),
            marks,
            children.valid.sum(),
            n_new,
        )
        return _FilterState(
            nt_obj, nt_rel, nt_depth, n_new,
            hit, cause, st.step + 1, stats,
        )

    def cond_fn(st: _FilterState):
        # a flagged walk stops early (the engine host-replays anyway);
        # an all-candidates-hit walk stops early too — the remaining
        # frontier can only re-confirm positives
        ci = jnp.arange(C, dtype=jnp.int32)
        all_hit = jnp.all(st.hit | (ci >= n_cand))
        return (
            (st.step < max_steps)
            & (st.n_tasks > 0)
            & (st.cause == 0)
            & ~all_hit
        )

    final = bounded_loop(cond_fn, step_fn, init, max_steps)
    # step budget ran out with live tasks and unmarked candidates: the
    # walk did NOT finish — unmarked candidates cannot be trusted as
    # negatives (host replay). All-hit exhaustion is complete.
    ci = jnp.arange(C, dtype=jnp.int32)
    all_hit = jnp.all(final.hit | (ci >= n_cand))
    exhausted = (
        (final.step >= max_steps) & (final.n_tasks > 0) & ~all_hit
    )
    cause = jnp.maximum(
        final.cause,
        jnp.where(exhausted, CAUSE_STEP_EXHAUSTED, 0).astype(jnp.int32),
    )
    return final.hit, cause, final.stats


@functools.partial(jax.jit, static_argnames=_FILTER_STATICS)
@jax.named_scope("keto.filter")
def filter_kernel_packed(
    tables: dict,
    qcpack: jnp.ndarray,  # [5 + C] int32: sa, tag, rel, depth, n_cand, cand
    *,
    rvh_probes: int,
    rsh_probes: int,
    RK: int,
    max_steps: int,
    wildcard_rel: int,
    n_config_rels: int,
    frontier_cap: int,
    has_delta: bool,
):
    """Single-buffer I/O: ONE int32 upload (query scalars + the sorted
    candidate column) and ONE int32 readback
    [ hit (C) | cause (1) | stats (N_LAUNCH_STATS) ]."""
    hit, cause, stats = _filter_impl(
        tables,
        qcpack[0], qcpack[1], qcpack[2], qcpack[3], qcpack[4], qcpack[5:],
        rvh_probes=rvh_probes, rsh_probes=rsh_probes, RK=RK,
        max_steps=max_steps, wildcard_rel=wildcard_rel,
        n_config_rels=n_config_rels, frontier_cap=frontier_cap,
        has_delta=has_delta,
    )
    return jnp.concatenate([
        hit.astype(jnp.int32),
        cause[None].astype(jnp.int32),
        stats.astype(jnp.int32),
    ])


def pack_filter_query(
    sa: int, tag: int, rel: int, depth: int, cand_sorted: np.ndarray,
    C: int,
) -> np.ndarray:
    """Host-side twin of filter_kernel_packed's input layout: the
    candidate column padded to the static width C with CAND_PAD
    sentinels (sorted order preserved — no real slot reaches it)."""
    n = len(cand_sorted)
    pad = np.full(C, CAND_PAD, dtype=np.int32)
    pad[:n] = np.asarray(cand_sorted, dtype=np.int32)
    head = np.array([sa, tag, rel, depth, n], dtype=np.int32)
    return np.concatenate([head, pad])


def unpack_filter_results(flat: np.ndarray, C: int):
    """(hit[C] bool, cause int, stats[N_LAUNCH_STATS]) views of
    filter_kernel_packed's result vector."""
    hit = flat[:C].astype(bool)
    cause = int(flat[C])
    stats = flat[C + 1 : C + 1 + N_LAUNCH_STATS]
    return hit, cause, stats
