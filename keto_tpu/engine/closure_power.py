"""On-device GraphBLAS closure powering — the Leopard index built where
the mirrors already live.

The host builder (engine/closure.py::power_closure) is a multi-source
level-synchronous BFS over the cost-1 folded edge CSR: exact minimum
distances, `req = dist + 1` subject entries, poison one ring past the
subject horizon, per-source row caps. That is literally sparse boolean
matrix powering (the RedisGraph/GraphBLAS formulation the index was
designed around), and numpy on the host is the wrong engine for it at
the 1e6+-tuple drive topologies — ROADMAP item 2.

This module lowers the SAME contract onto the device as bit-packed
boolean matmul:

  * The reachability frontier is a dense bit matrix `F[N, W]` — one row
    per graph node, 32 SOURCES per uint32 lane (`W = lanes/32` words),
    so one wave powers up to `lanes` sources simultaneously and a
    frontier×adjacency step is word-parallel across all of them.
  * One powering step is the boolean product new = Aᵀ·F over the
    OR-AND semiring: gather the packed frontier row of every edge's
    source, OR rows that share a destination (a segment-max over the
    unpacked bit planes — OR of bits IS max), AND-NOT against the seen
    matrix `R` so only first discoveries survive. Steps run under the
    shared `bounded_loop` with `max_steps = max_depth` — the same loop
    as every other kernel.
  * First-discovery depth bookkeeping: a per-(direct-node, source)
    level plane records the step at which each source first reached
    each direct-incidence node; `req = level + 1` reproduces the host
    builder's depth contract bit for bit (the R·D product only needs
    levels at nodes that own direct entries).
  * `closure.max_set_rows` row-cap semantics are preserved IN the loop:
    per-source reach counts accumulate from the fresh-discovery bit
    planes and over-cap sources have their frontier lanes masked off —
    exactly the host builder's stop-expanding rule. Poison (AND/NOT
    islands, relation-not-found) reads the final seen matrix against
    the host-precomputed per-node poison mask, covering the extra ring.
  * Each wave launch reads back through ONE designated sync point
    (`_closure_power_resolve`, ketolint host-sync annotated like every
    kernel's resolve): the level plane + a packed summary vector
    (per-source reach counts, poison flags, and the launch-stats vector
    riding last, as always).

The host side then finalizes exactly like `power_closure`'s tail —
R·D span expansion, min-req dedupe, `req <= max_depth` trim, entry
caps — and emits a ClosureBuild whose arrays are BIT-IDENTICAL to the
host builder's (the differential tests compare them array-for-array).
`closure.powering = "host"` (the default) keeps the numpy builder as
the fallback and the differential oracle; any device-path failure
raises and the ClosureIndex falls back to host powering for that
build, counted, never wrong.

Scale shape: the bit matrix is dense over (nodes × wave lanes), so the
wave width adapts to a scratch budget (KETO_CLOSURE_POWER_MB, default
256 MB of unpacked intermediates) and sources stream through in waves;
every wave reuses the same compiled kernel (shapes are per-build
constants). Device work per wave step is O(E·W + N·lanes) word-ops —
32-way bit-parallel over sources — vs the host's per-pair sort/merge.
"""

from __future__ import annotations

import functools
import os
import time
from typing import NamedTuple, Optional

import jax
import numpy as np
from jax import numpy as jnp

from .closure import (
    MAX_CLOSURE_NODES,
    ClosureBuild,
    ClosureGraph,
    _expand_spans,
    node_poison_keys,
    snapshot_vocab_fp,
)
from .kernel import (
    bounded_loop,
    empty_launch_stats,
    launch_stats_dict,
    update_launch_stats,
)
from .snapshot import GraphSnapshot


class PoweringUnsupported(Exception):
    """The device powering cannot honor the host contract for this
    (graph, limits) shape — the caller falls back to host powering."""


# int8 level planes: first-discovery levels go up to max_depth inclusive
# (the poison ring), so the depth clamp must fit the plane dtype
_MAX_INT8_DEPTH = 100

# wave-width floor/ceiling: lanes are uint32-bit-packed, so multiples of 32
_MIN_LANES = 32
_MAX_LANES = 8192

_BITS = tuple(range(32))


def _unpack_bits(pack: jnp.ndarray) -> jnp.ndarray:
    """[..., W] uint32 -> [..., W*32] uint8 bit planes (lane s of word w
    is source w*32+s — the one packing layout, shared with _pack_bits)."""
    bits = jnp.arange(32, dtype=jnp.uint32)
    u = (pack[..., None] >> bits) & jnp.uint32(1)
    return u.reshape(*pack.shape[:-1], pack.shape[-1] * 32).astype(jnp.uint8)


def _pack_bits(planes: jnp.ndarray) -> jnp.ndarray:
    """[..., S] 0/1 -> [..., S//32] uint32 (inverse of _unpack_bits)."""
    bits = jnp.arange(32, dtype=jnp.uint32)
    b = planes.reshape(*planes.shape[:-1], -1, 32).astype(jnp.uint32)
    return (b << bits).sum(axis=-1, dtype=jnp.uint32)


class _PState(NamedTuple):
    R: jnp.ndarray       # [N, W] uint32 — seen (reach) bit matrix
    F: jnp.ndarray       # [N, W] uint32 — current frontier bit matrix
    lvl: jnp.ndarray     # [n_dnode, S] int8 — first-discovery levels
    counts: jnp.ndarray  # [S] int32 — per-source reach size (incl. self)
    level: jnp.ndarray   # scalar int32 — BFS distance of F
    stats: jnp.ndarray   # [N_LAUNCH_STATS] int32


@functools.partial(
    jax.jit, static_argnames=("max_depth", "max_set_rows")
)
def closure_power_wave(
    e_src: jnp.ndarray,      # [E] int32 — edge source node indices
    e_dst: jnp.ndarray,      # [E] int32 — edge dest indices, SORTED by dst
    d_rows: jnp.ndarray,     # [n_dnode] int32 — direct-incidence node idx
    pois_mask: jnp.ndarray,  # [N] uint8 — host-computed per-node poison
    R0: jnp.ndarray,         # [N, W] uint32 — self bits (seen at level 0)
    lvl0: jnp.ndarray,       # [n_dnode, S] int8 — 0 at self d-nodes, -1
    counts0: jnp.ndarray,    # [S] int32 — 1 per valid lane
    *,
    max_depth: int,
    max_set_rows: int,
):
    """One powering wave: level-synchronous bit-packed boolean matmul to
    a fixpoint (or the depth budget), returning (level plane, packed
    summary = [reach counts | poison flags | launch stats])."""
    n_nodes = R0.shape[0]

    def cond_fn(st: _PState):
        return (st.level < max_depth) & jnp.any(st.F != 0)

    def step_fn(st: _PState) -> _PState:
        # frontier occupancy BEFORE the step (the stats vocabulary)
        n_tasks = jax.lax.population_count(st.F).sum(dtype=jnp.int32)
        # frontier×adjacency: gather each edge source's packed frontier
        # row, OR rows per destination. OR over bit planes is max, so
        # the segmented OR is one segment-max over the unpacked planes
        # (e_dst sorted at pack time).
        g = st.F[e_src]                                   # [E, W] uint32
        n_children = jax.lax.population_count(g).sum(dtype=jnp.int32)
        n_hits = (g != 0).any(axis=1).sum(dtype=jnp.int32)
        gu = _unpack_bits(g)                              # [E, S] uint8
        nu = jax.ops.segment_max(
            gu, e_dst, num_segments=n_nodes, indices_are_sorted=True
        )                                                 # [N, S] uint8
        # first discoveries only: AND-NOT against the seen matrix
        fresh = _pack_bits(nu) & ~st.R                    # [N, W] uint32
        freshu = _unpack_bits(fresh)                      # [N, S] uint8
        level = st.level + 1
        # depth bookkeeping at direct-incidence nodes: req = level + 1
        freshd = freshu[d_rows]                           # [n_dnode, S]
        lvl = jnp.where(
            (st.lvl < 0) & (freshd > 0), level.astype(jnp.int8), st.lvl
        )
        # per-source reach growth, then the row cap: over-cap sources
        # stop expanding (their seen rows stay — the host keeps them
        # too; coverage drops them at finalize)
        counts = st.counts + freshu.sum(axis=0, dtype=jnp.int32)
        over = counts > max_set_rows
        kill = _pack_bits(over.astype(jnp.uint8)[None, :])[0]  # [W]
        n_kept = jax.lax.population_count(fresh).sum(dtype=jnp.int32)
        stats = update_launch_stats(
            st.stats, n_tasks, n_tasks, n_hits, n_children, n_kept
        )
        return _PState(
            R=st.R | fresh,
            F=fresh & ~kill[None, :],
            lvl=lvl,
            counts=counts,
            level=level,
            stats=stats,
        )

    init = _PState(
        R=R0, F=R0, lvl=lvl0, counts=counts0,
        level=jnp.int32(0), stats=empty_launch_stats(),
    )
    final = bounded_loop(cond_fn, step_fn, init, max_depth)
    # poison over the whole seen matrix — the loop ran one ring past the
    # subject horizon, exactly like the host builder
    seen_u = _unpack_bits(final.R)                        # [N, S] uint8
    pois = jnp.where(
        pois_mask[:, None] > 0, seen_u, jnp.uint8(0)
    ).max(axis=0).astype(jnp.int32)                       # [S]
    summary = jnp.concatenate(
        [final.counts, pois, final.stats]
    )
    return final.lvl, summary


def _closure_power_resolve(outputs):
    """Synchronize one powering wave: the launch's single designated
    readback carries the level plane, the per-source summary, and the
    launch-stats vector in one transfer (the same one-sync resolve
    contract as every other kernel; ketolint's host-sync pass pins it)."""
    # ketolint: allow[host-sync] reason=this IS the powering wave's designated sync point: one packed readback carries the first-discovery level plane, per-source reach/poison summary, and the launch stats vector — the single-transfer resolve contract every kernel rides
    lvl, summary = jax.device_get(outputs)
    return lvl, summary


def _power_budget_bytes() -> int:
    """Unpacked-scratch budget per wave: the dominant intermediates are
    the per-edge gathered planes [E, S] and two [N, S] node planes, all
    uint8 — one byte per (row, lane). A wave whose component-restricted
    subgraph times its lane count exceeds this is bisected."""
    return int(os.environ.get("KETO_CLOSURE_POWER_MB", "256")) << 20


def _next_pow2(n: int, floor: int) -> int:
    """Shape quantum: padding every wave's (nodes, edges, d-nodes,
    lanes) up to powers of two bounds the number of DISTINCT compiled
    kernel shapes at log2 of the largest — waves re-use compilations
    instead of retracing per subgraph."""
    cap = max(int(n), floor)
    return 1 << (cap - 1).bit_length()


def _components(n_nodes: int, e_src: np.ndarray,
                e_dst: np.ndarray) -> np.ndarray:
    """Weakly-connected component label (min node index in the
    component) per node, by vectorized min-label propagation with
    pointer jumping — O(E) per round, O(log N) rounds. Reachability
    never leaves a weak component, so a powering wave only needs the
    induced subgraph of its sources' components: THE restriction that
    keeps the dense bit matrix proportional to what the wave can
    actually reach instead of the whole graph (1e6-node topologies are
    unions of small components; a global dense plane would be 1e12
    bit-cells)."""
    label = np.arange(n_nodes, dtype=np.int64)
    if len(e_src) == 0:
        return label
    while True:
        before = label
        m = np.minimum(label[e_src], label[e_dst])
        label = label.copy()
        np.minimum.at(label, e_src, m)
        np.minimum.at(label, e_dst, m)
        label = np.minimum(label, label[label])
        label = label[label]
        if np.array_equal(label, before):
            return label


def estimate_power_bytes(
    n_nodes: int, n_edges: int, n_dnode: int, lanes: int
) -> dict:
    """Device-buffer accounting for one powering wave (the
    hbm_snapshot `closure_power` family and the flight-recorder entry):
    packed adjacency operands, the resident bit matrices, and the
    transient unpacked scratch the step materializes."""
    words = lanes // 32
    return {
        # per-edge index arrays + direct rows + poison mask: the packed
        # adjacency the matmul runs against
        "adjacency_pack": 4 * (2 * n_edges + n_dnode) + n_nodes,
        # R + F packed bit matrices, plus the level plane
        "bit_matrix": 2 * n_nodes * words * 4 + n_dnode * lanes,
        # unpacked uint8 intermediates per step (gather + segment planes)
        "scratch": (n_edges + 2 * n_nodes) * lanes,
    }


def power_closure_device(
    graph: ClosureGraph,
    snapshot: GraphSnapshot,
    max_depth: int,
    max_set_rows: int,
    base_version: int,
    sources: Optional[np.ndarray] = None,
    flightrec=None,
    nid: str = "",
) -> tuple[ClosureBuild, dict]:
    """`power_closure` lowered onto the device: same signature-shaped
    inputs, same ClosureBuild output, bit-identical arrays. Returns
    (build, record) where record carries the wave/step/HBM accounting
    the index folds into its stats and hbm_snapshot. Raises
    PoweringUnsupported when the contract cannot be honored on device
    (the caller falls back to host powering)."""
    t0 = time.perf_counter()
    if int(max_depth) > _MAX_INT8_DEPTH:
        raise PoweringUnsupported(
            f"max_depth {max_depth} exceeds the int8 level plane"
        )
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
    record = {
        "waves": 0, "steps": 0, "lanes": 0, "nodes": 0, "edges": 0,
        "hbm": {"adjacency_pack": 0, "bit_matrix": 0, "scratch": 0},
    }
    if n_src == 0:
        build.build_s = time.perf_counter() - t0
        record["build_s"] = build.build_s
        return build, record

    # -- host prepack: node universe, dst-sorted edge index arrays ---------
    all_keys = np.unique(np.concatenate([
        srcs, graph.e_src_keys, graph.e_dst, graph.d_node_keys,
    ]))
    n_nodes = len(all_keys)
    if n_nodes > MAX_CLOSURE_NODES:
        raise PoweringUnsupported(f"{n_nodes} nodes exceeds the node cap")
    e_counts = np.diff(graph.e_ptr)
    e_src = np.repeat(
        np.searchsorted(all_keys, graph.e_src_keys), e_counts
    ).astype(np.int32)
    e_dst = np.searchsorted(all_keys, graph.e_dst).astype(np.int32)
    order = np.argsort(e_dst, kind="stable")
    e_src, e_dst = e_src[order], e_dst[order]
    d_rows = np.searchsorted(all_keys, graph.d_node_keys).astype(np.int32)
    d_counts = np.diff(graph.d_ptr)
    pois_mask = node_poison_keys(graph, all_keys).astype(np.uint8)
    src_node = np.searchsorted(all_keys, srcs).astype(np.int32)
    n_dnode = len(d_rows)
    n_edges = len(e_src)

    comp = _components(n_nodes, e_src, e_dst)
    budget = _power_budget_bytes()
    record.update(nodes=n_nodes, edges=n_edges)

    from ..observability import next_launch_id

    uncovered = np.zeros(n_src, dtype=bool)
    parts: list[tuple] = []
    hbm_hw = {"adjacency_pack": 0, "bit_matrix": 0, "scratch": 0}

    def run_range(s: int, e: int) -> None:
        """Power sources [s, e): build the induced subgraph of their
        weak components (reachability cannot leave one), quantize its
        shape, and launch — bisecting the range when the unpacked
        scratch would blow the budget. Ranges stay contiguous in source
        INDEX order, so the per-wave entry blocks concatenate into the
        host builder's global p_src-major order exactly."""
        nl = e - s
        lanes = _next_pow2(nl, _MIN_LANES)
        wave_comps = np.unique(comp[src_node[s:e]])
        nmask = np.isin(comp, wave_comps)
        nodes_sel = np.flatnonzero(nmask)
        n_sub = len(nodes_sel)
        remap = np.full(n_nodes, -1, dtype=np.int32)
        remap[nodes_sel] = np.arange(n_sub, dtype=np.int32)
        # an edge's endpoints share a weak component: one endpoint test
        # selects whole edges
        emask = nmask[e_src]
        n_esub = int(emask.sum())
        dmask = nmask[d_rows]
        d_sel = np.flatnonzero(dmask)
        n_dsub = len(d_sel)
        # the dummy node rides at index n_sub: padded edges and d-rows
        # point at it; it owns no self bits, no poison, no entries
        Nq = _next_pow2(n_sub + 1, 2)
        Eq = _next_pow2(n_esub, 1)
        Dq = _next_pow2(n_dsub, 1)
        if (Eq + 2 * Nq + Dq) * lanes > budget and nl > _MIN_LANES:
            mid = s + (((nl + 1) // 2 + 31) // 32) * 32
            run_range(s, mid)
            run_range(mid, e)
            return
        dummy = np.int32(n_sub)
        we_src = np.full(Eq, dummy, dtype=np.int32)
        we_dst = np.full(Eq, dummy, dtype=np.int32)
        we_src[:n_esub] = remap[e_src[emask]]
        # remap is monotone over node index and the dummy is the max
        # index, so the filtered+padded dst array STAYS sorted — the
        # segment-max's indices_are_sorted contract holds per wave
        we_dst[:n_esub] = remap[e_dst[emask]]
        wd_rows = np.full(Dq, dummy, dtype=np.int32)
        wd_rows[:n_dsub] = remap[d_rows[dmask]]
        wpois = np.zeros(Nq, dtype=np.uint8)
        wpois[:n_sub] = pois_mask[nodes_sel]
        words = lanes // 32
        lane_ids = np.arange(nl)
        # self bits: source s (lane l) has seen its own node at level 0
        R0 = np.zeros((Nq, words), dtype=np.uint32)
        np.bitwise_or.at(
            R0,
            (remap[src_node[s:e]], lane_ids // 32),
            (np.uint32(1) << (lane_ids % 32).astype(np.uint32)),
        )
        lvl0 = np.full((Dq, lanes), -1, dtype=np.int8)
        if n_dsub:
            sub_dkeys = graph.d_node_keys[d_sel]
            dpos = np.searchsorted(sub_dkeys, srcs[s:e])
            dpos_c = np.clip(dpos, 0, n_dsub - 1)
            at_d = sub_dkeys[dpos_c] == srcs[s:e]
            lvl0[dpos_c[at_d], lane_ids[at_d]] = 0
        counts0 = np.zeros(lanes, dtype=np.int32)
        counts0[:nl] = 1
        hbm = estimate_power_bytes(Nq, Eq, Dq, lanes)
        for k, v in hbm.items():
            hbm_hw[k] = max(hbm_hw[k], v)
        record["lanes"] = max(record["lanes"], lanes)

        launch_id = next_launch_id()
        outputs = closure_power_wave(
            jnp.asarray(we_src), jnp.asarray(we_dst),
            jnp.asarray(wd_rows), jnp.asarray(wpois),
            jnp.asarray(R0), jnp.asarray(lvl0), jnp.asarray(counts0),
            max_depth=int(max_depth), max_set_rows=int(max_set_rows),
        )
        lvl, summary = _closure_power_resolve(outputs)
        counts = summary[:lanes]
        pois = summary[lanes:2 * lanes]
        stats = summary[2 * lanes:]
        record["waves"] += 1
        record["steps"] += int(stats[0])
        if flightrec is not None and flightrec.enabled:
            flightrec.record({
                "launch_id": launch_id,
                "kind": "closure_power",
                "nid": nid,
                "bucket": lanes,
                "n": nl,
                "occupancy": round(nl / lanes, 4),
                "wave_nodes": n_sub,
                "wave_edges": n_esub,
                "adjacency_bytes": hbm["adjacency_pack"],
                "scratch_bytes": hbm["bit_matrix"] + hbm["scratch"],
                **launch_stats_dict(stats),
            })

        # reach-cap + poison uncoverage, exactly the host's predicates
        uncovered[s:e] |= (counts[:nl] > max_set_rows) | (pois[:nl] > 0)
        # R·D product for this wave: levels >= 0 are first discoveries;
        # entries need req = level + 1 <= max_depth (the extra ring only
        # feeds poison). Expansion over each direct node's entry span +
        # min-req dedupe mirror power_closure's tail bit for bit.
        if n_dsub:
            dn, lane = np.nonzero(
                (lvl[:n_dsub, :nl] >= 0)
                & (lvl[:n_dsub, :nl] + 1 <= max_depth)
            )
        else:
            dn = lane = np.zeros(0, dtype=np.int64)
        if len(dn):
            gdn = d_sel[dn]
            req = lvl[dn, lane].astype(np.int32) + 1
            pos = _expand_spans(graph.d_ptr[gdn], d_counts[gdn])
            p_src = np.repeat(s + lane, d_counts[gdn])
            p_req = np.repeat(req, d_counts[gdn])
            p_skind = graph.d_skind[pos]
            p_sa = graph.d_sa[pos]
            p_sb = graph.d_sb[pos]
            # dedupe (src, subject triple) keeping MIN req — lexsort with
            # req fastest, first-of-group wins (== the host builder)
            sort = np.lexsort((p_req, p_sb, p_sa, p_skind, p_src))
            p_src, p_req = p_src[sort], p_req[sort]
            p_skind, p_sa, p_sb = p_skind[sort], p_sa[sort], p_sb[sort]
            first = np.ones(len(p_src), dtype=bool)
            first[1:] = ~(
                (p_src[1:] == p_src[:-1])
                & (p_skind[1:] == p_skind[:-1])
                & (p_sa[1:] == p_sa[:-1])
                & (p_sb[1:] == p_sb[:-1])
            )
            p_src, p_req = p_src[first], p_req[first]
            p_skind, p_sa, p_sb = p_skind[first], p_sa[first], p_sb[first]
            per_src = np.bincount(p_src, minlength=n_src)
            uncovered[:] |= per_src > max_set_rows
            parts.append((p_src, p_req, p_skind, p_sa, p_sb))

    for base in range(0, n_src, _MAX_LANES):
        run_range(base, min(base + _MAX_LANES, n_src))
    record["hbm"] = hbm_hw

    if parts:
        p_src = np.concatenate([p[0] for p in parts])
        p_req = np.concatenate([p[1] for p in parts])
        p_skind = np.concatenate([p[2] for p in parts])
        p_sa = np.concatenate([p[3] for p in parts])
        p_sb = np.concatenate([p[4] for p in parts])
    else:
        p_src = np.zeros(0, np.int64)
        p_req = np.zeros(0, np.int32)
        p_skind = p_sa = p_sb = np.zeros(0, np.int32)

    covered_keys = srcs[np.flatnonzero(~uncovered)]
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
    record["build_s"] = build.build_s
    return build, record
