"""SPMD multi-chip expand kernel: shard_map over the 1-D device mesh.

Same BFS-subgraph-gather semantics as the single-chip expand kernel
(engine/expand_kernel.py) with the full-edge CSR sharded by object slot
(the check tables' partition, parallel/sharding.build_sharded_full_csr)
and three collectives:

  - `psum` of per-task row lengths each step (a row lives on exactly one
    shard, so summing the per-shard lengths yields the global count —
    every shard then derives the IDENTICAL edge-buffer allocation)
  - `all_gather` of per-shard candidate children before the shared
    dedupe (as in the check kernel)
  - ONE `psum` of the edge buffers after the loop: each buffer slot is
    written by exactly the owning shard (values carried +1 so the empty
    sentinel stays EMPTY = sum(0s) - 1), so the merge is a single
    all-reduce instead of per-step traffic

The frontier, per-query counters, and needs_host masks stay replicated —
every device runs the identical merged state, so the loop's trip count
agrees across the mesh.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.delta import DIRTY_FOR_EXPAND
from ..engine.expand_kernel import _ExpandState
from ..engine.kernel import (
    Expansion,
    _pair_key_probe,
    bounded_loop,
    dedupe_phase,
    dirty_lookup,
    empty_launch_stats,
    update_launch_stats,
)
from ..engine.snapshot import EMPTY
from .sharding import _EXPAND_SHARDED_KEYS

_kernel_cache: dict = {}
_kernel_cache_lock = threading.Lock()
_KERNEL_CACHE_CAP = 8


def _build_kernel(mesh: Mesh, axis: str, statics: tuple):
    fh_probes, max_steps, frontier_cap, edge_cap = statics
    F = frontier_cap
    E = edge_cap

    @jax.named_scope("keto.expand")
    def run(shard_tabs, rep_tabs, q_obj, q_rel, q_depth, q_valid):
        tables = {k: v[0] for k, v in shard_tabs.items()}
        tables.update(rep_tabs)
        B = q_obj.shape[0]
        n_edges = tables["f_skind"].shape[0]
        n_rows = tables["f_row_ptr"].shape[0] - 1

        def row_span(row):
            row_c = jnp.clip(row, 0, n_rows)
            start = tables["f_row_ptr"][row_c]
            end = tables["f_row_ptr"][jnp.minimum(row_c + 1, n_rows)]
            start = jnp.where(row == EMPTY, 0, start)
            length = jnp.where(row == EMPTY, 0, end - start)
            return start, length

        def row_lookup(obj, rel):
            return _pair_key_probe(tables, "fh", obj, rel, fh_probes)

        root_row = row_lookup(q_obj, q_rel)
        _, root_len_local = row_span(root_row)
        root_len = jax.lax.psum(root_len_local, axis)
        root_has_children = (root_len > 0) & q_valid

        # dirty roots: replicated delta tables, identical per shard
        init_needs_host = q_valid & (
            (dirty_lookup(tables, q_obj, q_rel) & DIRTY_FOR_EXPAND) != 0
        )

        def step_fn(st: _ExpandState) -> _ExpandState:
            idx = jnp.arange(F, dtype=jnp.int32)
            live = (idx < st.n_tasks) & ~st.needs_host[st.t_q]
            q, obj, rel, depth = st.t_q, st.t_obj, st.t_rel, st.t_depth

            row = row_lookup(obj, rel)
            start, length_local = row_span(row)
            owned = length_local > 0  # the owner shard (or an empty row)
            # global row length: exactly one shard contributes
            length = jax.lax.psum(length_local, axis)
            emit = live & (depth >= 2)
            task_dirty = emit & (
                (dirty_lookup(tables, obj, rel) & DIRTY_FOR_EXPAND) != 0
            )
            needs_host_d = st.needs_host.at[q].max(task_dirty)
            emit = emit & ~task_dirty
            counts = jnp.where(emit, length, 0)  # REPLICATED

            # per-query bump allocation over the replicated counts: every
            # shard computes the identical global slot assignment
            order = jnp.argsort(q + jnp.where(live, 0, B))
            sq = q[order]
            scounts = counts[order]
            cum = jnp.cumsum(scounts) - scounts
            seg_first = jnp.concatenate(
                [jnp.ones(1, dtype=bool), sq[1:] != sq[:-1]]
            )
            seg_base = jnp.where(seg_first, cum, 0)
            seg_base = jax.lax.associative_scan(jnp.maximum, seg_base)
            within_q = cum - seg_base
            alloc = st.eb_count[sq] + within_q
            inv = jnp.zeros(F, dtype=jnp.int32).at[order].set(
                jnp.arange(F, dtype=jnp.int32)
            )
            alloc_t = alloc[inv]

            overflow = emit & ((alloc_t + counts) > E)
            needs_host = needs_host_d.at[q].max(overflow)
            emit = emit & ~overflow

            # segmented emission work list over the GLOBAL offsets; only
            # the owning shard writes content for its rows
            flat_counts = jnp.where(emit, counts, 0)
            offsets = jnp.cumsum(flat_counts) - flat_counts
            total = offsets[-1] + flat_counts[-1]
            j = jnp.arange(F * 4, dtype=jnp.int32)
            seg = jnp.searchsorted(offsets, j, side="right").astype(jnp.int32) - 1
            seg = jnp.clip(seg, 0, F - 1)
            within = j - offsets[seg]
            in_range = j < jnp.minimum(total, F * 4)
            local = owned[seg]  # this shard owns the row's content
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
            write = in_range & emit[seg]
            dest = jnp.where(
                write & local, dest_q * E + alloc_t[seg] + within, B * E
            )
            # +1-carried values: the final cross-shard psum restores them
            # (slots default 0; exactly one shard writes each slot)
            eb_pobj = st.eb_pobj.at[dest].set(obj[seg] + 1, mode="drop")
            eb_prel = st.eb_prel.at[dest].set(rel[seg] + 1, mode="drop")
            eb_skind = st.eb_skind.at[dest].set(c_skind + 1, mode="drop")
            eb_sa = st.eb_sa.at[dest].set(c_sa + 1, mode="drop")
            eb_sb = st.eb_sb.at[dest].set(c_sb + 1, mode="drop")
            # replicated count update (derived from replicated values)
            eb_count = st.eb_count.at[dest_q].add(
                jnp.where(write, 1, 0), mode="drop"
            )
            trunc = (offsets + flat_counts) > F * 4
            needs_host = needs_host.at[q].max(emit & trunc)

            # next frontier: local subject-set children -> all_gather
            child_depth = depth[seg] - 1
            cand_valid = (
                write & local & (c_skind == 1) & (child_depth >= 2)
            )
            children_local = Expansion(
                q=dest_q, ctx=dest_q, obj=c_sa, rel=c_sb,
                depth=child_depth, valid=cand_valid,
            )
            gathered = Expansion(
                *(
                    jax.lax.all_gather(part, axis).reshape(-1)
                    for part in children_local
                )
            )
            nt_q, _nt_ctx, nt_obj, nt_rel, nt_depth, n_new, overflow_q = (
                dedupe_phase(gathered, F, B)
            )
            # dedupe reports int32 cause codes (shared with the check
            # kernel); the expand state keeps a boolean flag
            needs_host = needs_host | (overflow_q > 0)
            # launch counters: `write` and the dedupe output are derived
            # from REPLICATED values, so the stats vector stays identical
            # on every shard (sound under the replicated out_spec)
            stats = update_launch_stats(
                st.stats,
                st.n_tasks,
                (live & (depth >= 0)).sum(),
                jnp.int32(0),
                write.sum(),
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
            eb_pobj=jnp.zeros(B * E, jnp.int32),
            eb_prel=jnp.zeros(B * E, jnp.int32),
            eb_skind=jnp.zeros(B * E, jnp.int32),
            eb_sa=jnp.zeros(B * E, jnp.int32),
            eb_sb=jnp.zeros(B * E, jnp.int32),
            eb_count=jnp.zeros(B, jnp.int32),
            needs_host=init_needs_host,
            step=jnp.int32(0),
            stats=empty_launch_stats(),
        )

        def cond_fn(st: _ExpandState):
            return (st.step < max_steps) & (st.n_tasks > 0)

        # the predicate is replicated, so all shards branch together
        # and step_fn's collectives stay aligned
        final = bounded_loop(cond_fn, step_fn, init, max_steps)
        # single merge: each slot was written (value+1) by its owner only
        merged = [
            jax.lax.psum(a, axis) - 1
            for a in (
                final.eb_pobj, final.eb_prel, final.eb_skind,
                final.eb_sa, final.eb_sb,
            )
        ]
        return (
            *merged, final.eb_count, root_has_children, final.needs_host,
            final.stats,
        )

    mapped = shard_map(
        run,
        mesh=mesh,
        in_specs=(P(axis), P(), P(), P(), P(), P()),
        out_specs=tuple([P()] * 9),
        check_vma=False,
    )
    return jax.jit(mapped)


def get_sharded_expand_kernel(mesh: Mesh, statics: tuple, axis: str = "x"):
    key = (mesh, axis, statics)
    with _kernel_cache_lock:
        fn = _kernel_cache.pop(key, None)
        if fn is None:
            fn = _build_kernel(mesh, axis, statics)
            while len(_kernel_cache) >= _KERNEL_CACHE_CAP:
                _kernel_cache.pop(next(iter(_kernel_cache)))
        _kernel_cache[key] = fn
    return fn


def place_sharded_expand_tables(
    stacked: dict, delta_np: dict, mesh: Mesh, axis: str = "x"
) -> tuple[dict, dict]:
    from ..engine.kernel import (
        device_table,
        pack_delta_tables,
        pack_pair_table,
    )
    from .kernel import stack_shard_packs

    assert set(stacked) == set(_EXPAND_SHARDED_KEYS)
    fh_pack = stack_shard_packs(
        stacked["fh_obj"].shape[0],
        lambda i: pack_pair_table(
            stacked["fh_obj"][i], stacked["fh_rel"][i], stacked["fh_row"][i]
        ),
    )
    raw = {
        "fh_pack": fh_pack,
        "f_row_ptr": stacked["f_row_ptr"],
        "f_skind": stacked["f_skind"],
        "f_sa": stacked["f_sa"],
        "f_sb": stacked["f_sb"],
    }
    sharded = {
        k: device_table(
            v, NamedSharding(mesh, P(axis, *([None] * (v.ndim - 1))))
        )
        for k, v in raw.items()
    }
    replicated = {
        "dirty_pack": device_table(
            pack_delta_tables(delta_np)["dirty_pack"],
            NamedSharding(mesh, P()),
        )
    }
    return sharded, replicated


def sharded_expand_kernel(
    mesh: Mesh,
    sharded_tables: dict,
    replicated_tables: dict,
    q_obj, q_rel, q_depth, q_valid,
    *,
    fh_probes: int,
    max_steps: int,
    frontier_cap: int,
    edge_cap: int,
    axis: str = "x",
):
    fn = get_sharded_expand_kernel(
        mesh, (fh_probes, max_steps, frontier_cap, edge_cap), axis
    )
    return fn(sharded_tables, replicated_tables, q_obj, q_rel, q_depth, q_valid)
