"""SPMD multi-chip check kernel: shard_map over a 1-D device mesh.

Same BFS semantics as the single-chip kernel (engine/kernel.py) — the
step phases are shared code — with the edge tables sharded by object
slot and two ICI collectives per step:

  - `psum` OR-merge of per-shard direct-probe hits (a direct edge lives
    on exactly one shard, the one owning its object slot)
  - `all_gather` of per-shard candidate children before the dedupe (a
    task's CSR row lives on one shard; other shards contribute nothing)

The frontier and per-query result masks stay replicated: every device
runs the identical merged state, so the BFS loop's trip count agrees
across the mesh and the host reads back one copy. This mirrors the
scaling-book recipe — pick a mesh, shard the big arrays, let collectives
ride ICI — rather than the reference's shared-SQL-database fan-out
(SURVEY.md §2.11).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.kernel import (
    Expansion,
    _State,
    dedupe_phase,
    expand_phase,
    finalize,
    flag_phase,
    kernel_static_config,
    probe_phase,
    program_lookup,
    run_bfs_loop,
    seed_state,
    update_launch_stats,
)
from .sharding import (
    ShardedSnapshot,
    _DELTA_DEVICE_KEYS,
    _REPLICATED_KEYS,
    _SHARDED_DEVICE_KEYS,
)

# compiled-executable cache; statics change as the graph grows (probe
# counts track hash-table clustering), so bound it LRU-style — older
# snapshots' kernels are never called again. Guarded by a lock: the
# engine facade serves concurrent check_batch calls.
_kernel_cache: dict = {}
_kernel_cache_lock = threading.Lock()
_KERNEL_CACHE_CAP = 8


def _build_kernel(mesh: Mesh, axis: str, statics: tuple):
    (
        K, dh_probes, rh_probes, max_steps,
        wildcard_rel, n_config_rels, frontier_cap,
        n_island_cap, has_delta,
    ) = statics
    F = frontier_cap

    @jax.named_scope("keto.check")
    def run(shard_tabs, rep_tabs, q_obj, q_rel, q_depth, q_skind, q_sa, q_sb, q_valid):
        tables = {k: v[0] for k, v in shard_tabs.items()}
        tables.update(rep_tabs)
        B = q_obj.shape[0]
        qsub = jnp.stack(
            [q_skind, q_sa, q_sb, jnp.zeros_like(q_skind)], axis=-1
        )  # [B, 4]: one packed row-gather per step (see engine kernel)

        def step_fn(st: _State) -> _State:
            idx = jnp.arange(F, dtype=jnp.int32)
            q = st.t_q
            ctx = st.t_ctx
            root_done = st.ctx_hit[:B] | (st.needs_host > 0)
            live = (idx < st.n_tasks) & ~root_done[q] & ~st.ctx_hit[ctx]
            obj, rel, depth = st.t_obj, st.t_rel, st.t_depth

            # flags depend only on replicated tables: identical everywhere
            prog = program_lookup(
                tables, obj, rel, live, n_config_rels=n_config_rels
            )
            flagged = flag_phase(
                tables, obj, rel, live,
                n_config_rels=n_config_rels,
                island_is_host=(n_island_cap == 0),
                prog=prog,
            )
            sub = jax.lax.optimization_barrier(qsub[q])  # [F, 4]
            hit_local = probe_phase(
                tables, obj, rel, sub[:, 0], sub[:, 1], sub[:, 2], depth,
                live, dh_probes=dh_probes, has_delta=has_delta,
            )
            # a direct edge lives on exactly one shard: OR-merge the hits
            hit = jax.lax.psum(hit_local.astype(jnp.int32), axis) > 0
            ctx_hit = st.ctx_hit.at[ctx].max(hit)
            needs_host = st.needs_host.at[q].max(flagged)
            live = live & ~(ctx_hit[:B] | (needs_host > 0))[q] & ~ctx_hit[ctx]

            # island allocation inside expand_phase is a pure function of
            # the REPLICATED frontier + program tables, so every shard
            # derives the identical island table and leaf-ctx assignment
            # with no collective
            children, overflow_q, isl_state = expand_phase(
                tables, q, ctx, obj, rel, depth, live,
                (st.isl_parent, st.isl_pid, st.n_isl),
                K=K, rh_probes=rh_probes, n_config_rels=n_config_rels,
                wildcard_rel=wildcard_rel, n_queries=B,
                n_island_cap=n_island_cap, has_delta=has_delta, prog=prog,
            )
            # per-shard expansions differ (CSR rows are shard-local), so
            # the cause codes merge with pmax — same priority semantics
            # as the single-chip maximum
            needs_host = jnp.maximum(
                needs_host, jax.lax.pmax(overflow_q, axis)
            )

            # merge candidate frontiers: [ndev, F] -> [ndev * F]
            gathered = Expansion(
                *(
                    jax.lax.all_gather(part, axis).reshape(-1)
                    for part in children
                )
            )
            nt_q, nt_ctx, nt_obj, nt_rel, nt_depth, n_new, overflow2 = dedupe_phase(
                gathered, F, B
            )
            needs_host = jnp.maximum(needs_host, overflow2)
            # launch counters: every operand is REPLICATED (post-psum
            # hit, the all-gathered candidate set, the shared dedupe
            # output), so the stats vector stays identical on all shards
            # and the replicated out_spec is sound
            stats = update_launch_stats(
                st.stats,
                st.n_tasks,
                (live & (depth >= 0)).sum(),
                hit.sum(),
                gathered.valid.sum(),
                n_new,
            )
            return _State(
                nt_q, nt_ctx, nt_obj, nt_rel, nt_depth, n_new,
                ctx_hit, needs_host, *isl_state, st.step + 1, stats,
            )

        # engine/kernel.bounded_loop via run_bfs_loop: the trip decision
        # is a pure function of the REPLICATED state, so every shard
        # takes the same branch and the collectives inside step_fn stay
        # aligned across the mesh.
        init = seed_state(q_obj, q_rel, q_depth, q_valid, F, n_island_cap, K)
        final = run_bfs_loop(step_fn, init, max_steps, B)
        return finalize(final, max_steps, B)

    mapped = shard_map(
        run,
        mesh=mesh,
        in_specs=(P(axis), P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


def get_sharded_kernel(mesh: Mesh, statics: tuple, axis: str = "x"):
    key = (mesh, axis, statics)
    with _kernel_cache_lock:
        fn = _kernel_cache.pop(key, None)
        if fn is None:
            fn = _build_kernel(mesh, axis, statics)
            while len(_kernel_cache) >= _KERNEL_CACHE_CAP:
                _kernel_cache.pop(next(iter(_kernel_cache)))
        _kernel_cache[key] = fn  # re-insert = move to MRU position
    return fn


def sharded_static_config(
    snap: ShardedSnapshot,
    max_depth: int,
    frontier_cap: int,
    n_island_cap: int = 0,
    has_delta: bool = True,
) -> tuple:
    """Single-chip static config (one source of truth for the step-budget
    formula) with the per-shard probe maxima patched in."""
    cfg = kernel_static_config(
        snap.base, max_depth, frontier_cap,
        n_island_cap=n_island_cap, has_delta=has_delta,
    )
    cfg["dh_probes"] = snap.dh_probes
    cfg["rh_probes"] = snap.rh_probes
    return (
        cfg["K"], cfg["dh_probes"], cfg["rh_probes"], cfg["max_steps"],
        cfg["wildcard_rel"], cfg["n_config_rels"], cfg["frontier_cap"],
        cfg["n_island_cap"], cfg["has_delta"],
    )


def stack_shard_packs(n: int, pack_shard):
    """[n, *pack] stack of `pack_shard(i)` for i < n, in whatever shape
    the builder stores a pack (kernel.as_bucket_rows). Preallocates and
    packs in place: a list-of-arrays + np.stack would hold a second full
    copy of the dominant tables at peak (GBs at 1e8 edges)."""
    import numpy as np

    first = pack_shard(0)
    out = np.zeros((n, *first.shape), dtype=first.dtype)
    out[0] = first
    del first
    for i in range(1, n):
        out[i] = pack_shard(i)
    return out


def place_sharded_tables(
    snap: ShardedSnapshot, mesh: Mesh, axis: str = "x",
    release_columns: bool = False,
) -> tuple[dict, dict]:
    """Upload tables once: sharded arrays split along the mesh axis (one
    shard per device), small tables replicated. Hash tables pack into
    interleaved rows per shard (kernel.pack_edge_table layout).

    `release_columns=True` (the engine's setting) drops each raw column
    array from snap.sharded as soon as its packed form is uploaded, and
    uploads one table at a time: at 1e8 edges the raw columns + packed
    copy + device copy held simultaneously cost ~3x the table bytes and
    OOM-killed the 1e8 virtual-mesh run on a 128 GB host. The statics
    only need snap's scalar probe counts afterwards."""
    import numpy as np

    from ..engine.kernel import (
        device_table,
        device_tables,
        pack_edge_table,
        pack_rh_span_table,
    )

    s = snap.sharded
    n = s["dh_obj"].shape[0]

    def put_sharded(v):
        return device_table(
            v, NamedSharding(mesh, P(axis, *([None] * (v.ndim - 1))))
        )

    sharded = {}
    dh_pack = stack_shard_packs(n, lambda i: pack_edge_table(
        s["dh_obj"][i], s["dh_rel"][i], s["dh_skind"][i],
        s["dh_sa"][i], s["dh_sb"][i], s["dh_val"][i],
    ))
    if release_columns:
        for k in ("dh_obj", "dh_rel", "dh_skind", "dh_sa", "dh_sb", "dh_val"):
            s[k] = None
    sharded["dh_pack"] = put_sharded(dh_pack)
    del dh_pack

    # per-shard row_ptr resolves into the span lanes at pack time
    rh_pack = stack_shard_packs(n, lambda i: pack_rh_span_table(
        s["rh_obj"][i], s["rh_rel"][i], s["rh_row"][i], s["row_ptr"][i]
    ))
    if release_columns:
        for k in ("rh_obj", "rh_rel", "rh_row", "row_ptr"):
            s[k] = None
    sharded["rh_pack"] = put_sharded(rh_pack)
    del rh_pack

    e_pack = np.stack(
        [np.asarray(s["e_obj"]), np.asarray(s["e_rel"])], axis=-1
    ).astype(np.int32)
    if release_columns:
        for k in ("e_obj", "e_rel"):
            s[k] = None
    sharded["e_pack"] = put_sharded(e_pack)
    del e_pack

    replicated = device_tables(snap.replicated, NamedSharding(mesh, P()))
    return sharded, replicated


def sharded_check_kernel(
    mesh: Mesh,
    sharded_tables: dict,
    replicated_tables: dict,
    q_obj, q_rel, q_depth, q_skind, q_sa, q_sb, q_valid,
    *,
    statics: tuple,
    axis: str = "x",
):
    """Returns (ctx_hit, needs_host[B] cause codes, isl_parent, isl_pid,
    n_isl, stats); see engine/kernel.check_kernel."""
    assert set(sharded_tables) == set(_SHARDED_DEVICE_KEYS)
    assert set(replicated_tables) == set(_REPLICATED_KEYS) | set(
        _DELTA_DEVICE_KEYS
    )
    fn = get_sharded_kernel(mesh, statics, axis)
    return fn(
        sharded_tables, replicated_tables,
        q_obj, q_rel, q_depth, q_skind, q_sa, q_sb, q_valid,
    )
