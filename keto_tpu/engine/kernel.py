"""Batched BFS check kernel (single device) + shared step phases.

The TPU replacement for the reference's goroutine-per-branch recursive
walk (internal/check/engine.go:183-207 + checkgroup): all branches of all
in-flight checks advance together as one frontier of tasks
(query, object-slot, relation, remaining-depth), inside one bounded loop
(bounded_loop) with static shapes:

  per step:
    1. flag tasks whose (ns, rel) program needs host evaluation (AND/NOT
       islands, missing relation config — engine.go:219-228)
    2. direct-probe every task against the edge hash table (the batched
       analog of checkDirect's single-row SELECT) and OR hits into the
       per-query member mask (short-circuit = per-query done-mask)
    3. expand every task: subject-set CSR row (checkExpandSubject), plus
       its compiled rewrite instructions (COMPUTED relation swap at the
       SAME depth, rewrites.go:161-193; TTU row traversal at depth-1,
       rewrites.go:195-260); expansion counts → exclusive scan →
       vectorized segmented gather into the next frontier
    4. dedupe the next frontier on (query, object, relation) keeping the
       deepest remaining-depth instance (safe: more depth explores more)

TPU-specific gather discipline (measured, tools/microbench2.py): a
row-gather from a 2-D table moves its whole row for roughly the cost of
one element (~15ns/row on v5e), while N per-column gathers pay N times.
So every hash table lives on device as PACKED interleaved slots —
8 lanes for the 5-key edge tables, 4 for (obj, rel)->value — stored and
placed as the 64-lane bucket rows a probe fetches ([cap/spb, spb*w]:
as_bucket_rows, device_table), so no program relays a table out before
it gathers; each logical lookup is ONE [F, PB, row]-shaped row-gather, fenced
with optimization_barrier so XLA emits its fast standalone gather
kernel instead of scalarizing it inside a fusion. All probe rounds/
slots batch into one wide trailing index dim per lookup.

The phases are factored as standalone functions so the sharded multi-chip
kernel (keto_tpu/parallel/kernel.py) can interleave them with mesh
collectives: probe hits are psum-OR-merged across edge shards and local
expansions are all-gathered before the shared dedupe.

Depth bookkeeping matches the reference exactly: direct probes need
depth ≥ 1 (restDepth-1 ≥ 0), expand-subject and TTU children are enqueued
at depth-1 (only when ≥ 0), computed children keep their depth.

Tasks touching host-only programs (AND/NOT islands), config-missing
relations, delta-dirty rows, or overflowing the frontier raise the
per-query needs_host flag; the engine facade re-runs those queries on the
exact host engine.

All arrays int32/uint32/bool — no 64-bit emulation on TPU.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from .delta import DELTA_PROBES, DIRTY_FOR_CHECK, empty_delta_tables
from .snapshot import (
    EMPTY,
    FLAG_CONFIG_MISSING,
    FLAG_HOST_ONLY,
    FLAG_ISLAND,
    INSTR_COMPUTED,
    INSTR_NONE,
    INSTR_TTU,
    GraphSnapshot,
    slots_per_bucket,
)

_GOLDEN = jnp.uint32(0x9E3779B9)

# Host-replay cause codes, priority-ordered (VERDICT r2 item 7: a host
# fallback because of an AND/NOT cap must be distinguishable from one
# because of an error). Each flag site scatter-maxes its code into the
# per-query needs_host array — the SAME single scatter per site as the
# old boolean scheme, so observability costs no extra device work. A
# query flagged for several reasons reports the highest code (more
# specific/semantic causes outrank capacity ones).
CAUSE_NONE = 0
CAUSE_STEP_EXHAUSTED = 1  # step budget ran out with live tasks
CAUSE_FRONTIER_OVERFLOW = 2  # expansion truncated / dedupe survivors > F
CAUSE_ISLAND_OVERFLOW = 3  # island instance table full (island_cap)
CAUSE_DIRTY = 4  # delta-dirty CSR row (stale compacted data)
CAUSE_REL_NOT_FOUND = 5  # relation missing from a configured namespace
CAUSE_CONFIG_MISSING = 6  # FLAG_CONFIG_MISSING program
CAUSE_REWRITE_CAP = 7  # FLAG_HOST_ONLY: rewrite exceeds instr/circuit caps
CAUSE_ISLAND_HOST = 8  # AND/NOT program, kernel compiled without islands

CAUSE_NAMES = {
    CAUSE_STEP_EXHAUSTED: "step_exhausted",
    CAUSE_FRONTIER_OVERFLOW: "frontier_overflow",
    CAUSE_ISLAND_OVERFLOW: "island_overflow",
    CAUSE_DIRTY: "dirty_row",
    CAUSE_REL_NOT_FOUND: "relation_not_found",
    CAUSE_CONFIG_MISSING: "config_missing",
    CAUSE_REWRITE_CAP: "rewrite_cap",
    CAUSE_ISLAND_HOST: "island_host",
}
# host-side-only cause (query vocabulary never reached the device)
CAUSE_NAME_UNINDEXED = "unindexed"

# -- launch introspection counters ---------------------------------------------
# Every BFS kernel (check, sharded check, expand, reverse) accumulates a
# small int32 stats vector inside its bounded loop and appends it to the
# packed result, so the counters ride the batch's EXISTING resolve-phase
# readback — zero extra host syncs (ketolint's host-sync pass still sees
# exactly one annotated sync point per batch). Slot layout is shared so
# the flight recorder (observability.FlightRecorder) and the bench
# summaries can treat every launch kind uniformly; kernels that have no
# value for a slot leave it zero.
N_LAUNCH_STATS = 8
STAT_STEPS = 0          # loop iterations actually executed (vs the cap)
STAT_FRONTIER_SUM = 1   # sum of n_tasks over executed steps (task-steps)
STAT_FRONTIER_MAX = 2   # max n_tasks over executed steps
STAT_LIVE_SUM = 3       # sum of genuinely-live tasks (excludes bucket
                        # padding: seeded invalid queries sit at depth -1)
STAT_PROBE_HITS = 4     # direct-edge probe hits accumulated (check only)
STAT_EDGE_ROWS = 5      # candidate rows materially gathered (valid
                        # expansion children / emitted expand edges)
STAT_DEDUPE_KEPT = 6    # dedupe survivors admitted to the next frontier
STAT_RESERVED = 7

STAT_NAMES = (
    "steps", "frontier_sum", "frontier_max", "live_sum",
    "probe_hits", "edge_rows", "dedupe_kept", "reserved",
)


def empty_launch_stats():
    return jnp.zeros(N_LAUNCH_STATS, dtype=jnp.int32)


def update_launch_stats(
    stats: jnp.ndarray,
    n_tasks: jnp.ndarray,
    n_live: jnp.ndarray,
    n_hits: jnp.ndarray,
    n_children: jnp.ndarray,
    n_kept: jnp.ndarray,
) -> jnp.ndarray:
    """One step's counter accumulation (shared by the single-device and
    sharded check kernels so both report identical semantics). All
    operands must be REPLICATED values on a mesh — the sharded caller
    passes post-collective quantities only."""
    inc = jnp.stack([
        jnp.int32(1),
        n_tasks.astype(jnp.int32),
        jnp.int32(0),
        n_live.astype(jnp.int32),
        n_hits.astype(jnp.int32),
        n_children.astype(jnp.int32),
        n_kept.astype(jnp.int32),
        jnp.int32(0),
    ])
    return (stats + inc).at[STAT_FRONTIER_MAX].max(n_tasks.astype(jnp.int32))


def launch_stats_dict(stats) -> dict:
    """Host-side view of a stats vector as named fields (entry payload
    for the flight recorder and the bench aggregates)."""
    vals = [int(v) for v in stats]
    return {
        name: vals[i]
        for i, name in enumerate(STAT_NAMES)
        if name != "reserved"
    }


def estimate_step_gather_bytes(cfg: dict) -> int:
    """Estimated bytes the check kernel's gather sites move in ONE BFS
    step, from the launch's static config. The hot gathers are DENSE over
    the frontier cap (padding rows gather like live ones — that is the
    measured cost model, tools/microbench_gather_layout.py: one bucket
    row = one 256 B gather regardless of occupancy), so the estimate is
    exact up to XLA fusion choices and scales with frontier_cap and the
    probe depths, which themselves grow with table load. Multiply by
    STAT_STEPS for a launch's total; the resolve path records it in the
    flight-recorder entry."""
    F = int(cfg["frontier_cap"])
    K = int(cfg["K"])
    S = K + 1
    has_delta = bool(cfg.get("has_delta", True))
    bucket_row = 256  # every bucket is one 256 B gather row (snapshot.py)

    def pb(probes: int, spb: int) -> int:
        return (int(probes) + spb - 1) // spb

    b = F * 16                                  # qsub packed subject rows
    b += F * pb(cfg["dh_probes"], 8) * bucket_row       # dh edge probe
    b += F * S * pb(cfg["rh_probes"], 16) * bucket_row  # rh span probe
    if has_delta:
        b += F * pb(DELTA_PROBES, 8) * bucket_row       # dd overlay probe
        b += F * S * pb(DELTA_PROBES, 16) * bucket_row  # dirty-row probe
    b += F * K * 16                             # instruction row lanes
    b += F * 32                                 # srcmat [F, 8] rows
    b += F * 8                                  # e_pack (obj, rel) rows
    b += 2 * F * 16                             # dedupe winner + key rows
    return b


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _hash_combine(*parts: jnp.ndarray) -> jnp.ndarray:
    shape = jnp.broadcast_shapes(*(jnp.shape(p) for p in parts))
    h = jnp.full(shape, _GOLDEN, dtype=jnp.uint32)
    for p in parts:
        h = _mix32(h ^ p.astype(jnp.uint32))
    return h


def _isolate(x: jnp.ndarray) -> jnp.ndarray:
    """Fence a gather from surrounding fusions: XLA TPU emits a fast
    standalone gather kernel, but a gather fused into a loop fusion
    scalarizes (measured ~6x slower on v5e, tools/microbench2.py)."""
    (x,) = jax.lax.optimization_barrier((x,))
    return x


@jax.named_scope("keto.bucket_rows")
def _bucket_rows(pack: jnp.ndarray, h1: jnp.ndarray, h2: jnp.ndarray,
                 probes: int, spb: int) -> jnp.ndarray:
    """Gather every table row a probe chain of `probes` slots can touch,
    as BUCKET rows: the device twin of snapshot.probe_slot's bucketized
    sequence. `pack` is stored as those bucket rows, [cap/spb, spb*w]
    (as_bucket_rows), so the gather indexes its operand as it lies in
    memory and no launch relays a table out; slots j = 0..probes-1 live
    in buckets (h1 + (j//spb)*h2) mod (cap/spb), spb consecutive slots
    each, so PB = ceil(probes/spb) bucket-row gathers of 64 ints (256 B)
    cover the chain. Returns [..., PB*spb, w] slot rows (leading dims =
    h1's shape).

    `spb` MUST be snapshot.slots_per_bucket(n_key_cols) for the probed
    table — each probe helper passes it from the same single source the
    builders key off, so a future table with a new (width, key-count)
    pairing cannot silently probe a different sequence than it was built
    with.

    This is the gather-volume lever (tools/microbench_gather_layout.py:
    a gathered row costs ~the same at any width 32-256 B, and adjacent
    rows do NOT coalesce): one spb-slot bucket row per spb probe slots
    instead of one slot row per probe — the dominant per-step cost
    divides by ~min(probes, spb)."""
    nb, row = pack.shape
    w = row // spb
    PB = (probes + spb - 1) // spb
    jb = jnp.arange(PB, dtype=jnp.uint32)
    bidx = ((h1[..., None] + jb * h2[..., None]) & jnp.uint32(nb - 1)).astype(
        jnp.int32
    )  # [..., PB]
    rows = _isolate(pack[bidx])  # [..., PB, spb*w]
    return rows.reshape(*h1.shape, PB * spb, w)


def _edge_key_probe(tables, prefix, obj, rel, skind, sa, sb, probes: int,
                    key=None):
    """Probe a 5-key edge hash table stored as PACKED slots
    (obj, rel, skind, sa, sb, val, pad, pad), 8 to a bucket row of
    `{prefix}_pack[cap/8, 64]`, fetched as [F, PB, 64] bucket rows
    (_bucket_rows) — ONE gathered row per 8 slots of probe depth, the
    measured round-5 cost lever.

    Matching compares WHOLE rows against a [F, 8] key matrix (lanes >= 5
    auto-pass; the value rides lane 5 of the same masked reduce), which
    keeps the match+value computation in fused elementwise+reduce form.
    Comparing the full bucket (up to PB*8 slots, possibly beyond the
    exact probe limit) is safe: a slot either holds a different full key
    (never matches) or OUR key placed by the builder inside its own
    chain — extra compared slots can only confirm true membership.
    `key` lets a caller probing two tables with the same key (main +
    delta overlay) build the matrix once. Returns (found[F], value[F])."""
    h1 = _hash_combine(obj, rel, skind, sa, sb)
    h2 = _mix32(h1 ^ _GOLDEN) | jnp.uint32(1)
    rows = _bucket_rows(
        tables[f"{prefix}_pack"], h1, h2, probes, slots_per_bucket(5)
    )  # [F, PB*8, 8]
    if key is None:
        key = edge_probe_key(obj, rel, skind, sa, sb)
    lane = jnp.arange(8, dtype=jnp.int32)
    match = jnp.all((rows == key[:, None, :]) | (lane >= 5), axis=-1)
    found = jnp.any(match, axis=-1)
    # lane-5 extraction rides the same fused reduce (EMPTY = -1 < values)
    val = jnp.max(
        jnp.where(match[:, :, None] & (lane == 5), rows, EMPTY), axis=(1, 2)
    )
    return found, val


def edge_probe_key(obj, rel, skind, sa, sb) -> jnp.ndarray:
    """[F, 8] whole-row key matrix for _edge_key_probe (pad lanes 0)."""
    z = jnp.zeros_like(obj)
    return jnp.stack([obj, rel, skind, sa, sb, z, z, z], axis=-1)


def _multi_pair_key_probe(tables, prefix, obj, rels, probes: int,
                          n_vals: int = 1):
    """Probe a (obj, rel)-keyed packed table `{prefix}_pack[cap/16, 64]`
    of (obj, rel, val, val2/pad) slots for MANY relations per task at once.
    `rels` is a [F, S] relation matrix; returns the [F, S] value matrix
    (EMPTY = miss), or with `n_vals=2` a [F, S, 2] matrix carrying BOTH
    value lanes (the rh span table stores (row_start, row_end) so the
    CSR row lookup needs zero extra gathers — both extractions reduce
    over the SAME gathered bucket rows). Each (task, slot) chain rides
    PB = ceil(probes/8) bucket-row gathers ([F, S, PB, 32] via
    _bucket_rows) — the gather count is S*PB rows per task, the
    dominant per-step cost (ablate_step.py)."""
    F, S = rels.shape
    h1 = _hash_combine(obj[:, None], rels)  # [F, S]
    h2 = _mix32(h1 ^ _GOLDEN) | jnp.uint32(1)
    rows = _bucket_rows(
        tables[f"{prefix}_pack"], h1, h2, probes, slots_per_bucket(2)
    )
    # rows: [F, S, PB*8, 4]
    z = jnp.zeros_like(rels)
    key = jnp.stack(
        [jnp.broadcast_to(obj[:, None], rels.shape), rels, z, z], axis=-1
    )  # [F, S, 4]
    lane = jnp.arange(4, dtype=jnp.int32)
    match = jnp.all((rows == key[:, :, None, :]) | (lane >= 2), axis=-1)
    # value extraction through the same masked reduce (EMPTY = -1 floor)
    masked = jnp.where(match[..., None], rows, EMPTY)  # [F, S, PB*8, 4]
    if n_vals == 1:
        return jnp.max(
            jnp.where(lane == 2, masked, EMPTY), axis=(-1, -2)
        )  # [F, S]
    vals = jnp.max(masked, axis=-2)  # [F, S, 4] per-lane winners
    return vals[..., 2 : 2 + n_vals]  # [F, S, n_vals]


def _pair_key_probe(tables, prefix, obj, rel, probes: int):
    """Single-relation probe of a (obj, rel)-keyed table -> value or EMPTY."""
    return _multi_pair_key_probe(tables, prefix, obj, rel[:, None], probes)[:, 0]


def dirty_lookup(tables, obj, rel):
    """Dirty-row bitmask for (obj, rel), 0 when the row is clean."""
    val = _pair_key_probe(tables, "dirty", obj, rel, DELTA_PROBES)
    return jnp.maximum(val, 0)


BUCKET_ROW_BYTES = 256  # one gathered bucket row (snapshot.slots_per_bucket)


def as_bucket_rows(slots: np.ndarray, n_key_cols: int) -> np.ndarray:
    """THE stored shape of a hash-probed table: [cap, w] slot rows seen
    as the [cap/spb, spb*w] bucket rows _bucket_rows gathers (64 lanes,
    256 B).
    Row-major, so this is a view and the slot order is probe_slot's.
    Every probed `*_pack` goes through here on its way to the device —
    a table stored one way and probed another cannot be built."""
    cap, w = slots.shape
    spb = slots_per_bucket(n_key_cols)
    return slots.reshape(cap // spb, spb * w)


def bucket_row_layout(shape, dtype):
    """How a table of this shape has to lie on the device: row-major
    (a jax Layout) where its rows are whole 256 B bucket rows, which the
    kernels gather one row at a time; None for narrower tables, whose
    few columns are read as columns and keep the client's choice."""
    if len(shape) < 2 or shape[-1] * np.dtype(dtype).itemsize < BUCKET_ROW_BYTES:
        return None
    return Layout(major_to_minor=tuple(range(len(shape))))


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def tiled_nbytes(shape, dtype) -> int:
    """Bytes a table of this shape takes on the chip, by arithmetic on
    its shape, dtype and layout alone, so the CPU gives the chip's answer.
    HBM is tiled: the minor dimension in multiples of 128 lanes and the
    next in sublanes of 8 (of 1, 2 or 4 under a table of so few columns);
    a long vector in tiles of 1,024. Bucket rows lie row-major
    (bucket_row_layout), so a 64-lane int32 row is padded to 128 lanes:
    512 B for its 256. A narrower two-dimensional table the client lays
    column-major, rows along the lanes, and only their count is padded."""
    item = np.dtype(dtype).itemsize
    if len(shape) == 0:
        return item
    if len(shape) == 1:
        return item * _round_up(shape[0], 1024 if shape[0] >= 1024 else 128)
    if bucket_row_layout(shape, dtype) is not None:
        *major, sub, lanes = shape
        sub = _round_up(sub, 8)
    elif len(shape) == 2:
        major, (lanes, sub) = (), shape
        sub = _round_up(sub, 8) if sub > 4 else 1 << (sub - 1).bit_length()
    else:
        return item * int(np.prod(shape))  # no table has this shape
    return item * int(np.prod(major, dtype=np.int64)) * sub * _round_up(lanes, 128)


def table_nbytes(table) -> int:
    """tiled_nbytes of a device table, every device's shard or copy of it
    counted: what the devices hold of it."""
    sharding = table.sharding
    shard = sharding.shard_shape(table.shape)
    return len(sharding.device_set) * tiled_nbytes(shard, table.dtype)


UPLOAD_ROWS = 1 << 15  # bucket rows a step of device_table: 8 MB of int32


@functools.lru_cache(maxsize=None)
def _row_writer(fmt: Format):
    """The jitted step of device_table for tables lying as `fmt`: `rows`
    written into `table` from row `start` on. The table is donated and
    comes back in the layout it came in, so the step writes in place."""

    def write_rows(table, rows, start):
        return jax.lax.dynamic_update_slice_in_dim(table, rows, start, 0)

    return jax.jit(write_rows, donate_argnums=0, out_shardings=fmt)


def device_table(host, sharding=None) -> jax.Array:
    """One host table onto the device (the default one, or as `sharding`
    says), lying the way the kernels read it (bucket_row_layout). Left to
    itself the TPU client stores a [n, 64] int32 array column-major (no
    lane padding that way), and every program that gathers bucket rows
    from it first copies the whole table back to row-major, once a
    launch. A committed array carries its layout into every jit that
    takes it, so nothing is said at the kernels.

    The device holds the table once. A transfer lays an array the
    client's way whatever is asked of it, so a table of more than
    UPLOAD_ROWS rows is not sent whole and then copied (both copies live
    until the first is dropped: 15.2 GB of a 16.9 GB chip for 13.0 GB
    of tables at 1.25e7 tuples): it is made empty where and as it will
    lie and filled UPLOAD_ROWS rows a step, each step ended before the
    next begins, so that beside the table the device holds one step's
    rows. A table sharded over a mesh is a shard's size a device and is
    placed as before."""
    if sharding is not None:
        a = jax.device_put(host, sharding)
        layout = bucket_row_layout(a.shape, a.dtype)
        return a if layout is None else jax.device_put(a, Format(layout, a.sharding))
    layout = bucket_row_layout(np.shape(host), host.dtype)
    if layout is None:
        return jnp.asarray(host)
    # the default device's sharding, as jnp.asarray would choose it
    fmt = Format(layout, jnp.zeros((), host.dtype).sharding)
    if len(host) <= UPLOAD_ROWS:
        return jax.device_put(host, fmt)
    write_rows = _row_writer(fmt)
    table = jax.jit(
        functools.partial(jnp.zeros, host.shape, host.dtype), out_shardings=fmt
    )()
    for start in range(0, len(host), UPLOAD_ROWS):
        table = write_rows(table, host[start : start + UPLOAD_ROWS], start)
        table.block_until_ready()
    return table


def device_tables(host: dict, sharding=None) -> dict:
    return {k: device_table(v, sharding) for k, v in host.items()}


def _lane_rows(width: int, cols) -> np.ndarray:
    """Interleave columns into [n, width] int32 rows (pad lanes zeroed)."""
    out = np.zeros((cols[0].shape[0], width), dtype=np.int32)
    for i, col in enumerate(cols):
        out[:, i] = col
    return out


def pack_edge_table(obj, rel, skind, sa, sb, val) -> np.ndarray:
    """Interleave six edge-table columns into 8-lane slot rows (pad
    lanes zeroed), stored as bucket rows (as_bucket_rows) — the device
    layout every 5-key probe gathers."""
    return as_bucket_rows(_lane_rows(8, (obj, rel, skind, sa, sb, val)), 5)


def pack_pair_table(obj, rel, val) -> np.ndarray:
    """Interleave three (obj, rel)->val columns into 4-lane slot rows,
    stored as bucket rows (as_bucket_rows)."""
    return as_bucket_rows(_lane_rows(4, (obj, rel, val)), 2)


def pack_row_table(a, b, c) -> np.ndarray:
    """Three columns as [n, 4] rows for a table INDEXED by row (the CSR
    edge rows rv_pack / fe_pack), never hash-probed: no bucket shape."""
    return _lane_rows(4, (a, b, c))


def pack_rh_span_table(rh_obj, rh_rel, rh_row, row_ptr) -> np.ndarray:
    """(obj, rel) -> CSR span packed as 4-lane slot rows
    (obj, rel, row_start, row_end), stored as bucket rows: resolving
    row_ptr at PACK time means the kernel's row lookup needs zero extra
    gathers — the span rides the probe's own bucket-row fetch (EMPTY
    rows pack (-1, -1))."""
    valid = rh_row != EMPTY
    if row_ptr.shape[0] >= 2:
        rc = np.clip(rh_row, 0, row_ptr.shape[0] - 2)
        start = np.where(valid, row_ptr[rc], EMPTY)
        end = np.where(valid, row_ptr[rc + 1], EMPTY)
    else:
        start = end = np.full(rh_obj.shape[0], EMPTY, dtype=np.int32)
    return as_bucket_rows(_lane_rows(4, (rh_obj, rh_rel, start, end)), 2)


def pack_instr_table(instr_kind, instr_rel, instr_rel2) -> np.ndarray:
    """Interleave the K-slot instruction columns into [NP, K*4] rows of
    (kind, rel, rel2, pad) lanes — one row-gather per task instead of
    three [F, K] gathers."""
    import numpy as _np

    NP, K = instr_kind.shape
    out = _np.zeros((NP, K, 4), dtype=_np.int32)
    out[..., 0] = instr_kind
    out[..., 1] = instr_rel
    out[..., 2] = instr_rel2
    return out.reshape(NP, K * 4)


def pack_delta_tables(delta: dict) -> dict:
    """The delta overlay's packed device tables (dd_pack + dirty_pack) —
    the ONE place the delta column-to-row layout is defined."""
    return {
        "dd_pack": pack_edge_table(
            delta["dd_obj"], delta["dd_rel"], delta["dd_skind"],
            delta["dd_sa"], delta["dd_sb"], delta["dd_val"],
        ),
        "dirty_pack": pack_pair_table(
            delta["dirty_obj"], delta["dirty_rel"], delta["dirty_val"]
        ),
        # reverse-mirror staleness (engine/reverse_kernel.py); packed
        # here so ONE delta dict serves both traversal directions
        "rd_pack": pack_pair_table(
            delta["rd_obj"], delta["rd_tag"], delta["rd_val"]
        ),
    }


class _State(NamedTuple):
    t_q: jnp.ndarray  # [F] owning query index
    t_ctx: jnp.ndarray  # [F] result accumulator id (0..B-1 = query roots)
    t_obj: jnp.ndarray  # [F] object slot
    t_rel: jnp.ndarray  # [F] relation id
    t_depth: jnp.ndarray  # [F] remaining depth
    n_tasks: jnp.ndarray  # scalar int32
    # ctx_hit[:B] is the per-query root verdict (the old `member`);
    # ctx_hit[B + i*K + k] accumulates island i's leaf-k sub-check
    ctx_hit: jnp.ndarray  # [B + NI*K] bool
    needs_host: jnp.ndarray  # [B] int32 cause code (CAUSE_*; 0 = on device)
    # island instance table (populated only when NI > 0)
    isl_parent: jnp.ndarray  # [max(NI,1)] ctx the island's result ORs into
    isl_pid: jnp.ndarray  # [max(NI,1)] program id (selects the circuit)
    n_isl: jnp.ndarray  # scalar int32
    step: jnp.ndarray  # scalar int32
    stats: jnp.ndarray  # [N_LAUNCH_STATS] launch introspection counters


class Expansion(NamedTuple):
    """Candidate children of one expansion phase (pre-dedupe)."""

    q: jnp.ndarray
    ctx: jnp.ndarray
    obj: jnp.ndarray
    rel: jnp.ndarray
    depth: jnp.ndarray
    valid: jnp.ndarray


def program_lookup(tables, obj, rel, live, *, n_config_rels: int):
    """Shared (ns, has_prog, pid, flags) lookup used by flag_phase and
    expand_phase: the two phases need the identical gathers (objslot_ns,
    prog_flags x2 before this factoring), and the step cost is
    gather-volume bound (tools/ablate_step.py), so recomputing them per
    phase was pure overhead. Pure function of replicated tables."""
    ns = tables["objslot_ns"][jnp.clip(obj, 0, None)]
    has_prog = (rel < n_config_rels) & live
    pid = jnp.where(has_prog, ns * n_config_rels + rel, 0)
    flags = jnp.where(has_prog, tables["prog_flags"][pid], 0)
    return ns, has_prog, pid, flags


@jax.named_scope("keto.flag")
def flag_phase(
    tables, obj, rel, live, *, n_config_rels: int, island_is_host: bool = False,
    prog=None,
):
    """Per-task host-replay CAUSE codes (0 = stay on device); pure
    function of replicated tables, so every shard computes the identical
    result (no collective needed). ref: engine.go:219-228
    (relation-not-found), snapshot FLAG_* bits. `island_is_host=True`
    (a kernel compiled with n_island_cap=0) routes AND/NOT programs to
    exact host replay — evaluating them with the pure-union fast path
    would silently corrupt verdicts. The per-task causes here are
    mutually exclusive by construction (a program compiles to exactly one
    of HOST_ONLY / ISLAND / plain; CONFIG_MISSING programs are never
    compiled), so one int code loses nothing vs a bitmask."""
    if prog is None:
        prog = program_lookup(tables, obj, rel, live, n_config_rels=n_config_rels)
    ns, has_prog, pid, flags = prog
    code = jnp.where((flags & FLAG_HOST_ONLY) != 0, CAUSE_REWRITE_CAP, 0)
    code = jnp.where((flags & FLAG_CONFIG_MISSING) != 0, CAUSE_CONFIG_MISSING, code)
    if island_is_host:
        code = jnp.where((flags & FLAG_ISLAND) != 0, CAUSE_ISLAND_HOST, code)
    # a data-only relation (id >= n_config_rels) visited inside a
    # namespace that HAS a relation config is the reference's
    # "relation not found" error (engine.go:219-228): host replay
    rel_nf = (rel >= n_config_rels) & tables["ns_has_config"][ns].astype(bool)
    code = jnp.maximum(code, jnp.where(rel_nf, CAUSE_REL_NOT_FOUND, 0))
    return jnp.where(live, code, 0).astype(jnp.int32)


@jax.named_scope("keto.probe")
def probe_phase(
    tables, obj, rel, skind, sa, sb, depth, live, *,
    dh_probes: int, has_delta: bool = True,
):
    """Direct-edge probe; needs depth >= 1 (checkDirect gets restDepth-1).
    A delta-overlay entry for the exact key overrides the compacted table
    (insert adds the edge, tombstone masks a deleted one). `has_delta` is
    static: a clean mirror (the common serving state between writes)
    skips the overlay probe entirely — half the probe gathers."""
    key = edge_probe_key(obj, rel, skind, sa, sb)
    main_hit, main_val = _edge_key_probe(
        tables, "dh", obj, rel, skind, sa, sb, dh_probes, key=key
    )
    # value-liveness: incremental compaction (engine/compact.py) deletes
    # by zeroing the value in place (removing the key would break other
    # keys' probe chains); freshly-built tables store val=1 everywhere,
    # and the value lane rides the same packed-row gather — free
    main_hit = main_hit & (main_val == 1)
    if has_delta:
        in_delta, dval = _edge_key_probe(
            tables, "dd", obj, rel, skind, sa, sb, DELTA_PROBES, key=key
        )
        main_hit = jnp.where(in_delta, dval == 1, main_hit)
    return main_hit & live & (depth >= 1)


@jax.named_scope("keto.expand")
def expand_phase(
    tables,
    q,
    ctx,
    obj,
    rel,
    depth,
    live,
    isl_state,
    *,
    K: int,
    rh_probes: int,
    n_config_rels: int,
    wildcard_rel: int,
    n_queries: int,
    n_island_cap: int,
    has_delta: bool = True,
    prog=None,
) -> tuple[Expansion, jnp.ndarray, tuple]:
    """Expand every live task through its CSR row + rewrite instructions.

    Monotone programs: instruction children inherit the task's ctx (any
    hit anywhere resolves the accumulator — pure-union semantics).

    Island programs (FLAG_ISLAND — the rewrite contains AND/NOT): the
    task allocates an island instance; each instruction becomes a LEAF
    sub-check whose children carry a fresh leaf ctx. The island's boolean
    circuit is combined on host after the BFS (engine/islands.py) and the
    result ORs into the task's own ctx — the data-parallel form of the
    reference's synchronous binop.and/checkInverted islands
    (internal/check/binop.go:38-70, rewrites.go:95-159). The task's CSR
    slot (checkExpandSubject) still inherits the task ctx: subject-set
    expansion is an or-branch BESIDE the rewrite, not inside it
    (engine.go:183-207).

    Returns (candidates, per-query host flags, island updates):
    candidates beyond the frontier capacity are truncated and their
    owning queries flagged for host replay; delta-dirty rows and island-
    table overflow flag their queries too.
    """
    F = q.shape[0]
    S = K + 1  # expansion slots per task: CSR row + K instructions
    NI = n_island_cap
    n_edges = tables["e_pack"].shape[0]

    if prog is None:
        prog = program_lookup(tables, obj, rel, live, n_config_rels=n_config_rels)
    ns, has_prog, pid, prog_flags = prog

    # instruction load: ONE [F, K*4] row-gather of the packed
    # (kind, rel, rel2, pad) lanes instead of three [F, K] gathers
    mask_prog = has_prog[:, None]
    ipack = _isolate(tables["instr_pack"][pid]).reshape(F, K, 4)
    ik = jnp.where(mask_prog, ipack[..., 0], INSTR_NONE)  # [F, K]
    ir = jnp.where(mask_prog, ipack[..., 1], 0)
    ir2 = jnp.where(mask_prog, ipack[..., 2], 0)

    # relation per expansion slot: slot 0 = the task's own relation
    # (subject-set row), slots 1..K = the instruction relation
    rels = jnp.concatenate([rel[:, None], ir], axis=1)  # [F, S]

    # row lookup for every (obj, slot-relation): the rh span table
    # stores (row_start, row_end) in its two value lanes, so the CSR
    # span arrives with the probe — no row_ptr gathers at all
    spans = _multi_pair_key_probe(
        tables, "rh", obj, rels, rh_probes, n_vals=2
    )  # [F, S, 2]
    starts = spans[..., 0]
    row_len = jnp.where(starts < 0, 0, spans[..., 1] - starts)

    can_expand = live & (depth >= 1)
    is_comp = (ik == INSTR_COMPUTED) & live[:, None]
    is_ttu = (ik == INSTR_TTU) & (live & (depth >= 1))[:, None]

    counts = jnp.concatenate(
        [
            jnp.where(can_expand, row_len[:, 0], 0)[:, None],
            jnp.where(is_comp, 1, jnp.where(is_ttu, row_len[:, 1:], 0)),
        ],
        axis=1,
    )  # [F, S]

    # per-query host-replay cause codes raised by this phase (int32;
    # scatter-max per flag site — same scatter count as the old booleans)
    overflow_q = jnp.zeros(n_queries, dtype=jnp.int32)

    # delta-dirty rows (stale CSR contents): slot-0 expansion or TTU rows
    if has_delta:
        dirty_vals = _multi_pair_key_probe(
            tables, "dirty", obj, rels, DELTA_PROBES
        )
        row_dirty = (jnp.maximum(dirty_vals, 0) & DIRTY_FOR_CHECK) != 0  # [F, S]
        dirty = (can_expand & row_dirty[:, 0]) | jnp.any(
            is_ttu & row_dirty[:, 1:], axis=1
        )
        overflow_q = overflow_q.at[q].max(
            jnp.where(dirty, CAUSE_DIRTY, 0).astype(jnp.int32)
        )

    # island allocation: one instance per live task whose program has
    # AND/NOT; its instruction slots seed leaf ctxs B + idx*K + (k-1)
    isl_parent, isl_pid, n_isl = isl_state
    if NI > 0:
        is_island = ((prog_flags & FLAG_ISLAND) != 0) & live
        inc = is_island.astype(jnp.int32)
        rank = jnp.cumsum(inc) - inc  # exclusive rank among island tasks
        idx = n_isl + rank
        isl_ok = is_island & (idx < NI)
        # island-table overflow: exact host replay for those queries
        overflow_q = overflow_q.at[q].max(
            jnp.where(is_island & (idx >= NI), CAUSE_ISLAND_OVERFLOW, 0).astype(
                jnp.int32
            )
        )
        dest = jnp.where(isl_ok, idx, NI)
        isl_parent = isl_parent.at[dest].set(ctx, mode="drop")
        isl_pid = isl_pid.at[dest].set(pid, mode="drop")
        n_isl = jnp.minimum(n_isl + inc.sum(), NI)
        # per-(task, slot) child ctx: islands route instruction slots to
        # leaf ctxs; everything else inherits the task ctx
        B = n_queries
        leaf_base = B + idx * K
        slot_ctx = jnp.concatenate(
            [
                ctx[:, None],
                jnp.where(
                    isl_ok[:, None],
                    leaf_base[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :],
                    ctx[:, None],
                ),
            ],
            axis=1,
        )  # [F, S]
        # an overflowed island must not seed leaves under the PARENT ctx
        # (that would mix island semantics into the plain accumulator);
        # its instruction slots are suppressed instead — the query is
        # host-flagged anyway
        suppress = (is_island & ~isl_ok)[:, None]
        counts = jnp.concatenate(
            [
                counts[:, :1],
                jnp.where(suppress, 0, counts[:, 1:]),
            ],
            axis=1,
        )
    else:
        slot_ctx = jnp.broadcast_to(ctx[:, None], (F, S))

    # child relation: slot 0 = edge relation (from e_rel), computed = ir,
    # ttu = ir2; child depth: computed keeps depth, others depth-1
    crel = jnp.concatenate(
        [jnp.zeros((F, 1), jnp.int32), jnp.where(ik == INSTR_COMPUTED, ir, ir2)],
        axis=1,
    )

    flat_counts = counts.reshape(-1)
    offsets = jnp.cumsum(flat_counts) - flat_counts  # exclusive scan
    total = offsets[-1] + flat_counts[-1]

    # queries whose expansions overflow the frontier need host replay
    truncated_seg = (offsets + flat_counts) > F
    seg_q = jnp.repeat(q, S, total_repeat_length=F * S)
    overflow_q = overflow_q.at[seg_q].max(
        jnp.where(
            truncated_seg & (flat_counts > 0), CAUSE_FRONTIER_OVERFLOW, 0
        ).astype(jnp.int32)
    )

    # build candidate children by segmented gather; all per-(task, slot)
    # source columns flatten to [F*S] 1-D arrays (no small-lane layouts)
    seg, j = covering_segments(offsets, flat_counts, F)
    # within rides srcmat lane 7 (offsets[seg]) — no standalone gather
    in_range = j < jnp.minimum(total, F)

    # ONE [F, 8] row-gather of a stacked per-(task, slot) source matrix
    # replaces seven separate [F]-sized gathers (q[ti], slot_ctx[seg],
    # obj[ti], depth[ti], starts[seg], comp[seg], crel[seg]) — the
    # gather-volume model again: a row costs the same as an element
    srcmat = jnp.stack(
        [
            jnp.broadcast_to(q[:, None], (F, S)),
            slot_ctx,
            jnp.broadcast_to(obj[:, None], (F, S)),
            jnp.broadcast_to(depth[:, None], (F, S)),
            starts,
            jnp.concatenate(
                [jnp.zeros((F, 1), jnp.int32), is_comp.astype(jnp.int32)],
                axis=1,
            ),
            crel,
            offsets.reshape(F, S),  # lane 7: within = j - offsets[seg]
        ],
        axis=-1,
    ).reshape(F * S, 8)
    src = _isolate(srcmat[seg])  # [F, 8]
    src_q = src[:, 0]
    src_ctx = src[:, 1]
    src_obj = src[:, 2]
    src_depth = src[:, 3]
    src_start = src[:, 4]
    src_comp = src[:, 5].astype(bool)
    src_crel = src[:, 6]
    within = j - src[:, 7]
    src_slot0 = (seg % S) == 0

    e = jnp.clip(src_start + within, 0, max(n_edges - 1, 0))
    if n_edges:
        ep = _isolate(tables["e_pack"][e])  # [F, 2] = (obj, rel)
        edge_obj = ep[:, 0]
        edge_rel = ep[:, 1]
    else:
        edge_obj = jnp.zeros(F, jnp.int32)
        edge_rel = jnp.zeros(F, jnp.int32)

    child_obj = jnp.where(src_comp, src_obj, edge_obj)
    child_rel = jnp.where(src_slot0, edge_rel, src_crel)
    child_depth = jnp.where(src_comp, src_depth, src_depth - 1)
    child_valid = in_range & ~(src_slot0 & (edge_rel == wildcard_rel))
    return (
        Expansion(src_q, src_ctx, child_obj, child_rel, child_depth, child_valid),
        overflow_q,
        (isl_parent, isl_pid, n_isl),
    )


@jax.named_scope("keto.dedupe")
def dedupe_phase(
    children: Expansion, F: int, n_queries: int
) -> tuple[
    jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
    jnp.ndarray, jnp.ndarray,
]:
    """Dedupe candidates on (ctx, obj, rel) keeping the deepest instance
    and pack the survivors into the next frontier (ctx implies the query:
    root ctxs ARE query ids, leaf ctxs belong to one island instance).
    Candidates may be longer than F (multi-shard gather); survivors
    beyond F flag their queries for host replay.

    Sort-free: candidates race for a hash bucket (scatter-max of a
    priority encoding depth then candidate index); each candidate then
    reads its bucket's winner back. Losing against the SAME key is a
    duplicate (dropped — the winner carries >= depth); losing against a
    DIFFERENT key (bucket collision) keeps the candidate — dedupe is an
    optimization and duplicates are safe, so collisions only cost slots.
    A sort-based dedupe costs a multi-MB unrolled bitonic network on TPU;
    this is two scatters + a few gathers.

    Returns (t_q, t_obj, t_rel, t_depth, n_new, overflow_q[B]).
    """
    G = children.q.shape[0]
    cap = 1
    while cap < 2 * G:
        cap *= 2
    h = _hash_combine(children.ctx, children.obj, children.rel)
    bucket = (h & jnp.uint32(cap - 1)).astype(jnp.int32)
    bucket = jnp.where(children.valid, bucket, cap)  # invalid -> dropped

    # priority: deeper wins (uint32: depth in the top bits, candidate
    # index below). The bit split is derived from the STATIC candidate
    # count G (= n_shards * F after a multi-shard gather) so winner_idx
    # can never silently truncate — oversized meshes shrink the depth
    # field instead (deep depths tie, acceptable: the step budget caps
    # effective exploration long before such depths anyway).
    idx_bits = max(1, (G - 1).bit_length())
    if idx_bits > 28:
        raise ValueError(
            f"dedupe candidate count {G} needs {idx_bits} index bits; "
            "max 28 (shrink frontier_cap or the shard count)"
        )
    depth_max = (1 << (32 - idx_bits)) - 1
    idx = jnp.arange(G, dtype=jnp.int32)
    prio = (
        jnp.clip(children.depth, 0, depth_max).astype(jnp.uint32)
        << jnp.uint32(idx_bits)
    ) | idx.astype(jnp.uint32)
    winner_prio = (
        jnp.zeros(cap, jnp.uint32).at[bucket].max(prio, mode="drop")
    )
    winner_idx = (
        winner_prio[jnp.clip(bucket, 0, cap - 1)]
        & jnp.uint32((1 << idx_bits) - 1)
    ).astype(jnp.int32)

    won = children.valid & (winner_idx == idx)
    # same-key losers are duplicates; different-key losers survive.
    # ONE packed [G, 4] row-gather of the winners' keys instead of three
    # column gathers: a row-gather costs the same as a one-column gather
    # (gather-volume model, tools/microbench_gather_layout.py), so this
    # is 3 gathered-row sets -> 1
    keys = jnp.stack(
        [children.ctx, children.obj, children.rel,
         jnp.zeros_like(children.ctx)], axis=-1
    )  # [G, 4]
    same_key = jnp.all(keys[winner_idx] == keys, axis=-1)
    keep = children.valid & (won | ~same_key)

    pos = jnp.cumsum(keep) - 1
    n_keep = keep.sum().astype(jnp.int32)
    kept_in_cap = keep & (pos < F)
    # survivors that don't fit in the frontier: their queries go to host
    overflow_q = (
        jnp.zeros(n_queries, dtype=jnp.int32)
        .at[children.q]
        .max(
            jnp.where(
                keep & (pos >= F), CAUSE_FRONTIER_OVERFLOW, 0
            ).astype(jnp.int32),
            mode="drop",
        )
    )
    # non-kept entries park at index F: out-of-bounds scatter drops them
    dest = jnp.where(kept_in_cap, pos, F)
    nt_q = jnp.zeros(F, jnp.int32).at[dest].set(children.q, mode="drop")
    nt_ctx = jnp.zeros(F, jnp.int32).at[dest].set(children.ctx, mode="drop")
    nt_obj = jnp.zeros(F, jnp.int32).at[dest].set(children.obj, mode="drop")
    nt_rel = jnp.zeros(F, jnp.int32).at[dest].set(children.rel, mode="drop")
    nt_depth = jnp.zeros(F, jnp.int32).at[dest].set(children.depth, mode="drop")
    n_new = jnp.minimum(n_keep, F)
    return nt_q, nt_ctx, nt_obj, nt_rel, nt_depth, n_new, overflow_q


@jax.named_scope("keto.seed")
def seed_state(
    q_obj, q_rel, q_depth, q_valid, frontier_cap: int, n_island_cap: int = 0,
    K: int = 1,
) -> _State:
    """Initial frontier: one task per valid query (frontier_cap >= B);
    task i starts in root ctx i. NC = B + NI*K ctx accumulators."""
    B = q_obj.shape[0]
    pad = frontier_cap - B
    NC = B + n_island_cap * K
    depth0 = jnp.pad(q_depth.astype(jnp.int32), (0, pad))
    # invalid queries contribute inert tasks (depth -1 ⇒ no probes/expansion)
    depth0 = jnp.where(
        jnp.pad(q_valid, (0, pad), constant_values=False),
        depth0,
        -jnp.ones(frontier_cap, jnp.int32),
    )
    return _State(
        t_q=jnp.pad(jnp.arange(B, dtype=jnp.int32), (0, pad)),
        t_ctx=jnp.pad(jnp.arange(B, dtype=jnp.int32), (0, pad)),
        t_obj=jnp.pad(q_obj.astype(jnp.int32), (0, pad)),
        t_rel=jnp.pad(q_rel.astype(jnp.int32), (0, pad)),
        t_depth=depth0,
        n_tasks=jnp.int32(B),
        ctx_hit=jnp.zeros(NC, dtype=bool),
        needs_host=jnp.zeros(B, dtype=jnp.int32),
        isl_parent=jnp.zeros(max(n_island_cap, 1), jnp.int32),
        isl_pid=jnp.zeros(max(n_island_cap, 1), jnp.int32),
        n_isl=jnp.int32(0),
        step=jnp.int32(0),
        stats=empty_launch_stats(),
    )


def loop_cond(max_steps: int, n_queries: int):
    def cond_fn(st: _State) -> jnp.ndarray:
        return (
            (st.step < max_steps)
            & (st.n_tasks > 0)
            & ~jnp.all(st.ctx_hit[:n_queries] | (st.needs_host > 0))
        )

    return cond_fn


def bounded_loop(cond_fn, step_fn, init, max_steps: int):
    """Drive step_fn while cond_fn holds, never past max_steps: THE loop
    of every BFS kernel (check, sharded check, both expand kernels, the
    reverse and filter walks, closure powering), on every backend.

    A counted fori_loop whose body is a cond: once cond_fn fails the
    remaining trips skip step_fn, so the result is that of a while_loop
    over the same pair. The counted form was adopted in round 5 against
    a fixed cost per while_loop iteration seen on the device arrangement
    of that time; what it gains or costs on the attached chip is not
    measured (ROADMAP S2). If while_loop wins there it replaces this
    body, here and nowhere else, for every backend."""

    def body(i, st):
        return jax.lax.cond(cond_fn(st), step_fn, lambda s: s, st)

    return jax.lax.fori_loop(0, max_steps, body, init)


def covering_segments(offsets: jnp.ndarray, flat_counts: jnp.ndarray, F: int):
    """Covering-segment map over a [F] work list: (seg[F], j[F]) where
    slot j lies in the span [offsets[seg], offsets[seg] + flat_counts[seg])
    of segment seg (clipped into range past the last span).

    ONE scatter of segment-start markers + a running max: a searchsorted
    over the offsets is ~17 sequential gather rounds of F random rows
    each, and the step cost on the chip is gather-volume bound. Nonempty
    segments have strictly increasing starts, so the running max of the
    marks names the segment that covers each slot."""
    n_seg = flat_counts.shape[0]
    j = jnp.arange(F, dtype=jnp.int32)
    startpos = jnp.where(flat_counts > 0, offsets, F)  # empty segs drop
    marks = jnp.zeros(F, jnp.int32).at[startpos].max(
        jnp.arange(1, n_seg + 1, dtype=jnp.int32), mode="drop"
    )
    seg = jax.lax.cummax(marks) - 1  # -1 before the first segment
    return jnp.clip(seg, 0, n_seg - 1), j


def run_bfs_loop(step_fn, init, max_steps: int, n_queries: int):
    """bounded_loop under the check kernels' standard predicate."""
    return bounded_loop(loop_cond(max_steps, n_queries), step_fn, init, max_steps)


@jax.named_scope("keto.finalize")
def finalize(
    final: _State, max_steps: int, n_queries: int
) -> tuple[
    jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
    jnp.ndarray,
]:
    """Step-budget exhaustion with live tasks means the device did NOT
    finish exploring: those queries must go to the host, not be reported
    NotMember (silent false denials otherwise).

    Returns (ctx_hit, needs_host, isl_parent, isl_pid, n_isl, stats) —
    the engine combines island circuits on host and reads the per-query
    verdict from ctx_hit[:B] (engine/islands.py). needs_host carries the
    CAUSE_* code (nonzero => host replay); stats is the launch's
    introspection counter vector (STAT_* slots)."""
    F = final.t_q.shape[0]
    exhausted = (final.step >= max_steps) & (final.n_tasks > 0)
    live = jnp.arange(F, dtype=jnp.int32) < final.n_tasks
    needs_host = final.needs_host.at[final.t_q].max(
        jnp.where(exhausted & live, CAUSE_STEP_EXHAUSTED, 0).astype(jnp.int32)
    )
    return (
        final.ctx_hit, needs_host, final.isl_parent, final.isl_pid,
        final.n_isl, final.stats,
    )


@jax.named_scope("keto.check")
def _check_kernel_impl(
    tables: dict,
    q_obj: jnp.ndarray,  # [B] seed object slots
    q_rel: jnp.ndarray,  # [B] seed relation ids
    q_depth: jnp.ndarray,  # [B] clamped max depths
    q_skind: jnp.ndarray,  # [B] subject kind (0 plain, 1 set)
    q_sa: jnp.ndarray,  # [B]
    q_sb: jnp.ndarray,  # [B]
    q_valid: jnp.ndarray,  # [B] bool: evaluate on device
    *,
    K: int,
    dh_probes: int,
    rh_probes: int,
    max_steps: int,
    wildcard_rel: int,
    n_config_rels: int,
    frontier_cap: int,
    n_island_cap: int = 0,
    has_delta: bool = True,
) -> tuple[
    jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
    jnp.ndarray,
]:
    """Returns (ctx_hit[B + NI*K], needs_host[B], isl_parent, isl_pid,
    n_isl, stats[N_LAUNCH_STATS]); the per-query verdict is ctx_hit[:B]
    after the host island combine (a no-op for monotone-only configs,
    where n_island_cap=0)."""
    B = q_obj.shape[0]
    F = frontier_cap
    # packed per-query subject key: ONE [F, 4] row-gather per step
    # instead of three [F] gathers (q_skind/q_sa/q_sb share the index q)
    qsub = jnp.stack(
        [q_skind, q_sa, q_sb, jnp.zeros_like(q_skind)], axis=-1
    )  # [B, 4]

    def step_fn(st: _State) -> _State:
        idx = jnp.arange(F, dtype=jnp.int32)
        q = st.t_q
        ctx = st.t_ctx
        root_done = st.ctx_hit[:B] | (st.needs_host > 0)
        # a task dies when its query is resolved (top-level or short-
        # circuit) or its own accumulator already hit (per-ctx
        # short-circuit: an island leaf is an OR accumulation too)
        live = (idx < st.n_tasks) & ~root_done[q] & ~st.ctx_hit[ctx]
        obj, rel, depth = st.t_obj, st.t_rel, st.t_depth

        prog = program_lookup(tables, obj, rel, live, n_config_rels=n_config_rels)
        flagged = flag_phase(
            tables, obj, rel, live,
            n_config_rels=n_config_rels, island_is_host=(n_island_cap == 0),
            prog=prog,
        )
        sub = _isolate(qsub[q])  # [F, 4]
        hit = probe_phase(
            tables, obj, rel, sub[:, 0], sub[:, 1], sub[:, 2], depth, live,
            dh_probes=dh_probes, has_delta=has_delta,
        )
        ctx_hit = st.ctx_hit.at[ctx].max(hit)
        needs_host = st.needs_host.at[q].max(flagged)

        # refresh liveness after accumulator updates (short-circuit)
        live = live & ~(ctx_hit[:B] | (needs_host > 0))[q] & ~ctx_hit[ctx]

        children, overflow_q, isl_state = expand_phase(
            tables, q, ctx, obj, rel, depth, live,
            (st.isl_parent, st.isl_pid, st.n_isl),
            K=K, rh_probes=rh_probes, n_config_rels=n_config_rels,
            wildcard_rel=wildcard_rel, n_queries=B,
            n_island_cap=n_island_cap, has_delta=has_delta, prog=prog,
        )
        needs_host = jnp.maximum(needs_host, overflow_q)

        nt_q, nt_ctx, nt_obj, nt_rel, nt_depth, n_new, overflow2 = dedupe_phase(
            children, F, B
        )
        needs_host = jnp.maximum(needs_host, overflow2)
        # launch introspection: a handful of scalar reductions per step
        # (measured in the committed A/B leg as within-noise); depth >= 0
        # excludes the seed bucket's padding tasks from the live count
        stats = update_launch_stats(
            st.stats,
            st.n_tasks,
            (live & (depth >= 0)).sum(),
            hit.sum(),
            children.valid.sum(),
            n_new,
        )
        return _State(
            nt_q, nt_ctx, nt_obj, nt_rel, nt_depth, n_new,
            ctx_hit, needs_host, *isl_state, st.step + 1, stats,
        )

    init = seed_state(q_obj, q_rel, q_depth, q_valid, F, n_island_cap, K)
    final = run_bfs_loop(step_fn, init, max_steps, B)
    return finalize(final, max_steps, B)


_KERNEL_STATICS = (
    "K", "dh_probes", "rh_probes", "max_steps",
    "wildcard_rel", "n_config_rels", "frontier_cap",
    "n_island_cap", "has_delta",
)

check_kernel = functools.partial(
    jax.jit, static_argnames=_KERNEL_STATICS
)(_check_kernel_impl)


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def check_kernel_packed(
    tables: dict,
    qpack: jnp.ndarray,
    *,
    K: int,
    dh_probes: int,
    rh_probes: int,
    max_steps: int,
    wildcard_rel: int,
    n_config_rels: int,
    frontier_cap: int,
    n_island_cap: int = 0,
    has_delta: bool = True,
):
    """check_kernel with single-buffer I/O: `qpack` is ONE [7, B] int32
    array (obj, rel, depth, skind, sa, sb, valid) and the result is ONE
    int32 vector [n_isl, ctx_hit(B + NI*K), needs_host(B), isl_parent(NI),
    isl_pid(NI), stats(N_LAUNCH_STATS)]. The launch stats ride the same
    single readback — the flight recorder costs no extra transfer.

    Every host<->device buffer transfer pays a fixed cost of its own, so
    seven query uploads and five result readbacks per batch cost more
    than the kernel. One upload + one readback per batch is the
    transfer-count floor. unpack/concat compile to free reshapes on
    device."""
    ctx_hit, needs_host, isl_parent, isl_pid, n_isl, stats = _check_kernel_impl(
        tables,
        qpack[0], qpack[1], qpack[2], qpack[3], qpack[4], qpack[5],
        qpack[6].astype(bool),
        K=K, dh_probes=dh_probes, rh_probes=rh_probes, max_steps=max_steps,
        wildcard_rel=wildcard_rel, n_config_rels=n_config_rels,
        frontier_cap=frontier_cap, n_island_cap=n_island_cap,
        has_delta=has_delta,
    )
    return jnp.concatenate([
        n_isl[None].astype(jnp.int32),
        ctx_hit.astype(jnp.int32),
        needs_host.astype(jnp.int32),
        isl_parent.astype(jnp.int32),
        isl_pid.astype(jnp.int32),
        # stats LAST so existing front-anchored slicing (e.g.
        # tools/scale_1e8_shard.py) keeps working unchanged
        stats.astype(jnp.int32),
    ])


def pack_queries(
    q_obj, q_rel, q_depth, q_skind, q_sa, q_sb, q_valid
) -> np.ndarray:
    """Host-side twin of check_kernel_packed's input layout."""
    import numpy as _np

    return _np.stack([
        q_obj, q_rel, q_depth, q_skind, q_sa, q_sb,
        q_valid.astype(_np.int32),
    ]).astype(_np.int32)


def unpack_results(flat: np.ndarray, B: int, n_island_cap: int, K: int):
    """Slice check_kernel_packed's result vector back into
    (ctx_hit, needs_host, isl_parent, isl_pid, n_isl, stats) numpy
    views. `stats` is the launch introspection counter vector
    (STAT_* slots; launch_stats_dict names them)."""
    NI = max(n_island_cap, 1)
    NC = B + n_island_cap * K
    n_isl = int(flat[0])
    ctx_hit = flat[1 : 1 + NC].astype(bool)
    needs_host = flat[1 + NC : 1 + NC + B]
    isl_parent = flat[1 + NC + B : 1 + NC + B + NI]
    isl_pid = flat[1 + NC + B + NI : 1 + NC + B + 2 * NI]
    base = 1 + NC + B + 2 * NI
    stats = flat[base : base + N_LAUNCH_STATS]
    return ctx_hit, needs_host, isl_parent, isl_pid, n_isl, stats


PASSTHROUGH_TABLE_KEYS = (
    "objslot_ns", "ns_has_config", "prog_flags",
)


def pack_raw_tables(raw: dict) -> dict:
    """Interleave the 1-D column arrays into the packed device layout
    (host-side numpy; GraphSnapshot / checkpoint formats stay columnar).
    Everything hot rides packed row layouts: dh/rh bucket tables, the
    (obj, rel) edge pack, and the per-program instruction lanes —
    row_ptr is resolved into the rh span lanes at pack time and never
    uploaded."""
    import numpy as _np

    out = {k: raw[k] for k in PASSTHROUGH_TABLE_KEYS if k in raw}
    out["dh_pack"] = pack_edge_table(
        raw["dh_obj"], raw["dh_rel"], raw["dh_skind"],
        raw["dh_sa"], raw["dh_sb"], raw["dh_val"],
    )
    out["rh_pack"] = pack_rh_span_table(
        raw["rh_obj"], raw["rh_rel"], raw["rh_row"], raw["row_ptr"]
    )
    out["e_pack"] = _np.stack(
        [_np.asarray(raw["e_obj"]), _np.asarray(raw["e_rel"])], axis=-1
    ).astype(_np.int32)
    if "instr_kind" in raw:
        # edge-table-only dicts (per-shard builds: the instruction
        # tables are replicated, packed once by the caller) skip this
        out["instr_pack"] = pack_instr_table(
            raw["instr_kind"], raw["instr_rel"], raw["instr_rel2"]
        )
    if "dd_obj" in raw:
        out.update(pack_delta_tables(raw))
    return out


def pack_snapshot_tables(snapshot: GraphSnapshot, delta: dict | None = None) -> dict:
    """The host side of snapshot_tables: a snapshot's columns packed into
    the table rows the device holds; the delta-overlay tables default to
    empty (fixed shapes either way)."""
    raw = dict(snapshot.device_arrays())
    raw.update(delta or empty_delta_tables())
    return pack_raw_tables(raw)


def snapshot_tables(snapshot: GraphSnapshot, delta: dict | None = None) -> dict:
    """Device-resident table dict for check_kernel (uploads once)."""
    return device_tables(pack_snapshot_tables(snapshot, delta))


def refresh_delta_tables(tables: dict, delta: dict, vocab_arrays: dict) -> dict:
    """New table dict with only the overlay (and the vocab-dependent
    objslot_ns / ns_has_config arrays, which grow with delta vocab) re-
    uploaded; the big compacted tables are reused as-is."""
    return {
        **tables,
        **device_tables({**vocab_arrays, **pack_delta_tables(delta)}),
    }


def kernel_static_config(
    snapshot: GraphSnapshot,
    max_depth: int,
    frontier_cap: int,
    n_island_cap: int = 0,
    has_delta: bool = True,
) -> dict:
    """The static kwargs for check_kernel, derived from a snapshot.
    Monotone-only configs force n_island_cap=0 (zero island overhead);
    has_delta=False compiles out the overlay probes for a clean mirror."""
    return dict(
        K=snapshot.K,
        dh_probes=snapshot.dh_probes,
        rh_probes=snapshot.rh_probes,
        # depth decrements bound chain steps; computed hops at constant
        # depth are bounded by the relation count before cycling
        max_steps=int(max_depth + snapshot.n_config_rels + 4),
        wildcard_rel=snapshot.wildcard_rel,
        n_config_rels=max(snapshot.n_config_rels, 1),
        frontier_cap=frontier_cap,
        n_island_cap=n_island_cap if snapshot.island_circuits else 0,
        has_delta=has_delta,
    )
