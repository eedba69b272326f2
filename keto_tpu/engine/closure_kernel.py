"""Device closure-intersection kernel: deep checks in ONE probe step.

The runtime half of the Leopard index (engine/closure.py): where the BFS
check kernel pays one `bounded_loop` iteration per nesting level (each a
full frontier-wide gather set — deep-20 chains ran 6x slower than flat
checks, BENCH_r07_cpu), this kernel answers a whole batch in a single
step regardless of chain depth:

  1. `cc` coverage probe — is this (obj, rel) node proven closure-
     complete (monotone region, set under the row cap)?
  2. `cd` dirty probe — has a committed write potentially perturbed this
     node's closure since the last powering (transitive-ancestor marking
     by the maintenance plane)?
  3. `ch` membership probe — the materialized R·D product keyed exactly
     like the direct-edge table (obj, rel, skind, sa, sb), value = the
     entry's minimum required depth. The intersection of the query's
     {subject} with the node's closure set IS this one hash probe, and
     the depth gate (`req <= q_depth`) reproduces the BFS kernel's depth
     bookkeeping bit-for-bit.

Queries that fail (1) or (2), or whose vocabulary never encoded
(q_valid false), are NOT answered — the engine routes them to the BFS
kernel with a cause-coded fallback counter. A resolved query's verdict
is final: covered + clean means the closure set is provably complete at
the view's synced version, so a membership miss is a definitive
NOT_MEMBER.

Same conventions as every other kernel: packed single-buffer I/O (one
[7, B] query upload, one int32 result readback), tables as packed
bucket rows probed through the shared `_edge_key_probe` /
`_pair_key_probe` helpers, the launch-stats vector accumulated inside
the shared `bounded_loop` (max_steps=1 — the whole point) and appended
LAST so flight-recorder counters ride the batch's one readback.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .delta import DELTA_PROBES
from .kernel import (
    N_LAUNCH_STATS,
    _edge_key_probe,
    _pair_key_probe,
    bounded_loop,
    empty_launch_stats,
    update_launch_stats,
)

# kernel-side fallback causes (a launch happened; these queries leave it
# unresolved). Host-side causes (disabled/unbuilt/stale/lag — no launch)
# are defined in engine/closure.py.
CL_CAUSE_OK = 0
CL_CAUSE_UNCOVERED = 1  # node not in the covered set (poison / row cap /
# outside the interesting universe)
CL_CAUSE_DIRTY = 2  # node transitively touched by a post-build write
CL_CAUSE_INVALID = 3  # query vocabulary never encoded (host replay)

CL_CAUSE_NAMES = {
    CL_CAUSE_UNCOVERED: "uncovered",
    CL_CAUSE_DIRTY: "dirty",
    CL_CAUSE_INVALID: "unindexed",
}


class _CState(NamedTuple):
    member: jnp.ndarray  # [B] bool closure verdict (meaningful iff resolved)
    cause: jnp.ndarray  # [B] int32 CL_CAUSE_* (0 = resolved on closure)
    step: jnp.ndarray  # scalar int32
    stats: jnp.ndarray  # [N_LAUNCH_STATS]


def _closure_kernel_impl(
    tables: dict,
    q_obj: jnp.ndarray,
    q_rel: jnp.ndarray,
    q_depth: jnp.ndarray,
    q_skind: jnp.ndarray,
    q_sa: jnp.ndarray,
    q_sb: jnp.ndarray,
    q_valid: jnp.ndarray,
    *,
    cc_probes: int,
    ch_probes: int,
    has_dirty: bool,
):
    B = q_obj.shape[0]

    def step_fn(st: _CState) -> _CState:
        covered = (
            _pair_key_probe(tables, "cc", q_obj, q_rel, cc_probes) == 1
        )
        if has_dirty:
            dirty = (
                jnp.maximum(
                    _pair_key_probe(tables, "cd", q_obj, q_rel, DELTA_PROBES),
                    0,
                )
                == 1
            )
        else:
            # clean overlay compiles the dirty probe out entirely (the
            # same static-flag trick as the check kernel's has_delta)
            dirty = jnp.zeros(B, dtype=bool)
        found, req = _edge_key_probe(
            tables, "ch", q_obj, q_rel, q_skind, q_sa, q_sb, ch_probes
        )
        resolved = q_valid & covered & ~dirty
        member = resolved & found & (req >= 1) & (req <= q_depth)
        cause = jnp.where(
            ~q_valid,
            CL_CAUSE_INVALID,
            jnp.where(
                ~covered,
                CL_CAUSE_UNCOVERED,
                jnp.where(dirty, CL_CAUSE_DIRTY, CL_CAUSE_OK),
            ),
        ).astype(jnp.int32)
        stats = update_launch_stats(
            st.stats,
            jnp.int32(B),
            q_valid.sum(),
            member.sum(),
            jnp.int32(0),
            jnp.int32(0),
        )
        return _CState(member, cause, st.step + jnp.int32(1), stats)

    init = _CState(
        member=jnp.zeros(B, dtype=bool),
        cause=jnp.zeros(B, dtype=jnp.int32),
        step=jnp.int32(0),
        stats=empty_launch_stats(),
    )
    # ONE iteration through the shared loop construct: the closure's
    # whole pitch is a step count that does not grow with chain depth,
    # and running it under bounded_loop keeps the launch-stats contract
    # (steps=1 lands in the same STAT_STEPS slot the BFS kernels fill)
    final = bounded_loop(
        lambda st: st.step < jnp.int32(1), step_fn, init, 1
    )
    return final.member, final.cause, final.stats


_CLOSURE_STATICS = ("cc_probes", "ch_probes", "has_dirty")


@functools.partial(jax.jit, static_argnames=_CLOSURE_STATICS)
@jax.named_scope("keto.closure")
def closure_kernel_packed(
    tables: dict,
    qpack: jnp.ndarray,
    *,
    cc_probes: int,
    ch_probes: int,
    has_dirty: bool,
):
    """Single-buffer I/O twin of check_kernel_packed: `qpack` is the
    SAME [7, B] layout (obj, rel, depth, skind, sa, sb, valid) so the
    engine packs queries once and feeds either kernel; result is ONE
    int32 vector [member(B), cause(B), stats(N_LAUNCH_STATS)]."""
    member, cause, stats = _closure_kernel_impl(
        tables,
        qpack[0], qpack[1], qpack[2], qpack[3], qpack[4], qpack[5],
        qpack[6].astype(bool),
        cc_probes=cc_probes, ch_probes=ch_probes, has_dirty=has_dirty,
    )
    return jnp.concatenate([
        member.astype(jnp.int32),
        cause,
        stats.astype(jnp.int32),
    ])


def unpack_closure_results(flat, B: int):
    """(member[B] bool, cause[B] int32, stats[N_LAUNCH_STATS]) numpy
    views of closure_kernel_packed's result vector."""
    member = flat[:B].astype(bool)
    cause = flat[B : 2 * B]
    stats = flat[2 * B : 2 * B + N_LAUNCH_STATS]
    return member, cause, stats


def estimate_closure_gather_bytes(
    B: int, cc_probes: int, ch_probes: int, has_dirty: bool
) -> int:
    """Gather volume of ONE closure launch (the flight-recorder
    gather_bytes_est field): each probe chain costs ceil(probes/spb)
    256-byte bucket rows per query — no frontier, no steps."""
    bucket_row = 256

    def pb(probes: int, spb: int) -> int:
        return (int(probes) + spb - 1) // spb

    b = B * pb(cc_probes, 16) * bucket_row  # cc coverage probe
    b += B * pb(ch_probes, 8) * bucket_row  # ch membership probe
    if has_dirty:
        b += B * pb(DELTA_PROBES, 16) * bucket_row  # cd dirty probe
    return b
