"""Island combine: host-side evaluation of AND/NOT rewrite circuits.

The kernel evaluates the MONOTONE mass of every query on device: each
island leaf (a computed/TTU sub-check under an AND/NOT rewrite) is a
full BFS exploration accumulating hits in its own ctx slot. What remains
after the BFS converges is pure boolean algebra over those leaf bits —
a few thousand ops at most — combined here in numpy.

Two-valued logic is EXACT for check verdicts (not an approximation):
every or/and in the reference collapses MembershipUnknown to NotMember
(internal/check/binop.go:15-36 `or` falls through to NotMember,
binop.go:52-57 `and` returns NotMember for any non-IsMember child, and
the checkgroup consumer finalizes to NotMember likewise,
checkgroup/concurrent_checkgroup.go:100-121). Unknown therefore only
survives along a chain of the nodes' own `restDepth < 0` guards
(rewrites.go:36-39,:96-105,:172-175,:211-214) — and island tasks always
run at depth >= 0, so those guards never fire on device. Depth-bounded
branches below a leaf evaluate to NotMember exactly as the reference
reports them (e.g. not(exhausted-branch) => IsMember, reference
semantics replicated deliberately).

Ordering: islands are allocated in BFS step order, so a nested island
(spawned by a leaf task of an earlier island) always has a HIGHER index
than its parent. An island's nesting level (how many islands it sits
inside) is therefore one more than its parent island's, and resolving
the deepest level first is an inner-first sweep: by the time an island's
circuit reads its leaf bits, every nested island feeding those leaves
has already resolved. Within a level no island feeds another, so each
program's circuit runs once a level over the islands' leaf bits as
arrays.
"""

from __future__ import annotations

import numpy as np

from .snapshot import CIRC_AND, CIRC_FALSE, CIRC_LEAF, CIRC_NOT, CIRC_OR


def eval_circuit(ops: tuple, leaves: np.ndarray) -> np.ndarray:
    """Evaluate one postfix boolean circuit over `leaves` ([n, K] leaf
    bits of n islands): [n] verdicts."""
    stack: list[np.ndarray] = []
    for op in ops:
        code = op[0]
        if code == CIRC_LEAF:
            stack.append(leaves[:, op[1]])
        elif code == CIRC_FALSE:
            stack.append(np.zeros(len(leaves), bool))
        elif code == CIRC_NOT:
            stack[-1] = ~stack[-1]
        elif code == CIRC_AND:
            b = stack.pop()
            stack[-1] = stack[-1] & b
        elif code == CIRC_OR:
            b = stack.pop()
            stack[-1] = stack[-1] | b
        else:  # pragma: no cover — compiler emits only the codes above
            raise ValueError(f"unknown circuit op {code!r}")
    return stack[-1]


def nesting_levels(isl_parent: np.ndarray, n_queries: int, K: int) -> np.ndarray:
    """[n_isl] nesting level of every island: 0 where its parent is a
    query's root ctx, else one more than the island that owns the parent
    leaf ctx. A parent island has the lower index, so the levels settle in
    as many passes as islands nest."""
    owner = (isl_parent - n_queries) // K  # island of a parent leaf ctx
    nested = isl_parent >= n_queries
    level = np.zeros(len(isl_parent), np.int64)
    while True:
        again = np.where(nested, level[np.where(nested, owner, 0)] + 1, 0)
        if np.array_equal(again, level):
            return level
        level = again


def combine_islands(
    ctx_hit: np.ndarray,
    isl_parent: np.ndarray,
    isl_pid: np.ndarray,
    n_isl: int,
    circuits: dict,
    n_queries: int,
    K: int,
) -> np.ndarray:
    """Resolve all island instances inner first; returns the per-query
    verdict ctx_hit[:n_queries] (mutates the ctx_hit copy passed in).

    Each numpy call over arrays of this size lets the serving threads
    take the interpreter, and on a loaded host each costs a wait, so the
    calls are few. Leaf k of island i is ctx n_queries + i*K + k, so the
    leaves are one [n_isl, K] view of ctx_hit (a level reads through it
    what the level nested inside wrote); a circuit runs over every island
    and keeps its own program's; without nested islands there is one
    level."""
    parent = isl_parent[:n_isl]
    pid = isl_pid[:n_isl]
    leaves = ctx_hit[n_queries : n_queries + n_isl * K].reshape(n_isl, K)
    level = (
        nesting_levels(parent, n_queries, K)
        if (parent >= n_queries).any() else None
    )
    programs = set(pid.tolist())
    for lv in range(0 if level is None else int(level.max()), -1, -1):
        holds = np.zeros(n_isl, bool)
        for p in programs:
            mine = pid == p
            if level is not None:
                mine &= level == lv
            holds |= mine & eval_circuit(circuits[p], leaves)
        ctx_hit[parent[holds]] = True
    return ctx_hit[:n_queries]
