"""Chains of `depth` parent edges with one owner at the tail: the depth-20
nested-folder shape of bench.py's `_deep_columns`, copied so that the
yardstick does not move when bench.py does.

`viewer` on the head `c<c>f0` is allowed exactly for the owner of chain c,
found only after `depth` tuple-to-userset steps.
"""

from __future__ import annotations

import numpy as np


class Truth:
    def __init__(self, params: dict, seed: int, tuples: int):
        self.depth = params["depth"]
        self.n_chains = self.n_targets = tuples // (self.depth + 1)
        self.tuples = self.n_chains * (self.depth + 1)
        rng = np.random.default_rng(seed)
        self.owners = rng.integers(0, params["users"], self.n_chains)

    def query(self, target: int, allowed: bool, nonce: str):
        subject = f"u{self.owners[target]}" if allowed else f"nobody{nonce}"
        return "deep", f"c{target}f0", "viewer", subject


def columns(truth: Truth):
    from keto_tpu.storage.columns import TupleColumns, concat_columns

    n_chains, depth = truth.n_chains, truth.depth
    n_par = n_chains * depth
    chain = np.repeat(np.arange(n_chains), depth)
    level = np.tile(np.arange(depth), n_chains)
    stem = np.char.add(np.char.add("c", chain.astype("U8")), "f")
    par = TupleColumns(
        ns=np.full(n_par, "deep", "U4"),
        obj=np.char.add(stem, level.astype("U3")),
        rel=np.full(n_par, "parent", "U6"),
        skind=np.ones(n_par, np.int8),
        sns=np.full(n_par, "deep", "U4"),
        sobj=np.char.add(stem, (level + 1).astype("U3")),
        srel=np.full(n_par, "...", "U3"),
    )
    own = TupleColumns(
        ns=np.full(n_chains, "deep", "U4"),
        obj=np.char.add(
            np.char.add("c", np.arange(n_chains).astype("U8")), f"f{depth}"
        ),
        rel=np.full(n_chains, "owner", "U5"),
        skind=np.zeros(n_chains, np.int8),
        sns=np.full(n_chains, "", "U1"),
        sobj=np.char.add("u", truth.owners.astype("U8")),
        srel=np.full(n_chains, "", "U1"),
    )
    return concat_columns([own, par])
