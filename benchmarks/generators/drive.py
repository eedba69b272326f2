"""The drive topology: folders with one owner, 80 files to a folder with a
parent edge, and an RBAC overlay of roles. Copied from chip_smoke.build_drive
and tools/scale_bench.synth_columns / synth_rbac_columns, so that the
yardstick does not move when those do.

`Truth` is what the construction fixes and costs almost nothing to make, so
the load generator rebuilds it from the seed without building the columns:
`view` on file `/d<i>/v<j>` is allowed exactly for the owner of folder i.
"""

from __future__ import annotations

import numpy as np

FILES_PER = 80


class Truth:
    def __init__(self, params: dict, seed: int, tuples: int):
        self.tuples = tuples
        self.n_users = max(100, tuples // params["tuples_per_user"])
        self.n_roles = max(16, min(params["roles"], tuples // 1000))
        # the overlay is made first: what it leaves is the drive's share
        self.rbac = _rbac_columns(self.n_roles, self.n_users, seed + 16)
        n_drive = tuples - len(self.rbac)
        self.n_folders = max(1, n_drive // (FILES_PER + 1))
        rng = np.random.default_rng(seed)
        self.owners = rng.integers(0, self.n_users, self.n_folders)
        self.n_targets = self.n_folders * FILES_PER

    def query(self, target: int, allowed: bool, nonce: str):
        """(namespace, object, relation, subject id) of a check on file
        `target`, by its folder's owner or by a subject that owns nothing."""
        folder, file = divmod(target, FILES_PER)
        subject = f"u{self.owners[folder]}" if allowed else f"nobody{nonce}"
        return "videos", f"/d{folder}/v{file}", "view", subject


def columns(truth: Truth):
    """Exactly `truth.tuples` relation tuples: folder owners, file->folder
    parent edges, the RBAC overlay, and co-owners on the first folders to
    make the count exact."""
    from keto_tpu.storage.columns import TupleColumns, concat_columns

    n_folders, n_files = truth.n_folders, truth.n_targets
    f_names = np.char.add("/d", np.arange(n_folders).astype("U10"))
    own = TupleColumns(
        ns=np.full(n_folders, "videos", "U6"),
        obj=f_names,
        rel=np.full(n_folders, "owner", "U6"),
        skind=np.zeros(n_folders, np.int8),
        sns=np.full(n_folders, "", "U1"),
        sobj=np.char.add("u", truth.owners.astype("U10")),
        srel=np.full(n_folders, "", "U1"),
    )
    parent_names = np.repeat(f_names, FILES_PER)
    par = TupleColumns(
        ns=np.full(n_files, "videos", "U6"),
        obj=np.char.add(
            np.char.add(parent_names, "/v"),
            np.tile(np.arange(FILES_PER), n_folders).astype("U3"),
        ),
        rel=np.full(n_files, "parent", "U6"),
        skind=np.ones(n_files, np.int8),
        sns=np.full(n_files, "videos", "U6"),
        sobj=parent_names,
        srel=np.full(n_files, "...", "U3"),
    )
    n_co = truth.tuples - len(truth.rbac) - n_folders - n_files
    if not 0 <= n_co <= n_folders:
        raise ValueError(f"cannot make {truth.tuples} tuples exact")
    co = TupleColumns(
        ns=np.full(n_co, "videos", "U6"),
        obj=f_names[:n_co],
        rel=np.full(n_co, "owner", "U6"),
        skind=np.zeros(n_co, np.int8),
        sns=np.full(n_co, "", "U1"),
        sobj=np.char.add("co", np.arange(n_co).astype("U10")),
        srel=np.full(n_co, "", "U1"),
    )
    return concat_columns([own, par, truth.rbac, co])


def _distinct(rng, rows: int, per: int, n: int):
    """`per` distinct draws from range(n) in each of `rows` rows: a row that
    drew one number twice draws again."""
    out = rng.integers(0, n, (rows, per))
    while True:
        s = np.sort(out, axis=1)
        again = (s[:, 1:] == s[:, :-1]).any(axis=1)
        if not again.any():
            return out
        out[again] = rng.integers(0, n, (int(again.sum()), per))


def _rbac_columns(n_roles: int, n_users: int, seed: int):
    """Each role holds 12 distinct user members and nests 2 distinct roles
    among the 97 of next higher id (the last role none, the one before it
    one: the graph stays acyclic). So the overlay has 14 n_roles - 3 tuples
    for every seed, and with it the folders, the files and the edges: the
    seed draws who owns and who belongs, never how much there is. (Copied
    from tools/scale_bench.synth_rbac_columns, which draws with replacement
    and drops what it drew twice: a store of another size, and so another
    program to compile, for every seed.)"""
    from keto_tpu.storage.columns import TupleColumns, concat_columns

    rng = np.random.default_rng(seed)
    members_per, nested_per, reach = 12, 2, 97
    role_of = np.repeat(np.arange(n_roles), members_per)
    member = _distinct(rng, n_roles, members_per, n_users).reshape(-1)
    direct = TupleColumns(
        ns=np.full(len(role_of), "rbac", "U4"),
        obj=np.char.add("role", role_of.astype("U7")),
        rel=np.full(len(role_of), "member", "U6"),
        skind=np.zeros(len(role_of), np.int8),
        sns=np.full(len(role_of), "", "U1"),
        sobj=np.char.add("u", member.astype("U10")),
        srel=np.full(len(role_of), "", "U1"),
    )
    room = np.minimum(reach, n_roles - 1 - np.arange(n_roles))  # roles above
    first = rng.integers(0, np.maximum(room, 1))
    second = rng.integers(0, np.maximum(room - 1, 1))
    second += second >= first  # the other of two distinct offsets
    offsets = np.stack([first, second], axis=1)
    keep = np.arange(nested_per)[None, :] < room[:, None]
    parent_role = np.repeat(np.arange(n_roles), nested_per)[keep.reshape(-1)]
    child_role = parent_role + 1 + offsets.reshape(-1)[keep.reshape(-1)]
    nested = TupleColumns(
        ns=np.full(len(parent_role), "rbac", "U4"),
        obj=np.char.add("role", parent_role.astype("U7")),
        rel=np.full(len(parent_role), "member", "U6"),
        skind=np.ones(len(parent_role), np.int8),
        sns=np.full(len(parent_role), "rbac", "U4"),
        sobj=np.char.add("role", child_role.astype("U7")),
        srel=np.full(len(parent_role), "member", "U6"),
    )
    return concat_columns([direct, nested])
