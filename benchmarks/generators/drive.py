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


def _rbac_columns(n_roles: int, n_users: int, seed: int):
    """Each role holds 12 user members and 2 nested roles of higher id (the
    graph stays acyclic). A role may draw one member twice: only the tuples
    the store will keep are returned."""
    from keto_tpu.storage.columns import TupleColumns, concat_columns

    rng = np.random.default_rng(seed)
    members_per, nested_per = 12, 2
    role_of = np.repeat(np.arange(n_roles), members_per)
    member = rng.integers(0, n_users, n_roles * members_per)
    keep = np.sort(np.unique(role_of * n_users + member, return_index=True)[1])
    role_of, member = role_of[keep], member[keep]
    direct = TupleColumns(
        ns=np.full(len(keep), "rbac", "U4"),
        obj=np.char.add("role", role_of.astype("U7")),
        rel=np.full(len(keep), "member", "U6"),
        skind=np.zeros(len(keep), np.int8),
        sns=np.full(len(keep), "", "U1"),
        sobj=np.char.add("u", member.astype("U10")),
        srel=np.full(len(keep), "", "U1"),
    )
    n_nest = n_roles * nested_per
    parent_role = np.repeat(np.arange(n_roles), nested_per)
    child_role = np.minimum(
        parent_role + 1 + rng.integers(0, 97, n_nest), n_roles - 1
    )
    keep = np.sort(
        np.unique(parent_role * n_roles + child_role, return_index=True)[1]
    )
    parent_role, child_role = parent_role[keep], child_role[keep]
    nested = TupleColumns(
        ns=np.full(len(keep), "rbac", "U4"),
        obj=np.char.add("role", parent_role.astype("U7")),
        rel=np.full(len(keep), "member", "U6"),
        skind=np.ones(len(keep), np.int8),
        sns=np.full(len(keep), "rbac", "U4"),
        sobj=np.char.add("role", child_role.astype("U7")),
        srel=np.full(len(keep), "member", "U6"),
    )
    return concat_columns([direct, nested])
