"""Keto's own rewrite namespaces (internal/check/rewrites_test.go:20-100):
resources owned by groups and held at a clearance level, and access lists
with a deny list, at a size. Every rewrite is Keto's:

    resource#delete = owner AND level->member
    resource#read   = viewer OR owner   (each owner->member)
    acl#access      = allow AND NOT deny

The construction, for every seed alike (the seed draws who, never how
many, so no seed compiles another program):

- users = tuples / 100, each a member of exactly one of 4 levels;
- groups = users / 4, each of 12 distinct direct members, 3 from each
  level, none nested;
- resources and acl documents in equal numbers, N each: a resource has
  `owner@(group:g#...)`, one direct `owner@u` and `level@(level:l#...)`;
  a document has 4 distinct `allow` users and one `deny` among them;
- co-owners on the first resources make the count exact.

`Truth` is what the construction fixes and costs little to make, so the
load generator rebuilds it from the seed. Target t asks, by t mod 5,
`delete` (0, 1), `access` (2, 3) or `read` (4) of resource or document
t // 5. An allowed item asks for a member of the owner group in the
resource's level (`delete`), an allowed user not denied (`access`), or a
member of the owner group or the direct owner (`read`). A denied item fails
on its circuit's last leaf where it has one: a member of the owner group
outside the level (`delete`), the denied user (`access`); `read` is denied
to a subject that holds nothing.
"""

from __future__ import annotations

import zlib

import numpy as np

from .drive import _distinct

LEVELS = 4
PER_LEVEL = 3  # members of a group from each level
MEMBERS = LEVELS * PER_LEVEL
ALLOW = 4  # allowed users of an acl document, one of them denied
KINDS = ("delete", "delete", "access", "access", "read")


class Truth:
    def __init__(self, params: dict, seed: int, tuples: int):
        self.tuples = tuples
        self.n_users = max(MEMBERS, tuples // params["tuples_per_user"])
        self.n_groups = max(1, self.n_users // params["users_per_group"])
        rest = tuples - self.n_users - MEMBERS * self.n_groups
        self.n_objects = rest // (3 + ALLOW + 1)  # resources, and documents
        self.n_filler = rest - (3 + ALLOW + 1) * self.n_objects
        if self.n_objects < 1 or self.n_filler > self.n_objects:
            raise ValueError(f"cannot make {tuples} tuples exact")
        self.n_targets = len(KINDS) * self.n_objects

        rng = np.random.default_rng(seed)
        order = rng.permutation(self.n_users)
        self.level_of = np.empty(self.n_users, np.int64)
        self.level_of[order] = np.arange(self.n_users) % LEVELS
        # members[g, PER_LEVEL * l + j]: the j-th member of group g in level l
        self.members = np.concatenate([
            pool[_distinct(rng, self.n_groups, PER_LEVEL, len(pool))]
            for pool in (order[lv::LEVELS] for lv in range(LEVELS))
        ], axis=1)
        n = self.n_objects
        self.group = rng.integers(0, self.n_groups, n)
        self.owner = rng.integers(0, self.n_users, n)
        self.level = rng.integers(0, LEVELS, n)
        self.allow = _distinct(rng, n, ALLOW, self.n_users)
        self.deny = rng.integers(0, ALLOW, n)  # which allowed user is denied

    def query(self, target: int, allowed: bool, nonce: str):
        """(namespace, object, relation, subject id) of check `target`;
        `nonce` picks among the subjects that give the answer."""
        i = target // len(KINDS)
        kind = KINDS[target % len(KINDS)]
        pick = zlib.crc32(nonce.encode())
        if kind == "access":
            slot = (int(self.deny[i]) + (1 + pick % (ALLOW - 1) if allowed else 0)) % ALLOW
            return "acl", f"d{i}", "access", f"u{self.allow[i, slot]}"
        if kind == "delete":
            lv = int(self.level[i])
            if not allowed:
                lv = (lv + 1 + pick % (LEVELS - 1)) % LEVELS
            k = PER_LEVEL * lv + (pick // LEVELS) % PER_LEVEL
            return "resource", f"r{i}", "delete", f"u{self.members[self.group[i], k]}"
        if not allowed:
            return "resource", f"r{i}", "read", f"nobody{nonce}"
        k = pick % (MEMBERS + 1)
        user = self.owner[i] if k == MEMBERS else self.members[self.group[i], k]
        return "resource", f"r{i}", "read", f"u{user}"


def _columns(ns, obj, rel, sobj, sns="", srel=""):
    """TupleColumns of direct subjects (`sns` empty) or of subject sets."""
    from keto_tpu.storage.columns import TupleColumns

    n = len(obj)
    return TupleColumns(
        ns=np.full(n, ns),
        obj=obj,
        rel=np.full(n, rel),
        skind=np.full(n, 1 if sns else 0, np.int8),
        sns=np.full(n, sns, f"U{max(len(sns), 1)}"),
        sobj=sobj,
        srel=np.full(n, srel, f"U{max(len(srel), 1)}"),
    )


def _names(prefix: str, ids) -> np.ndarray:
    return np.char.add(prefix, np.asarray(ids).astype("U10"))


def columns(truth: Truth):
    """Exactly `truth.tuples` relation tuples, as the module docstring
    lays them out."""
    from keto_tpu.storage.columns import concat_columns

    n = truth.n_objects
    users = np.arange(truth.n_users)
    resources = _names("r", np.arange(n))
    documents = _names("d", np.arange(n))
    return concat_columns([
        _columns("level", _names("l", truth.level_of), "member", _names("u", users)),
        _columns(
            "group", _names("g", np.repeat(np.arange(truth.n_groups), MEMBERS)),
            "member", _names("u", truth.members.reshape(-1)),
        ),
        _columns("resource", resources, "owner", _names("g", truth.group),
                 sns="group", srel="..."),
        _columns("resource", resources, "owner", _names("u", truth.owner)),
        _columns("resource", resources, "level", _names("l", truth.level),
                 sns="level", srel="..."),
        _columns("acl", np.repeat(documents, ALLOW), "allow",
                 _names("u", truth.allow.reshape(-1))),
        _columns("acl", documents, "deny",
                 _names("u", truth.allow[np.arange(n), truth.deny])),
        _columns("resource", resources[: truth.n_filler], "owner",
                 _names("co", np.arange(truth.n_filler))),
    ])
