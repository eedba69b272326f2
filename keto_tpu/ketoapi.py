"""Public (string-based) API types and encodings.

Parity with the reference's `ketoapi` package:
  - types: ketoapi/public_api_definitions.go (RelationTuple :24-50,
    SubjectSet :53-68, RelationQuery :71-91, PatchDelta/PatchAction :93-105,
    TreeNodeType :138-147, Tree :171-183, GetResponse :114-121)
  - canonical string form "ns:obj#rel@sub" / "ns:obj#rel@(ns:obj#rel)":
    ketoapi/enc_string.go:13-95
  - URL-query form: ketoapi/enc_url_query.go:12-127
  - tree rendering for CLI output: ketoapi/enc_string.go:97-153

Subjects are polymorphic: a plain subject id (str) or a SubjectSet; exactly
one must be set on a tuple (CHECK-constraint exclusivity in the reference,
internal/persistence/sql/relationtuples.go).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from enum import Enum
from typing import Mapping, Optional, Union

from .errors import (
    DroppedSubjectKeyError,
    DuplicateSubjectError,
    IncompleteSubjectError,
    IncompleteTupleError,
    MalformedInputError,
    NilSubjectError,
    UnknownNodeTypeError,
)

__all__ = [
    "SubjectSet",
    "Subject",
    "RelationTuple",
    "CheckColumns",
    "RelationQuery",
    "PatchAction",
    "PatchDelta",
    "TreeNodeType",
    "Tree",
    "GetResponse",
    "subject_from_string",
    "subject_to_string",
]

# URL-query keys, ref: ketoapi/public_api_definitions.go:107-112
SUBJECT_ID_KEY = "subject_id"
SUBJECT_SET_NAMESPACE_KEY = "subject_set.namespace"
SUBJECT_SET_OBJECT_KEY = "subject_set.object"
SUBJECT_SET_RELATION_KEY = "subject_set.relation"


@dataclass(frozen=True)
class SubjectSet:
    """A set of subjects: all subjects that have `relation` on `object` in
    `namespace`. Ref: ketoapi/public_api_definitions.go:53-68."""

    namespace: str
    object: str
    relation: str

    def __str__(self) -> str:
        # ref: ketoapi/enc_string.go:75-77
        return f"{self.namespace}:{self.object}#{self.relation}"

    @classmethod
    def from_string(cls, s: str) -> "SubjectSet":
        # ref: ketoapi/enc_string.go:79-95
        namespace_and_object, sep, relation = s.partition("#")
        if not sep:
            raise MalformedInputError(debug="expected subject set to contain '#'")
        namespace, sep, obj = namespace_and_object.partition(":")
        if not sep:
            raise MalformedInputError(debug="expected subject set to contain ':'")
        return cls(namespace=namespace, object=obj, relation=relation)

    def to_dict(self) -> dict:
        return {
            "namespace": self.namespace,
            "object": self.object,
            "relation": self.relation,
        }

    @classmethod
    def from_dict(cls, d: Mapping) -> "SubjectSet":
        try:
            return cls(
                namespace=d["namespace"], object=d["object"], relation=d["relation"]
            )
        except KeyError:
            raise IncompleteSubjectError()

    def unique_id(self) -> str:
        """Stable identity used by visited-set cycle detection.
        Ref: internal/relationtuple definitions' Subject.UniqueID."""
        return str(self)


# A subject is either a plain subject id (str) or a SubjectSet.
Subject = Union[str, SubjectSet]


def subject_from_string(s: str) -> Subject:
    """Parse a subject: anything containing '#' is a subject set; optional
    surrounding parens are stripped. Ref: ketoapi/enc_string.go:60-70."""
    s = s.strip("()")
    if "#" in s:
        return SubjectSet.from_string(s)
    return s


def subject_to_string(sub: Subject) -> str:
    if isinstance(sub, SubjectSet):
        return str(sub)
    return sub


# Stable identity used by visited-set cycle detection (Subject.UniqueID in
# the reference); identical to the canonical string form.
subject_unique_id = subject_to_string


def _subject_fields_from_dict(d: Mapping) -> tuple[Optional[str], Optional[SubjectSet]]:
    if "subject" in d:
        raise DroppedSubjectKeyError()
    subject_id = d.get("subject_id")
    raw_set = d.get("subject_set")
    if subject_id is not None and raw_set is not None:
        raise DuplicateSubjectError()
    subject_set = SubjectSet.from_dict(raw_set) if raw_set is not None else None
    return subject_id, subject_set


@dataclass
class RelationTuple:
    """A relation tuple: subject has `relation` on `object` in `namespace`.
    Exactly one of subject_id / subject_set is set.
    Ref: ketoapi/public_api_definitions.go:24-50."""

    namespace: str
    object: str
    relation: str
    subject_id: Optional[str] = None
    subject_set: Optional[SubjectSet] = None

    def __post_init__(self):
        if self.subject_id is not None and self.subject_set is not None:
            raise DuplicateSubjectError()

    # -- subject polymorphism -------------------------------------------------

    @property
    def subject(self) -> Subject:
        if self.subject_id is not None:
            return self.subject_id
        if self.subject_set is not None:
            return self.subject_set
        raise NilSubjectError()

    def with_subject(self, sub: Subject) -> "RelationTuple":
        t = RelationTuple(self.namespace, self.object, self.relation)
        if isinstance(sub, SubjectSet):
            t.subject_set = sub
        else:
            t.subject_id = sub
        return t

    @classmethod
    def make(
        cls, namespace: str, object: str, relation: str, subject: Subject
    ) -> "RelationTuple":
        t = cls(namespace=namespace, object=object, relation=relation)
        return t.with_subject(subject)

    # -- canonical string form ------------------------------------------------

    def __str__(self) -> str:
        # ref: ketoapi/enc_string.go:13-39
        if self.subject_id is not None:
            sub = self.subject_id
        elif self.subject_set is not None:
            sub = f"({self.subject_set})"
        else:
            sub = "<ERROR: no subject>"
        return f"{self.namespace}:{self.object}#{self.relation}@{sub}"

    @classmethod
    def from_string(cls, s: str) -> "RelationTuple":
        # ref: ketoapi/enc_string.go:41-73
        namespace, sep, rest = s.partition(":")
        if not sep:
            raise MalformedInputError(debug="expected input to contain ':'")
        obj, sep, rest = rest.partition("#")
        if not sep:
            raise MalformedInputError(debug="expected input to contain '#'")
        relation, sep, subject = rest.partition("@")
        if not sep:
            raise MalformedInputError(debug="expected input to contain '@'")
        t = cls(namespace=namespace, object=obj, relation=relation)
        return t.with_subject(subject_from_string(subject))

    # -- JSON form (proto JSON field names) -----------------------------------

    def to_dict(self) -> dict:
        d = {
            "namespace": self.namespace,
            "object": self.object,
            "relation": self.relation,
        }
        if self.subject_id is not None:
            d["subject_id"] = self.subject_id
        elif self.subject_set is not None:
            d["subject_set"] = self.subject_set.to_dict()
        return d

    @classmethod
    def from_dict(cls, d: Mapping) -> "RelationTuple":
        subject_id, subject_set = _subject_fields_from_dict(d)
        if subject_id is None and subject_set is None:
            raise NilSubjectError()
        if "namespace" not in d or "object" not in d or "relation" not in d:
            raise IncompleteTupleError()
        return cls(
            namespace=d["namespace"],
            object=d["object"],
            relation=d["relation"],
            subject_id=subject_id,
            subject_set=subject_set,
        )

    # -- URL-query form -------------------------------------------------------

    def to_url_query(self) -> dict[str, str]:
        return self.to_query().to_url_query()

    @classmethod
    def from_url_query(cls, query: Mapping[str, str]) -> "RelationTuple":
        # ref: ketoapi/enc_url_query.go:78-97
        q = RelationQuery.from_url_query(query)
        if q.subject_id is None and q.subject_set is None:
            raise NilSubjectError()
        if q.namespace is None or q.object is None or q.relation is None:
            raise IncompleteTupleError()
        return cls(
            namespace=q.namespace,
            object=q.object,
            relation=q.relation,
            subject_id=q.subject_id,
            subject_set=q.subject_set,
        )

    def to_query(self) -> "RelationQuery":
        return RelationQuery(
            namespace=self.namespace,
            object=self.object,
            relation=self.relation,
            subject_id=self.subject_id,
            subject_set=self.subject_set,
        )

    def _key(self) -> tuple:
        return (
            self.namespace,
            self.object,
            self.relation,
            self.subject_id,
            self.subject_set,
        )

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, RelationTuple) and self._key() == other._key()


class CheckColumns:
    """The items of one batch of checks as seven parallel lists, the form
    a BatchCheck has on the wire and the query encoder wants: namespace,
    object, relation, subject kind (1 for a subject set, 0 for a plain
    id), and the subject's namespace, object-or-id and relation ("" in
    the two a plain id lacks). It reads as a sequence of RelationTuples,
    each built when it is asked for and not before: a 2,048-item batch
    that the device answers whole builds none."""

    __slots__ = ("ns", "obj", "rel", "skind", "sns", "sobj", "srel")

    def __init__(self, ns, obj, rel, skind, sns, sobj, srel):
        self.ns, self.obj, self.rel = ns, obj, rel
        self.skind, self.sns, self.sobj, self.srel = skind, sns, sobj, srel

    @classmethod
    def of(cls, tuples) -> "CheckColumns":
        """`tuples` itself where it already is columns, else the columns
        of a run of RelationTuples (a tuple without a subject reads as
        the plain id "", as the query encoder has always read it)."""
        if isinstance(tuples, cls):
            return tuples
        n = len(tuples)
        ns = [t.namespace for t in tuples]
        obj = [t.object for t in tuples]
        rel = [t.relation for t in tuples]
        sobj = [t.subject_id or "" for t in tuples]
        skind, sns, srel = [0] * n, [""] * n, [""] * n
        for i, s in enumerate([t.subject_set for t in tuples]):
            if s is not None:
                skind[i] = 1
                sns[i], sobj[i], srel[i] = s.namespace, s.object, s.relation
        return cls(ns, obj, rel, skind, sns, sobj, srel)

    def columns(self) -> tuple:
        return (
            self.ns, self.obj, self.rel,
            self.skind, self.sns, self.sobj, self.srel,
        )

    def __len__(self) -> int:
        return len(self.ns)

    def tuple_at(self, i: int) -> RelationTuple:
        t = RelationTuple(self.ns[i], self.obj[i], self.rel[i])
        if self.skind[i]:
            t.subject_set = SubjectSet(self.sns[i], self.sobj[i], self.srel[i])
        else:
            t.subject_id = self.sobj[i]
        return t

    def __getitem__(self, i):
        """Row i as a RelationTuple, or a slice as columns."""
        if isinstance(i, slice):
            return CheckColumns(*(col[i] for col in self.columns()))
        return self.tuple_at(i)

    def __iter__(self):
        return map(self.tuple_at, range(len(self)))

    def take(self, rows) -> "CheckColumns":
        """The given rows, in the given order, as columns."""
        return CheckColumns(*([col[i] for i in rows] for col in self.columns()))

    def subjects(self) -> list:
        """Every row's subject: the id string, or the SubjectSet of a
        subject-set row. Not to be written to: without a subject set
        among the rows it is the id column itself."""
        if 1 not in self.skind:
            return self.sobj
        return [
            SubjectSet(sns, sobj, srel) if kind else sobj
            for kind, sns, sobj, srel in zip(
                self.skind, self.sns, self.sobj, self.srel
            )
        ]


@dataclass
class RelationQuery:
    """Query over tuples; None fields are wildcards.
    Ref: ketoapi/public_api_definitions.go:71-91."""

    namespace: Optional[str] = None
    object: Optional[str] = None
    relation: Optional[str] = None
    subject_id: Optional[str] = None
    subject_set: Optional[SubjectSet] = None

    def __post_init__(self):
        if self.subject_id is not None and self.subject_set is not None:
            raise DuplicateSubjectError()

    @property
    def subject(self) -> Optional[Subject]:
        if self.subject_id is not None:
            return self.subject_id
        return self.subject_set

    @classmethod
    def make(cls, namespace=None, object=None, relation=None, subject=None):
        q = cls(namespace=namespace, object=object, relation=relation)
        if subject is not None:
            if isinstance(subject, SubjectSet):
                q.subject_set = subject
            else:
                q.subject_id = subject
        return q

    # -- URL-query form, ref: ketoapi/enc_url_query.go:12-76 -----------------

    @classmethod
    def from_url_query(cls, query: Mapping[str, str]) -> "RelationQuery":
        if "subject" in query:
            raise DroppedSubjectKeyError()
        q = cls()
        has_sid = SUBJECT_ID_KEY in query
        has_ss = (
            SUBJECT_SET_NAMESPACE_KEY in query
            or SUBJECT_SET_OBJECT_KEY in query
            or SUBJECT_SET_RELATION_KEY in query
        )
        has_full_ss = (
            SUBJECT_SET_NAMESPACE_KEY in query
            and SUBJECT_SET_OBJECT_KEY in query
            and SUBJECT_SET_RELATION_KEY in query
        )
        if not has_sid and not has_ss:
            pass  # not queried for the subject
        elif has_sid and has_ss:
            raise DuplicateSubjectError(
                debug=f"please provide either {SUBJECT_ID_KEY} or all of "
                f"{SUBJECT_SET_NAMESPACE_KEY}, {SUBJECT_SET_OBJECT_KEY}, "
                f"and {SUBJECT_SET_RELATION_KEY}"
            )
        elif has_sid:
            q.subject_id = query[SUBJECT_ID_KEY]
        elif has_full_ss:
            q.subject_set = SubjectSet(
                namespace=query[SUBJECT_SET_NAMESPACE_KEY],
                object=query[SUBJECT_SET_OBJECT_KEY],
                relation=query[SUBJECT_SET_RELATION_KEY],
            )
        else:
            raise IncompleteSubjectError()

        if "namespace" in query:
            q.namespace = query["namespace"]
        if "object" in query:
            q.object = query["object"]
        if "relation" in query:
            q.relation = query["relation"]
        return q

    def to_url_query(self) -> dict[str, str]:
        v: dict[str, str] = {}
        if self.namespace is not None:
            v["namespace"] = self.namespace
        if self.relation is not None:
            v["relation"] = self.relation
        if self.object is not None:
            v["object"] = self.object
        if self.subject_id is not None:
            v[SUBJECT_ID_KEY] = self.subject_id
        elif self.subject_set is not None:
            v[SUBJECT_SET_NAMESPACE_KEY] = self.subject_set.namespace
            v[SUBJECT_SET_OBJECT_KEY] = self.subject_set.object
            v[SUBJECT_SET_RELATION_KEY] = self.subject_set.relation
        return v

    def to_dict(self) -> dict:
        d: dict = {}
        if self.namespace is not None:
            d["namespace"] = self.namespace
        if self.object is not None:
            d["object"] = self.object
        if self.relation is not None:
            d["relation"] = self.relation
        if self.subject_id is not None:
            d["subject_id"] = self.subject_id
        elif self.subject_set is not None:
            d["subject_set"] = self.subject_set.to_dict()
        return d

    @classmethod
    def from_dict(cls, d: Mapping) -> "RelationQuery":
        subject_id, subject_set = _subject_fields_from_dict(d)
        return cls(
            namespace=d.get("namespace"),
            object=d.get("object"),
            relation=d.get("relation"),
            subject_id=subject_id,
            subject_set=subject_set,
        )

    def matches(self, t: RelationTuple) -> bool:
        """Does tuple t satisfy this query? (host-store filtering)"""
        if self.namespace is not None and t.namespace != self.namespace:
            return False
        if self.object is not None and t.object != self.object:
            return False
        if self.relation is not None and t.relation != self.relation:
            return False
        if self.subject_id is not None and t.subject_id != self.subject_id:
            return False
        if self.subject_set is not None and t.subject_set != self.subject_set:
            return False
        return True


class PatchAction(str, Enum):
    # ref: ketoapi/public_api_definitions.go:99-105
    INSERT = "insert"
    DELETE = "delete"


@dataclass
class PatchDelta:
    # ref: ketoapi/public_api_definitions.go:93-97
    action: PatchAction
    relation_tuple: RelationTuple

    def to_dict(self) -> dict:
        return {
            "action": self.action.value,
            "relation_tuple": self.relation_tuple.to_dict(),
        }

    @classmethod
    def from_dict(cls, d: Mapping) -> "PatchDelta":
        try:
            action = PatchAction(d["action"])
        except (KeyError, ValueError):
            raise MalformedInputError(debug="unknown patch action")
        raw_tuple = d.get("relation_tuple")
        if not isinstance(raw_tuple, Mapping):
            raise MalformedInputError(debug='missing "relation_tuple"')
        return cls(action=action, relation_tuple=RelationTuple.from_dict(raw_tuple))


class TreeNodeType(str, Enum):
    # ref: ketoapi/public_api_definitions.go:138-147
    UNION = "union"
    EXCLUSION = "exclusion"
    INTERSECTION = "intersection"
    LEAF = "leaf"
    TUPLE_TO_SUBJECT_SET = "tuple_to_subject_set"
    COMPUTED_SUBJECT_SET = "computed_subject_set"
    NOT = "not"
    UNSPECIFIED = "unspecified"

    @classmethod
    def parse(cls, s: str) -> "TreeNodeType":
        try:
            return cls(s)
        except ValueError:
            raise UnknownNodeTypeError()


@dataclass
class Tree:
    """A proof/expand tree node. Ref: ketoapi/public_api_definitions.go:171-183.
    `tuple` is the relation tuple this node represents; for expand trees the
    node's subject is carried in the tuple's subject fields (the reference maps
    internal subject-only nodes the same way, internal/relationtuple/
    uuid_mapping.go:307-356)."""

    type: TreeNodeType
    tuple: Optional[RelationTuple] = None
    children: list["Tree"] = field(default_factory=list)

    def to_dict(self) -> dict:
        d: dict = {"type": self.type.value}
        d["tuple"] = self.tuple.to_dict() if self.tuple is not None else None
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    @classmethod
    def from_dict(cls, d: Mapping) -> "Tree":
        if "type" not in d:
            raise UnknownNodeTypeError()
        t = cls(type=TreeNodeType.parse(d["type"]))
        if d.get("tuple") is not None:
            t.tuple = RelationTuple.from_dict(d["tuple"])
        t.children = [cls.from_dict(c) for c in d.get("children") or []]
        return t

    def label(self) -> str:
        return str(self.tuple) if self.tuple is not None else ""

    def __str__(self) -> str:
        # CLI rendering, ref: ketoapi/enc_string.go:109-152
        label = self.label()
        if self.type == TreeNodeType.LEAF:
            return f"∋ {label}️"
        children = []
        n = len(self.children)
        for i, c in enumerate(self.children):
            indent = "   " if i == n - 1 else "│  "
            children.append(("\n" + indent).join(str(c).split("\n")))
        set_op = {
            TreeNodeType.INTERSECTION: "and",
            TreeNodeType.UNION: "or",
            TreeNodeType.EXCLUSION: "\\",
            TreeNodeType.NOT: "not",
            TreeNodeType.TUPLE_TO_SUBJECT_SET: "┐ tuple to userset",
            TreeNodeType.COMPUTED_SUBJECT_SET: "┐ computed userset",
        }.get(self.type, "")
        box = "└" if len(children) == 1 else "├"
        return f"{set_op} {label}\n{box}──" + "\n└──".join(children)


@dataclass
class GetResponse:
    # ref: ketoapi/public_api_definitions.go:114-121
    relation_tuples: list[RelationTuple]
    next_page_token: str = ""

    def to_dict(self) -> dict:
        return {
            "relation_tuples": [t.to_dict() for t in self.relation_tuples],
            "next_page_token": self.next_page_token,
        }
