"""ketolint — repo-invariant checker (`python -m keto_tpu.analysis.lint`).

Six AST passes encode the invariants the codebase lives by; each was
prose in CHANGES.md / code comments until this tier existed. Pure
stdlib: runs before deps are installed, in CI's analysis job and beside
the metrics-golden check in the test job.

Rules
-----
lock-blocking-call   No blocking work inside a held-lock region: no
                     `time.sleep`, `Future.result`, blocking queue
                     `.get`, thread `.join`, foreign `.wait`, no
                     store/manager calls, no listener/callback fires.
                     A "locked region" is the body of `with <lock>` for
                     a lock-named context (`*_lock`, `*_mu`, `*_cond`),
                     the body of any `*_locked` method (the repo's
                     caller-holds-the-lock naming convention), and —
                     one fixpoint step further — any private method
                     whose every intra-file call site sits in a locked
                     region.
typed-error          Transport modules (rest_server / grpc_server /
                     aio_server) surface only KetoError subclasses;
                     nowhere in the package may a bare `except:` or a
                     silent `except Exception: pass` swallow errors.
config-key           Every literal dotted `config.get("a.b.c")` key
                     exists in config_schema.json, and every schema
                     leaf is read somewhere (an ancestor-object read
                     covers its subtree) — dead keys fail, the config
                     analog of the metrics-golden check.
clock-monotonic      Deadline/backoff/retry math uses `time.monotonic`
                     (or perf_counter); `time.time()` / naive
                     `datetime.now()` never appear in keto_tpu. Wall
                     clocks jump (NTP, suspend) and break deadlines.
host-sync            Inside the engine batch hot path (check/list/
                     expand submit+resolve), every device
                     synchronization — `np.asarray` readback,
                     `.block_until_ready()`, `jax.device_get`, scalar
                     `int()`/`float()` coercion of a device value, or a
                     fresh `jax.jit` — must be an annotated sync point.
one-program          Under engine/ and parallel/ nothing asks which
                     backend it runs on: no `jax.default_backend()`, no
                     comparison of a device's `.platform`. The CPU
                     traces the program the chip runs, so tier-1 tests
                     what the benchmark's cells measure. (The registry's
                     `check.platform` pin is a deployment setting and
                     lies outside.)

Suppressions: `# ketolint: allow[<rule>] reason=...` on the offending
line or the line directly above. A reasonless allow and an allow that
matches no finding are both errors (rule `suppression`) — annotations
carry their justification in-code and can never rot silently.
"""

from __future__ import annotations

import ast
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

from .source_scan import (
    config_key_reads,
    iter_py_files,
    key_matches,
    package_root,
    read_text,
    repo_root,
    schema_key_tree,
)

RULES = {
    "lock-blocking-call": "blocking call inside a held-lock region",
    "typed-error": "transport boundary must surface typed KetoErrors",
    "config-key": "config keys must exist in the schema and be read",
    "clock-monotonic": "deadline/backoff math must use a monotonic clock",
    "host-sync": "device sync in the batch hot path must be annotated",
    "one-program": "engine and parallel code must not branch on the backend",
    "suppression": "ketolint allow[] annotations must carry a reason and match a finding",
}

# transport boundary modules for the typed-error raise check
_BOUNDARY_FILES = {"rest_server.py", "grpc_server.py", "aio_server.py"}
# engine modules whose hot-path functions the host-sync pass inspects
_HOT_FILES = {
    "tpu_engine.py", "kernel.py", "reverse_kernel.py", "expand_kernel.py",
    "closure_kernel.py", "closure_power.py",
}
# `_inner` variants: the public hot entry points wrap their bodies in a
# launch-id-stamping try/except (engine flight recorder); the moved-out
# body keeps the `<public>_inner` name precisely so this pass keeps
# inspecting it — renaming a hot body out of coverage must not be possible
# by accident
_HOT_FUNCS = re.compile(
    r"^_?(check_batch_submit|check_batch_resolve(_v)?|check_batch"
    r"|closure_batch_resolve(_v)?|closure_power_resolve"
    r"|list_objects_batch|list_subjects_batch|expand_batch"
    r"|filter_batch|filter_chunk)(_inner)?$"
)

# packages that build the device programs, for the one-program pass
_PROGRAM_DIRS = {"engine", "parallel"}

# a with-context (or receiver) names a lock when its final segment does
_LOCK_NAME = re.compile(r"(^|_)(lock|mu|mutex|cond)\d*$")
# attribute names that hold listener/callback collections
_LISTENER_NAME = re.compile(r"(_listeners?|_notify_fns|_callbacks|_hooks)$")
# receivers that denote the store/manager layer
_STORE_SEGMENT = re.compile(r"^_?(manager|store)$")

_ALLOW = re.compile(
    r"#\s*ketolint:\s*allow\[([a-z\-,\s]+)\](?:\s+reason=(.*\S))?\s*$"
)


@dataclass
class Finding:
    path: Path
    line: int
    rule: str
    msg: str

    def render(self, root: Path) -> str:
        try:
            rel = self.path.resolve().relative_to(root.resolve())
        except ValueError:
            rel = self.path
        return f"{rel}:{self.line}: {self.rule}: {self.msg}"


@dataclass
class _Suppression:
    rule: str
    line: int  # the line this allow covers
    comment_line: int
    has_reason: bool
    used: bool = False


@dataclass
class FileCtx:
    path: Path
    text: str
    tree: ast.Module
    suppressions: list[_Suppression] = field(default_factory=list)


def _parse_suppressions(path: Path, text: str) -> list[_Suppression]:
    out: list[_Suppression] = []
    lines = text.splitlines()
    for i, raw in enumerate(lines, start=1):
        m = _ALLOW.search(raw)
        if m is None:
            continue
        rules = [r.strip() for r in m.group(1).split(",") if r.strip()]
        # a comment-only line covers the next source line; a trailing
        # comment covers its own line
        covered = i + 1 if raw.lstrip().startswith("#") else i
        for rule in rules:
            out.append(
                _Suppression(
                    rule=rule,
                    line=covered,
                    comment_line=i,
                    has_reason=bool(m.group(2)),
                )
            )
    return out


def load_file(path: Path) -> Optional[FileCtx]:
    text = read_text(path)
    try:
        tree = ast.parse(text, filename=str(path))
    except SyntaxError as e:
        raise SystemExit(f"ketolint: cannot parse {path}: {e}")
    return FileCtx(path, text, tree, _parse_suppressions(path, text))


# -- shared AST helpers --------------------------------------------------------


def _attr_chain(node: ast.AST) -> list[str]:
    """['self', '_queue', 'get'] for self._queue.get — outermost first."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    parts.reverse()
    return parts


def _is_lock_expr(node: ast.AST) -> bool:
    chain = _attr_chain(node)
    return bool(chain) and _LOCK_NAME.search(chain[-1]) is not None


def _expr_key(node: ast.AST) -> str:
    return ".".join(_attr_chain(node))


def _walk_no_nested_defs(body: list[ast.stmt], skip_with: bool = False):
    """Walk statements without descending into nested function/class
    bodies (code defined under a lock does not RUN under it).
    `skip_with=True` additionally yields nested With nodes WITHOUT
    descending into them — the lock-discipline pass recurses into those
    bodies itself so inner lock keys stay scoped to the inner body."""
    stack: list[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        yield node
        if skip_with and isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        for child in ast.iter_child_nodes(node):
            if isinstance(
                child,
                (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef),
            ):
                continue
            stack.append(child)


# -- pass 1: lock discipline ---------------------------------------------------


def _blocking_findings(
    path: Path, body: list[ast.stmt], lock_keys: set[str], where: str
) -> list[Finding]:
    out: list[Finding] = []

    def finding(node: ast.AST, msg: str) -> None:
        out.append(Finding(path, node.lineno, "lock-blocking-call", f"{msg} {where}"))

    for node in _walk_no_nested_defs(body, skip_with=True):
        # nested with on another lock: its body is still under the outer
        # lock; RECURSE so the inner lock/cond key is scoped to that
        # body only (a leaked key would exempt a sibling's foreign
        # .wait from the check). Non-lock context exprs ride the
        # recursion as bare expressions so a blocking call in the with
        # HEADER is still scanned.
        if isinstance(node, (ast.With, ast.AsyncWith)):
            inner = {
                _expr_key(i.context_expr)
                for i in node.items
                if _is_lock_expr(i.context_expr)
            }
            headers: list[ast.stmt] = [
                ast.Expr(value=i.context_expr)
                for i in node.items
                if not _is_lock_expr(i.context_expr)
            ]
            out.extend(
                _blocking_findings(
                    path, headers + node.body, lock_keys | inner, where
                )
            )
            continue
        # listener/callback fire: `for fn in <...listeners...>: fn(...)`
        # (the loop body also keeps riding the generic walk below, so a
        # sleep inside a for-loop under the lock still trips)
        if isinstance(node, ast.For):
            it_names = [
                n.attr
                for n in ast.walk(node.iter)
                if isinstance(n, ast.Attribute)
            ] + [n.id for n in ast.walk(node.iter) if isinstance(n, ast.Name)]
            if any(_LISTENER_NAME.search(n) for n in it_names) and isinstance(
                node.target, ast.Name
            ):
                tgt = node.target.id
                for sub in _walk_no_nested_defs(node.body):
                    if (
                        isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Name)
                        and sub.func.id == tgt
                    ):
                        finding(sub, "listener/callback fired")
            continue
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not isinstance(func, ast.Attribute):
            continue
        attr = func.attr
        chain = _attr_chain(func)
        recv = chain[:-1]
        if attr == "sleep" and recv and recv[-1] in ("time", "_time"):
            finding(node, "time.sleep")
        elif attr == "result":
            finding(node, "Future.result wait")
        elif attr == "join" and recv:
            finding(node, f"{'.'.join(recv)}.join")
        elif attr == "get" and recv and "queue" in recv[-1].lower():
            finding(node, "blocking queue.get")
        elif attr == "wait":
            # waiting on the held lock's own condition releases it (the
            # Condition contract) — `with self._cond: self._cond.wait()`
            # and the sibling pairing `with state.lock: state.cond.wait()`
            # are fine; waiting on anything else (an Event, a foreign
            # condition) blocks while holding
            key = _expr_key(func.value)
            base = ".".join(chain[:-2])
            receiver_is_cond = bool(recv) and _LOCK_NAME.search(recv[-1])
            paired = key in lock_keys or (
                receiver_is_cond
                and base
                and any(lk.rsplit(".", 1)[0] == base for lk in lock_keys)
            )
            if not paired:
                finding(node, f"{key}.wait")
        elif any(_STORE_SEGMENT.match(seg) for seg in recv):
            finding(node, f"store/manager call {'.'.join(chain)}")
    return out


def pass_lock_discipline(ctx: FileCtx) -> list[Finding]:
    findings: list[Finding] = []
    classes = [n for n in ast.walk(ctx.tree) if isinstance(n, ast.ClassDef)]
    # per-class scopes: same-named methods in different classes must not
    # collide — `self.X()` resolves within ONE class, so the locked-
    # region fixpoint is only sound class-by-class. Module-level
    # functions form one more scope of their own (no `self` call
    # sites there, so only the with-body and *_locked rules apply).
    import types

    module_scope = types.SimpleNamespace(
        body=[
            n
            for n in ctx.tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
    )
    for cls in classes + [module_scope]:
        findings.extend(_lock_discipline_scope(ctx, cls))
    return findings


def _lock_discipline_scope(ctx: FileCtx, cls) -> list[Finding]:
    findings: list[Finding] = []
    funcs: dict[str, ast.FunctionDef] = {}
    for item in cls.body:
        if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            funcs[item.name] = item

    locked_funcs: set[str] = {
        name for name in funcs if name.endswith("_locked")
    }

    # fixpoint: a private method whose every intra-file call site is in a
    # locked region inherits the region (one-file, conservative — a
    # method with zero visible call sites stays unlocked)
    def call_sites(name: str) -> list[tuple[str, ast.Call]]:
        sites = []
        for fname, fnode in funcs.items():
            for node in ast.walk(fnode):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == name
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"
                ):
                    sites.append((fname, node))
        return sites

    def in_locked_region(fname: str, call: ast.Call) -> bool:
        if fname in locked_funcs:
            return True
        fnode = funcs.get(fname)
        if fnode is None:
            return False
        for node in ast.walk(fnode):
            if isinstance(node, (ast.With, ast.AsyncWith)) and any(
                _is_lock_expr(i.context_expr) for i in node.items
            ):
                for sub in _walk_no_nested_defs(node.body):
                    if sub is call:
                        return True
        return False

    changed = True
    while changed:
        changed = False
        for name in funcs:
            if name in locked_funcs or not name.startswith("_"):
                continue
            sites = call_sites(name)
            if sites and all(in_locked_region(f, c) for f, c in sites):
                locked_funcs.add(name)
                changed = True

    # findings inside with-lock bodies (async-with included: blocking
    # calls under an asyncio lock stall the whole event loop)
    for fnode in funcs.values():
        for node in ast.walk(fnode):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                keys = {
                    _expr_key(i.context_expr)
                    for i in node.items
                    if _is_lock_expr(i.context_expr)
                }
                if keys:
                    findings.extend(
                        _blocking_findings(
                            ctx.path, node.body, keys,
                            f"under {'/'.join(sorted(keys))} "
                            f"(in {fnode.name})",
                        )
                    )
    # findings inside *_locked / lock-only-called method bodies
    for name in sorted(locked_funcs):
        fnode = funcs[name]
        # skip `with` bodies inside (already covered above; the rest of
        # the body is lock-held by the caller's contract)
        findings.extend(
            _blocking_findings(
                ctx.path,
                [s for s in fnode.body],
                set(),
                f"in lock-held method {name}",
            )
        )
    # dedupe (a with-body inside a _locked method reports twice)
    seen: set[tuple] = set()
    out = []
    for f in findings:
        k = (f.line, f.msg.split(" under ")[0].split(" in lock-held")[0])
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


# -- pass 2: typed-error boundary ----------------------------------------------


def collect_keto_errors(trees: list[ast.AST]) -> set[str]:
    """Transitive KetoError subclass names across the package."""
    parents: dict[str, set[str]] = {}
    for tree in trees:
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                bases = set()
                for b in node.bases:
                    chain = _attr_chain(b)
                    if chain:
                        bases.add(chain[-1])
                parents.setdefault(node.name, set()).update(bases)
    typed = {"KetoError"}
    changed = True
    while changed:
        changed = False
        for name, bases in parents.items():
            if name not in typed and bases & typed:
                typed.add(name)
                changed = True
    return typed


def pass_typed_error(
    ctx: FileCtx, keto_errors: set[str]
) -> list[Finding]:
    findings: list[Finding] = []
    boundary = ctx.path.name in _BOUNDARY_FILES
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ExceptHandler):
            if node.type is None:
                findings.append(
                    Finding(
                        ctx.path, node.lineno, "typed-error",
                        "bare `except:` — name the exception types",
                    )
                )
                continue
            names = {
                c.id for c in ast.walk(node.type) if isinstance(c, ast.Name)
            }
            swallows = names & {"Exception", "BaseException"}
            body_is_silent = all(
                isinstance(s, (ast.Pass, ast.Continue))
                or (
                    isinstance(s, ast.Expr)
                    and isinstance(s.value, ast.Constant)
                )
                for s in node.body
            )
            if swallows and body_is_silent:
                findings.append(
                    Finding(
                        ctx.path, node.lineno, "typed-error",
                        "`except Exception: pass` swallows errors "
                        "silently — handle, log, or narrow it",
                    )
                )
        elif boundary and isinstance(node, ast.Raise) and node.exc is not None:
            exc = node.exc
            name = None
            if isinstance(exc, ast.Call):
                chain = _attr_chain(exc.func)
                name = chain[-1] if chain else None
            elif isinstance(exc, ast.Name):
                name = exc.id
            if (
                name
                and name[:1].isupper()
                and name not in keto_errors
            ):
                findings.append(
                    Finding(
                        ctx.path, node.lineno, "typed-error",
                        f"transport raises untyped {name} — clients see "
                        "an unmapped 500; raise a KetoError subclass",
                    )
                )
    return findings


# -- pass 3: config-key coverage -----------------------------------------------


def pass_config_keys(
    files: list[dict], schema: dict
) -> list[Finding]:
    """`files` is a list of {path, tree, is_config} records (the whole
    package — the pass is cross-file)."""
    all_paths, leaves = schema_key_tree(schema)
    reads: dict[str, tuple[Path, int]] = {}
    findings: list[Finding] = []
    for rec in files:
        for key, line in config_key_reads(
            rec["tree"], self_is_config=rec["is_config"]
        ):
            reads.setdefault(key, (rec["path"], line))
            if "*" in key:
                # a wildcard (f-string) read must still land in the schema
                if not any(key_matches(key, p) for p in all_paths):
                    findings.append(
                        Finding(
                            rec["path"], line, "config-key",
                            f"config key pattern {key!r} matches nothing "
                            "in config_schema.json",
                        )
                    )
            elif key not in all_paths:
                findings.append(
                    Finding(
                        rec["path"], line, "config-key",
                        f"config key {key!r} is not declared in "
                        "config_schema.json",
                    )
                )
    schema_path = package_root() / "config_schema.json"
    read_keys = set(reads)
    for leaf in sorted(leaves):
        ancestors = [leaf]
        parts = leaf.split(".")
        for i in range(1, len(parts)):
            ancestors.append(".".join(parts[:i]))
        covered = any(
            key_matches(r, a) for r in read_keys for a in ancestors
        )
        if not covered:
            findings.append(
                Finding(
                    schema_path, 1, "config-key",
                    f"schema key {leaf!r} is never read by any "
                    "config.get() — dead config keys mislead operators",
                )
            )
    return findings


# -- pass 4: clock discipline --------------------------------------------------


def pass_clock(ctx: FileCtx) -> list[Finding]:
    findings = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Attribute):
            continue
        chain = _attr_chain(node)
        if len(chain) >= 2 and chain[-1] == "time" and chain[-2] in (
            "time", "_time",
        ):
            findings.append(
                Finding(
                    ctx.path, node.lineno, "clock-monotonic",
                    "time.time() is a wall clock (jumps on NTP/suspend) "
                    "— use time.monotonic() for deadlines/backoff",
                )
            )
        elif chain[-1] in ("utcnow", "now") and len(chain) >= 2 and chain[
            -2
        ] in ("datetime", "dt"):
            findings.append(
                Finding(
                    ctx.path, node.lineno, "clock-monotonic",
                    f"datetime.{chain[-1]}() in interval math — use "
                    "time.monotonic() (wall clocks jump)",
                )
            )
    return findings


# -- pass 5: host-sync purity --------------------------------------------------


def pass_host_sync(ctx: FileCtx) -> list[Finding]:
    if ctx.path.name not in _HOT_FILES:
        return []
    findings: list[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _HOT_FUNCS.match(node.name):
            continue
        for sub in _walk_no_nested_defs(node.body):
            if not isinstance(sub, ast.Call):
                continue
            func = sub.func
            if isinstance(func, ast.Attribute):
                chain = _attr_chain(func)
                attr = func.attr
                if attr == "block_until_ready":
                    findings.append(
                        Finding(
                            ctx.path, sub.lineno, "host-sync",
                            f"block_until_ready in hot path {node.name} "
                            "— annotate the sync point or defer",
                        )
                    )
                elif attr in ("asarray", "array") and chain[:-1] and chain[
                    -2
                ] in ("np", "_np", "numpy"):
                    findings.append(
                        Finding(
                            ctx.path, sub.lineno, "host-sync",
                            f"np.{attr} device readback in hot path "
                            f"{node.name} — a host sync; annotate the "
                            "intended sync point",
                        )
                    )
                elif attr in ("jit", "pmap") and chain[:-1] and chain[
                    -2
                ] == "jax":
                    findings.append(
                        Finding(
                            ctx.path, sub.lineno, "host-sync",
                            f"fresh jax.{attr} inside hot path "
                            f"{node.name} — recompiles per call; hoist "
                            "and cache it",
                        )
                    )
                elif attr == "device_get":
                    findings.append(
                        Finding(
                            ctx.path, sub.lineno, "host-sync",
                            f"jax.device_get in hot path {node.name} — "
                            "annotate the sync point",
                        )
                    )
            elif isinstance(func, ast.Name) and func.id in ("int", "float"):
                if len(sub.args) == 1 and isinstance(sub.args[0], ast.Name):
                    findings.append(
                        Finding(
                            ctx.path, sub.lineno, "host-sync",
                            f"scalar {func.id}() coercion in hot path "
                            f"{node.name} forces a device sync when the "
                            "operand is a device value — annotate it",
                        )
                    )
    return findings


# -- pass 6: one program on every backend --------------------------------------


def pass_one_program(ctx: FileCtx) -> list[Finding]:
    if ctx.path.parent.name not in _PROGRAM_DIRS:
        return []
    findings = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            asks = _attr_chain(node.func)[-1:] == ["default_backend"]
            what = "default_backend()"
        elif isinstance(node, ast.Compare):
            asks = any(
                isinstance(side, ast.Attribute) and side.attr == "platform"
                for side in (node.left, *node.comparators)
            )
            what = "comparison of a device's .platform"
        else:
            continue
        if asks:
            findings.append(
                Finding(
                    ctx.path, node.lineno, "one-program",
                    f"{what} — a program chosen by backend is one the "
                    "tests here never run; keep the form the chip runs, "
                    "on every backend",
                )
            )
    return findings


# -- driver --------------------------------------------------------------------


def apply_suppressions(
    findings: list[Finding], ctxs: dict[Path, FileCtx]
) -> list[Finding]:
    out: list[Finding] = []
    for f in findings:
        ctx = ctxs.get(f.path)
        suppressed = False
        if ctx is not None:
            for s in ctx.suppressions:
                if s.rule == f.rule and s.line == f.line:
                    s.used = True
                    suppressed = True
                    if not s.has_reason:
                        out.append(
                            Finding(
                                f.path, s.comment_line, "suppression",
                                f"allow[{s.rule}] has no reason= — every "
                                "suppression documents why the invariant "
                                "bends here",
                            )
                        )
        if not suppressed:
            out.append(f)
    # unused suppressions are errors too (stale annotations lie)
    for ctx in ctxs.values():
        for s in ctx.suppressions:
            if not s.used:
                out.append(
                    Finding(
                        ctx.path, s.comment_line, "suppression",
                        f"allow[{s.rule}] suppresses nothing — remove "
                        "the stale annotation",
                    )
                )
    return out


def lint_paths(
    py_files: list[Path], schema: Optional[dict], root: Path
) -> list[Finding]:
    ctxs: dict[Path, FileCtx] = {}
    for path in py_files:
        ctx = load_file(path)
        if ctx is not None:
            ctxs[path] = ctx
    keto_errors = collect_keto_errors([ctx.tree for ctx in ctxs.values()])
    findings: list[Finding] = []
    for ctx in ctxs.values():
        findings.extend(pass_lock_discipline(ctx))
        findings.extend(pass_typed_error(ctx, keto_errors))
        findings.extend(pass_clock(ctx))
        findings.extend(pass_host_sync(ctx))
        findings.extend(pass_one_program(ctx))
    if schema is not None:
        findings.extend(
            pass_config_keys(
                [
                    {
                        "path": ctx.path,
                        "tree": ctx.tree,
                        "is_config": ctx.path.name == "config.py",
                    }
                    for ctx in ctxs.values()
                ],
                schema,
            )
        )
    findings = apply_suppressions(findings, ctxs)
    findings.sort(key=lambda f: (str(f.path), f.line, f.rule))
    return findings


def main(argv: Optional[list[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--list-rules" in argv:
        for rule, desc in RULES.items():
            print(f"{rule:20s} {desc}")
        return 0
    no_config = "--no-config-pass" in argv
    argv = [a for a in argv if a != "--no-config-pass"]
    root = repo_root()
    if argv:
        # explicit files/dirs: per-file passes only unless a schema rides
        # along (golden-fixture mode for tests)
        py_files = []
        for a in argv:
            p = Path(a)
            py_files.extend(iter_py_files(p) if p.is_dir() else [p])
        schema = None
    else:
        py_files = iter_py_files(package_root())
        schema_path = package_root() / "config_schema.json"
        schema = json.loads(read_text(schema_path))
    if no_config:
        schema = None
    findings = lint_paths(py_files, schema, root)
    for f in findings:
        print(f.render(root))
    if findings:
        print(f"ketolint: {len(findings)} finding(s)")
        return 1
    print(f"ketolint: ok ({len(py_files)} files clean)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
