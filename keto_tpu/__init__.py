"""keto_tpu — a TPU-native Zanzibar-style authorization engine.

A from-scratch framework with the capabilities of Ory Keto (reference:
/root/reference, module github.com/ory/keto): relation-tuple storage,
namespace configuration with the Ory Permission Language, and the
Check / Expand / Read / Write API surface — re-designed TPU-first.

Instead of a goroutine-per-branch graph walk issuing one SQL query per
edge page (reference internal/check/engine.go), the relation graph is
mirrored in device memory as dictionary-encoded hash tables + CSR
adjacency, and permission checks run as batched BFS frontier expansion
inside one bounded device loop, sharded over a `jax.sharding.Mesh`.

Layout (mirrors the layer map in SURVEY.md §1):
  ketoapi     — public string-based API types + encodings   (ref: ketoapi/)
  namespace   — namespace model + userset-rewrite AST       (ref: internal/namespace)
  opl         — Ory Permission Language lexer/parser        (ref: internal/schema)
  config      — config provider + namespace managers        (ref: internal/driver/config)
  storage     — tuple stores (memory, sqlite) + UUID map    (ref: internal/persistence)
  engine      — host reference engine + TPU BFS kernel      (ref: internal/check, internal/expand)
  api         — service layer + REST server                 (ref: internal/*/handler.go, internal/driver/daemon.go)
  registry    — composition root                            (ref: internal/driver/registry*.go)
  cli         — command-line interface                      (ref: cmd/)
"""

__version__ = "0.1.0"
