"""Error values mirroring Keto's public error surface.

Reference: ketoapi/public_api_definitions.go:14-21 (herodot-wrapped error
values) and internal/x errors. Each error carries an HTTP status so the REST
layer can map it the same way herodot does in the reference.
"""

from __future__ import annotations


class KetoError(Exception):
    """Base error. `status` is the HTTP status code the REST layer returns."""

    status = 500
    code = "internal_server_error"

    def __init__(self, message: str | None = None, *, debug: str | None = None):
        super().__init__(message or self.__class__.default_message)
        self.message = message or self.__class__.default_message
        self.debug = debug

    default_message = "internal server error"

    def to_dict(self) -> dict:
        body = {
            "code": self.status,
            "status": self.code,
            "message": self.message,
        }
        if self.debug:
            body["debug"] = self.debug
        return {"error": body}


class MalformedInputError(KetoError):
    # ref: ketoapi/enc_string.go:11 ErrMalformedInput
    status = 400
    code = "bad_request"
    default_message = "malformed string input"


class DroppedSubjectKeyError(KetoError):
    # ref: ketoapi/public_api_definitions.go:15 ErrDroppedSubjectKey
    status = 400
    code = "bad_request"
    default_message = (
        'provide "subject_id" or "subject_set.*"; support for "subject" was dropped'
    )


class DuplicateSubjectError(KetoError):
    # ref: ketoapi/public_api_definitions.go:16 ErrDuplicateSubject
    status = 400
    code = "bad_request"
    default_message = "exactly one of subject_set or subject_id has to be provided"


class IncompleteSubjectError(KetoError):
    # ref: ketoapi/public_api_definitions.go:17 ErrIncompleteSubject
    status = 400
    code = "bad_request"
    default_message = (
        'incomplete subject, provide "subject_id" or a complete "subject_set.*"'
    )


class NilSubjectError(KetoError):
    # ref: ketoapi/public_api_definitions.go:18 ErrNilSubject
    status = 400
    code = "bad_request"
    default_message = "subject is not allowed to be nil"


class IncompleteTupleError(KetoError):
    # ref: ketoapi/public_api_definitions.go:19 ErrIncompleteTuple
    status = 400
    code = "bad_request"
    default_message = (
        'incomplete tuple, provide "namespace", "object", "relation", and a subject'
    )


class UnknownNodeTypeError(KetoError):
    # ref: ketoapi/public_api_definitions.go:20 ErrUnknownNodeType
    status = 400
    code = "bad_request"
    default_message = "unknown node type"


class NotFoundError(KetoError):
    status = 404
    code = "not_found"
    default_message = "resource not found"


class NamespaceNotFoundError(NotFoundError):
    default_message = "namespace not found"

    def __init__(self, namespace: str):
        super().__init__(f"namespace {namespace!r} not found")
        self.namespace = namespace


class RelationNotFoundError(KetoError):
    # Engine error when a namespace config exists but the relation is absent
    # (ref: internal/check/engine.go:228 `relation %q not found`).
    status = 400
    code = "bad_request"
    default_message = "relation not found"

    def __init__(self, relation: str):
        super().__init__(f"relation {relation!r} not found")
        self.relation = relation


class MaxDepthExceededError(KetoError):
    status = 400
    code = "bad_request"
    default_message = "max depth exceeded"


class InvalidPageTokenError(KetoError):
    # ref: internal/persistence/sql/persister.go (x.ErrInvalidToken analog)
    status = 400
    code = "bad_request"
    default_message = "invalid page token"


class NotImplementedYetError(KetoError):
    # ref: snaptokens: "not yet implemented" (internal/check/handler.go:273)
    status = 501
    code = "not_implemented"
    default_message = "not yet implemented"


class FilterTooLargeError(KetoError):
    # BatchFilter admission (resilience.admit_filter): the candidate
    # list exceeds `filter.max_objects`. A typed 400 BEFORE any device
    # work — an unbounded candidate column would buy unbounded device
    # launches; clients split the list and chain snaptokens instead.
    status = 400
    code = "bad_request"
    default_message = "filter candidate list exceeds filter.max_objects"


class DeadlineExceededError(KetoError):
    # Resilience plane (keto_tpu/resilience.py): the request's end-to-end
    # deadline (REST x-request-timeout-ms / native gRPC deadline /
    # serve.check.default_deadline_ms) expired before an answer was
    # produced. 504 on REST, DEADLINE_EXCEEDED on gRPC — Zanzibar's
    # deadline-scoped evaluation (paper §2.4.1) fails fast instead of
    # occupying a batch slot.
    status = 504
    code = "deadline_exceeded"
    default_message = "request deadline exceeded"


class OverloadedError(KetoError):
    # Admission control / load shedding: the request was rejected BEFORE
    # any work was done (bounded batcher queue at serve.check.max_queue,
    # or the daemon's shutdown drain window). 429 on REST (with a
    # Retry-After header from `retry_after_s`), RESOURCE_EXHAUSTED on
    # gRPC. Shedding with a typed error is the graceful-degradation
    # contract: memory stays bounded and clients get a clear retry signal
    # instead of an unbounded queue wait.
    status = 429
    code = "too_many_requests"
    default_message = "server is overloaded, retry later"

    def __init__(
        self,
        message: str | None = None,
        *,
        debug: str | None = None,
        retry_after_s: float | None = None,
    ):
        super().__init__(message, debug=debug)
        self.retry_after_s = retry_after_s


class BatcherClosedError(OverloadedError, RuntimeError):
    # A check racing batcher shutdown: typed like the admission gate's
    # drain shed (429 + Retry-After — retryable against a live replica),
    # and ALSO a RuntimeError so embedders' `except RuntimeError`
    # handlers around CheckBatcher.check keep working (this raise site
    # was a bare RuntimeError before the typed-error boundary existed;
    # same dual-inheritance compat contract as CheckBatchFailedError).
    default_message = "check batcher is closed"


class StoreUnavailableError(KetoError):
    # Store-outage degradation plane (storage/health.py): the tuple
    # store is unreachable — the store-path circuit breaker is open
    # (fail-fast, `breaker_open=True`), or an in-flight store op failed.
    # 503 on REST (Retry-After from `retry_after_s`), UNAVAILABLE on
    # gRPC — the retryable code ReadClient's RetryPolicy backs off on.
    # While the breaker is open, reads the device mirror can answer at
    # its covered version are served degraded instead (the snaptoken is
    # the staleness bound); everything else gets this typed 503 — never
    # a wrong answer, never a hung thread.
    status = 503
    code = "store_unavailable"
    default_message = "the tuple store is unavailable, retry later"

    def __init__(
        self,
        message: str | None = None,
        *,
        debug: str | None = None,
        retry_after_s: float | None = None,
        breaker_open: bool = False,
    ):
        super().__init__(message, debug=debug)
        self.retry_after_s = retry_after_s
        # True only for the store breaker's fail-fast rejection: the
        # signal the degraded-serving gates key on (an in-flight op
        # failure must NOT degrade-serve — the transport may have minted
        # a fresher snaptoken an instant earlier, and a mirror answer
        # below it would time-travel)
        self.breaker_open = breaker_open


class StoreTimeoutError(StoreUnavailableError):
    # A store op exceeded its `store.op_timeout_ms` budget (bounded
    # executor, storage/health.py): the op thread may still be wedged in
    # the driver, but the serving thread is answered and freed — a hung
    # SQL read can no longer pin a batcher or dispatch thread.
    default_message = "tuple store operation timed out"


class StoreBusyError(StoreUnavailableError):
    # SQLITE_BUSY / "database is locked" mapped to the typed retryable
    # surface (storage/sqlite.py _PrepConn): transient lock contention a
    # client should back off and retry, not an internal error. 503 /
    # UNAVAILABLE like its parent, so RetryPolicy retries it.
    default_message = "the tuple store is busy (locked), retry"


class CheckpointIncompatibleError(KetoError):
    # A checkpoint file that is INTACT but unusable by this process —
    # wrong format version or a table build of another layout (its keys
    # lie in other slots; probing it mis-answers every lookup). Distinct from a torn/corrupt file,
    # which silently degrades to a rebuild: an explicit restore request
    # (the HA follower's cold start, engine/checkpoint.restore_snapshot)
    # answering from such a file would be WRONG, so the caller gets a
    # typed refusal to act on, never a crash and never silent garbage.
    status = 500
    code = "internal_server_error"
    default_message = "checkpoint incompatible with this process"


class CheckBatchFailedError(KetoError, RuntimeError):
    # Engine-batch failure classified into the typed error surface
    # (api/batcher.py classify_engine_error) instead of leaking the raw
    # exception to every rider. Also a RuntimeError so embedders'
    # `except RuntimeError` handlers around CheckBatcher.check keep
    # working.
    status = 500
    code = "internal_server_error"
    default_message = "check batch evaluation failed"
