"""gRPC services: Check, Expand, Read, Write, Version, Health.

Wire-compatible with the reference's v1alpha2 services (the route strings
and message bytes match; see protos/keto.proto). Handlers are registered
through `grpc.method_handlers_generic_handler` against the runtime message
classes from descriptors.py, so no generated service stubs are needed.

Behavioral parity:
  - Check: `tuple` field preferred over the deprecated flat fields
    (check/handler.go:248-256); unknown namespace is an ERROR here (only
    REST swallows it to allowed=false); snaptokens are REAL (the
    reference answers "not yet implemented", handler.go:273 — see
    engine/snaptoken.py): requests may pin a minimum snapshot version,
    responses carry the evaluated version's token
  - Expand: SubjectID short-circuits to a leaf carrying only the
    deprecated subject field (expand/handler.go:110-118)
  - List/Delete: `relation_query` preferred, deprecated `query` accepted,
    neither -> InvalidArgument (read_server.go:65-75, transact_server.go:62-75)
  - Transact: one REAL snaptoken per INSERT delta carrying the
    post-write store version (the reference stubs these,
    transact_server.go:54-58)
  - errors map through the KetoError HTTP status the way the herodot
    unwrap interceptor does (daemon.go:351-360)

Check rides the CheckBatcher so concurrent RPCs share device batches.
"""

from __future__ import annotations

import time as _time
from concurrent import futures as _futures

import grpc

from ..errors import KetoError
from ..observability import (
    RequestTrace,
    current_request_trace,
    finish_request_telemetry,
    parse_traceparent,
    reset_request_trace,
    set_request_trace,
)
from ..ketoapi import CheckColumns, RelationQuery, RelationTuple, SubjectSet
from .descriptors import (
    BATCH_CHECK_SERVICE,
    CHECK_SERVICE,
    EXPAND_SERVICE,
    FILTER_SERVICE,
    HEALTH_SERVICE,
    READ_SERVICE,
    REVERSE_READ_SERVICE,
    VERSION_SERVICE,
    WATCH_SERVICE,
    WRITE_SERVICE,
    pb,
)
from .messages import (
    query_from_legacy_proto,
    query_from_proto,
    subject_from_proto,
    subject_to_proto,
    tree_to_proto,
    tuple_from_proto,
    tuple_to_proto,
)

# kept for compatibility: the literal the REFERENCE answers from its
# stubbed snaptoken surfaces; parse_snaptoken accepts it as "no
# constraint" so clients that echo it back keep working. This framework
# returns REAL tokens (engine/snaptoken.py) — one of the places it
# exceeds the reference rather than matching it.
NOT_IMPLEMENTED_SNAPTOKEN = "not yet implemented"

_CODE_BY_STATUS = {
    400: grpc.StatusCode.INVALID_ARGUMENT,
    403: grpc.StatusCode.PERMISSION_DENIED,
    404: grpc.StatusCode.NOT_FOUND,
    409: grpc.StatusCode.FAILED_PRECONDITION,  # unsatisfiable snaptoken
    429: grpc.StatusCode.RESOURCE_EXHAUSTED,  # shed by admission control
    500: grpc.StatusCode.INTERNAL,
    501: grpc.StatusCode.UNIMPLEMENTED,
    503: grpc.StatusCode.UNAVAILABLE,
    504: grpc.StatusCode.DEADLINE_EXCEEDED,  # end-to-end deadline expired
}


def _grpc_code(err: Exception) -> grpc.StatusCode:
    if isinstance(err, KetoError):
        return _CODE_BY_STATUS.get(err.status, grpc.StatusCode.INTERNAL)
    return grpc.StatusCode.INTERNAL


def _attach_retry_after(context, err) -> None:
    """Shed responses carry the retry hint as trailing metadata — the
    gRPC twin of the REST Retry-After header (same OverloadedError
    field, so the hint is plane-identical)."""
    ra = getattr(err, "retry_after_s", None)
    if ra is None:
        return
    from ..resilience import retry_after_header_value

    try:
        context.set_trailing_metadata(
            (("retry-after", retry_after_header_value(ra)),)
        )
    # ketolint: allow[typed-error] reason=trailing metadata is best-effort decoration on an ALREADY-typed error response; a metadata failure must never replace the typed 429 the client is about to receive
    except Exception:
        pass


def _metadata_dict(context) -> dict:
    """Invocation metadata as a plain dict; tolerant of both the sync
    plane's Metadatum objects and the aio plane's (key, value) tuples."""
    out = {}
    for m in context.invocation_metadata() or ():
        if isinstance(m, tuple):
            out[m[0]] = m[1]
        else:
            out[m.key] = m.value
    return out


class _Services:
    """The shared handler implementations behind both gRPC servers."""

    def __init__(self, registry, batcher=None, worker=None):
        self.registry = registry
        self.batcher = batcher
        # replica mode (api/replica.py): the ServeWorker this server
        # belongs to — Check rides the worker's snaptoken-routed
        # cache/batcher path (with hedging) instead of the registry
        # singletons; None = single-stack serving, exactly as before
        self.worker = worker
        self.metrics = registry.metrics()
        # streaming RPCs (health Watch, tuple WatchService) pin one
        # sync-server worker thread each for their lifetime; ONE shared
        # cap keeps all watcher kinds from starving the pool. Config:
        # serve.read.grpc.max_watchers (schema-validated), default 16.
        import threading as _threading

        self.max_watchers = int(
            registry.config.get("serve.read.grpc.max_watchers", 16)
        )
        self._watch_slots = _threading.BoundedSemaphore(self.max_watchers)

    # -- helpers --------------------------------------------------------------

    def _begin_trace(self, context):
        """RequestTrace for one RPC: joins the caller's trace when the
        invocation metadata carries a W3C `traceparent` entry (the gRPC
        twin of the REST header), else starts a fresh one. The native
        gRPC deadline (context.time_remaining) becomes the request's
        end-to-end Deadline, clamped/defaulted by serve.check.*_deadline_ms
        — so the server fails fast and frees the batch slot instead of
        computing an answer the cancelled client will never read."""
        from ..resilience import ingest_deadline

        ctx = parse_traceparent(_metadata_dict(context).get("traceparent"))
        try:
            native_s = context.time_remaining()
        except Exception:  # noqa: BLE001 — stub contexts in tests
            native_s = None
        return RequestTrace(
            ctx.child() if ctx is not None else None,
            deadline=ingest_deadline(self.registry.config, native_s=native_s),
        )

    def _finish_trace(self, method, rt, code, duration) -> None:
        """Stage bookkeeping + request/slow-query logs after one RPC
        (the with-block has already recorded the flat histogram);
        shared-helper semantics in observability.finish_request_telemetry."""
        finish_request_telemetry(
            self.metrics,
            self.registry.config.get("log.slow_query_ms"),
            "grpc", method, rt, code, duration,
            sample_rate=self.registry.config.get("log.request_sample_rate"),
            workload=self.registry.workload_observatory(),
        )

    def _observed(self, method, context, fn, request):
        rt = self._begin_trace(context)
        token = set_request_trace(rt)
        t0 = _time.perf_counter()
        outcome = None
        try:
            with self.metrics.observe_request("grpc", method) as outcome:
                try:
                    # span-per-RPC (ref: otelgrpc interceptors,
                    # daemon.go:360-380); root=True: this span anchors
                    # the exported trace (see rest_server._route)
                    with self.registry.tracer().span(
                        f"grpc.{method}", ctx=rt.ctx, root=True
                    ):
                        return fn(request, context)
                except KetoError as e:
                    outcome["code"] = _grpc_code(e).name
                    _attach_retry_after(context, e)
                    context.abort(_grpc_code(e), e.message)
                except Exception as e:  # noqa: BLE001 — RPC boundary
                    outcome["code"] = "INTERNAL"
                    context.abort(grpc.StatusCode.INTERNAL, str(e))
        finally:
            reset_request_trace(token)
            self._finish_trace(
                method, rt,
                outcome.code if outcome is not None else "INTERNAL",
                _time.perf_counter() - t0,
            )

    def _nid(self, context) -> str:
        """Per-request network id from gRPC invocation metadata (ref:
        ketoctx/contextualizer.go:12-19). Without a contextualizer the
        metadata is never consulted — skip materializing it (per-RPC
        hot path)."""
        if self.registry.contextualizer is None:
            return self.registry.nid
        return self.registry.nid_for(_metadata_dict(context))

    def _check_tuple(self, req) -> RelationTuple:
        src = req.tuple if req.HasField("tuple") else req
        sub = subject_from_proto(src.subject)
        if sub is None:
            from ..errors import NilSubjectError

            raise NilSubjectError()
        return RelationTuple.make(src.namespace, src.object, src.relation, sub)

    def _query_from(self, req) -> RelationQuery:
        if req.HasField("relation_query"):
            return query_from_proto(req.relation_query)
        if req.HasField("query"):
            return query_from_legacy_proto(req.query)
        from ..errors import MalformedInputError

        raise MalformedInputError("you must provide a query")

    # -- snaptokens -----------------------------------------------------------

    def _enforce_snaptoken(self, token: str, nid: str) -> int:
        from ..engine.snaptoken import enforce_snaptoken

        return enforce_snaptoken(self.registry, token, nid)

    # -- CheckService ---------------------------------------------------------

    def check(self, req, context):
        from ..engine.snaptoken import encode_snaptoken
        from ..resilience import admit_check, admit_explain

        # admission gate BEFORE any work (typed 429/504; see
        # resilience.admit_check): shed/expired requests cost nothing.
        # explain=true rides its own token bucket (explain.max_per_s)
        # instead of the batcher's queue bound — it never queues there.
        explain = bool(getattr(req, "explain", False))
        if explain:
            admit_explain(self.registry, current_request_trace())
        else:
            admit_check(self.registry, self.batcher, current_request_trace())
        t = self._check_tuple(req)
        self.registry.validate_namespaces(t)
        nid = self._nid(context)
        max_depth = int(req.max_depth)
        if explain:
            # §5m explain plane: cache bypassed, engine explain path,
            # DecisionTrace serialized as canonical JSON bytes — the
            # SAME bytes the aio plane returns and the REST body embeds
            # (tri-plane parity is canonical-byte equality)
            from ..engine.explain import canonical_json, serve_explain

            if self.worker is not None:
                from .replica import resolve_version

                _target, version = resolve_version(
                    self.worker.group, self.worker, nid, req.snaptoken,
                    current_request_trace(),
                )
            else:
                version = self._enforce_snaptoken(req.snaptoken, nid)
            res, trace = serve_explain(
                self.registry, nid, t, max_depth, version,
                current_request_trace(),
            )
            if res.error is not None:
                raise res.error
            return pb.CheckResponse(
                allowed=res.allowed,
                snaptoken=encode_snaptoken(version, nid),
                decision_trace=canonical_json(trace).decode(),
            )
        if self.worker is not None:
            # replica mode: snaptoken routing (hold for catch-up ->
            # route to a fresh worker -> escalate, never stale) + the
            # answering worker's cache/batcher with hedging; the
            # response token is minted at the answering version
            from .replica import replica_check

            res, version = replica_check(
                self.worker, nid, t, max_depth, req.snaptoken,
                current_request_trace(),
            )
        else:
            version = self._enforce_snaptoken(req.snaptoken, nid)
            # serve fast path (api/check_cache.py): a hit returns before
            # the batcher — no assemble/dispatch/device stages run, and
            # the response (snaptoken included) is byte-identical to a
            # miss at the same store version
            from .check_cache import cached_check

            res = cached_check(
                self.registry, self.batcher, nid, t, max_depth, version,
                current_request_trace(),
            )
        if res.error is not None:
            raise res.error
        return pb.CheckResponse(
            allowed=res.allowed, snaptoken=encode_snaptoken(version, nid)
        )

    def batch_check(self, req, context):
        """keto_tpu extension (keto_tpu_batch.proto): one RPC carries a
        whole batch straight into engine.check_batch — the reference's
        API resolves one check per RPC and its server-side checkgroup
        fan-out cannot feed a device kernel
        (check_service.proto:18-21). Per-item failures (nil subject,
        engine errors, unknown names via host replay) come back as
        per-result error strings; one bad item never fails the batch."""
        from ..engine.snaptoken import encode_snaptoken
        from ..resilience import admit_check

        rt = current_request_trace()
        with self.metrics.stage("decode", rt):
            # draining/expired gate (no queue bound: the batch rides one
            # direct engine launch, not the batcher queue)
            admit_check(self.registry, None, rt)
            nid = self._nid(context)
            version = self._enforce_snaptoken(req.snaptoken, nid)
            cols, refused = self._batch_check_columns(req.tuples)
            n = len(cols)
            if refused:
                launched = [i for i in range(n) if i not in refused]
                cols = cols.take(launched)
            engine = self.registry.check_engine(nid)
        # the engine adds assemble / dispatch / device_wait / resolve to
        # this RPC's trace itself (check_batch reads the contextvar)
        results = engine.check_batch(cols, int(req.max_depth))
        with self.metrics.stage("respond", rt):
            # no object an item: the two results without an error are
            # built once, and extend() copies a message it is handed
            verdicts = [r.allowed for r in results]
            yes = pb.BatchCheckResult(allowed=True)
            no = pb.BatchCheckResult(allowed=False)
            out = [yes if allowed else no for allowed in verdicts]
            failed = [j for j, r in enumerate(results) if r.error is not None]
            for j in failed:
                out[j] = pb.BatchCheckResult(
                    allowed=False, error=str(results[j].error)
                )
            obs = self.registry.workload_observatory()
            if obs is not None:
                # workload accounting, once a batch (the batch bypasses
                # the single-check serve gate; no per-item tier), of the
                # answered items: an errored one is left out
                if failed:
                    answered = [
                        j for j, r in enumerate(results) if r.error is None
                    ]
                    cols = cols.take(answered)
                    verdicts = [verdicts[j] for j in answered]
                obs.record_check_batch(nid, cols, verdicts)
            if refused:
                launched_out, out = out, [None] * n
                for i, result in zip(launched, launched_out):
                    out[i] = result
                for i, error in refused.items():
                    out[i] = pb.BatchCheckResult(allowed=False, error=error)
            resp = pb.BatchCheckResponse(snaptoken=encode_snaptoken(version, nid))
            resp.results.extend(out)
        return resp

    def _batch_check_columns(self, wire_tuples):
        """A BatchCheckRequest's items as CheckColumns, read field by
        field off the wire with no RelationTuple between, and {row: error
        string} for the rows that must not launch: a nil subject, or a
        namespace (the item's first, then its subject set's) that is not
        configured, with the same per-item semantics and message as the
        single-check plane's validate_namespaces (an ERROR, not a silent
        deny). The caller takes those rows out of the columns."""
        # one pass over the repeated field makes the 2,048 wrappers, the
        # column reads go over the list of them
        wire_tuples = list(wire_tuples)
        n = len(wire_tuples)
        ns = [pt.namespace for pt in wire_tuples]
        obj = [pt.object for pt in wire_tuples]
        rel = [pt.relation for pt in wire_tuples]
        subjects = [pt.subject for pt in wire_tuples]
        sobj = [s.id for s in subjects]
        skind, sns, srel = [0] * n, [""] * n, [""] * n
        refused: dict[int, str] = {}
        names = set(ns)
        # a subject that reads as a non-empty id is one; the oneof is
        # asked only about the rest
        for i in [i for i, subject_id in enumerate(sobj) if not subject_id]:
            kind = subjects[i].WhichOneof("ref")
            if kind == "set":
                sset = subjects[i].set
                skind[i] = 1
                sns[i], sobj[i], srel[i] = (
                    sset.namespace, sset.object, sset.relation
                )
                names.add(sset.namespace)
            elif kind is None:
                refused[i] = "subject is not allowed to be nil"
        # once a distinct name, not once an item
        unknown: dict[str, str] = {}
        manager = self.registry.namespace_manager()
        for name in names:
            try:
                manager.get_namespace_by_name(name)
            except KetoError as e:
                unknown[name] = e.message
        if unknown:
            for i in range(n):
                if i in refused:
                    continue
                error = unknown.get(ns[i])
                if error is None and skind[i]:
                    error = unknown.get(sns[i])
                if error is not None:
                    refused[i] = error
        return CheckColumns(ns, obj, rel, skind, sns, sobj, srel), refused

    # -- ExpandService --------------------------------------------------------

    def expand(self, req, context):
        self._enforce_snaptoken(req.snaptoken, self._nid(context))
        sub = subject_from_proto(req.subject)
        if not isinstance(sub, SubjectSet):
            resp = pb.ExpandResponse()
            resp.tree.node_type = 4  # NODE_TYPE_LEAF
            if sub is not None:
                resp.tree.subject.CopyFrom(subject_to_proto(sub))
            return resp
        self.registry.validate_namespaces(sub)
        tree = self.registry.expand_engine(self._nid(context)).expand(
            sub, int(req.max_depth)
        )
        if tree is None:
            return pb.ExpandResponse()
        resp = pb.ExpandResponse()
        resp.tree.CopyFrom(tree_to_proto(tree))
        return resp

    # -- ReverseReadService (keto_tpu extension) ------------------------------

    def list_objects(self, req, context):
        """keto_tpu extension (keto_tpu_reverse.proto): which objects in
        a namespace can this subject reach via a relation — the inverse
        of Check, served by the reverse-BFS kernel over the transposed
        device mirror (engine/reverse_kernel.py). Paginated and
        snaptoken-enforced like Check; unknown namespace is an ERROR
        (gRPC plane semantics)."""
        from ..engine.snaptoken import encode_snaptoken
        from ..ketoapi import RelationQuery

        sub = subject_from_proto(req.subject)
        if sub is None:
            from ..errors import NilSubjectError

            raise NilSubjectError()
        self.registry.validate_namespaces(
            RelationQuery(namespace=req.namespace),
            sub if isinstance(sub, SubjectSet) else None,
        )
        nid = self._nid(context)
        version = self._enforce_snaptoken(req.snaptoken, nid)
        engine = self.registry.check_engine(nid)
        page_size = int(req.page_size) or self.registry.config.page_size()
        objects, next_token = engine.list_objects(
            req.namespace, req.relation, sub, int(req.max_depth),
            page_size=page_size, page_token=req.page_token,
        )
        resp = pb.ListObjectsResponse(
            next_page_token=next_token, snaptoken=encode_snaptoken(version, nid)
        )
        resp.objects.extend(objects)
        return resp

    def list_subjects(self, req, context):
        """keto_tpu extension: which plain subject ids reach
        namespace:object#relation — forward enumeration over the
        full-edge CSR + rewrite instructions."""
        from ..engine.snaptoken import encode_snaptoken
        from ..ketoapi import RelationQuery

        self.registry.validate_namespaces(RelationQuery(namespace=req.namespace))
        nid = self._nid(context)
        version = self._enforce_snaptoken(req.snaptoken, nid)
        engine = self.registry.check_engine(nid)
        page_size = int(req.page_size) or self.registry.config.page_size()
        subjects, next_token = engine.list_subjects(
            req.namespace, req.object, req.relation, int(req.max_depth),
            page_size=page_size, page_token=req.page_token,
        )
        resp = pb.ListSubjectsResponse(
            next_page_token=next_token, snaptoken=encode_snaptoken(version, nid)
        )
        resp.subject_ids.extend(subjects)
        return resp

    # -- FilterService (keto_tpu extension) -----------------------------------

    def filter(self, req, context):
        """keto_tpu extension (keto_tpu_filter.proto): bulk ACL filter —
        which of these candidate objects can the subject see? One RPC
        carries the whole candidate column into the engine's
        shared-subject device formulation (closure fast path + shared-
        frontier reverse walk, engine/filter_kernel.py). Admission
        (typed 429/504 + the filter.max_objects 400) runs BEFORE any
        work; the deadline is re-checked at every chunk boundary inside
        the engine; snaptoken gating matches Check (replica mode routes
        through the snaptoken hold/route/escalate rule)."""
        from ..engine.snaptoken import encode_snaptoken
        from ..ketoapi import RelationQuery
        from ..resilience import admit_filter

        rt = current_request_trace()
        admit_filter(self.registry, len(req.objects), rt)
        sub = subject_from_proto(req.subject)
        if sub is None:
            from ..errors import NilSubjectError

            raise NilSubjectError()
        self.registry.validate_namespaces(
            RelationQuery(namespace=req.namespace),
            sub if isinstance(sub, SubjectSet) else None,
        )
        nid = self._nid(context)
        if self.worker is not None:
            from .replica import resolve_version

            _target, version = resolve_version(
                self.worker.group, self.worker, nid, req.snaptoken, rt
            )
        else:
            version = self._enforce_snaptoken(req.snaptoken, nid)
        engine = self.registry.check_engine(nid)
        allowed = engine.filter_objects(
            req.namespace, req.relation, sub, list(req.objects),
            int(req.max_depth),
            deadline=getattr(rt, "deadline", None) if rt is not None else None,
        )
        resp = pb.FilterResponse(snaptoken=encode_snaptoken(version, nid))
        resp.allowed_objects.extend(allowed)
        return resp

    # -- ReadService ----------------------------------------------------------

    def list_relation_tuples(self, req, context):
        self._enforce_snaptoken(req.snaptoken, self._nid(context))
        q = self._query_from(req)
        self.registry.validate_namespaces(q)
        manager = self.registry.relation_tuple_manager()
        page_size = int(req.page_size) or self.registry.config.page_size()
        tuples, next_token = manager.get_relation_tuples(
            q,
            page_token=req.page_token,
            page_size=page_size,
            nid=self._nid(context),
        )
        resp = pb.ListRelationTuplesResponse(next_page_token=next_token)
        for t in tuples:
            resp.relation_tuples.append(tuple_to_proto(t))
        return resp

    # -- WriteService ---------------------------------------------------------

    def transact_relation_tuples(self, req, context):
        inserts: list[RelationTuple] = []
        deletes: list[RelationTuple] = []
        for d in req.relation_tuple_deltas:
            if d.action == 1:  # ACTION_INSERT
                inserts.append(tuple_from_proto(d.relation_tuple))
            elif d.action == 2:  # ACTION_DELETE
                deletes.append(tuple_from_proto(d.relation_tuple))
            # ACTION_UNSPECIFIED deltas are ignored (transact_server.go:20-31)
        self.registry.validate_namespaces(*inserts, *deletes)
        from ..engine.snaptoken import encode_snaptoken

        nid = self._nid(context)
        manager = self.registry.relation_tuple_manager()
        manager.transact_relation_tuples(inserts, deletes, nid=nid)
        # REAL tokens (the reference stubs these, transact_server.go:
        # 55-58): one per INSERT delta, all carrying the post-write
        # version — a Check presenting this token is guaranteed to see
        # the write (read-your-writes)
        token = encode_snaptoken(manager.version(nid=nid), nid)
        return pb.TransactRelationTuplesResponse(
            snaptokens=[token] * len(inserts)
        )

    def delete_relation_tuples(self, req, context):
        if req.HasField("relation_query"):
            q = query_from_proto(req.relation_query)
        elif req.HasField("query"):
            q = query_from_legacy_proto(req.query)
        else:
            from ..errors import MalformedInputError

            raise MalformedInputError("invalid request")
        self.registry.validate_namespaces(q)
        self.registry.relation_tuple_manager().delete_all_relation_tuples(
            q, nid=self._nid(context)
        )
        return pb.DeleteRelationTuplesResponse()

    # -- VersionService / Health ----------------------------------------------

    def get_version(self, req, context):
        return pb.GetVersionResponse(version=self.registry.version)

    def health_check(self, req, context):
        status = 1 if self.registry.ready.is_set() else 2  # SERVING / NOT_SERVING
        return pb.HealthCheckResponse(status=status)

    # -- WatchService (keto_tpu extension) ------------------------------------

    @staticmethod
    def watch_event_to_proto(event):
        """WatchEvent (watch/hub.py) -> WatchResponse proto."""
        resp = pb.WatchResponse(
            event_type=event.kind, snaptoken=event.snaptoken
        )
        for op, t in event.changes:
            c = resp.changes.add()
            c.action = op
            c.relation_tuple.CopyFrom(tuple_to_proto(t))
        return resp

    def watch_subscribe(self, req, context):
        """Shared stream setup for the sync and aio planes: parse +
        validate the resume cursor, open the hub subscription. Raises
        KetoError (snaptoken 400/409) for the caller to map."""
        from ..engine.snaptoken import parse_snaptoken

        nid = self._nid(context)
        if req.namespace:
            self.registry.validate_namespaces(
                RelationQuery(namespace=req.namespace)
            )
        min_version = parse_snaptoken(req.snaptoken, nid)
        return self.registry.watch_hub().subscribe(nid, min_version)

    def watch_tuples(self, req, context):
        """Server-streaming changelog watch (keto_tpu.watch.v1): resume
        from the request snaptoken, then live-tail; overflow surfaces as
        an in-band RESET event, never a silent gap. Shares the watcher
        cap with health Watch (both pin a worker thread)."""
        if not self._watch_slots.acquire(blocking=False):
            context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                "too many concurrent watchers",
            )
        try:
            try:
                sub = self.watch_subscribe(req, context)
            except KetoError as e:
                context.abort(_grpc_code(e), e.message)
            # in-band keep-alives (watch.heartbeat_s, the gRPC twin of
            # the SSE comment frame): an idle stream writes a
            # `heartbeat` event each period, so a half-open TCP
            # connection fails the write and the finally frees this
            # subscriber's ring instead of pinning changelog retention
            # forever. ReadClient.watch() filters them out.
            from ..engine.snaptoken import encode_snaptoken

            heartbeat_s = float(
                self.registry.config.get("watch.heartbeat_s", 5.0)
            )
            last_write = _time.monotonic()
            try:
                while context.is_active():
                    # heartbeat check runs EVERY iteration, not only on
                    # an idle get: a stream whose events are all
                    # namespace-filtered out is busy AND wire-silent —
                    # without this, a half-open peer on such a stream
                    # would never be detected
                    if _time.monotonic() - last_write >= heartbeat_s:
                        last_write = _time.monotonic()
                        # the frame carries the cursor's snaptoken (HA
                        # follower plane): an idle tail learns the store
                        # version it is current THROUGH without a single
                        # change having been delivered
                        yield pb.WatchResponse(
                            event_type="heartbeat",
                            snaptoken=encode_snaptoken(sub.cursor, sub.nid),
                        )
                    try:
                        event = sub.get(timeout=0.5)
                    except KetoError as e:
                        # e.g. an overflow resume against an unavailable
                        # store: end the stream with the typed code, not
                        # a raw INTERNAL (the client re-subscribes from
                        # its cursor after recovery)
                        context.abort(_grpc_code(e), e.message)
                    if event is None:
                        if sub.closed:  # daemon drain ends the stream
                            break
                        continue
                    event = event.filtered(req.namespace)
                    if event is None:
                        continue
                    yield self.watch_event_to_proto(event)
                    last_write = _time.monotonic()
            finally:
                sub.close()
        finally:
            self._watch_slots.release()

    def health_watch(self, req, context):
        """Streams the current status, then pushes changes until the client
        disconnects (grpc.health.v1 Watch contract). Event-driven: the
        stream parks on the registry ReadyState condition and wakes on
        transitions; the 5s timeout only re-checks client liveness."""
        if not self._watch_slots.acquire(blocking=False):
            context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                "too many concurrent health watchers",
            )
        try:
            flag, gen = self.registry.ready.state()
            last = None
            while context.is_active():
                current = 1 if flag else 2
                if current != last:
                    last = current
                    yield pb.HealthCheckResponse(status=current)
                flag, gen = self.registry.ready.wait_change(gen, timeout=5.0)
        finally:
            self._watch_slots.release()


def _unary(services: _Services, name: str, fn, req_cls):
    def handler(request, context):
        return services._observed(name, context, fn, request)

    return grpc.unary_unary_rpc_method_handler(
        handler,
        request_deserializer=req_cls.FromString,
        response_serializer=lambda m: m.SerializeToString(),
    )


def _service_handlers(services: _Services, write: bool):
    """Generic handlers for one server. Version + Health live on both
    (daemon.go:387-419)."""
    s = services
    handlers = [
        grpc.method_handlers_generic_handler(
            VERSION_SERVICE,
            {"GetVersion": _unary(s, "GetVersion", s.get_version, pb.GetVersionRequest)},
        ),
        grpc.method_handlers_generic_handler(
            HEALTH_SERVICE,
            {
                "Check": _unary(s, "HealthCheck", s.health_check, pb.HealthCheckRequest),
                "Watch": grpc.unary_stream_rpc_method_handler(
                    lambda req, ctx: s.health_watch(req, ctx),
                    request_deserializer=pb.HealthCheckRequest.FromString,
                    response_serializer=lambda m: m.SerializeToString(),
                ),
            },
        ),
    ]
    if write:
        handlers.append(
            grpc.method_handlers_generic_handler(
                WRITE_SERVICE,
                {
                    "TransactRelationTuples": _unary(
                        s, "TransactRelationTuples", s.transact_relation_tuples,
                        pb.TransactRelationTuplesRequest,
                    ),
                    "DeleteRelationTuples": _unary(
                        s, "DeleteRelationTuples", s.delete_relation_tuples,
                        pb.DeleteRelationTuplesRequest,
                    ),
                },
            )
        )
    else:
        handlers.extend(
            [
                grpc.method_handlers_generic_handler(
                    CHECK_SERVICE,
                    {"Check": _unary(s, "Check", s.check, pb.CheckRequest)},
                ),
                grpc.method_handlers_generic_handler(
                    BATCH_CHECK_SERVICE,
                    {
                        "BatchCheck": _unary(
                            s, "BatchCheck", s.batch_check,
                            pb.BatchCheckRequest,
                        )
                    },
                ),
                grpc.method_handlers_generic_handler(
                    EXPAND_SERVICE,
                    {"Expand": _unary(s, "Expand", s.expand, pb.ExpandRequest)},
                ),
                grpc.method_handlers_generic_handler(
                    READ_SERVICE,
                    {
                        "ListRelationTuples": _unary(
                            s, "ListRelationTuples", s.list_relation_tuples,
                            pb.ListRelationTuplesRequest,
                        )
                    },
                ),
                grpc.method_handlers_generic_handler(
                    REVERSE_READ_SERVICE,
                    {
                        "ListObjects": _unary(
                            s, "ListObjects", s.list_objects,
                            pb.ListObjectsRequest,
                        ),
                        "ListSubjects": _unary(
                            s, "ListSubjects", s.list_subjects,
                            pb.ListSubjectsRequest,
                        ),
                    },
                ),
                grpc.method_handlers_generic_handler(
                    FILTER_SERVICE,
                    {
                        "Filter": _unary(
                            s, "Filter", s.filter, pb.FilterRequest
                        ),
                    },
                ),
                grpc.method_handlers_generic_handler(
                    WATCH_SERVICE,
                    {
                        "Watch": grpc.unary_stream_rpc_method_handler(
                            lambda req, ctx: s.watch_tuples(req, ctx),
                            request_deserializer=pb.WatchRequest.FromString,
                            response_serializer=lambda m: m.SerializeToString(),
                        ),
                    },
                ),
            ]
        )
    return handlers


def build_grpc_server(
    registry, *, write: bool, batcher=None, max_workers: int = 32,
    worker=None, so_reuseport: bool | None = None,
) -> grpc.Server:
    """One gRPC server for the read (:4466) or write (:4467) API.
    The caller binds ports and manages lifecycle (see daemon.py).
    `worker` attaches the server to one replica ServeWorker;
    `so_reuseport` pins the grpc.so_reuseport channel arg (replica
    workers share one public direct port through it)."""
    services = _Services(registry, batcher=batcher, worker=worker)
    options = None
    if so_reuseport is not None:
        options = (("grpc.so_reuseport", 1 if so_reuseport else 0),)
    server = grpc.server(
        _futures.ThreadPoolExecutor(
            max_workers=max_workers,
            thread_name_prefix="keto-grpc-write" if write else "keto-grpc-read",
        ),
        options=options,
    )
    for h in _service_handlers(services, write=write):
        server.add_generic_rpc_handlers((h,))
    return server
