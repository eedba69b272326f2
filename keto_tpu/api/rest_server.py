"""REST API: Keto-compatible HTTP routes on stdlib ThreadingHTTPServer.

Route/behavior parity (ref files in internal/):
  read router (:4466)  — GET /relation-tuples (relationtuple/read_server.go:122-175),
    GET+POST /relation-tuples/check and .../check/openapi — the bare routes
    mirror the check status as 403-on-deny, the /openapi variants always
    200 (check/handler.go:49-55, :129-142, :183-226); GET
    /relation-tuples/expand (expand/handler.go:43-107)
  write router (:4467) — PUT /admin/relation-tuples -> 201 + Location +
    echoed tuple (transact_server.go:105-133), DELETE by URL query -> 204
    (:152-181), PATCH with [{action, relation_tuple}] deltas -> 204
    (:211-252)
  both                 — /health/alive, /health/ready, /version (healthx)
  metrics (:4468)      — GET /metrics/prometheus (prometheusx path)

Error bodies use the herodot JSON shape {"error": {code, status, message}}
via KetoError.to_dict. Unknown namespaces on the REST check path answer
{"allowed": false} instead of erroring (check/handler.go:156-161) — unlike
gRPC, which propagates NOT_FOUND.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..errors import KetoError, MalformedInputError, NamespaceNotFoundError
from ..observability import (
    RequestTrace,
    finish_request_telemetry,
    parse_traceparent,
    reset_request_trace,
    set_request_trace,
)
from ..ketoapi import (
    GetResponse,
    PatchDelta,
    RelationQuery,
    RelationTuple,
    SubjectSet,
)

READ_ROUTE_BASE = "/relation-tuples"
CHECK_ROUTE_BASE = "/relation-tuples/check"
CHECK_OPENAPI_ROUTE = "/relation-tuples/check/openapi"
# keto_tpu extension beside the parity surface: POST an ARRAY of tuples,
# get per-item verdicts in one round-trip (the reference has no batch
# check API — check/handler.go resolves one tuple per request)
CHECK_BATCH_ROUTE = "/relation-tuples/check/batch"
EXPAND_ROUTE = "/relation-tuples/expand"
# keto_tpu reverse-reachability extension (engine/reverse_kernel.py):
# "which objects can this subject reach" / "which subjects reach this
# object" — the reference has no such routes (Zanzibar's Leopard family)
LIST_OBJECTS_ROUTE = "/relation-tuples/list-objects"
LIST_SUBJECTS_ROUTE = "/relation-tuples/list-subjects"
# keto_tpu bulk-ACL-filter extension (engine/filter_kernel.py): POST a
# candidate object column, get back the subset the subject can see —
# search-result filtering (Zanzibar's dominant workload) as ONE request
FILTER_ROUTE = "/relation-tuples/filter"
# keto_tpu watch extension (keto_tpu/watch): the streaming changelog as
# Server-Sent Events — Zanzibar's Watch API (§2.4.3), absent from the
# reference
WATCH_ROUTE = "/relation-tuples/watch"
WRITE_ROUTE_BASE = "/admin/relation-tuples"
ALIVE_PATH = "/health/alive"
READY_PATH = "/health/ready"
VERSION_PATH = "/version"
METRICS_PATH = "/metrics/prometheus"
# on-demand capture admin (metrics listener only — the operator plane):
# POST starts a cpu/mem/jax capture against the RUNNING serve, POST
# .../stop writes the artifact; see keto_tpu/profiling.py
PROFILING_ROUTE = "/admin/profiling"
PROFILING_STOP_ROUTE = "/admin/profiling/stop"
# engine flight recorder (metrics listener): the live per-launch ring —
# device introspection counters, launch ids (join key for slow-query
# lines and typed batch errors), HBM/staleness accounting per built engine
FLIGHTREC_ROUTE = "/admin/flightrec"
# replica serving group (metrics listener): per-worker applied versions,
# pending counts, listener ports, and the hedge policy's live state
REPLICAS_ROUTE = "/admin/replicas"
# anti-entropy mirror scrubber (metrics listener, engine/scrub.py): GET
# reads counters/last-pass state, POST runs one full pass on demand and
# returns the per-nid report
SCRUB_ROUTE = "/admin/scrub"
# multi-daemon HA plane (metrics listener, api/follower.py): role,
# applied/observed leader versions, tail state, bootstrap/reconnect
# counters on a follower; store version + watch heartbeat on a leader
HA_ROUTE = "/admin/ha"
# workload observatory (metrics listener, observability_workload.py):
# hot-key sketch top-K + cache attribution, live SLO burn rates, and the
# capture/replay traffic profile `keto-tpu admin capture` downloads
HOTKEYS_ROUTE = "/admin/hotkeys"
SLO_ROUTE = "/admin/slo"
WORKLOAD_ROUTE = "/admin/workload"
SPEC_ROUTE = "/.well-known/openapi.json"

# route -> router kind, the ONE ownership table (consumed by the spec
# builder so a port's served spec can never advertise a route the port
# 404s; keep in sync with _resolve when adding routes)
ROUTE_KINDS = {
    READ_ROUTE_BASE: "read",
    CHECK_ROUTE_BASE: "read",
    CHECK_OPENAPI_ROUTE: "read",
    CHECK_BATCH_ROUTE: "read",
    EXPAND_ROUTE: "read",
    LIST_OBJECTS_ROUTE: "read",
    LIST_SUBJECTS_ROUTE: "read",
    FILTER_ROUTE: "read",
    WATCH_ROUTE: "read",
    WRITE_ROUTE_BASE: "write",
    ALIVE_PATH: "shared",
    READY_PATH: "shared",
    VERSION_PATH: "shared",
    SPEC_ROUTE: "shared",
    METRICS_PATH: "metrics",
    PROFILING_ROUTE: "metrics",
    PROFILING_STOP_ROUTE: "metrics",
    FLIGHTREC_ROUTE: "metrics",
    REPLICAS_ROUTE: "metrics",
    SCRUB_ROUTE: "metrics",
    HA_ROUTE: "metrics",
    HOTKEYS_ROUTE: "metrics",
    SLO_ROUTE: "metrics",
    WORKLOAD_ROUTE: "metrics",
}


def _get_page_size(params: dict[str, str], default: int) -> int:
    """page_size query param; malformed values are a 400, not a 500."""
    raw = params.get("page_size", "")
    if not raw:
        return default
    try:
        return int(raw) or default
    except ValueError:
        raise MalformedInputError(debug=f"invalid page_size {raw!r}")


def _get_max_depth(params: dict[str, str]) -> int:
    """ref: internal/x/max_depth.go (param name "max-depth", 0 if absent)."""
    raw = params.get("max-depth", "")
    if not raw:
        return 0
    try:
        return int(raw, 0)
    except ValueError:
        raise MalformedInputError(debug=f"invalid max-depth {raw!r}")


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server_version = "keto_tpu"

    # members injected by make_handler_class
    registry = None
    batcher = None
    worker = None  # replica ServeWorker (api/replica.py) | None
    kind = "read"  # read | write | metrics
    cors = None  # serve.<kind>.cors config dict (ref: daemon.go:289-349)
    watch_slots = None  # per-listener SSE watcher cap (make_handler_class)

    # -- plumbing -------------------------------------------------------------

    def log_message(self, fmt, *args):  # route through our logger, not stderr
        from ..observability import logger

        logger.debug("http %s", fmt % args)

    def _cors_headers(self) -> list[tuple[str, str]]:
        """CORS response headers for allowed origins (ref: negroni CORS
        middleware wired per listener, daemon.go:289-349)."""
        cfg = self.cors
        if not cfg or not cfg.get("enabled"):
            return []
        origin = self.headers.get("Origin")
        if not origin:
            return []
        allowed = cfg.get("allowed_origins") or ["*"]
        if "*" not in allowed and origin not in allowed:
            return []
        methods = cfg.get("allowed_methods") or [
            "GET", "POST", "PUT", "PATCH", "DELETE", "OPTIONS",
        ]
        headers = cfg.get("allowed_headers") or ["Authorization", "Content-Type"]
        return [
            (
                "Access-Control-Allow-Origin",
                "*" if "*" in allowed else origin,
            ),
            ("Access-Control-Allow-Methods", ", ".join(methods)),
            ("Access-Control-Allow-Headers", ", ".join(headers)),
            ("Vary", "Origin"),
        ]

    def _write(
        self, code: int, body: bytes, content_type="application/json",
        extra_headers: list[tuple[str, str]] | None = None,
    ) -> None:
        self._last_status = code
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        for k, v in extra_headers or ():
            self.send_header(k, v)
        for k, v in self._cors_headers():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _json(
        self, code: int, obj, location: str | None = None,
        extra_headers: list[tuple[str, str]] | None = None,
    ) -> None:
        body = json.dumps(obj).encode()
        self._last_status = code
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        if location is not None:
            self.send_header("Location", location)
        self.send_header("Content-Length", str(len(body)))
        for k, v in extra_headers or ():
            self.send_header(k, v)
        for k, v in self._cors_headers():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _error(self, err: Exception) -> None:
        if isinstance(err, KetoError):
            extra = None
            ra = getattr(err, "retry_after_s", None)
            if ra is not None:
                # shed responses (OverloadedError) carry the retry hint
                # the way HTTP specifies it; the gRPC planes mirror it as
                # trailing metadata from the same field
                from ..resilience import retry_after_header_value

                extra = [("Retry-After", retry_after_header_value(ra))]
            self._json(err.status, err.to_dict(), extra_headers=extra)
        else:
            e = KetoError(str(err))
            self._json(500, e.to_dict())

    def _params(self) -> dict[str, str]:
        qs = urllib.parse.urlparse(self.path).query
        return {k: v[0] for k, v in urllib.parse.parse_qs(qs).items()}

    def _body_json(self):
        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length) if length else b""
        try:
            return json.loads(raw or b"null")
        except json.JSONDecodeError as e:
            raise MalformedInputError(f"could not unmarshal json: {e}")

    def _route(self, method: str) -> None:
        path = urllib.parse.urlparse(self.path).path.rstrip("/") or "/"
        metrics = self.registry.metrics()
        # metrics are labeled by the MATCHED route constant — never the raw
        # request path (arbitrary scanner URLs would create unbounded
        # Prometheus label cardinality); unmatched requests share one label
        resolved = self._resolve(method, path)
        label = f"{method} {resolved[0]}" if resolved else "unmatched"
        # W3C trace ingestion: a traceparent header joins the caller's
        # trace (as a child span); absence starts a fresh one. The
        # RequestTrace rides the contextvar so the batcher/engine layers
        # and the traced store ops correlate without signature threading.
        ctx = parse_traceparent(self.headers.get("traceparent"))
        rt = RequestTrace(ctx.child() if ctx is not None else None)
        self._rt = rt
        self._last_status = 200
        token = set_request_trace(rt)
        t0 = time.perf_counter()
        outcome = None
        try:
            with metrics.observe_request("http", label) as outcome:
                if resolved is None:
                    outcome["code"] = "404"
                    from ..errors import NotFoundError

                    self._json(404, NotFoundError("route not found").to_dict())
                    return
                try:
                    # span-per-request (ref: otelx.TraceHandler,
                    # daemon.go:131-133); root=True makes this span the
                    # request's trace ROOT — it takes rt.ctx's span id,
                    # so the batcher/engine/store spans parent-link to
                    # it and IT parent-links to the caller's client span
                    # from the ingested traceparent (the OTLP export
                    # plane's hierarchy)
                    with self.registry.tracer().span(
                        f"http.{label}", ctx=rt.ctx, root=True
                    ):
                        resolved[1]()
                    # handlers that WRITE an error status directly (503
                    # ready probe, 404 nil expand, 403 check mirror, 429
                    # watch cap) must not count as code="OK"
                    if self._last_status >= 400:
                        outcome["code"] = str(self._last_status)
                except KetoError as e:
                    outcome["code"] = str(e.status)
                    self._error(e)
                except (BrokenPipeError, ConnectionResetError):
                    raise
                except Exception as e:  # noqa: BLE001 — HTTP boundary
                    outcome["code"] = "500"
                    self._error(e)
        finally:
            reset_request_trace(token)
            # SSE watch streams block in the handler for their whole
            # lifetime by design — a stream's duration is not a slow
            # query, so it never trips the threshold log
            finish_request_telemetry(
                metrics,
                self.registry.config.get("log.slow_query_ms"),
                "http", label, rt,
                outcome.code if outcome is not None else "500",
                time.perf_counter() - t0,
                skip_slow=(
                    resolved is not None and resolved[0] == WATCH_ROUTE
                ),
                sample_rate=self.registry.config.get(
                    "log.request_sample_rate"
                ),
                workload=self.registry.workload_observatory(),
            )

    # -- routing --------------------------------------------------------------

    def _resolve(self, method: str, path: str):
        """(route constant, handler thunk) for a matched route, else None."""
        # shared routes
        if method == "GET":
            if path == ALIVE_PATH:
                return ALIVE_PATH, lambda: self._json(200, {"status": "ok"})
            if path == READY_PATH:

                def ready():
                    ok = self.registry.ready.is_set()
                    self._json(
                        200 if ok else 503,
                        {"status": "ok" if ok else "unavailable"},
                    )

                return READY_PATH, ready
            if path == VERSION_PATH:
                return VERSION_PATH, lambda: self._json(
                    200, {"version": self.registry.version}
                )
            if path == SPEC_ROUTE and self.kind in ("read", "write"):
                # generated-from-route-table OpenAPI document (ref serves
                # its swagger spec + docs, doc_swagger.go:1)
                def spec():
                    from .openapi import build_spec

                    self._json(
                        200, build_spec(self.registry.version, kind=self.kind)
                    )

                return SPEC_ROUTE, spec

        if self.kind == "metrics":
            if method == "GET" and path == METRICS_PATH:
                return METRICS_PATH, self._metrics_export
            if path == PROFILING_ROUTE:
                if method == "GET":
                    return PROFILING_ROUTE, self._profiling_status
                if method == "POST":
                    return PROFILING_ROUTE, self._profiling_start
            if method == "POST" and path == PROFILING_STOP_ROUTE:
                return PROFILING_STOP_ROUTE, self._profiling_stop
            if method == "GET" and path == FLIGHTREC_ROUTE:
                return FLIGHTREC_ROUTE, self._flightrec_dump
            if method == "GET" and path == REPLICAS_ROUTE:
                return REPLICAS_ROUTE, self._replicas_status
            if method == "GET" and path == HA_ROUTE:
                return HA_ROUTE, self._ha_status
            if path == SCRUB_ROUTE:
                if method == "GET":
                    return SCRUB_ROUTE, self._scrub_status
                if method == "POST":
                    return SCRUB_ROUTE, self._scrub_trigger
            if method == "GET" and path == HOTKEYS_ROUTE:
                return HOTKEYS_ROUTE, self._hotkeys_dump
            if method == "GET" and path == SLO_ROUTE:
                return SLO_ROUTE, self._slo_dump
            if method == "GET" and path == WORKLOAD_ROUTE:
                return WORKLOAD_ROUTE, self._workload_profile
            return None

        if self.kind == "read":
            if method == "GET" and path == READ_ROUTE_BASE:
                return READ_ROUTE_BASE, self._get_relations
            if path == CHECK_ROUTE_BASE and method in ("GET", "POST"):
                return CHECK_ROUTE_BASE, lambda: self._check(
                    method, mirror_status=True
                )
            if path == CHECK_OPENAPI_ROUTE and method in ("GET", "POST"):
                return CHECK_OPENAPI_ROUTE, lambda: self._check(
                    method, mirror_status=False
                )
            if path == CHECK_BATCH_ROUTE and method == "POST":
                return CHECK_BATCH_ROUTE, self._check_batch
            if method == "GET" and path == EXPAND_ROUTE:
                return EXPAND_ROUTE, self._expand
            if method == "GET" and path == LIST_OBJECTS_ROUTE:
                return LIST_OBJECTS_ROUTE, self._list_objects
            if method == "GET" and path == LIST_SUBJECTS_ROUTE:
                return LIST_SUBJECTS_ROUTE, self._list_subjects
            if method == "POST" and path == FILTER_ROUTE:
                return FILTER_ROUTE, self._filter
            if method == "GET" and path == WATCH_ROUTE:
                return WATCH_ROUTE, self._watch
            return None

        # write router
        if path == WRITE_ROUTE_BASE:
            if method == "PUT":
                return WRITE_ROUTE_BASE, self._create_relation
            if method == "DELETE":
                return WRITE_ROUTE_BASE, self._delete_relations
            if method == "PATCH":
                return WRITE_ROUTE_BASE, self._patch_relations
        return None

    # -- read handlers --------------------------------------------------------

    def _nid(self) -> str:
        """Per-request network id via the Contextualizer hook (ref:
        ketoctx/contextualizer.go:12-19); default: the registry nid."""
        return self.registry.nid_for(self.headers)

    def _get_relations(self) -> None:
        """ref: read_server.go:122-175."""
        params = self._params()
        query = RelationQuery.from_url_query(params)
        self.registry.validate_namespaces(query)
        page_size = int(params.get("page_size") or 0) or self.registry.config.page_size()
        tuples, next_token = self.registry.relation_tuple_manager().get_relation_tuples(
            query,
            page_token=params.get("page_token", ""),
            page_size=page_size,
            nid=self._nid(),
        )
        self._json(200, GetResponse(tuples, next_token).to_dict())

    def _enforce_snaptoken(self, token: str, nid: str) -> int:
        from ..engine.snaptoken import enforce_snaptoken

        return enforce_snaptoken(self.registry, token, nid)

    def _ingest_deadline(self):
        """The request's end-to-end Deadline from the
        `x-request-timeout-ms` header (or serve.check.default_deadline_ms,
        clamped to max_deadline_ms), attached to the RequestTrace so the
        cache -> batcher -> device pipeline fails fast at every stage
        boundary once the budget is spent. Returns the rt (or None)."""
        from ..resilience import ingest_deadline, parse_timeout_ms

        rt = getattr(self, "_rt", None)
        if rt is not None:
            rt.deadline = ingest_deadline(
                self.registry.config,
                request_ms=parse_timeout_ms(
                    self.headers.get("x-request-timeout-ms")
                ),
            )
        return rt

    def _check(self, method: str, mirror_status: bool) -> None:
        """ref: check/handler.go getCheck/postCheck + 403 mirroring.
        Snaptokens (keto_tpu extension; the reference REST check has no
        token surface at all): a `snaptoken` query param pins the read,
        and the response carries the evaluated version's token in the
        X-Keto-Snaptoken header — a header, so the parity JSON body
        stays byte-identical to the reference's {"allowed": ...}.

        `explain=true` (query param, or an `explain` body field on POST
        — keto_tpu extension, §5m) returns a DecisionTrace beside the
        verdict: answering tier + cause, host-re-walked witness path
        (differential-checked against the authoritative device
        verdict), exhaustion summary for DENY, per-stage ms, launch
        ids. Explain bypasses the check cache and is admission-bounded
        by the explain.max_per_s token bucket (typed 429)."""
        from ..engine.snaptoken import encode_snaptoken
        from ..resilience import admit_check, admit_explain

        # deadline ingestion + admission gate BEFORE any work — body
        # parsing included: a shed/draining POST must cost nothing (the
        # overload path is exactly what this gate protects). The explain
        # flag picks the gate: explain rides the token bucket, never the
        # batcher's queue accounting. The query param decides PRE-parse;
        # a POST that opts in via the body field instead pays one extra
        # advisory batcher check (state-free) and then the token gate.
        rt = self._ingest_deadline()
        params = self._params()
        explain = params.get("explain", "").lower() in ("1", "true")
        if explain:
            admit_explain(self.registry, rt)
        else:
            admit_check(self.registry, self.batcher, rt)
        body = None
        if method != "GET":
            body = self._body_json()
            if not isinstance(body, dict):
                raise MalformedInputError(
                    "could not unmarshal json: expected object"
                )
            if not explain and body.get("explain"):
                explain = True
                admit_explain(self.registry, rt)
        max_depth = _get_max_depth(params)
        if method == "GET":
            t = RelationTuple.from_url_query(params)
        else:
            t = RelationTuple.from_dict(body)
        nid = self._nid()
        token = params.get("snaptoken", "")
        if self.worker is not None:
            # replica mode: the snaptoken routing rule picks the
            # answering worker and the version the response token is
            # minted at (token parse/409 precedence matches the
            # single-stack enforce path: before the namespace corner)
            from .replica import resolve_version, serve_on

            target, version = resolve_version(
                self.worker.group, self.worker, nid, token, rt
            )
        else:
            target = None
            version = self._enforce_snaptoken(token, nid)
        token_hdr = [("X-Keto-Snaptoken", encode_snaptoken(version, nid))]
        try:
            self.registry.validate_namespaces(t)
        except NamespaceNotFoundError:
            # unknown namespace => allowed=false, not 404 (handler.go:156-161)
            rt.tier = "vocab"
            obs = self.registry.workload_observatory()
            if obs is not None:
                # the swallowed corner never reaches the serve gate, so
                # the workload accounting records it here
                obs.record_check(nid, t, False, tier="vocab")
            code = 403 if mirror_status else 200
            payload: dict = {"allowed": False}
            if explain:
                # the REST-only swallowed corner never reaches the
                # engine: the trace says so (vocab tier — the name is
                # outside the configured vocabulary)
                from ..engine.explain import vocab_trace

                self.registry.metrics().explain_requests_total.inc()
                payload["decision_trace"] = vocab_trace(
                    version, encode_snaptoken(version, nid),
                    "namespace_not_found",
                )
            self._json(code, payload, extra_headers=token_hdr)
            return
        if explain:
            from ..engine.explain import serve_explain

            res, trace = serve_explain(
                self.registry, nid, t, max_depth, version, rt
            )
            if res.error is not None:
                raise res.error
            code = 403 if (mirror_status and not res.allowed) else 200
            self._json(
                code, {"allowed": res.allowed, "decision_trace": trace},
                extra_headers=token_hdr,
            )
            return
        if target is not None:
            res = serve_on(target, nid, t, max_depth, version, rt)
        else:
            # serve fast path (api/check_cache.py): a hit returns before
            # the batcher — no assemble/dispatch/device stages run, and
            # the response (snaptoken included) is byte-identical to a
            # miss at the same store version
            from .check_cache import cached_check

            res = cached_check(
                self.registry, self.batcher, nid, t, max_depth, version,
                rt,
            )
        if res.error is not None:
            raise res.error
        code = 403 if (mirror_status and not res.allowed) else 200
        self._json(code, {"allowed": res.allowed}, extra_headers=token_hdr)

    def _check_batch(self) -> None:
        """keto_tpu extension: POST {"tuples": [...], "max_depth"?} (or a
        bare array) -> {"results": [{"allowed": bool} | {"allowed":
        false, "error": str}, ...]} in request order. The whole batch
        rides ONE engine.check_batch launch; per-item problems (bad
        subject, unknown names via host replay) never fail the batch."""
        from ..resilience import admit_check

        metrics = self.registry.metrics()
        # draining/expired gate (no queue bound: the batch rides one
        # direct engine launch, not the batcher queue)
        rt = self._ingest_deadline()
        with metrics.stage("decode", rt):
            admit_check(self.registry, None, rt)
            params = self._params()
            body = self._body_json()
            if isinstance(body, dict):
                raw = body.get("tuples")
                raw_depth = body.get("max_depth")
                if raw_depth is None:
                    # ABSENCE, not falsiness: an explicit JSON max_depth
                    # of 0 must override a non-zero ?max-depth query param
                    max_depth = _get_max_depth(params)
                else:
                    try:
                        max_depth = int(raw_depth)
                    except (TypeError, ValueError):
                        raise MalformedInputError(
                            "max_depth must be an integer"
                        )
            else:
                raw = body
                max_depth = _get_max_depth(params)
            if not isinstance(raw, list):
                raise MalformedInputError(
                    "could not unmarshal json: expected array of relation tuples"
                )
            from ..engine.snaptoken import encode_snaptoken

            nid = self._nid()
            req_token = params.get("snaptoken", "")
            if isinstance(body, dict):
                req_token = body.get("snaptoken") or req_token
            version = self._enforce_snaptoken(req_token, nid)
            idx: list[int] = []
            tuples: list[RelationTuple] = []
            out: list[dict] = [None] * len(raw)  # type: ignore[list-item]
            for i, d in enumerate(raw):
                try:
                    if not isinstance(d, dict):
                        raise MalformedInputError(
                            "could not unmarshal json: expected object"
                        )
                    t = RelationTuple.from_dict(d)
                    # unlike the single-check REST route (which swallows
                    # unknown namespaces to allowed=false for parity), the
                    # batch extension reports them per item — strictly
                    # more information, and consistent with the gRPC
                    # batch plane
                    self.registry.validate_namespaces(t)
                except KetoError as e:
                    out[i] = {"allowed": False, "error": e.message}
                    continue
                idx.append(i)
                tuples.append(t)
            engine = self.registry.check_engine(nid)
        # the engine adds assemble / dispatch / device_wait / resolve to
        # this request's trace itself (check_batch reads the contextvar)
        results = engine.check_batch(tuples, max_depth)
        with metrics.stage("respond", rt):
            answered: list[RelationTuple] = []
            verdicts: list[bool] = []
            for i, t, res in zip(idx, tuples, results):
                if res.error is not None:
                    out[i] = {"allowed": False, "error": str(res.error)}
                else:
                    allowed = res.allowed
                    out[i] = {"allowed": allowed}
                    answered.append(t)
                    verdicts.append(allowed)
            obs = self.registry.workload_observatory()
            if obs is not None:
                # workload accounting, once a batch (the batch bypasses
                # the single-check serve gate); the whole batch rode one
                # launch, so no per-item tier stamp exists here
                obs.record_check_batch(nid, answered, verdicts)
            self._json(
                200,
                {"results": out, "snaptoken": encode_snaptoken(version, nid)},
            )

    def _expand(self) -> None:
        """ref: expand/handler.go:43-107 (GET, subject-set params)."""
        params = self._params()
        max_depth = _get_max_depth(params)
        try:
            subject_set = SubjectSet(
                namespace=params["namespace"],
                object=params["object"],
                relation=params["relation"],
            )
        except KeyError:
            raise MalformedInputError(
                debug="expand requires namespace, object, and relation"
            )
        self.registry.validate_namespaces(subject_set)
        tree = self.registry.expand_engine(self._nid()).expand(subject_set, max_depth)
        if tree is None:
            from ..errors import NotFoundError

            self._json(404, NotFoundError("no relation tuples found").to_dict())
            return
        self._json(200, tree.to_dict())

    def _list_objects(self) -> None:
        """keto_tpu reverse-reachability extension: GET with namespace,
        relation, and a subject (subject_id or subject_set.*) -> the
        sorted objects the subject reaches, paginated; snaptoken-
        enforced like check, evaluated-version token in the
        X-Keto-Snaptoken header."""
        from ..engine.snaptoken import encode_snaptoken

        params = self._params()
        max_depth = _get_max_depth(params)
        namespace = params.get("namespace")
        relation = params.get("relation")
        if not namespace or not relation:
            raise MalformedInputError(
                debug="list-objects requires namespace and relation"
            )
        subject = self._subject_from_params(params)
        nid = self._nid()
        version = self._enforce_snaptoken(params.get("snaptoken", ""), nid)
        self.registry.validate_namespaces(
            RelationQuery(namespace=namespace),
            subject if isinstance(subject, SubjectSet) else None,
        )
        page_size = _get_page_size(params, self.registry.config.page_size())
        engine = self.registry.check_engine(nid)
        objects, next_token = engine.list_objects(
            namespace, relation, subject, max_depth,
            page_size=page_size, page_token=params.get("page_token", ""),
        )
        self._json(
            200,
            {"objects": objects, "next_page_token": next_token},
            extra_headers=[("X-Keto-Snaptoken", encode_snaptoken(version, nid))],
        )

    def _list_subjects(self) -> None:
        """keto_tpu reverse-reachability extension: GET with namespace,
        object, relation -> the sorted plain subject ids that reach the
        node, paginated."""
        from ..engine.snaptoken import encode_snaptoken

        params = self._params()
        max_depth = _get_max_depth(params)
        try:
            namespace = params["namespace"]
            obj = params["object"]
            relation = params["relation"]
        except KeyError:
            raise MalformedInputError(
                debug="list-subjects requires namespace, object, and relation"
            )
        nid = self._nid()
        version = self._enforce_snaptoken(params.get("snaptoken", ""), nid)
        self.registry.validate_namespaces(RelationQuery(namespace=namespace))
        page_size = _get_page_size(params, self.registry.config.page_size())
        engine = self.registry.check_engine(nid)
        subjects, next_token = engine.list_subjects(
            namespace, obj, relation, max_depth,
            page_size=page_size, page_token=params.get("page_token", ""),
        )
        self._json(
            200,
            {"subject_ids": subjects, "next_page_token": next_token},
            extra_headers=[("X-Keto-Snaptoken", encode_snaptoken(version, nid))],
        )

    def _filter(self) -> None:
        """keto_tpu bulk-ACL-filter extension: POST {"namespace",
        "relation", "subject_id" | "subject_set", "objects": [...],
        "max_depth"?, "snaptoken"?} -> {"allowed_objects": [...],
        "snaptoken": ...} — the subset of the candidate column the
        subject can see, in request order. Admission (draining 429 /
        expired 504 / filter.max_objects 400) runs BEFORE any work; the
        engine re-checks the deadline at every chunk boundary; replica
        mode routes the snaptoken through the hold/route/escalate rule
        like Check."""
        from ..engine.snaptoken import encode_snaptoken
        from ..ketoapi import _subject_fields_from_dict
        from ..resilience import admit_filter

        rt = self._ingest_deadline()
        body = self._body_json()
        if not isinstance(body, dict):
            raise MalformedInputError("could not unmarshal json: expected object")
        objects = body.get("objects")
        if not isinstance(objects, list) or not all(
            isinstance(o, str) for o in objects
        ):
            raise MalformedInputError(
                "filter requires \"objects\": an array of object names"
            )
        admit_filter(self.registry, len(objects), rt)
        namespace = body.get("namespace")
        relation = body.get("relation")
        if not namespace or not relation:
            raise MalformedInputError(
                debug="filter requires namespace and relation"
            )
        subject_id, subject_set = _subject_fields_from_dict(body)
        if subject_id is None and subject_set is None:
            from ..errors import NilSubjectError

            raise NilSubjectError()
        subject = subject_set if subject_set is not None else subject_id
        raw_depth = body.get("max_depth")
        if raw_depth is None:
            max_depth = _get_max_depth(self._params())
        else:
            try:
                max_depth = int(raw_depth)
            except (TypeError, ValueError):
                raise MalformedInputError("max_depth must be an integer")
        nid = self._nid()
        token = body.get("snaptoken") or self._params().get("snaptoken", "")
        if self.worker is not None:
            from .replica import resolve_version

            _target, version = resolve_version(
                self.worker.group, self.worker, nid, token, rt
            )
        else:
            version = self._enforce_snaptoken(token, nid)
        self.registry.validate_namespaces(
            RelationQuery(namespace=namespace),
            subject if isinstance(subject, SubjectSet) else None,
        )
        engine = self.registry.check_engine(nid)
        allowed = engine.filter_objects(
            namespace, relation, subject, objects, max_depth,
            deadline=getattr(rt, "deadline", None) if rt is not None else None,
        )
        self._json(
            200,
            {
                "allowed_objects": allowed,
                "snaptoken": encode_snaptoken(version, nid),
            },
        )

    # SSE keep-alive cadence: also the disconnect-detection bound (a
    # vanished client is only noticed on the next write). Default for
    # the `watch.heartbeat_s` schema key — a half-open TCP connection
    # (NAT drop, killed peer) is detected within one heartbeat, the
    # write fails, and the finally below frees the subscriber ring
    # instead of letting an orphaned cursor pin changelog retention.
    WATCH_HEARTBEAT_S = 5.0

    def _watch(self) -> None:
        """keto_tpu watch extension: the streaming changelog as
        Server-Sent Events. `snaptoken` resumes the cursor (every change
        strictly after it, exactly once, in version order — 409 when the
        token is ahead of the store, an explicit `reset` event when the
        bounded changelog no longer reaches it); `namespace` filters;
        `max_events` (scripting/testing aid) closes the stream after N
        events. Each SSE message is one committed store version:

            event: change | reset
            data: {"event_type", "snaptoken", "changes": [
                      {"action": "insert"|"delete", "relation_tuple": {...}}]}

        Token/parse errors surface as normal JSON errors (they happen
        before the stream opens)."""
        from ..engine.snaptoken import parse_snaptoken

        params = self._params()
        nid = self._nid()
        namespace = params.get("namespace", "")
        if namespace:
            self.registry.validate_namespaces(RelationQuery(namespace=namespace))
        max_events = None
        if params.get("max_events"):
            try:
                max_events = int(params["max_events"])
            except ValueError:
                raise MalformedInputError(
                    debug=f"invalid max_events {params['max_events']!r}"
                )
        min_version = parse_snaptoken(params.get("snaptoken", ""), nid)
        # SSE streams pin one server thread each, exactly like gRPC
        # watch streams pin a worker. The CONFIG KNOB is shared
        # (serve.read.grpc.max_watchers) but the slot pool is
        # per-listener: each transport serves from its own thread pool,
        # so the process-wide ceiling is the knob times the number of
        # watch-capable listeners
        if not self.watch_slots.acquire(blocking=False):
            self._json(
                429,
                {"error": {"code": 429, "status": "Too Many Requests",
                           "message": "too many concurrent watchers"}},
            )
            return
        try:
            self._watch_stream(nid, namespace, min_version, max_events)
        finally:
            self.watch_slots.release()

    def _watch_stream(self, nid, namespace, min_version, max_events) -> None:
        sub = self.registry.watch_hub().subscribe(nid, min_version)
        self.close_connection = True  # the stream IS the response body
        try:
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            for k, v in self._cors_headers():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(b": stream open\n\n")
            self.wfile.flush()
            heartbeat_s = float(
                self.registry.config.get(
                    "watch.heartbeat_s", self.WATCH_HEARTBEAT_S
                )
            )
            delivered = 0
            last_write = time.monotonic()
            while max_events is None or delivered < max_events:
                # keep-alives are due by WALL time, not idle-gets: a
                # stream whose events are all namespace-filtered out is
                # busy and would otherwise stay wire-silent forever
                if time.monotonic() - last_write >= heartbeat_s:
                    last_write = time.monotonic()
                    self.wfile.write(b": keep-alive\n\n")
                    self.wfile.flush()
                event = sub.get(
                    timeout=max(
                        0.05,
                        heartbeat_s - (time.monotonic() - last_write),
                    )
                )
                if event is None:
                    if sub.closed:  # daemon drain ends the stream
                        break
                    continue
                event = event.filtered(namespace)
                if event is None:
                    continue
                payload = json.dumps(event.to_dict())
                self.wfile.write(
                    f"event: {event.kind}\ndata: {payload}\n\n".encode()
                )
                self.wfile.flush()
                last_write = time.monotonic()
                delivered += 1
        except (BrokenPipeError, ConnectionResetError):
            pass  # client went away: normal end of a watch stream
        finally:
            sub.close()

    @staticmethod
    def _subject_from_params(params: dict[str, str]):
        """subject_id or subject_set.{namespace,object,relation} from URL
        params (the check route's subject vocabulary)."""
        if "subject_id" in params:
            return params["subject_id"]
        try:
            return SubjectSet(
                namespace=params["subject_set.namespace"],
                object=params["subject_set.object"],
                relation=params["subject_set.relation"],
            )
        except KeyError:
            raise MalformedInputError(
                debug="a subject_id or subject_set.* subject is required"
            )

    # -- profiling admin (metrics listener) -----------------------------------

    def _profiling_status(self) -> None:
        self._json(200, self.registry.profiler().status())

    @staticmethod
    def _confine_profile_path(path: str) -> str:
        """Client-supplied artifact paths resolve INSIDE the profile
        directory (KETO_PROFILE_DIR, default the system tempdir) — the
        admin endpoint must not be an arbitrary-file-write primitive for
        whoever can reach the metrics port."""
        import os
        import tempfile

        base = os.path.realpath(
            os.environ.get("KETO_PROFILE_DIR") or tempfile.gettempdir()
        )
        resolved = os.path.realpath(os.path.join(base, path))
        if resolved != base and not resolved.startswith(base + os.sep):
            raise MalformedInputError(
                debug=f"profiling path must stay inside {base!r} "
                "(set KETO_PROFILE_DIR to change the allowed directory)"
            )
        return resolved

    def _profiling_start(self) -> None:
        """POST /admin/profiling {"mode": "cpu"|"mem"|"jax", "path"?}
        (or ?mode= query param): start an on-demand capture against the
        RUNNING serve. 400 on unknown mode or a path escaping the
        profile directory, 409 while one is running."""
        body = self._body_json()
        params = self._params()
        mode = ""
        path = None
        if isinstance(body, dict):
            mode = body.get("mode") or ""
            path = body.get("path") or None
        mode = mode or params.get("mode", "")
        path = path or params.get("path") or None
        if path is not None:
            path = self._confine_profile_path(path)
        try:
            self._json(200, self.registry.profiler().start(mode, path))
        except ValueError as e:
            raise MalformedInputError(debug=str(e))
        except RuntimeError as e:
            self._json(
                409,
                {"error": {"code": 409, "status": "Conflict",
                           "message": str(e)}},
            )

    def _profiling_stop(self) -> None:
        """POST /admin/profiling/stop: end the capture and write its
        artifact. Idempotent — a stop with nothing running answers
        {"running": false, "artifact": null} instead of erroring."""
        artifact = self.registry.profiler().stop()
        self._json(200, {"running": False, "artifact": artifact})

    def _metrics_export(self) -> None:
        """GET /metrics/prometheus: classic text exposition by default;
        an Accept header asking for `application/openmetrics-text` gets
        the OpenMetrics format instead — the one that carries the
        EXEMPLARS (trace_id per stage-histogram bucket) linking the
        metrics plane to the trace plane."""
        metrics = self.registry.metrics()
        accept = self.headers.get("Accept") or ""
        if "application/openmetrics-text" in accept:
            self._write(
                200, metrics.export_openmetrics(),
                content_type=metrics.OPENMETRICS_CONTENT_TYPE,
            )
            return
        self._write(
            200, metrics.export(),
            content_type="text/plain; version=0.0.4; charset=utf-8",
        )

    def _flightrec_dump(self) -> None:
        """GET /admin/flightrec: the live launch ring plus
        per-built-engine HBM/staleness snapshots. Entries come back in
        LAUNCH-ID order (newest last): the ring itself holds resolve
        order, and with two batching planes sharing one engine a later
        submit can resolve first — id order is the submission order
        consumers join on. Entry launch_ids join the slow-query WARNING
        lines, the request log, and typed CheckBatchFailedError
        messages; entry ages are derivable from `now_mono` - entry
        `t_mono` (monotonic stamps — wall clocks are banned repo-wide).
        Reads only already-built state: no engine or device mirror is
        instantiated from the admin plane.

        Filters (the ring now holds 7 launch kinds — dumping everything
        to find one filter launch is noise): `?kind=` keeps entries of
        one launch kind (check | closure | expand | list_objects |
        list_subjects | filter | filter_closure), `?trace_id=` keeps
        entries whose riders carried that trace id, `?since_launch_id=`
        keeps entries with a STRICTLY larger launch id — the tail
        cursor: a poller passes the max id it has seen and downloads
        only the increment instead of the whole ring (id order is the
        documented join order, so the cursor is total). All compose."""
        import time as _time

        params = self._params()
        fr = self.registry.flight_recorder()
        hbm = {}
        for nid, engine in self.registry.built_engines().items():
            snap = getattr(engine, "hbm_snapshot", None)
            if snap is not None:
                hbm[nid] = snap()
        entries = sorted(
            fr.entries(), key=lambda e: e.get("launch_id") or 0
        )
        kind = params.get("kind", "")
        if kind:
            entries = [e for e in entries if e.get("kind") == kind]
        trace_id = params.get("trace_id", "")
        if trace_id:
            entries = [
                e for e in entries
                if trace_id in (e.get("trace_ids") or ())
            ]
        since = params.get("since_launch_id", "")
        if since:
            try:
                since_id = int(since)
            except ValueError:
                raise MalformedInputError(
                    "since_launch_id must be an integer"
                )
            entries = [
                e for e in entries
                if (e.get("launch_id") or 0) > since_id
            ]
        self._json(200, {
            "enabled": fr.enabled,
            "capacity": fr.capacity,
            "now_mono": _time.monotonic(),
            "entries": entries,
            "hbm": hbm,
        })

    def _scrub_status(self) -> None:
        """GET /admin/scrub: the anti-entropy scrubber's config +
        counters + last-pass facts (engine/scrub.py). Reads state only —
        no pass runs, no engine is built."""
        self._json(200, self.registry.mirror_scrubber().status())

    def _scrub_trigger(self) -> None:
        """POST /admin/scrub: run ONE full scrub pass NOW (works with
        `scrub.enabled: false` — the on-demand audit an operator runs
        after a device scare) and return the per-nid report plus the
        refreshed status."""
        scrubber = self.registry.mirror_scrubber()
        report = scrubber.scrub_pass()
        self._json(200, {"report": report, **scrubber.status()})

    def _replicas_status(self) -> None:
        """GET /admin/replicas: the replica serving group's live state —
        per-worker applied store versions (the snaptoken routing rule's
        input), admitted-but-unresolved counts, listener ports, and the
        hedge policy's current quantile delay. {"workers": []} outside
        replica mode (serve.check.workers unset or 1)."""
        group = self.registry.replica_group
        if group is None:
            self._json(200, {"workers": [], "group_pending": 0})
            return
        self._json(200, group.stats())

    def _ha_status(self) -> None:
        """GET /admin/ha: this daemon's HA-plane view. On a follower
        (follower.enabled): role, leader address, tail state, applied vs
        observed leader version (the per-daemon staleness the router's
        snaptoken rule keys on), last-frame age, and the bootstrap /
        reconnect counters the HA smoke pins (zero full reads in steady
        state). On a leader: role + live store version + watch
        heartbeat config — the ground truth followers converge to."""
        self._json(200, self.registry.ha_status())

    def _hotkeys_dump(self) -> None:
        """GET /admin/hotkeys: the Space-Saving sketches' live top-K
        (object keys, subject keys, full check tuples) with counts,
        overestimation errors, and traffic shares — plus the check-cache
        attribution join ("the top 100 keys are X% of traffic, hit-ratio
        Y" in one response). `?top=` bounds the per-kind entry count
        (default 100, capped at the sketch capacity by construction)."""
        params = self._params()
        top = 100
        raw = params.get("top", "")
        if raw:
            try:
                top = max(1, int(raw))
            except ValueError:
                raise MalformedInputError("top must be an integer")
        obs = self.registry.workload_observatory()
        cache = self.registry.check_cache()
        self._json(200, obs.hotkeys(
            top=top,
            cache_stats=cache.stats() if cache is not None else None,
        ))

    def _slo_dump(self) -> None:
        """GET /admin/slo: live burn rates per objective over both
        windows, event/bad counts, and the fast-burn flags — the same
        numbers the keto_tpu_slo_* gauges export, with the window
        arithmetic visible."""
        self._json(200, self.registry.workload_observatory().slo_status())

    def _workload_profile(self) -> None:
        """GET /admin/workload: the capture/replay traffic profile
        (key-popularity histograms, per-(nid, namespace, relation)
        accounting, read/write ratio) — `keto-tpu admin capture`
        downloads this and `tools/load_gen.py --profile` replays its
        shape. `?top=` bounds the key-popularity histogram length."""
        params = self._params()
        top = 100
        raw = params.get("top", "")
        if raw:
            try:
                top = max(1, int(raw))
            except ValueError:
                raise MalformedInputError("top must be an integer")
        self._json(200, self.registry.workload_observatory().profile(top=top))

    # -- write handlers -------------------------------------------------------

    def _create_relation(self) -> None:
        """ref: transact_server.go:105-133 (201 + Location + echo)."""
        body = self._body_json()
        if not isinstance(body, dict):
            raise MalformedInputError("could not unmarshal json: expected object")
        t = RelationTuple.from_dict(body)
        self.registry.validate_namespaces(t)
        from ..engine.snaptoken import encode_snaptoken

        nid = self._nid()
        manager = self.registry.relation_tuple_manager()
        manager.write_relation_tuples([t], nid=nid)
        location = READ_ROUTE_BASE + "?" + urllib.parse.urlencode(t.to_url_query())
        # post-write token in a header: the parity body stays the echoed
        # tuple exactly as the reference returns it
        self._json(
            201, t.to_dict(), location=location,
            extra_headers=[(
                "X-Keto-Snaptoken",
                encode_snaptoken(manager.version(nid=nid), nid),
            )],
        )

    def _delete_relations(self) -> None:
        """ref: transact_server.go:152-181 (by URL query, 204)."""
        query = RelationQuery.from_url_query(self._params())
        self.registry.validate_namespaces(query)
        self.registry.relation_tuple_manager().delete_all_relation_tuples(
            query, nid=self._nid()
        )
        self._write(204, b"", content_type="application/json")

    def _patch_relations(self) -> None:
        """ref: transact_server.go:211-252 (deltas, 204)."""
        body = self._body_json()
        if not isinstance(body, list):
            raise MalformedInputError("could not unmarshal json: expected array")
        deltas = [PatchDelta.from_dict(d) for d in body]
        inserts = [d.relation_tuple for d in deltas if d.action.value == "insert"]
        deletes = [d.relation_tuple for d in deltas if d.action.value == "delete"]
        self.registry.validate_namespaces(*inserts, *deletes)
        from ..engine.snaptoken import encode_snaptoken

        nid = self._nid()
        manager = self.registry.relation_tuple_manager()
        manager.transact_relation_tuples(inserts, deletes, nid=nid)
        # 204 has no body to carry a token; the header does (the parity
        # status/body stay exactly the reference's)
        self._write(
            204, b"",
            extra_headers=[(
                "X-Keto-Snaptoken",
                encode_snaptoken(manager.version(nid=nid), nid),
            )],
        )

    # -- HTTP verbs -----------------------------------------------------------

    def do_GET(self):
        self._route("GET")

    def do_POST(self):
        self._route("POST")

    def do_PUT(self):
        self._route("PUT")

    def do_DELETE(self):
        self._route("DELETE")

    def do_PATCH(self):
        self._route("PATCH")

    def do_OPTIONS(self):
        # CORS preflight: 204 with the allow headers (no routing)
        self.send_response(204)
        for k, v in self._cors_headers():
            self.send_header(k, v)
        self.send_header("Content-Length", "0")
        self.end_headers()


def make_handler_class(registry, kind: str, batcher=None, cors=None,
                       worker=None):
    # one watcher-slot pool per listener, shared by every connection of
    # the handler class (the SSE analog of _Services._watch_slots)
    watch_slots = threading.BoundedSemaphore(
        int(registry.config.get("serve.read.grpc.max_watchers", 16))
    )
    return type(
        f"KetoHTTP{kind.capitalize()}Handler",
        (_Handler,),
        {"registry": registry, "kind": kind, "batcher": batcher,
         "cors": cors, "watch_slots": watch_slots, "worker": worker},
    )


class RESTServer:
    """One HTTP listener (read, write, or metrics router)."""

    def __init__(
        self, registry, kind: str, host: str, port: int, batcher=None,
        cors=None, worker=None,
    ):
        handler = make_handler_class(registry, kind, batcher, cors=cors,
                                     worker=worker)
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.httpd.daemon_threads = True
        self.kind = kind
        self._thread: threading.Thread | None = None

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.httpd.serve_forever,
            name=f"keto-http-{self.kind}",
            daemon=True,
        )
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)
