"""Observability: Prometheus metrics, OpenTelemetry tracing, request logs.

Parity with the reference's aux subsystems (SURVEY.md §5.1/§5.5):
prometheusx metrics served on the metrics port (registry_default.go:
131-143, daemon.go:421-436), otelx tracer with spans in every persister/
handler method, logrusx structured request logging (daemon.go:294).

Beyond parity, this module carries the request-scoped telemetry plane:
W3C `traceparent` contexts ingested at the transports flow (as a
`RequestTrace`) through the batcher into the engine, so one Check yields
correlated spans for transport handling, batcher queue wait, batch
assembly/padding, device dispatch, device wait, and host-fallback replay
— and the same stage breakdown lands in the `check_stage_duration`
histogram, the structured request log, and the threshold-configurable
slow-query log (`log.slow_query_ms`).

Everything here degrades gracefully: metrics use a dedicated
CollectorRegistry (so embedders/tests never hit duplicate-collector
errors), and tracing is a no-op unless `tracing.enabled` is set.

The §5m export plane rides the same machinery: setting
`observability.otlp.endpoint` turns the tracer into an exporting
recorder — completed spans leave the process as OTLP/HTTP-JSON through
the bounded, never-blocking SpanExporter, transport spans anchor the
trace as parent-linked roots, engine stage spans carry flight-recorder
launch ids as span events, and the check-stage histogram attaches
trace_id exemplars served via OpenMetrics content negotiation.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import secrets
import sys
import threading
import time
from typing import Optional

import prometheus_client as prom

logger = logging.getLogger("keto_tpu")

# the canonical stage vocabulary, transport to silicon; every stage name
# used with Metrics.observe_stage / RequestTrace.add_stage comes from
# here so the docs table and the bench summary can enumerate them
CHECK_STAGES = (
    "transport",      # handler time NO other stage covers (the residual:
                      # what the spans below leave unexplained)
    "cache",          # check-cache fast-path lookup (hits only: a hit
                      # request records NO assemble/dispatch/device_wait
                      # because those stages never run)
    "decode",         # BatchCheck handler: admission + wire items ->
                      # CheckColumns (gRPC) or RelationTuples (REST)
                      # + namespace validation
    "queue",          # batcher queue wait (enqueue -> group dispatch)
    "assemble",       # state refresh + batch encoding + bucket padding
    "dispatch",       # device launch (H2D upload + async kernel dispatch)
    "device_wait",    # block-until-ready + readback + unpack
    "frontier_retry", # the queries that overflowed a narrowed frontier,
                      # launched again at the bucket's widest and read
                      # back (launches that needed it only)
    "islands",        # the AND/NOT island circuits over the leaf bits
                      # (launches that allocated an island only)
    "resolve",        # verdicts -> CheckResults + counters after the
                      # readback, less any host_fallback seconds
    "host_fallback",  # exact host replay of cause-flagged queries
    "respond",        # BatchCheck handler: results -> response message
                      # + per-item workload accounting
)

# what an engine's device queue held, second by second
# (engine/device_feed.py; keto_tpu_device_feed_seconds_total{state})
DEVICE_FEED_STATES = (
    "busy",                # at least one check launch not yet read back
    "starved_no_request",  # empty, and no request had arrived
    "starved_decode",      # empty while the handler decoded the request
    "starved_queue",       # empty while the request sat in the batcher
    "starved_assemble",    # empty while the engine encoded the batch
    "starved_dispatch",    # empty while the launch was being dispatched
)


# the edges of the launch-geometry histograms: the launch ladder
# (engine/tpu_engine.py _BUCKETS) and the two frontier rungs above it. A
# launch's frontier is one, two or four times its bucket, so a peak's
# bucket edge says how far from the frontier it sat
_LADDER_EDGES = tuple(1 << k for k in range(4, 17))

# the phases of a mirror rebuild, in order
# (keto_tpu_mirror_build_seconds{phase}; `keto.mirror.<phase>` spans)
MIRROR_BUILD_PHASES = ("build", "pack", "upload")


# the phases of a unary gRPC call at the server's edge, outside the
# handler (keto_tpu_grpc_edge_seconds{method, phase}; api/grpc_server.py)
EDGE_PHASES = (
    "queued",     # accepted -> parse starts: a free worker, then the bytes
    "parse",      # request bytes -> message (`keto.grpc.parse`)
    "serialize",  # response message -> bytes (`keto.grpc.serialize`)
)


class StageSpan:
    """One stage of the served check path, on two clocks at once: a
    `jax.profiler.TraceAnnotation` named `keto.<stage>` (the profiler's
    clock: while a trace is being taken the span lands in the host plane
    of the same .xplane.pb as the device's `XLA Ops`, which is what lets
    an idle gap of the device be put down to a host span; `launch_id`
    and any other `stats` ride it) and `seconds` of `time.perf_counter()`
    between `start` and the exit (the clock the stage histograms use).
    Beside the wall, `cpu_seconds` of `time.thread_time()` over the same
    stretch: what the span's thread spent on a core. Wall minus CPU is the
    time the thread was off the CPU: waiting for the interpreter, a lock
    or the device, or a core taken by another process. Outside a trace
    the annotation costs under a microsecond. Per RPC and per launch
    only, never per item."""

    __slots__ = ("start", "seconds", "cpu_start", "cpu_seconds", "_annotation")

    def __init__(self, name: str, launch_id: Optional[int] = None, **stats):
        self.start = self.seconds = 0.0
        self.cpu_start = self.cpu_seconds = 0.0
        # a process that never imported JAX cannot be taking a JAX
        # trace: host-only processes (CLI, check.engine=host) pay no
        # import for a span nobody could read
        jax = sys.modules.get("jax")
        if jax is None:
            self._annotation = contextlib.nullcontext()
        else:
            if launch_id is not None:
                stats["launch_id"] = launch_id
            self._annotation = jax.profiler.TraceAnnotation(
                f"keto.{name}", **stats
            )

    def __enter__(self) -> "StageSpan":
        self._annotation.__enter__()
        self.start = time.perf_counter()
        self.cpu_start = time.thread_time()
        return self

    def __exit__(self, *exc) -> None:
        self.cpu_seconds = time.thread_time() - self.cpu_start
        self.seconds = time.perf_counter() - self.start
        self._annotation.__exit__(*exc)


class CollectorWatch:
    """The interpreter's collector, timed from inside: one `gc.callbacks`
    entry per process, installed once whatever number of daemons asks
    (`install`), feeding every `Metrics` that called `watch_collector`
    (each once; held weakly). A collection holds the interpreter on the
    thread that runs it, so its wall is a pause of every serving thread.
    Each collection adds to `keto_tpu_gc_collections_total{generation}`
    and its wall to `keto_tpu_gc_pause_seconds_total{generation}`; a full
    one (generation 2) is also a `keto.gc` annotation on the profiler's
    clock, entered at `start` and left at `stop` on the collecting
    thread. Generations 0 and 1 come a few times an RPC and are not
    annotated."""

    _installed: Optional["CollectorWatch"] = None
    _lock = threading.Lock()

    def __init__(self):
        # replaced whole under _lock, never changed in place: the
        # callback iterates it while another thread may be adding
        self._refs: tuple = ()
        self._started = 0.0
        self._annotation = None

    @classmethod
    def install(cls) -> "CollectorWatch":
        import gc

        with cls._lock:
            if cls._installed is None:
                cls._installed = cls()
                gc.callbacks.append(cls._installed)
            return cls._installed

    def watch(self, metrics: "Metrics") -> None:
        import weakref

        with self._lock:
            others = tuple(
                ref for ref in self._refs if ref() not in (None, metrics)
            )
            self._refs = (*others, weakref.ref(metrics))

    def sinks(self) -> list:
        """The live registries fed."""
        return [m for m in (ref() for ref in self._refs) if m is not None]

    def __call__(self, phase: str, info: dict) -> None:
        # collections never overlap (the interpreter runs one at a time),
        # so one start stamp serves every thread
        if phase == "start":
            if info["generation"] == 2:
                jax = sys.modules.get("jax")
                if jax is not None:
                    self._annotation = jax.profiler.TraceAnnotation("keto.gc")
                    self._annotation.__enter__()
            self._started = time.perf_counter()
            return
        seconds = time.perf_counter() - self._started
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        generation = info["generation"]
        for ref in self._refs:
            metrics = ref()
            if metrics is not None:
                metrics.observe_collection(generation, seconds)


# -- W3C trace context --------------------------------------------------------


class SpanContext:
    """One W3C trace-context vertex: (trace_id, span_id). `child()` mints
    a new span id under the same trace — the propagation primitive.
    `parent_span_id` remembers the span this one was minted under (the
    caller's span id for a context ingested from `traceparent`): the
    OTLP exporter needs it so the transport ROOT span can parent-link to
    the caller's client span instead of dangling."""

    __slots__ = ("trace_id", "span_id", "sampled", "parent_span_id")

    def __init__(self, trace_id: str, span_id: str, sampled: bool = True,
                 parent_span_id: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled
        self.parent_span_id = parent_span_id

    def child(self) -> "SpanContext":
        return SpanContext(
            self.trace_id, secrets.token_hex(8), self.sampled,
            parent_span_id=self.span_id,
        )

    def to_traceparent(self) -> str:
        return (
            f"00-{self.trace_id}-{self.span_id}-"
            f"{'01' if self.sampled else '00'}"
        )


def new_trace() -> SpanContext:
    return SpanContext(secrets.token_hex(16), secrets.token_hex(8))


def parse_traceparent(value: Optional[str]) -> Optional[SpanContext]:
    """Parse a W3C `traceparent` header/metadata value; None for absent
    or malformed input (a bad header must never fail the request — the
    spec says restart the trace)."""
    if not value:
        return None
    parts = value.strip().lower().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id, flags = parts[0], parts[1], parts[2], parts[3]
    if len(version) != 2 or version == "ff":
        return None
    if len(trace_id) != 32 or len(span_id) != 16 or len(flags) != 2:
        return None
    try:
        if int(trace_id, 16) == 0 or int(span_id, 16) == 0:
            return None
        sampled = bool(int(flags, 16) & 1)
    except ValueError:
        return None
    return SpanContext(trace_id, span_id, sampled)


class RequestTrace:
    """Per-request telemetry carrier: the span context plus accumulated
    per-stage seconds. Created at the transport, handed through the
    batcher into the engine; every layer adds its stage durations.
    `deadline` (resilience.Deadline | None) rides the same handoff so
    every stage boundary can fail the request fast once the end-to-end
    budget is spent — the Zanzibar deadline-scoped-evaluation carrier.
    `launch_ids` collects the flight-recorder launch ids of every device
    batch this request rode (normally one; multi-split batches append
    several), so a slow-query line or request log joins its exact
    launch record in `GET /admin/flightrec`.
    `tier` is the ANSWERING tier (cache | closure | device | host |
    vocab), stamped by whichever layer produced the verdict — the check
    cache on a hit, the engine resolve paths beside their explain-sink
    fills, the REST unknown-namespace corner — so the request log and
    the workload observatory see the tier on EVERY check, not just
    explain=true ones."""

    __slots__ = (
        "ctx", "stages", "deadline", "launch_ids", "min_version", "tier",
        "arrived", "enqueued",
    )

    def __init__(self, ctx: Optional[SpanContext] = None, deadline=None):
        self.ctx = ctx if ctx is not None else new_trace()
        # perf_counter() stamps the device-feed account walks back along
        # (engine/device_feed.py): when the transport took the request
        # up, and when a batcher queued it (None on the direct path)
        self.arrived = time.perf_counter()
        self.enqueued: Optional[float] = None
        self.stages: dict[str, float] = {}
        self.deadline = deadline
        self.launch_ids: list[int] = []
        # answering tier, stamped by the layer that produced the verdict
        self.tier: Optional[str] = None
        # the store version this request's response snaptoken is minted
        # at, stamped by snaptoken enforcement: the store-outage
        # degradation plane's no-time-travel floor — a degraded (mirror)
        # answer below this version must 503, never serve (the token
        # would overstate the answer's freshness)
        self.min_version: Optional[int] = None

    def add_stage(self, name: str, seconds: float) -> None:
        self.stages[name] = self.stages.get(name, 0.0) + seconds


# current request telemetry for the executing handler; transports set it
# so nested layers (traced store ops, engine spans on the same thread)
# correlate without threading an argument through every signature
CURRENT_TRACE: contextvars.ContextVar[Optional[RequestTrace]] = (
    contextvars.ContextVar("keto_tpu_request_trace", default=None)
)


def set_request_trace(rt: Optional[RequestTrace]):
    return CURRENT_TRACE.set(rt)


def reset_request_trace(token) -> None:
    CURRENT_TRACE.reset(token)


def current_request_trace() -> Optional[RequestTrace]:
    return CURRENT_TRACE.get()


# -- flight recorder -----------------------------------------------------------

# process-wide monotonically increasing launch ids: unique across every
# engine/plane in the process so one id joins the slow-query log, the
# typed batch-failure error, and the ring entry unambiguously
_launch_id_lock = threading.Lock()
_launch_id_next = 0


def next_launch_id() -> int:
    """Allocate one launch id (ids advance even when recording is
    disabled — logs and errors still need a stable correlation key)."""
    global _launch_id_next
    with _launch_id_lock:
        _launch_id_next += 1
        return _launch_id_next


class FlightRecorder:
    """Bounded per-process ring of per-launch device introspection
    entries — the serving plane's black-box recorder.

    One entry per device launch (check batches; expand and reverse
    launches record too), written at the launch's EXISTING resolve-phase
    sync point from counters the kernel accumulated on device
    (engine/kernel.py STAT_*): loop iterations used vs cap, frontier
    occupancy (sum/max/live), probe hits, candidate rows gathered,
    estimated gather bytes, batch occupancy real/padded, host-replay
    causes, per-stage seconds, and the riders' trace ids. Context
    providers (registry-wired: breaker state, armed faults) stamp every
    entry with ambient device-path health.

    `dump()` is the failure path's escape hatch: the batchers call it on
    device-batch failure / watchdog abandon so the last launches' records
    land in the log before the evidence scrolls out of the ring; the
    metrics listener serves the live ring at `GET /admin/flightrec`.

    Thread-safe; recording is O(1) appends onto a deque. Entries carry
    `t_mono` (time.monotonic at resolve) — wall-clock stamps are banned
    repo-wide (ketolint clock-monotonic); readers compute ages against
    the monotonic clock they already hold."""

    DUMP_TAIL = 16  # entries logged per dump (the full ring would spam)

    def __init__(self, enabled: bool = True, capacity: int = 256,
                 metrics=None):
        import collections

        self.enabled = bool(enabled)
        self.capacity = max(int(capacity), 1)
        self.metrics = metrics
        self._ring = collections.deque(maxlen=self.capacity)
        self._mu = threading.Lock()
        # () -> dict merged into every entry; registered by the registry
        # (breaker state, armed faults). Called OUTSIDE the ring lock.
        self.context_providers: list = []

    def record(self, entry: dict) -> None:
        if not self.enabled:
            return
        for provider in self.context_providers:
            try:
                entry.update(provider())
            except Exception:  # a broken provider must never fail a launch
                logger.debug("flightrec context provider failed", exc_info=True)
        entry.setdefault("t_mono", time.monotonic())
        with self._mu:
            self._ring.append(entry)

    def entries(self) -> list[dict]:
        with self._mu:
            return list(self._ring)

    def dump(self, reason: str) -> list[dict]:
        """Auto-dump on batch failure / watchdog abandon: log the tail of
        the ring as one structured WARNING (the entries most likely to
        explain the failure) and count the dump. Returns the full ring
        for programmatic callers (smoke tools, tests). Disabled recorder:
        silent no-op — an empty-tail WARNING per batch failure is noise
        with zero evidence (batch-failed counters already count those)."""
        if not self.enabled:
            return []
        entries = self.entries()
        if self.metrics is not None:
            self.metrics.flightrec_dumps_total.labels(reason).inc()
        tail = entries[-self.DUMP_TAIL:]
        logger.warning(
            "flight recorder dump reason=%s entries=%d tail=%s",
            reason, len(entries), tail,
        )
        return entries


def summarize_launches(entries: list[dict], kind: str = "check") -> dict:
    """Per-leg aggregates of flight-recorder entries — the BENCH/SCALE
    json's launch-telemetry record (mean/p95 iterations, gather bytes
    per check, padding waste). Schema pinned by the bench golden test;
    returns {} for an empty window so legs without launches stay absent
    from the json instead of recording degenerate zeros. `kind` selects
    the launch family (the closure-on deep leg summarizes its
    single-step `closure` launches instead of BFS `check` ones)."""
    checks = [e for e in entries if e.get("kind") == kind]
    if not checks:
        return {}

    def _vals(key):
        return [float(e.get(key, 0)) for e in checks]

    def _p95(vals):
        s = sorted(vals)
        return s[min(int(0.95 * (len(s) - 1) + 0.5), len(s) - 1)]

    iters = _vals("steps")
    waste = [1.0 - float(e.get("occupancy", 1.0)) for e in checks]
    n_checks = sum(int(e.get("n", 0)) for e in checks) or 1
    return {
        "launches": len(checks),
        "iterations_mean": round(sum(iters) / len(iters), 2),
        "iterations_p95": round(_p95(iters), 2),
        "step_cap": int(max(e.get("step_cap", 0) for e in checks)),
        "frontier_peak_max": int(max(e.get("frontier_max", 0) for e in checks)),
        "live_task_steps_mean": round(
            sum(_vals("live_sum")) / len(checks), 1
        ),
        "gather_bytes_per_check": round(
            sum(_vals("gather_bytes_est")) / n_checks, 1
        ),
        "edge_rows_per_check": round(sum(_vals("edge_rows")) / n_checks, 3),
        "padding_waste_mean": round(sum(waste) / len(waste), 4),
    }


class Metrics:
    """Prometheus metrics for the serving path + the TPU engine."""

    def __init__(self):
        self.registry = prom.CollectorRegistry()
        self.requests_total = prom.Counter(
            "keto_tpu_requests_total",
            "RPC/REST requests served",
            ["transport", "method", "code"],
            registry=self.registry,
        )
        self.request_duration = prom.Histogram(
            "keto_tpu_request_duration_seconds",
            "Request latency",
            ["transport", "method"],
            registry=self.registry,
            buckets=(
                0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        self.checks_total = prom.Counter(
            "keto_tpu_checks_total",
            "Check() queries evaluated, by engine path",
            ["path"],  # device | host
            registry=self.registry,
        )
        self.host_fallback_total = prom.Counter(
            "keto_tpu_host_fallback_total",
            "Check() queries replayed on the exact host engine, by kernel "
            "cause code (engine/kernel.py CAUSE_*) — distinguishes "
            "capacity cliffs (island_overflow, frontier_overflow, "
            "rewrite_cap) from semantic causes (relation_not_found, "
            "config_missing) and staleness (dirty_row)",
            ["cause"],
            registry=self.registry,
        )
        self.check_batch_tuples_built_total = prom.Counter(
            "keto_tpu_check_batch_tuples_built_total",
            "Items of BatchChecks served as columns that were built as "
            "RelationTuples after all, because the host oracle had to "
            "answer them (host replay, or a host engine); over "
            "keto_tpu_checks_total, the share of items that still cost "
            "an object",
            registry=self.registry,
        )
        self.island_instances_total = prom.Counter(
            "keto_tpu_island_instances_total",
            "AND/NOT island instances the check launches allocated, each "
            "one circuit combined on the host after the launch; over "
            "keto_tpu_checks_total, the islands a check costs",
            registry=self.registry,
        )
        self.check_batch_size = prom.Histogram(
            "keto_tpu_check_batch_size",
            "Queries per device batch",
            registry=self.registry,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096),
        )
        self.snapshot_builds_total = prom.Counter(
            "keto_tpu_snapshot_builds_total",
            "Device graph-mirror rebuilds",
            registry=self.registry,
        )
        self.snapshot_tuples = prom.Gauge(
            "keto_tpu_snapshot_tuples",
            "Relation tuples in the current device snapshot",
            registry=self.registry,
        )
        self.snapshot_build_duration = prom.Histogram(
            "keto_tpu_snapshot_build_duration_seconds",
            "Device graph-mirror rebuild latency",
            registry=self.registry,
        )
        # watch subsystem (keto_tpu/watch): changelog streaming health
        self.watch_streams_active = prom.Gauge(
            "keto_tpu_watch_streams_active",
            "Open watch subscriptions (gRPC streams + SSE connections)",
            registry=self.registry,
        )
        self.watch_events_delivered_total = prom.Counter(
            "keto_tpu_watch_events_delivered_total",
            "Tuple changes delivered to watch subscribers (counts "
            "individual insert/delete changes, summed over subscribers)",
            registry=self.registry,
        )
        self.watch_resets_total = prom.Counter(
            "keto_tpu_watch_resets_total",
            "RESET events handed to watch subscribers (ring-buffer "
            "overflow, trimmed changelog, bulk load) — every gap is "
            "explicit, never a silent drop",
            registry=self.registry,
        )
        self.watch_lag_seconds = prom.Gauge(
            "keto_tpu_watch_lag_seconds",
            "Delay between the oldest undelivered commit's write hook "
            "and its fan-out to subscribers (watch hub tail lag)",
            registry=self.registry,
        )
        self.watch_heartbeats_total = prom.Counter(
            "keto_tpu_watch_heartbeats_total",
            "In-band HEARTBEAT frames broadcast on idle watch streams "
            "(opt-in via watch.heartbeat_s) — the liveness signal an "
            "out-of-process follower tail uses to tell a quiet upstream "
            "from a dead one; emitted through store outages too",
            registry=self.registry,
        )
        # request-scoped telemetry plane: the per-stage Check breakdown
        # (CHECK_STAGES) — one observation per stage per device batch
        # (batch-shared stages are observed once, not per rider), so a
        # p95 regression attributes to queue wait vs padding vs dispatch
        # vs device wait vs host replay instead of one flat duration
        self.check_stage_duration = prom.Histogram(
            "keto_tpu_check_stage_duration_seconds",
            "Check serving time per pipeline stage (transport | cache | "
            "decode | queue | assemble | dispatch | device_wait | "
            "frontier_retry | islands | resolve | host_fallback | respond); "
            "batch-level stages "
            "observe once per device batch; `cache` observes per cache "
            "hit (hit requests record no assemble/dispatch/device_wait "
            "time); decode/respond observe once per BatchCheck; "
            "`transport` is the residual no other stage covers",
            ["stage"],
            registry=self.registry,
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 1.0,
            ),
        )
        self.check_stage_cpu_seconds = prom.Summary(
            "keto_tpu_check_stage_cpu_seconds",
            "CPU seconds (time.thread_time of the stage's own thread) "
            "beside each wall sample of "
            "keto_tpu_check_stage_duration_seconds that a StageSpan "
            "timed: decode, assemble, dispatch, device_wait, "
            "frontier_retry, islands, resolve, respond. The stage's wall "
            "less this is time its thread spent off the CPU, waiting for "
            "the interpreter, a lock or the device",
            ["stage"],
            registry=self.registry,
        )
        self.grpc_edge_seconds = prom.Histogram(
            "keto_tpu_grpc_edge_seconds",
            "Unary gRPC calls at the server's edge, outside the handler "
            "(api/grpc_server.py): `queued` from the call's acceptance to "
            "the start of its parse (the wait for a free worker, then for "
            "the message's bytes), `parse` the request's FromString, "
            "`serialize` the response's SerializeToString. None of it is "
            "inside keto_tpu_request_duration_seconds",
            ["method", "phase"],
            registry=self.registry,
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 1.0,
            ),
        )
        self.gc_pause_seconds = prom.Counter(
            "keto_tpu_gc_pause_seconds_total",
            "Wall seconds the interpreter's collector ran, by generation "
            "(CollectorWatch: one gc.callbacks entry a process); every "
            "thread of the process is paused meanwhile",
            ["generation"],
            registry=self.registry,
        )
        self.gc_collections = prom.Counter(
            "keto_tpu_gc_collections_total",
            "Collections the interpreter's collector ran, by generation",
            ["generation"],
            registry=self.registry,
        )
        # by generation, declared from the start (CollectorWatch feeds
        # them once `watch_collector` is called)
        self._gc_children = tuple(
            (self.gc_collections.labels(str(g)), self.gc_pause_seconds.labels(str(g)))
            for g in range(3)
        )
        self.batcher_queue_depth = prom.Gauge(
            "keto_tpu_batcher_queue_depth",
            "Requests waiting in a check-batcher queue, sampled at "
            "enqueue/drain; `plane` separates the threaded batcher from "
            "the aio one (both can serve simultaneously — an unlabeled "
            "gauge would be last-writer-wins between them)",
            ["plane"],  # threaded | aio
            registry=self.registry,
        )
        self.inflight_launches = prom.Gauge(
            "keto_tpu_inflight_launches",
            "Launched-but-unresolved device batches (bounded by the "
            "batcher's in-flight semaphore)",
            registry=self.registry,
        )
        self.delta_overlay_ops = prom.Gauge(
            "keto_tpu_delta_overlay_ops",
            "Pending store ops compiled into the current delta overlay "
            "(0 after a compaction/rebuild; compaction forces at "
            "DELTA_COMPACT_THRESHOLD)",
            registry=self.registry,
        )
        self.snapshot_hbm_bytes = prom.Gauge(
            "keto_tpu_snapshot_hbm_bytes",
            "Device bytes held by the current check-table mirror "
            "(packed edge/rewrite/delta tables; expand/reverse extras "
            "not included)",
            registry=self.registry,
        )
        self.device_bytes_in_use = prom.Gauge(
            "keto_tpu_device_bytes_in_use",
            "Bytes allocated on the mirror's device(s) now, by the "
            "device's own memory_stats(): tables, launch buffers and "
            "whatever else the process holds there. Read at every scrape "
            "once a mirror is built; 0 before that, and on a backend that "
            "keeps no such statistic (the CPU)",
            registry=self.registry,
        )
        self.device_bytes_limit = prom.Gauge(
            "keto_tpu_device_bytes_limit",
            "Bytes the mirror's device(s) can allocate at all "
            "(memory_stats() bytes_limit): the room "
            "keto_tpu_device_bytes_in_use is a share of; 0 where the "
            "backend does not say",
            registry=self.registry,
        )
        self.mirror_build_seconds = prom.Gauge(
            "keto_tpu_mirror_build_seconds",
            "Seconds the last mirror rebuild spent in each phase: build "
            "(store columns to the host snapshot), pack (columns to "
            "packed table rows) and upload (host tables onto the "
            "device); they add up to that rebuild's "
            "keto_tpu_snapshot_build_duration_seconds sample",
            ["phase"],
            registry=self.registry,
        )
        self.compaction_lag_versions = prom.Gauge(
            "keto_tpu_compaction_lag_versions",
            "Store commits folded into the delta overlay since the base "
            "snapshot (covered_version - base_version): distance toward "
            "the next compaction",
            registry=self.registry,
        )
        self.refresh_lag_seconds = prom.Gauge(
            "keto_tpu_refresh_lag_seconds",
            "Push-refresher lag: seconds from the triggering commit's "
            "write hook to delta-overlay fold completion (last refresh)",
            registry=self.registry,
        )
        # snaptoken-consistent serve-side check cache (api/check_cache.py)
        self.check_cache_ops = prom.Counter(
            "keto_tpu_check_cache_ops_total",
            "Check-cache outcomes: hit (served before the batcher — no "
            "assemble/dispatch/device stages run), miss (no entry), "
            "stale (entry pinned to an older store version than the "
            "request's), invalidation (entries removed by commit-driven "
            "precise invalidation)",
            ["op"],  # hit | miss | stale | invalidation
            registry=self.registry,
        )
        self.check_cache_entries = prom.Gauge(
            "keto_tpu_check_cache_entries",
            "Entries currently held by the serve-side check cache "
            "(bounded by check.cache.max_entries, LRU-evicted)",
            registry=self.registry,
        )
        self.check_coalesced_total = prom.Counter(
            "keto_tpu_check_coalesced_total",
            "Concurrent identical pending checks collapsed onto one "
            "in-flight batch slot and fanned back out (singleflight "
            "dedupe, Zanzibar's hot-spot lock table)",
            registry=self.registry,
        )
        # overload & failure resilience plane (keto_tpu/resilience.py):
        # deadlines, admission control, device-path circuit breaker
        self.deadline_exceeded_total = prom.Counter(
            "keto_tpu_deadline_exceeded_total",
            "Checks failed with a typed DEADLINE_EXCEEDED (REST 504), by "
            "the pipeline stage that detected expiry: admission (gate "
            "before any work), queue (expired while batched — dropped "
            "without occupying a device slot), wait (the caller's "
            "remaining budget ran out waiting on the batch result)",
            ["stage"],
            registry=self.registry,
        )
        self.requests_shed_total = prom.Counter(
            "keto_tpu_requests_shed_total",
            "Check admissions rejected with a typed OverloadedError "
            "(429 / RESOURCE_EXHAUSTED, Retry-After attached) before any "
            "work was done, by reason: queue_full (admitted-but-"
            "unresolved checks at serve.check.max_queue), draining (the "
            "daemon's shutdown grace window)",
            ["reason"],
            registry=self.registry,
        )
        self.batcher_queue_limit = prom.Gauge(
            "keto_tpu_batcher_queue_limit",
            "Configured admission bound on admitted-but-unresolved "
            "checks per batching plane (serve.check.max_queue; 0 = "
            "unbounded). Compare with keto_tpu_batcher_queue_depth for "
            "rejection headroom",
            ["plane"],  # threaded | aio
            registry=self.registry,
        )
        self.breaker_state = prom.Gauge(
            "keto_tpu_breaker_state",
            "Device-path circuit breaker state: 0 closed (device "
            "serving), 1 open (every check degraded to the exact host "
            "oracle — correct answers, degraded latency), 2 half-open "
            "(one probe batch deciding recovery)",
            registry=self.registry,
        )
        self.breaker_transitions_total = prom.Counter(
            "keto_tpu_breaker_transitions_total",
            "Circuit-breaker state transitions, labeled by the state "
            "entered (closed | open | half_open) — the closed -> open -> "
            "half-open -> closed recovery cycle is countable from scrapes "
            "alone",
            ["to"],
            registry=self.registry,
        )
        # store-outage degradation plane (storage/health.py): the
        # store-path twin of the device breaker above — when SQL dies,
        # reads degrade onto the HBM mirror at its covered version,
        # writes shed typed 503s, and the whole episode is observable
        self.store_breaker_state = prom.Gauge(
            "keto_tpu_store_breaker_state",
            "Store-path circuit breaker state: 0 closed (store serving), "
            "1 open (reads the mirror covers served degraded at its "
            "covered version, everything else typed 503), 2 half-open "
            "(one probe read deciding recovery)",
            registry=self.registry,
        )
        self.store_breaker_transitions_total = prom.Counter(
            "keto_tpu_store_breaker_transitions_total",
            "Store-path breaker transitions, labeled by the state "
            "entered (closed | open | half_open) — the outage -> "
            "degraded-serve -> probe -> recovery cycle is countable "
            "from scrapes alone",
            ["to"],
            registry=self.registry,
        )
        self.store_op_timeouts_total = prom.Counter(
            "keto_tpu_store_op_timeouts_total",
            "Store ops that exceeded their per-op budget "
            "(store.op_timeout_ms / store.bulk_timeout_ms on the "
            "bounded executor) and answered the caller with a typed "
            "StoreTimeoutError instead of pinning its thread, by op",
            ["op"],
            registry=self.registry,
        )
        self.store_op_failures_total = prom.Counter(
            "keto_tpu_store_op_failures_total",
            "Store ops that failed outright (driver/disk/injected "
            "error; timeouts are counted separately) — consecutive "
            "failures trip the store breaker, by op",
            ["op"],
            registry=self.registry,
        )
        self.store_unavailable_total = prom.Counter(
            "keto_tpu_store_unavailable_total",
            "Store ops rejected fail-fast with a typed 503 because the "
            "store breaker was open (no store contact was made), by op",
            ["op"],
            registry=self.registry,
        )
        self.store_degraded_serves_total = prom.Counter(
            "keto_tpu_store_degraded_serves_total",
            "Requests answered in DEGRADED mode during a store outage, "
            "by surface: snaptoken (enforcement fell back to the "
            "mirror's covered version), check/filter/expand/list (the "
            "engine served from the device mirror + delta overlay at "
            "its covered version — the response snaptoken IS the "
            "staleness bound), watch (in-band DEGRADED markers pushed "
            "to subscribers instead of a silent stall)",
            ["surface"],
            registry=self.registry,
        )
        self.mirror_staleness_age_seconds = prom.Gauge(
            "keto_tpu_mirror_staleness_age_seconds",
            "Seconds since the default network's device mirror last "
            "confirmed it covered the store's current version (0 while "
            "healthy; grows during a store outage — the "
            "serve.check.degraded.max_staleness_s ceiling converts a "
            "silently-ancient mirror into typed 503s)",
            registry=self.registry,
        )
        self.check_batch_failed_total = prom.Counter(
            "keto_tpu_check_batch_failed_total",
            "Engine batch evaluations that failed, by cause: device "
            "(submit/resolve raised; riders re-answered by the host "
            "oracle), device_timeout (launch watchdog abandoned a batch "
            "past serve.check.device_timeout_ms; riders re-answered by "
            "the host oracle), engine (a non-split-phase engine raised; "
            "riders fail with a typed KetoError), host (the host-oracle "
            "fallback itself raised), keto (a typed KetoError propagated "
            "as-is), store (a store outage reached the submit path — "
            "counted here, owned by the STORE breaker, never recorded "
            "as device-health evidence)",
            ["cause"],
            registry=self.registry,
        )
        # engine flight recorder (this module's FlightRecorder + the
        # kernel launch counters, engine/kernel.py STAT_*): the device
        # side of every launch measured instead of projected
        self.launch_iterations = prom.Histogram(
            "keto_tpu_launch_iterations",
            "BFS loop iterations actually executed per device check "
            "launch (the counted-loop budget is keto_tpu_launch_step_cap; "
            "iterations == cap with live tasks means step-exhausted host "
            "replays)",
            registry=self.registry,
            buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48),
        )
        self.launch_flag_steps = prom.Counter(
            "keto_tpu_launch_flag_steps_total",
            "BFS steps of the device check launches that scattered a "
            "host-replay cause into their per-query codes (engine/"
            "kernel.py STAT_FLAG_STEPS, raise_causes): over the sum of "
            "keto_tpu_launch_iterations, how often a step's cause gate "
            "opened",
            registry=self.registry,
        )
        self.launch_step_cap = prom.Gauge(
            "keto_tpu_launch_step_cap",
            "Static step budget (max_steps) of the most recent device "
            "check launch — the denominator for iterations-vs-cap",
            registry=self.registry,
        )
        self.launch_frontier_peak = prom.Histogram(
            "keto_tpu_launch_frontier_peak",
            "Peak per-step frontier task count within one device check "
            "launch (capacity is the launch frontier_cap; peaks at cap "
            "mean frontier-overflow host replays are near)",
            registry=self.registry,
            buckets=_LADDER_EDGES,
        )
        self.launch_demand_peak = prom.Histogram(
            "keto_tpu_launch_demand_peak",
            "Largest candidate total of one BFS step, before the frontier "
            "cut, per single-device check launch (engine/kernel.py "
            "STAT_DEMAND_MAX): the frontier the launch needed. Above the "
            "launch's frontier means its frontier overflowed",
            registry=self.registry,
            buckets=_LADDER_EDGES,
        )
        self.launch_frontier_slots_total = prom.Counter(
            "keto_tpu_launch_frontier_slots_total",
            "Frontier slots (F) of the check launches, "
            "summed; over keto_tpu_launch_bucket_slots_total, the "
            "frontier slots a launch paid for each query slot",
            registry=self.registry,
        )
        self.launch_bucket_slots_total = prom.Counter(
            "keto_tpu_launch_bucket_slots_total",
            "Query slots (the bucket B) of the check launches, summed",
            registry=self.registry,
        )
        self.mesh_devices = prom.Gauge(
            "keto_tpu_mesh_devices",
            "Devices the check engine's tables are sharded over "
            "(check.mesh_devices; 1 where it serves from one device): "
            "each BFS step of a launch merges that many shards' probe "
            "hits, host-replay causes and candidate frontiers over ICI",
            registry=self.registry,
        )
        self.mesh_launches_total = prom.Counter(
            "keto_tpu_mesh_launches_total",
            "Check launches of the sharded program (parallel/kernel.py): "
            "one program over every device of the mesh",
            registry=self.registry,
        )
        self.mesh_place_seconds = prom.Histogram(
            "keto_tpu_mesh_place_seconds",
            "Host seconds a sharded check launch spends placing its query "
            "arrays on every device of the mesh (span keto.mesh.place, "
            "inside stage dispatch and counted there)",
            registry=self.registry,
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1,
            ),
        )
        self.frontier_retry_total = prom.Counter(
            "keto_tpu_frontier_retry_total",
            "Queries launched again on the device at their bucket's widest "
            "frontier after they overflowed the narrower one their launch "
            "ran at (what overflows that too is replayed on the host)",
            registry=self.registry,
        )
        self.launch_gather_bytes = prom.Histogram(
            "keto_tpu_launch_gather_bytes",
            "Estimated bytes moved by the kernel's gather sites per "
            "device check launch (engine/kernel.py "
            "estimate_step_gather_bytes x iterations used) — the "
            "measured stand-in for the gather-volume droop hypothesis",
            registry=self.registry,
            buckets=(
                1e5, 1e6, 4e6, 1.6e7, 6.4e7, 2.56e8, 1e9, 4e9,
            ),
        )
        self.launch_edge_rows = prom.Histogram(
            "keto_tpu_launch_edge_rows",
            "Candidate rows materially gathered per device check launch "
            "(valid expansion children across all steps) — the dynamic "
            "half of gather volume, scales with graph fanout",
            registry=self.registry,
            buckets=(1, 10, 100, 1000, 10000, 100000, 1000000),
        )
        self.launch_padding_waste = prom.Histogram(
            "keto_tpu_launch_padding_waste",
            "Padded fraction of the launch bucket ((B - real) / B): 0 = "
            "full bucket, 0.9 = 90% of the launch cost spent on padding "
            "rows",
            registry=self.registry,
            buckets=(0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99),
        )
        self.device_feed_seconds = prom.Counter(
            "keto_tpu_device_feed_seconds_total",
            "Wall seconds since an engine's first check launch, by what "
            "its device queue held (engine/device_feed.py): `busy` = at "
            "least one check launch dispatched and not read back; "
            "`starved_*` = nothing queued, charged to what the NEXT "
            "launch was doing meanwhile, walking back along its own "
            "timeline (dispatch, assemble, batcher queue, handler "
            "decode) and `starved_no_request` before its request "
            "arrived. The six states sum to wall time. A resolver that "
            "wakes late under the interpreter lock sees its readback "
            "late, so starved time is a LOWER bound",
            ["state"],
            registry=self.registry,
        )
        # every state declared from the start: a scrape before the
        # first launch (and a reader that names the series) sees zeros,
        # not a missing family
        self.device_feed_state = {
            state: self.device_feed_seconds.labels(state)
            for state in DEVICE_FEED_STATES
        }
        self.launch_device_seconds = prom.Histogram(
            "keto_tpu_launch_device_seconds",
            "Estimated device service time of one check launch: readback "
            "done minus the later of its dispatch and the latest "
            "readback seen before it (launches run in order on one "
            "device queue, so this leaves out the wait behind earlier "
            "launches that device_wait includes). Over EVERY launch, "
            "not the few a profiler trace holds. A late-waking resolver "
            "makes it an UPPER bound",
            registry=self.registry,
            buckets=(
                0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5,
            ),
        )
        self.flightrec_dumps_total = prom.Counter(
            "keto_tpu_flightrec_dumps_total",
            "Flight-recorder auto-dumps, by reason (device | "
            "device_timeout | host | manual): each dump writes the ring "
            "tail to the log before the failure evidence scrolls out",
            ["reason"],
            registry=self.registry,
        )
        self.hbm_table_bytes = prom.Gauge(
            "keto_tpu_hbm_table_bytes",
            "Device bytes held per buffer family of the default "
            "network's mirror (check = packed check tables incl. the "
            "delta overlay, expand / reverse / subjects = the lazy "
            "read-path extras) — refreshed by TPUCheckEngine."
            "hbm_snapshot(), which GET /admin/flightrec calls",
            ["buffer"],
            registry=self.registry,
        )
        self.client_retries_total = prom.Counter(
            "keto_tpu_client_retries_total",
            "In-process ReadClient retries (resilience.RetryPolicy: "
            "exponential backoff + full jitter, UNAVAILABLE/"
            "RESOURCE_EXHAUSTED only, idempotent reads only, deadline-"
            "budget-aware) — fed when a RetryPolicy is constructed with "
            "this counter (embedders, bench, load tools)",
            registry=self.registry,
        )
        # multi-replica serving plane (api/replica.py): N serve workers
        # over one device engine, snaptoken-routed consistency, and
        # deadline-budget-aware request hedging (Zanzibar §2.4.1/§4)
        self.worker_checks_total = prom.Counter(
            "keto_tpu_worker_checks_total",
            "Check() requests answered per replica serve worker (replica "
            "mode only, serve.check.workers >= 2) — the per-worker QPS "
            "breakdown the bench records; routed requests count on the "
            "ANSWERING worker",
            ["worker"],
            registry=self.registry,
        )
        self.replica_applied_version = prom.Gauge(
            "keto_tpu_replica_applied_version",
            "Store version a replica serve worker has applied from its "
            "Watch-changelog tail (default network; compare across "
            "workers for replica lag — snaptoken routing holds/routes "
            "requests demanding newer versions)",
            ["worker"],
            registry=self.registry,
        )
        self.replica_routed_total = prom.Counter(
            "keto_tpu_replica_routed_total",
            "Checks whose snaptoken demanded a version newer than the "
            "receiving worker's applied version, by resolution: "
            "caught_up (the worker's tail applied it within the "
            "catch-up hold), routed (proxied to a fresh worker), "
            "escalated (no worker fresh — served at the live store "
            "version; still never stale)",
            ["outcome"],
            registry=self.registry,
        )
        self.hedge_launched_total = prom.Counter(
            "keto_tpu_hedge_launched_total",
            "Hedge duplicates launched (a check unanswered within the "
            "hedge policy's latency quantile fired one duplicate onto "
            "another worker's batcher; deadline-budget-aware — a budget "
            "too thin to fit a hedge never fires one)",
            registry=self.registry,
        )
        self.hedge_wins_total = prom.Counter(
            "keto_tpu_hedge_wins_total",
            "Hedged checks resolved, by which ride answered first "
            "(primary | hedge) — first answer wins, the loser is "
            "cancelled",
            ["ride"],
            registry=self.registry,
        )
        self.hedge_cancelled_total = prom.Counter(
            "keto_tpu_hedge_cancelled_total",
            "Losing hedge rides cancelled before their batch launched "
            "(a cancelled pending never occupies a device batch slot)",
            registry=self.registry,
        )
        # multi-daemon HA plane (api/follower.py, api/router.py,
        # tools/ha_smoke.py): Watch-fed follower mirrors + snaptoken-safe
        # cross-process failover (Zanzibar §2.4 multi-cluster serving)
        self.ha_applied_version = prom.Gauge(
            "keto_tpu_ha_applied_version",
            "Leader store version this follower daemon has applied from "
            "its network Watch-changelog tail, per network id — the "
            "version its snaptoken gate enforces; compare against the "
            "leader's keto_tpu_store_version-equivalent for fleet lag",
            ["nid"],
            registry=self.registry,
        )
        self.ha_version_lag = prom.Gauge(
            "keto_tpu_ha_version_lag",
            "Versions between the leader tail this follower has OBSERVED "
            "(latest watch frame) and what it has APPLIED, per network "
            "id — sustained nonzero means the apply path is behind, not "
            "the network",
            ["nid"],
            registry=self.registry,
        )
        self.ha_tail_state = prom.Gauge(
            "keto_tpu_ha_tail_state",
            "Follower changelog-tail state (0 disconnected, 1 "
            "bootstrapping, 2 tailing) — the rotation signal the front "
            "router's health probes reflect",
            ["nid"],
            registry=self.registry,
        )
        self.ha_bootstrap_reads_total = prom.Counter(
            "keto_tpu_ha_bootstrap_reads_total",
            "Full leader store sweeps the follower performed (cold start "
            "with no usable checkpoint, or a watch RESET gap). The HA "
            "smoke pins this at its floor to prove steady state is "
            "changelog-fed — zero full reads after cold start",
            registry=self.registry,
        )
        self.ha_stream_reconnects_total = prom.Counter(
            "keto_tpu_ha_stream_reconnects_total",
            "Follower watch-stream reconnects, by cause: silent (no "
            "frame within follower.liveness_s — the severed-connection "
            "detector), error (transport error / stream end), reset "
            "(server RESET forced a re-bootstrap), stale (snaptoken "
            "ahead of the leader — leader lost state, resync)",
            ["cause"],
            registry=self.registry,
        )
        self.ha_failovers_total = prom.Counter(
            "keto_tpu_ha_failovers_total",
            "Requests the HA front router re-routed away from a failed "
            "or lagging daemon mid-call (the kill -9 smoke's failover "
            "counter; latency to the winning answer is the failover "
            "latency the smoke bounds)",
            registry=self.registry,
        )
        self.ha_rotation_state = prom.Gauge(
            "keto_tpu_ha_rotation_state",
            "Router rotation membership per backend daemon (1 in "
            "rotation, 0 drained — breaker open or probes failing); "
            "drained daemons keep being probed and rejoin on recovery",
            ["target"],
            registry=self.registry,
        )
        # crash-recovery plane (engine/scrub.py, engine/checkpoint.py,
        # tools/crash_smoke.py): cold-start recovery + anti-entropy
        self.checkpoint_load_fallbacks_total = prom.Counter(
            "keto_tpu_checkpoint_load_fallbacks_total",
            "Warm-restart mirror checkpoints that existed but could not "
            "be used, by reason: corrupt (torn/truncated/incompatible "
            "file — crash mid-write or format drift) or stale (valid "
            "file for another (store version, config) pair). Either way "
            "the engine rebuilt from the store — the fallback is the "
            "contract, this counts how often it fires",
            ["reason"],
            registry=self.registry,
        )
        self.checkpoint_write_failures_total = prom.Counter(
            "keto_tpu_checkpoint_write_failures_total",
            "Mirror checkpoint writes that failed (full disk, revoked "
            "mount) — deferred-flush OSErrors and shutdown-flush "
            "failures both count; serving and drain continue either "
            "way (the store is the durability, the checkpoint is a "
            "warm-restart optimization)",
            registry=self.registry,
        )
        self.scrub_passes_total = prom.Counter(
            "keto_tpu_scrub_passes_total",
            "Completed anti-entropy scrub passes (every engine's device "
            "mirror fully checksummed against the host truth once per "
            "pass; incremental slices — scrub.slice_rows — spread the "
            "work across the interval)",
            registry=self.registry,
        )
        self.scrub_slices_total = prom.Counter(
            "keto_tpu_scrub_slices_total",
            "Device-mirror table slices checksummed by the anti-entropy "
            "scrubber (engine/scrub.py)",
            registry=self.registry,
        )
        self.scrub_divergence_total = prom.Counter(
            "keto_tpu_scrub_divergence_total",
            "Device-mirror slices whose checksum DIVERGED from the host "
            "recomputation at the mirror's covered version, by device "
            "table — a silent HBM/table corruption caught by the "
            "scrubber; each divergence dumps the flight-recorder tail "
            "and triggers the breaker-degrade auto-repair",
            ["table"],
            registry=self.registry,
        )
        self.scrub_repairs_total = prom.Counter(
            "keto_tpu_scrub_repairs_total",
            "Automatic mirror repairs triggered by scrub divergence: "
            "the breaker opens (checks host-oracle-serve, staying "
            "correct), the poisoned state is dropped, and the next "
            "check rebuilds the mirror from the store",
            registry=self.registry,
        )
        # Leopard closure index (engine/closure.py): deep checks answered
        # by the precomputed transitive-closure sets in one probe step
        self.closure_hits_total = prom.Counter(
            "keto_tpu_closure_hits_total",
            "Check() queries answered by the Leopard closure index "
            "(covered node, clean overlay, index synced through the "
            "serving state's version) — positives AND definitive "
            "negatives both count; every hit skipped the per-level BFS "
            "entirely",
            registry=self.registry,
        )
        self.closure_fallback_total = prom.Counter(
            "keto_tpu_closure_fallback_total",
            "Check() queries the closure index declined, by cause: "
            "kernel-side `uncovered` (poisoned/oversized/unindexed "
            "node), `dirty` (write-perturbed since the last powering), "
            "`unindexed` (query vocabulary never encoded) and host-side "
            "`unbuilt`/`stale_snapshot`/`lag` (index not ready for the "
            "serving state — the batch never launched a closure probe). "
            "Fallbacks ride the BFS kernel: correct, depth-priced",
            ["cause"],
            registry=self.registry,
        )
        self.closure_lag_versions = prom.Gauge(
            "keto_tpu_closure_lag_versions",
            "Store versions the closure index's dirty overlay trails the "
            "serving state by (0 = synced; answers are version-gated, so "
            "lag costs latency, never correctness)",
            registry=self.registry,
        )
        self.closure_builds_total = prom.Counter(
            "keto_tpu_closure_builds_total",
            "Closure index powerings (initial build + re-powerings after "
            "dirty-overlay overflow / changelog resets / snapshot "
            "rebuilds)",
            registry=self.registry,
        )
        self.closure_entries = prom.Gauge(
            "keto_tpu_closure_entries",
            "Materialized (node, subject) closure entries in the current "
            "index build (the R·D product's row count on device)",
            registry=self.registry,
        )
        # on-device GraphBLAS powering (engine/closure_power.py): the
        # closure built AS bit-packed boolean matmul on the accelerator
        # when closure.powering = "device" (host stays the fallback)
        self.closure_power_builds_total = prom.Counter(
            "keto_tpu_closure_power_builds_total",
            "Closure powerings completed BY the device GraphBLAS kernel "
            "(closure.powering = device; host-fallback powerings count "
            "under keto_tpu_closure_builds_total only)",
            registry=self.registry,
        )
        self.closure_power_steps_total = prom.Counter(
            "keto_tpu_closure_power_steps_total",
            "frontier×adjacency powering steps executed on device across "
            "all waves (each step is one bit-packed boolean matmul level "
            "under the shared bounded loop)",
            registry=self.registry,
        )
        self.closure_power_bytes = prom.Gauge(
            "keto_tpu_closure_power_bytes",
            "Device working-set bytes of the most recent device powering "
            "(packed adjacency operands + seen/frontier bit matrices + "
            "unpacked step scratch; transient — freed after the build)",
            registry=self.registry,
        )
        # bulk ACL filtering (engine/filter_kernel.py): one subject,
        # thousands of candidate objects, one device ride
        self.filter_requests_total = prom.Counter(
            "keto_tpu_filter_requests_total",
            "BatchFilter evaluations (engine.filter_batch calls — one "
            "per API request regardless of how many chunks the "
            "candidate list split into)",
            registry=self.registry,
        )
        self.filter_request_objects = prom.Histogram(
            "keto_tpu_filter_request_objects",
            "Candidate-list size per BatchFilter request (the workload's "
            "defining dimension: per-object cost amortizes over it)",
            buckets=(16, 64, 256, 1024, 4096, 10000, 16384, 65536),
            registry=self.registry,
        )
        self.filter_objects_total = prom.Counter(
            "keto_tpu_filter_objects_total",
            "Candidate objects answered, by resolution path: `closure` "
            "(one batched Leopard membership gather — no BFS at all), "
            "`frontier` (the shared-frontier reverse walk intersected "
            "the whole leftover column in one launch), `vocab` (name "
            "unknown to graph+config under a monotone-only config — "
            "definitively invisible, zero work), `host` (cause-coded "
            "exact oracle replay: AND/NOT islands, dirty rows, "
            "overflow, unknown vocabulary under non-monotone configs)",
            ["path"],
            registry=self.registry,
        )
        self.filter_shed_total = prom.Counter(
            "keto_tpu_filter_shed_total",
            "Filter requests rejected before any device work, by "
            "reason: `max_objects` (candidate list over "
            "filter.max_objects — typed 400 so oversized requests "
            "cannot buy unbounded device work)",
            ["reason"],
            registry=self.registry,
        )
        # decision explain plane + OTLP span export (this module's
        # SpanExporter + engine/explain.py): the observability plane's
        # own health counters
        self.explain_requests_total = prom.Counter(
            "keto_tpu_explain_requests_total",
            "Check requests served with explain=true (the DecisionTrace "
            "slow path: cache bypassed, host witness re-walk beside the "
            "authoritative device verdict) — admission-bounded by the "
            "explain.max_per_s token bucket, so this counts served "
            "explains, not shed ones (those land in "
            "keto_tpu_requests_shed_total{explain_rate})",
            registry=self.registry,
        )
        self.otlp_exported_total = prom.Counter(
            "keto_tpu_otlp_exported_total",
            "Spans successfully POSTed to observability.otlp.endpoint "
            "as OTLP/HTTP-JSON by the background SpanExporter",
            registry=self.registry,
        )
        self.otlp_dropped_total = prom.Counter(
            "keto_tpu_otlp_dropped_total",
            "Spans dropped by the OTLP exporter instead of blocking a "
            "request thread, by reason: queue_full (the bounded export "
            "queue was at capacity at enqueue) or post_error (the "
            "collector POST failed/timed out and the batch was "
            "abandoned) — export is observability; dropping beats "
            "back-pressure",
            ["reason"],
            registry=self.registry,
        )
        # workload observatory + SLO plane (observability_workload.py,
        # §5o): per-namespace accounting, hot-key sketch shares, and
        # multi-window burn rates against the BASELINE.json objectives
        self.workload_requests_total = prom.Counter(
            "keto_tpu_workload_requests_total",
            "Answered checks by (namespace, relation, answering tier, "
            "verdict) — the per-workload accounting plane "
            "(observability_workload.py): tier is cache | closure | "
            "device | host | vocab | other (the §5m explain tiers, now "
            "stamped on every check), verdict is allowed | denied. "
            "Label cardinality is bounded by the configured vocabulary "
            "(namespaces x relations), never by request content",
            ["namespace", "relation", "tier", "verdict"],
            registry=self.registry,
        )
        self.workload_fold_seconds_total = prom.Counter(
            "keto_tpu_workload_fold_seconds_total",
            "CPU seconds (the folding thread's own, without its waits "
            "for the interpreter) the workload observatory spent folding "
            "pending events into its accounting, sketches and SLO "
            "buckets, by whose thread paid: folder (the daemon's keto-workload-fold "
            "thread) or inline (a handler past the pending-checks "
            "valve, an admin read surface, or an embedder with no "
            "folder). One interpreter runs both, so the sum is what "
            "the plane costs the serve path; inline seconds are also "
            "inside some request's latency",
            ["where"],
            registry=self.registry,
        )
        self.workload_folded_checks_total = prom.Counter(
            "keto_tpu_workload_folded_checks_total",
            "Answered checks the workload observatory folded (a "
            "BatchCheck counts its answered items), by the same "
            "where as keto_tpu_workload_fold_seconds_total; summed "
            "over both it equals keto_tpu_workload_requests_total "
            "once the pending buffer is drained",
            ["where"],
            registry=self.registry,
        )
        self.workload_tier_duration = prom.Histogram(
            "keto_tpu_workload_tier_duration_seconds",
            "Served request duration by ANSWERING tier (cache | "
            "closure | device | host | vocab | other) — the workload "
            "observatory's per-tier latency attribution: which tier "
            "burns the latency budget, per scrape. OpenMetrics "
            "exposition carries a trace_id exemplar per bucket, same "
            "as the stage histogram",
            ["tier"],
            registry=self.registry,
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 1.0,
            ),
        )
        self.hotkey_share = prom.Gauge(
            "keto_tpu_hotkey_share",
            "Fraction of the sliding hot-key window's traffic answered "
            "by the top-k keys of the Space-Saving sketch "
            "(observability_workload.py): kind is object | subject, k "
            "is 1 | 10 | 100 — the Zanzibar §4 hot-spot instrument as "
            "a scrapeable gauge (join it with "
            "keto_tpu_check_cache_ops_total for cache-hit "
            "attribution); refreshed at most once per second from the "
            "serve path, full detail at GET /admin/hotkeys",
            ["kind", "k"],
            registry=self.registry,
        )
        self.slo_objective_target = prom.Gauge(
            "keto_tpu_slo_objective_target",
            "The configured target per SLO objective "
            "(slo.objectives.*): served_p95_ms in milliseconds, "
            "availability as a fraction, max_staleness_s in seconds — "
            "exported so dashboards and the perf gate judge by the "
            "same number the live burn tracker uses",
            ["objective"],
            registry=self.registry,
        )
        self.slo_burn_rate = prom.Gauge(
            "keto_tpu_slo_burn_rate",
            "Error-budget burn rate per objective and window (short | "
            "long, slo.window_short_s / slo.window_long_s): (bad "
            "fraction over the window) / budget — 1.0 spends the "
            "budget exactly on schedule, above slo.fast_burn_threshold "
            "on BOTH windows is a fast burn (multi-window rule: the "
            "short window catches the spike, the long window keeps one "
            "blip from paging)",
            ["objective", "window"],
            registry=self.registry,
        )
        self.slo_fast_burn_active = prom.Gauge(
            "keto_tpu_slo_fast_burn_active",
            "1 while the objective is in fast burn (burn rate over "
            "slo.fast_burn_threshold on both windows), else 0; every "
            "evaluation tick spent fast-burning also emits a WARNING "
            "log line — never sampled away",
            ["objective"],
            registry=self.registry,
        )
        self.slo_fast_burn_total = prom.Counter(
            "keto_tpu_slo_fast_burn_total",
            "Fast-burn EPISODES per objective (transitions into the "
            "fast-burn state, not ticks spent in it) — the incident "
            "counter an alert acknowledges",
            ["objective"],
            registry=self.registry,
        )
        # hot-path cache: (transport, method) -> (duration child,
        # {code: counter child})
        self._observe_cache: dict = {}
        # stage -> histogram child (stage names are the CHECK_STAGES
        # constants, so this cache is bounded by construction)
        self._stage_cache: dict = {}
        self._stage_cpu_cache: dict = {}
        # tier -> histogram child (tier names are the TIERS constants
        # of observability_workload.py — bounded by construction)
        self._tier_cache: dict = {}

    OPENMETRICS_CONTENT_TYPE = (
        "application/openmetrics-text; version=1.0.0; charset=utf-8"
    )

    def export(self) -> bytes:
        return prom.generate_latest(self.registry)

    def export_openmetrics(self) -> bytes:
        """OpenMetrics exposition — the format that carries EXEMPLARS
        (the trace_id attached to check-stage histogram buckets, linking
        the metrics plane to the trace plane); served by the metrics
        listener when the scraper's Accept header asks for it."""
        from prometheus_client.openmetrics import exposition as om

        return om.generate_latest(self.registry)

    def observe_mirror_build(self, phases: dict) -> None:
        """One mirror rebuild, from the seconds of its phases (build,
        pack, upload: the engine's `mirror.<phase>` StageSpans): each
        phase's gauge, and their sum as the rebuild's duration sample, so
        that the two are read off the same clock reads."""
        for phase, seconds in phases.items():
            self.mirror_build_seconds.labels(phase).set(seconds)
        self.snapshot_build_duration.observe(sum(phases.values()))

    def watch_device_memory(self, devices) -> None:
        """Read the two device-memory gauges from `devices`' own
        memory_stats(), summed, at every scrape from now on. A backend
        that keeps no such statistic (the CPU's memory_stats() is None)
        or lacks a key reads as 0: nothing is made up for it."""

        def total(key: str) -> float:
            return float(
                sum((d.memory_stats() or {}).get(key, 0) for d in devices)
            )

        self.device_bytes_in_use.set_function(lambda: total("bytes_in_use"))
        self.device_bytes_limit.set_function(lambda: total("bytes_limit"))

    def observe_launch(
        self,
        steps: int,
        step_cap: int,
        frontier_max: int,
        gather_bytes: float,
        edge_rows: int,
        padding_waste: float,
        flag_steps: int,
    ) -> None:
        """One check launch's counter samples (called once per device
        batch at its resolve sync point)."""
        self.launch_iterations.observe(steps)
        self.launch_flag_steps.inc(flag_steps)
        self.launch_step_cap.set(step_cap)
        self.launch_frontier_peak.observe(frontier_max)
        self.launch_gather_bytes.observe(gather_bytes)
        self.launch_edge_rows.observe(edge_rows)
        self.launch_padding_waste.observe(padding_waste)

    def observe_frontier(
        self, bucket: int, frontier: int, demand: int | None
    ) -> None:
        """One check launch's geometry: its query slots, its frontier
        slots, and the frontier its steps asked for (None from the
        sharded program, which reports no demand)."""
        self.launch_bucket_slots_total.inc(bucket)
        self.launch_frontier_slots_total.inc(frontier)
        if demand is not None:
            self.launch_demand_peak.observe(demand)

    def observe_stage(
        self, stage: str, seconds: float, trace_id: Optional[str] = None
    ) -> None:
        """One per-stage sample (cached label child; see observe_request
        for why `.labels()` is avoided on the serve hot path).

        `trace_id` attaches an OpenMetrics EXEMPLAR to the bucket this
        observation lands in: a scrape of the stage histogram then
        carries a concrete trace id per bucket — the metrics->trace join
        Grafana/Tempo navigate on. Costs one small dict per exemplared
        observation; callers pass it only when a request context exists."""
        child = self._stage_cache.get(stage)
        if child is None:
            child = self._stage_cache[stage] = (
                self.check_stage_duration.labels(stage)
            )
        if trace_id:
            child.observe(seconds, exemplar={"trace_id": trace_id})
        else:
            child.observe(seconds)

    def observe_stage_cpu(self, stage: str, seconds: float) -> None:
        """The CPU seconds beside one observe_stage sample."""
        child = self._stage_cpu_cache.get(stage)
        if child is None:
            child = self._stage_cpu_cache[stage] = (
                self.check_stage_cpu_seconds.labels(stage)
            )
        child.observe(seconds)

    @contextlib.contextmanager
    def stage(self, name: str, rt: Optional[RequestTrace] = None):
        """Time one handler stage as a StageSpan and, when the body ends
        without raising, feed the SAME elapsed seconds to the stage
        histogram and to the request's breakdown, and its CPU seconds to
        the stage's CPU summary. A body that raises (a shed or malformed
        request) never rode the pipeline and records nothing."""
        with StageSpan(name) as span:
            yield span
        self.observe_stage(
            name, span.seconds,
            trace_id=rt.ctx.trace_id if rt is not None else None,
        )
        self.observe_stage_cpu(name, span.cpu_seconds)
        if rt is not None:
            rt.add_stage(name, span.seconds)

    def grpc_edge(self, method: str) -> tuple:
        """The histogram children of one gRPC method's EDGE_PHASES, in
        that order (resolved once a route, when a server is built)."""
        return tuple(
            self.grpc_edge_seconds.labels(method, phase) for phase in EDGE_PHASES
        )

    def watch_collector(self) -> None:
        """Count the process's collections here from now on (idempotent:
        one callback a process, one feed a Metrics)."""
        CollectorWatch.install().watch(self)

    def observe_collection(self, generation: int, seconds: float) -> None:
        collections, pause = self._gc_children[generation]
        collections.inc()
        pause.inc(seconds)

    def observe_tier(
        self, tier: str, seconds: float, trace_id: Optional[str] = None
    ) -> None:
        """One served request's duration attributed to its ANSWERING
        tier (cached label child, exemplared like observe_stage — the
        workload observatory's per-tier latency feed)."""
        child = self._tier_cache.get(tier)
        if child is None:
            child = self._tier_cache[tier] = (
                self.workload_tier_duration.labels(tier)
            )
        if trace_id:
            child.observe(seconds, exemplar={"trace_id": trace_id})
        else:
            child.observe(seconds)

    def observe_request(self, transport: str, method: str):
        """Times a request and counts its outcome code.

        Label-child resolution (`.labels(...)`) walks locked dicts in
        prometheus_client; on the serve hot path (thousands of calls/sec
        on a 1-core host) that shows up, so children are cached per
        (transport, method[, code]). Label sets stay route-constant by
        construction — the cache cannot grow unboundedly."""
        key = (transport, method)
        cached = self._observe_cache.get(key)
        if cached is None:
            cached = (
                self.request_duration.labels(transport, method),
                {"OK": self.requests_total.labels(transport, method, "OK")},
            )
            self._observe_cache[key] = cached
        return _RequestObservation(self, key, cached)


class _RequestObservation:
    """Plain-class context manager for observe_request (a generator CM
    costs ~2x more per request; this path runs per RPC)."""

    __slots__ = ("_metrics", "_key", "_cached", "_start", "code")

    def __init__(self, metrics, key, cached):
        self._metrics = metrics
        self._key = key
        self._cached = cached
        self.code = "OK"

    def __enter__(self):
        self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        duration_child, counters = self._cached
        duration_child.observe(time.perf_counter() - self._start)
        counter = counters.get(self.code)
        if counter is None:
            counter = self._metrics.requests_total.labels(*self._key, self.code)
            counters[self.code] = counter
        counter.inc()
        return False

    # dict-style writes kept for handler compatibility
    # (handlers do `outcome["code"] = ...`)
    def __setitem__(self, k, v):
        if k == "code":
            self.code = v

    def __getitem__(self, k):
        if k == "code":
            return self.code
        raise KeyError(k)


class _NoopSpan:
    def set_attribute(self, *a, **k):
        pass

    def record_exception(self, *a, **k):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NOOP_SPAN = _NoopSpan()


class _NoopTracer:
    # False lets hot paths skip per-request span bookkeeping entirely
    active = False

    def span(self, name: str, ctx=None, root: bool = False, **attrs):
        # singleton CM: no generator frame per call on the serve path
        return _NOOP_SPAN

    def record(self, name: str, ctx=None, duration_s=None, **attrs):
        pass


class RecordedSpan:
    __slots__ = ("name", "attrs")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def set_attribute(self, key, value):
        self.attrs[key] = value

    def record_exception(self, err):
        self.attrs["exception"] = repr(err)


class RecordingTracer:
    """In-memory span recorder (`tracing.provider: memory`): the test/
    debug exporter — this image ships only the OTel API, not the SDK, so
    span visibility needs a built-in sink. Thread-safe append-only.

    Spans carry trace correlation: an explicit `ctx` (SpanContext) or,
    when absent, the executing request's CURRENT_TRACE — so persistence
    spans recorded deep in a handler share the request's trace_id
    without any signature changes.

    `root=True` marks the request's TRANSPORT span: it takes the
    context's OWN span id (instead of minting a child) so every other
    span in the request — batcher.queue, engine stages, store ops —
    parent-links to it, and its own parent is the caller's span id from
    the ingested `traceparent` (ctx.parent_span_id). That's what turns
    the flat recording into a real parent-linked trace at an OTel
    collector.

    `exporter` (SpanExporter | None) receives every COMPLETED span that
    carries a trace id — the OTLP/HTTP-JSON export plane. Enqueue is
    non-blocking by contract (bounded queue, drop counter)."""

    active = True

    def __init__(self, cap: int = 4096, exporter=None):
        import collections

        self.spans = collections.deque(maxlen=cap)
        self.exporter = exporter

    @staticmethod
    def _trace_attrs(ctx, attrs: dict, root: bool = False) -> dict:
        if ctx is None:
            rt = CURRENT_TRACE.get()
            ctx = rt.ctx if rt is not None else None
        if ctx is not None:
            attrs["trace_id"] = ctx.trace_id
            if root:
                # the transport span IS the request's span: ctx.span_id
                # is what every nested span parents to, and the caller's
                # client span (parent_span_id) is what THIS span parents
                # to across the process boundary
                attrs["span_id"] = ctx.span_id
                if ctx.parent_span_id:
                    attrs["parent_span_id"] = ctx.parent_span_id
            else:
                attrs["parent_span_id"] = ctx.span_id
                attrs["span_id"] = secrets.token_hex(8)
        return attrs

    def _export(self, s: "RecordedSpan") -> None:
        if self.exporter is not None and "trace_id" in s.attrs:
            self.exporter.enqueue(s)

    @contextlib.contextmanager
    def span(self, name: str, ctx=None, root: bool = False, **attrs):
        s = RecordedSpan(name, self._trace_attrs(ctx, dict(attrs), root))
        self.spans.append(s)
        start = time.perf_counter()
        try:
            yield s
        finally:
            s.attrs["duration_ms"] = round(
                (time.perf_counter() - start) * 1e3, 3
            )
            # monotonic END stamp: the exporter anchors it to the epoch
            # (wall clocks are banned repo-wide; one anchored conversion
            # at the export boundary is the OTLP wire requirement)
            s.attrs.setdefault("t_mono", time.monotonic())
            self._export(s)

    def record(self, name: str, ctx=None, duration_s=None, **attrs):
        """Retroactive span: stages measured after the fact (batcher
        queue wait, batch-shared engine stages) become spans without a
        live context manager around the work."""
        attrs = self._trace_attrs(ctx, dict(attrs))
        if duration_s is not None:
            attrs["duration_ms"] = round(duration_s * 1e3, 3)
        attrs.setdefault("t_mono", time.monotonic())
        s = RecordedSpan(name, attrs)
        self.spans.append(s)
        self._export(s)

    def span_names(self) -> list:
        return [s.name for s in self.spans]

    def spans_for_trace(self, trace_id: str) -> list:
        return [s for s in self.spans if s.attrs.get("trace_id") == trace_id]


class TracedManager:
    """Span-per-store-op proxy around any Manager implementation — the
    analog of the reference's otel spans in every persister method
    (internal/persistence/sql/relationtuples.go:203-205 etc.) without
    touching the store classes.

    Every public Manager method is either in _TRACED or in _EXEMPT (with
    the reason); tests/test_observability.py asserts the union covers
    the real store classes, so a new store op cannot silently bypass the
    span proxy again (the PR-2 watch ops did)."""

    _TRACED = (
        "get_relation_tuples", "write_relation_tuples",
        "delete_relation_tuples", "delete_all_relation_tuples",
        "transact_relation_tuples", "relation_tuple_exists",
        "all_relation_tuples",
        # watch-era store ops (PR 2): the changelog reads feeding the
        # delta overlay and the watch hub's versioned tail
        "changes_since", "changelog_since",
        # scale/ingest ops: O(edges) reads/writes are exactly the spans
        # an operator wants to see
        "all_tuple_columns", "bulk_load",
        # migration runners (operator-invoked, slow, worth a span)
        "migrate_up", "migrate_down",
        "map_strings_to_uuids", "map_uuids_to_strings",
    )
    # public methods deliberately NOT traced, with the reason — the
    # coverage test fails on any public store method in neither tuple
    _EXEMPT = (
        "version",             # per-batch staleness counter read (hot path)
        "add_write_listener",  # one-time hook registration, not an op
        "set_trim_guard",      # registration; guard runs inside store locks
        "migration_status",    # trivial metadata read (CLI status verb)
        "legacy_row_count",    # trivial metadata read (migration gate)
        "close",               # teardown; tracer may already be gone
    )

    def __init__(self, inner, tracer):
        self._inner = inner
        self._tracer = tracer

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name in self._TRACED and callable(attr):
            tracer = self._tracer

            def traced(*args, **kwargs):
                with tracer.span(f"persistence.{name}"):
                    return attr(*args, **kwargs)

            return traced
        return attr


class _OtelTracer:
    active = True

    def __init__(self, service_name: str):
        from opentelemetry import trace

        self._tracer = trace.get_tracer(service_name)

    @contextlib.contextmanager
    def span(self, name: str, ctx=None, root: bool = False, **attrs):
        with self._tracer.start_as_current_span(name) as s:
            if ctx is not None:
                s.set_attribute("keto.trace_id", ctx.trace_id)
            for k, v in attrs.items():
                s.set_attribute(k, v)
            yield s

    def record(self, name: str, ctx=None, duration_s=None, **attrs):
        # the OTel API (no SDK) has no retroactive-span surface; emit a
        # zero-length span carrying the duration as an attribute
        if duration_s is not None:
            attrs["duration_ms"] = round(duration_s * 1e3, 3)
        with self.span(name, ctx=ctx, **attrs):
            pass


# -- OTLP/HTTP-JSON span export ------------------------------------------------


def _otlp_value(v) -> dict:
    """One attribute value in OTLP AnyValue JSON shape."""
    if isinstance(v, bool):
        return {"boolValue": v}
    if isinstance(v, int):
        return {"intValue": str(v)}  # OTLP JSON carries int64 as string
    if isinstance(v, float):
        return {"doubleValue": v}
    return {"stringValue": str(v)}


# span-record attrs that are structural (identity/timing), not payload —
# everything else exports as OTLP span attributes
_SPAN_STRUCTURAL = frozenset(
    ("trace_id", "span_id", "parent_span_id", "duration_ms", "t_mono",
     "launch_id", "launch_ids")
)


class SpanExporter:
    """Background OTLP/HTTP-JSON span exporter — stdlib wire format, no
    OTel SDK. The missing half of the PR-3 telemetry plane: the spans
    the RecordingTracer already correlates per trace_id leave the
    process as real OTLP `resourceSpans`, so the trace_id a client sent
    as `traceparent` comes back out as a parent-linked multi-span trace
    in any OTel collector/Jaeger.

    Contract with the serve hot path:
      - `enqueue` NEVER blocks: a bounded queue.Queue absorbs bursts,
        overflow increments `keto_tpu_otlp_dropped_total{queue_full}`
        and the span is gone — export is observability, dropping beats
        back-pressuring a request thread.
      - one daemon worker thread drains the queue in batches (at most
        `batch_max` spans per POST, at least every `flush_interval_s`)
        and POSTs to `observability.otlp.endpoint` with a bounded
        timeout; a failed POST counts its batch as
        dropped{post_error} and moves on — a dead collector costs
        drops, never latency.
      - timestamps: spans carry MONOTONIC end stamps (wall clocks are
        banned repo-wide, ketolint clock-monotonic); ONE epoch anchor
        captured at construction converts them to the unixNano the OTLP
        wire requires (time.time_ns is the sanctioned single wall-clock
        read — it is never used for interval math).
      - flight-recorder correlation: a span's `launch_id`/`launch_ids`
        attr becomes OTLP span EVENTS (name `flightrec.launch`), so a
        trace in Jaeger points straight at its GET /admin/flightrec
        ring entries.

    `flush(timeout)` blocks until everything enqueued so far has been
    POSTed (tests, daemon drain); `close()` stops the worker after a
    final flush attempt."""

    def __init__(
        self,
        endpoint: str,
        metrics=None,
        queue_size: int = 2048,
        flush_interval_s: float = 0.2,
        batch_max: int = 512,
        post_timeout_s: float = 2.0,
        service_name: str = "keto_tpu",
        instance_id: str = "",
    ):
        import os
        import queue as _queue

        self.endpoint = endpoint
        self.metrics = metrics
        self.flush_interval_s = max(float(flush_interval_s), 0.01)
        self.batch_max = max(int(batch_max), 1)
        self.post_timeout_s = float(post_timeout_s)
        self.service_name = service_name
        self.instance_id = instance_id or str(os.getpid())
        self._q: "_queue.Queue" = _queue.Queue(maxsize=max(int(queue_size), 1))
        self._stop = threading.Event()
        # flush accounting: enqueued vs settled (exported OR dropped);
        # flush() waits for settled to catch up under one condition
        self._mu = threading.Lock()
        self._settle_cond = threading.Condition(self._mu)
        self._enqueued = 0
        self._settled = 0
        self.stats = {"exported": 0, "dropped_queue_full": 0,
                      "dropped_post_error": 0, "posts": 0}
        # the ONE wall-clock read: an epoch anchor for OTLP unixNano
        # stamps; every span time is anchor + (its monotonic - anchor's)
        self._anchor_epoch_ns = time.time_ns()
        self._anchor_mono = time.monotonic()
        self._thread = threading.Thread(
            target=self._loop, name="keto-otlp-export", daemon=True
        )
        self._thread.start()

    # -- hot-path surface ------------------------------------------------------

    def enqueue(self, span) -> bool:
        """Queue one completed RecordedSpan for export. Non-blocking:
        False (+ drop counter) when the bounded queue is full."""
        import queue as _queue

        if self._stop.is_set():
            return False
        with self._mu:
            self._enqueued += 1
        try:
            self._q.put_nowait(span)
            return True
        except _queue.Full:
            self._drop(1, "queue_full")
            return False

    # -- bookkeeping -----------------------------------------------------------

    def _settle(self, n: int) -> None:
        with self._settle_cond:
            self._settled += n
            self._settle_cond.notify_all()

    def _drop(self, n: int, reason: str) -> None:
        self.stats[f"dropped_{reason}"] += n
        if self.metrics is not None:
            self.metrics.otlp_dropped_total.labels(reason).inc(n)
        self._settle(n)

    def _mark_exported(self, n: int) -> None:
        self.stats["exported"] += n
        if self.metrics is not None:
            self.metrics.otlp_exported_total.inc(n)
        self._settle(n)

    # -- worker ----------------------------------------------------------------

    def _loop(self) -> None:
        import queue as _queue

        # TICK-based, not wake-per-span: a blocking q.get would wake
        # this worker (json.dumps + POST, GIL-holding) the instant a
        # request thread enqueues — measured 1.2x serve latency on a
        # 2-core box. Sleeping the flush interval and draining in
        # batches decouples export work from request threads entirely;
        # the cost is at most one interval of added export delay.
        while True:
            stopped = self._stop.wait(self.flush_interval_s)
            while True:
                batch = []
                while len(batch) < self.batch_max:
                    try:
                        batch.append(self._q.get_nowait())
                    except _queue.Empty:
                        break
                if not batch:
                    break
                self._post(batch)
            if stopped:
                return

    def _epoch_ns(self, mono: float) -> int:
        return self._anchor_epoch_ns + int(
            (mono - self._anchor_mono) * 1e9
        )

    def _otlp_span(self, s) -> dict:
        attrs = s.attrs
        end_mono = attrs.get("t_mono", self._anchor_mono)
        end_ns = self._epoch_ns(end_mono)
        dur_ms = float(attrs.get("duration_ms", 0.0) or 0.0)
        start_ns = end_ns - int(dur_ms * 1e6)
        out = {
            "traceId": attrs.get("trace_id", ""),
            "spanId": attrs.get("span_id", ""),
            "name": s.name,
            "kind": 2,  # SPAN_KIND_SERVER-side work
            "startTimeUnixNano": str(start_ns),
            "endTimeUnixNano": str(end_ns),
            "attributes": [
                {"key": k, "value": _otlp_value(v)}
                for k, v in attrs.items()
                if k not in _SPAN_STRUCTURAL
            ],
        }
        parent = attrs.get("parent_span_id")
        if parent:
            out["parentSpanId"] = parent
        # flight-recorder launch ids ride as span EVENTS: the join key
        # into GET /admin/flightrec, visible per span in the collector
        launch_ids = tuple(
            lid for lid in (attrs.get("launch_ids") or ()) if lid is not None
        )
        if attrs.get("launch_id") is not None:
            launch_ids = (*launch_ids, attrs["launch_id"])
        if launch_ids:
            out["events"] = [
                {
                    "timeUnixNano": str(end_ns),
                    "name": "flightrec.launch",
                    "attributes": [
                        {"key": "launch_id", "value": _otlp_value(int(lid))}
                    ],
                }
                for lid in launch_ids
            ]
        return out

    def payload(self, spans: list) -> bytes:
        """The OTLP/HTTP-JSON request body for one span batch (public:
        the smoke validates the wire shape without a collector)."""
        import json as _json

        return _json.dumps({
            "resourceSpans": [{
                "resource": {
                    "attributes": [
                        {"key": "service.name",
                         "value": {"stringValue": self.service_name}},
                        {"key": "service.instance.id",
                         "value": {"stringValue": self.instance_id}},
                    ]
                },
                "scopeSpans": [{
                    "scope": {"name": "keto_tpu"},
                    "spans": [self._otlp_span(s) for s in spans],
                }],
            }]
        }).encode()

    def _post(self, batch: list) -> None:
        import urllib.request

        try:
            body = self.payload(batch)
            req = urllib.request.Request(
                self.endpoint, data=body,
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=self.post_timeout_s):
                pass
            self.stats["posts"] += 1
            self._mark_exported(len(batch))
        except Exception as e:  # noqa: BLE001 — a dead collector must
            # never fail (or slow) anything but this counter
            logger.debug("otlp export POST failed: %s", e)
            self._drop(len(batch), "post_error")

    # -- lifecycle -------------------------------------------------------------

    def flush(self, timeout: float = 5.0) -> bool:
        """Block until every span enqueued BEFORE this call is settled
        (exported or dropped); False on timeout."""
        deadline = time.monotonic() + timeout
        with self._settle_cond:
            target = self._enqueued
            while self._settled < target:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._settle_cond.wait(remaining)
        return True

    def close(self, timeout: float = 2.0) -> None:
        self.flush(timeout)
        self._stop.set()
        # wake the worker out of its queue.get wait
        self._thread.join(timeout=max(self.flush_interval_s * 2, 0.5))


def build_tracer(config, exporter=None):
    """ref: otelx tracer built once from config (registry_default.go:118-129).
    `tracing.provider: memory` selects the in-process recording sink.
    A SpanExporter (built by the registry when
    `observability.otlp.endpoint` is set) forces the recording sink —
    the export plane reads our RecordedSpan objects — regardless of
    provider: setting the endpoint IS the opt-in."""
    if exporter is not None:
        return RecordingTracer(exporter=exporter)
    if config.get("tracing.enabled", False):
        if config.get("tracing.provider", "otel") == "memory":
            return RecordingTracer()
        try:
            return _OtelTracer(config.get("tracing.service_name", "keto_tpu"))
        except Exception as e:  # otel mis-setup must never block serving
            logger.warning("tracing disabled: %s", e)
    return _NoopTracer()


def _stages_ms(stages: Optional[dict]) -> dict[str, float]:
    return {k: round(v * 1e3, 3) for k, v in (stages or {}).items()}


class _JsonLogFormatter(logging.Formatter):
    """One JSON object per line (`log.format: json`), carrying the
    structured extras request_log/slow_query_log attach — machine-
    ingestable parity with the reference's logrusx JSON mode."""

    _STD = frozenset(
        logging.LogRecord("", 0, "", 0, "", (), None).__dict__
    ) | {"message", "asctime", "taskName"}

    def format(self, record: logging.LogRecord) -> str:
        import json as _json

        out = {
            "time": self.formatTime(record),
            "level": record.levelname.lower(),
            "logger": record.name,
            "msg": record.getMessage(),
        }
        for k, v in record.__dict__.items():
            if k not in self._STD:
                out[k] = v
        if record.exc_info:
            out["exc"] = self.formatException(record.exc_info)
        return _json.dumps(out, default=str)


def configure_logging(config) -> None:
    """Apply `log.level` / `log.format` from the config to the keto_tpu
    logger tree (ref: logrusx setup in driver registry). Called by
    Daemon.start so an operator's config controls serve logging without
    code; idempotent — repeated starts just re-apply."""
    level = config.get("log.level")
    if level:
        logger.setLevel(getattr(logging, str(level).upper(), logging.INFO))
    fmt = config.get("log.format")
    json_handlers = [
        h for h in logger.handlers if getattr(h, "_keto_json", False)
    ]
    if fmt == "json":
        if not json_handlers:
            handler = logging.StreamHandler()
            handler._keto_json = True
            handler.setFormatter(_JsonLogFormatter())
            logger.addHandler(handler)
            # the JSON handler replaces root propagation (double lines
            # otherwise: one structured, one from the root handler)
            logger.propagate = False
    elif json_handlers:
        # symmetric: a later start with log.format text (or unset) must
        # UNDO json mode — a stuck handler + propagate=False would hide
        # keto_tpu records from root/caplog for the process's lifetime
        for h in json_handlers:
            logger.removeHandler(h)
        logger.propagate = True


def request_log(
    transport: str,
    method: str,
    code: str,
    duration_s: float,
    trace_id: str = "",
    stages: Optional[dict] = None,
    launch_ids: Optional[list] = None,
    tier: Optional[str] = None,
) -> None:
    """Structured per-request log line (ref: reqlog middleware
    daemon.go:294), now carrying the trace id, the per-stage ms
    breakdown, the flight-recorder launch ids the request rode, and the
    answering tier (cache | closure | device | host | vocab) — the tier
    used to be visible only via explain=true, which bypasses the cache
    and rate-limits. The isEnabledFor gate inside logger.info keeps
    this free on the serve hot path at the default WARNING level."""
    if not logger.isEnabledFor(logging.INFO):
        return
    extra = {
        "transport": transport,
        "method": method,
        "code": code,
        "duration_ms": round(duration_s * 1e3, 3),
    }
    if trace_id:
        extra["trace_id"] = trace_id
    if tier:
        extra["tier"] = tier
    if stages:
        extra["stages_ms"] = _stages_ms(stages)
    if launch_ids:
        extra["launch_ids"] = list(launch_ids)
    logger.info("request handled", extra=extra)


def slow_query_log(
    threshold_ms,
    transport: str,
    method: str,
    code: str,
    duration_s: float,
    trace_id: str = "",
    stages: Optional[dict] = None,
    launch_ids: Optional[list] = None,
    tier: Optional[str] = None,
) -> None:
    """Threshold-configurable slow-query line (`log.slow_query_ms`):
    one structured WARNING with the trace id, the answering tier,
    per-stage ms, and the launch ids of the device batches the request
    rode (join key into `GET /admin/flightrec`), so a single slow
    request is attributable — down to its exact launch record — without
    turning on full request logging. None threshold = disabled; fires
    at duration >= threshold."""
    if threshold_ms is None:
        return
    duration_ms = duration_s * 1e3
    if duration_ms < float(threshold_ms):
        return
    logger.warning(
        "slow request trace_id=%s transport=%s method=%r code=%s "
        "duration_ms=%.3f tier=%s launch_ids=%s stages_ms=%s",
        trace_id or "-",
        transport,
        method,
        code,
        duration_ms,
        tier or "-",
        list(launch_ids or ()),
        _stages_ms(stages),
    )


def finish_request_telemetry(
    metrics,
    threshold_ms,
    transport: str,
    method: str,
    rt: RequestTrace,
    code: str,
    duration_s: float,
    skip_slow: bool = False,
    sample_rate=None,
    workload=None,
) -> None:
    """Shared end-of-request bookkeeping for every transport (REST
    _route, sync-gRPC _observed, aio _observed): computes the transport
    residual stage, feeds the stage histogram ONLY for requests that
    rode the check pipeline (scrapes/lists/writes have no breakdown and
    would pollute the Check attribution), then emits the request and
    slow-query logs. `skip_slow` exempts by-design-long requests (SSE
    watch streams).

    `sample_rate` (log.request_sample_rate, default 1.0) probabilistically
    samples the per-request INFO `request handled` line: at 1M checks/s
    the unconditional line is itself an overload source, so operators
    can dial it down without losing the slow-query WARNINGs — those
    ALWAYS emit (a sampled-out slow request would be exactly the
    evidence an incident needs).

    `workload` (the registry's WorkloadObservatory, or None) receives
    every finished request: per-tier latency histogram, read/write
    accounting, and the SLO engine's latency + availability events —
    the same `skip_slow` flag exempts watch streams from the latency
    objective (still counted for availability)."""
    rode_pipeline = bool(rt.stages)
    rt.add_stage(
        "transport", max(0.0, duration_s - sum(rt.stages.values()))
    )
    if rode_pipeline and metrics is not None:
        metrics.observe_stage(
            "transport", rt.stages["transport"], trace_id=rt.ctx.trace_id
        )
    launch_ids = getattr(rt, "launch_ids", None)
    tier = getattr(rt, "tier", None)
    if workload is not None:
        workload.observe_request(
            method, code, duration_s, tier=tier,
            trace_id=rt.ctx.trace_id, latency_eligible=not skip_slow,
        )
    sampled_in = True
    if sample_rate is not None and float(sample_rate) < 1.0:
        import random as _random

        sampled_in = _random.random() < float(sample_rate)
    if sampled_in:
        request_log(
            transport, method, code, duration_s,
            trace_id=rt.ctx.trace_id, stages=rt.stages,
            launch_ids=launch_ids, tier=tier,
        )
    if not skip_slow:
        slow_query_log(
            threshold_ms, transport, method, code, duration_s,
            trace_id=rt.ctx.trace_id, stages=rt.stages,
            launch_ids=launch_ids, tier=tier,
        )
