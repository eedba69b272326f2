"""Registry: the dependency-injection composition root.

Parity with driver.Registry (internal/driver/registry.go:23-52) and
RegistryDefault's lazy singletons (internal/driver/registry_default.go:
98-192): config + logger, tuple manager (chosen by DSN), check/expand
engines (TPU or host, chosen by `check.engine`), mapper, health state,
metrics, and the server handlers hang off one object that everything
receives. This is the plugin boundary named in the north star: swapping
`check.engine=tpu` for `host` here changes nothing above it.

DSN forms (ref: internal/driver/config/provider.go:187-193 aliases
"memory"; pop DSNs otherwise):
  - "memory"            -> in-process dict-of-arrays store (fast path)
  - "sqlite://<path>"   -> durable SQLite persister (runs migrations)
  - "sqlite://:memory:" -> in-memory SQLite (the reference's "memory")
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from . import __version__
from .config import Config
from .engine.reference import ReferenceEngine
from .errors import NamespaceNotFoundError
from .ketoapi import CheckColumns, RelationQuery, RelationTuple
from .storage.definitions import DEFAULT_NETWORK
from .storage.memory import MemoryManager

logger = logging.getLogger("keto_tpu")


class ReadyState:
    """Event-compatible readiness flag with change notification.

    Health Watch streams park on `wait_change` (a Condition) instead of
    busy-polling, so idle watchers cost no CPU and wake immediately on a
    readiness transition (ref pushes on change; ADVICE round-1 flagged
    the 0.5s poll loop)."""

    def __init__(self):
        self._cond = threading.Condition()
        self._flag = False
        self._gen = 0

    def is_set(self) -> bool:
        return self._flag

    def set(self) -> None:
        with self._cond:
            if not self._flag:
                self._flag = True
                self._gen += 1
                self._cond.notify_all()

    def clear(self) -> None:
        with self._cond:
            if self._flag:
                self._flag = False
                self._gen += 1
                self._cond.notify_all()

    def state(self) -> tuple[bool, int]:
        with self._cond:
            return self._flag, self._gen

    def wait_change(self, gen: int, timeout: float) -> tuple[bool, int]:
        """Block until the generation moves past `gen` (or timeout, so
        stream handlers can re-check client liveness); returns the
        current (flag, generation)."""
        with self._cond:
            if self._gen == gen:
                self._cond.wait(timeout)
            return self._flag, self._gen


class Registry:
    """Composition root. Lazily builds every service exactly once."""

    def __init__(
        self,
        config: Optional[Config] = None,
        nid: str = DEFAULT_NETWORK,
        mesh=None,
        contextualizer=None,
    ):
        self.config = config or Config()
        self.nid = nid
        self.mesh = mesh
        self.version = __version__
        # operator platform pin: `check.platform: cpu` is an explicit
        # operator choice to serve from the host backend (a machine with
        # no chip, or one whose chip is being serviced). Nothing selects
        # it by itself, and it only takes effect before JAX has
        # initialized a backend
        platform = self.config.get("check.platform")
        if platform:
            import jax

            try:  # the pin is a silent no-op once a backend exists —
                # surface that instead of letting the operator believe
                # the pin took effect
                from jax._src import xla_bridge

                if xla_bridge.backends_are_initialized():
                    import logging

                    logging.getLogger("keto_tpu").warning(
                        "check.platform=%r set after a JAX backend "
                        "initialized; the pin has no effect in this "
                        "process", platform,
                    )
            except ImportError:
                pass
            jax.config.update("jax_platforms", platform)
        self._lock = threading.RLock()
        self._manager = None
        self._engine = None
        # per-request tenancy (ketoctx.Contextualizer analog): nid_for()
        # derives the network from transport metadata; engines are cached
        # per nid (each network has its own device mirror)
        if contextualizer is None:
            from . import ketoctx

            contextualizer = ketoctx.from_config(self.config)
        self.contextualizer = contextualizer
        import collections

        self._nid_engines: "collections.OrderedDict[str, object]" = (
            collections.OrderedDict()
        )
        self._metrics = None
        self._tracer = None
        self._span_exporter = None
        self._span_exporter_built = False
        self._explain_limiter = None
        self._profiler = None
        self._flightrec = None
        self._workload = None
        self._workload_built = False
        self._scrubber = None
        self._closure_maintainer = None
        self._watch_hub = None
        self._check_cache = None
        self._check_cache_built = False
        self._breaker = None
        self._store_breaker = None
        # health: flipped by the daemon around serving
        # (ref: registry_default.go:98-112 healthx readiness checkers)
        self.ready = ReadyState()
        # drain flag: set by Daemon.stop for the shutdown grace window —
        # the admission gate (resilience.admit_check) sheds new checks
        # with a typed 429 while in-flight work completes
        self.draining = threading.Event()
        # replica serving group (api/replica.py), attached by the daemon
        # when serve.check.workers >= 2; the metrics listener's
        # GET /admin/replicas reads it (None = single-stack serving)
        self.replica_group = None
        # HA follower plane (api/follower.py), attached by the daemon
        # when follower.enabled; GET /admin/ha reads it (None on a
        # leader — ha_status() then reports the leader-side view)
        self.ha_plane = None
        self._follower_store = None

    # -- storage --------------------------------------------------------------

    def relation_tuple_manager(self):
        with self._lock:
            if self._manager is None:
                dsn = self.config.dsn
                if bool(self.config.get("follower.enabled", False)):
                    # HA follower daemon (api/follower.py): the store is
                    # a network-fed mirror of the LEADER's — versions
                    # pinned to the leader's commit versions, local
                    # writes refused with a typed 503. The DSN is
                    # ignored: this process never owns tuples. The RAW
                    # store reference is kept for the replication plane
                    # (apply_remote must bypass the health guard —
                    # replication is not request traffic).
                    from .api.follower import FollowerStore

                    self._manager = self._follower_store = FollowerStore()
                elif dsn == "memory":
                    self._manager = MemoryManager()
                elif dsn == "columnar":
                    # scale tier: numpy-column store (1e8-tuple ingest)
                    from .storage.columnar import ColumnarStore

                    self._manager = ColumnarStore()
                else:
                    # sqlite:// | postgres:// | cockroach:// | mysql://
                    # route through the STRICT dialect layer
                    # (storage/dialect.py): an unknown scheme, a missing
                    # driver, or a bare-string typo ('Memory') raises
                    # with the reason — failing startup beats silently
                    # serving an empty store from a fresh sqlite file
                    from .storage.sqlite import SQLPersister

                    self._manager = SQLPersister(
                        dsn,
                        legacy_namespaces=self.config.legacy_namespace_ids(),
                    )
                # span-per-store-op when tracing (ref: otel spans in every
                # persister method, relationtuples.go:203-205); the OTLP
                # endpoint alone also turns these on — an exported trace
                # without its store-op spans is missing its leaves
                if self.config.get("tracing.enabled", False) or self.config.get(
                    "observability.otlp.endpoint"
                ):
                    from .observability import TracedManager

                    self._manager = TracedManager(self._manager, self.tracer())
                # store health plane (storage/health.py): the OUTERMOST
                # wrapper — per-op timeouts on a bounded executor (SQL
                # dialects; the in-process dict stores cannot hang, so
                # they run inline) + the store-path circuit breaker
                # every consumer shares. When SQL dies: reads the mirror
                # covers degrade to bounded staleness, everything else
                # sheds a typed 503 — never wrong, never hung.
                if bool(self.config.get("store.health.enabled", True)):
                    from .storage.health import StoreHealthGuard

                    self._manager = StoreHealthGuard(
                        self._manager,
                        breaker=self.store_breaker(),
                        op_timeout_s=float(
                            self.config.get("store.op_timeout_ms", 1000)
                        ) / 1e3,
                        bulk_timeout_s=float(
                            self.config.get("store.bulk_timeout_ms", 120000)
                        ) / 1e3,
                        # in-process dict stores cannot hang — and the
                        # follower's network-fed mirror is one of them,
                        # whatever the (ignored) DSN says
                        use_executor=(
                            dsn not in ("memory", "columnar")
                            and self._follower_store is None
                        ),
                        metrics=self.metrics(),
                    )
            return self._manager

    def follower_store(self):
        """The RAW FollowerStore when this process is a follower
        (follower.enabled), else None. Raw = unwrapped by Traced/
        HealthGuard: the replication tail writes through this reference
        (apply_remote/bootstrap_replace are infrastructure, not request
        traffic — they must land even while the request-path breaker is
        open)."""
        self.relation_tuple_manager()  # ensure built
        return self._follower_store

    def ha_status(self) -> dict:
        """The /admin/ha document: the follower plane's status when one
        is attached, else the leader-side view (store version + watch
        tail are the ground truth followers replicate toward)."""
        if self.ha_plane is not None:
            return self.ha_plane.status()
        from .errors import StoreUnavailableError

        try:
            version = self.relation_tuple_manager().version(nid=self.nid)
        except StoreUnavailableError:
            version = None
        status: dict = {
            "role": "leader",
            "nid": self.nid,
            "store_version": version,
        }
        hub = self._watch_hub
        if hub is not None:
            status["watch_heartbeat_s"] = hub.heartbeat_s
        breaker = self._store_breaker
        if breaker is not None:
            status["store_breaker"] = breaker.state
        return status

    # -- engines --------------------------------------------------------------

    # client-supplied tenant ids are untrusted input: they become store
    # scopes, engine-cache keys, and checkpoint file names — constrain
    # the alphabet (no path separators) and length before any of that
    _NID_RE = __import__("re").compile(r"^[A-Za-z0-9._-]{1,128}$")

    def nid_for(self, metadata=None) -> str:
        """The network id for one request (ref: Contextualizer.Network,
        /root/reference/ketoctx/contextualizer.go:12-19); metadata is the
        transport's header/metadata mapping. A malformed tenant id is a
        client error (400), never a silent fallback to the default
        network (that would serve another tenant's data)."""
        if self.contextualizer is None or metadata is None:
            return self.nid
        nid = self.contextualizer.network(metadata, self.nid)
        if nid != self.nid and not self._NID_RE.match(nid):
            from .errors import MalformedInputError

            raise MalformedInputError(debug=f"invalid network id {nid!r}")
        return nid

    def check_engine(self, nid: Optional[str] = None):
        """The configured check engine for one network; `check.engine`
        selects `tpu` (batched device kernel + exact host fallback) or
        `host` (pure reference semantics). Engines are cached per nid
        with an LRU bound (`tenancy.max_networks`) so arbitrary tenant
        ids can't grow memory without limit; evicted engines flush any
        pending mirror checkpoint and are rebuilt on demand."""
        if nid is None or nid == self.nid:
            with self._lock:
                if self._engine is None:
                    self._engine = self._build_engine(self.nid)
                return self._engine
        evicted: list = []
        with self._lock:
            engine = self._nid_engines.pop(nid, None)
            if engine is None:
                engine = self._build_engine(nid)
                cap = int(self.config.get("tenancy.max_networks", 64))
                while len(self._nid_engines) >= max(cap, 1):
                    evicted.append(self._nid_engines.popitem(last=False)[1])
            self._nid_engines[nid] = engine  # (re-)insert at MRU
        if evicted:
            # flush EVERY evicted engine's pending checkpoint, off the
            # request thread (the compressed write can take seconds)
            def _flush_evicted(engines=tuple(evicted)):
                for e in engines:
                    # end the push-refresh thread first: its bound-method
                    # target would pin the evicted engine in memory
                    stop = getattr(e, "stop_push_refresh", None)
                    if stop is not None:
                        stop()
                    flush = getattr(e, "flush_checkpoints", None)
                    if flush is not None:
                        flush()

            t = threading.Thread(
                target=_flush_evicted, name="keto-evict-flush", daemon=True
            )
            t.start()
        return engine

    def flush_checkpoints(self) -> None:
        """Flush pending device-mirror checkpoints for EVERY cached
        engine (default network + all tenants); the daemon calls this on
        graceful shutdown. A failing write (full disk, revoked mount)
        must not abort the drain: the checkpoint is a warm-restart
        optimization — the store is the durability — so each failure is
        logged + counted and the remaining engines still flush."""
        with self._lock:
            engines = list(self._nid_engines.values())
            if self._engine is not None:
                engines.append(self._engine)
        for engine in engines:
            flush = getattr(engine, "flush_checkpoints", None)
            if flush is None:
                continue
            try:
                flush()
            except Exception:  # noqa: BLE001 — shutdown must complete
                logger.warning(
                    "mirror checkpoint flush failed for nid=%s "
                    "(cold start will rebuild from the store)",
                    getattr(engine, "nid", "?"), exc_info=True,
                )
                self.metrics().checkpoint_write_failures_total.inc()

    def _build_engine(self, nid: str):
        kind = self.config.get("check.engine", "tpu")
        manager = self.relation_tuple_manager()
        if kind == "tpu":
            from .compile_cache import ensure_compile_cache
            from .engine.tpu_engine import TPUCheckEngine

            # before the engine's first compile: a restart on this
            # checkout then finds every kernel already built
            ensure_compile_cache()
            return TPUCheckEngine(
                manager, self.config, nid=nid, mesh=self.mesh,
                metrics=self.metrics(), tracer=self.tracer(),
                frontier_cap=int(
                    self.config.get("check.frontier_cap", 1 << 14)
                ),
                auto_frontier=bool(
                    self.config.get("check.auto_frontier", True)
                ),
                flightrec=self.flight_recorder(),
            )
        if kind == "host":
            return _HostEngineFacade(
                ReferenceEngine(manager, self.config), nid,
                metrics=self.metrics(),
            )
        raise ValueError(f"unknown check.engine: {kind!r}")

    def expand_engine(self, nid: Optional[str] = None):
        return self.check_engine(nid)

    # -- watch subsystem ------------------------------------------------------

    def watch_hub(self):
        """The process-wide changelog streaming hub (keto_tpu/watch):
        registers itself as the store's post-commit write listener and
        trim guard, and push-invalidates cached engines' device mirrors
        on every commit (delta refresh becomes event-driven instead of
        per-request changes_since polling)."""
        with self._lock:
            if self._watch_hub is None:
                from .watch import WatchHub

                # in-band heartbeats are OPT-IN (an explicitly set
                # watch.heartbeat_s): the HA follower tail needs them
                # for liveness + idle version discovery, while default
                # single-daemon streams keep the pre-HA event mix
                hb = self.config.get("watch.heartbeat_s")
                self._watch_hub = WatchHub(
                    self.relation_tuple_manager(),
                    poll_interval=float(
                        self.config.get("watch.poll_interval", 0.25)
                    ),
                    buffer=int(self.config.get("watch.buffer", 256)),
                    metrics=self.metrics(),
                    heartbeat_s=float(hb) if hb is not None else None,
                )
                self._watch_hub.add_commit_listener(self._push_invalidate)
            return self._watch_hub

    def _push_invalidate(self, nid: str) -> None:
        """Hub commit listener: poke the ALREADY-BUILT engine for `nid`
        (never builds one — a tenant nobody queries must not get a device
        mirror just because someone wrote to it) and the serve-side
        check cache's invalidation thread."""
        from . import faults as _faults

        # crash point (keto_tpu/faults.py): committed + hub-notified but
        # the engine/cache pokes never ran — the restarted process must
        # converge from the durable store alone (it does: invalidation
        # is hygiene, the per-request version gate is the correctness)
        _faults.inject("cache_invalidation")
        with self._lock:
            engine = (
                self._engine if nid == self.nid else self._nid_engines.get(nid)
            )
            cache = self._check_cache
        if cache is not None:
            cache.notify_commit(nid)
        if engine is None:
            return
        poke = getattr(engine, "notify_write", None)
        if poke is not None:
            poke()

    def check_cache(self):
        """The serve-side snaptoken-consistent check cache
        (api/check_cache.py), or None when `check.cache.enabled` is
        false. Consulted by all three transports before the batcher;
        invalidated through the watch hub's commit listeners (wired in
        _push_invalidate) — correctness, however, rides the per-request
        store-version gate, never invalidation delivery.

        Lock-free after the first call (every check consults this): the
        built flag is written LAST under the lock, so a reader seeing it
        set also sees the cache reference."""
        if self._check_cache_built:
            return self._check_cache
        with self._lock:
            if not self._check_cache_built:
                if bool(self.config.get("check.cache.enabled", True)):
                    from .api.check_cache import CheckCache

                    self._check_cache = CheckCache(
                        self.relation_tuple_manager(),
                        self.config,
                        max_entries=int(
                            self.config.get("check.cache.max_entries", 65536)
                        ),
                        ttl_s=float(self.config.get("check.cache.ttl_s", 0.0)),
                        metrics=self.metrics(),
                    )
                self._check_cache_built = True
            return self._check_cache

    def close_check_cache(self) -> None:
        """End the check cache's invalidation thread (daemon shutdown);
        safe when the cache was never built or is disabled."""
        with self._lock:
            cache = self._check_cache
        if cache is not None:
            cache.close()

    def namespace_manager(self):
        return self.config.namespace_manager()

    # -- namespace validation (the Mapper's role) -----------------------------

    def validate_namespaces(self, *objs) -> None:
        """Every namespace mentioned by a tuple/query must be configured —
        the reference enforces this inside Mapper.FromTuple/FromQuery via
        NamespaceManager.GetNamespaceByName (internal/relationtuple/
        uuid_mapping.go:70-81); raises NamespaceNotFoundError."""
        nm = self.namespace_manager()
        for o in objs:
            if o is None:
                continue
            names = []
            if isinstance(o, (RelationTuple, RelationQuery)):
                if o.namespace is not None:
                    names.append(o.namespace)
                if o.subject_set is not None:
                    names.append(o.subject_set.namespace)
            else:  # SubjectSet
                names.append(o.namespace)
            for name in names:
                nm.get_namespace_by_name(name)  # raises if unknown

    # -- observability --------------------------------------------------------

    def metrics(self):
        with self._lock:
            if self._metrics is None:
                from .observability import Metrics

                self._metrics = Metrics()
            return self._metrics

    def tracer(self):
        with self._lock:
            if self._tracer is None:
                from .observability import build_tracer

                self._tracer = build_tracer(
                    self.config, exporter=self.span_exporter()
                )
            return self._tracer

    def span_exporter(self):
        """The process-wide OTLP span exporter
        (observability.SpanExporter), or None when
        `observability.otlp.endpoint` is unset. Setting the endpoint is
        the opt-in: the tracer then records spans AND exports them —
        bounded queue, background batched POSTs, drop counters — so the
        trace_id a client sent as `traceparent` leaves the process as a
        real multi-span OTLP trace. The daemon flushes + closes it on
        stop."""
        with self._lock:
            if not self._span_exporter_built:
                endpoint = self.config.get("observability.otlp.endpoint")
                if endpoint:
                    from .observability import SpanExporter

                    self._span_exporter = SpanExporter(
                        str(endpoint),
                        metrics=self.metrics(),
                        queue_size=int(
                            self.config.get("observability.otlp.queue", 2048)
                        ),
                        flush_interval_s=float(
                            self.config.get(
                                "observability.otlp.flush_interval_ms", 200
                            )
                        ) / 1e3,
                        service_name=str(
                            self.config.get(
                                "tracing.service_name", "keto_tpu"
                            )
                        ),
                    )
                self._span_exporter_built = True
            return self._span_exporter

    def explain_limiter(self):
        """The explain plane's token bucket (resilience.TokenBucket,
        `explain.max_per_s`): one process-wide bucket shared by every
        transport, so the cache-bypassing witness-re-walk slow path is
        rate-bounded no matter which plane the requests arrive on."""
        with self._lock:
            if self._explain_limiter is None:
                from .resilience import (
                    DEFAULT_EXPLAIN_MAX_PER_S,
                    TokenBucket,
                )

                rate = float(
                    self.config.get(
                        "explain.max_per_s", DEFAULT_EXPLAIN_MAX_PER_S
                    )
                )
                self._explain_limiter = TokenBucket(rate)
            return self._explain_limiter

    def circuit_breaker(self):
        """The process-wide device-path circuit breaker
        (resilience.CircuitBreaker), shared by both batching planes so
        device health is judged from all traffic. Always built (the
        defaults are harmless when the device is healthy); tuned via
        serve.check.breaker.{threshold,cooldown_s}."""
        with self._lock:
            if self._breaker is None:
                from .resilience import CircuitBreaker

                self._breaker = CircuitBreaker(
                    threshold=int(
                        self.config.get("serve.check.breaker.threshold", 5)
                    ),
                    cooldown_s=float(
                        self.config.get("serve.check.breaker.cooldown_s", 5.0)
                    ),
                    metrics=self.metrics(),
                )
            return self._breaker

    def store_breaker(self):
        """The process-wide STORE-path circuit breaker (the twin of
        circuit_breaker(), which judges the DEVICE path): consecutive
        store read failures/timeouts trip it; while open, every store
        op fails fast (typed 503) and the serve path degrades onto the
        device mirror at its covered version. Tuned via
        store.breaker.{threshold,cooldown_s}; exported as
        keto_tpu_store_breaker_state."""
        with self._lock:
            if self._store_breaker is None:
                from .resilience import CircuitBreaker
                from .storage.health import StoreBreakerMetrics

                self._store_breaker = CircuitBreaker(
                    threshold=int(
                        self.config.get("store.breaker.threshold", 5)
                    ),
                    cooldown_s=float(
                        self.config.get("store.breaker.cooldown_s", 5.0)
                    ),
                    metrics=StoreBreakerMetrics(self.metrics()),
                )
            return self._store_breaker

    def mirror_scrubber(self):
        """The anti-entropy device-mirror scrubber (engine/scrub.py):
        one background singleton incrementally checksumming every built
        engine's device tables against the host truth at the mirror's
        covered version. `scrub.{enabled,interval_s,slice_rows}`
        configure it; the daemon starts/stops the loop around serving,
        and `GET/POST /admin/scrub` on the metrics listener read state /
        trigger a full pass on demand."""
        with self._lock:
            if self._scrubber is None:
                from .engine.scrub import MirrorScrubber

                self._scrubber = MirrorScrubber(
                    self,
                    enabled=bool(self.config.get("scrub.enabled", False)),
                    interval_s=float(self.config.get("scrub.interval_s", 30.0)),
                    slice_rows=int(self.config.get("scrub.slice_rows", 1 << 16)),
                    metrics=self.metrics(),
                )
            return self._scrubber

    def closure_maintainer(self):
        """The Leopard-index maintenance plane (keto_tpu/closure): one
        background tailer keeping every built engine's closure index
        synced from the Watch changelog and re-powering it off the
        request path. The daemon starts/stops it around serving when
        `closure.enabled`; correctness never depends on it (every
        closure answer is version-gated at submit)."""
        with self._lock:
            if self._closure_maintainer is None:
                from .closure import ClosureMaintainer

                self._closure_maintainer = ClosureMaintainer(
                    self,
                    poll_interval=float(
                        self.config.get("watch.poll_interval", 0.25)
                    ),
                )
            return self._closure_maintainer

    def profiler(self):
        """The process-wide on-demand capture session (profiling.py),
        toggled live through the metrics listener's /admin/profiling
        endpoint — no restart to profile a running serve."""
        with self._lock:
            if self._profiler is None:
                from .profiling import Profiler

                self._profiler = Profiler()
            return self._profiler

    def flight_recorder(self):
        """The process-wide launch flight recorder
        (observability.FlightRecorder): ONE bounded ring shared by every
        engine and both batching planes, so `GET /admin/flightrec` and
        the failure auto-dumps see all launches in arrival order.
        `observability.flightrec.{enabled,capacity}` configure it; ids
        keep advancing when disabled so logs stay correlatable."""
        with self._lock:
            if self._flightrec is None:
                from .observability import FlightRecorder

                self._flightrec = FlightRecorder(
                    enabled=bool(
                        self.config.get("observability.flightrec.enabled", True)
                    ),
                    capacity=int(
                        self.config.get("observability.flightrec.capacity", 256)
                    ),
                    metrics=self.metrics(),
                )
                # ambient device-path health stamped onto every entry;
                # attribute reads only (no locks) — a provider must never
                # contend with the serve path
                self._flightrec.context_providers.append(
                    self._flightrec_context
                )
            return self._flightrec

    def _flightrec_context(self) -> dict:
        """Breaker + armed-faults state for flight-recorder entries.
        Reads the already-built breaker reference (never builds one —
        recording must not construct services)."""
        from . import faults as _faults

        breaker = self._breaker
        ctx: dict = {
            "faults": sorted(_faults.armed_names()),
        }
        if breaker is not None:
            # .state is a property — calling its str return value raised
            # and (because record() guards providers) silently dropped
            # the whole context from every entry a breaker-ful process
            # recorded
            ctx["breaker"] = breaker.state
        store_breaker = self._store_breaker
        if store_breaker is not None:
            ctx["store_breaker"] = store_breaker.state
        return ctx

    def workload_observatory(self):
        """The process-wide workload observatory + SLO plane
        (observability_workload.WorkloadObservatory). ONE instance
        shared by every transport: per-(nid, relation) accounting and
        the hot-key sketches feed from the check serve gate, the SLO
        engine feeds from finish_request_telemetry. `workload.enabled`
        and `slo.enabled` gate the two halves internally (the object
        always exists, so the A/B off arm is one attribute test).

        Lock-free after the first call (every finished request consults
        this): the built flag is written LAST under the lock, so a
        reader seeing it set also sees the observatory reference — the
        check cache's publication pattern."""
        if self._workload_built:
            return self._workload
        with self._lock:
            if not self._workload_built:
                from .observability_workload import build_observatory

                self._workload = build_observatory(
                    self.config,
                    metrics=self.metrics(),
                    staleness_probe=self._mirror_staleness_age,
                )
                self._workload_built = True
            return self._workload

    def _mirror_staleness_age(self):
        """Max mirror staleness age (seconds) across ALREADY-BUILT
        engines, for the SLO max_staleness_s objective — never builds
        an engine (sampled once per SLO eval tick; a probe must not
        construct device mirrors), returns None when no built engine
        reports one (host facade, nothing built yet)."""
        worst = None
        for eng in self.built_engines().values():
            probe = getattr(eng, "mirror_staleness_age_s", None)
            if probe is None:
                continue
            try:
                age = probe()
            # ketolint: allow[typed-error] reason=SLO staleness probe isolation: one engine's introspection failure must cost that engine's sample, never the whole evaluation tick (the probe runs inside the SLO engine's lock-held tick path)
            except Exception:  # pragma: no cover - defensive isolation
                continue
            # a NEVER-synced engine reports inf — that is "no sync has
            # happened yet" (cold start, first batch still compiling),
            # not "the mirror is infinitely stale": nothing has been
            # served from it. Counting it latched a spurious
            # max_staleness_s fast burn on every cold start.
            if age is None or age == float("inf"):
                continue
            if worst is None or age > worst:
                worst = age
        return worst

    def built_engines(self) -> dict:
        """Engines that already exist (default network + tenant LRU),
        WITHOUT building any — the admin plane reads state, it must not
        instantiate device mirrors."""
        with self._lock:
            out: dict = {}
            if self._engine is not None:
                out[self.nid] = self._engine
            out.update(self._nid_engines)
            return out


class _HostEngineFacade:
    """Adapts ReferenceEngine to the engine surface the RPC layer uses
    (check_batch / check_is_member / check_relation_tuple / expand)."""

    def __init__(self, reference: ReferenceEngine, nid: str, metrics=None):
        self.reference = reference
        self.nid = nid
        self.stats = {"device_checks": 0, "host_checks": 0, "snapshot_builds": 0}
        self.metrics = metrics

    def check_is_member(self, r, max_depth: int = 0) -> bool:
        res = self.check_relation_tuple(r, max_depth)
        if res.error is not None:
            raise res.error
        from .engine.definitions import Membership

        return res.membership == Membership.IS_MEMBER

    def check_relation_tuple(self, r, max_depth: int = 0):
        return self.reference.check_relation_tuple(r, max_depth, self.nid)

    def check_batch(self, tuples, max_depth: int = 0):
        self.stats["host_checks"] += len(tuples)
        if self.metrics is not None and tuples:
            self.metrics.check_batch_size.observe(len(tuples))
            self.metrics.checks_total.labels("host").inc(len(tuples))
            if isinstance(tuples, CheckColumns):
                # the oracle takes tuples: the loop below builds every one
                self.metrics.check_batch_tuples_built_total.inc(len(tuples))
        return [self.check_relation_tuple(t, max_depth) for t in tuples]

    def explain_check(self, t, max_depth: int = 0, rt=None):
        """Explain on the host engine: verdict and witness come from the
        same walk family, tier is always `host` (there is no device to
        differ from, so witness_consistent is the walk agreeing with
        the pruned check — still a real differential on cyclic graphs).
        `rt` accepted for surface parity; the host walk records no
        engine stages or launch ids."""
        from .engine.explain import base_trace

        res = self.check_relation_tuple(t, max_depth)
        allowed = res.error is None and res.allowed
        wx = self.reference._complete_checker().explain_check(
            t, max_depth, self.nid
        )
        trace = base_trace(
            allowed=allowed,
            tier="host",
            version=self.reference.manager.version(nid=self.nid),
            max_depth=wx.get("max_depth"),
            witness=wx.get("witness", []) if allowed else [],
            exhaustion=None if allowed else wx.get("exhaustion"),
            witness_verdict=wx["allowed"],
            witness_consistent=(
                res.error is None and wx["allowed"] == allowed
            ),
        )
        if res.error is not None:
            trace["error"] = str(res.error)
        return res, trace

    def expand(self, subject, max_depth: int = 0):
        return self.reference.expand(subject, max_depth, self.nid)

    def list_objects(
        self, namespace, relation, subject, max_depth: int = 0,
        page_size: int = 100, page_token: str = "",
    ):
        from .engine.definitions import paginate_names

        self.stats["host_list_objects"] = (
            self.stats.get("host_list_objects", 0) + 1
        )
        return paginate_names(
            self.reference.list_objects(
                namespace, relation, subject, max_depth, self.nid
            ),
            page_size, page_token,
        )

    def list_subjects(
        self, namespace, obj, relation, max_depth: int = 0,
        page_size: int = 100, page_token: str = "",
    ):
        from .engine.definitions import paginate_names

        self.stats["host_list_subjects"] = (
            self.stats.get("host_list_subjects", 0) + 1
        )
        return paginate_names(
            self.reference.list_subjects(
                namespace, obj, relation, max_depth, self.nid
            ),
            page_size, page_token,
        )

    def invalidate(self) -> None:
        pass
