"""Serving daemon: read/write/metrics listeners with gRPC+REST port sharing.

Parity with internal/driver/daemon.go: ServeAll starts three listeners —
read (:4466), write (:4467), metrics (:4468) — and the read/write ports
serve BOTH gRPC (HTTP/2) and REST (HTTP/1.1) on the same address the way
the reference multiplexes them with cmux (daemon.go:191-276). The Python
equivalent is a tiny byte-sniffing mux: every accepted connection is
peeked for the HTTP/2 client preface ("PRI * HTTP/2.0") and spliced to an
internal loopback gRPC or REST listener accordingly. Shutdown is graceful
in the reference's order: stop accepting, drain, stop servers
(daemon.go:233-273).
"""

from __future__ import annotations

import logging
import selectors
import socket
import threading

from ..errors import KetoError
from .batcher import CheckBatcher
from .grpc_server import build_grpc_server
from .rest_server import RESTServer

logger = logging.getLogger("keto_tpu")

_H2_PREFACE = b"PRI * HTTP/2.0"


class PortMux:
    """cmux equivalent: route h2 connections to gRPC, h1 to REST.

    With `ssl_context` the mux TERMINATES TLS (serve.<kind>.tls config,
    ref: daemon.go:289-349): the preface sniff and the loopback splice
    run over the decrypted stream, so both gRPC and REST backends stay
    plaintext-internal.

    Replica mode (serve.check.workers >= 2): `grpc_addr`/`http_addr`
    accept LISTS of parallel backends — one (grpc, http) pair per serve
    worker — and each accepted connection round-robins across them (the
    lightweight FRONT MUX for platforms without SO_REUSEPORT). Where
    SO_REUSEPORT exists, the daemon instead binds one single-backend mux
    per worker on the same public port (`reuse_port=True`) and the
    kernel balances accepts — no extra splice hop."""

    def __init__(self, host: str, port: int, grpc_addr, http_addr,
                 ssl_context=None, reuse_port: bool = False):
        self.grpc_addrs = (
            list(grpc_addr) if isinstance(grpc_addr, list) else [grpc_addr]
        )
        self.http_addrs = (
            list(http_addr) if isinstance(http_addr, list) else [http_addr]
        )
        assert len(self.grpc_addrs) == len(self.http_addrs)
        import itertools

        self._rr = itertools.count()
        self.ssl_context = ssl_context
        self._listener = socket.create_server(
            (host, port), family=socket.AF_INET, backlog=128,
            reuse_port=reuse_port,
        )
        self._listener.settimeout(0.5)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._accept_loop, name=f"keto-mux-{port}", daemon=True
        )

    @property
    def port(self) -> int:
        return self._listener.getsockname()[1]

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        try:
            self._listener.close()
        except OSError:
            pass
        self._thread.join(timeout=5)

    # -- internals ------------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(
                target=self._handshake, args=(conn,), daemon=True
            ).start()

    def _handshake(self, conn: socket.socket) -> None:
        try:
            conn.settimeout(10)
            consumed = b""
            if self.ssl_context is not None:
                import ssl as _ssl

                try:
                    conn = self.ssl_context.wrap_socket(conn, server_side=True)
                except (_ssl.SSLError, OSError):
                    conn.close()
                    return
                # MSG_PEEK is not supported on TLS sockets: CONSUME the
                # preface-length prefix from the decrypted stream and
                # replay it to the chosen backend before splicing
                while len(consumed) < len(_H2_PREFACE):
                    try:
                        chunk = conn.recv(len(_H2_PREFACE) - len(consumed))
                    except socket.timeout:
                        chunk = b""
                    if not chunk:
                        break
                    consumed += chunk
                # drain decrypted bytes already buffered in the TLS layer:
                # they are invisible to selectors on the raw fd
                while conn.pending():
                    more = conn.recv(conn.pending())
                    if not more:
                        break
                    consumed += more
                head = consumed
            else:
                # Block (PEEK|WAITALL) for the full preface length: an
                # HTTP/1.1 request line is always longer, so a prefix-only
                # peek of a slow first segment (e.g. just b"P") can never
                # misroute.
                try:
                    head = conn.recv(
                        len(_H2_PREFACE), socket.MSG_PEEK | socket.MSG_WAITALL
                    )
                except socket.timeout:
                    head = b""
            if not head:
                conn.close()
                return
            # one backend PAIR per connection (round-robin): in front-mux
            # replica mode every worker owns a parallel (grpc, http) pair
            idx = next(self._rr) % len(self.grpc_addrs)
            backend_addr = (
                self.grpc_addrs[idx]
                if head.startswith(_H2_PREFACE) else self.http_addrs[idx]
            )
            backend = socket.create_connection(backend_addr)
            if consumed:
                backend.sendall(consumed)
            # TLS sockets keep a recv timeout in the splice: a partial TLS
            # record makes the raw fd selectable while SSLSocket.recv
            # blocks for the rest of the record — a stalled client must
            # not freeze the pump thread forever
            conn.settimeout(60 if self.ssl_context is not None else None)
            self._splice(conn, backend)
        except OSError:
            try:
                conn.close()
            except OSError:
                pass

    @staticmethod
    def _splice(a: socket.socket, b: socket.socket) -> None:
        """Bidirectional byte pump until either side closes."""
        sel = selectors.DefaultSelector()
        sel.register(a, selectors.EVENT_READ, b)
        sel.register(b, selectors.EVENT_READ, a)
        try:
            open_sides = 2
            while open_sides:
                for key, _ in sel.select(timeout=60):
                    src, dst = key.fileobj, key.data
                    try:
                        data = src.recv(65536)
                        # TLS sockets buffer whole decrypted records; bytes
                        # in that buffer never wake the selector, so drain
                        # pending() before waiting again
                        pending = getattr(src, "pending", None)
                        while pending is not None and pending():
                            more = src.recv(65536)
                            if not more:
                                break
                            data += more
                    except socket.timeout:
                        continue  # partial TLS record: not a close
                    except OSError:
                        data = b""
                    if not data:
                        sel.unregister(src)
                        open_sides -= 1
                        try:
                            dst.shutdown(socket.SHUT_WR)
                        except OSError:
                            pass
                        continue
                    try:
                        # the recv timeout must not govern sends: a slow
                        # but alive client with a full receive window is
                        # not a dead peer — clear it for the write
                        prev = dst.gettimeout()
                        if prev:
                            dst.settimeout(None)
                        try:
                            dst.sendall(data)
                        finally:
                            if prev:
                                dst.settimeout(prev)
                    except OSError:
                        return
        finally:
            sel.close()
            for s in (a, b):
                try:
                    s.close()
                except OSError:
                    pass


class Daemon:
    """ServeAll: compose batcher + 2 gRPC servers + 3 REST routers + muxes.
    ref: daemon.go:87-126 (errgroup of three listeners)."""

    def __init__(self, registry, host: str | None = None,
                 pid_file: str | None = None):
        self.registry = registry
        # optional pid file (supervisors/smokes): written by start(),
        # REMOVED by stop() — a stale pid file outliving a clean
        # shutdown is a lie a later supervisor can act on (kill -0
        # succeeding against a recycled pid)
        self.pid_file = pid_file
        cfg = registry.config
        # fail-fast store probe BEFORE any listener or batcher exists:
        # an unreachable/misconfigured DSN (bad path, unknown scheme,
        # absent network driver, locked/corrupt file) exits `keto-tpu
        # serve` with ONE typed line instead of a raw stack trace from
        # the middle of listener startup (the CLI prints KetoError
        # messages and returns non-zero)
        try:
            registry.relation_tuple_manager().version(nid=registry.nid)
        except KetoError:
            raise  # already typed (dialect/StoreUnavailable family)
        except Exception as e:
            from ..config import ConfigError

            raise ConfigError(
                f"store DSN {cfg.dsn!r} failed its startup probe: "
                f"{type(e).__name__}: {e}"
            ) from e
        self.read_addr = cfg.read_api_address()
        self.write_addr = cfg.write_api_address()
        self.metrics_addr = cfg.metrics_api_address()
        if host is not None:
            self.read_addr.host = self.write_addr.host = self.metrics_addr.host = host
        self.n_workers = max(int(cfg.get("serve.check.workers", 1)), 1)
        if self.n_workers > 1:
            # replica serving group (api/replica.py): N full serve stacks
            # over ONE device engine; each worker owns a batcher + cache
            # + replica view, and the Retry-After drain estimate scales
            # to group-wide pending across N parallel drains
            from .replica import ReplicaGroup

            self._group = ReplicaGroup(
                registry, self.n_workers,
                make_batcher=lambda group: self._make_batcher(
                    pending_total=group.group_pending,
                    drain_ways=self.n_workers,
                ),
                make_cache=self._make_worker_cache,
            )
            registry.replica_group = self._group
            # compat alias: tools/tests address `daemon.batcher`; worker
            # 0's is the group's first among equals
            self.batcher = self._group.workers[0].batcher
        else:
            self._group = None
            self.batcher = self._make_batcher()
        self._grpc_read = None
        self._grpc_write = None
        self.read_grpc_port = None
        self.write_grpc_port = None
        self._rest = {}
        self._muxes = {}
        self._worker_grpc: list = []
        self._worker_rest: list = []
        self._follower_plane = None
        self._started = False

    def _make_batcher(self, pending_total=None, drain_ways: int = 1):
        # pipeline depth bounds launched-but-unresolved device batches
        # (in-flight cap = 2x depth); raise it where the launch-to-
        # readback latency dwarfs per-batch compute
        registry = self.registry
        cfg = registry.config
        return CheckBatcher(
            registry.check_engine(),
            engine_resolver=registry.check_engine,
            pipeline_depth=int(cfg.get("check.pipeline_depth", 2)),
            window_s=float(cfg.get("check.batch_window_ms", 2.0)) / 1e3,
            metrics=registry.metrics(),
            tracer=registry.tracer(),
            max_inflight=cfg.get("serve.check.max_inflight"),
            # resilience plane: bounded admission, launch watchdog, and
            # the process-wide device-path breaker (shared with the aio
            # plane so device health is judged from all traffic)
            max_queue=cfg.get("serve.check.max_queue"),
            device_timeout_ms=cfg.get("serve.check.device_timeout_ms"),
            breaker=registry.circuit_breaker(),
            flightrec=registry.flight_recorder(),
            pending_total=pending_total,
            drain_ways=drain_ways,
        )

    def _make_worker_cache(self):
        """One replica-LOCAL check cache per serve worker (None when
        check.cache.enabled is false). Invalidation rides the worker's
        own changelog tail (ReplicaView) instead of the registry
        singleton's commit hook; the version gate carries correctness
        either way."""
        registry = self.registry
        cfg = registry.config
        if not bool(cfg.get("check.cache.enabled", True)):
            return None
        from .check_cache import CheckCache

        return CheckCache(
            registry.relation_tuple_manager(),
            cfg,
            max_entries=int(cfg.get("check.cache.max_entries", 65536)),
            ttl_s=float(cfg.get("check.cache.ttl_s", 0.0)),
            metrics=registry.metrics(),
        )

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> None:
        reg = self.registry
        # operator logging contract (log.level / log.format) applies
        # before the first listener can emit a line
        from ..observability import configure_logging

        configure_logging(reg.config)
        # workload observatory folder: with a daemon serving traffic,
        # event folding moves off the request threads onto this ticker
        # (observability_workload.WorkloadObservatory.start_folder)
        reg.workload_observatory().start_folder()
        # internal loopback backends (ephemeral ports)
        self._grpc_write = build_grpc_server(reg, write=True)
        grpc_write_port = self._grpc_write.add_insecure_port("127.0.0.1:0")
        self.write_grpc_port = self._add_direct_grpc("write", self._grpc_write)
        self._grpc_write.start()
        cfg = cfg0 = reg.config
        if self._group is not None:
            self._start_replica_read_plane()
        else:
            self._grpc_read = build_grpc_server(
                reg, write=False, batcher=self.batcher
            )
            grpc_read_port = self._grpc_read.add_insecure_port("127.0.0.1:0")
            # optional DIRECT public gRPC listeners (serve.<kind>.grpc):
            # gRPC traffic skips the mux's preface sniff + two-socket
            # byte splice — on a 1-core host the splice alone costs ~1/3
            # of the serve ceiling. The muxed port stays for reference
            # wire parity (one port, both protocols); this is the
            # high-throughput side door.
            if cfg0.get("serve.read.grpc") and cfg0.get("serve.read.grpc.aio"):
                # asyncio read plane for the direct listener: all RPCs
                # run as coroutines on one loop thread — no per-request
                # cross-thread handoff (api/aio_server.py); the muxed
                # port stays threaded for wire parity
                from .aio_server import AioReadServer

                g = cfg0.get("serve.read.grpc")
                self._aio_read = AioReadServer(
                    reg, g.get("host", "127.0.0.1"), int(g.get("port", 0)),
                    pipeline_depth=int(cfg0.get("check.pipeline_depth", 2)),
                    window_s=float(cfg0.get("check.batch_window_ms", 2.0)) / 1e3,
                )
                self.read_grpc_port = self._aio_read.start()
            else:
                self._aio_read = None
                self.read_grpc_port = self._add_direct_grpc(
                    "read", self._grpc_read
                )
            self._grpc_read.start()
            self._rest["read"] = RESTServer(
                reg, "read", "127.0.0.1", 0, batcher=self.batcher,
                cors=cfg.get("serve.read.cors"),
            )
            self._rest["read"].start()
            self._muxes["read"] = PortMux(
                self.read_addr.host,
                self.read_addr.port,
                ("127.0.0.1", grpc_read_port),
                ("127.0.0.1", self._rest["read"].port),
                ssl_context=self._tls_context("read"),
            )
        self._rest["write"] = RESTServer(
            reg, "write", "127.0.0.1", 0, cors=cfg.get("serve.write.cors")
        )
        self._rest["write"].start()
        self._muxes["write"] = PortMux(
            self.write_addr.host,
            self.write_addr.port,
            ("127.0.0.1", grpc_write_port),
            ("127.0.0.1", self._rest["write"].port),
            ssl_context=self._tls_context("write"),
        )
        # metrics is plain HTTP, no mux needed (daemon.go:152-189)
        self._rest["metrics"] = RESTServer(
            reg, "metrics", self.metrics_addr.host, self.metrics_addr.port
        )
        self._rest["metrics"].start()
        for m in self._muxes.values():
            m.start()
        # changelog streaming hub: built now (not lazily at first watcher)
        # so the store write hooks and engine push-invalidation are live
        # from the first request
        reg.watch_hub()
        # anti-entropy mirror scrubber (engine/scrub.py): background
        # device-vs-host checksum loop; start() is a no-op unless
        # scrub.enabled (POST /admin/scrub triggers a pass either way)
        reg.mirror_scrubber().start()
        # Leopard closure maintenance plane (keto_tpu/closure): the
        # changelog tailer that keeps the deep-check index fresh;
        # version-gating at submit keeps answers correct without it
        if bool(cfg.get("closure.enabled", False)):
            reg.closure_maintainer().start()
        # HA follower plane (api/follower.py): restore the follower
        # checkpoint, then tail the LEADER's watch changelog into the
        # network-fed store. Started after the hub (apply_remote's
        # write hooks must fan out to local subscribers) and before
        # readiness flips — a follower is "ready" as soon as it can
        # answer at SOME version; the snaptoken gate refuses anything
        # it has not reached yet
        if bool(cfg.get("follower.enabled", False)):
            from .follower import FollowerPlane

            self._follower_plane = FollowerPlane(reg)
            reg.ha_plane = self._follower_plane
            self._follower_plane.start()
        if self.pid_file:
            import os as _os

            with open(self.pid_file, "w") as f:
                f.write(str(_os.getpid()))
        self._log_recovery_state()
        reg.draining.clear()
        reg.ready.set()
        self._started = True
        logger.info(
            "serving read=%s:%d write=%s:%d metrics=%s:%d",
            self.read_addr.host, self.read_port,
            self.write_addr.host, self.write_port,
            self.metrics_addr.host, self.metrics_port,
        )

    def _log_recovery_state(self) -> None:
        """Cold-start recovery audit: ONE structured line pinning the
        version-consistency facts a post-crash start depends on — the
        durable store version and what the persisted mirror checkpoint
        (if any) can contribute. A torn/stale checkpoint is reported as
        the rebuild it will cause, never an error: the store is the
        truth, the checkpoint is a warm-restart optimization."""
        reg = self.registry
        try:
            store_version = reg.relation_tuple_manager().version(nid=reg.nid)
        except Exception:  # noqa: BLE001 — an audit line must not fail start
            logger.warning("recovery audit: store version unreadable",
                           exc_info=True)
            return
        checkpoint = "none"
        cache_dir = reg.config.get("check.mirror_cache")
        if cache_dir:
            from ..engine.checkpoint import checkpoint_info, mirror_cache_path

            info = checkpoint_info(mirror_cache_path(cache_dir, reg.nid))
            if info is None:
                checkpoint = "none"
            elif not info.get("loadable"):
                checkpoint = "torn/incompatible (will rebuild from store)"
            else:
                checkpoint = (
                    f"loadable n_tuples={info.get('n_tuples')} "
                    f"(trusted only if it matches store v{store_version} "
                    "+ config fingerprint)"
                )
        logger.info(
            "cold-start recovery: nid=%s store=v%d mirror_checkpoint=%s",
            reg.nid, store_version, checkpoint,
        )

    def _start_replica_read_plane(self) -> None:
        """Replica mode (serve.check.workers >= 2): one full read stack
        PER WORKER — its own gRPC server, REST listener, and public mux
        accept loop — all sharing the one device engine through the
        batchers' existing submit path.

        Listener strategy: where the platform supports SO_REUSEPORT
        (Linux), every worker binds its own socket on the SAME public
        read port and the kernel balances accepted connections across
        them; the direct gRPC listeners share their port the same way
        (grpc.so_reuseport). Platforms without it get ONE front mux
        whose accept loop round-robins connections across the workers'
        loopback backends."""
        reg = self.registry
        cfg = reg.config
        group = self._group
        tls = self._tls_context("read")
        reuseport = hasattr(socket, "SO_REUSEPORT")
        g = cfg.get("serve.read.grpc")
        aio = bool(g and cfg.get("serve.read.grpc.aio"))
        backends: list[tuple] = []  # (grpc_addr, http_addr) per worker
        direct_port: int | None = None
        for w in group.workers:
            server = build_grpc_server(
                reg, write=False, batcher=w.batcher, worker=w,
                so_reuseport=reuseport,
            )
            loop_port = server.add_insecure_port("127.0.0.1:0")
            if g and not aio:
                # direct public read-gRPC: worker 0 binds the configured
                # port (resolving 0 to an ephemeral one), the rest join
                # it via SO_REUSEPORT — or bind their own ephemeral port
                # where the platform lacks it (recorded per worker)
                if direct_port is None:
                    want = int(g.get("port", 0))
                elif reuseport:
                    want = direct_port
                else:
                    want = 0  # no SO_REUSEPORT: own ephemeral port
                addr = f"{g.get('host', '127.0.0.1')}:{want}"
                bound = server.add_insecure_port(addr)
                if direct_port is None:
                    direct_port = bound
                w.ports["grpc_direct"] = bound
            server.start()
            rest = RESTServer(
                reg, "read", "127.0.0.1", 0, batcher=w.batcher,
                cors=cfg.get("serve.read.cors"), worker=w,
            )
            rest.start()
            self._worker_grpc.append(server)
            self._worker_rest.append(rest)
            w.ports["grpc_loopback"] = loop_port
            w.ports["rest"] = rest.port
            backends.append(
                (("127.0.0.1", loop_port), ("127.0.0.1", rest.port))
            )
        if aio:
            # the no-handoff asyncio listener stays single (one loop
            # thread): worker 0 owns it; routing consistency applies,
            # hedging rides the threaded plane (api/replica.py)
            from .aio_server import AioReadServer

            self._aio_read = AioReadServer(
                reg, g.get("host", "127.0.0.1"), int(g.get("port", 0)),
                pipeline_depth=int(cfg.get("check.pipeline_depth", 2)),
                window_s=float(cfg.get("check.batch_window_ms", 2.0)) / 1e3,
                worker=group.workers[0],
            )
            self.read_grpc_port = self._aio_read.start()
        else:
            self._aio_read = None
            self.read_grpc_port = direct_port
        if reuseport:
            first = PortMux(
                self.read_addr.host, self.read_addr.port,
                backends[0][0], backends[0][1],
                ssl_context=tls, reuse_port=True,
            )
            self._muxes["read"] = first
            for i, (ga, ha) in enumerate(backends[1:], start=1):
                self._muxes[f"read_w{i}"] = PortMux(
                    self.read_addr.host, first.port, ga, ha,
                    ssl_context=tls, reuse_port=True,
                )
        else:
            self._muxes["read"] = PortMux(
                self.read_addr.host, self.read_addr.port,
                [b[0] for b in backends], [b[1] for b in backends],
                ssl_context=tls,
            )
        for i, w in enumerate(group.workers):
            w.ports["mux"] = self._muxes[
                "read" if (i == 0 or not reuseport) else f"read_w{i}"
            ].port

    def _add_direct_grpc(self, kind: str, server) -> int | None:
        """Bind `server` on serve.<kind>.grpc as a second, unmuxed public
        port. Returns the bound port or None when unconfigured. A
        listener with serve.<kind>.tls binds with the same cert — the
        side door must never downgrade a TLS deployment to plaintext."""
        g = self.registry.config.get(f"serve.{kind}.grpc")
        if not g:
            return None
        addr = f"{g.get('host', '127.0.0.1')}:{g.get('port', 0)}"
        tls = self.registry.config.get(f"serve.{kind}.tls")
        if tls and tls.get("cert_path"):
            import grpc

            with open(tls["cert_path"], "rb") as f:
                cert = f.read()
            with open(tls["key_path"], "rb") as f:
                key = f.read()
            creds = grpc.ssl_server_credentials(((key, cert),))
            return server.add_secure_port(addr, creds)
        return server.add_insecure_port(addr)

    def _tls_context(self, kind: str):
        """ssl.SSLContext from serve.<kind>.tls {cert_path, key_path},
        None when unconfigured (ref: daemon.go TLS listener options)."""
        tls = self.registry.config.get(f"serve.{kind}.tls")
        if not tls or not tls.get("cert_path"):
            return None
        import ssl

        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.set_alpn_protocols(["h2", "http/1.1"])
        ctx.load_cert_chain(tls["cert_path"], tls.get("key_path"))
        return ctx

    @property
    def read_port(self) -> int:
        return self._muxes["read"].port

    @property
    def write_port(self) -> int:
        return self._muxes["write"].port

    @property
    def metrics_port(self) -> int:
        return self._rest["metrics"].port

    def stop(self, grace: float = 5.0) -> None:
        """Graceful drain (ref: daemon.go:233-273 ordering, plus an
        explicit admission grace window): readiness flips first, then
        new check admissions are shed with a typed OverloadedError while
        in-flight checks complete — only then do the listeners close, so
        a request admitted before the drain never sees a torn-down
        pipeline."""
        import time as _time

        self.registry.ready.clear()
        # admission gate: resilience.admit_check sheds new checks with a
        # typed 429 the moment this flips — readiness is already off, so
        # balancers stop routing while stragglers get a clear signal
        self.registry.draining.set()
        # grace window: let admitted-but-unresolved checks finish (the
        # GROUP's pending count reaches zero — every worker's batcher)
        # before closing listeners
        deadline = _time.monotonic() + grace
        idle = self._group.idle if self._group is not None else self.batcher.idle
        while _time.monotonic() < deadline and not idle():
            _time.sleep(0.02)
        # end watch streams first so draining servers aren't pinned by
        # parked subscriber threads (this also ends the replica views'
        # changelog tails — the hub closes their subscriptions)
        # stop the follower replication tail BEFORE the hub: its
        # apply_remote commits fan out through hub write hooks, and the
        # shutdown checkpoint must capture a store nobody is advancing
        if self._follower_plane is not None:
            self._follower_plane.stop()
        # stop the closure maintainer BEFORE the hub: its subscriptions
        # close with it, so the hub's stop never waits on a tailer that
        # is mid-pass against a store about to be torn down
        if self.registry._closure_maintainer is not None:
            self.registry._closure_maintainer.stop()
        if self.registry._watch_hub is not None:
            self.registry._watch_hub.stop()
        if self.registry._scrubber is not None:
            self.registry._scrubber.stop()
        for m in self._muxes.values():
            m.stop()
        if getattr(self, "_aio_read", None) is not None:
            self._aio_read.stop(grace)
        if self._grpc_read is not None:
            self._grpc_read.stop(grace).wait(grace)
        for s in self._worker_grpc:
            s.stop(grace).wait(grace)
        if self._grpc_write is not None:
            self._grpc_write.stop(grace).wait(grace)
        for s in self._rest.values():
            s.stop()
        for s in self._worker_rest:
            s.stop()
        if self._group is not None:
            for w in self._group.workers:
                w.batcher.close()
            # replica views + per-worker cache invalidation threads
            self._group.close()
        else:
            self.batcher.close()
        # end the check cache's invalidation thread (daemon thread, but
        # a clean stop keeps test teardowns quiet)
        self.registry.close_check_cache()
        # stop the workload folder with a final drain: the last served
        # requests' accounting lands before the process reports stopped
        self.registry.workload_observatory().stop_folder()
        # flush + stop the OTLP span exporter: the drain's own spans are
        # the last ones worth having at the collector (a bounded flush —
        # a dead collector costs at most its POST timeout, never a hang)
        if self.registry._span_exporter is not None:
            self.registry._span_exporter.close()
        # persist any pending device-mirror checkpoints (default network
        # AND all tenant engines) before exiting so the next start
        # warm-restarts from the latest compaction
        self.registry.flush_checkpoints()
        # clean shutdown removes the pid file LAST: while any part of
        # the daemon is still draining, the pid is still meaningfully
        # alive to a supervisor. Remove only if WE still own it — a
        # supervisor may have restarted a replacement daemon onto the
        # same path while this one drained, and deleting the
        # replacement's file would recreate the exact lie this feature
        # exists to prevent.
        if self.pid_file:
            import contextlib
            import os as _os

            with contextlib.suppress(OSError, ValueError):
                with open(self.pid_file) as f:
                    owner = int(f.read().strip() or 0)
                if owner == _os.getpid():
                    _os.unlink(self.pid_file)

    def serve_forever(self) -> None:
        """Blocks until SIGINT/SIGTERM (ref: daemon.go:93-117 graceful)."""
        import signal

        stop_event = threading.Event()

        def _on_signal(signum, frame):
            logger.info("received signal %d, shutting down", signum)
            stop_event.set()

        signal.signal(signal.SIGINT, _on_signal)
        signal.signal(signal.SIGTERM, _on_signal)
        if not self._started:
            self.start()
        stop_event.wait()
        self.stop()
