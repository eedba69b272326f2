"""asyncio read-path gRPC server: the no-handoff serving plane.

Measured on the 1-core bench host in round 4: the threaded
serving stack's structural ceiling is ~68% of the raw gRPC echo
ceiling, because every request pays a cross-thread handoff — the gRPC
worker thread enqueues, a collector thread batches, a pool thread
resolves, and a per-request Future wakes the worker back up. The
reference doesn't have this problem (goroutines are cheap and its
checkgroup fans out per request, internal/check/checkgroup); a Python
batching server on one core needs the asyncio shape instead:

  - grpc.aio serves every RPC as a coroutine on ONE loop thread —
    request parsing, batch assembly, and result fan-out all happen
    in-loop with no thread wakeups
  - only the device work (check_batch_submit / _resolve — blocking jax
    dispatch + readback) runs in a small thread executor, bounded by
    the same in-flight semaphore discipline as the sync batcher (every
    in-flight handle holds device buffers and a full engine state)
  - asyncio futures resolve in-loop: one callback per request instead
    of one lock/notify/context-switch per request

The sync daemon (api/daemon.py) remains the composition root and the
wire-parity muxed listener; this server backs the DIRECT read-gRPC
listener when `serve.read.grpc.aio` is true. Handlers delegate to the
same `_Services` request/response logic (grpc_server.py) so both
planes share one behavior surface.
"""

from __future__ import annotations

import asyncio
import contextvars
import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import grpc
import grpc.aio

from .batcher import (
    _LaunchGuard,
    classify_engine_error,
    coalesce_pending,
    host_check_batch,
    note_queue_wait,
    resolve_max_inflight,
    submit_takes_telemetry,
)
from .descriptors import CHECK_SERVICE, pb
from .grpc_server import _grpc_code, _Services
from ..errors import (
    BatcherClosedError,
    DeadlineExceededError,
    KetoError,
    OverloadedError,
)
from ..observability import (
    current_request_trace,
    reset_request_trace,
    set_request_trace,
)


class AioCheckBatcher:
    """Event-loop-native micro-batcher: same contract as api/batcher.py
    (coalesce concurrent checks into device batches, bounded in-flight
    split-phase dispatch) with zero cross-thread handoffs on the
    request path."""

    def __init__(
        self,
        engine_resolver,
        max_batch: int = 1024,
        window_s: float = 0.002,
        pipeline_depth: int = 4,
        metrics=None,
        tracer=None,
        max_inflight: int | None = None,
        max_queue: int | None = None,
        device_timeout_ms: float | None = None,
        breaker=None,
        flightrec=None,
    ):
        self._resolve_engine = engine_resolver
        self.max_batch = max_batch
        self.window_s = window_s
        self._queue: asyncio.Queue = asyncio.Queue()
        # device dispatch is blocking (jax launch + readback): a small
        # executor keeps it off the loop; in-flight launches are bounded
        # (wedge discipline, see api/batcher.py; config:
        # serve.check.max_inflight)
        self._executor = ThreadPoolExecutor(
            max_workers=max(pipeline_depth, 2),
            thread_name_prefix="keto-aio-dispatch",
        )
        # degraded-serving executor: host-oracle evaluation never shares
        # threads with device submit/resolve — a wedged device blocks
        # dispatch workers unrecoverably, and degraded serving queued
        # behind them would never run (same split as the threaded
        # batcher's _host_pool). Threads spawn on first use.
        self._host_executor = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="keto-aio-hostserve"
        )
        self.max_inflight = resolve_max_inflight(max_inflight, pipeline_depth)
        self._inflight = asyncio.Semaphore(self.max_inflight)
        self._collector: asyncio.Task | None = None
        self._closed = False
        # admission bound + device-path resilience (same contract as the
        # threaded batcher: serve.check.{max_queue,device_timeout_ms},
        # shared breaker so device health is judged from all traffic).
        # The pending counter needs no lock — admission and completion
        # both run on the loop thread.
        self.max_queue = int(max_queue) if max_queue else 0
        self._pending = 0
        self.device_timeout_s = (
            float(device_timeout_ms) / 1e3 if device_timeout_ms else None
        )
        self.breaker = breaker
        # flight recorder (shared process-wide ring; see api/batcher.py)
        self.flightrec = flightrec
        # observability: queue-wait attribution + gauges, mirroring the
        # threaded batcher (api/batcher.py); own plane label — both
        # batchers can serve at once
        self.metrics = metrics
        self.tracer = tracer
        self._depth_gauge = (
            metrics.batcher_queue_depth.labels("aio")
            if metrics is not None else None
        )
        if metrics is not None:
            metrics.batcher_queue_limit.labels("aio").set(self.max_queue)
        self._submit_takes_telemetry: dict[type, bool] = {}

    def start(self) -> None:
        self._collector = asyncio.get_running_loop().create_task(self._run())

    async def close(self) -> None:
        self._closed = True
        if self._collector is not None:
            await self._queue.put(None)
            await self._collector
        self._executor.shutdown(wait=True)
        self._host_executor.shutdown(wait=True)

    def _queue_delay_estimate_s(self, pending: int) -> float:
        batches = pending // max(self.max_batch, 1) + 1
        return max(batches * max(self.window_s, 0.001), 0.05)

    def admit(self, deadline=None) -> None:
        """Queue-delay-aware admission gate, the aio twin of
        CheckBatcher.admit. Runs in-loop, so the pending count it reads
        is exact — no racer can push past max_queue."""
        if self._closed:
            raise OverloadedError("check batcher is closed", retry_after_s=1.0)
        if self.max_queue and self._pending >= self.max_queue:
            if self.metrics is not None:
                self.metrics.requests_shed_total.labels("queue_full").inc()
            raise OverloadedError(
                "check queue is full",
                retry_after_s=self._queue_delay_estimate_s(self._pending),
            )
        if deadline is not None and deadline.expired():
            if self.metrics is not None:
                self.metrics.deadline_exceeded_total.labels("admission").inc()
            raise DeadlineExceededError(
                "request deadline expired before admission"
            )

    def idle(self) -> bool:
        return self._pending == 0

    def _dec_pending(self, _f=None) -> None:
        self._pending -= 1

    async def check(self, tuple, max_depth: int = 0, nid=None, rt=None):
        res, _ = await self.check_versioned(tuple, max_depth, nid=nid, rt=rt)
        return res

    async def check_versioned(self, tuple, max_depth: int = 0, nid=None, rt=None):
        """(CheckResult, version | None) — same contract as the threaded
        CheckBatcher.check_versioned (the check cache's store input);
        `rt.deadline` bounds the wait with the typed 504."""
        if self._closed:
            # typed drain shed + embedder `except RuntimeError` compat
            # (same dual contract as the threaded plane)
            raise BatcherClosedError(retry_after_s=1.0)
        if self.max_queue and self._pending >= self.max_queue:
            # enqueue-time bound (exact: this coroutine runs in-loop)
            if self.metrics is not None:
                self.metrics.requests_shed_total.labels("queue_full").inc()
            raise OverloadedError(
                "check queue is full",
                retry_after_s=self._queue_delay_estimate_s(self._pending),
            )
        self._pending += 1
        fut = asyncio.get_running_loop().create_future()
        fut.add_done_callback(self._dec_pending)
        self._queue.put_nowait(
            (tuple, max_depth, nid, fut, rt, time.perf_counter())
        )
        if self._depth_gauge is not None:
            self._depth_gauge.set(self._queue.qsize())
        deadline = rt.deadline if rt is not None else None
        if deadline is None:
            return await fut
        try:
            return await asyncio.wait_for(
                fut, timeout=max(deadline.remaining_s(), 1e-4)
            )
        except asyncio.TimeoutError:
            if self.metrics is not None:
                self.metrics.deadline_exceeded_total.labels("wait").inc()
            raise DeadlineExceededError(
                "request deadline expired waiting for the check batch"
            )

    async def _drain(self, first) -> list:
        batch = [first]
        loop = asyncio.get_running_loop()
        end = loop.time() + self.window_s
        while len(batch) < self.max_batch:
            timeout = end - loop.time()
            if timeout <= 0:
                try:
                    item = self._queue.get_nowait()
                except asyncio.QueueEmpty:
                    break
            else:
                try:
                    item = await asyncio.wait_for(self._queue.get(), timeout)
                except asyncio.TimeoutError:
                    break
            if item is None:
                await self._queue.put(None)
                break
            batch.append(item)
        return batch

    def _submit_fn(self, engine, submit, slots, depth):
        """Bind the submit call for the coalesced slots, passing
        per-request telemetry when the engine's signature takes it
        (stubbed engines keep working; detection shared with the
        threaded batcher). Each slot's leader carries the telemetry."""
        tuples = [s[0][0] for s in slots]
        if submit_takes_telemetry(
            self._submit_takes_telemetry, engine, submit
        ):
            return functools.partial(
                submit, tuples, depth, telemetry=[s[0][4] for s in slots]
            )
        return functools.partial(submit, tuples, depth)

    def _expire(self, group: list) -> list:
        """Drop riders whose deadline expired while queued (the typed
        504, no batch slot occupied) — the aio twin of
        CheckBatcher._expire."""
        live = []
        for p in group:
            dl = p[4].deadline if p[4] is not None else None
            if dl is not None and dl.expired():
                if not p[3].done():
                    # a done (cancelled) future means the caller's
                    # wait_for already counted this expiry as "wait"
                    if self.metrics is not None:
                        self.metrics.deadline_exceeded_total.labels(
                            "queue"
                        ).inc()
                    p[3].set_exception(DeadlineExceededError(
                        "request deadline expired in the check queue"
                    ))
            else:
                live.append(p)
        return live

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            item = await self._queue.get()
            if item is None:
                return
            batch = await self._drain(item)
            by_key: dict = {}
            for p in batch:
                by_key.setdefault((p[1], p[2]), []).append(p)
            for (depth, nid), group in by_key.items():
                note_queue_wait(
                    ((p[4], p[5]) for p in group), self._queue.qsize(),
                    self.metrics, self.tracer, self._depth_gauge,
                )
                group = self._expire(group)
                if not group:
                    continue
                # singleflight: identical pendings share one batch slot
                # (shared with the threaded batcher)
                slots = coalesce_pending(
                    group, lambda p: p[0], self.metrics
                )
                # breaker routing in the collector (same reasoning as the
                # threaded plane: a stalled device submit must not block
                # degraded host serving); each group becomes ONE task so
                # the collector keeps draining either way
                if self.breaker is not None and not self.breaker.allow():
                    loop.create_task(self._host_serve(slots, depth, nid))
                else:
                    loop.create_task(self._device_serve(slots, depth, nid))

    def _release_inflight(self) -> None:
        self._inflight.release()
        if self.metrics is not None:
            self.metrics.inflight_launches.dec()

    def _record_device_failure(self, cause: str, err=None) -> None:
        from ..errors import StoreUnavailableError

        if isinstance(err, StoreUnavailableError):
            # a STORE outage is not device-health evidence (same rule
            # as the threaded batcher): the store breaker owns it
            if self.metrics is not None:
                self.metrics.check_batch_failed_total.labels("store").inc()
            return
        if self.breaker is not None:
            self.breaker.record_failure()
        if self.metrics is not None:
            self.metrics.check_batch_failed_total.labels(cause).inc()
        if self.flightrec is not None:
            # auto-dump on batch failure / watchdog abandon (same
            # contract as the threaded batcher)
            self.flightrec.dump(cause)

    @staticmethod
    def _fail_slots(slots, err) -> None:
        for slot in slots:
            for p in slot:
                if not p[3].done():
                    p[3].set_exception(err)

    async def _host_fallback(self, engine, slots, depth) -> None:
        """Exact-host-oracle answers for the riders after a device-path
        failure or while the breaker is open (graceful degradation:
        correct answers, host_fallback-stage latency)."""
        loop = asyncio.get_running_loop()
        t0 = time.perf_counter()
        try:
            results = await loop.run_in_executor(
                self._host_executor, host_check_batch, engine,
                [s[0][0] for s in slots], depth,
            )
        except Exception as e:
            self._fail_slots(
                slots, classify_engine_error(e, self.metrics, "host")
            )
            return
        dur = time.perf_counter() - t0
        if self.metrics is not None:
            self.metrics.observe_stage("host_fallback", dur)
        for slot, res in zip(slots, results):
            for p in slot:
                if p[4] is not None:
                    p[4].add_stage("host_fallback", dur)
                if not p[3].done():
                    p[3].set_result((res, None))

    async def _host_serve(self, slots, depth, nid) -> None:
        try:
            engine = self._resolve_engine(nid)
        except Exception as e:
            self._fail_slots(
                slots, classify_engine_error(e, self.metrics, "engine")
            )
            return
        await self._host_fallback(engine, slots, depth)

    def _watchdog_fire(self, guard, engine, slots, depth) -> None:
        """loop.call_later callback (runs in-loop): abandon a launch that
        outlived serve.check.device_timeout_ms — release its in-flight
        slot, trip the breaker, host-serve the riders."""
        if not guard.claim():
            return
        self._release_inflight()
        self._record_device_failure("device_timeout")
        asyncio.get_running_loop().create_task(
            self._host_fallback(engine, slots, depth)
        )

    async def _device_serve(self, slots, depth, nid) -> None:
        loop = asyncio.get_running_loop()
        try:
            engine = self._resolve_engine(nid)
        except Exception as e:
            self._fail_slots(
                slots, classify_engine_error(e, self.metrics, "engine")
            )
            return
        await self._inflight.acquire()
        if self.metrics is not None:
            self.metrics.inflight_launches.inc()
        # the semaphore wait can outlive every rider's budget: re-check
        # the deadline boundary so a fully-expired batch never launches
        live = self._expire([p for slot in slots for p in slot])
        if not live:
            self._release_inflight()
            return
        if len(live) != sum(len(s) for s in slots):
            slots = coalesce_pending(live, lambda p: p[0], None)
        submit = getattr(engine, "check_batch_submit", None)
        if submit is None:
            # host-engine fallback: no split-phase surface — evaluate the
            # whole batch on the executor (same contract as the threaded
            # batcher's _evaluate); releases the in-flight slot itself
            await self._evaluate(engine, slots, depth)
            return
        guard = _LaunchGuard()
        watchdog = (
            loop.call_later(
                self.device_timeout_s, self._watchdog_fire,
                guard, engine, slots, depth,
            )
            if self.device_timeout_s else None
        )
        try:
            handle = await loop.run_in_executor(
                self._executor,
                self._submit_fn(engine, submit, slots, depth),
            )
        except Exception as e:
            if guard.claim():
                if watchdog is not None:
                    watchdog.cancel()
                self._release_inflight()
                self._record_device_failure("device", err=e)
                await self._host_fallback(engine, slots, depth)
            return
        await self._finish(engine, handle, slots, depth, guard, watchdog)

    async def _evaluate(self, engine, slots, depth) -> None:
        loop = asyncio.get_running_loop()
        try:
            results = await loop.run_in_executor(
                self._executor,
                engine.check_batch,
                [s[0][0] for s in slots],
                depth,
            )
        except Exception as e:
            self._fail_slots(
                slots, classify_engine_error(e, self.metrics, "engine")
            )
            return
        finally:
            self._release_inflight()
        for slot, res in zip(slots, results):
            for p in slot:
                if not p[3].done():
                    p[3].set_result((res, None))

    async def _finish(
        self, engine, handle, slots, depth, guard=None, watchdog=None
    ) -> None:
        loop = asyncio.get_running_loop()
        if guard is not None and guard.peek():
            return  # the watchdog already abandoned this launch
        try:
            # version plumb-through (check_batch_resolve_v): pins each
            # answer to its evaluated state's covered store version —
            # the check cache's store contract
            resolve_v = getattr(engine, "check_batch_resolve_v", None)
            if resolve_v is not None:
                results, versions = await loop.run_in_executor(
                    self._executor, resolve_v, handle
                )
            else:
                results = await loop.run_in_executor(
                    self._executor, engine.check_batch_resolve, handle
                )
                versions = [None] * len(results)
        except Exception as e:
            if guard is None or guard.claim():
                if watchdog is not None:
                    watchdog.cancel()
                self._release_inflight()
                self._record_device_failure("device", err=e)
                await self._host_fallback(engine, slots, depth)
            return
        if guard is not None and not guard.claim():
            return  # the watchdog won the race mid-resolve
        if watchdog is not None:
            watchdog.cancel()
        self._release_inflight()
        if self.breaker is not None:
            self.breaker.record_success()
        for slot, res, ver in zip(slots, results, versions):
            # singleflight fan-out: every coalesced rider gets the
            # slot's result
            for p in slot:
                if not p[3].done():
                    p[3].set_result((res, ver))


class _AioReadServices:
    """The full read surface over grpc.aio. Check rides the in-loop
    batcher; Expand/List (blocking device/store work) delegate to the
    shared _Services bodies on a small executor; Version/Health answer
    in-loop. One behavior surface with the threaded plane."""

    def __init__(self, services: _Services, batcher: AioCheckBatcher,
                 worker=None):
        self._svc = services
        self._batcher = batcher
        # replica mode: the ServeWorker this listener belongs to (worker
        # 0 — the aio plane stays a single loop). Check applies the
        # snaptoken routing rule; hedging rides the threaded plane
        # (api/replica.py replica_check_async).
        self._worker = worker
        self._blocking = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="keto-aio-blocking"
        )
        # health watchers park a thread in ready.wait_change for up to
        # 5 s per wake; pool sized to the shared watcher cap
        # (serve.read.grpc.max_watchers). Tuple WatchService streams do
        # NOT draw from this pool — they are loop-native (see
        # watch_tuples: producer-side wakeups via call_soon_threadsafe,
        # no thread parks per stream).
        self._watch_pool = ThreadPoolExecutor(
            max_workers=services.max_watchers,
            thread_name_prefix="keto-aio-watch",
        )

    async def _observed(self, method, coro_fn, req, context):
        # same trace ingestion as the threaded plane: traceparent from
        # the invocation metadata, stage/log bookkeeping on the way out
        rt = self._svc._begin_trace(context)
        token = set_request_trace(rt)
        t0 = time.perf_counter()
        outcome = None
        try:
            with self._svc.metrics.observe_request("grpc", method) as outcome:
                try:
                    with self._svc.registry.tracer().span(
                        f"grpc.{method}", ctx=rt.ctx, root=True
                    ):
                        return await coro_fn(req, context)
                except KetoError as e:
                    outcome["code"] = _grpc_code(e).name
                    from .grpc_server import _attach_retry_after

                    _attach_retry_after(context, e)
                    await context.abort(_grpc_code(e), e.message)
                except grpc.aio.AbortError:
                    raise  # context.abort signalling, already coded
                except Exception as e:  # noqa: BLE001 — RPC boundary; same
                    # generic->INTERNAL mapping as the threaded plane
                    outcome["code"] = "INTERNAL"
                    await context.abort(grpc.StatusCode.INTERNAL, str(e))
        finally:
            reset_request_trace(token)
            self._svc._finish_trace(
                method, rt,
                outcome.code if outcome is not None else "INTERNAL",
                time.perf_counter() - t0,
            )

    async def check(self, req, context):
        async def body(req, context):
            from ..engine.snaptoken import encode_snaptoken
            from ..resilience import admit_check, admit_explain

            # admission gate BEFORE any work (typed 429/504, identical
            # mapping to the threaded planes); the aio batcher's pending
            # count is loop-local, so the bound check is exact. explain
            # rides the explain.max_per_s token bucket instead.
            explain = bool(getattr(req, "explain", False))
            if explain:
                admit_explain(self._svc.registry, current_request_trace())
            else:
                admit_check(
                    self._svc.registry, self._batcher,
                    current_request_trace(),
                )
            t = self._svc._check_tuple(req)
            self._svc.registry.validate_namespaces(t)
            nid = self._svc._nid(context)
            max_depth = int(req.max_depth)
            if explain:
                # §5m explain: the engine explain path is blocking
                # (device ride + host witness re-walk), so it runs on
                # the blocking executor with the request's contextvars
                # — same canonical DecisionTrace bytes as the sync plane
                from ..engine.explain import canonical_json, serve_explain

                rt = current_request_trace()
                if self._worker is not None:
                    from .replica import resolve_version

                    worker = self._worker
                    loop = asyncio.get_running_loop()
                    _t, version = await loop.run_in_executor(
                        self._blocking,
                        lambda: resolve_version(
                            worker.group, worker, nid, req.snaptoken, rt
                        ),
                    )
                else:
                    version = self._svc._enforce_snaptoken(
                        req.snaptoken, nid
                    )
                loop = asyncio.get_running_loop()
                cvctx = contextvars.copy_context()
                res, trace = await loop.run_in_executor(
                    self._blocking,
                    lambda: cvctx.run(
                        serve_explain, self._svc.registry, nid, t,
                        max_depth, version, rt,
                    ),
                )
                if res.error is not None:
                    raise res.error
                return pb.CheckResponse(
                    allowed=res.allowed,
                    snaptoken=encode_snaptoken(version, nid),
                    decision_trace=canonical_json(trace).decode(),
                )
            if self._worker is not None:
                # replica mode: the routing rule's fast path (applied
                # version satisfies the token) stays entirely in-loop;
                # catch-up holds and fresh-worker routing run on the
                # blocking executor (api/replica.py)
                from .replica import replica_check_async

                res, version = await replica_check_async(
                    self._worker, self._batcher, nid, t, max_depth,
                    req.snaptoken, current_request_trace(),
                    asyncio.get_running_loop(), self._blocking,
                )
            else:
                # store-version read + token enforcement are dict/counter
                # reads — fine in-loop (no device or SQL round-trip on
                # the memory manager; sqlite's counter SELECT is ~10 us)
                version = self._svc._enforce_snaptoken(req.snaptoken, nid)
                # serve fast path (api/check_cache.py): a hit answers
                # in-loop before the batcher — no executor hop, no
                # assemble/dispatch/device stages; the lookup is one
                # lock + two dict ops, loop-safe like the version read
                # above
                from .check_cache import cached_check_async

                res = await cached_check_async(
                    self._svc.registry, self._batcher, nid, t, max_depth,
                    version, current_request_trace(),
                )
            if res.error is not None:
                raise res.error
            return pb.CheckResponse(
                allowed=res.allowed, snaptoken=encode_snaptoken(version, nid)
            )

        return await self._observed("Check", body, req, context)

    def _delegated(self, name, sync_fn):
        async def body(req, context):
            loop = asyncio.get_running_loop()
            # carry the request's contextvars (CURRENT_TRACE) onto the
            # executor thread so traced store ops correlate
            cvctx = contextvars.copy_context()
            return await loop.run_in_executor(
                self._blocking, lambda: cvctx.run(sync_fn, req, context)
            )

        async def handler(req, context):
            return await self._observed(name, body, req, context)

        return handler

    async def get_version(self, req, context):
        return self._svc.get_version(req, context)

    async def health_check(self, req, context):
        return self._svc.health_check(req, context)

    async def watch_tuples(self, req, context):
        """Changelog watch as a NATIVE async generator: the hub pushes a
        loop wakeup via call_soon_threadsafe and the stream drains the
        subscription buffer in-loop — no thread pinned per stream (the
        sync plane parks a worker thread in Subscription.get instead).
        Same cursor/RESET contract and watcher cap as the sync plane."""
        svc = self._svc
        if not svc._watch_slots.acquire(blocking=False):
            await context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                "too many concurrent watchers",
            )
        try:
            loop = asyncio.get_running_loop()
            try:
                # subscribe replays history from the store — off-loop
                sub = await loop.run_in_executor(
                    self._blocking, svc.watch_subscribe, req, context
                )
            except KetoError as e:
                await context.abort(_grpc_code(e), e.message)
            wake = asyncio.Event()

            def _wake():
                try:
                    loop.call_soon_threadsafe(wake.set)
                except RuntimeError:
                    pass  # loop shutting down; the stream is ending

            sub.add_notify(_wake)
            hub = svc.registry.watch_hub()
            # in-band keep-alives (watch.heartbeat_s — same contract as
            # the sync plane's frames): detect half-open connections,
            # free the subscriber ring via the finally below
            from ..engine.snaptoken import encode_snaptoken

            heartbeat_s = float(
                svc.registry.config.get("watch.heartbeat_s", 5.0)
            )
            last_write = loop.time()
            try:
                while not context.cancelled():
                    # every iteration (not only idle ones): a stream
                    # whose events are all namespace-filtered out is
                    # busy AND wire-silent without this
                    if loop.time() - last_write >= heartbeat_s:
                        last_write = loop.time()
                        # cursor snaptoken rides the frame (HA follower
                        # plane): idle version discovery, same as the
                        # sync plane
                        yield pb.WatchResponse(
                            event_type="heartbeat",
                            snaptoken=encode_snaptoken(sub.cursor, sub.nid),
                        )
                    event, needs_resume = sub.pop_nowait()
                    if needs_resume:
                        try:
                            # overflow resume re-reads the store
                            # changelog — off-loop, like subscribe
                            event = await loop.run_in_executor(
                                self._blocking, hub._resume, sub
                            )
                        except KetoError as e:
                            # typed end-of-stream (store outage during
                            # an overflow resume): the client
                            # re-subscribes from its cursor
                            await context.abort(_grpc_code(e), e.message)
                    if event is None:
                        if sub.closed:  # daemon drain ends the stream
                            break
                        try:
                            await asyncio.wait_for(wake.wait(), timeout=0.5)
                        except asyncio.TimeoutError:
                            pass
                        wake.clear()
                        continue
                    event = event.filtered(req.namespace)
                    if event is None:
                        continue
                    yield svc.watch_event_to_proto(event)
                    last_write = loop.time()
            finally:
                sub.close()
        finally:
            svc._watch_slots.release()

    async def health_watch(self, req, context):
        """Async twin of _Services.health_watch: same event-driven
        contract and watcher cap; only the wait parks on an executor."""
        if not self._svc._watch_slots.acquire(blocking=False):
            await context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                "too many concurrent health watchers",
            )
        loop = asyncio.get_running_loop()
        ready = self._svc.registry.ready
        try:
            flag, gen = ready.state()
            last = None
            while not context.cancelled():
                current = 1 if flag else 2
                if current != last:
                    last = current
                    yield pb.HealthCheckResponse(status=current)
                flag, gen = await loop.run_in_executor(
                    self._watch_pool, ready.wait_change, gen, 5.0
                )
        finally:
            self._svc._watch_slots.release()

    def close(self) -> None:
        self._blocking.shutdown(wait=False)
        self._watch_pool.shutdown(wait=False)


def _aio_handlers(service: _AioReadServices):
    from .descriptors import (
        BATCH_CHECK_SERVICE,
        EXPAND_SERVICE,
        FILTER_SERVICE,
        HEALTH_SERVICE,
        READ_SERVICE,
        REVERSE_READ_SERVICE,
        VERSION_SERVICE,
        WATCH_SERVICE,
    )

    def unary(fn, req_cls):
        return grpc.unary_unary_rpc_method_handler(
            fn,
            request_deserializer=req_cls.FromString,
            response_serializer=lambda m: m.SerializeToString(),
        )

    svc = service._svc
    return [
        grpc.method_handlers_generic_handler(CHECK_SERVICE, {
            "Check": unary(service.check, pb.CheckRequest),
        }),
        # batch extension: a whole batch per RPC is blocking device work
        # (engine.check_batch), so it delegates like Expand/List — the
        # in-loop batcher exists to coalesce SINGLE checks, which a
        # batch request has already done client-side
        grpc.method_handlers_generic_handler(BATCH_CHECK_SERVICE, {
            "BatchCheck": unary(
                service._delegated("BatchCheck", svc.batch_check),
                pb.BatchCheckRequest,
            ),
        }),
        grpc.method_handlers_generic_handler(EXPAND_SERVICE, {
            "Expand": unary(
                service._delegated("Expand", svc.expand), pb.ExpandRequest
            ),
        }),
        grpc.method_handlers_generic_handler(READ_SERVICE, {
            "ListRelationTuples": unary(
                service._delegated(
                    "ListRelationTuples", svc.list_relation_tuples
                ),
                pb.ListRelationTuplesRequest,
            ),
        }),
        # reverse-reachability extension: blocking device/store work,
        # delegated like Expand/List
        grpc.method_handlers_generic_handler(REVERSE_READ_SERVICE, {
            "ListObjects": unary(
                service._delegated("ListObjects", svc.list_objects),
                pb.ListObjectsRequest,
            ),
            "ListSubjects": unary(
                service._delegated("ListSubjects", svc.list_subjects),
                pb.ListSubjectsRequest,
            ),
        }),
        # bulk ACL filter extension: a whole candidate column per RPC is
        # blocking device work (engine.filter_batch), delegated like
        # BatchCheck — the in-loop batcher coalesces SINGLE checks,
        # which a filter request has already batched client-side
        grpc.method_handlers_generic_handler(FILTER_SERVICE, {
            "Filter": unary(
                service._delegated("Filter", svc.filter), pb.FilterRequest
            ),
        }),
        # changelog watch extension: loop-native async stream
        grpc.method_handlers_generic_handler(WATCH_SERVICE, {
            "Watch": grpc.unary_stream_rpc_method_handler(
                service.watch_tuples,
                request_deserializer=pb.WatchRequest.FromString,
                response_serializer=lambda m: m.SerializeToString(),
            ),
        }),
        grpc.method_handlers_generic_handler(VERSION_SERVICE, {
            "GetVersion": unary(service.get_version, pb.GetVersionRequest),
        }),
        grpc.method_handlers_generic_handler(HEALTH_SERVICE, {
            "Check": unary(service.health_check, pb.HealthCheckRequest),
            "Watch": grpc.unary_stream_rpc_method_handler(
                service.health_watch,
                request_deserializer=pb.HealthCheckRequest.FromString,
                response_serializer=lambda m: m.SerializeToString(),
            ),
        }),
    ]


class AioReadServer:
    """Own-thread event loop hosting the aio gRPC read listener. The
    sync daemon composes it like any other listener: start() binds and
    returns the port, stop() drains."""

    def __init__(self, registry, host: str, port: int,
                 pipeline_depth: int = 4, window_s: float = 0.002,
                 worker=None):
        self.registry = registry
        self.host = host
        self.port = port
        self.worker = worker  # replica ServeWorker | None
        self.bound_port: int | None = None
        self._pipeline_depth = pipeline_depth
        self._window_s = window_s
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None
        self._started = threading.Event()
        self._server = None
        self._services = None
        self.batcher: AioCheckBatcher | None = None

    def start(self) -> int:
        self._thread = threading.Thread(
            target=self._run, name="keto-aio-read", daemon=True
        )
        self._thread.start()
        if not self._started.wait(timeout=30) or self.bound_port is None:
            # ketolint: allow[typed-error] reason=startup path: raises to the embedding process before any listener exists, so no client ever sees it — KetoError's HTTP/gRPC mapping has nothing to map to
            raise RuntimeError("aio read server failed to start")
        return self.bound_port

    def _run(self) -> None:
        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        # run_forever (not run_until_complete of a serve coroutine): the
        # loop must outlive wait_for_termination so stop()'s _shutdown
        # coroutine can finish closing the batcher/executors — ending the
        # loop the moment the server stops raced exactly that and burned
        # the full stop timeout on every shutdown
        self._loop.run_until_complete(self._start_server())
        self._loop.run_forever()
        self._loop.run_until_complete(self._loop.shutdown_asyncgens())
        self._loop.close()

    async def _start_server(self) -> None:
        services = _Services(self.registry)
        cfg = self.registry.config
        self.batcher = AioCheckBatcher(
            self.registry.check_engine,
            pipeline_depth=self._pipeline_depth,
            window_s=self._window_s,
            metrics=self.registry.metrics(),
            tracer=self.registry.tracer(),
            max_inflight=cfg.get("serve.check.max_inflight"),
            max_queue=cfg.get("serve.check.max_queue"),
            device_timeout_ms=cfg.get("serve.check.device_timeout_ms"),
            # ONE process-wide breaker shared with the threaded plane:
            # device health is judged from all traffic
            breaker=self.registry.circuit_breaker(),
            flightrec=self.registry.flight_recorder(),
        )
        self.batcher.start()
        self._services = _AioReadServices(
            services, self.batcher, worker=self.worker
        )
        server = grpc.aio.server()
        server.add_generic_rpc_handlers(tuple(_aio_handlers(self._services)))
        self.bound_port = server.add_insecure_port(f"{self.host}:{self.port}")
        await server.start()
        self._server = server
        self._started.set()

    def stop(self, grace: float = 2.0) -> None:
        if self._loop is None or self._server is None:
            return

        async def _shutdown():
            await self._server.stop(grace)
            await self.batcher.close()
            if self._services is not None:
                self._services.close()

        try:
            fut = asyncio.run_coroutine_threadsafe(_shutdown(), self._loop)
            fut.result(timeout=grace + 10)
        except TimeoutError:
            pass  # daemon shutdown must not hang on a stuck stream
        self._loop.call_soon_threadsafe(self._loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=5)
