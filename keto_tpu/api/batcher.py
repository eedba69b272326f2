"""Micro-batching front for Check().

The reference parallelizes one check across goroutines (checkgroup); the
TPU engine instead parallelizes across the batch dimension, so concurrent
RPC handler threads must be coalesced into device batches: each caller
enqueues (tuple, depth) and blocks on a future; a single collector thread
drains the queue — waiting at most `window_s` after the first arrival —
groups by effective depth (the kernel takes one depth per launch), runs
`engine.check_batch`, and resolves the futures.

Under no concurrency a request pays ~0 extra latency (the collector pops
it immediately and the window only applies while topping up an in-flight
batch); under load, batches approach `max_batch` and throughput rides the
kernel's batch curve instead of thread count.

Concurrent IDENTICAL checks additionally collapse onto one batch slot
(singleflight — Zanzibar's hot-spot lock table, paper §3) and the slot's
result fans back out to every rider, so a hot key costs one device slot
per batch no matter how many clients hammer it.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeoutError
from dataclasses import dataclass, field

from ..errors import (
    BatcherClosedError,
    CheckBatchFailedError,
    DeadlineExceededError,
    KetoError,
    OverloadedError,
)


def note_queue_wait(riders, queue_size: int, metrics, tracer, depth_gauge) -> None:
    """Shared queue-wait attribution for BOTH batching planes (threaded
    CheckBatcher here, AioCheckBatcher in aio_server.py): each rider's
    wait lands on its RequestTrace (slow-query breakdown) and as a
    batcher.queue span when tracing; the stage histogram gets one
    group-mean sample. `riders` iterates (RequestTrace|None, enqueue_t)
    pairs; `depth_gauge` is the plane's batcher_queue_depth label child
    (per-plane so the two batchers never overwrite each other)."""
    now = time.perf_counter()
    spans = tracer is not None and getattr(tracer, "active", False)
    total = 0.0
    n = 0
    for rt, enq_t in riders:
        w = now - enq_t
        total += w
        n += 1
        if rt is not None:
            rt.add_stage("queue", w)
            # the device-feed account (engine/device_feed.py) charges a
            # device that stood empty meanwhile to `starved_queue`
            rt.enqueued = enq_t
            if spans:
                tracer.record("batcher.queue", ctx=rt.ctx, duration_s=w)
    if metrics is not None and n:
        metrics.observe_stage("queue", total / n)
        depth_gauge.set(queue_size)


def resolve_max_inflight(max_inflight, pipeline_depth: int) -> int:
    """One formula for both batching planes: the configured
    serve.check.max_inflight, or 2x pipeline depth (min 4)."""
    return int(max_inflight) if max_inflight else max(2 * pipeline_depth, 4)


def coalesce_pending(group, key_fn, metrics):
    """Singleflight dedupe (Zanzibar's hot-spot lock table, paper §3):
    concurrent identical pending checks collapse onto ONE batch slot and
    the result fans back out to every rider. Shared by BOTH batching
    planes; `group` is one (depth, nid) dispatch group, `key_fn` maps a
    pending to its identity (the RelationTuple — depth/nid are already
    the group key). Returns a list of slots (lists of pendings, leader
    first) in arrival order."""
    slots: dict = {}
    for p in group:
        slots.setdefault(key_fn(p), []).append(p)
    out = list(slots.values())
    coalesced = len(group) - len(out)
    if coalesced and metrics is not None:
        metrics.check_coalesced_total.inc(coalesced)
    return out


def classify_engine_error(e: Exception, metrics, cause: str) -> KetoError:
    """Engine-batch failures reach riders as typed KetoErrors, never the
    raw exception (the transports map KetoError.status / grpc code; a
    bare ValueError was a 500 with an unhelpful body). Shared by BOTH
    batching planes; counts keto_tpu_check_batch_failed_total{cause}.
    `cause` is one of the fixed label values (engine | host — device
    failures are counted by the recovery paths directly).

    The engine stamps `launch_id` onto submit/resolve exceptions
    (tpu_engine.check_batch_submit); it is carried into the typed error's
    message and attribute so an operator can join the failure to its
    flight-recorder entry (`GET /admin/flightrec`)."""
    launch_id = getattr(e, "launch_id", None)
    if isinstance(e, KetoError):
        cause = "keto"
        err = e
    else:
        suffix = f" (launch={launch_id})" if launch_id is not None else ""
        err = CheckBatchFailedError(
            f"check batch failed: {type(e).__name__}: {e}{suffix}"
        )
    if launch_id is not None and getattr(err, "launch_id", None) is None:
        err.launch_id = launch_id
    if metrics is not None:
        metrics.check_batch_failed_total.labels(cause).inc()
    return err


def host_check_batch(engine, tuples, max_depth: int):
    """The exact-host-oracle evaluation of one batch — the breaker's
    graceful-degradation path and the launch watchdog's recovery path.
    TPU engines expose `check_batch_host` (reference replay, zero device
    contact); host facades and stub engines fall back to their only
    surface, `check_batch`."""
    fn = getattr(engine, "check_batch_host", None)
    if fn is not None:
        return fn(tuples, max_depth)
    return engine.check_batch(tuples, max_depth)


class _LaunchGuard:
    """Exactly one of {resolver, launch watchdog} finishes a device
    launch: the winner releases the in-flight slot and answers the
    riders; the loser becomes a no-op (a stalled resolve returning after
    the watchdog already host-served its riders must not double-release
    the semaphore or double-resolve the futures)."""

    __slots__ = ("_lock", "_done")

    def __init__(self):
        self._lock = threading.Lock()
        self._done = False

    def claim(self) -> bool:
        with self._lock:
            if self._done:
                return False
            self._done = True
            return True

    def peek(self) -> bool:
        with self._lock:
            return self._done


def submit_takes_telemetry(cache: dict, engine, submit) -> bool:
    """check_batch_submit grew a `telemetry` kwarg; engines stubbed with
    the bare two-arg signature (tests, embedders) keep working. The
    signature inspection is cached per engine type in `cache`."""
    takes = cache.get(type(engine))
    if takes is None:
        import inspect

        try:
            takes = "telemetry" in inspect.signature(submit).parameters
        except (TypeError, ValueError):
            takes = False
        cache[type(engine)] = takes
    return takes


@dataclass
class _Pending:
    tuple: object
    max_depth: int
    nid: object = None  # None = the registry's default network
    rt: object = None  # observability.RequestTrace | None
    enq_t: float = 0.0
    future: Future = field(default_factory=Future)
    # caller already counted this request's deadline expiry (the "wait"
    # stage): the collector's later queue-drop must not count it twice
    dl_counted: bool = False


class CheckBatcher:
    def __init__(
        self,
        engine,
        max_batch: int = 1024,
        window_s: float = 0.002,
        pipeline_depth: int = 2,
        engine_resolver=None,
        metrics=None,
        tracer=None,
        max_inflight: int | None = None,
        max_queue: int | None = None,
        device_timeout_ms: float | None = None,
        breaker=None,
        flightrec=None,
        pending_total=None,
        drain_ways: int = 1,
    ):
        # per-request tenancy: batches are grouped by nid and dispatched
        # to that tenant's engine (ref: ketoctx Contextualizer,
        # /root/reference/ketoctx/contextualizer.go:12-19); the default
        # resolver pins everything to the constructor engine
        self.engine = engine
        self._resolve = engine_resolver or (lambda nid: engine)
        self.max_batch = max_batch
        self.window_s = window_s
        self._queue: queue.Queue[_Pending | None] = queue.Queue()
        self._thread = threading.Thread(
            target=self._run, name="keto-check-batcher", daemon=True
        )
        # dispatch pool: while one batch synchronizes on device results,
        # the collector keeps building and dispatching the next — device
        # execution of consecutive batches overlaps (jax dispatch is
        # async; the sync point is reading results back)
        from concurrent.futures import ThreadPoolExecutor

        self._pool = ThreadPoolExecutor(
            max_workers=max(pipeline_depth, 1),
            thread_name_prefix="keto-check-dispatch",
        )
        # launch thread: device submits run here, NOT on the collector —
        # a first-seen bucket's XLA compile or a post-write snapshot
        # rebuild must not stop the collector from draining the queue
        self._launcher = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="keto-check-launch"
        )
        # degraded-serving pool: breaker-open host groups run HERE, never
        # on `_pool` — a wedged device blocks pool workers inside
        # check_batch_resolve (only the watchdog's semaphore release is
        # possible; the blocked thread is not recoverable), and degraded
        # serving queued behind them would never run. Threads spawn on
        # first use, so unbroken deployments pay nothing.
        self._host_pool = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="keto-check-hostserve"
        )
        # backpressure: at most max_inflight launched-but-unresolved
        # device batches (every handle holds device buffers and a full
        # engine state, so the launch queue must stay bounded); operators
        # tune it via serve.check.max_inflight (schema-validated),
        # default 2x pipeline depth
        self.max_inflight = resolve_max_inflight(max_inflight, pipeline_depth)
        self._inflight = threading.BoundedSemaphore(self.max_inflight)
        # admission control (serve.check.max_queue): a hard bound on
        # admitted-but-unresolved checks — queued items, batched groups,
        # and in-flight device waits all count, so memory stays bounded
        # under a wedged device instead of queueing without limit.
        # 0/None = unbounded (reference parity).
        self.max_queue = int(max_queue) if max_queue else 0
        self._pending = 0
        self._pending_mu = threading.Lock()
        # replica group wiring: `pending_total` reports the GROUP's
        # admitted-but-unresolved count (Retry-After drain estimates must
        # reflect group-wide load, not one worker's queue) and
        # `drain_ways` how many batchers drain it in parallel; solo
        # batchers keep the local count and 1 way
        self._pending_total = pending_total
        self._drain_ways = max(int(drain_ways), 1)
        # device-path resilience: launch watchdog budget + shared breaker
        # (serve.check.device_timeout_ms / serve.check.breaker.*)
        self.device_timeout_s = (
            float(device_timeout_ms) / 1e3 if device_timeout_ms else None
        )
        self.breaker = breaker
        # flight recorder (observability.FlightRecorder | None): device-
        # path failures auto-dump the ring tail to the log before the
        # evidence scrolls out
        self.flightrec = flightrec
        # True while a _launch executes (benign unlocked flag): the
        # collector arms the routing watchdog only when the launcher is
        # occupied, so the healthy fast path creates no timer thread
        self._launcher_busy = False
        # observability (both optional): queue-depth/inflight gauges,
        # per-request queue-wait stage attribution, batcher.queue spans
        self.metrics = metrics
        self.tracer = tracer
        self._depth_gauge = (
            metrics.batcher_queue_depth.labels("threaded")
            if metrics is not None else None
        )
        if metrics is not None:
            metrics.batcher_queue_limit.labels("threaded").set(self.max_queue)
        # engine type -> whether check_batch_submit accepts `telemetry`
        # (feature-detected once; tests stub engines with the bare
        # two-arg signature)
        self._submit_takes_telemetry: dict[type, bool] = {}
        self._closed = False
        self._thread.start()

    # -- caller side ----------------------------------------------------------

    def _queue_delay_estimate_s(self, pending: int) -> float:
        """Retry-after hint for a shed request: how long the currently
        admitted work plausibly takes to drain (batches of max_batch, one
        window each) — a heuristic floor, never a promise. In a replica
        group the numerator is the GROUP-wide pending count and the
        denominator scales by how many batchers drain in parallel."""
        if self._pending_total is not None:
            pending = self._pending_total()
        batches = pending // max(self.max_batch * self._drain_ways, 1) + 1
        return max(batches * max(self.window_s, 0.001), 0.05)

    def admit(self, deadline=None) -> None:
        """Queue-delay-aware admission gate (transports call this BEFORE
        any check work): typed OverloadedError when the admitted-but-
        unresolved count is at serve.check.max_queue, typed
        DeadlineExceededError when the request's budget is already
        spent. The check here is advisory (no slot is reserved); the
        atomic bound is enforced again at enqueue."""
        if self._closed:
            raise OverloadedError("check batcher is closed", retry_after_s=1.0)
        if self.max_queue:
            with self._pending_mu:
                pending = self._pending
            if pending >= self.max_queue:
                self._count_shed()
                raise OverloadedError(
                    "check queue is full",
                    retry_after_s=self._queue_delay_estimate_s(pending),
                )
        if deadline is not None and deadline.expired():
            if self.metrics is not None:
                self.metrics.deadline_exceeded_total.labels("admission").inc()
            raise DeadlineExceededError(
                "request deadline expired before admission"
            )

    def _count_shed(self) -> None:
        if self.metrics is not None:
            self.metrics.requests_shed_total.labels("queue_full").inc()

    def _dec_pending(self, _f=None) -> None:
        with self._pending_mu:
            self._pending -= 1

    def idle(self) -> bool:
        """True when nothing is admitted-but-unresolved (the daemon's
        drain loop polls this during the shutdown grace window)."""
        with self._pending_mu:
            return self._pending == 0

    def check(self, tuple, max_depth: int = 0, nid=None, rt=None):
        """Blocking single check; returns a CheckResult. `rt` is the
        caller's RequestTrace: the batcher adds the queue-wait stage and
        the engine adds its stages, so the transport that created it can
        log/span the full pipeline breakdown; `rt.deadline` (if any)
        bounds the wait end-to-end."""
        return self.check_versioned(tuple, max_depth, nid=nid, rt=rt)[0]

    def check_versioned(self, tuple, max_depth: int = 0, nid=None, rt=None):
        """(CheckResult, version | None): the version is the store
        version the answer is authoritative at (the evaluated engine
        state's covered_version, plumbed through check_batch_resolve_v)
        or None when the evaluation path cannot pin one (host engine,
        host-replayed rider) — the check cache's store contract."""
        return self.wait_pending(self.submit(tuple, max_depth, nid, rt), rt)

    def submit(self, tuple, max_depth: int = 0, nid=None, rt=None) -> _Pending:
        """Enqueue one check WITHOUT blocking on its result; returns the
        _Pending whose `future` resolves to (CheckResult, version).
        The non-blocking half of check_versioned — the replica plane's
        hedging needs future-level access so two rides can race."""
        if self._closed:
            # typed drain shed + embedder `except RuntimeError` compat
            # (tri-plane parity with AioCheckBatcher.check_versioned)
            raise BatcherClosedError(retry_after_s=1.0)
        # atomic admission bound: check-and-increment under one lock so
        # concurrent callers can never push past max_queue (the
        # acceptance property "queue never grows past max_queue"). The
        # shed's retry-after estimate is computed AFTER releasing the
        # lock: in a replica group it reads every worker's pending count
        # — including this batcher's own non-reentrant _pending_mu
        shed_pending = None
        with self._pending_mu:
            if self.max_queue and self._pending >= self.max_queue:
                shed_pending = self._pending
            else:
                self._pending += 1
        if shed_pending is not None:
            self._count_shed()
            raise OverloadedError(
                "check queue is full",
                retry_after_s=self._queue_delay_estimate_s(shed_pending),
            )
        p = _Pending(tuple, max_depth, nid, rt, time.perf_counter())
        p.future.add_done_callback(self._dec_pending)
        self._queue.put(p)
        if self._depth_gauge is not None:
            self._depth_gauge.set(self._queue.qsize())
        return p

    def wait_pending(self, p: _Pending, rt=None):
        """Block on one submitted pending, bounded by `rt.deadline`."""
        deadline = rt.deadline if rt is not None else None
        if deadline is None:
            return p.future.result()
        try:
            return p.future.result(timeout=max(deadline.remaining_s(), 1e-4))
        except FutureTimeoutError:
            # the pending stays queued; the collector drops it as expired
            # at its launch boundary (no batch slot occupied), and the
            # caller fails fast with the typed 504 — Zanzibar's
            # deadline-scoped evaluation
            p.dl_counted = True
            if self.metrics is not None:
                self.metrics.deadline_exceeded_total.labels("wait").inc()
            raise DeadlineExceededError(
                "request deadline expired waiting for the check batch"
            )

    def close(self) -> None:
        self._closed = True
        self._queue.put(None)
        self._thread.join(timeout=5)
        # fail any requests that raced past the _closed gate so no caller
        # blocks forever on a future the dead collector will never resolve
        while True:
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                break
            if p is not None and not p.future.done():
                p.future.set_exception(BatcherClosedError(retry_after_s=1.0))

    # -- collector ------------------------------------------------------------

    def _drain(self, first: _Pending) -> list[_Pending]:
        batch = [first]
        end = time.monotonic() + self.window_s
        while len(batch) < self.max_batch:
            timeout = end - time.monotonic()
            if timeout <= 0:
                # window expired: take whatever is already queued, no waiting
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    break
            else:
                try:
                    item = self._queue.get(timeout=timeout)
                except queue.Empty:
                    break
            if item is None:
                self._queue.put(None)  # re-signal shutdown for the main loop
                break
            batch.append(item)
        return batch

    @staticmethod
    def _fail_slots(slots: list[list[_Pending]], err: Exception) -> None:
        for slot in slots:
            for p in slot:
                if not p.future.done():
                    p.future.set_exception(err)

    def _expire(self, group: list[_Pending]) -> list[_Pending]:
        """Drop riders whose deadline expired while queued: they fail
        with the typed 504 WITHOUT occupying a batch slot (their caller
        has usually already timed out in check_versioned; this is the
        slot-reclamation half of the contract)."""
        live: list[_Pending] = []
        for p in group:
            if p.future.done():
                # already answered elsewhere — a cancelled hedge loser
                # (the winning ride answered the caller) must not occupy
                # a batch slot; its pending count was released by the
                # future's done callback
                continue
            dl = p.rt.deadline if p.rt is not None else None
            if dl is not None and dl.expired():
                if self.metrics is not None and not p.dl_counted:
                    self.metrics.deadline_exceeded_total.labels("queue").inc()
                if not p.future.done():
                    p.future.set_exception(DeadlineExceededError(
                        "request deadline expired in the check queue"
                    ))
            else:
                live.append(p)
        return live

    def _evaluate(self, slots: list[list[_Pending]], depth: int, nid=None) -> None:
        try:
            engine = self._resolve(nid)
            results = engine.check_batch([s[0].tuple for s in slots], depth)
        except Exception as e:  # engine-level failure fails the batch —
            # with a typed KetoError, never the raw exception
            self._fail_slots(
                slots, classify_engine_error(e, self.metrics, "engine")
            )
            return
        for slot, res in zip(slots, results):
            for p in slot:
                if not p.future.done():
                    p.future.set_result((res, None))

    def _record_device_failure(self, cause: str, err=None) -> None:
        from ..errors import StoreUnavailableError

        if isinstance(err, StoreUnavailableError):
            # a STORE outage reaching the submit path is not
            # device-health evidence: the store breaker owns it — the
            # DEVICE breaker must not trip (breaker-open host serving
            # would read the same dead store), and the flight recorder
            # must not dump per failed batch through a whole outage
            if self.metrics is not None:
                self.metrics.check_batch_failed_total.labels("store").inc()
            return
        if self.breaker is not None:
            self.breaker.record_failure()
        if self.metrics is not None:
            self.metrics.check_batch_failed_total.labels(cause).inc()
        if self.flightrec is not None:
            # auto-dump on batch failure / watchdog abandon: the recent
            # launches' records reach the log while still correlated
            self.flightrec.dump(cause)

    def _host_fallback_slots(
        self, engine, slots: list[list[_Pending]], depth: int
    ) -> None:
        """Graceful degradation: answer the riders from the exact host
        oracle after a device-path failure (submit/resolve raised, or
        the launch watchdog fired). Answers stay correct; the latency
        lands in the host_fallback stage."""
        t0 = time.perf_counter()
        try:
            results = host_check_batch(
                engine, [s[0].tuple for s in slots], depth
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
                if p.rt is not None:
                    p.rt.add_stage("host_fallback", dur)
                    p.rt.tier = "host"
                if not p.future.done():
                    # host answers read the LIVE store: no pinned version
                    p.future.set_result((res, None))

    def _host_serve(self, group: list[_Pending], depth: int, nid=None) -> None:
        """Breaker-open route (runs on the dispatch pool, NOT the launch
        thread — a wedged launch thread must not block degraded serving):
        the whole group is answered by the exact host oracle."""
        note_queue_wait(
            ((p.rt, p.enq_t) for p in group), self._queue.qsize(),
            self.metrics, self.tracer, self._depth_gauge,
        )
        group = self._expire(group)
        if not group:
            return
        slots = coalesce_pending(group, lambda p: p.tuple, self.metrics)
        try:
            engine = self._resolve(nid)
        except Exception as e:
            self._fail_slots(
                slots, classify_engine_error(e, self.metrics, "engine")
            )
            return
        self._host_fallback_slots(engine, slots, depth)

    def _device_timed_out(self, guard, engine, slots, depth: int) -> None:
        """Launch watchdog (serve.check.device_timeout_ms): a batch that
        has not resolved within the budget is abandoned — the in-flight
        slot is RELEASED (a wedged device must not pin the semaphore and
        starve every later batch), the breaker records the failure, and
        the riders are answered by the exact host oracle. If the stalled
        resolve eventually returns, the guard makes it a no-op."""
        if not guard.claim():
            return
        self._release_inflight()
        self._record_device_failure("device_timeout")
        self._host_fallback_slots(engine, slots, depth)

    def _resolve_inflight(
        self, engine, handle, slots: list[list[_Pending]], depth: int = 0,
        guard=None, watchdog=None,
    ) -> None:
        if guard is not None and guard.peek():
            # the watchdog already abandoned this launch and host-served
            # its riders; don't block a pool thread on the wedged handle
            return
        try:
            # version plumb-through: engines exposing the versioned
            # resolve surface pin each answer to the store version its
            # evaluated state covered (the check cache's store contract)
            resolve_v = getattr(engine, "check_batch_resolve_v", None)
            if resolve_v is not None:
                results, versions = resolve_v(handle)
            else:
                results = engine.check_batch_resolve(handle)
                versions = [None] * len(results)
        except Exception as e:
            if guard is None or guard.claim():
                if watchdog is not None:
                    watchdog.cancel()
                self._release_inflight()
                self._record_device_failure("device", err=e)
                self._host_fallback_slots(engine, slots, depth)
            return
        if guard is not None and not guard.claim():
            return  # the watchdog won the race mid-resolve
        if watchdog is not None:
            watchdog.cancel()
        self._release_inflight()
        if self.breaker is not None:
            self.breaker.record_success()
        for slot, res, ver in zip(slots, results, versions):
            # singleflight fan-out: every coalesced rider gets the slot's
            # result (CheckResults are shared immutable singletons)
            for p in slot:
                if not p.future.done():
                    p.future.set_result((res, ver))

    def _acquire_inflight(self) -> None:
        self._inflight.acquire()
        if self.metrics is not None:
            self.metrics.inflight_launches.inc()

    def _release_inflight(self) -> None:
        self._inflight.release()
        if self.metrics is not None:
            self.metrics.inflight_launches.dec()

    def _stuck_in_launcher(
        self, route_guard, group: list[_Pending], depth: int, nid
    ) -> None:
        """Routing watchdog: a group still WAITING on the (single)
        launch thread after device_timeout_ms — the launcher is wedged
        inside an earlier group's stalled submit, so the per-launch
        watchdog never armed for this one. Host-serve it from the timer
        thread; the guard makes the eventual _launch a no-op. NO breaker
        failure is recorded here: a long launcher wait is backpressure
        evidence, not a device-health verdict (a healthy-but-saturated
        device must not trip the breaker open) — the per-launch watchdog
        on the wedged group itself carries the breaker signal."""
        if not route_guard.claim():
            return
        if self.metrics is not None:
            self.metrics.check_batch_failed_total.labels(
                "device_timeout"
            ).inc()
        self._host_serve(group, depth, nid)

    def _launch(
        self, group: list[_Pending], depth: int, nid=None,
        route_guard=None, route_wd=None,
    ) -> None:
        """Split-phase dispatch (runs on the launch thread): LAUNCH the
        device batch — async jax dispatch, returns before the device
        finishes — and hand only the readback to the pool. Batch N+1's
        launch no longer waits for batch N's readback. The in-flight
        semaphore bounds launched-but-unresolved batches."""
        if route_guard is not None:
            if not route_guard.claim():
                return  # the routing watchdog already host-served this group
            if route_wd is not None:
                route_wd.cancel()
        self._launcher_busy = True
        try:
            self._launch_inner(group, depth, nid)
        finally:
            self._launcher_busy = False

    def _launch_inner(self, group: list[_Pending], depth: int, nid) -> None:
        note_queue_wait(
            ((p.rt, p.enq_t) for p in group), self._queue.qsize(),
            self.metrics, self.tracer, self._depth_gauge,
        )
        # deadline boundary: riders that expired while queued fail fast
        # here instead of occupying a slot in the device batch
        group = self._expire(group)
        if not group:
            return
        # singleflight: identical pendings share one batch slot; engine
        # stage telemetry is attributed to each slot's leader (followers
        # keep their queue/transport stages)
        slots = coalesce_pending(group, lambda p: p.tuple, self.metrics)
        try:
            engine = self._resolve(nid)
        except Exception as e:
            self._fail_slots(
                slots, classify_engine_error(e, self.metrics, "engine")
            )
            return
        submit = getattr(engine, "check_batch_submit", None)
        if submit is None:
            self._pool.submit(self._evaluate, slots, depth, nid)
            return
        self._acquire_inflight()
        # the semaphore wait can outlive every rider's budget: re-check
        # the deadline boundary so a fully-expired batch never launches
        # (the slot goes back to live work; partial expiry still rides)
        live = self._expire([p for slot in slots for p in slot])
        if not live:
            self._release_inflight()
            return
        if len(live) != sum(len(s) for s in slots):
            # rebuild without re-counting coalesce metrics
            slots = coalesce_pending(live, lambda p: p.tuple, None)
        # launch watchdog: armed BEFORE the submit so a stalled launch
        # (not just a stalled resolve) is bounded too; exactly one of
        # {watchdog, resolver} finishes this launch (the guard)
        guard = _LaunchGuard()
        watchdog = None
        if self.device_timeout_s:
            watchdog = threading.Timer(
                self.device_timeout_s, self._device_timed_out,
                args=(guard, engine, slots, depth),
            )
            watchdog.daemon = True
            watchdog.start()
        try:
            if submit_takes_telemetry(
                self._submit_takes_telemetry, engine, submit
            ):
                handle = submit(
                    [s[0].tuple for s in slots], depth,
                    telemetry=[s[0].rt for s in slots],
                )
            else:
                handle = submit([s[0].tuple for s in slots], depth)
        except Exception as e:
            if guard.claim():
                if watchdog is not None:
                    watchdog.cancel()
                self._release_inflight()
                self._record_device_failure("device", err=e)
                # graceful degradation: the riders are answered by the
                # exact host oracle instead of failing (a store-outage
                # submit failure ends there too — the oracle's reads
                # yield the typed per-item 503)
                self._host_fallback_slots(engine, slots, depth)
            return
        self._pool.submit(
            self._resolve_inflight, engine, handle, slots, depth,
            guard, watchdog,
        )

    def _run(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                self._launcher.shutdown(wait=True)
                self._pool.shutdown(wait=True)
                self._host_pool.shutdown(wait=True)
                return
            batch = self._drain(item)
            by_key: dict[tuple, list[_Pending]] = {}
            for p in batch:
                by_key.setdefault((p.max_depth, p.nid), []).append(p)
            for (depth, nid), group in by_key.items():
                # breaker routing happens HERE (the collector), not in
                # _launch: while the breaker is open, groups bypass the
                # launch thread entirely — a launch thread wedged on a
                # stalled device must not block degraded host serving
                if self.breaker is not None and not self.breaker.allow():
                    self._host_pool.submit(self._host_serve, group, depth, nid)
                else:
                    # routing watchdog (device route only): bounds the
                    # WAIT for the single launch thread, which an earlier
                    # group's wedged submit can hold for arbitrarily long
                    # — without it, queued groups sat unprotected until
                    # the launcher freed (the per-launch watchdog only
                    # arms once _launch runs). Armed ONLY when the
                    # launcher is already occupied: an idle launcher
                    # starts _launch immediately and its own watchdog
                    # covers everything — the healthy fast path pays no
                    # timer thread here.
                    route_guard = route_wd = None
                    if self.device_timeout_s and self._launcher_busy:
                        route_guard = _LaunchGuard()
                        route_wd = threading.Timer(
                            self.device_timeout_s, self._stuck_in_launcher,
                            args=(route_guard, group, depth, nid),
                        )
                        route_wd.daemon = True
                        route_wd.start()
                    self._launcher.submit(
                        self._launch, group, depth, nid,
                        route_guard, route_wd,
                    )
