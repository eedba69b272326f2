"""Multi-replica serving plane: N serve workers over one device engine.

The host serving plane — one transport/cache/batcher stack in one Python
process — is the structural ceiling on served throughput (BENCH_r07_cpu,
a CPU run: served_vs_echo_ceiling 0.711) while the engine underneath
sustains far more checks per second than one stack can feed it. This module fans
the serve plane into a REPLICA GROUP: `serve.check.workers` ServeWorkers
that each run the full transport/cache/batcher stack (own gRPC server,
own REST listener, own mux accept loop, own CheckBatcher, own
CheckCache) but share ONE device engine through the existing batch
submit path — the GraphBLAS-style engine stays singular; this is purely
host-plane parallelism (ROADMAP item 1).

Replica-local state is kept consistent the Zanzibar way (PAPER.md §2.4):

  - Each worker TAILS the Watch changelog (the PR 2 hub) through a
    per-nid subscription: every committed store version advances the
    worker's `applied` version and drives its own check cache's precise
    invalidation — the same feed any out-of-process replica would ride.
  - SNAPTOKENS GATE ROUTING exactly as they already gate the PR 4
    cache: a request carrying a snaptoken newer than the worker's
    applied version is (1) HELD for catch-up within a slice of its
    deadline budget (`serve.check.replica_catchup_ms`), then (2)
    ROUTED to a fresh worker (one whose applied version satisfies the
    token — the in-process proxy: the check executes through that
    worker's cache and batcher), and only if NO worker is fresh (3)
    ESCALATED to the live store version (the shared engine always
    evaluates at the latest store state, so the answer is fresh; a
    token ahead of the store itself still 409s). A request is NEVER
    answered staler than its token demands.
  - The response snaptoken is minted from the ANSWERING worker's
    version: bounded staleness with read-your-writes, the zookie
    contract.

On top of the group, REQUEST HEDGING (Zanzibar §2.4.1/§4 — the one
latency-tolerance mechanism PR 5 explicitly could not claim because a
single-process plane has "no replica to hedge to"): a check that has not
answered within a configurable latency quantile of recent checks fires
ONE duplicate onto another worker's batcher; first answer wins, the
loser's pending is cancelled (a cancelled pending never occupies a
device batch slot). Hedges ride the PR 5 Deadline machinery — the
duplicate carries the caller's deadline, so it can never outlive the
budget, and a budget too thin to fit a hedge never fires one. Idempotent
reads only (Check; writes never hedge). Both rides' flight-recorder
launch ids land on the caller's RequestTrace, so a hedged request's two
device rides are correlatable in `GET /admin/flightrec` and the request
log.
"""

from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import FIRST_COMPLETED
from concurrent.futures import TimeoutError as FutureTimeoutError
from concurrent.futures import wait as futures_wait
from typing import Optional

from ..engine.snaptoken import parse_snaptoken, require_version
from ..errors import DeadlineExceededError, OverloadedError
from ..observability import RequestTrace
from .check_cache import _fastpath_begin, require_answer_floor

# catch-up hold default: long enough for the in-process push-driven tail
# (microseconds normally), short enough that a genuinely stalled worker
# routes instead of burning the caller's budget
DEFAULT_CATCHUP_MS = 50.0


class ReplicaView:
    """One worker's replica-local applied-version view.

    A per-nid tailer thread subscribes to the WatchHub at the current
    store version and advances `applied[nid]` one committed version at a
    time, poking the worker's check cache's precise invalidation on the
    way (the cache's own changelog pass stays the source of truth for
    WHICH entries die; the tail is the wakeup any out-of-process replica
    would also have). `hold()` freezes application — the forced-lag
    test/fault hook: a held view stops advancing, so snaptoken routing
    must carry reads elsewhere."""

    def __init__(self, hub, manager, cache=None, metrics_gauge=None):
        self._hub = hub
        self._manager = manager
        self._cache = cache
        self._gauge = metrics_gauge  # per-worker applied-version gauge child
        self._cond = threading.Condition()
        self._applied: dict[str, int] = {}
        self._subs: dict[str, object] = {}
        self._hold = threading.Event()
        self._closed = False

    # -- hot path --------------------------------------------------------------

    def applied_version(self, nid: str) -> int:
        """The worker's applied store version for `nid` (lazily attaching
        the tailer on first touch). Lock-free dict read on the hot path —
        updates publish under the condition, reads ride the GIL."""
        v = self._applied.get(nid)
        if v is not None:
            return v
        return self._attach(nid)

    def catch_up(self, nid: str, min_version: int, timeout_s: float) -> int:
        """Hold the request for catch-up: wait until `applied[nid]`
        reaches `min_version` or the budget slice runs out; returns the
        applied version either way (the caller routes on a miss)."""
        deadline = time.monotonic() + max(timeout_s, 0.0)
        with self._cond:
            while self._applied.get(nid, 0) < min_version:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._closed:
                    break
                self._cond.wait(remaining)
            return self._applied.get(nid, 0)

    # -- lifecycle -------------------------------------------------------------

    def _attach(self, nid: str) -> int:
        # store read + hub subscribe OUTSIDE the condition (lock
        # discipline: no store calls under held locks), then publish
        current = self._manager.version(nid=nid)
        sub = self._hub.subscribe(nid, min_version=current)
        with self._cond:
            if nid in self._applied:  # lost the attach race: keep the winner
                existing = self._applied[nid]
                late = sub
                sub = None
            else:
                self._applied[nid] = current
                self._subs[nid] = sub
                existing = None
                late = None
        if late is not None:
            late.close()
            return existing
        t = threading.Thread(
            target=self._tail_loop, args=(nid, sub),
            name=f"keto-replica-tail-{nid}", daemon=True,
        )
        t.start()
        if self._gauge is not None:
            self._gauge.set(current)
        return current

    def _tail_loop(self, nid: str, sub) -> None:
        while not self._closed:
            event = sub.get(timeout=1.0)
            if event is None:
                if sub.closed:
                    return
                continue
            # forced-lag hook: a held view buffers in the subscription
            # ring instead of applying (exactly what a wedged replica
            # tail looks like from the routing rule's perspective)
            while self._hold.is_set() and not self._closed:
                self._hold_wait()
            if self._closed:
                return
            version = event.version
            if event.is_reset:
                # unrecoverable gap (overflow/trim/bulk load): resync to
                # the reset's version and let the cache's invalidation
                # pass take its conservative whole-nid path
                version = max(version, self._applied.get(nid, 0))
            with self._cond:
                if version > self._applied.get(nid, 0):
                    self._applied[nid] = version
                self._cond.notify_all()
            if self._gauge is not None:
                self._gauge.set(version)
            if self._cache is not None:
                self._cache.notify_commit(nid)

    def _hold_wait(self) -> None:
        # tiny poll so close() and release interleave promptly; only runs
        # while the TEST/fault hold hook is set, never on the live path
        time.sleep(0.005)

    def hold(self) -> None:
        """Freeze version application (forced-lag test/fault hook)."""
        self._hold.set()

    def release(self) -> None:
        self._hold.clear()
        with self._cond:
            self._cond.notify_all()

    def close(self) -> None:
        self._closed = True
        self._hold.clear()
        with self._cond:
            subs = list(self._subs.values())
            self._subs.clear()
            self._cond.notify_all()
        for sub in subs:
            sub.close()


class HedgePolicy:
    """Deadline-budget-aware hedge trigger.

    Tracks a bounded window of recent primary-ride latencies; a hedge
    fires after the configured QUANTILE of that window (never below the
    `min_delay_ms` floor). Budget rule (the PR 5 Deadline machinery): a
    request with a deadline hedges only while at least 2x the hedge
    delay remains — a duplicate that could not finish inside the budget
    is never launched, and the duplicate itself carries the caller's
    deadline so the batchers' expiry boundaries bound it end to end."""

    WARMUP = 16  # no quantile before this many observed rides

    def __init__(self, enabled: bool = True, quantile: float = 0.95,
                 min_delay_ms: float = 1.0, window: int = 512):
        self.enabled = bool(enabled)
        self.quantile = min(max(float(quantile), 0.5), 0.999)
        self.min_delay_s = max(float(min_delay_ms), 0.0) / 1e3
        self._lat: "collections.deque[float]" = collections.deque(
            maxlen=max(int(window), HedgePolicy.WARMUP)
        )
        self._mu = threading.Lock()

    def observe(self, seconds: float) -> None:
        with self._mu:
            self._lat.append(seconds)

    def delay_s(self) -> Optional[float]:
        """Seconds to wait on the primary ride before hedging, or None
        while disabled/warming (no hedge)."""
        if not self.enabled:
            return None
        with self._mu:
            n = len(self._lat)
            if n < self.WARMUP:
                return None
            s = sorted(self._lat)
        idx = min(int(self.quantile * (n - 1) + 0.5), n - 1)
        return max(s[idx], self.min_delay_s)

    def hedge_after_s(self, deadline) -> Optional[float]:
        """The budget-gated trigger: the quantile delay, or None when
        hedging is off, still warming, or the remaining budget cannot
        fit a duplicate (< 2x the delay)."""
        delay = self.delay_s()
        if delay is None:
            return None
        if deadline is not None and deadline.remaining_s() < 2.0 * delay:
            return None
        return delay


class ServeWorker:
    """One replica: its own batcher + cache + replica view; transports
    built by the daemon carry a reference back here."""

    def __init__(self, worker_id: int, registry, batcher, cache, view,
                 group: "ReplicaGroup"):
        self.worker_id = worker_id
        self.registry = registry
        self.batcher = batcher
        self.cache = cache  # per-worker CheckCache | None (replica-local)
        self.view = view
        self.group = group
        metrics = registry.metrics()
        self._checks_counter = (
            metrics.worker_checks_total.labels(str(worker_id))
            if metrics is not None else None
        )
        # plain-int twin of worker_checks_total: the public per-worker
        # answered-check count (bench breakdown, /admin/replicas) — no
        # reaching into prometheus_client internals
        self.checks_answered = 0
        # per-worker listener ports, filled in by the daemon (observable
        # at GET /admin/replicas; tests address one replica directly)
        self.ports: dict[str, int] = {}

    def count_check(self) -> None:
        self.checks_answered += 1
        if self._checks_counter is not None:
            self._checks_counter.inc()

    def stats(self) -> dict:
        with self.batcher._pending_mu:
            pending = self.batcher._pending
        return {
            "worker": self.worker_id,
            "applied": dict(self.view._applied),
            "pending": pending,
            "checks_answered": self.checks_answered,
            "cache_entries": (
                len(self.cache._entries) if self.cache is not None else 0
            ),
            "ports": dict(self.ports),
        }


class ReplicaGroup:
    """The worker set plus the shared routing/hedging machinery."""

    def __init__(self, registry, n_workers: int, make_batcher, make_cache):
        self.registry = registry
        self.metrics = registry.metrics()
        cfg = registry.config
        self.catchup_s = float(
            cfg.get("serve.check.replica_catchup_ms", DEFAULT_CATCHUP_MS)
        ) / 1e3
        self.hedge = HedgePolicy(
            enabled=bool(cfg.get("serve.check.hedge.enabled", True)),
            quantile=float(cfg.get("serve.check.hedge.quantile", 0.95)),
            min_delay_ms=float(cfg.get("serve.check.hedge.min_delay_ms", 1.0)),
        )
        hub = registry.watch_hub()
        manager = registry.relation_tuple_manager()
        self.workers: list[ServeWorker] = []
        for i in range(n_workers):
            cache = make_cache()
            gauge = (
                self.metrics.replica_applied_version.labels(str(i))
                if self.metrics is not None else None
            )
            view = ReplicaView(hub, manager, cache=cache, metrics_gauge=gauge)
            batcher = make_batcher(self)
            self.workers.append(
                ServeWorker(i, registry, batcher, cache, view, self)
            )
        self._route_rr = 0  # fresh-worker rotation (no lock: approximate)
        self._routed = {
            outcome: self.metrics.replica_routed_total.labels(outcome)
            for outcome in ("caught_up", "routed", "escalated")
        } if self.metrics is not None else None

    # -- group state -----------------------------------------------------------

    def group_pending(self) -> int:
        """Admitted-but-unresolved checks across EVERY worker's batcher —
        the Retry-After drain estimate's numerator (a shed request cares
        how loaded the GROUP is, not one worker's queue)."""
        total = 0
        for w in self.workers:
            with w.batcher._pending_mu:
                total += w.batcher._pending
        return total

    def idle(self) -> bool:
        return all(w.batcher.idle() for w in self.workers)

    def _count_route(self, outcome: str) -> None:
        if self._routed is not None:
            self._routed[outcome].inc()

    def fresh_worker(self, nid: str, min_version: int,
                     exclude: ServeWorker) -> Optional[ServeWorker]:
        """A worker (not `exclude`) whose applied version satisfies the
        token, rotating the start index so routed load spreads."""
        n = len(self.workers)
        start = self._route_rr = (self._route_rr + 1) % max(n, 1)
        for k in range(n):
            w = self.workers[(start + k) % n]
            if w is exclude:
                continue
            if w.view.applied_version(nid) >= min_version:
                return w
        return None

    def hedge_worker(self, exclude: ServeWorker) -> Optional[ServeWorker]:
        """The next worker (round-robin) to carry a hedge duplicate."""
        n = len(self.workers)
        if n < 2:
            return None
        start = self._route_rr = (self._route_rr + 1) % n
        for k in range(n):
            w = self.workers[(start + k) % n]
            if w is not exclude:
                return w
        return None

    def stats(self) -> dict:
        return {
            "workers": [w.stats() for w in self.workers],
            "group_pending": self.group_pending(),
            "hedge": {
                "enabled": self.hedge.enabled,
                "quantile": self.hedge.quantile,
                "min_delay_ms": self.hedge.min_delay_s * 1e3,
                "delay_ms": (
                    None if self.hedge.delay_s() is None
                    else round(self.hedge.delay_s() * 1e3, 3)
                ),
            },
        }

    def close(self) -> None:
        for w in self.workers:
            w.view.close()
            if w.cache is not None:
                w.cache.close()


# -- the replica serve path ----------------------------------------------------


def resolve_version(group: ReplicaGroup, worker: ServeWorker, nid: str,
                     token: str, rt) -> tuple[ServeWorker, int]:
    """The snaptoken routing rule. Returns (answering worker, version the
    answer/response token is minted at). Raises
    SnaptokenUnsatisfiableError (409) only when the token is ahead of
    the STORE itself — replica lag alone never 409s, it routes."""
    target, version = _resolve_version(group, worker, nid, token, rt)
    if rt is not None:
        # the store-outage no-time-travel floor (same stamp as
        # enforce_snaptoken): a degraded mirror answer below the minted
        # version must 503, never serve
        rt.min_version = version
    return target, version


def _resolve_version(group: ReplicaGroup, worker: ServeWorker, nid: str,
                     token: str, rt) -> tuple[ServeWorker, int]:
    min_v = parse_snaptoken(token, nid)
    local = worker.view.applied_version(nid)
    if min_v is None or min_v <= local:
        return worker, local
    # hold for catch-up within a slice of the deadline budget (half the
    # remaining budget, capped by the configured catch-up window): the
    # in-process tail applies pushed commits in microseconds, so this is
    # the common read-your-writes path
    budget = group.catchup_s
    deadline = getattr(rt, "deadline", None) if rt is not None else None
    if deadline is not None:
        budget = min(budget, deadline.remaining_s() * 0.5)
    local = worker.view.catch_up(nid, min_v, budget)
    if local >= min_v:
        group._count_route("caught_up")
        return worker, local
    fresh = group.fresh_worker(nid, min_v, exclude=worker)
    if fresh is not None:
        group._count_route("routed")
        return fresh, fresh.view.applied_version(nid)
    # every worker is behind the token: escalate to the live store
    # version — the shared engine always evaluates at the latest store
    # state, so the answer is fresh; a token ahead of the store itself
    # is the existing 409 contract
    current = group.registry.relation_tuple_manager().version(nid=nid)
    require_version(current, min_v)
    group._count_route("escalated")
    return worker, current


def _wait_result(batcher, pending, rt):
    """CheckBatcher.wait_pending with the hedge policy's latency feed."""
    return batcher.wait_pending(pending, rt)


def _hedged_ride(group: ReplicaGroup, worker: ServeWorker, t, max_depth: int,
                 nid, rt):
    """One check through `worker`'s batcher with deadline-budget-aware
    hedging: if the primary ride has not answered within the hedge
    policy's quantile delay, fire ONE duplicate onto another worker's
    batcher; first answer wins, the loser's pending is cancelled (a
    cancelled pending never occupies a device batch slot — the batchers
    skip done futures at their expiry boundary). Returns
    (CheckResult, covered_version | None) like check_versioned."""
    metrics = group.metrics
    deadline = getattr(rt, "deadline", None) if rt is not None else None
    t0 = time.perf_counter()
    primary = worker.batcher.submit(t, max_depth, nid=nid, rt=rt)
    hedge_after = group.hedge.hedge_after_s(deadline)
    if hedge_after is not None and deadline is not None:
        hedge_after = min(hedge_after, max(deadline.remaining_s(), 1e-4))
    if hedge_after is None:
        out = _wait_result(worker.batcher, primary, rt)
        group.hedge.observe(time.perf_counter() - t0)
        return out
    try:
        out = primary.future.result(timeout=hedge_after)
        group.hedge.observe(time.perf_counter() - t0)
        return out
    except FutureTimeoutError:
        pass
    other = group.hedge_worker(exclude=worker)
    if other is None:
        out = _wait_result(worker.batcher, primary, rt)
        group.hedge.observe(time.perf_counter() - t0)
        return out
    # the duplicate carries its own RequestTrace (child span, SAME
    # deadline): its launch ids accumulate separately, then merge onto
    # the caller's trace so the request log shows both rides
    hedge_rt = RequestTrace(
        rt.ctx.child() if rt is not None and rt.ctx is not None else None,
        deadline=deadline,
    )
    try:
        hedge = other.batcher.submit(t, max_depth, nid=nid, rt=hedge_rt)
    except OverloadedError:
        # the hedge target's queue is full or its batcher is draining:
        # hedging is a pure latency optimization, so a failed duplicate
        # must never fail the request — the healthy primary ride wins
        out = _wait_result(worker.batcher, primary, rt)
        group.hedge.observe(time.perf_counter() - t0)
        return out
    if metrics is not None:
        metrics.hedge_launched_total.inc()
    remaining = None
    if deadline is not None:
        remaining = max(deadline.remaining_s(), 1e-4)
    done, _ = futures_wait(
        {primary.future, hedge.future},
        timeout=remaining, return_when=FIRST_COMPLETED,
    )
    try:
        if not done:
            # neither ride answered inside the budget: the typed 504,
            # counted once (both pendings marked so the collectors'
            # queue-drop never double-counts)
            primary.dl_counted = hedge.dl_counted = True
            if metrics is not None:
                metrics.deadline_exceeded_total.labels("wait").inc()
            raise DeadlineExceededError(
                "request deadline expired waiting for the check batch"
            )
        winner = primary if primary.future in done else hedge
        loser = hedge if winner is primary else primary
        if loser.future.cancel() and metrics is not None:
            metrics.hedge_cancelled_total.inc()
        if metrics is not None:
            metrics.hedge_wins_total.labels(
                "primary" if winner is primary else "hedge"
            ).inc()
        group.hedge.observe(time.perf_counter() - t0)
        return winner.future.result()
    finally:
        # flight-recorder correlation: the hedge ride's launch ids join
        # the caller's trace whatever the outcome
        if rt is not None and hedge_rt.launch_ids:
            rt.launch_ids.extend(hedge_rt.launch_ids)


def serve_on(worker: ServeWorker, nid: str, t, max_depth: int, version: int,
              rt, hedged: bool = True):
    """The per-worker serve fast path (cache -> batcher -> store), the
    replica twin of check_cache.cached_check. `version` is the version
    the answer must be authoritative at (the worker's applied version or
    the escalated store version)."""
    cache = worker.cache
    res, gen = _fastpath_begin(cache, nid, t, max_depth, version, rt)
    if res is not None:
        worker.count_check()
        return res
    if hedged:
        res, computed_v = _hedged_ride(
            worker.group, worker, t, max_depth, nid, rt
        )
    else:
        res, computed_v = worker.batcher.check_versioned(
            t, max_depth, nid=nid, rt=rt
        )
    require_answer_floor(computed_v, version)
    if cache is not None:
        cache.store(nid, t, max_depth, res, computed_v, version, gen=gen)
    worker.count_check()
    return res


def replica_check(worker: ServeWorker, nid: str, t, max_depth: int,
                  token: str, rt):
    """The transports' replica-mode check path: snaptoken routing, then
    the answering worker's cache/batcher with hedging. Returns
    (CheckResult, version) — the version mints the response snaptoken."""
    group = worker.group
    target, version = resolve_version(group, worker, nid, token, rt)
    res = serve_on(target, nid, t, max_depth, version, rt)
    return res, version


async def replica_check_async(worker: ServeWorker, aio_batcher, nid: str, t,
                              max_depth: int, token: str, rt, loop,
                              executor):
    """The aio plane's replica check: the same routing rule; the fast
    path (applied version already satisfies the token) stays entirely
    in-loop — version read and cache lookup are dict operations. The
    slow paths (catch-up hold, routing to another worker's threaded
    stack) run on the executor. Hedging rides the threaded plane only:
    an aio check that routes executes on the target worker's threaded
    batcher (which hedges); an unrouted one rides this listener's own
    in-loop batcher unhedged — cross-loop duplicate cancellation is not
    worth the loop hops for the listener that already has no handoffs."""
    group = worker.group
    min_v = parse_snaptoken(token, nid)
    local = worker.view.applied_version(nid)
    if min_v is None or min_v <= local:
        version = local
        cache = worker.cache
        res, gen = _fastpath_begin(cache, nid, t, max_depth, version, rt)
        if res is not None:
            worker.count_check()
            return res, version
        res, computed_v = await aio_batcher.check_versioned(
            t, max_depth, nid=nid, rt=rt
        )
        require_answer_floor(computed_v, version)
        if cache is not None:
            cache.store(nid, t, max_depth, res, computed_v, version, gen=gen)
        worker.count_check()
        return res, version
    # behind the token: hold/route/escalate off-loop (condition waits and
    # store reads must not block the event loop)
    return await loop.run_in_executor(
        executor,
        lambda: replica_check(worker, nid, t, max_depth, token, rt),
    )
