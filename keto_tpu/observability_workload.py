"""Workload observatory + SLO plane (§5o).

The telemetry planes before this one see *requests* (request logs,
per-stage histograms) and *launches* (the flight recorder) — never the
*workload*. This module holds the three instruments that close that
gap, Zanzibar §4's production-monitoring story in process form:

  - per-(nid, namespace, relation) ACCOUNTING: sharded, lock-cheap
    counters for request rate, verdict mix, and answering-tier mix
    (cache | closure | device | host | vocab — the §5m explain tiers,
    now stamped on every request, not just explain=true ones), fed from
    the serve fast path on all three transports;
  - HEAVY-HITTER SKETCHES: bounded Space-Saving top-K over object keys,
    subject keys, and full check tuples per sliding window — the
    hot-spot instrument behind `GET /admin/hotkeys` and the
    `keto_tpu_hotkey_share` gauges ("the top 100 keys are X% of
    traffic, hit-ratio Y" as a scrapeable fact);
  - an SLO ENGINE: declarative objectives (served p95 ms, availability,
    max mirror staleness — defaults derived from BASELINE.json's north
    star) evaluated over short+long sliding windows into multi-window
    burn rates, `keto_tpu_slo_*` gauges, `GET /admin/slo`, and an
    always-emitted WARNING while a fast burn is active.

`profile()` renders the accounting + sketches as a committed-artifact
traffic profile (key-popularity histogram, per-nid mix, read/write
ratio) — `keto-tpu admin capture` writes it and `tools/load_gen.py
--profile` replays its shape, so saturation runs can be driven with
measured traffic instead of uniform synthetic queries.

Everything here is monotonic-clock only (wall clocks are banned
repo-wide) and stays off the serve path's critical microseconds: a
single check appends one small event tuple to a buffer under one short
lock, a BatchCheck appends ONE event for all of its answered items
(`record_check_batch`: columns of references, not 2,048 events), and
the actual folding (per-pair stats, sketch merges, prom children, SLO
buckets) runs on the daemon's folder thread four times a second, or,
with no folder (library use, tests), every `_FOLD_BATCH` checks or at
most ~1 s behind. A fold counts by columns (`collections.Counter` over
comprehensions) and hands each sketch its pre-aggregated counts as one
merge (`SpaceSaving.offer_many`), so a hot key's sixteen events cost
one sketch entry and a uniform fold one sort, not an eviction a key.
Read surfaces drain first, so nothing an admin endpoint or a test reads
is ever stale by more than the pending buffer. When `workload.enabled`
is false every record call returns after one attribute test.

What it costs, as measured. WORKLOAD_AB_r18.json, the on/off A/B the
plane was admitted on, is a CPU run of SINGLE checks at a few hundred a
second (0.985 on the served check leg) and says nothing of a batch.
Under 2,048-item BatchChecks at 38,000 checks a second the plane as it
was (an event, a lock and a Python fold an item, every second RPC
folding 4,096 events on its handler's thread) was half of the host's
time an RPC: 45 to 55 ms with it, 21 to 24 without (CPU stage sums,
ISSUE 32). The fold by columns costs about 1 us a check where the fold
by events took 4 to 5 (CPU timings; PERF.md section 6, PR 32, has the
chip's numbers), and `keto_tpu_workload_fold_seconds_total{where}`
keeps the account from here on.
"""

from __future__ import annotations

import heapq
import logging
import threading
import time
from collections import Counter
from operator import itemgetter
from typing import Callable, Optional

from .ketoapi import CheckColumns

logger = logging.getLogger("keto_tpu")

# the answering-tier vocabulary (§5m's explain tiers + the REST-only
# vocab corner); "other" buckets requests that finished without a stamp
# (non-check requests, multi-split residue)
TIERS = ("cache", "closure", "device", "host", "vocab", "other")

PROFILE_SCHEMA = "keto-tpu-workload-profile/1"

# whose thread paid for a fold (the `where` label of
# keto_tpu_workload_fold_seconds_total / _folded_checks_total): the
# daemon's folder thread, or whoever else drained — a handler past the
# valve, a read surface, an embedder with no folder
FOLD_WHERE = ("folder", "inline")

# method substrings that classify a request as a WRITE for the
# read/write-ratio accounting (REST write plane verbs + the write-plane
# gRPC service methods); everything else counts as a read
_WRITE_MARKERS = (
    "PUT ", "PATCH ", "DELETE ",
    "TransactRelationTuples", "DeleteRelationTuples",
)

# gRPC status names that count against the availability objective; the
# HTTP side counts 5xx. Client-caused outcomes (bad input, unknown
# routes, shed 429s with a Retry-After the client asked for) and the
# 403 a DENIED check answers with (reference parity: denial IS the
# answer) are served requests, not unavailability.
_BAD_GRPC_CODES = frozenset((
    "INTERNAL", "UNAVAILABLE", "DEADLINE_EXCEEDED", "UNKNOWN",
    "DATA_LOSS", "ABORTED",
))


def code_is_ok(code: str) -> bool:
    """Availability classification for a transport outcome code (HTTP
    numeric string or gRPC status name)."""
    if code in _BAD_GRPC_CODES:
        return False
    if len(code) == 3 and code.isdigit():
        return code[0] != "5"
    return True


def subject_key(t) -> str:
    """The sketch key for a tuple's subject: the plain id, or the
    subject set rendered in its (ns:obj#rel) display form."""
    if t.subject_id is not None:
        return t.subject_id
    s = t.subject_set
    return f"({s.namespace}:{s.object}#{s.relation})"


class SpaceSaving:
    """Bounded top-K frequency sketch (Metwally's Space-Saving): at most
    `capacity` tracked keys; when a new key arrives at capacity the
    current minimum is EVICTED and the newcomer inherits its count as
    overestimation error (`err`). Guarantees: every key with true count
    > total/capacity is present, and reported counts overestimate by at
    most `err` — exactly the hot-spot question's shape (is this key
    hot?), at O(capacity) memory regardless of key cardinality.

    Min tracking rides a lazy-deletion heap: updates leave stale heap
    entries behind (a stale count is always a LOWER bound, so the heap
    top remains a valid minimum candidate); eviction pops until the top
    is fresh. Offers are O(log capacity) amortized; a fold's worth of
    pre-aggregated keys goes through `offer_many`, one merge in place
    of one eviction a key. Not thread-safe — callers hold their own
    lock (one sketch update is a few dict ops; the lock is cheaper than
    sharding the sketch)."""

    __slots__ = ("capacity", "total", "_counts", "_heap")

    def __init__(self, capacity: int):
        self.capacity = max(int(capacity), 1)
        self.total = 0  # every offer, tracked and not
        # key -> [count, err]
        self._counts: dict[str, list] = {}
        self._heap: list[tuple[int, str]] = []  # (count-at-push, key)

    def offer(self, key: str, n: int = 1) -> None:
        self.total += n
        e = self._counts.get(key)
        if e is not None:
            e[0] += n
            return
        if len(self._counts) < self.capacity:
            self._counts[key] = [n, 0]
            heapq.heappush(self._heap, (n, key))
            return
        # evict the true minimum: pop stale entries (count moved on
        # since push) back in at their current count until the top is
        # fresh, then replace it
        while True:
            cnt, victim = self._heap[0]
            cur = self._counts[victim][0]
            if cur == cnt:
                break
            heapq.heapreplace(self._heap, (cur, victim))
        del self._counts[victim]
        heapq.heapreplace(self._heap, (cnt + n, key))
        self._counts[key] = [cnt + n, cnt]

    # a merge walks every tracked entry and rebuilds the heap whatever
    # the fold's size, an eviction touches one: on this many new keys a
    # tracked slot the two cost the same (CPU timing, capacity 256: a
    # merge 45 us and 0.2 us a new key, an eviction 0.6 us), and under
    # it `offer_many` is so many `offer`s
    _MERGE_FROM = 1 / 2

    def offer_many(self, counts) -> None:
        """Every key of `counts` ({key: n}, a fold's pre-aggregate)
        offered n times, as one merge of mergeable summaries (Agarwal
        et al., PODS 2012): tracked keys add their n; new keys enter as
        (m + n, m), m the minimum tracked count before the merge (0
        while there is free room: nothing was evicted yet, so an
        untracked key was never seen); the `capacity` largest of
        tracked and new stay. Every guarantee of `offer` holds: each
        dropped entry counted at least m, so the tracked counts still
        sum to at most `total`; a new key's true count was at most m
        before this fold. What differs is which of several equal
        counts stays, which a fold's dict order never defined either."""
        tracked = self._counts
        capacity = self.capacity
        hits = tracked.keys() & counts.keys()
        if len(counts) - len(hits) < capacity * self._MERGE_FROM:
            for key, n in counts.items():
                self.offer(key, n)
            return
        self.total += sum(counts.values())
        m = (
            0 if len(tracked) < capacity
            else min(map(itemgetter(0), tracked.values()))
        )
        for key in hits:
            tracked[key][0] += counts[key]
        # list.sort and sorted, not heapq.nlargest: they are C, it is a
        # Python loop an entry; equal counts (a uniform fold's ones) are
        # one run to them. At most `capacity` new keys can stay
        ranked = sorted(counts, key=counts.__getitem__, reverse=True)
        new = [key for key in ranked[:capacity + len(hits)] if key not in hits]
        entries = [(e[0], key, e) for key, e in tracked.items()]
        entries += [(m + counts[key], key, None) for key in new[:capacity]]
        if len(entries) > capacity:
            entries.sort(key=itemgetter(0), reverse=True)
            del entries[capacity:]
        self._counts = {
            key: e if e is not None else [cnt, m] for cnt, key, e in entries
        }
        self._heap = [(cnt, key) for cnt, key, _ in entries]
        heapq.heapify(self._heap)

    def top(self, k: int) -> list[tuple[str, int, int]]:
        """[(key, count, err)] for the k largest tracked counts."""
        items = sorted(
            self._counts.items(), key=lambda kv: kv[1][0], reverse=True
        )
        return [(key, e[0], e[1]) for key, e in items[:k]]

    def __len__(self) -> int:
        return len(self._counts)


class WindowedSketch:
    """A Space-Saving sketch per jumping window: offers land in the
    CURRENT generation; every `window_s` seconds the current generation
    rotates to `previous` and a fresh one starts. Queries merge both
    generations, so a read just after rotation still sees a full
    window's heat instead of an empty sketch — the answer always covers
    between one and two windows of traffic (the bound §5o documents;
    a true sliding window would cost a generation per sub-interval for
    no decision the hot-spot question needs)."""

    __slots__ = ("capacity", "window_s", "_cur", "_prev", "_rotated_at")

    def __init__(self, capacity: int, window_s: float):
        self.capacity = max(int(capacity), 1)
        self.window_s = float(window_s)
        self._cur = SpaceSaving(self.capacity)
        self._prev: Optional[SpaceSaving] = None
        self._rotated_at = time.monotonic()

    def _maybe_rotate(self, now: float) -> None:
        if now - self._rotated_at >= self.window_s:
            self._prev = self._cur
            self._cur = SpaceSaving(self.capacity)
            self._rotated_at = now

    def offer(self, key: str, n: int = 1, now: Optional[float] = None) -> None:
        self._maybe_rotate(time.monotonic() if now is None else now)
        self._cur.offer(key, n)

    def offer_many(self, counts, now: Optional[float] = None) -> None:
        self._maybe_rotate(time.monotonic() if now is None else now)
        self._cur.offer_many(counts)

    def total(self) -> int:
        return self._cur.total + (self._prev.total if self._prev else 0)

    def top(self, k: int) -> list[tuple[str, int, int]]:
        """Merged top-k across both generations (counts summed, err
        maxed, so the overestimation bound survives the merge)."""
        merged: dict[str, list] = {}
        for gen in (self._cur, self._prev):
            if gen is None:
                continue
            for key, cnt, err in gen.top(gen.capacity):
                e = merged.get(key)
                if e is None:
                    merged[key] = [cnt, err]
                else:
                    e[0] += cnt
                    e[1] = max(e[1], err)
        items = sorted(
            merged.items(), key=lambda kv: kv[1][0], reverse=True
        )
        return [(key, e[0], e[1]) for key, e in items[:k]]

    def share_of_top(self, k: int) -> float:
        """Fraction of ALL window traffic (tracked + evicted) answered
        by the top-k keys — the cache-attribution number."""
        total = self.total()
        if total <= 0:
            return 0.0
        return min(1.0, sum(cnt for _, cnt, _ in self.top(k)) / total)


class _PairStats:
    """Per-(nid, namespace, relation) accumulator: request count,
    verdict mix, answering-tier mix."""

    __slots__ = ("requests", "allowed", "denied", "tiers")

    def __init__(self):
        self.requests = 0
        self.allowed = 0
        self.denied = 0
        self.tiers: dict[str, int] = {}

    def as_dict(self) -> dict:
        return {
            "requests": self.requests,
            "allowed": self.allowed,
            "denied": self.denied,
            "tiers": dict(self.tiers),
        }


class _Shard:
    __slots__ = ("lock", "pairs")

    def __init__(self):
        self.lock = threading.Lock()
        self.pairs: dict[tuple, _PairStats] = {}


# -- SLO engine ----------------------------------------------------------------

# budget fraction per objective kind: a p95 target tolerates 5% slow
# events by definition; availability/staleness budgets derive from the
# target itself
_P95_BUDGET = 0.05


class SLOEngine:
    """Multi-window burn-rate tracker over declarative objectives.

    Objectives (config `slo.objectives.*`, defaults from BASELINE.json's
    north star):
      served_p95_ms    — an event is BAD when its served duration
                         exceeds the target; budget is 5% (that is what
                         p95 means)
      availability     — BAD when the request finished with an error
                         code; budget is 1 - target
      max_staleness_s  — BAD when the sampled mirror staleness age
                         exceeds the target (sampled once per
                         evaluation tick from the built engines);
                         budget is 5%

    Events land in per-second ring buckets covering the LONG window;
    burn rate over a window = (bad fraction) / budget — 1.0 means
    exactly spending the budget, >1 means burning ahead of it. A FAST
    BURN is burn > `slo.fast_burn_threshold` on BOTH the short and the
    long window (the Google SRE multi-window rule: the short window
    catches the spike, the long window keeps one blip from paging).
    While fast-burning, every evaluation tick (at most 1/s) emits a
    WARNING — never sampled, never rate-limited away: a swallowed burn
    warning is exactly the evidence an incident needs."""

    def __init__(
        self,
        objectives: dict,
        window_short_s: float = 300.0,
        window_long_s: float = 3600.0,
        fast_burn_threshold: float = 14.0,
        metrics=None,
        staleness_probe: Optional[Callable[[], float]] = None,
    ):
        self.objectives = dict(objectives)
        self.window_short_s = float(window_short_s)
        self.window_long_s = max(float(window_long_s), self.window_short_s)
        self.fast_burn_threshold = float(fast_burn_threshold)
        self.metrics = metrics
        self.staleness_probe = staleness_probe
        self._lock = threading.Lock()
        # ring of per-second buckets spanning the long window:
        # [second_id, {objective: [total, bad]}] — a slot is lazily
        # reclaimed when its second comes around again
        self._size = int(self.window_long_s) + 2
        self._ring: list = [None] * self._size
        self._last_eval_sec = -1
        self._fast_burn: dict[str, bool] = {
            name: False for name in self.objectives
        }
        self._budgets = {
            name: self._budget_for(name, target)
            for name, target in self.objectives.items()
        }
        if metrics is not None:
            for name, target in self.objectives.items():
                metrics.slo_objective_target.labels(name).set(target)

    @staticmethod
    def _budget_for(name: str, target: float) -> float:
        if name == "availability":
            return max(1.0 - float(target), 1e-9)
        return _P95_BUDGET

    def _bucket(self, sec: int):
        slot = self._ring[sec % self._size]
        if slot is None or slot[0] != sec:
            slot = [sec, {}]
            self._ring[sec % self._size] = slot
        return slot[1]

    def _mark_locked(self, sec: int, name: str, bad: bool) -> None:
        b = self._bucket(sec)
        cell = b.get(name)
        if cell is None:
            cell = b[name] = [0, 0]
        cell[0] += 1
        if bad:
            cell[1] += 1

    def record(
        self, duration_s: float, ok: bool, now: Optional[float] = None,
        latency_eligible: bool = True,
    ) -> None:
        """One finished request: feeds the latency and availability
        objectives, then (at most once per second) evaluates burn
        rates. `now` is injectable for tests; serving passes None.
        `latency_eligible=False` exempts by-design-long requests (SSE
        watch streams) from the latency objective — they still count
        for availability."""
        now = time.monotonic() if now is None else now
        sec = int(now)
        warn = None
        with self._lock:
            p95_ms = self.objectives.get("served_p95_ms")
            if p95_ms is not None and latency_eligible:
                self._mark_locked(
                    sec, "served_p95_ms", duration_s * 1e3 > p95_ms
                )
            if "availability" in self.objectives:
                self._mark_locked(sec, "availability", not ok)
            if sec != self._last_eval_sec:
                self._last_eval_sec = sec
                warn = self._evaluate_locked(now)
        # logging happens OUTSIDE the lock (repo rule: nothing that can
        # block — a formatting handler included — runs under a lock)
        if warn:
            for level, line in warn:
                logger.log(level, *line)

    def _sample_staleness_locked(self, now: float) -> None:
        if self.staleness_probe is None:
            return
        target = self.objectives.get("max_staleness_s")
        if target is None:
            return
        try:
            age = self.staleness_probe()
        except Exception:  # noqa: BLE001 — a probe must never fail a request
            return
        if age is None:
            return
        self._mark_locked(int(now), "max_staleness_s", age > target)

    def _window_locked(self, name: str, window_s: float, now: float):
        """(total, bad) over the trailing window. The window start is
        quantized to whole seconds — a window of W covers the last W
        FULL seconds plus the current partial one — because events
        bucket by integer second: an unquantized start would drop the
        whole previous bucket the instant a second rolls over, leaving
        an evaluation tick (which fires on the FIRST event of a new
        second) a near-empty short window that flaps burn to zero."""
        lo = int(now) - window_s
        total = bad = 0
        for slot in self._ring:
            if slot is None or slot[0] < lo:
                continue
            cell = slot[1].get(name)
            if cell is not None:
                total += cell[0]
                bad += cell[1]
        return total, bad

    def _burn_locked(self, name: str, window_s: float, now: float) -> float:
        total, bad = self._window_locked(name, window_s, now)
        if total <= 0:
            return 0.0
        return (bad / total) / self._budgets[name]

    def _evaluate_locked(self, now: float):
        """Once-per-second tick: staleness sample, gauges, fast-burn
        transitions. Returns WARNING lines to emit outside the lock."""
        self._sample_staleness_locked(now)
        warnings = []
        for name in self.objectives:
            burn_short = self._burn_locked(name, self.window_short_s, now)
            burn_long = self._burn_locked(name, self.window_long_s, now)
            if self.metrics is not None:
                self.metrics.slo_burn_rate.labels(name, "short").set(
                    burn_short
                )
                self.metrics.slo_burn_rate.labels(name, "long").set(
                    burn_long
                )
            fast = (
                burn_short > self.fast_burn_threshold
                and burn_long > self.fast_burn_threshold
            )
            was = self._fast_burn[name]
            self._fast_burn[name] = fast
            if self.metrics is not None:
                self.metrics.slo_fast_burn_active.labels(name).set(
                    1.0 if fast else 0.0
                )
                if fast and not was:
                    self.metrics.slo_fast_burn_total.labels(name).inc()
            if fast:
                # emitted EVERY tick while burning (at most 1/s): the
                # log is incident evidence, not a notification
                warnings.append((logging.WARNING, (
                    "slo fast burn objective=%s burn_short=%.2f "
                    "burn_long=%.2f threshold=%.2f target=%s",
                    name, burn_short, burn_long,
                    self.fast_burn_threshold, self.objectives[name],
                )))
            elif was:
                warnings.append((logging.INFO, (
                    "slo burn recovered objective=%s burn_short=%.2f "
                    "burn_long=%.2f",
                    name, burn_short, burn_long,
                )))
        return warnings

    def status(self, now: Optional[float] = None) -> dict:
        now = time.monotonic() if now is None else now
        out: dict = {
            "window_short_s": self.window_short_s,
            "window_long_s": self.window_long_s,
            "fast_burn_threshold": self.fast_burn_threshold,
            "now_mono": now,
            "objectives": {},
        }
        with self._lock:
            for name, target in self.objectives.items():
                tot_s, bad_s = self._window_locked(
                    name, self.window_short_s, now
                )
                tot_l, bad_l = self._window_locked(
                    name, self.window_long_s, now
                )
                out["objectives"][name] = {
                    "target": target,
                    "budget": self._budgets[name],
                    "burn_short": (
                        0.0 if tot_s <= 0
                        else (bad_s / tot_s) / self._budgets[name]
                    ),
                    "burn_long": (
                        0.0 if tot_l <= 0
                        else (bad_l / tot_l) / self._budgets[name]
                    ),
                    "events_short": tot_s,
                    "bad_short": bad_s,
                    "events_long": tot_l,
                    "bad_long": bad_l,
                    "fast_burn": self._fast_burn[name],
                }
        return out


# -- a fold's checks as columns -------------------------------------------------


def _field_columns(tuples) -> tuple[list, list, list, list]:
    """(namespaces, objects, relations, subjects) of a run of tuples:
    references to the tuples' own strings (a subject set rides as the
    object it is), so whoever holds the columns holds no tuple. A
    BatchCheck that came as CheckColumns hands over the lists it has."""
    if isinstance(tuples, CheckColumns):
        return tuples.ns, tuples.obj, tuples.rel, tuples.subjects()
    return (
        [t.namespace for t in tuples],
        [t.object for t in tuples],
        [t.relation for t in tuples],
        [s if (s := t.subject_id) is not None else t.subject_set for t in tuples],
    )


def _count_keys(keys: dict, columns) -> None:
    """Add one run of checks to a fold's sketch-key counters. The keys
    are built here, on the folding thread: `namespace:object`, the
    subject as `subject_key` renders it, and the tuple's canonical
    string (`str(RelationTuple)`), which is the one joined to the
    other."""
    ns, objs, rels, subs = columns
    okeys = [f"{n}:{o}" for n, o in zip(ns, objs)]
    skeys = [s if type(s) is str else f"({s})" for s in subs]
    keys["object"].update(okeys)
    keys["subject"].update(skeys)
    keys["check"].update(
        [f"{o}#{r}@{s}" for o, r, s in zip(okeys, rels, skeys)]
    )


# -- the observatory -----------------------------------------------------------


class WorkloadObservatory:
    """The per-process workload plane: accounting shards + hot-key
    sketches + the SLO engine, one object built by the registry and fed
    from the serve fast path (`check_cache.cached_check*`) and
    `finish_request_telemetry` on all three transports.

    `enabled` gates the accounting/sketch half with a bare attribute
    read (the A/B off arm); the SLO engine has its own `slo_enabled`
    gate. Both off = every record call returns after one attribute
    test."""

    def __init__(
        self,
        enabled: bool = True,
        shards: int = 8,
        hotkey_capacity: int = 256,
        hotkey_window_s: float = 60.0,
        slo: Optional[SLOEngine] = None,
        metrics=None,
    ):
        self.enabled = bool(enabled)
        self.metrics = metrics
        self.slo = slo
        self._nshards = max(int(shards), 1)
        self._shards = [_Shard() for _ in range(self._nshards)]
        self._sketch_lock = threading.Lock()
        self.sketches = {
            kind: WindowedSketch(hotkey_capacity, hotkey_window_s)
            for kind in ("object", "subject", "check")
        }
        self._rw_lock = threading.Lock()
        self._reads = 0
        self._writes = 0
        # bounded label-child cache for the per-pair counter (vocabulary
        # is bounded by the configured namespaces x relations x tiers x
        # verdicts; .labels() walks locked dicts, see Metrics.observe_*)
        self._pair_cache: dict[tuple, object] = {}
        self._hotkey_gauge_sec = -1
        if metrics is not None:
            for where in FOLD_WHERE:  # both series from the first scrape
                metrics.workload_fold_seconds_total.labels(where)
                metrics.workload_folded_checks_total.labels(where)
        # the feed buffers: record_check/observe_request append one event
        # tuple and return, record_check_batch one event of columns for
        # a whole BatchCheck; _drain() folds what is pending every
        # _FOLD_BATCH checks or ~1 s, whichever first — the serve path
        # pays one append, not the sketch/stats/prom walk
        self._buf_lock = threading.Lock()
        self._check_buf: list[tuple] = []
        self._batch_buf: list[tuple] = []
        self._batch_checks = 0  # items held by _batch_buf's events
        self._req_buf: list[tuple] = []
        self._last_fold = time.monotonic()
        # method -> is-write classification cache (the method vocabulary
        # is the bounded set of route constants + gRPC method names)
        self._rw_class: dict[str, bool] = {}
        # the optional folder thread (daemon-owned: start_folder in
        # Daemon.start, stop_folder in Daemon.stop); while it runs, the
        # serve path folds inline only past _FOLD_CAP — a fold costs
        # about a microsecond a check on the one interpreter, and
        # carrying it on a handler's thread is the median-vs-tail
        # distortion the A/B bar catches
        self._folder: Optional[threading.Thread] = None
        self._folder_stop = threading.Event()

    # -- feed points -----------------------------------------------------------

    # inline-fold cadence WITHOUT a folder thread (library use, unit
    # tests): fold once this many checks and requests are pending or
    # ~1 s passes. With the folder thread running (daemon mode) the
    # inline trigger backs off to _FOLD_CAP, which bounds memory and
    # nothing else: it counts CHECKS pending (a batch event counts its
    # items), each of which holds five list slots and keeps its object
    # and subject strings alive, some 200 B, so a full valve is about
    # 13 MB and one inline fold of about 0.1 s.
    # The folder wakes 4 times a second; at the 60,000 checks a second
    # of a host-bound BatchCheck cell (PERF.md, PR 32) that is 15,000
    # pending at a wake-up, so the valve opens only when the folder
    # has not run for a second. At 4,096, as it was until PR 32, two
    # 2,048-item RPCs filled it and every second RPC folded inline.
    _FOLD_BATCH = 16
    _FOLD_CAP = 65536

    def start_folder(self, interval_s: float = 0.25) -> None:
        """Start the background folder (idempotent): pending events fold
        on this thread every `interval_s`, so a serve thread's cost is
        one buffer append, never the fold itself."""
        if self._folder is not None:
            return
        self._folder_stop.clear()

        def run() -> None:
            while not self._folder_stop.wait(interval_s):
                self._drain(where="folder")

        self._folder = threading.Thread(
            target=run, name="keto-workload-fold", daemon=True
        )
        self._folder.start()

    def stop_folder(self) -> None:
        """Stop the folder and fold whatever is still pending — a
        drained daemon leaves no accounting on the floor."""
        folder = self._folder
        if folder is None:
            return
        self._folder_stop.set()
        folder.join(timeout=5)
        self._folder = None
        self._drain()

    def record_check(self, nid: str, t, allowed: bool, tier=None) -> None:
        """One answered check, from the serve fast path: enqueue one
        event — the tuple object rides the buffer as-is (it is never
        mutated after parse) and the fold builds the sketch keys."""
        if not self.enabled:
            return
        with self._buf_lock:
            self._check_buf.append((nid, t, allowed, tier))
            pending = self._pending_locked()
        self._fold_if_full(pending)

    def record_check_batch(self, nid: str, tuples, allowed, tier=None) -> None:
        """The answered items of one BatchCheck (`allowed[i]` is the
        verdict on `tuples[i]`; errored items are the caller's to leave
        out), as RelationTuples or as the CheckColumns the handler read
        off the wire: enqueue ONE event for all of them. It holds the items'
        fields as columns of string references, not the tuples: 2,048
        tuples pinned until the fold are 2,048 more objects for the
        collector to promote and walk, five lists are five."""
        if not self.enabled or not tuples:
            return
        event = (nid, tier, list(allowed), _field_columns(tuples))
        with self._buf_lock:
            self._batch_buf.append(event)
            self._batch_checks += len(tuples)
            pending = self._pending_locked()
        self._fold_if_full(pending)

    def _pending_locked(self) -> int:
        return len(self._check_buf) + self._batch_checks + len(self._req_buf)

    def _fold_if_full(self, pending: int) -> None:
        limit = self._FOLD_BATCH if self._folder is None else self._FOLD_CAP
        if pending >= limit:
            self._drain()

    def observe_request(
        self,
        method: str,
        code: str,
        duration_s: float,
        tier=None,
        trace_id=None,
        ok: Optional[bool] = None,
        latency_eligible: bool = True,
    ) -> None:
        """One finished request (any transport, any method), from
        finish_request_telemetry: enqueue one event carrying its own
        monotonic stamp (the SLO ring buckets by second, so a folded
        event must land in the second it FINISHED in, not the second it
        was folded in) plus whether accounting was on at enqueue time —
        the fold must not re-gate on a flag that may have flipped."""
        acct = self.enabled
        if not acct and self.slo is None:
            return
        now = time.monotonic()
        with self._buf_lock:
            self._req_buf.append((
                method, code, duration_s, tier, trace_id, ok,
                latency_eligible, now, acct,
            ))
            pending = self._pending_locked()
            stale = now - self._last_fold >= 1.0
        if stale and self._folder is None:
            self._drain()
        else:
            self._fold_if_full(pending)

    # -- the fold --------------------------------------------------------------

    def _drain(self, where: str = "inline") -> None:
        """Fold every pending event into the real sinks. Swaps the
        buffers under the buffer lock, folds OUTSIDE it (the fold takes
        the shard/sketch/slo/prom locks; never nested under the buffer
        lock). Concurrent drains each fold their own swapped batch.
        `where` says whose thread pays: the folder's, or ("inline") a
        handler's, a reader's or an embedder's."""
        with self._buf_lock:
            checks, self._check_buf = self._check_buf, []
            batches, self._batch_buf = self._batch_buf, []
            n_checks = len(checks) + self._batch_checks
            self._batch_checks = 0
            reqs, self._req_buf = self._req_buf, []
            self._last_fold = time.monotonic()
        if not (n_checks or reqs):
            return
        # this thread's CPU time, not the wall's: what the fold takes of
        # the one interpreter, without its waits for the lock on it
        t0 = time.thread_time()
        if n_checks:
            self._fold_checks(checks, batches)
        if reqs:
            self._fold_requests(reqs)
        if self.metrics is not None:
            self.metrics.workload_fold_seconds_total.labels(where).inc(
                time.thread_time() - t0
            )
            self.metrics.workload_folded_checks_total.labels(where).inc(n_checks)

    def _fold_checks(self, checks: list[tuple], batches: list[tuple]) -> None:
        """Count a fold's checks by columns — per (nid, pair, tier,
        verdict) and per sketch key, `Counter` over comprehensions, no
        Python statement an item — then apply each aggregate under its
        lock once: a hot key's sixteen events cost one sketch entry
        with n=16, and each sketch takes the fold as one merge."""
        # (nid, namespace, relation, tier, verdict) -> checks
        acct: Counter = Counter()
        keys = {"object": Counter(), "subject": Counter(), "check": Counter()}
        if checks:
            # the single checks, seen as one more run of columns
            nids, tuples, allowed, tiers = zip(*checks)
            columns = _field_columns(tuples)
            acct.update(zip(nids, columns[0], columns[2], tiers, allowed))
            _count_keys(keys, columns)
        for nid, tier, allowed, columns in batches:
            # one nid and one tier a batch: count the short tuples
            run = Counter(zip(columns[0], columns[2], allowed))
            for (ns, rel, verdict), n in run.items():
                acct[(nid, ns, rel, tier, verdict)] += n
            _count_keys(keys, columns)
        by_pair: dict[tuple, list] = {}
        by_child: Counter = Counter()
        for (nid, ns, rel, tier, allowed), n in acct.items():
            tier = tier if tier in TIERS else "other"
            agg = by_pair.get((nid, ns, rel))
            if agg is None:
                agg = by_pair[(nid, ns, rel)] = [0, 0, 0, Counter()]
            agg[0] += n
            agg[1 if allowed else 2] += n
            agg[3][tier] += n
            by_child[(ns, rel, tier, bool(allowed))] += n
        for pair, agg in by_pair.items():
            shard = self._shards[hash(pair) % self._nshards]
            with shard.lock:
                st = shard.pairs.get(pair)
                if st is None:
                    st = shard.pairs[pair] = _PairStats()
                st.requests += agg[0]
                st.allowed += agg[1]
                st.denied += agg[2]
                for tier, n in agg[3].items():
                    st.tiers[tier] = st.tiers.get(tier, 0) + n
        now = time.monotonic()
        with self._sketch_lock:
            for kind, counts in keys.items():
                self.sketches[kind].offer_many(counts, now=now)
        if self.metrics is not None:
            for ckey, n in by_child.items():
                child = self._pair_cache.get(ckey)
                if child is None:
                    ns, rel, tier, allowed = ckey
                    child = self._pair_cache[ckey] = (
                        self.metrics.workload_requests_total.labels(
                            ns, rel, tier,
                            "allowed" if allowed else "denied",
                        )
                    )
                child.inc(n)

    def _method_is_write(self, method: str) -> bool:
        is_write = self._rw_class.get(method)
        if is_write is None:
            is_write = any(m in method for m in _WRITE_MARKERS)
            if len(self._rw_class) < 512:  # vocabulary is route constants
                self._rw_class[method] = is_write
        return is_write

    def _fold_requests(self, events: list[tuple]) -> None:
        reads = writes = 0
        slo = self.slo
        for (method, code, duration_s, tier, trace_id, ok,
             latency_eligible, now, acct) in events:
            if acct:
                if self._method_is_write(method):
                    writes += 1
                else:
                    reads += 1
                if tier in TIERS and self.metrics is not None:
                    self.metrics.observe_tier(tier, duration_s, trace_id)
            if slo is not None:
                if ok is None:
                    ok = code_is_ok(code)
                slo.record(
                    duration_s, ok, now=now,
                    latency_eligible=latency_eligible,
                )
        if reads or writes:
            with self._rw_lock:
                self._reads += reads
                self._writes += writes
        if self.enabled and self.metrics is not None:
            self._maybe_refresh_hotkey_gauges()

    def note_staleness(self, age_s: float) -> None:
        """Optional direct staleness feed (beside the engine's probe)
        for planes that learn a concrete served-staleness age."""
        slo = self.slo
        if slo is None:
            return
        target = slo.objectives.get("max_staleness_s")
        if target is None:
            return
        with slo._lock:
            slo._mark_locked(
                int(time.monotonic()), "max_staleness_s", age_s > target
            )

    def _maybe_refresh_hotkey_gauges(self) -> None:
        sec = int(time.monotonic())
        if sec == self._hotkey_gauge_sec:
            return
        self._hotkey_gauge_sec = sec
        with self._sketch_lock:
            for kind in ("object", "subject"):
                sk = self.sketches[kind]
                for k in (1, 10, 100):
                    self.metrics.hotkey_share.labels(kind, str(k)).set(
                        sk.share_of_top(k)
                    )

    # -- read surfaces ---------------------------------------------------------

    def hotkeys(self, top: int = 100, cache_stats=None) -> dict:
        """The `GET /admin/hotkeys` payload: per-kind top-K with counts,
        overestimation errors, and traffic shares, plus the check-cache
        attribution join (top-K share beside the cache hit ratio)."""
        self._drain()  # surfaces never lag the pending buffer
        out: dict = {
            "enabled": self.enabled,
            "now_mono": time.monotonic(),
            "kinds": {},
        }
        with self._sketch_lock:
            for kind, sk in self.sketches.items():
                total = sk.total()
                entries = [
                    {
                        "key": key,
                        "count": cnt,
                        "err": err,
                        "share": (cnt / total) if total else 0.0,
                    }
                    for key, cnt, err in sk.top(top)
                ]
                out["kinds"][kind] = {
                    "window_s": sk.window_s,
                    "capacity": sk.capacity,
                    "total": total,
                    "top": entries,
                    "top_share": {
                        str(k): sk.share_of_top(k) for k in (1, 10, 100)
                    },
                }
        if cache_stats is not None:
            # "the top 100 keys are X% of traffic, hit-ratio Y" in one
            # response: the attribution Zanzibar's hot-spot story runs on
            out["check_cache"] = cache_stats
        return out

    def accounting(self) -> dict:
        """Per-(nid, namespace, relation) stats, merged across shards."""
        self._drain()
        merged: dict = {}
        for shard in self._shards:
            with shard.lock:
                for (nid, ns, rel), st in shard.pairs.items():
                    merged[f"{nid}/{ns}#{rel}"] = st.as_dict()
        return merged

    def profile(self, top: int = 100) -> dict:
        """The capture/replay artifact (`keto-tpu admin capture` writes
        it; `tools/load_gen.py --profile` replays it): key-popularity
        histograms, per-nid/namespace mix, read/write ratio."""
        self._drain()
        with self._rw_lock:
            reads, writes = self._reads, self._writes
        acct = self.accounting()
        per_namespace: dict = {}
        total_requests = 0
        for key, st in acct.items():
            ns_rel = key.split("/", 1)[1]
            per_namespace[ns_rel] = st
            total_requests += st["requests"]
        key_popularity: dict = {}
        with self._sketch_lock:
            for kind, sk in self.sketches.items():
                total = sk.total()
                key_popularity[kind] = [
                    {
                        "key": key,
                        "count": cnt,
                        "share": (cnt / total) if total else 0.0,
                    }
                    for key, cnt, _err in sk.top(top)
                ]
        denom = reads + writes
        return {
            "schema": PROFILE_SCHEMA,
            "captured_requests": total_requests,
            "reads": reads,
            "writes": writes,
            "read_share": (reads / denom) if denom else 1.0,
            "write_share": (writes / denom) if denom else 0.0,
            "per_namespace": per_namespace,
            "key_popularity": key_popularity,
        }

    def slo_status(self) -> dict:
        if self.slo is None:
            return {"enabled": False, "objectives": {}}
        self._drain()
        out = self.slo.status()
        out["enabled"] = True
        return out


def build_observatory(config, metrics=None, staleness_probe=None):
    """Registry constructor: one WorkloadObservatory (with an embedded
    SLOEngine unless `slo.enabled` is false) from the `workload.*` and
    `slo.*` config keys. Objective defaults come from BASELINE.json's
    north star: p95 < 10 ms on the served check leg, three nines of
    availability, and a minute of tolerated mirror staleness (the
    degraded-serving plane's own default ceiling)."""
    slo = None
    if bool(config.get("slo.enabled", True)):
        objectives = {
            "served_p95_ms": float(
                config.get("slo.objectives.served_p95_ms", 10.0)
            ),
            "availability": float(
                config.get("slo.objectives.availability", 0.999)
            ),
            "max_staleness_s": float(
                config.get("slo.objectives.max_staleness_s", 60.0)
            ),
        }
        slo = SLOEngine(
            objectives,
            window_short_s=float(config.get("slo.window_short_s", 300.0)),
            window_long_s=float(config.get("slo.window_long_s", 3600.0)),
            fast_burn_threshold=float(
                config.get("slo.fast_burn_threshold", 14.0)
            ),
            metrics=metrics,
            staleness_probe=staleness_probe,
        )
    return WorkloadObservatory(
        enabled=bool(config.get("workload.enabled", True)),
        shards=int(config.get("workload.shards", 8)),
        hotkey_capacity=int(config.get("workload.hotkeys.capacity", 256)),
        hotkey_window_s=float(config.get("workload.hotkeys.window_s", 60.0)),
        slo=slo,
        metrics=metrics,
    )
