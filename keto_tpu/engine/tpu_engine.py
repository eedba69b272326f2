"""TPU check engine facade.

Owns the device mirror lifecycle and the batched check path:

  - snapshot management: one immutable `_EngineState` per store/config
    version — base GraphSnapshot + vocabulary overlay view + device
    tables + delta overlay. Writes refresh the fixed-shape delta overlay
    (engine/delta.py) in a NEW state object; a full rebuild (compaction)
    happens only on config changes, truncated change logs, or oversized
    deltas. Concurrent batches capture one state atomically and stay
    internally consistent.
  - batching front: single checks ride in padded buckets so the jitted
    kernel compiles once per (bucket, static-config) pair — the
    goroutine-per-branch concurrency of the reference becomes batch-
    dimension parallelism
  - exact-semantics fallback: queries flagged needs_host (AND/NOT rewrite
    islands, config-missing-relation errors, frontier overflow, delta-
    dirty rows) and queries whose namespace/object/relation never occur
    in the graph are re-evaluated by the host ReferenceEngine; proof
    trees always come from the host engine

The public surface mirrors check.Engine (CheckIsMember/CheckRelationTuple,
internal/check/engine.go:54-80) plus batch entry points the RPC layer's
micro-batcher feeds.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from .. import faults as _faults
from ..config import Config
from ..errors import StoreUnavailableError
from ..ketoapi import CheckColumns, RelationTuple, Subject, Tree
from ..storage.definitions import DEFAULT_NETWORK, Manager
from .definitions import (
    RESULT_IS_MEMBER,
    RESULT_NOT_MEMBER,
    CheckResult,
    Membership,
    paginate_names,
)
from ..observability import StageSpan, next_launch_id
from .device_feed import DeviceFeed
from .delta import SnapshotView, empty_delta_tables
from .kernel import (
    CAUSE_NAME_UNINDEXED,
    CAUSE_NAMES,
    _KERNEL_STATICS,
    check_kernel,
    device_tables,
    estimate_step_gather_bytes,
    kernel_static_config,
    launch_stats_dict,
    pack_snapshot_tables,
    snapshot_tables,
    table_nbytes,
)
from .reference import ReferenceEngine
from .snapshot import (
    ArrayMap,
    GraphSnapshot,
    build_snapshot,
    build_snapshot_columnar,
    encode_query_batch,
)

# the launch ladder: a batch of n items runs in the smallest power of two
# >= n, floor 16, so above the floor under half of a launch's static shape
# is padding. Every batched verb reads this one ladder.
_BUCKETS = tuple(1 << k for k in range(4, 15))

_paginate = paginate_names


def _tables_nbytes_by_key(tables) -> dict:
    """Device bytes of each table of a table dict (or of the mesh path's
    (sharded, replicated) tuple of dicts, a key's tables summed), as the
    device holds them (kernel.table_nbytes): a 64-lane int32 bucket row is
    512 B on the chip, twice its `nbytes`."""
    if tables is None:
        return {}
    if isinstance(tables, tuple):
        merged: dict = {}
        for part in tables:
            for k, v in _tables_nbytes_by_key(part).items():
                merged[k] = merged.get(k, 0) + v
        return merged
    return {k: table_nbytes(v) for k, v in tables.items()}


def _tables_devices(tables) -> list:
    """The devices that hold a table dict (or the mesh path's tuple of
    dicts), in id order."""
    parts = tables if isinstance(tables, tuple) else (tables,)
    devices = {d for part in parts for v in part.values() for d in v.devices()}
    return sorted(devices, key=lambda d: d.id)


def _tables_nbytes(tables) -> int:
    """The snapshot_hbm_bytes gauge: _tables_nbytes_by_key, summed."""
    return sum(_tables_nbytes_by_key(tables).values())


@contextlib.contextmanager
def _mirror_phase(phases: dict, name: str):
    """One phase of a mirror rebuild as a StageSpan `mirror.<name>`
    (`keto.mirror.<name>` in a profiler trace's host plane); its seconds
    go into `phases` when the phase ends without raising."""
    with StageSpan(f"mirror.{name}") as span:
        yield
    phases[name] = span.seconds


@dataclass
class _EngineState:
    """One consistent device-mirror generation. Immutable except for the
    lazily-built expand fields, which are only written under the engine
    lock and only transition None -> value."""

    snapshot: GraphSnapshot
    view: SnapshotView
    sharded: object  # ShardedSnapshot | None
    tables: object  # dict | (sharded_tables, replicated_tables)
    delta_np: dict
    base_version: int
    covered_version: int
    config_fp: int
    # False for a clean mirror: the kernel compiles out the delta-overlay
    # probes entirely (they're half the probe gathers per step)
    has_delta: bool = False
    # expand-kernel extras (lazy)
    expand_tables: Optional[dict] = None  # device full CSR + dirty tables
    fh_probes: Optional[int] = None
    base_decoder: object = None  # reverse vocab of the base snapshot only
    decoder: object = None  # base_decoder extended with the overlay
    # host mirror of the single-device full CSR (fh_* / f_*): retained so
    # an incremental compaction can PATCH the expand state (affected rows
    # only) instead of dropping it — a lazy full-CSR rebuild costs ~212 s
    # at 1e7 (SCALE_1e7_r04). ~1 GB extra host RAM at 1e7; "garbage"
    # counts tail-rewritten slots for the amortizing rebuild
    expand_np: Optional[dict] = None
    # reverse-reachability subsystem (lazy, engine/reverse_kernel.py):
    # host transposed mirror (patchable by incremental compaction, same
    # retention rationale as expand_np) + its device tables; the
    # list-subjects leg packs its device tables from the expand full CSR
    reverse_np: Optional[dict] = None
    reverse_tables: Optional[dict] = None
    subjects_tables: Optional[dict] = None
    subjects_probes: Optional[int] = None


class TPUCheckEngine:
    def __init__(
        self,
        manager: Manager,
        config: Config,
        nid: str = DEFAULT_NETWORK,
        frontier_cap: int = 1 << 14,
        rewrite_instr_cap: int = 8,
        mesh=None,
        metrics=None,
        tracer=None,
        auto_frontier: bool = True,
        flightrec=None,
    ):
        self.manager = manager
        self.config = config
        self.nid = nid
        # the frontier must hold at least one task per batched query
        self.frontier_cap = max(frontier_cap, _BUCKETS[0])
        # size each launch's frontier to its bucket: four slots a query
        # slot of the `_BUCKETS` ladder, `frontier_cap` at most (a step
        # costs what its frontier holds, padding included, so a 16-query
        # launch must not pay a 16k-task frontier). False pins every
        # launch at `frontier_cap` — for operators who sized it
        # explicitly to keep wide-fanout queries on-device (overflow
        # falls back to exact-but-slow host replay).
        self.auto_frontier = auto_frontier
        self._allowed_buckets = [b for b in _BUCKETS if b <= self.frontier_cap]
        self.rewrite_instr_cap = rewrite_instr_cap
        # multi-chip: a 1-D jax.sharding.Mesh shards the edge tables and
        # runs the SPMD kernel (keto_tpu/parallel); None = single device
        self.mesh = mesh
        self.reference = ReferenceEngine(manager, config)
        self._lock = threading.Lock()
        self._state: Optional[_EngineState] = None
        # mirror-checkpoint persistence runs OUTSIDE self._lock (an
        # O(edges) compressed write must not block check traffic) and is
        # throttled so frequent compaction cycles don't re-write it;
        # throttled snapshots are DEFERRED (timer), never dropped, so the
        # last compaction before an idle period still reaches disk
        self._persist_mu = threading.Lock()
        self._write_mu = threading.Lock()
        self._pending_persist: Optional[GraphSnapshot] = None
        self._persist_timer: Optional[threading.Timer] = None
        self._last_persist = 0.0
        self.persist_min_interval = float(
            config.get("check.mirror_persist_interval", 60.0)
        )
        # push-invalidation (watch hub): a write hook sets an event and a
        # lazy background refresher folds the delta in off the request
        # path — requests then find a state already covering the latest
        # store version instead of paying the refresh inline
        self._refresh_mu = threading.Lock()
        self._refresh_event: Optional[threading.Event] = None
        self._refresh_stopped = False
        self._notify_t = 0.0  # monotonic stamp of the oldest unserved poke
        # monotonic stamp of the last time a state provably covered the
        # store's CURRENT version (every successful _ensure_state):
        # during a store outage `now - _synced_t` is the mirror's
        # staleness AGE, the serve.check.degraded.max_staleness_s
        # ceiling's measurand (0.0 = never synced)
        self._synced_t = 0.0
        # device-path observability (served vs host-fallback checks);
        # `metrics` is an optional observability.Metrics mirror of the same.
        # host_cause splits host_checks by kernel CAUSE_* code (VERDICT r2
        # item 7: "host because AND/NOT overflow" must be distinguishable
        # from "host because error")
        self.stats = {
            "device_checks": 0,
            "host_checks": 0,
            "snapshot_builds": 0,
            "host_cause": {},
        }
        self.metrics = metrics
        # launch flight recorder (observability.FlightRecorder | None):
        # one ring entry per device launch, written at the resolve sync
        # point; launch ids are allocated process-wide either way so logs
        # and typed errors stay correlatable when recording is off
        self.flightrec = flightrec
        # what the host was doing whenever no check launch was queued
        # (keto_tpu_device_feed_seconds_total, launch_device_seconds)
        self.device_feed = DeviceFeed(metrics)
        # Leopard closure index (engine/closure.py): deep checks answered
        # in one probe step when the index covers them. `closure_enabled`
        # is an attribute (not re-read per batch) so the bench's A/B legs
        # can toggle it per call like the flight recorder
        self.closure_enabled = bool(config.get("closure.enabled", False))
        self._closure = None
        self._closure_mu = threading.Lock()
        if tracer is None:
            from ..observability import _NoopTracer

            tracer = _NoopTracer()
        self.tracer = tracer

    # -- snapshot lifecycle ---------------------------------------------------

    def notify_write(self) -> None:
        """Watch-hub push invalidation: called (via the registry commit
        listener) after every store commit for this nid. Only flips an
        event — the refresher thread does the work, and bursts of writes
        coalesce into one refresh. The per-request staleness check in
        _ensure_state stays as the correctness backstop (out-of-process
        writers, refresh races)."""
        if self._refresh_stopped:
            return
        ev = self._refresh_event
        if ev is None:
            with self._refresh_mu:
                ev = self._refresh_event
                if ev is None:
                    ev = threading.Event()
                    thread = threading.Thread(
                        target=self._push_refresh_loop,
                        args=(ev,),
                        name=f"keto-push-refresh-{self.nid}",
                        daemon=True,
                    )
                    self._refresh_event = ev
                    thread.start()
        if not ev.is_set():
            # stamp the OLDEST unserved poke: refresh_lag_seconds then
            # measures hook -> fold completion, including coalesced bursts
            self._notify_t = time.monotonic()
        ev.set()

    def stop_push_refresh(self) -> None:
        """End the refresher thread. Called when the registry evicts this
        engine from the per-tenant LRU — the thread's bound-method target
        would otherwise pin the evicted engine (and its device mirror) in
        memory forever."""
        self._refresh_stopped = True
        ev = self._refresh_event
        if ev is not None:
            ev.set()

    def _push_refresh_loop(self, ev: threading.Event) -> None:
        while True:
            ev.wait()
            if self._refresh_stopped:
                return
            ev.clear()
            try:
                self._ensure_state()
                self.stats["push_refreshes"] = (
                    self.stats.get("push_refreshes", 0) + 1
                )
                if self.metrics is not None and self._notify_t:
                    self.metrics.refresh_lag_seconds.set(
                        time.monotonic() - self._notify_t
                    )
            except Exception:  # noqa: BLE001 — background refresh must
                # never die; the per-request sync path will surface the
                # error to a caller who can handle it
                import logging

                logging.getLogger("keto_tpu").debug(
                    "push-invalidated mirror refresh failed", exc_info=True
                )

    def _ensure_state(self) -> _EngineState:
        """Returns one consistent engine state.

        A namespace-config change (rewrite programs compile into the
        tables), truncated/oversized change log, or missing change-log
        support compacts — full rebuild; otherwise writes since the base
        snapshot refresh only the fixed-shape delta overlay, so the write
        path never re-uploads the O(edges) tables nor recompiles XLA."""
        from .checkpoint import stable_fingerprint

        store_version = self.manager.version(nid=self.nid)
        namespaces = self.config.namespace_manager().namespaces()
        # process-stable so persisted mirror checkpoints stay comparable
        config_fp = stable_fingerprint([ns.to_dict() for ns in namespaces])
        persist_snap = None
        with self._lock:
            state = self._state
            rebuild = state is None or state.config_fp != config_fp
            if not rebuild and state.covered_version != store_version:
                state = self._delta_refresh(state, store_version)
                rebuild = state is None
            if rebuild:
                with self.tracer.span("engine.snapshot_build") as sp:
                    state, persist_snap = self._rebuild(
                        store_version, config_fp, namespaces
                    )
                    sp.set_attribute("tuples", state.snapshot.n_tuples)
            self._state = state
            self._synced_t = time.monotonic()
        if self.metrics is not None:
            self.metrics.mirror_staleness_age_seconds.set(0.0)
        if persist_snap is not None:
            self._maybe_persist(persist_snap)
        return state

    # -- store-outage degradation (storage/health.py's serve half) ------------

    def degraded_covered_version(self):
        """The store version the CURRENT mirror state covers, with ZERO
        store contact (the store is down when anyone asks) — what a
        degraded response's snaptoken is minted at. None = no state."""
        with self._lock:
            state = self._state
        return None if state is None else state.covered_version

    def mirror_staleness_age_s(self) -> float:
        """Seconds since this engine last confirmed its state covered
        the store's current version — the degraded-serving staleness
        ceiling's measurand. Infinity when never synced."""
        if not self._synced_t:
            return float("inf")
        return time.monotonic() - self._synced_t

    def _degraded_state(self, cause, surface: str) -> _EngineState:
        """The bounded-stale serving gate: the existing mirror state,
        iff the shared degraded-serving rule (storage/health.py
        degraded_gate — one policy for this gate AND snaptoken
        enforcement) permits it: breaker fail-fast, a state exists, age
        under serve.check.degraded.max_staleness_s, and the ambient
        request's snaptoken floor (RequestTrace.min_version, stamped by
        enforce_snaptoken) not above the state's covered version.
        Anything else re-raises the typed 503: a degraded answer is
        byte-identical to an authoritative answer at its snaptoken or
        it is not served at all."""
        from ..observability import current_request_trace
        from ..storage.health import degraded_gate

        with self._lock:
            state = self._state
        age = self.mirror_staleness_age_s()
        if self.metrics is not None and state is not None:
            self.metrics.mirror_staleness_age_seconds.set(
                0.0 if age == float("inf") else age
            )
        rt = current_request_trace()
        degraded_gate(
            cause,
            None if state is None else state.covered_version,
            age,
            self.config.get("serve.check.degraded.max_staleness_s"),
            getattr(rt, "min_version", None) if rt is not None else None,
        )
        self.stats["degraded_serves"] = (
            self.stats.get("degraded_serves", 0) + 1
        )
        if self.metrics is not None:
            self.metrics.store_degraded_serves_total.labels(surface).inc()
        return state

    def _ensure_state_degraded_ok(
        self, surface: str = "check"
    ) -> tuple[_EngineState, bool]:
        """(state, degraded): the normal synced state, or — when the
        store-path breaker is open — the existing mirror state at its
        covered version (the Zanzibar §2.4.1 bounded-staleness degrade:
        availability decays to an older-but-valid snapshot, never to a
        wrong answer or a hung thread)."""
        try:
            return self._ensure_state(), False
        except StoreUnavailableError as e:
            return self._degraded_state(e, surface), True

    def _maybe_persist(self, snap: GraphSnapshot) -> None:
        """Checkpoint the freshly-built mirror without holding the engine
        lock. Writes are throttled to one per persist_min_interval, but a
        throttled snapshot is kept pending and flushed by a timer when
        the window opens — dropping it would leave the cache stale until
        the NEXT rebuild, which may never come before a restart."""
        cache_path = self._mirror_cache_path()
        if cache_path is None:
            return
        with self._persist_mu:
            self._pending_persist = snap
            if self._persist_timer is not None:
                return  # an already-scheduled flush will pick this up
            delay = 0.0
            if self._last_persist:
                delay = max(
                    0.0,
                    self._last_persist
                    + self.persist_min_interval
                    - time.monotonic(),
                )
            # ALWAYS deferred to the timer thread (even delay 0): the
            # O(edges) compressed write never runs on the check/serve
            # thread that happened to trigger the rebuild
            timer = threading.Timer(delay, self._flush_deferred)
            timer.daemon = True
            self._persist_timer = timer
            timer.start()

    def flush_checkpoints(self) -> None:
        """Write any pending mirror checkpoint NOW (synchronously).
        Called by the daemon on graceful shutdown and by tests that
        assert on-disk state; safe to call concurrently."""
        with self._persist_mu:
            timer, self._persist_timer = self._persist_timer, None
        if timer is not None:
            timer.cancel()
        self._flush_deferred()

    def _flush_deferred(self) -> None:
        """Take the pending snapshot under the mutex, write it OUTSIDE —
        _persist_mu protects only the pending/timer fields, never the
        O(edges) compressed write, so a serve thread scheduling the next
        persist can't stall behind an in-flight one. _write_mu serializes
        the actual file writes (rename ordering)."""
        from .checkpoint import save_snapshot

        cache_path = self._mirror_cache_path()
        with self._persist_mu:
            self._persist_timer = None
            snap, self._pending_persist = self._pending_persist, None
        try:
            # ALWAYS pass through _write_mu, even with nothing to write:
            # flush_checkpoints() may race a timer thread that already took
            # the pending snapshot — the empty-handed caller must BARRIER
            # on the in-flight write so "flushed" means "on disk"
            with self._write_mu:
                if cache_path is not None and snap is not None:
                    save_snapshot(snap, cache_path)
            if snap is not None:
                with self._persist_mu:
                    self._last_persist = time.monotonic()
        except OSError as err:  # cache write failure must not block serving
            import logging

            logging.getLogger("keto_tpu").warning(
                "mirror checkpoint write failed: %s", err
            )
            if self.metrics is not None:
                # counted HERE, where the failure is swallowed — the
                # registry-level shutdown catch never sees this path
                self.metrics.checkpoint_write_failures_total.inc()

    def _delta_refresh(
        self, state: _EngineState, store_version: int
    ) -> Optional[_EngineState]:
        """Incremental overlay refresh into a NEW state; None => compact."""
        from .delta import (
            DeltaOverflow,
            build_delta_tables,
            build_vocab_overlay,
        )

        changes_since = getattr(self.manager, "changes_since", None)
        if changes_since is None:
            return None
        ops = changes_since(state.base_version, nid=self.nid)
        if ops is None:
            return None
        try:
            overlay = build_vocab_overlay(state.snapshot, ops)
            view = SnapshotView(state.snapshot, overlay)
            delta = build_delta_tables(view, ops)
        except DeltaOverflow:
            # oversized delta: merge the ops into a new base incrementally
            # (only affected slots/rows) before paying the full O(edges)
            # rebuild — the write-churn cliff fix (engine/compact.py)
            return self._incremental_compact(state, store_version, ops)

        from .kernel import refresh_delta_tables

        vocab_arrays = {
            "objslot_ns": overlay.objslot_ns,
            "ns_has_config": overlay.ns_has_config,
        }
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .kernel import device_tables, pack_delta_tables

            sharded_tables, replicated = state.tables
            packed = dict(vocab_arrays)
            packed.update(pack_delta_tables(delta))
            replicated = {
                **replicated,
                **device_tables(packed, NamedSharding(self.mesh, P())),
            }
            tables = (sharded_tables, replicated)
        else:
            tables = refresh_delta_tables(state.tables, delta, vocab_arrays)

        new_state = _EngineState(
            snapshot=state.snapshot,
            view=view,
            sharded=state.sharded,
            tables=tables,
            delta_np=delta,
            base_version=state.base_version,
            covered_version=store_version,
            config_fp=state.config_fp,
            has_delta=True,
        )
        # carry the base full-CSR + base decoder forward; the dirty tables
        # and overlay extension re-derive from the fresh delta (O(delta))
        if state.expand_tables is not None:
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from .kernel import device_table, pack_delta_tables

                sharded_csr, _ = state.expand_tables
                fresh_dirty = {
                    "dirty_pack": device_table(
                        pack_delta_tables(delta)["dirty_pack"],
                        NamedSharding(self.mesh, P()),
                    )
                }
                new_state.expand_tables = (sharded_csr, fresh_dirty)
            else:
                base_csr = {
                    k: v
                    for k, v in state.expand_tables.items()
                    if not k.startswith("dirty_")
                }
                new_state.expand_tables = self._merge_expand_dirty(base_csr, delta)
            new_state.fh_probes = state.fh_probes
            new_state.base_decoder = state.base_decoder
            new_state.decoder = state.base_decoder.extended(overlay)
            new_state.expand_np = state.expand_np
        # reverse-reachability state rides along: the big transposed CSRs
        # follow the BASE snapshot; only the reverse-dirty overlay (rd)
        # re-derives from the fresh delta — queries touching changed
        # subjects/rows host-replay, so no table rebuild on the write path
        if state.reverse_tables is not None:
            new_state.reverse_np = state.reverse_np
            new_state.reverse_tables = self._merge_reverse_dirty(
                state.reverse_tables, delta
            )
        if state.subjects_tables is not None:
            new_state.subjects_tables = self._merge_subjects_dirty(
                state.subjects_tables, delta
            )
            new_state.subjects_probes = state.subjects_probes
        if state.base_decoder is not None and new_state.base_decoder is None:
            new_state.base_decoder = state.base_decoder
            new_state.decoder = state.base_decoder.extended(overlay)
        if self.metrics is not None:
            self.metrics.delta_overlay_ops.set(len(ops))
            self.metrics.compaction_lag_versions.set(
                store_version - state.base_version
            )
        return new_state

    def _incremental_compact(
        self, state: _EngineState, store_version: int, ops
    ) -> Optional[_EngineState]:
        """Delta overflow: fold `ops` into a NEW base snapshot by copying
        + patching only the affected table slots/rows (engine/compact.py)
        instead of the full store re-ingest. None => full rebuild (mesh
        path, too-large op batch, load/garbage/probe gates). The merged
        state drops the expand tables — they lazily rebuild from the
        store on the next expand call; the check path (the write-churn
        hot path) never pays the rebuild."""
        if self.mesh is not None:
            return None  # sharded tables merge per-shard; rebuild for now
        from .checkpoint import stable_fingerprint
        from .compact import merge_ops_into_snapshot

        version = stable_fingerprint([store_version, state.config_fp])
        with self.tracer.span("engine.incremental_compact") as sp:
            merged, enc_u, ins_u = merge_ops_into_snapshot(
                state.snapshot, ops, version, with_encoded=True
            )
            if merged is None:
                return None
            sp.set_attribute("ops", len(ops))
        new_state = _EngineState(
            snapshot=merged,
            view=SnapshotView(merged),
            sharded=None,
            tables=snapshot_tables(merged),
            delta_np=empty_delta_tables(),
            base_version=store_version,
            covered_version=store_version,
            config_fp=state.config_fp,
            has_delta=False,
        )
        # patch the retained expand full-CSR mirror with the same op set
        # (None falls back to the lazy rebuild on next expand)
        expand_np, device_csr, fh_probes = self._patched_expand_state(
            state, enc_u, ins_u
        )
        if expand_np is not None:
            from .expand_kernel import ExpandDecoder

            new_state.expand_np = expand_np
            new_state.fh_probes = fh_probes
            new_state.base_decoder = ExpandDecoder(merged)
            new_state.decoder = new_state.base_decoder.extended(None)
            new_state.expand_tables = self._merge_expand_dirty(
                device_csr, new_state.delta_np
            )
        # patch the retained transposed mirror with the same op set (the
        # reverse twin of the expand patch; None => lazy rebuild). The
        # subjects_tables leg stays None — it re-packs from the freshly
        # patched expand full CSR on the next ListSubjects call (a pack,
        # not a rebuild).
        reverse_np, reverse_tables = self._patched_reverse_state(
            state, enc_u, ins_u, merged
        )
        if reverse_np is not None:
            new_state.reverse_np = reverse_np
            new_state.reverse_tables = self._merge_reverse_dirty(
                reverse_tables, new_state.delta_np
            )
            if new_state.base_decoder is None:
                from .expand_kernel import ExpandDecoder

                new_state.base_decoder = ExpandDecoder(merged)
                new_state.decoder = new_state.base_decoder.extended(None)
        self.stats["incremental_merges"] = (
            self.stats.get("incremental_merges", 0) + 1
        )
        self._set_mirror_gauges(new_state.tables)
        # scheduling only (the O(edges) compressed write runs on the
        # timer thread) — safe under the engine lock
        self._maybe_persist(merged)
        return new_state

    def _set_mirror_gauges(self, tables) -> None:
        """Fresh-base gauges after a rebuild/compaction: empty delta
        overlay, zero compaction lag, current device-table footprint."""
        m = self.metrics
        if m is None:
            return
        m.delta_overlay_ops.set(0)
        m.compaction_lag_versions.set(0)
        m.snapshot_hbm_bytes.set(_tables_nbytes(tables))
        m.watch_device_memory(_tables_devices(tables))

    @staticmethod
    def _pack_expand_csr(csr: dict) -> dict:
        """Host full-CSR arrays -> the expand kernel's device table dict."""
        from .kernel import device_tables, pack_pair_table

        return device_tables({
            "fh_pack": pack_pair_table(
                csr["fh_obj"], csr["fh_rel"], csr["fh_row"]
            ),
            **{k: csr[k] for k in ("f_row_ptr", "f_skind", "f_sa", "f_sb")},
        })

    def _patched_expand_state(self, state: _EngineState, enc_u, ins_u):
        """Patch the retained host full-CSR mirror with the merged ops
        (affected rows only) and return (expand_np, device_csr,
        fh_probes), or (None, None, None) to fall back to the lazy
        rebuild (no mirror retained, or garbage past the amortization
        threshold)."""
        import numpy as np

        from .compact import GARBAGE_FLOOR, GARBAGE_FRACTION, patch_csr

        src = state.expand_np
        if src is None:
            return None, None, None
        per_row: dict = {}
        for (obj, rel, sk, sa, sb), ins in zip(enc_u.tolist(), ins_u.tolist()):
            ch = per_row.setdefault((obj, rel), {"ins": [], "del": set()})
            if ins:
                ch["ins"].append((sk, sa, sb))
                ch["del"].discard((sk, sa, sb))
            else:
                ch["del"].add((sk, sa, sb))
                ch["ins"] = [t for t in ch["ins"] if t != (sk, sa, sb)]
        (fh_obj, fh_rel, fh_row), fh_probes, f_row_ptr, payloads, garbage = (
            patch_csr(
                (src["fh_obj"], src["fh_rel"], src["fh_row"]),
                src["fh_probes"],
                src["f_row_ptr"],
                (src["f_skind"], src["f_sa"], src["f_sb"]),
                per_row,
            )
        )
        total_garbage = src["garbage"] + garbage
        if total_garbage > max(
            GARBAGE_FLOOR, GARBAGE_FRACTION * len(payloads[0])
        ):
            return None, None, None
        expand_np = {
            "fh_obj": fh_obj, "fh_rel": fh_rel, "fh_row": fh_row,
            "fh_probes": fh_probes, "f_row_ptr": f_row_ptr,
            "f_skind": payloads[0], "f_sa": payloads[1], "f_sb": payloads[2],
            "garbage": total_garbage,
        }
        return expand_np, self._pack_expand_csr(expand_np), fh_probes

    def _patched_reverse_state(self, state: _EngineState, enc_u, ins_u, merged):
        """Patch the retained transposed mirror (reverse-edge CSR rows
        keyed by subject slot, seed CSR rows keyed by full subject key)
        with the merged ops — the same patch_csr machinery the forward
        CSRs use. Returns (reverse_np, device tables) or (None, None) for
        the lazy rebuild (no mirror retained, pathological clustering, or
        garbage past the amortization threshold)."""
        from .compact import (
            GARBAGE_FLOOR,
            GARBAGE_FRACTION,
            MergeFallback,
            patch_csr,
        )
        from .reverse_kernel import pack_reverse_tables
        from .snapshot import reverse_subject_tag

        src = state.reverse_np
        if src is None:
            return None, None
        per_rev: dict = {}
        per_seed: dict = {}

        def _apply(per_row, key, pay, ins):
            ch = per_row.setdefault(key, {"ins": [], "del": set()})
            if ins:
                ch["ins"].append(pay)
                ch["del"].discard(pay)
            else:
                ch["del"].add(pay)
                ch["ins"] = [t for t in ch["ins"] if t != pay]

        for (obj, rel, sk, sa, sb), ins in zip(enc_u.tolist(), ins_u.tolist()):
            if sk == 1:
                _apply(per_rev, (sa, 0), (obj, rel, sb), ins)
            tag = int(reverse_subject_tag(sk, sb))
            _apply(per_seed, (sa, tag), (obj, rel), ins)
        try:
            (
                (rvh_obj, rvh_rel, rvh_row), rvh_probes, rv_row_ptr,
                (rv_pobj, rv_prel, rv_sb), g_rev,
            ) = patch_csr(
                (src["rvh_obj"], src["rvh_rel"], src["rvh_row"]),
                src["rvh_probes"],
                src["rv_row_ptr"],
                (src["rv_pobj"], src["rv_prel"], src["rv_sb"]),
                per_rev,
            )
            (
                (rsh_obj, rsh_tag, rsh_row), rsh_probes, rs_row_ptr,
                (rs_obj, rs_rel), g_seed,
            ) = patch_csr(
                (src["rsh_obj"], src["rsh_tag"], src["rsh_row"]),
                src["rsh_probes"],
                src["rs_row_ptr"],
                (src["rs_obj"], src["rs_rel"]),
                per_seed,
            )
        except MergeFallback:
            return None, None
        total_garbage = src["garbage"] + g_rev + g_seed
        if total_garbage > max(
            GARBAGE_FLOOR, GARBAGE_FRACTION * (len(rv_pobj) + len(rs_obj))
        ):
            return None, None
        reverse_np = {
            **src,
            "rvh_obj": rvh_obj, "rvh_rel": rvh_rel, "rvh_row": rvh_row,
            "rvh_probes": rvh_probes, "rv_row_ptr": rv_row_ptr,
            "rv_pobj": rv_pobj, "rv_prel": rv_prel, "rv_sb": rv_sb,
            "rsh_obj": rsh_obj, "rsh_tag": rsh_tag, "rsh_row": rsh_row,
            "rsh_probes": rsh_probes, "rs_row_ptr": rs_row_ptr,
            "rs_obj": rs_obj, "rs_rel": rs_rel,
            "garbage": total_garbage,
        }
        from .kernel import device_tables

        return reverse_np, device_tables(
            pack_reverse_tables(reverse_np, merged)
        )

    @staticmethod
    def _merge_expand_dirty(base_csr: dict, delta_np: dict) -> dict:
        from .kernel import device_table, pack_delta_tables

        merged = dict(base_csr)
        merged["dirty_pack"] = device_table(
            pack_delta_tables(delta_np)["dirty_pack"]
        )
        return merged

    @staticmethod
    def _merge_reverse_dirty(base_tables: dict, delta_np: dict) -> dict:
        """Reverse-kernel tables + the delta's reverse-dirty (rd) overlay
        — only the small rd pack re-uploads on a delta refresh."""
        from .kernel import device_table, pack_pair_table

        merged = {k: v for k, v in base_tables.items() if k != "rd_pack"}
        merged["rd_pack"] = device_table(
            pack_pair_table(
                delta_np["rd_obj"], delta_np["rd_tag"], delta_np["rd_val"]
            )
        )
        return merged

    @staticmethod
    def _merge_subjects_dirty(base_tables: dict, delta_np: dict) -> dict:
        from .kernel import device_table, pack_pair_table

        merged = {k: v for k, v in base_tables.items() if k != "dirty_pack"}
        merged["dirty_pack"] = device_table(
            pack_pair_table(
                delta_np["dirty_obj"], delta_np["dirty_rel"],
                delta_np["dirty_val"],
            )
        )
        return merged

    def _mirror_cache_path(self) -> Optional[str]:
        d = self.config.get("check.mirror_cache")
        if not d:
            return None
        from .checkpoint import mirror_cache_path

        return mirror_cache_path(d, self.nid)

    def _rebuild(
        self, store_version: int, config_fp, namespaces
    ) -> tuple[_EngineState, Optional[GraphSnapshot]]:
        """Returns (state, snapshot-to-persist). The snapshot is non-None
        only for a fresh build; the caller checkpoints it AFTER releasing
        the engine lock (an O(edges) compressed write must not stall
        check traffic)."""
        from .checkpoint import load_snapshot, stable_fingerprint

        version = stable_fingerprint([store_version, config_fp])
        # warm-restart path: a persisted mirror for exactly this
        # (store version, config) skips the O(edges) host build
        cache_path = self._mirror_cache_path()
        if cache_path is not None and self.mesh is None:
            cached = load_snapshot(cache_path)
            if cached is not None and cached.version == version:
                state = _EngineState(
                    snapshot=cached,
                    view=SnapshotView(cached),
                    sharded=None,
                    tables=snapshot_tables(cached),
                    delta_np=empty_delta_tables(),
                    base_version=store_version,
                    covered_version=store_version,
                    config_fp=config_fp,
                )
                self.stats["snapshot_loads"] = self.stats.get("snapshot_loads", 0) + 1
                self._set_mirror_gauges(state.tables)
                return state, None
            # a checkpoint existed but could not warm this restart:
            # count why (cold-start recovery audit — "stale" is a file
            # for another (store version, config) pair, "corrupt" a
            # torn/truncated/incompatible one). The rebuild below IS the
            # degrade path; answers never depend on the cache.
            import os as _os

            if _os.path.exists(cache_path):
                reason = "stale" if cached is not None else "corrupt"
                self.stats[f"checkpoint_fallback_{reason}"] = (
                    self.stats.get(f"checkpoint_fallback_{reason}", 0) + 1
                )
                if self.metrics is not None:
                    self.metrics.checkpoint_load_fallbacks_total.labels(
                        reason
                    ).inc()
        # columnar fast path: stores exposing all_tuple_columns feed the
        # vectorized builder directly — no per-tuple Python objects on
        # the ingest path (the 1e7..1e8-scale requirement), single-device
        # AND mesh (the round-2 VERDICT's one structural gap)
        columns_fn = getattr(self.manager, "all_tuple_columns", None)
        columnar = columns_fn is not None
        phases: dict = {}
        sharded = None
        with _mirror_phase(phases, "build"):
            if columnar:
                source = columns_fn(nid=self.nid)
            else:
                # ketolint: allow[lock-blocking-call] reason=the O(edges) mirror rebuild must read the store under the engine lock: the built state is stamped covered_version=store_version, and a write landing mid-read would silently decouple the two; the store never calls back into the engine while holding its own lock (write hooks fire post-commit, outside store locks), so the engine->store lock order cannot invert
                source = self.manager.all_relation_tuples(nid=self.nid)
            if self.mesh is not None:
                from ..parallel import build_sharded_snapshot
                from ..parallel.sharding import build_sharded_snapshot_columnar

                build = (
                    build_sharded_snapshot_columnar if columnar
                    else build_sharded_snapshot
                )
                sharded = build(
                    source, namespaces, n_shards=self.mesh.devices.size,
                    K=self.rewrite_instr_cap, version=version,
                )
                snap = sharded.base
            else:
                build = build_snapshot_columnar if columnar else build_snapshot
                snap = build(
                    source, namespaces, K=self.rewrite_instr_cap,
                    version=version,
                )
        if self.mesh is not None:
            from ..parallel.kernel import place_sharded_tables

            # a shard's tables are packed and placed one at a time (at
            # 1e8 edges the packed copies held together would not fit the
            # host), so on a mesh packing counts under `upload`
            phases["pack"] = 0.0
            with _mirror_phase(phases, "upload"):
                tables = place_sharded_tables(
                    sharded, self.mesh, axis=self.mesh.axis_names[0],
                    release_columns=True,
                )
        else:
            with _mirror_phase(phases, "pack"):
                packed = pack_snapshot_tables(snap)
            with _mirror_phase(phases, "upload"):
                tables = device_tables(packed)
        state = _EngineState(
            snapshot=snap,
            view=SnapshotView(snap),
            sharded=sharded,
            tables=tables,
            delta_np=empty_delta_tables(),
            base_version=store_version,
            covered_version=store_version,
            config_fp=config_fp,
        )
        self.stats["snapshot_builds"] += 1
        if self.metrics is not None:
            self.metrics.snapshot_builds_total.inc()
            self.metrics.snapshot_tuples.set(snap.n_tuples)
            self.metrics.observe_mirror_build(phases)
            self._set_mirror_gauges(tables)
        # mirror checkpoints cover the single-device path only (the
        # sharded build re-derives per-shard tables anyway)
        return state, (snap if self.mesh is None else None)

    def invalidate(self) -> None:
        with self._lock:
            self._state = None

    def mirror_state(self):
        """The current immutable state generation (or None before the
        first build). The anti-entropy scrubber (engine/scrub.py) reads
        it to checksum device tables against `state.snapshot`'s host
        truth — both sides of that comparison live on the SAME state
        object, so the scrub stays consistent even if the engine swaps
        states mid-pass."""
        with self._lock:
            return self._state

    def corrupt_mirror(
        self, table: Optional[str] = None, bit: int = 0
    ) -> Optional[str]:
        """Flip one bit in a device-mirror table in place — the
        `mirror_corrupt` fault's payload (a silent HBM fault stand-in,
        test/smoke only). Returns the corrupted table key, or None when
        no single-device state is built. The host-side snapshot is left
        intact: exactly the divergence the scrubber exists to catch."""
        with self._lock:
            state = self._state
        if state is None or not isinstance(state.tables, dict):
            return None  # mesh path: per-shard tables, not scrubbed
        tables = state.tables
        key = table or max(
            tables,
            key=lambda k: int(getattr(tables[k], "nbytes", 0) or 0),
        )
        from .kernel import device_table

        host = np.asarray(tables[key]).copy()
        flat = host.reshape(-1).view(np.uint8)
        if flat.size == 0:
            return None
        flat[bit // 8 % flat.size] ^= np.uint8(1 << (bit % 8))
        with self._lock:
            if self._state is state:  # don't poison a successor state
                tables[key] = device_table(host)
        self.stats["mirror_corruptions"] = (
            self.stats.get("mirror_corruptions", 0) + 1
        )
        return key

    def hbm_snapshot(self) -> dict:
        """Structured device-memory + staleness accounting for the
        current mirror generation: per-buffer table bytes (forward check
        tables incl. the delta overlay and rewrite programs, plus the
        lazily-built expand/reverse/subjects extras) and how stale the
        mirror is relative to the live store. Served by
        `GET /admin/flightrec` and read by the bench; also refreshes the
        keto_tpu_hbm_table_bytes{buffer} gauges. Zero device contact —
        the bytes are arithmetic on array metadata, as the chip tiles
        the tables (kernel.tiled_nbytes)."""
        with self._lock:
            state = self._state
        if state is None:
            return {"built": False}
        # store read OUTSIDE the engine lock (ketolint lock-discipline)
        store_version = self.manager.version(nid=self.nid)

        check_keys = _tables_nbytes_by_key(state.tables)
        delta_bytes = sum(
            v for k, v in check_keys.items()
            if k in ("dd_pack", "dirty_pack", "rd_pack")
        )
        program_bytes = sum(
            v for k, v in check_keys.items()
            if k in ("instr_pack", "prog_flags", "ns_has_config")
        )
        # closure CSR + its delta overlay broken out as their own buffer
        # families (the Leopard index lives in HBM beside the check
        # tables; capacity planning must see it separately)
        closure_keys = _tables_nbytes_by_key(self.closure_device_tables())
        # device-powering working set (engine/closure_power.py): packed
        # adjacency operands + bit matrices + unpacked step scratch of
        # the LAST device build — transient buffers, reported at their
        # high-water shape so capacity planning sees the build's
        # footprint beside the resident index it produces
        power_keys = {}
        with self._closure_mu:
            if self._closure is not None:
                power_keys = {
                    k: int(v)
                    for k, v in self._closure._power_hbm.items()
                }
        buffers = {
            "check": check_keys,
            "expand": _tables_nbytes_by_key(state.expand_tables),
            "reverse": _tables_nbytes_by_key(state.reverse_tables),
            "subjects": _tables_nbytes_by_key(state.subjects_tables),
            "closure": {
                k: v for k, v in closure_keys.items() if k != "cd_pack"
            },
            "closure_delta": {
                k: v for k, v in closure_keys.items() if k == "cd_pack"
            },
            "closure_power": power_keys,
        }
        totals = {
            name: sum(keys.values()) for name, keys in buffers.items()
        }
        if self.metrics is not None:
            for name, total in totals.items():
                self.metrics.hbm_table_bytes.labels(name).set(total)
        return {
            "built": True,
            "nid": self.nid,
            "n_tuples": state.snapshot.n_tuples,
            "buffers": buffers,
            "totals": totals,
            "delta_overlay_bytes": delta_bytes,
            "rewrite_program_bytes": program_bytes,
            "total_bytes": sum(totals.values()),
            # mirror staleness: how far the served snapshot trails the
            # live store, and how much churn the overlay absorbs
            "base_version": state.base_version,
            "covered_version": state.covered_version,
            "store_version": store_version,
            "staleness_versions": store_version - state.covered_version,
            "compaction_lag_versions": (
                state.covered_version - state.base_version
            ),
            "has_delta": state.has_delta,
        }

    # -- Leopard closure index (engine/closure.py) ----------------------------

    def closure_index(self):
        """The per-engine ClosureIndex (lazily created; a cheap shell
        until the maintenance plane or closure_ensure_built powers it).
        Exists regardless of `closure.enabled` so tests/bench can drive
        it directly; the submit path gates on the enabled flag."""
        with self._closure_mu:
            if self._closure is None:
                from .closure import (
                    DEFAULT_LAG_BUDGET,
                    DEFAULT_MAX_SET_ROWS,
                    ClosureIndex,
                )

                cache_dir = self.config.get("check.mirror_cache")
                cache_path = None
                if cache_dir and self.mesh is None:
                    from .checkpoint import closure_cache_path

                    cache_path = closure_cache_path(cache_dir, self.nid)
                self._closure = ClosureIndex(
                    self.nid,
                    max_set_rows=int(
                        self.config.get(
                            "closure.max_set_rows", DEFAULT_MAX_SET_ROWS
                        )
                    ),
                    lag_budget_versions=int(
                        self.config.get(
                            "closure.lag_budget_versions", DEFAULT_LAG_BUDGET
                        )
                    ),
                    metrics=self.metrics,
                    cache_path=cache_path,
                    powering=str(
                        self.config.get("closure.powering", "host")
                    ),
                    flightrec=self.flightrec,
                )
            return self._closure

    def closure_ensure_built(self) -> bool:
        """Power (or refresh) the closure index for the CURRENT engine
        state and fold in every committed write — the maintenance
        plane's per-pass entry point (keto_tpu/closure), also called by
        tests/bench for a deterministic warm index. Never called on the
        check submit path: powering there would stall a batch."""
        state = self._ensure_state()
        idx = self.closure_index()
        max_depth = self.config.max_read_depth()
        ready = idx.ensure_for(state, self.manager, max_depth)
        # incremental dirty refresh: re-power ONLY the write-perturbed
        # nodes from current content (encoded through the state's
        # overlay view, so post-base vocabulary resolves) — their checks
        # return to the closure without waiting for the next compaction
        idx.refresh_dirty(self.manager, max_depth, view=state.view)
        return ready

    def closure_device_tables(self) -> Optional[dict]:
        """The installed closure device tables (hbm_snapshot's closure
        buffer family), or None before the first build."""
        idx = self._closure
        if idx is None:
            return None
        with idx._mu:
            view = idx._view
        return view.tables if view is not None else None

    def _closure_gate(self, state):
        """(view, fallback_cause): the consistent closure view for one
        submit, or the host-side cause every query in the batch will be
        counted under. A LAGGING index gets one bounded inline catch-up
        attempt (a changes_since read — comparable to the staleness read
        _ensure_state just did) when the lag fits the budget; past the
        budget the batch falls back and the background maintainer owns
        recovery."""
        from .closure import CAUSE_LAG

        idx = self.closure_index()
        view, cause = idx.view_for(state)
        if view is None and cause == CAUSE_LAG:
            lag = idx.lag_versions(state.covered_version)
            try:
                caught = lag <= idx.lag_budget_versions and idx.catch_up(
                    self.manager, state.covered_version
                )
            except StoreUnavailableError:
                # store outage mid-catch-up: the batch falls back to the
                # BFS kernel (cause stays LAG) — a lagging index during
                # an outage degrades latency, never correctness
                caught = False
            if caught:
                view, cause = idx.view_for(state)
        if self.metrics is not None:
            self.metrics.closure_lag_versions.set(
                idx.lag_versions(state.covered_version)
            )
        return view, cause

    def _count_closure_fallback(self, cause: str, n: int) -> None:
        per = self.stats.setdefault("closure_fallback", {})
        per[cause] = per.get(cause, 0) + n
        if self.metrics is not None and n:
            self.metrics.closure_fallback_total.labels(cause).inc(n)

    def _ensure_expand_state(self) -> _EngineState:
        """State with the expand-kernel extras (full-edge CSR + dirty
        tables + decoder) populated. The CSR follows the BASE snapshot;
        writes since then ride the overlay's dirty tables — the expand
        kernel sends queries touching dirty rows to the host, so the CSR
        needs no rebuild on the write path."""
        # store outage: an already-built expand mirror serves degraded
        # at its covered version; a missing one cannot lazily build
        # from a dead store (typed 503 from the read below)
        state = self._ensure_state_degraded_ok("expand")[0]
        if state.expand_tables is not None:
            return state
        import jax.numpy as jnp

        from .expand_kernel import ExpandDecoder, build_full_csr

        with self._lock:
            if state.expand_tables is not None:  # raced with another filler
                return state
            # columnar stores feed the vectorized CSR builders — no
            # per-tuple Python objects on the expand-state build either
            columns_fn = getattr(self.manager, "all_tuple_columns", None)
            if self.mesh is not None:
                # sharded full CSR: same object-slot partition as check
                from ..parallel.expand import place_sharded_expand_tables
                from ..parallel.sharding import (
                    build_sharded_full_csr,
                    build_sharded_full_csr_columnar,
                )

                if columns_fn is not None:
                    stacked, fh_probes = build_sharded_full_csr_columnar(
                        columns_fn(nid=self.nid), state.snapshot,
                        n_shards=self.mesh.devices.size,
                    )
                else:
                    stacked, fh_probes = build_sharded_full_csr(
                        # ketolint: allow[lock-blocking-call] reason=lazy state fill: the full-CSR build must read the store under the engine lock so the derived tables match the state's covered_version exactly; post-commit write hooks fire outside store locks, so the engine->store order cannot invert
                        list(self.manager.all_relation_tuples(nid=self.nid)),
                        state.snapshot,
                        n_shards=self.mesh.devices.size, view=state.view,
                    )
                state.fh_probes = fh_probes
                state.base_decoder = ExpandDecoder(state.snapshot)
                state.decoder = state.base_decoder.extended(state.view.overlay)
                state.expand_tables = place_sharded_expand_tables(
                    stacked, state.delta_np, self.mesh,
                    axis=self.mesh.axis_names[0],
                )
                return state
            if columns_fn is not None:
                from .expand_kernel import build_full_csr_columnar

                csr = build_full_csr_columnar(
                    columns_fn(nid=self.nid), state.snapshot
                )
            else:
                csr = build_full_csr(
                    # ketolint: allow[lock-blocking-call] reason=lazy state fill: the full-CSR build must read the store under the engine lock so the derived tables match the state's covered_version exactly; post-commit write hooks fire outside store locks, so the engine->store order cannot invert
                    list(self.manager.all_relation_tuples(nid=self.nid)),
                    state.snapshot, view=state.view,
                )
            fh_probes = csr.pop("fh_probes")
            device_csr = self._pack_expand_csr(csr)
            state.fh_probes = fh_probes
            state.expand_np = {**csr, "fh_probes": fh_probes, "garbage": 0}
            state.base_decoder = ExpandDecoder(state.snapshot)
            state.decoder = state.base_decoder.extended(state.view.overlay)
            # expand_tables is the readiness signal: set it last
            state.expand_tables = self._merge_expand_dirty(
                device_csr, state.delta_np
            )
            return state

    def _ensure_reverse_state(self) -> _EngineState:
        """State with the transposed mirror (reverse-edge CSR + seed CSR
        + inverted programs) built and on device. Lazy like the expand
        state: the mirror follows the BASE snapshot; writes since then
        ride the delta's reverse-dirty table — affected queries host-
        replay, so the write path never rebuilds it. Under a mesh the
        reverse tables are built unsharded (replicated execution): the
        reverse workload is an analytical read, not the sharded check hot
        path."""
        # store outage: a built transposed mirror serves degraded at its
        # covered version (same contract as the expand state above)
        state = self._ensure_state_degraded_ok("list")[0]
        if state.reverse_tables is not None:
            return state
        from .expand_kernel import ExpandDecoder
        from .kernel import device_tables
        from .reverse_kernel import (
            build_reverse_state,
            build_reverse_state_columnar,
            pack_reverse_tables,
        )

        namespaces = self.config.namespace_manager().namespaces()
        with self._lock:
            if state.reverse_tables is not None:  # raced another filler
                return state
            columns_fn = getattr(self.manager, "all_tuple_columns", None)
            if columns_fn is not None:
                rnp = build_reverse_state_columnar(
                    columns_fn(nid=self.nid), state.snapshot, namespaces
                )
            else:
                rnp = build_reverse_state(
                    # ketolint: allow[lock-blocking-call] reason=lazy state fill: the full-CSR build must read the store under the engine lock so the derived tables match the state's covered_version exactly; post-commit write hooks fire outside store locks, so the engine->store order cannot invert
                    list(self.manager.all_relation_tuples(nid=self.nid)),
                    state.snapshot, namespaces, view=state.view,
                )
            state.reverse_np = rnp
            if state.base_decoder is None:
                state.base_decoder = ExpandDecoder(state.snapshot)
                state.decoder = state.base_decoder.extended(state.view.overlay)
            tables = device_tables(pack_reverse_tables(rnp, state.snapshot))
            # reverse_tables is the readiness signal: set it last
            state.reverse_tables = self._merge_reverse_dirty(
                tables, state.delta_np
            )
            return state

    def _ensure_subjects_state(self) -> _EngineState:
        """State with the list-subjects tables (span-packed full-edge CSR
        + instruction lanes) on device. Reuses the expand state's host
        full-CSR mirror when available (single-device path — including
        its incremental-compaction patches); under a mesh it builds its
        own unsharded CSR."""
        state = self._ensure_state_degraded_ok("list")[0]
        if state.subjects_tables is not None:
            return state
        if self.mesh is None:
            state = self._ensure_expand_state()
        from .expand_kernel import (
            ExpandDecoder,
            build_full_csr,
            build_full_csr_columnar,
        )
        from .kernel import device_tables
        from .reverse_kernel import pack_subjects_tables

        with self._lock:
            if state.subjects_tables is not None:
                return state
            csr = state.expand_np
            if csr is None:
                columns_fn = getattr(self.manager, "all_tuple_columns", None)
                if columns_fn is not None:
                    csr = build_full_csr_columnar(
                        columns_fn(nid=self.nid), state.snapshot
                    )
                else:
                    csr = build_full_csr(
                        # ketolint: allow[lock-blocking-call] reason=lazy state fill: the full-CSR build must read the store under the engine lock so the derived tables match the state's covered_version exactly; post-commit write hooks fire outside store locks, so the engine->store order cannot invert
                        list(self.manager.all_relation_tuples(nid=self.nid)),
                        state.snapshot, view=state.view,
                    )
            state.subjects_probes = int(csr["fh_probes"])
            if state.base_decoder is None:
                state.base_decoder = ExpandDecoder(state.snapshot)
                state.decoder = state.base_decoder.extended(state.view.overlay)
            tables = device_tables(pack_subjects_tables(csr, state.snapshot))
            state.subjects_tables = self._merge_subjects_dirty(
                tables, state.delta_np
            )
            return state

    # -- reverse reachability (ListObjects / ListSubjects) --------------------

    def _count_reverse(self, leg: str, n_device: int, n_host: int, causes):
        self.stats[f"device_{leg}"] = (
            self.stats.get(f"device_{leg}", 0) + n_device
        )
        self.stats[f"host_{leg}"] = self.stats.get(f"host_{leg}", 0) + n_host
        for cause, cnt in causes.items():
            self.stats["host_cause"][cause] = (
                self.stats["host_cause"].get(cause, 0) + cnt
            )

    def list_objects_batch(
        self,
        queries: Sequence[tuple],
        max_depth: int = 0,
        frontier_cap: int = 4096,
        result_cap: int = 2048,
        pool_cap: int = 0,
    ) -> list[list[str]]:
        """Batched reverse reachability: queries are (namespace,
        relation, subject) triples; each answer is the SORTED list of
        objects in `namespace` the subject reaches via `relation` —
        exactly { obj : Check(ns:obj#rel@subject) is IS_MEMBER }, the
        host oracle's definition (reference.list_objects).

        One device launch per batch (reverse BFS over the transposed
        mirror); queries the kernel cause-flags (AND/NOT programs, dirty
        rows, frontier/result overflow, step exhaustion, error-semantics
        nodes) replay on the exact host oracle. Names the graph+config
        never mention answer [] directly — no edge can seed or match, so
        the enumeration is exactly empty."""
        from ..ketoapi import RelationTuple as _RT
        from ..ketoapi import SubjectSet as _SubjectSet
        from .reverse_kernel import (
            decode_pool_slice,
            list_objects_kernel_packed,
            unpack_list_results,
        )
        from .snapshot import reverse_subject_tag

        n = len(queries)
        if n == 0:
            return []
        state = self._ensure_reverse_state()
        global_max = self.config.max_read_depth()
        depth = max_depth if 0 < max_depth <= global_max else global_max
        rnp = state.reverse_np

        if rnp["host_all"]:
            # a NOT exists somewhere in the config: NOT-members exist
            # precisely where no path exists, which reverse reachability
            # cannot enumerate — exact host oracle for every query
            self._count_reverse(
                "list_objects", 0, n, {"island_host": n}
            )
            return [
                self.reference.list_objects(ns, rel, sub, max_depth, self.nid)
                for ns, rel, sub in queries
            ]

        B = next((b for b in _BUCKETS if b >= n), None)
        if B is None:
            out = []
            step = _BUCKETS[-1]
            for i in range(0, n, step):
                out.extend(
                    self.list_objects_batch(
                        queries[i : i + step], max_depth, frontier_cap,
                        result_cap, pool_cap,
                    )
                )
            return out

        q_sa = np.zeros(B, dtype=np.int32)
        q_tag = np.zeros(B, dtype=np.int32)
        q_ns = np.zeros(B, dtype=np.int32)
        q_rel = np.zeros(B, dtype=np.int32)
        q_valid = np.zeros(B, dtype=bool)
        empty_idx: set[int] = set()
        for i, (ns_name, rel_name, subject) in enumerate(queries):
            ns_id = state.view.ns_id(ns_name)
            rel_id = state.view.rel_id(rel_name)
            proxy = _RT(namespace=ns_name, object="", relation=rel_name)
            if isinstance(subject, _SubjectSet):
                proxy.subject_set = subject
            else:
                proxy.subject_id = subject
            sub = state.view.encode_subject(proxy)
            if ns_id is None or rel_id is None or sub is None:
                empty_idx.add(i)
                continue
            skind, sa, sb = sub
            q_sa[i] = sa
            q_tag[i] = int(reverse_subject_tag(skind, sb))
            q_ns[i] = ns_id
            q_rel[i] = rel_id
            q_valid[i] = True

        qpack = np.stack(
            [
                q_sa, q_tag, q_ns, q_rel,
                np.full(B, depth, dtype=np.int32),
                q_valid.astype(np.int32),
            ]
        ).astype(np.int32)
        launch_id = next_launch_id()
        with self.tracer.span("engine.list_objects_launch", batch=B):
            flat = list_objects_kernel_packed(
                state.reverse_tables,
                qpack,
                rvh_probes=rnp["rvh_probes"],
                rsh_probes=rnp["rsh_probes"],
                RK=rnp["RK"],
                max_steps=int(global_max + state.snapshot.n_config_rels + 4),
                wildcard_rel=state.snapshot.wildcard_rel,
                n_config_rels=max(state.snapshot.n_config_rels, 1),
                frontier_cap=max(frontier_cap, B),
                result_cap=result_cap,
                # default pool sizes for serve-path result sets; callers
                # expecting wide enumerations (the bench) pass pool_cap
                pool_cap=pool_cap or max(8 * B, 4096),
                has_delta=state.has_delta,
            )
        # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
        offs, needs, pool, lstats = unpack_list_results(np.asarray(flat), B)
        self._record_list_launch("list_objects", B, n, lstats, launch_id)
        return self._resolve_reverse(
            "list_objects", queries, empty_idx, q_valid, needs,
            lambda i: sorted(
                state.decoder.slot_to_obj[slot][1]
                for slot in decode_pool_slice(pool, int(offs[i]), int(offs[i + 1]))
            ),
            lambda qr: self.reference.list_objects(
                qr[0], qr[1], qr[2], max_depth, self.nid
            ),
        )

    def list_subjects_batch(
        self,
        queries: Sequence[tuple],
        max_depth: int = 0,
        frontier_cap: int = 4096,
        result_cap: int = 2048,
        pool_cap: int = 0,
    ) -> list[list[str]]:
        """Batched subject enumeration: queries are (namespace, object,
        relation) triples; each answer is the SORTED list of plain
        subject ids with Check(ns:obj#rel@id) IS_MEMBER (the host
        oracle's definition, reference.list_subjects). Forward BFS over
        the full-edge CSR + rewrite instructions with the check kernel's
        exact depth bookkeeping; same cause-coded fallback contract as
        list_objects_batch."""
        from .reverse_kernel import (
            decode_pool_slice,
            list_subjects_kernel_packed,
            unpack_list_results,
        )

        n = len(queries)
        if n == 0:
            return []
        state = self._ensure_subjects_state()
        global_max = self.config.max_read_depth()
        depth = max_depth if 0 < max_depth <= global_max else global_max

        B = next((b for b in _BUCKETS if b >= n), None)
        if B is None:
            out = []
            step = _BUCKETS[-1]
            for i in range(0, n, step):
                out.extend(
                    self.list_subjects_batch(
                        queries[i : i + step], max_depth, frontier_cap,
                        result_cap, pool_cap,
                    )
                )
            return out

        q_obj = np.zeros(B, dtype=np.int32)
        q_rel = np.zeros(B, dtype=np.int32)
        q_valid = np.zeros(B, dtype=bool)
        empty_idx: set[int] = set()
        for i, (ns_name, obj_name, rel_name) in enumerate(queries):
            node = state.view.encode_node(ns_name, obj_name, rel_name)
            if node is None:
                empty_idx.add(i)
                continue
            q_obj[i], q_rel[i] = node
            q_valid[i] = True

        qpack = np.stack(
            [
                q_obj, q_rel,
                np.full(B, depth, dtype=np.int32),
                q_valid.astype(np.int32),
            ]
        ).astype(np.int32)
        launch_id = next_launch_id()
        with self.tracer.span("engine.list_subjects_launch", batch=B):
            flat = list_subjects_kernel_packed(
                state.subjects_tables,
                qpack,
                K=state.snapshot.K,
                fsh_probes=state.subjects_probes,
                max_steps=int(global_max + state.snapshot.n_config_rels + 4),
                wildcard_rel=state.snapshot.wildcard_rel,
                n_config_rels=max(state.snapshot.n_config_rels, 1),
                frontier_cap=max(frontier_cap, B),
                result_cap=result_cap,
                # default pool sizes for serve-path result sets; callers
                # expecting wide enumerations (the bench) pass pool_cap
                pool_cap=pool_cap or max(8 * B, 4096),
                has_delta=state.has_delta,
            )
        # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
        offs, needs, pool, lstats = unpack_list_results(np.asarray(flat), B)
        self._record_list_launch("list_subjects", B, n, lstats, launch_id)
        return self._resolve_reverse(
            "list_subjects", queries, empty_idx, q_valid, needs,
            lambda i: sorted(
                state.decoder.subject_name(sid)
                for sid in decode_pool_slice(pool, int(offs[i]), int(offs[i + 1]))
            ),
            lambda qr: self.reference.list_subjects(
                qr[0], qr[1], qr[2], max_depth, self.nid
            ),
        )

    def _record_list_launch(
        self, kind: str, B: int, n: int, stats, launch_id: int
    ) -> None:
        """Flight-recorder entry for a reverse/expand/filter launch:
        lighter than the check entry (no stage breakdown — these legs
        resolve inline), but the same counter vocabulary. The caller
        allocates `launch_id` BEFORE its kernel dispatch so ids keep
        advancing while recording is disabled and id order tracks
        dispatch order across launch kinds.

        These legs evaluate ON the request thread (no batcher handoff),
        so the executing request's trace rides the ambient contextvar:
        the entry gets the trace id (the `?trace_id=` flightrec filter
        and the exported trace join on it) and the request's trace gets
        the launch id (slow-query lines and request logs then point at
        this entry, exactly like check launches)."""
        from ..observability import current_request_trace

        rt = current_request_trace()
        if rt is not None:
            ids = getattr(rt, "launch_ids", None)
            if ids is not None:
                ids.append(launch_id)
        fr = self.flightrec
        if fr is None or not fr.enabled:
            return
        entry = {
            "launch_id": launch_id,
            "kind": kind,
            "nid": self.nid,
            "bucket": B,
            "n": n,
            "occupancy": round((n / B) if B else 1.0, 4),
        }
        if rt is not None:
            entry["trace_ids"] = [rt.ctx.trace_id]
        if stats is not None:
            entry.update(launch_stats_dict(stats))
        fr.record(entry)

    def _resolve_reverse(
        self, leg, queries, empty_idx, q_valid, needs, decode_fn, host_fn
    ) -> list[list[str]]:
        """Shared result assembly for the two reverse legs: device
        decodes, cause-coded host replays, and stats bookkeeping."""
        results: list[list[str]] = []
        n_host = 0
        causes: dict[str, int] = {}
        for i, qr in enumerate(queries):
            if i in empty_idx:
                # names unknown to graph+config: exactly-empty enumeration
                results.append([])
                continue
            if not q_valid[i] or needs[i]:
                n_host += 1
                cause = (
                    CAUSE_NAMES.get(int(needs[i]), CAUSE_NAME_UNINDEXED)
                    if q_valid[i]
                    else CAUSE_NAME_UNINDEXED
                )
                causes[cause] = causes.get(cause, 0) + 1
                results.append(host_fn(qr))
                continue
            results.append(decode_fn(i))
        self._count_reverse(leg, len(queries) - n_host, n_host, causes)
        return results

    def list_objects(
        self,
        namespace: str,
        relation: str,
        subject,
        max_depth: int = 0,
        page_size: int = 100,
        page_token: str = "",
    ) -> tuple[list[str], str]:
        """Paginated single-query ListObjects: (object names, next page
        token). Tokens are offsets into the sorted enumeration (the batch
        path returns deterministic sorted results, so tokens are stable
        for a fixed snapshot)."""
        objs = self.list_objects_batch([(namespace, relation, subject)], max_depth)[0]
        return _paginate(objs, page_size, page_token)

    def list_subjects(
        self,
        namespace: str,
        obj: str,
        relation: str,
        max_depth: int = 0,
        page_size: int = 100,
        page_token: str = "",
    ) -> tuple[list[str], str]:
        """Paginated single-query ListSubjects: (subject ids, next page
        token)."""
        subs = self.list_subjects_batch([(namespace, obj, relation)], max_depth)[0]
        return _paginate(subs, page_size, page_token)

    # -- bulk ACL filtering (BatchFilter) --------------------------------------

    def _count_filter(
        self, n_closure: int, n_frontier: int, n_host: int, causes
    ) -> None:
        """Per-path resolution bookkeeping for one filter evaluation:
        engine stats + the keto_tpu_filter_objects_total{path} series +
        the shared host_cause split."""
        self.stats["filter_closure"] = (
            self.stats.get("filter_closure", 0) + n_closure
        )
        self.stats["filter_frontier"] = (
            self.stats.get("filter_frontier", 0) + n_frontier
        )
        self.stats["filter_host"] = self.stats.get("filter_host", 0) + n_host
        for cause, cnt in causes.items():
            self.stats["host_cause"][cause] = (
                self.stats["host_cause"].get(cause, 0) + cnt
            )
        if self.metrics is not None:
            for path, n in (
                ("closure", n_closure), ("frontier", n_frontier),
                ("host", n_host),
            ):
                if n:
                    self.metrics.filter_objects_total.labels(path).inc(n)

    @staticmethod
    def _degraded_host_filter_guard(degraded: bool) -> None:
        """Filter has no per-candidate error channel (absence from the
        response means NOT VISIBLE), and the host oracle maps an errored
        candidate to False — during a store outage that would silently
        turn 'unknown' into 'hidden'. A degraded chunk that cannot fully
        resolve on the mirror therefore sheds the typed 503 instead:
        never wrong beats partially answered."""
        if degraded:
            raise StoreUnavailableError(
                "store unavailable and this filter request needs the "
                "exact host oracle for some candidates — retry after "
                "recovery",
                breaker_open=True,
            )

    def _filter_host(self, namespace, relation, subject, objects, max_depth):
        """Exact host-oracle verdicts for a candidate slice (the
        complete checker — the same admission rule the device paths
        reproduce)."""
        return self.reference.filter_objects(
            namespace, relation, subject, objects, max_depth, self.nid
        )

    def filter_batch(
        self,
        namespace: str,
        relation: str,
        subject,
        objects: Sequence[str],
        max_depth: int = 0,
        frontier_cap: int = 4096,
        deadline=None,
        chunk_size: int = 0,
    ) -> list[bool]:
        """Bulk ACL filter: verdicts[i] is True iff
        Check(namespace:objects[i]#relation@subject) is IS_MEMBER — the
        search-result-filtering workload (Zanzibar's dominant production
        query shape) priced as ONE device ride instead of N.

        Device formulation (the shared-subject exploit):
          1. closure fast path — every candidate covered by the Leopard
             index resolves with a single batched membership gather over
             the packed-bucket subject-set tables (`req <= depth` gating
             exactly as closure_kernel.py); no per-object BFS at all.
          2. shared-frontier fallback (engine/filter_kernel.py) — the
             subject's reverse-reachable set expands ONCE over the
             transposed mirror and intersects against the whole leftover
             candidate column; a clean completed walk answers positives
             AND definitive negatives.
          3. cause-coded host fallback — AND/NOT islands (the reverse
             kernel's POISON discipline), dirty rows, overflow, unknown
             vocabulary, or a NOT-bearing config replay on the exact
             host oracle (reference.filter_objects).

        `deadline` (observability.Deadline | None) is checked at every
        chunk boundary — a 10k-object request respects its budget by
        failing fast with the typed 504 instead of finishing device work
        whose client is gone. `chunk_size` 0 reads filter.chunk_size."""
        from ..errors import DeadlineExceededError

        n = len(objects)
        if n == 0:
            return []
        self.stats["filter_requests"] = (
            self.stats.get("filter_requests", 0) + 1
        )
        if self.metrics is not None:
            self.metrics.filter_requests_total.inc()
            self.metrics.filter_request_objects.observe(n)
        chunk = int(
            chunk_size or self.config.get("filter.chunk_size", 4096)
        )
        chunk = max(1, min(chunk, _BUCKETS[-1]))
        out: list[bool] = []
        for i in range(0, n, chunk):
            if deadline is not None and deadline.expired():
                if self.metrics is not None:
                    self.metrics.deadline_exceeded_total.labels(
                        "filter_chunk"
                    ).inc()
                raise DeadlineExceededError(
                    "filter deadline expired mid-evaluation "
                    f"({i}/{n} candidates answered)"
                )
            out.extend(
                self._filter_chunk(
                    namespace, relation, subject, list(objects[i : i + chunk]),
                    max_depth, frontier_cap,
                )
            )
        return out

    def filter_objects(
        self,
        namespace: str,
        relation: str,
        subject,
        objects: Sequence[str],
        max_depth: int = 0,
        deadline=None,
    ) -> list[str]:
        """The transport-facing subset form: the candidates the subject
        CAN see, in input order (duplicates preserved — each occurrence
        answers independently, like N checks would)."""
        verdicts = self.filter_batch(
            namespace, relation, subject, objects, max_depth,
            deadline=deadline,
        )
        return [o for o, ok in zip(objects, verdicts) if ok]

    def _filter_chunk(
        self, namespace, relation, subject, objects, max_depth, frontier_cap
    ) -> list[bool]:
        """One bounded evaluation: closure probe, shared-frontier walk,
        host replay — in that order, each consuming what the previous
        stage could not resolve."""
        from ..ketoapi import RelationTuple as _RT
        from ..ketoapi import SubjectSet as _SubjectSet
        from .closure_kernel import CL_CAUSE_NAMES
        from .filter_kernel import (
            filter_kernel_packed,
            pack_filter_query,
            unpack_filter_results,
        )
        from .snapshot import (
            FLAG_HOST_ONLY as _F_HOST,
            FLAG_ISLAND as _F_ISL,
            reverse_subject_tag,
        )

        n = len(objects)
        # store outage: the chunk serves from the mirror at its covered
        # version (closure probe + shared-frontier walk need no store);
        # candidates that fall to the host replay get the typed
        # per-item error from the dead store via reference.filter_objects
        state, degraded = self._ensure_state_degraded_ok("filter")
        global_max = self.config.max_read_depth()
        depth = max_depth if 0 < max_depth <= global_max else global_max

        # monotone-only configs (no AND/NOT islands, no host-only
        # rewrites anywhere): membership needs an actual edge path, and
        # the reference has no trivial self-membership, so a subject or
        # candidate whose name never encodes is DEFINITIVELY invisible —
        # False with zero device or host work (errors the candidate's
        # region could raise map to False on the filter surface anyway).
        # Any island/host-only program disables the shortcut: a NOT can
        # make unknown names members, so they host-replay instead.
        monotone_vocab = not bool(
            np.any(state.snapshot.prog_flags & (_F_HOST | _F_ISL))
        )

        # -- shared-query encoding (one subject, one relation) ----------------
        ns_id = state.view.ns_id(namespace)
        rel_id = state.view.rel_id(relation)
        proxy = _RT(namespace=namespace, object="", relation=relation)
        if isinstance(subject, _SubjectSet):
            proxy.subject_set = subject
        else:
            proxy.subject_id = subject
        sub = state.view.encode_subject(proxy)
        if ns_id is not None and rel_id is not None and sub is None \
                and monotone_vocab:
            # known target node vocabulary, unknown subject, monotone
            # config: no edge can mention the subject — every candidate
            # is a definitive NOT_MEMBER
            self._count_filter(0, 0, 0, {})
            self.stats["filter_vocab"] = (
                self.stats.get("filter_vocab", 0) + n
            )
            if self.metrics is not None:
                self.metrics.filter_objects_total.labels("vocab").inc(n)
            return [False] * n
        if ns_id is None or rel_id is None or sub is None:
            # names unknown to graph+config under a non-monotone (or
            # unknown-relation) config: error semantics and NOT rewrites
            # may still apply per candidate — exact host eval
            self._degraded_host_filter_guard(degraded)
            verdicts = self._filter_host(
                namespace, relation, subject, objects, max_depth
            )
            self._count_filter(0, 0, n, {CAUSE_NAME_UNINDEXED: n})
            return verdicts
        # ketolint: allow[host-sync] reason=encode_subject returns host-side python/numpy scalars (vocabulary lookups never touch the device), so these int() coercions cannot sync
        skind, sa, sb = (int(x) for x in sub)

        # -- candidate encoding: one composed-key binary search ---------------
        from .snapshot import encode_object_column

        # ketolint: allow[host-sync] reason=ns_id is a host-side vocabulary lookup result (python int / numpy scalar), never a device value — no sync
        c_obj, c_valid = encode_object_column(state.view, int(ns_id), objects)

        # resolved/value masks instead of a per-candidate Python loop:
        # at 10k candidates the bookkeeping must be numpy-vectorized or
        # the host loop dominates the device work it orchestrates
        resolved = np.zeros(n, dtype=bool)
        value = np.zeros(n, dtype=bool)
        causes: dict[str, int] = {}
        n_closure = 0
        n_vocab = 0
        if monotone_vocab and not c_valid.all():
            # candidate names unknown to graph+config: no edge can seed
            # or match them — definitive NOT_MEMBER (the common "most of
            # these documents have no ACLs at all" case answers free)
            unknown = ~c_valid
            resolved |= unknown  # value stays False
            n_vocab = int(unknown.sum())
            self.stats["filter_vocab"] = (
                self.stats.get("filter_vocab", 0) + n_vocab
            )
            if self.metrics is not None:
                self.metrics.filter_objects_total.labels("vocab").inc(n_vocab)

        # -- 1. closure fast path: one batched subject-set gather -------------
        if self.closure_enabled:
            cl_view, cl_cause = self._closure_gate(state)
            if cl_view is not None:
                from .closure_kernel import (
                    closure_kernel_packed,
                    unpack_closure_results,
                )
                from .kernel import pack_queries

                B = next((b for b in _BUCKETS if b >= n), _BUCKETS[-1])
                q_obj = np.zeros(B, dtype=np.int32)
                q_obj[:n] = c_obj[:n]
                q_valid = np.zeros(B, dtype=bool)
                q_valid[:n] = c_valid[:n]
                launch_id = next_launch_id()
                with self.tracer.span("engine.filter_closure", batch=B):
                    flat = closure_kernel_packed(
                        cl_view.tables,
                        pack_queries(
                            q_obj,
                            np.full(B, rel_id, dtype=np.int32),
                            np.full(B, depth, dtype=np.int32),
                            np.full(B, skind, dtype=np.int32),
                            np.full(B, sa, dtype=np.int32),
                            np.full(B, sb, dtype=np.int32),
                            q_valid,
                        ),
                        cc_probes=cl_view.cc_probes,
                        ch_probes=cl_view.ch_probes,
                        has_dirty=cl_view.has_dirty,
                    )
                member, ccause, cstats = unpack_closure_results(
                    # ketolint: allow[host-sync] reason=this IS the closure probe's designated sync point: one packed readback carries verdicts, causes, and the launch stats vector — the shared single-transfer resolve contract
                    np.asarray(flat), B,
                )
                self._record_list_launch(
                    "filter_closure", B, n, cstats, launch_id
                )
                ok = c_valid & (ccause[:n] == 0)
                value |= member[:n] & ok
                resolved |= ok
                n_closure = int(ok.sum())
                declined = c_valid & ~ok
                if declined.any():
                    codes, cnts = np.unique(
                        ccause[:n][declined], return_counts=True
                    )
                    for code, cnt in zip(codes.tolist(), cnts.tolist()):
                        self._count_closure_fallback(
                            # ketolint: allow[host-sync] reason=code is a host python int from np.unique(...).tolist() over the already-synced readback — no device contact
                            CL_CAUSE_NAMES.get(int(code), "uncovered"),
                            # ketolint: allow[host-sync] reason=cnt is a host python int from the same tolist() — no device contact
                            int(cnt),
                        )
            elif cl_cause is not None:
                self._count_closure_fallback(cl_cause, n)

        vp = np.flatnonzero(c_valid & ~resolved)
        n_frontier = 0

        # -- 2. shared-frontier walk over the leftover column -----------------
        if len(vp):
            rstate = self._ensure_reverse_state()
            rnp = rstate.reverse_np
            if rstate.snapshot is not state.snapshot:
                # a compaction swapped the base snapshot between the
                # encode and the reverse build: candidate slots no
                # longer address these tables — exact host replay for
                # the leftovers (rare; the next call re-encodes)
                causes[CAUSE_NAME_UNINDEXED] = (
                    causes.get(CAUSE_NAME_UNINDEXED, 0) + len(vp)
                )
            elif rnp["host_all"]:
                # a NOT exists somewhere in the config: NOT-members
                # exist precisely where no path exists, which the
                # reachability walk cannot observe — exact host oracle
                causes["island_host"] = (
                    causes.get("island_host", 0) + len(vp)
                )
            else:
                uniq = np.unique(c_obj[vp])
                C = next(
                    (b for b in _BUCKETS if b >= len(uniq)), _BUCKETS[-1]
                )
                qc = pack_filter_query(
                    sa, int(reverse_subject_tag(skind, sb)), rel_id, depth,
                    uniq, C,
                )
                launch_id = next_launch_id()
                with self.tracer.span("engine.filter_launch", batch=C):
                    flat = filter_kernel_packed(
                        rstate.reverse_tables,
                        qc,
                        rvh_probes=rnp["rvh_probes"],
                        rsh_probes=rnp["rsh_probes"],
                        RK=rnp["RK"],
                        max_steps=int(
                            global_max + state.snapshot.n_config_rels + 4
                        ),
                        wildcard_rel=state.snapshot.wildcard_rel,
                        n_config_rels=max(state.snapshot.n_config_rels, 1),
                        frontier_cap=max(frontier_cap, 1024),
                        has_delta=state.has_delta,
                    )
                hit, wcause, fstats = unpack_filter_results(
                    # ketolint: allow[host-sync] reason=this IS the filter walk's designated sync point: resolve is the synchronize phase of the split-phase contract, and the single-buffer design makes this readback the ONE device->host transfer for the whole candidate column
                    np.asarray(flat), C,
                )
                self._record_list_launch(
                    "filter", C, len(vp), fstats, launch_id
                )
                if wcause == 0:
                    # clean completed walk: hits are members, unmarked
                    # candidates are definitive NOT_MEMBER
                    pos = np.searchsorted(uniq, c_obj[vp])
                    value[vp] = hit[pos]
                    resolved[vp] = True
                    n_frontier = len(vp)
                else:
                    name = CAUSE_NAMES.get(wcause, CAUSE_NAME_UNINDEXED)
                    causes[name] = causes.get(name, 0) + len(vp)

        # -- 3. exact host replay for everything still unresolved -------------
        host_idx = np.flatnonzero(~resolved)
        if len(host_idx):
            unindexed = len(host_idx) - sum(causes.values())
            if unindexed > 0:
                # candidates whose vocabulary never encoded (under a
                # non-monotone config, where unknown is not a verdict)
                causes[CAUSE_NAME_UNINDEXED] = (
                    causes.get(CAUSE_NAME_UNINDEXED, 0) + unindexed
                )
            self._degraded_host_filter_guard(degraded)
            host_verdicts = self._filter_host(
                namespace, relation, subject,
                # ketolint: allow[host-sync] reason=host_idx is host numpy (np.flatnonzero over a host mask) — these int() coercions never touch a device value
                [objects[int(i)] for i in host_idx], max_depth,
            )
            value[host_idx] = host_verdicts
            resolved[host_idx] = True
        self._count_filter(n_closure, n_frontier, len(host_idx), causes)
        return value.tolist()

    # -- check API ------------------------------------------------------------

    def check_is_member(
        self, r: RelationTuple, max_depth: int = 0
    ) -> bool:
        res = self.check_batch([r], max_depth)[0]
        if res.error is not None:
            raise res.error
        return res.membership == Membership.IS_MEMBER

    def check_relation_tuple(
        self, r: RelationTuple, max_depth: int = 0
    ) -> CheckResult:
        """Single check; proof trees come from the host engine, so this
        delegates entirely (the RPC check path wants only `allowed` and
        uses check_batch)."""
        return self.reference.check_relation_tuple(r, max_depth, self.nid)

    def expand(self, subject: Subject, max_depth: int = 0) -> Optional[Tree]:
        res = self.expand_batch([subject], max_depth)
        return res[0]

    def expand_batch(
        self,
        subjects: Sequence[Subject],
        max_depth: int = 0,
        frontier_cap: int = 1024,
        edge_cap: int = 4096,
        pool_cap: int = 0,
    ) -> list:
        """Batched expand: device BFS subgraph gather + exact host DFS
        assembly (engine/expand_kernel.py); SubjectIDs and overflowing /
        unknown-vocabulary / delta-dirty queries fall back to the host."""
        from ..ketoapi import SubjectSet as _SubjectSet
        from .expand_kernel import assemble_tree, decode_edge_buffer, expand_kernel

        n = len(subjects)
        if n == 0:
            return []
        state = self._ensure_expand_state()
        global_max = self.config.max_read_depth()
        depth = max_depth if 0 < max_depth <= global_max else global_max

        B = next((b for b in _BUCKETS if b >= n), None)
        if B is None:
            out = []
            step = _BUCKETS[-1]
            for i in range(0, n, step):
                out.extend(
                    self.expand_batch(subjects[i : i + step], max_depth,
                                      frontier_cap, edge_cap, pool_cap)
                )
            return out

        host_idx: set[int] = set()
        if isinstance(state.snapshot.obj_slots, ArrayMap):
            # big-vocab snapshots: vectorized node encoding (scalar
            # ArrayMap lookups cost ~1 ms each at 1e7 vocab)
            from .snapshot import encode_node_batch

            triples = []
            for i, sub in enumerate(subjects):
                if isinstance(sub, _SubjectSet):
                    triples.append((sub.namespace, sub.object, sub.relation))
                else:
                    triples.append(None)
                    host_idx.add(i)
            q_obj, q_rel, q_valid = encode_node_batch(state.view, triples, B)
            for i in np.flatnonzero(~q_valid[: len(subjects)]):
                # unknown to graph+config: no tuples can match => nil
                # tree, but keep exact host semantics for the verdict
                # ketolint: allow[host-sync] reason=host numpy value (np.flatnonzero over a host-side validity mask), not a device array — no sync occurs
                host_idx.add(int(i))
        else:
            q_obj = np.zeros(B, dtype=np.int32)
            q_rel = np.zeros(B, dtype=np.int32)
            q_valid = np.zeros(B, dtype=bool)
            for i, sub in enumerate(subjects):
                if not isinstance(sub, _SubjectSet):
                    host_idx.add(i)
                    continue
                node = state.view.encode_node(
                    sub.namespace, sub.object, sub.relation
                )
                if node is None:
                    # unknown to graph+config: no tuples can match =>
                    # nil tree, but keep exact host semantics
                    host_idx.add(i)
                    continue
                q_obj[i], q_rel[i] = node
                q_valid[i] = True

        launch_id = next_launch_id()
        if self.mesh is not None:
            from ..parallel.expand import sharded_expand_kernel

            sharded_csr, replicated_dirty = state.expand_tables
            eb = sharded_expand_kernel(
                self.mesh, sharded_csr, replicated_dirty,
                q_obj, q_rel,
                np.full(B, depth, dtype=np.int32),
                q_valid,
                fh_probes=state.fh_probes,
                max_steps=global_max + 2,
                frontier_cap=max(frontier_cap, B),
                edge_cap=edge_cap,
                axis=self.mesh.axis_names[0],
            )
        else:
            from .expand_kernel import (
                expand_kernel_packed,
                unpack_expand_results,
            )

            # single-buffer I/O + device-side compaction: the raw edge
            # buffers are [B*edge_cap] (~99% padding at real tree sizes),
            # so the readback, not kernel compute, would bound a batch.
            # Pool overflow
            # flags needs_host — exact host replay, same contract as
            # edge_cap overflow. Callers expecting wide trees (the scale
            # bench's RBAC fixtures) pass pool_cap explicitly; the
            # default sizes for serve-path trees (~10 nodes avg).
            pool_cap = pool_cap or max(32 * B, 4096)
            qpack = np.stack([
                q_obj, q_rel, np.full(B, depth, dtype=np.int32),
                q_valid.astype(np.int32),
            ]).astype(np.int32)
            flat = expand_kernel_packed(
                state.expand_tables,
                qpack,
                fh_probes=state.fh_probes,
                # static step budget keyed to the GLOBAL depth cap, not the
                # per-call depth (avoids one recompile per requested depth);
                # the loop exits early once the frontier drains
                max_steps=global_max + 2,
                frontier_cap=max(frontier_cap, B),
                edge_cap=edge_cap,
                pool_cap=pool_cap,
            )
            offs, root_has_children, needs_host, pool_cols, estats = (
                # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
                unpack_expand_results(np.asarray(flat), B, pool_cap)
            )
            self._record_list_launch("expand", B, n, estats, launch_id)
            eb = None
        if eb is not None:
            eb_pobj, eb_prel, eb_skind, eb_sa, eb_sb = (
                # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
                np.asarray(x) for x in eb[:5]
            )
            # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
            eb_count = np.asarray(eb[5])
            # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
            root_has_children = np.asarray(eb[6])
            # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
            needs_host = np.asarray(eb[7])
            if self.flightrec is not None and self.flightrec.enabled:
                # gated so a DISABLED recorder costs zero extra
                # transfers on the mesh path (the eager np.asarray
                # would otherwise run before record()'s enabled check)
                self._record_list_launch(
                    # ketolint: allow[host-sync] reason=part of the same designated resolve sync point: the sharded expand's replicated stats vector reads back with the batch results, not as an extra round-trip
                    "expand", B, n, np.asarray(eb[8]), launch_id
                )
            offs = None
            pool_cols = None

        results = []
        n_host_exp = 0
        for i, sub in enumerate(subjects):
            if i in host_idx or not q_valid[i] or needs_host[i]:
                n_host_exp += 1
                results.append(self.reference.expand(sub, max_depth, self.nid))
                continue
            if offs is not None:
                adjacency = decode_edge_buffer(
                    *pool_cols, int(offs[i + 1] - offs[i]), int(offs[i]),
                )
            else:
                adjacency = decode_edge_buffer(
                    eb_pobj, eb_prel, eb_skind, eb_sa, eb_sb,
                    int(eb_count[i]), i * edge_cap,
                )
            results.append(
                assemble_tree(
                    sub, int(q_obj[i]), int(q_rel[i]), depth,
                    adjacency, bool(root_has_children[i]), state.decoder,
                )
            )
        self.stats["device_expands"] = (
            self.stats.get("device_expands", 0) + n - n_host_exp
        )
        self.stats["host_expands"] = self.stats.get("host_expands", 0) + n_host_exp
        return results

    def check_batch(
        self, tuples: Sequence[RelationTuple], max_depth: int = 0
    ) -> list[CheckResult]:
        """Batched membership checks (no proof trees). Evaluates ON the
        calling thread, so a served BatchCheck's RequestTrace rides the
        ambient contextvar into the launch as its one rider: the engine
        stages land on the RPC's breakdown as they do for the batcher's
        riders. `tuples` is a run of RelationTuples or, from a handler
        that read them off the wire, CheckColumns: the same launch
        either way, and of columns only the items that need the host
        oracle are ever built as tuples (_resolve_items)."""
        from ..observability import current_request_trace

        return self.check_batch_resolve(
            self.check_batch_submit(
                tuples, max_depth, batch_rt=current_request_trace()
            )
        )

    def check_batch_host(
        self, tuples: Sequence[RelationTuple], max_depth: int = 0
    ) -> list[CheckResult]:
        """Exact host-oracle evaluation of a whole batch with ZERO device
        contact (no state build, no launch) — the circuit breaker's
        graceful-degradation route and the launch watchdog's recovery
        path (api/batcher.py host_check_batch): answers stay correct
        while the device path is unhealthy, latency degrades."""
        results = [
            self.reference.check_relation_tuple(t, max_depth, self.nid)
            for t in tuples
        ]
        self.stats["host_checks"] += len(tuples)
        if self.metrics is not None and tuples:
            self.metrics.check_batch_size.observe(len(tuples))
            self.metrics.checks_total.labels("host").inc(len(tuples))
        return results

    def explain_check(self, t: RelationTuple, max_depth: int = 0, rt=None):
        """One Check with a DecisionTrace beside the verdict — the §5m
        explain plane's engine half. The DEVICE verdict stays
        authoritative: the query rides the normal submit/resolve path
        (closure probe first, BFS kernel, cause-coded host replay) with
        the explain sink recording which tier answered; a host re-walk
        (reference.explain_check, complete-walk semantics — exactly what
        the kernels implement) then reconstructs the WITNESS PATH for
        ALLOW / the exhaustion summary for DENY, and is DIFFERENTIALLY
        CHECKED against the device verdict (`witness_consistent`; a
        store write racing the re-walk sets `witness_racy` instead of
        crying wolf). Returns (CheckResult, engine trace dict) — the
        serve helper (engine/explain.py) adds the snaptoken surface.

        Deliberately the slow path: no check-cache consult (a cached
        verdict has no fresh witness), one extra exact host walk per
        call — which is why the transports admission-bound it
        (`explain.max_per_s`).

        `rt` is the TRANSPORT's RequestTrace when serving (None for
        embedders): riding the caller's trace keeps the joins this
        plane exists for — the engine spans parent-link to the
        transport root in the exported trace, the flight-recorder entry
        carries the request's trace id (`?trace_id=` filter), and the
        launch ids land on the request log / slow-query line."""
        from ..observability import RequestTrace

        if rt is None:
            rt = RequestTrace()
        sink: list = [None]
        v_before = self.manager.version(nid=self.nid)
        try:
            handle = self.check_batch_submit(
                [t], max_depth, telemetry=[rt], explain_sink=sink
            )
            results, versions = self.check_batch_resolve_v(handle)
            res, version = results[0], versions[0]
            tier_info = sink[0] or {"tier": "device"}
        except Exception:
            # a failing device path must not take explain down with it:
            # the exact host oracle answers (the breaker-degrade route's
            # semantics), tier-coded so the trace says what happened
            res = self.reference.check_relation_tuple(
                t, max_depth, self.nid
            )
            version = None
            tier_info = {"tier": "host", "cause": "engine_error"}
        if version is None:
            # host replays read the LIVE store — the answer's version is
            # the store version at resolve (same rule the check cache
            # applies to unpinned answers)
            version = self.manager.version(nid=self.nid)
        allowed = res.error is None and res.allowed
        checker = self.reference._complete_checker()
        wx = checker.explain_check(t, max_depth, self.nid)
        v_after = self.manager.version(nid=self.nid)
        racy = v_after != v_before
        consistent = res.error is None and wx["allowed"] == allowed
        if not consistent and not racy and res.error is None:
            # a quiet-store witness/verdict disagreement is exactly the
            # divergence the differential suite hunts — log it loudly
            # (the trace still reports the device verdict as the answer)
            import logging

            logging.getLogger("keto_tpu").warning(
                "explain witness mismatch: device=%s host_walk=%s "
                "tuple=%s tier=%s", allowed, wx["allowed"], t,
                tier_info.get("tier"),
            )
        from .explain import base_trace

        trace = base_trace(
            allowed=allowed,
            tier=tier_info.get("tier"),
            cause=tier_info.get("cause"),
            closure_fallback=tier_info.get("closure_fallback"),
            version=version,
            max_depth=wx.get("max_depth"),
            witness=wx.get("witness", []) if allowed else [],
            exhaustion=None if allowed else wx.get("exhaustion"),
            witness_verdict=wx["allowed"],
            witness_consistent=consistent,
            witness_racy=racy,
            stages_ms={
                k: round(v * 1e3, 3) for k, v in rt.stages.items()
            },
            launch_ids=list(rt.launch_ids),
        )
        if res.error is not None:
            trace["error"] = str(res.error)
        return res, trace

    def check_batch_submit(
        self, tuples: Sequence[RelationTuple], max_depth: int = 0,
        telemetry=None, allow_closure: bool = True, explain_sink=None,
        batch_rt=None,
    ):
        """Launch the device kernel for one batch WITHOUT synchronizing.

        Returns an opaque in-flight handle for check_batch_resolve. jax
        dispatch is async: the returned handle holds device futures, so a
        caller can keep several batches in flight and the device
        pipelines them (one batch at a time is bound by the launch-to-
        readback latency).

        `telemetry` is an optional per-tuple list of RequestTrace|None:
        the engine's stage breakdown (assemble/dispatch at submit,
        device_wait/host_fallback at resolve) is added to every rider —
        batch-shared stages, attributed identically to each request in
        the batch — and emitted as per-request engine spans when tracing.
        `batch_rt` is the ONE RequestTrace a whole direct batch belongs
        to (check_batch on a BatchCheck handler's thread): it receives
        the same stages once per launch, every slice of a multi-split
        included; per-tuple fields (tier, the degraded floor) stay the
        riders' own.

        `explain_sink` is an optional per-tuple list the RESOLVE phase
        fills with each query's ANSWERING TIER ({"tier": closure |
        device | host, "cause": kernel CAUSE_* for host replays}) — the
        explain plane's plumb-through. Supported for batches that fit
        one bucket (explain rides 1-item batches); oversized multi-split
        batches ignore it.
        """
        n = len(tuples)
        if n == 0:
            return ("empty", [], None)
        # flight-recorder correlation: the launch id exists BEFORE any
        # failable work (fault injection, state build, XLA compile) so a
        # submit-phase failure carries it into classify_engine_error's
        # typed CheckBatchFailedError and the auto-dump
        launch_id = next_launch_id()
        try:
            return self._check_batch_submit_inner(
                tuples, max_depth, telemetry, launch_id, allow_closure,
                explain_sink=explain_sink, batch_rt=batch_rt,
            )
        except Exception as e:
            # don't clobber an id a recursive split-slice submit already
            # stamped: the slice's id has the ring entry, not the parent's
            if getattr(e, "launch_id", None) is None:
                e.launch_id = launch_id
            raise

    def _check_batch_submit_inner(
        self, tuples: Sequence[RelationTuple], max_depth: int,
        telemetry, launch_id: int, allow_closure: bool = True,
        explain_sink=None, batch_rt=None,
    ):
        n = len(tuples)
        # fault-injection point (keto_tpu/faults.py): a stall here models
        # a wedged device launch, an error a dying device — BEFORE
        # any state build, so the batcher's watchdog/breaker see exactly
        # what a real launch failure looks like. Disarmed: one dict miss.
        _faults.inject("device_launch")
        B = next((b for b in self._allowed_buckets if b >= n), None)
        if B is None:
            # split oversized batches along the largest allowed bucket;
            # all slices go in flight BEFORE any synchronizes
            step = self._allowed_buckets[-1]
            return (
                "multi",
                [
                    self.check_batch_submit(
                        tuples[i : i + step], max_depth,
                        telemetry=(
                            telemetry[i : i + step] if telemetry else None
                        ),
                        allow_closure=allow_closure, batch_rt=batch_rt,
                    )
                    for i in range(0, n, step)
                ],
                None,
            )

        with StageSpan("assemble", launch_id) as asm:
            # store outage: the breaker-open path serves this batch from
            # the existing mirror + delta overlay at its covered version
            # (the response snaptoken is the staleness bound); riders
            # pinned to a newer version are routed to the host-replay
            # path below, where the dead store answers them with the
            # typed per-item 503
            state, degraded = self._ensure_state_degraded_ok("check")
            # marker fault (keto_tpu/faults.py mirror_corrupt): flip one
            # bit in a device table before this launch — the
            # silent-HBM-fault stand-in the anti-entropy scrubber
            # (engine/scrub.py) must detect and auto-repair. Disarmed:
            # one dict miss.
            corrupt_spec = _faults.get("mirror_corrupt")
            if corrupt_spec is not None and corrupt_spec.should_fire():
                self.corrupt_mirror()
            global_max = self.config.max_read_depth()
            depth = max_depth if 0 < max_depth <= global_max else global_max
            queries = self._encode_queries(
                state, tuples, B, depth, degraded, telemetry
            )

            # Leopard closure fast path: when the index covers this
            # engine state (same base snapshot, synced through
            # covered_version), the WHOLE batch rides one single-step
            # intersection launch first — chain depth stops mattering.
            # Queries the index cannot answer (uncovered/dirty/invalid)
            # are re-submitted through the BFS kernel at resolve time
            # with cause-coded counters; host-side skip causes
            # (unbuilt/stale/lag) count here, once per query.
            # allow_closure=False is the resolve-time re-submission
            # itself.
            cl_view = None
            if allow_closure and self.closure_enabled:
                cl_view, cl_cause = self._closure_gate(state)
                if cl_view is None and cl_cause is not None:
                    self._count_closure_fallback(cl_cause, n)

        meta = {
            "state": state,
            "tuples": tuples,
            "n": n,
            "B": B,
            "max_depth": max_depth,
            "q_valid": queries[-1],
            "telemetry": telemetry,
            "batch_rt": batch_rt,
            "explain_sink": explain_sink,
            # flight-recorder fields, read back at the resolve sync
            # point together with the device stats vector
            "launch_id": launch_id,
            "t_submit": asm.start,
        }
        if cl_view is not None:
            from .closure_kernel import (
                closure_kernel_packed,
                estimate_closure_gather_bytes,
            )
            from .kernel import pack_queries

            with StageSpan("dispatch", launch_id) as dsp, self.tracer.span(
                "engine.closure_launch", batch=B
            ):
                outputs = closure_kernel_packed(
                    cl_view.tables,
                    pack_queries(*queries),
                    cc_probes=cl_view.cc_probes,
                    ch_probes=cl_view.ch_probes,
                    has_dirty=cl_view.has_dirty,
                )
            meta.update(
                kind="closure",
                step_cap=1,
                gather_step_bytes=estimate_closure_gather_bytes(
                    B, cl_view.cc_probes, cl_view.ch_probes,
                    cl_view.has_dirty,
                ),
            )
            return self._launched("closure", outputs, meta, asm, dsp)

        # per-launch frontier sizing: every gather, scatter and scan of a
        # BFS step is dense over the frontier length F, padding included,
        # so a launch costs what its F costs whatever is live in it. On
        # the chip the same 2,048 items read 103.5 ms of device time at
        # F=16384 and 50.2 ms at F=8192 over 20 steps, 14.4 and 7.1 ms
        # over 2 (PERF.md section 6, PR 34). So F follows the bucket, and
        # the bucket the batch (_BUCKETS); queries whose exploration
        # outgrows it are flagged needs_host and replayed exactly — a
        # safe (slower) path.
        if self.auto_frontier:
            # 4x headroom over the seed tasks, at every rung: a 16-item
            # launch at F=64 reads 1.6 ms of device time in 7.2 ms of
            # wall on the chip (chip_smoke.py, PR 34) — small-batch serve
            # latency is the launch's fixed cost, not its frontier
            launch_cap = min(self.frontier_cap, max(4 * B, 64))
        else:
            launch_cap = self.frontier_cap

        # islands: one ctx block of K leaves per instance; cap scales with
        # the batch so island-heavy workloads don't immediately overflow
        # to host replay (overflow is safe, just slow)
        island_cap = 2 * B if state.snapshot.island_circuits else 0
        n_shards = 1
        with StageSpan("dispatch", launch_id) as dsp, self.tracer.span(
            "engine.kernel_launch", batch=B, frontier=launch_cap
        ):
            if self.mesh is not None:
                from ..parallel.kernel import (
                    sharded_check_kernel,
                    sharded_static_config,
                )

                statics = sharded_static_config(
                    state.sharded, global_max, launch_cap,
                    n_island_cap=island_cap, has_delta=state.has_delta,
                )
                # dict view of the statics tuple for the gather-bytes
                # estimate (each shard runs the full per-step gather set
                # over its own tables)
                cfg = dict(zip(_KERNEL_STATICS, statics))
                n_shards = int(self.mesh.devices.size)
                sharded_tables, replicated_tables = state.tables
                outputs = sharded_check_kernel(
                    self.mesh, sharded_tables, replicated_tables, *queries,
                    statics=statics, axis=self.mesh.axis_names[0],
                )
            else:
                from .kernel import check_kernel_packed, pack_queries

                cfg = kernel_static_config(
                    state.snapshot, global_max, launch_cap,
                    n_island_cap=island_cap, has_delta=state.has_delta,
                )
                # single-buffer I/O: ONE host->device upload (the packed
                # query array) and ONE device->host readback at resolve,
                # in place of seven uploads and five readbacks that each
                # pay a transfer's fixed cost.
                outputs = check_kernel_packed(
                    state.tables, pack_queries(*queries), **cfg
                )
        # everything past the launch is deferred to resolve: touching the
        # outputs here would block on the device round-trip
        meta.update(
            island_cap=island_cap if self.mesh is None else None,
            launch_cap=launch_cap,
            step_cap=int(cfg["max_steps"]),
            gather_step_bytes=n_shards * estimate_step_gather_bytes(cfg),
        )
        return self._launched("batch", outputs, meta, asm, dsp)

    def _launched(self, kind: str, outputs, meta: dict, asm, dsp):
        """The in-flight handle of a launch just dispatched. The stage
        seconds so far ride it (resolve adds device_wait / resolve /
        host_fallback and finalizes attribution), and the device-feed
        account learns that the launch entered the device queue."""
        t_done = dsp.start + dsp.seconds
        meta["stage_s"] = {
            "assemble": dsp.start - asm.start,
            "dispatch": dsp.seconds,
        }
        # the requests this launch answers: the batcher's riders, or the
        # one request a direct batch belongs to
        meta["riders"] = [
            rt
            for rt in (meta["telemetry"] or (meta["batch_rt"],))
            if rt is not None
        ]
        meta["feed_token"], meta["starved_s"] = self.device_feed.dispatched(
            asm.start, dsp.start, t_done, meta["riders"]
        )
        return (kind, outputs, meta)

    def _encode_queries(
        self, state, tuples: Sequence[RelationTuple], B: int, depth: int,
        degraded: bool, telemetry,
    ):
        """One batch's query columns padded to bucket `B`, in
        pack_queries' order: (q_obj, q_rel, q_depth, q_skind, q_sa, q_sb,
        q_valid)."""
        q_depth = np.full(B, depth, dtype=np.int32)
        if isinstance(state.snapshot.obj_slots, ArrayMap) or B > 4096:
            # vectorized batch encoding for big (ArrayMap) vocabs at any
            # size — scalar lookups cost ~1 ms each at 1e7 vocab and
            # dominated check_batch (988/s engine vs 77k/s kernel) —
            # and for LARGE batches on dict vocabs too: the scalar loop
            # scales linearly (~19 ms at B=16384 on the bench fixture,
            # serialized against the kernel launch) while the vectorized
            # path's fixed costs (list->U-array conversions, key
            # composition) amortize. Small dict batches keep the scalar
            # loop (gate is B > 4096, so the measured-scalar-faster
            # 4096 bucket stays scalar): 4.7 ms/4096 vs 7.0 ms vectorized.
            q_obj, q_rel, q_skind, q_sa, q_sb, q_valid = encode_query_batch(
                state.view, tuples, B
            )
        else:
            q_obj = np.zeros(B, dtype=np.int32)
            q_rel = np.zeros(B, dtype=np.int32)
            q_skind = np.zeros(B, dtype=np.int32)
            q_sa = np.full(B, -2, dtype=np.int32)  # sentinel: matches nothing
            q_sb = np.zeros(B, dtype=np.int32)
            q_valid = np.zeros(B, dtype=bool)

            encode_node = state.view.encode_node
            encode_subject = state.view.encode_subject_fields
            rows = zip(*CheckColumns.of(tuples).columns())
            for i, (ns, obj, rel, skind, sns, sobj, srel) in enumerate(rows):
                node = encode_node(ns, obj, rel)
                if node is None:
                    # namespace/object/relation absent from graph+config:
                    # no edge can match, but error semantics (missing
                    # relation in a configured namespace) still apply ->
                    # exact host eval (q_valid[i] stays False, routing it
                    # to the replay loop)
                    continue
                q_obj[i], q_rel[i] = node
                subject = encode_subject(skind, sns, sobj, srel)
                if subject is not None:
                    q_skind[i], q_sa[i], q_sb[i] = subject
                # unknown subject keeps the sentinel: traversal still runs
                # so error flags surface, but no direct probe can hit
                q_valid[i] = True

        if degraded and telemetry:
            # no-time-travel floor: a rider whose snaptoken enforcement
            # ran BEFORE the outage (min_version newer than the mirror
            # covers) must not receive a mirror answer its token would
            # claim fresher than it is — invalidating it routes it to
            # the host replay loop, where the dead store yields the
            # typed per-item StoreUnavailableError
            covered = state.covered_version
            for i, rt in enumerate(telemetry):
                mv = getattr(rt, "min_version", None)
                if mv is not None and mv > covered:
                    q_valid[i] = False
        return q_obj, q_rel, q_depth, q_skind, q_sa, q_sb, q_valid

    def check_batch_resolve(self, handle) -> list[CheckResult]:
        """Synchronize one in-flight batch and produce its CheckResults
        (device readback + island combine + exact host replays)."""
        return self.check_batch_resolve_v(handle)[0]

    def check_batch_resolve_v(self, handle):
        """check_batch_resolve with version plumb-through: returns
        (results, versions) where versions[i] is the store version the
        answer is authoritative at — the evaluated state's
        covered_version for device-path answers — or None for
        host-replayed items (the replay reads the LIVE store, so its
        answer is not pinned to any particular version). The serve-side
        check cache (api/check_cache.py) stores verdicts at exactly
        these versions; None falls back to its raced-write re-check."""
        kind, outputs, meta = handle
        if kind == "empty":
            return [], []
        if kind == "multi":
            results: list[CheckResult] = []
            versions: list = []
            for h in outputs:
                r, v = self.check_batch_resolve_v(h)
                results.extend(r)
                versions.extend(v)
            return results, versions
        if kind == "closure":
            try:
                return self._closure_batch_resolve_v(outputs, meta)
            except Exception as e:
                # a failing leftover re-submission already stamped its
                # own launch id — that id has the ring entry
                if getattr(e, "launch_id", None) is None:
                    e.launch_id = meta.get("launch_id")
                raise
        try:
            return self._check_batch_resolve_v_inner(outputs, meta)
        except Exception as e:
            # resolve-phase failures carry the launch id into the typed
            # error surface and the flight-recorder dump
            e.launch_id = meta.get("launch_id")
            raise

    def _closure_batch_resolve_v(self, outputs, meta):
        """Synchronize one closure launch: read the intersection verdicts
        back, answer every resolved query at the view's (== the state's)
        covered version, and re-submit the cause-coded remainder through
        the BFS kernel (allow_closure=False — exactly one closure attempt
        per batch). The common serving case resolves the whole batch here
        with zero BFS contact."""
        from .closure_kernel import CL_CAUSE_NAMES, unpack_closure_results

        state = meta["state"]
        tuples = meta["tuples"]
        n, B, max_depth = meta["n"], meta["B"], meta["max_depth"]
        telemetry = meta.get("telemetry")
        with self._device_wait(meta) as waited:
            member, cause, stats = unpack_closure_results(
                # ketolint: allow[host-sync] reason=this IS the closure batch's designated sync point: one packed readback carries verdicts, causes, and the launch stats vector — the same single-transfer resolve contract as every other kernel
                np.asarray(outputs), B,
            )

        sink = meta.get("explain_sink")
        results: list = [None] * n
        versions: list = [None] * n
        covered = state.covered_version
        leftover: list[int] = []
        leftover_cause: dict[int, str] = {}
        causes: dict[str, int] = {}
        with StageSpan("resolve", meta.get("launch_id")) as resolved:
            for i in range(n):
                c = int(cause[i])
                if c == 0:
                    results[i] = (
                        RESULT_IS_MEMBER if member[i] else RESULT_NOT_MEMBER
                    )
                    versions[i] = covered
                    if sink is not None:
                        sink[i] = {"tier": "closure"}
                    if telemetry is not None and telemetry[i] is not None:
                        telemetry[i].tier = "closure"
                else:
                    leftover.append(i)
                    name = CL_CAUSE_NAMES.get(c, "uncovered")
                    leftover_cause[i] = name
                    causes[name] = causes.get(name, 0) + 1
            n_hits = n - len(leftover)
            self.stats["closure_hits"] = (
                self.stats.get("closure_hits", 0) + n_hits
            )
            if self.metrics is not None:
                if n_hits:
                    self.metrics.closure_hits_total.inc(n_hits)
                    self.metrics.checks_total.labels("device").inc(n_hits)
                self.metrics.check_batch_size.observe(n)
            self.stats["device_checks"] += n_hits
            for name, cnt in causes.items():
                self._count_closure_fallback(name, cnt)

        meta["closure_resolved"] = n_hits
        self._finish_check_stages(
            meta, waited, resolved, 0.0, n, B, stats=stats,
            host_causes=causes,
        )
        if leftover:
            sub_sink = [None] * len(leftover) if sink is not None else None
            sub_handle = self.check_batch_submit(
                (
                    tuples.take(leftover)
                    if isinstance(tuples, CheckColumns)
                    else [tuples[i] for i in leftover]
                ),
                max_depth,
                telemetry=(
                    [telemetry[i] for i in leftover] if telemetry else None
                ),
                allow_closure=False,
                explain_sink=sub_sink,
                batch_rt=meta["batch_rt"],
            )
            sub_res, sub_ver = self.check_batch_resolve_v(sub_handle)
            for j, i in enumerate(leftover):
                results[i] = sub_res[j]
                versions[i] = sub_ver[j]
                if sink is not None:
                    info = dict(sub_sink[j] or {"tier": "device"})
                    # the explain trace says WHY the closure probe
                    # declined this query before the BFS ride answered
                    info["closure_fallback"] = leftover_cause.get(i)
                    sink[i] = info
        return results, versions

    @contextlib.contextmanager
    def _device_wait(self, meta: dict):
        """A batch's designated sync point as the `device_wait` stage.
        Whether the readback lands or raises, the device-feed account
        learns that the launch left the device queue."""
        with StageSpan("device_wait", meta.get("launch_id")) as waited:
            try:
                yield waited
            finally:
                meta["device_s"] = self.device_feed.ready(
                    meta["feed_token"], time.perf_counter()
                )

    def _check_batch_resolve_v_inner(self, outputs, meta):
        state = meta["state"]
        n, B = meta["n"], meta["B"]
        q_valid = meta["q_valid"]
        with self._device_wait(meta) as waited:
            if meta.get("island_cap") is not None:
                # packed single-device result: ONE device->host readback —
                # the launch stats vector rides the same transfer
                from .kernel import unpack_results

                ctx_hit, needs_host, isl_parent, isl_pid, n_isl, stats = (
                    unpack_results(
                        # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
                        np.asarray(outputs), B, meta["island_cap"],
                        state.snapshot.K,
                    )
                )
                ctx_hit = ctx_hit.copy()
            else:
                ctx_hit, needs_host, isl_parent, isl_pid, n_isl, stats = outputs
                # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
                ctx_hit = np.asarray(ctx_hit).copy()
                # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
                needs_host = np.asarray(needs_host)
                # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
                n_isl = int(n_isl)
                # ketolint: allow[host-sync] reason=part of the same designated resolve sync point: the mesh path's replicated stats vector reads back with the batch results, not as an extra round-trip
                stats = np.asarray(stats)
            if _faults.get("batch_corrupt") is not None:
                # fault-injection point: poison every slot's device verdict
                # so each query takes the exact-host-replay escape hatch the
                # capacity overflows use — answers must stay byte-correct
                _faults.inject("batch_corrupt")
                # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
                needs_host = np.maximum(np.asarray(needs_host), 1)
            if n_isl:
                from .islands import combine_islands

                member = combine_islands(
                    # ketolint: allow[host-sync] reason=this IS the batch's designated sync point: resolve is the synchronize phase of the split-phase submit/resolve contract, and the single-buffer I/O design makes this readback the ONE device->host transfer for the whole batch
                    ctx_hit, np.asarray(isl_parent), np.asarray(isl_pid),
                    n_isl, state.snapshot.island_circuits, B, state.snapshot.K,
                )
            else:
                member = ctx_hit[:B]

        sink = meta.get("explain_sink")
        telemetry = meta.get("telemetry")
        covered = state.covered_version
        n_host = 0
        host_s = 0.0
        host_causes: dict[str, int] = {}
        with StageSpan("resolve", meta.get("launch_id")) as resolved, (
            self.tracer.span("engine.resolve_batch", batch=n)
        ) as sp:
            if (
                n <= B
                and bool(q_valid[:n].all())
                and not bool((needs_host[:n] > 0).any())
            ):
                # fast path: every query ran on device (the steady
                # serving state) — one numpy reduction decides, then
                # results come from a bare list comprehension over the
                # verdict array instead of the per-item bookkeeping loop
                # (~3x less host time per batch, and the host loop
                # serializes against the next launch's encode)
                results = [
                    RESULT_IS_MEMBER if m else RESULT_NOT_MEMBER
                    for m in member[:n].tolist()
                ]
                versions: list = [covered] * n
                if sink is not None:
                    for i in range(n):
                        sink[i] = {"tier": "device"}
                if telemetry is not None:
                    for rt in telemetry:
                        if rt is not None:
                            rt.tier = "device"
            else:
                results, versions, n_host, host_s = self._resolve_items(
                    meta, member, needs_host, host_causes
                )
            sp.set_attribute("host_replays", n_host)
            self.stats["device_checks"] += n - n_host
            self.stats["host_checks"] += n_host
            for cause, cnt in host_causes.items():
                self.stats["host_cause"][cause] = (
                    self.stats["host_cause"].get(cause, 0) + cnt
                )
            if self.metrics is not None:
                self.metrics.check_batch_size.observe(n)
                self.metrics.checks_total.labels("device").inc(n - n_host)
                if n_host:
                    self.metrics.checks_total.labels("host").inc(n_host)
                for cause, cnt in host_causes.items():
                    self.metrics.host_fallback_total.labels(cause).inc(cnt)
        self._finish_check_stages(
            meta, waited, resolved, host_s, n, B,
            stats=stats, host_causes=host_causes,
        )
        return results, versions

    def _resolve_items(self, meta, member, needs_host, host_causes: dict):
        """The per-item bookkeeping loop of a batch that did NOT wholly
        run on device: device verdicts where they stand, exact host
        replay (counted by cause into `host_causes`) for the rest.
        Returns (results, versions, host replays, host replay seconds)."""
        state = meta["state"]
        B, max_depth, q_valid = meta["B"], meta["max_depth"], meta["q_valid"]
        sink = meta.get("explain_sink")
        telemetry = meta.get("telemetry")
        covered = state.covered_version
        results = []
        versions: list = []
        n_host = 0
        host_s = 0.0
        # identical host-replayed queries within one batch evaluate once
        # (an adversarial batch of 4096 same-tuple fallbacks would
        # otherwise serialize 4096 recursive walks)
        replay_memo: dict[tuple, CheckResult] = {}
        tuples = meta["tuples"]
        for i in range(meta["n"]):
            if i < B and q_valid[i] and not needs_host[i]:
                # shared immutable singletons: 4096 CheckResult
                # constructions per batch are measurable on the
                # 1-core serve host
                results.append(
                    RESULT_IS_MEMBER if member[i] else RESULT_NOT_MEMBER
                )
                versions.append(covered)
                if sink is not None:
                    sink[i] = {"tier": "device"}
                if telemetry is not None and telemetry[i] is not None:
                    telemetry[i].tier = "device"
            else:
                n_host += 1
                # cause bookkeeping: the kernel reports a CAUSE_* code
                # per query; queries that never reached the device
                # (unknown vocabulary) count as "unindexed"
                if i < B and q_valid[i]:
                    cause = CAUSE_NAMES.get(
                        int(needs_host[i]), CAUSE_NAME_UNINDEXED
                    )
                else:
                    cause = CAUSE_NAME_UNINDEXED
                host_causes[cause] = host_causes.get(cause, 0) + 1
                t = tuples[i]  # of CheckColumns, built here
                # field-structured key: the display string is NOT
                # injective (a subject_id spelled "(ns:obj#rel)"
                # renders like a real subject set)
                key = (
                    t.namespace, t.object, t.relation, t.subject_id,
                    t.subject_set, max_depth,
                )
                res = replay_memo.get(key)
                if res is None:
                    t_host = time.perf_counter()
                    res = self.reference.check_relation_tuple(
                        t, max_depth, self.nid
                    )
                    host_s += time.perf_counter() - t_host
                    replay_memo[key] = res
                results.append(res)
                versions.append(None)
                if sink is not None:
                    sink[i] = {"tier": "host", "cause": cause}
                if telemetry is not None and telemetry[i] is not None:
                    telemetry[i].tier = "host"
        if self.metrics is not None and isinstance(tuples, CheckColumns):
            # a batch that came as columns built one RelationTuple a
            # replayed item, for the oracle, and none for the others:
            # beside keto_tpu_checks_total, the share of a served
            # BatchCheck's items that still cost an object
            self.metrics.check_batch_tuples_built_total.inc(n_host)
        return results, versions, n_host, host_s

    def _finish_check_stages(
        self, meta, waited, resolved, host_s: float, n: int, B: int,
        stats=None, host_causes=None,
    ) -> None:
        """Finalize one batch's stage attribution: per-stage histogram
        samples (once per batch), each rider's RequestTrace stages
        (+ launch id), the flight-recorder entry, and per-request engine
        spans when tracing. `waited` and `resolved` are the batch's
        device_wait and resolve StageSpans; `resolve` is what followed
        the readback less the `host_s` seconds of host replay inside it.
        Batch-shared stages are attributed identically to every rider —
        the breakdown says where the BATCH spent its time, which is what
        a tail-latency investigation needs."""
        stage_s = dict(meta.get("stage_s") or ())
        stage_s["device_wait"] = waited.seconds
        stage_s["resolve"] = max(0.0, resolved.seconds - host_s)
        if host_s > 0.0:
            stage_s["host_fallback"] = host_s
        riders = meta["riders"]
        if self.metrics is not None:
            # exemplar: the first rider's trace id rides the stage
            # histogram buckets (OpenMetrics exemplars — the metrics ->
            # trace join); batch-shared stages observe once, so one
            # representative trace id per batch is the honest grain
            exemplar_tid = riders[0].ctx.trace_id if riders else None
            for name, dur in stage_s.items():
                self.metrics.observe_stage(name, dur, trace_id=exemplar_tid)
        self._record_launch(meta, stats, n, B, host_causes, stage_s, riders)
        spans = getattr(self.tracer, "active", False)
        launch_id = meta.get("launch_id")
        for rt in riders:
            if launch_id is not None:
                ids = getattr(rt, "launch_ids", None)
                if ids is not None:
                    ids.append(launch_id)
            for name, dur in stage_s.items():
                rt.add_stage(name, dur)
                if spans:
                    # launch_id rides the span: the OTLP exporter turns
                    # it into a `flightrec.launch` span EVENT, so a
                    # trace at the collector points at its ring entry
                    self.tracer.record(
                        f"engine.{name}", ctx=rt.ctx, duration_s=dur,
                        batch=B, launch_id=launch_id,
                    )


    def _record_launch(
        self, meta, stats, n: int, B: int, host_causes, stage_s, riders
    ) -> None:
        """One flight-recorder entry + the keto_tpu_launch_* metric
        samples for a resolved device batch. Everything here is host
        arithmetic over the counters that rode the batch's existing
        readback — no extra device contact."""
        sd = launch_stats_dict(stats) if stats is not None else {}
        step_cap = int(meta.get("step_cap", 0))
        gather_bytes = sd.get("steps", 0) * int(
            meta.get("gather_step_bytes", 0)
        )
        occupancy = (n / B) if B else 1.0
        if self.metrics is not None and sd:
            self.metrics.observe_launch(
                sd["steps"], step_cap, sd["frontier_max"], gather_bytes,
                sd["edge_rows"], round(1.0 - occupancy, 4),
            )
        fr = self.flightrec
        if fr is None or not fr.enabled:
            return
        t_submit = meta.get("t_submit")
        entry = {
            "launch_id": meta.get("launch_id"),
            "kind": meta.get("kind", "check"),
            "nid": self.nid,
            "bucket": B,
            "n": n,
            "occupancy": round(occupancy, 4),
            "frontier_cap": meta.get("launch_cap"),
            "step_cap": step_cap,
            "gather_bytes_est": gather_bytes,
            "host_causes": dict(host_causes or {}),
            "trace_ids": [rt.ctx.trace_id for rt in riders],
            "stage_ms": {
                k: round(v * 1e3, 3) for k, v in stage_s.items()
            },
            # the device-feed account's view of this launch: its
            # estimated device service time, and how long the device
            # queue had stood empty when it was dispatched
            "device_ms": round(meta.get("device_s", 0.0) * 1e3, 3),
            "starved_ms": round(meta.get("starved_s", 0.0) * 1e3, 3),
            **sd,
        }
        if "closure_resolved" in meta:
            entry["closure_resolved"] = meta["closure_resolved"]
        if t_submit is not None:
            entry["wall_ms"] = round(
                (time.perf_counter() - t_submit) * 1e3, 3
            )
        fr.record(entry)
