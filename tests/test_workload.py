"""Workload observatory + SLO plane (§5o): Space-Saving sketch
guarantees, windowed rotation, SLO burn-rate math with injected clocks
(fast-burn both-windows rule, WARNING/recovery lines, the quantized
window), the buffered-fold feed semantics, config schema keys, and the
live admin endpoints (/admin/hotkeys, /admin/slo, /admin/workload)
plus the request log's `tier=` attribute and the per-tier histogram's
OpenMetrics exemplars on a real daemon."""

import bisect
import json
import logging
import random
import time
from collections import Counter
import urllib.error
import urllib.request

import pytest

from keto_tpu.config import Config, ConfigError
from keto_tpu.api import ReadClient, open_channel
from keto_tpu.api.daemon import Daemon
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.observability import Metrics
from keto_tpu.registry import Registry
from keto_tpu.observability_workload import (
    PROFILE_SCHEMA,
    SLOEngine,
    SpaceSaving,
    TIERS,
    WindowedSketch,
    WorkloadObservatory,
    code_is_ok,
    subject_key,
)

NAMESPACES = [Namespace(name="files")]
TUPLE = "files:doc#owner@alice"


# -- sketches ------------------------------------------------------------------


class TestSpaceSaving:
    def test_exact_under_capacity(self):
        sk = SpaceSaving(capacity=8)
        for key, n in (("a", 5), ("b", 3), ("c", 1)):
            for _ in range(n):
                sk.offer(key)
        assert sk.top(3) == [("a", 5, 0), ("b", 3, 0), ("c", 1, 0)]
        assert sk.total == 9
        assert len(sk) == 3

    def test_eviction_inherits_min_count_as_err(self):
        sk = SpaceSaving(capacity=2)
        for _ in range(5):
            sk.offer("a")
        for _ in range(3):
            sk.offer("b")
        sk.offer("c")  # evicts b (the min), inherits its count as err
        top = dict((k, (cnt, err)) for k, cnt, err in sk.top(2))
        assert top["a"] == (5, 0)
        assert top["c"] == (4, 3)  # count = 3 + 1, overestimates by <= 3
        assert "b" not in top
        assert sk.total == 9  # total counts evicted traffic too

    def test_zipfian_heavy_hitters_recovered_with_error_bound(self):
        # deterministic Zipfian (s=1.1) stream over 1000 keys through a
        # 64-entry sketch: every true top-10 key must be tracked (each
        # exceeds total/capacity by construction at s=1.1), and every
        # reported count must satisfy the Space-Saving bound
        # true <= count <= true + err
        rng = random.Random(7)
        n_keys, s = 1000, 1.1
        weights = [1.0 / (i + 1) ** s for i in range(n_keys)]
        cum, acc = [], 0.0
        for w in weights:
            acc += w
            cum.append(acc)
        truth: dict[str, int] = {}
        sk = SpaceSaving(capacity=64)
        import bisect

        for _ in range(20000):
            i = bisect.bisect_left(cum, rng.random() * cum[-1])
            key = f"k{i}"
            truth[key] = truth.get(key, 0) + 1
            sk.offer(key)
        true_top10 = {
            k for k, _ in sorted(
                truth.items(), key=lambda kv: kv[1], reverse=True
            )[:10]
        }
        reported = {k: (cnt, err) for k, cnt, err in sk.top(64)}
        assert true_top10 <= set(reported), (
            "every guaranteed-hot key must be tracked"
        )
        for key in true_top10:
            cnt, err = reported[key]
            assert truth[key] <= cnt <= truth[key] + err

    def test_batch_offer_n(self):
        sk = SpaceSaving(capacity=4)
        sk.offer("a", n=16)  # the pre-aggregated fold path
        sk.offer("b", n=2)
        assert sk.top(1) == [("a", 16, 0)]
        assert sk.total == 18


def _stream(kind: str, seed: int, n: int, n_keys: int = 2000) -> list[str]:
    """A seeded key stream: Zipfian (s=1.1) or uniform over n_keys."""
    rng = random.Random(seed)
    if kind == "uniform":
        return [f"k{rng.randrange(n_keys)}" for _ in range(n)]
    cum, acc = [], 0.0
    for i in range(n_keys):
        acc += 1.0 / (i + 1) ** 1.1
        cum.append(acc)
    return [
        f"k{bisect.bisect_left(cum, rng.random() * acc)}" for _ in range(n)
    ]


def _assert_space_saving_guarantees(sk: SpaceSaving, truth: Counter):
    assert sk.total == sum(truth.values())
    assert len(sk) <= sk.capacity
    reported = {key: (cnt, err) for key, cnt, err in sk.top(sk.capacity)}
    for key, (cnt, err) in reported.items():
        assert cnt >= truth[key], "a tracked count under the true count"
        assert cnt - err <= truth[key], "count - err over the true count"
    hot = {k for k, n in truth.items() if n > sk.total / sk.capacity}
    assert hot <= set(reported), "a key over total/capacity is not tracked"
    # what the first guarantee rests on: the tracked counts never sum
    # to more than everything offered
    assert sum(cnt for cnt, _ in reported.values()) <= sk.total


class TestOfferMany:
    CAPACITY = 64

    @pytest.mark.parametrize("fold", [16, 256, 4096])
    @pytest.mark.parametrize("kind", ["zipf", "uniform"])
    def test_guarantees_hold_fold_after_fold(self, kind, fold):
        # folds of 16 stay under the merge line of a 64-entry sketch
        # (so many single offers), folds of 256 and 4,096 cross it (one
        # merge): the same guarantees on both sides, after every fold
        sk = SpaceSaving(self.CAPACITY)
        truth: Counter = Counter()
        stream = _stream(kind, seed=fold, n=max(8 * fold, 4096))
        for at in range(0, len(stream), fold):
            counts = Counter(stream[at:at + fold])
            truth.update(counts)
            sk.offer_many(counts)
            _assert_space_saving_guarantees(sk, truth)
        if kind == "zipf":
            top10 = {k for k, _ in truth.most_common(10)}
            assert top10 <= {k for k, _, _ in sk.top(self.CAPACITY)}

    def test_merges_and_single_offers_interleave(self):
        # the heap a merge rebuilds must serve the evictions after it,
        # and a merge must read counts that offers moved past the heap
        sk = SpaceSaving(self.CAPACITY)
        truth: Counter = Counter()
        stream = _stream("zipf", seed=11, n=6000)
        for at in range(0, len(stream), 300):
            counts = Counter(stream[at:at + 200])
            truth.update(counts)
            sk.offer_many(counts)
            for key in stream[at + 200:at + 300]:
                truth[key] += 1
                sk.offer(key)
            _assert_space_saving_guarantees(sk, truth)

    def test_under_the_merge_line_it_is_so_many_offers(self):
        a, b = SpaceSaving(self.CAPACITY), SpaceSaving(self.CAPACITY)
        for sk in (a, b):
            for i in range(200):
                sk.offer(f"w{i}", n=1 + i % 5)
        counts = {f"n{i}": 2 for i in range(8)} | {"w199": 3}
        assert len(counts) < self.CAPACITY * SpaceSaving._MERGE_FROM
        a.offer_many(counts)
        for key, n in counts.items():
            b.offer(key, n)
        assert a.top(self.CAPACITY) == b.top(self.CAPACITY)
        assert a.total == b.total

    def test_free_room_fills_exactly_then_new_keys_inherit_the_minimum(self):
        sk = SpaceSaving(4)
        sk.offer_many({"a": 5, "b": 3, "c": 2})  # free room: exact
        assert sk.top(4) == [("a", 5, 0), ("b", 3, 0), ("c", 2, 0)]
        # not full before the merge: nothing was ever evicted, so the
        # new keys are exact too, and the smallest candidate drops
        sk.offer_many({"d": 4, "e": 1, "a": 1})
        assert sk.top(4) == [("a", 6, 0), ("d", 4, 0), ("b", 3, 0), ("c", 2, 0)]
        assert sk.total == 16
        # full: new keys enter as (m + n, m), m = 2 the minimum before
        # the merge; the tracked key adds its n; the four largest stay
        sk.offer_many({"f": 3, "g": 1, "b": 2})
        assert sk.top(4) == [("a", 6, 0), ("b", 5, 0), ("f", 5, 2), ("d", 4, 0)]
        assert sk.total == 22

    def test_windowed_offer_many_rotates_like_offer(self):
        sk = WindowedSketch(capacity=8, window_s=10.0)
        t0 = sk._rotated_at
        sk.offer_many({"old": 5, "older": 1}, now=t0 + 1.0)
        sk.offer_many({"new": 3}, now=t0 + 10.5)
        assert dict((k, c) for k, c, _ in sk.top(8)) == {
            "old": 5, "new": 3, "older": 1,
        }
        assert sk.total() == 9
        sk.offer_many({"newer": 1}, now=t0 + 21.0)
        assert "old" not in {k for k, _, _ in sk.top(8)}


class TestWindowedSketch:
    def test_rotation_merges_current_and_previous(self):
        sk = WindowedSketch(capacity=8, window_s=10.0)
        t0 = sk._rotated_at
        sk.offer("old", n=5, now=t0 + 1.0)
        # crossing the window rotates: "old" moves to the previous
        # generation but stays visible in the merged answer
        sk.offer("new", n=3, now=t0 + 10.5)
        top = dict((k, cnt) for k, cnt, _ in sk.top(8))
        assert top == {"old": 5, "new": 3}
        assert sk.total() == 8
        # a second rotation ages "old" out entirely (1-2 window bound)
        sk.offer("newer", n=1, now=t0 + 21.0)
        top = dict((k, cnt) for k, cnt, _ in sk.top(8))
        assert "old" not in top
        assert top == {"new": 3, "newer": 1}

    def test_share_of_top(self):
        sk = WindowedSketch(capacity=8, window_s=60.0)
        now = sk._rotated_at
        sk.offer("hot", n=9, now=now)
        sk.offer("cold", n=1, now=now)
        assert sk.share_of_top(1) == pytest.approx(0.9)
        assert sk.share_of_top(10) == pytest.approx(1.0)
        assert WindowedSketch(4, 60.0).share_of_top(10) == 0.0


class TestSubjectKey:
    def test_plain_and_subject_set_forms(self):
        t = RelationTuple.from_string(TUPLE)
        assert subject_key(t) == "alice"
        ts = RelationTuple.from_string("files:doc#owner@(files:dir#view)")
        assert subject_key(ts) == "(files:dir#view)"


# -- SLO engine ----------------------------------------------------------------


def _feed(engine, sec, n_good=0, n_bad=0, good_s=0.001, bad_s=0.050):
    """n events into one second (first event triggers that second's
    evaluation tick), with injected monotonic stamps."""
    for i in range(n_good + n_bad):
        bad = i < n_bad
        engine.record(
            bad_s if bad else good_s, True,
            now=sec + 0.01 + i * 1e-4,
        )


class TestCodeIsOk:
    def test_classification(self):
        assert code_is_ok("200")
        assert code_is_ok("403")  # a DENY answer is a served request
        assert code_is_ok("429")  # shed is the client's signal, not 5xx
        assert not code_is_ok("500")
        assert not code_is_ok("503")
        assert code_is_ok("OK")
        assert code_is_ok("NOT_FOUND")
        assert not code_is_ok("INTERNAL")
        assert not code_is_ok("UNAVAILABLE")
        assert not code_is_ok("DEADLINE_EXCEEDED")


class TestSLOEngine:
    def test_latency_burn_math(self):
        eng = SLOEngine(
            {"served_p95_ms": 10.0}, window_short_s=5.0,
            window_long_s=10.0, fast_burn_threshold=100.0,
        )
        # 10 bad of 100 with a 5% budget: burn = 0.10 / 0.05 = 2.0
        _feed(eng, sec=1000, n_good=90, n_bad=10)
        st = eng.status(now=1000.9)
        obj = st["objectives"]["served_p95_ms"]
        assert obj["events_short"] == 100
        assert obj["bad_short"] == 10
        assert obj["burn_short"] == pytest.approx(2.0)
        assert obj["burn_long"] == pytest.approx(2.0)
        assert obj["fast_burn"] is False

    def test_availability_budget_from_target(self):
        eng = SLOEngine(
            {"availability": 0.999}, window_short_s=5.0,
            window_long_s=10.0, fast_burn_threshold=100.0,
        )
        for i in range(100):
            eng.record(0.001, ok=(i != 0), now=2000.01 + i * 1e-4)
        obj = eng.status(now=2000.9)["objectives"]["availability"]
        # budget = 1 - target = 0.001; 1 bad in 100 burns at 10x
        assert obj["budget"] == pytest.approx(0.001)
        assert obj["burn_short"] == pytest.approx(10.0)

    def test_window_start_quantized_to_whole_seconds(self):
        # regression: an evaluation tick fires on the FIRST event of a
        # new second (now ~= sec.0x). An unquantized `now - window_s`
        # start would drop the whole previous bucket at that instant,
        # flapping the short-window burn to zero exactly when it must
        # be visible. The window is quantized: W covers the last W FULL
        # seconds plus the current partial one.
        eng = SLOEngine(
            {"served_p95_ms": 10.0}, window_short_s=1.0,
            window_long_s=5.0, fast_burn_threshold=100.0,
        )
        _feed(eng, sec=3000, n_good=10, n_bad=10)
        st = eng.status(now=3001.02)  # just after the second rolls over
        obj = st["objectives"]["served_p95_ms"]
        assert obj["events_short"] == 20, (
            "the previous second's full bucket must stay in the window"
        )
        assert obj["burn_short"] == pytest.approx(10.0)

    def test_fast_burn_requires_both_windows(self, caplog):
        eng = SLOEngine(
            {"served_p95_ms": 10.0}, window_short_s=1.0,
            window_long_s=5.0, fast_burn_threshold=5.0,
        )
        with caplog.at_level(logging.INFO, logger="keto_tpu"):
            # seconds 1000-1003: healthy traffic fills the long window
            for sec in (1000, 1001, 1002, 1003):
                _feed(eng, sec=sec, n_good=20)
            # second 1004: all bad — at the 1005 tick the short window
            # burns at 20x but the long window (21 bad of 101, burn
            # ~4.2) is still diluted below the 5x threshold by the
            # healthy seconds, so NO fast burn (one blip must not page)
            _feed(eng, sec=1004, n_bad=20)
            eng.record(0.050, True, now=1005.01)
            st = eng.status(now=1005.1)["objectives"]["served_p95_ms"]
            assert st["burn_short"] > 5.0
            assert st["fast_burn"] is False
            assert not [
                r for r in caplog.records
                if r.msg.startswith("slo fast burn")
            ]
            # seconds 1005-1008 keep burning: the long window crosses
            # the threshold too -> fast burn latches + WARNING emits
            for sec in (1005, 1006, 1007, 1008):
                _feed(eng, sec=sec, n_bad=20)
            eng.record(0.050, True, now=1009.01)
            st = eng.status(now=1009.1)["objectives"]["served_p95_ms"]
            assert st["fast_burn"] is True
        warns = [
            r for r in caplog.records
            if r.levelno == logging.WARNING
            and r.msg.startswith("slo fast burn objective=%s")
        ]
        assert warns, "an active fast burn must emit a WARNING"
        assert warns[-1].args[0] == "served_p95_ms"

    def test_warning_every_tick_and_recovery_line(self, caplog):
        eng = SLOEngine(
            {"served_p95_ms": 10.0}, window_short_s=1.0,
            window_long_s=2.0, fast_burn_threshold=2.0,
        )
        with caplog.at_level(logging.INFO, logger="keto_tpu"):
            for sec in (5000, 5001, 5002):
                _feed(eng, sec=sec, n_bad=10)
            warns = [
                r for r in caplog.records
                if r.msg.startswith("slo fast burn objective=%s")
            ]
            # every evaluation tick while burning emits (never sampled
            # away): the 5001 and 5002 ticks both see burn on both
            # windows
            assert len(warns) >= 2
            # recovery: healthy seconds push both windows back under
            # the threshold -> one INFO transition line
            for sec in (5003, 5004, 5005):
                _feed(eng, sec=sec, n_good=40)
            eng.record(0.001, True, now=5006.01)
        recov = [
            r for r in caplog.records
            if r.msg.startswith("slo burn recovered objective=%s")
        ]
        assert recov and recov[-1].args[0] == "served_p95_ms"
        assert recov[-1].levelno == logging.INFO
        st = eng.status(now=5006.1)["objectives"]["served_p95_ms"]
        assert st["fast_burn"] is False

    def test_staleness_probe_sampled_on_tick(self):
        eng = SLOEngine(
            {"max_staleness_s": 60.0}, window_short_s=5.0,
            window_long_s=10.0, fast_burn_threshold=100.0,
            staleness_probe=lambda: 120.0,
        )
        eng.record(0.001, True, now=7000.01)  # tick samples the probe
        obj = eng.status(now=7000.5)["objectives"]["max_staleness_s"]
        assert obj["events_short"] == 1
        assert obj["bad_short"] == 1

    def test_latency_exemption_still_counts_availability(self):
        eng = SLOEngine(
            {"served_p95_ms": 10.0, "availability": 0.999},
            window_short_s=5.0, window_long_s=10.0,
            fast_burn_threshold=100.0,
        )
        # an SSE watch stream: minutes long by design, not a latency
        # violation — but its outcome still counts for availability
        eng.record(120.0, True, now=8000.01, latency_eligible=False)
        st = eng.status(now=8000.5)["objectives"]
        assert st["served_p95_ms"]["events_short"] == 0
        assert st["availability"]["events_short"] == 1
        assert st["availability"]["bad_short"] == 0


# -- the buffered-fold feed ----------------------------------------------------


def _obs(**kw):
    kw.setdefault("enabled", True)
    kw.setdefault("shards", 2)
    kw.setdefault("hotkey_capacity", 16)
    kw.setdefault("hotkey_window_s", 60.0)
    return WorkloadObservatory(**kw)


class TestObservatoryFold:
    def test_read_surfaces_drain_pending_events(self):
        obs = _obs()
        t = RelationTuple.from_string(TUPLE)
        for allowed in (True, True, False):
            obs.record_check("net0", t, allowed, tier="device")
        # fewer than _FOLD_BATCH events: still buffered...
        assert obs._check_buf
        acct = obs.accounting()  # ...but a read surface drains first
        assert not obs._check_buf
        st = acct["net0/files#owner"]
        assert st["requests"] == 3
        assert st["allowed"] == 2
        assert st["denied"] == 1
        assert st["tiers"] == {"device": 3}

    def test_inline_fold_triggers_at_batch_size(self):
        obs = _obs()
        t = RelationTuple.from_string(TUPLE)
        for _ in range(obs._FOLD_BATCH):
            obs.record_check("net0", t, True, tier="cache")
        # the batch-size trigger folded without any read-surface call
        assert obs._check_buf == []
        with obs._sketch_lock:
            assert obs.sketches["object"].total() == obs._FOLD_BATCH

    def test_unknown_tier_buckets_as_other(self):
        obs = _obs()
        t = RelationTuple.from_string(TUPLE)
        obs.record_check("net0", t, True, tier=None)
        obs.record_check("net0", t, True, tier="warp-drive")
        st = obs.accounting()["net0/files#owner"]
        assert st["tiers"] == {"other": 2}

    def test_hotkeys_payload_shape(self):
        obs = _obs()
        t = RelationTuple.from_string(TUPLE)
        obs.record_check("net0", t, True, tier="device")
        out = obs.hotkeys(top=5, cache_stats={"hits": 1})
        assert set(out["kinds"]) == {"object", "subject", "check"}
        objk = out["kinds"]["object"]
        assert objk["total"] == 1
        assert objk["top"][0]["key"] == "files:doc"
        assert objk["top"][0]["share"] == pytest.approx(1.0)
        assert out["kinds"]["subject"]["top"][0]["key"] == "alice"
        assert out["kinds"]["check"]["top"][0]["key"] == TUPLE
        assert set(objk["top_share"]) == {"1", "10", "100"}
        assert out["check_cache"] == {"hits": 1}

    def test_profile_read_write_split(self):
        obs = _obs()
        t = RelationTuple.from_string(TUPLE)
        obs.record_check("net0", t, True, tier="cache")
        obs.observe_request("GET /relation-tuples/check", "200", 0.001)
        obs.observe_request("GET /relation-tuples/check", "200", 0.001)
        obs.observe_request("PUT /admin/relation-tuples", "200", 0.002)
        obs.observe_request("TransactRelationTuples", "OK", 0.002)
        p = obs.profile()
        assert p["schema"] == PROFILE_SCHEMA
        assert p["reads"] == 2
        assert p["writes"] == 2
        assert p["read_share"] == pytest.approx(0.5)
        assert p["captured_requests"] == 1
        assert p["per_namespace"]["files#owner"]["requests"] == 1
        assert p["key_popularity"]["object"][0]["key"] == "files:doc"

    def test_disabled_records_nothing(self):
        obs = _obs(enabled=False)
        t = RelationTuple.from_string(TUPLE)
        obs.record_check("net0", t, True, tier="cache")
        obs.observe_request("GET /x", "200", 0.001)
        assert obs.accounting() == {}
        assert obs.profile()["reads"] == 0

    def test_acct_flag_captured_at_enqueue_time(self):
        # the fold must honor the flag as it was when the event landed,
        # not re-read one an admin may have flipped mid-flight
        obs = _obs()
        obs.observe_request("GET /x", "200", 0.001)
        obs.enabled = False
        assert obs.profile()["reads"] == 1

    def test_folder_thread_owns_the_fold(self):
        obs = _obs()
        t = RelationTuple.from_string(TUPLE)
        obs.start_folder(interval_s=0.01)
        obs.start_folder()  # idempotent
        try:
            # with the folder running the inline trigger backs off to
            # _FOLD_CAP: a full batch stays buffered until the folder
            # picks it up
            for _ in range(obs._FOLD_BATCH * 2):
                obs.record_check("net0", t, True, tier="cache")
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                with obs._buf_lock:
                    if not obs._check_buf:
                        break
                time.sleep(0.005)
            with obs._buf_lock:
                assert not obs._check_buf, "folder must drain the buffer"
        finally:
            obs.stop_folder()
        assert obs._folder is None
        # stop folds the tail: nothing on the floor
        obs.record_check("net0", t, False, tier="host")
        obs.stop_folder()  # no folder running: a no-op
        st = obs.accounting()["net0/files#owner"]
        assert st["requests"] == obs._FOLD_BATCH * 2 + 1

    def test_slo_events_keep_their_finish_second(self):
        # folded late (here: by the read-surface drain), the event must
        # still land in the second it FINISHED in — the enqueue stamp
        # rides the buffer
        eng = SLOEngine(
            {"served_p95_ms": 10.0}, window_short_s=5.0,
            window_long_s=10.0, fast_burn_threshold=100.0,
        )
        obs = _obs(slo=eng)
        obs.observe_request("GET /x", "200", 0.050)
        obj = obs.slo_status()["objectives"]["served_p95_ms"]
        assert obj["events_short"] == 1
        assert obj["bad_short"] == 1

    def test_grpc_error_code_counts_against_availability(self):
        eng = SLOEngine(
            {"availability": 0.999}, window_short_s=5.0,
            window_long_s=10.0, fast_burn_threshold=100.0,
        )
        obs = _obs(slo=eng)
        obs.observe_request("Check", "OK", 0.001)
        obs.observe_request("Check", "INTERNAL", 0.001)
        obj = obs.slo_status()["objectives"]["availability"]
        assert obj["events_short"] == 2
        assert obj["bad_short"] == 1

    def test_note_staleness_direct_feed(self):
        eng = SLOEngine(
            {"max_staleness_s": 60.0}, window_short_s=5.0,
            window_long_s=10.0, fast_burn_threshold=100.0,
        )
        obs = _obs(slo=eng)
        obs.note_staleness(30.0)
        obs.note_staleness(120.0)
        obj = obs.slo_status()["objectives"]["max_staleness_s"]
        assert obj["events_short"] == 2
        assert obj["bad_short"] == 1


def _folded(metrics: Metrics, where: str) -> float:
    return metrics.registry.get_sample_value(
        "keto_tpu_workload_folded_checks_total", {"where": where}
    ) or 0.0


def _request_children(metrics: Metrics) -> dict:
    (family,) = [
        f for f in metrics.registry.collect()
        if f.name == "keto_tpu_workload_requests"
    ]
    return {
        tuple(sorted(s.labels.items())): s.value
        for s in family.samples if s.name.endswith("_total")
    }


class TestBatchEvent:
    TUPLES = [
        RelationTuple.from_string(s) for s in (
            "files:doc#owner@alice",
            "files:doc#owner@bob",
            "files:doc2#viewer@alice",
            "files:doc3#viewer@(files:dir#view)",
            "dirs:d#parent@(files:dir#view)",
            "files:doc#owner@alice",
        )
    ]
    ALLOWED = [True, False, True, True, False, True]

    def test_a_batch_is_one_event_and_equals_n_single_checks(self):
        one, many = _obs(metrics=Metrics()), _obs(metrics=Metrics())
        one.start_folder(interval_s=3600.0)  # hold the fold off
        try:
            one.record_check_batch("net0", self.TUPLES, self.ALLOWED)
            assert len(one._batch_buf) == 1 and not one._check_buf
            assert one._batch_checks == len(self.TUPLES)
        finally:
            one.stop_folder()
        for t, allowed in zip(self.TUPLES, self.ALLOWED):
            many.record_check("net0", t, allowed)
        assert one.accounting() == many.accounting()
        assert one.accounting()["net0/files#owner"] == {
            "requests": 3, "allowed": 2, "denied": 1, "tiers": {"other": 3},
        }
        assert _request_children(one.metrics) == _request_children(many.metrics)
        assert sum(_request_children(one.metrics).values()) == len(self.TUPLES)
        for kind in ("object", "subject", "check"):
            a, b = one.sketches[kind], many.sketches[kind]
            assert a.total() == b.total() == len(self.TUPLES)
            # under capacity both are exact, so the keys agree too
            assert sorted(a.top(16)) == sorted(b.top(16))
        assert {k for k, _, _ in one.sketches["check"].top(16)} == {
            str(t) for t in self.TUPLES
        }
        assert {k for k, _, _ in one.sketches["subject"].top(16)} == {
            subject_key(t) for t in self.TUPLES
        }

    def test_columns_give_the_counts_and_keys_the_tuples_give(self):
        """A served BatchCheck hands over the CheckColumns it read off the
        wire: the same accounting, and every sketch key byte for byte,
        subject sets included."""
        from keto_tpu.ketoapi import CheckColumns

        cols = CheckColumns.of(self.TUPLES)
        by_tuples, by_columns = _obs(metrics=Metrics()), _obs(metrics=Metrics())
        by_columns.start_folder(interval_s=3600.0)  # hold the fold off
        try:
            by_columns.record_check_batch("net0", cols, self.ALLOWED)
            (_, _, _, columns), = by_columns._batch_buf
            assert columns[3] == [t.subject for t in self.TUPLES]
            assert columns[0] is cols.ns  # the handler's lists, not copies
        finally:
            by_columns.stop_folder()
        by_tuples.record_check_batch("net0", self.TUPLES, self.ALLOWED)
        assert by_columns.accounting() == by_tuples.accounting()
        assert _request_children(by_columns.metrics) == _request_children(
            by_tuples.metrics
        )
        for kind in ("object", "subject", "check"):
            assert sorted(by_columns.sketches[kind].top(16)) == sorted(
                by_tuples.sketches[kind].top(16)
            )
        assert {k for k, _, _ in by_columns.sketches["check"].top(16)} == {
            str(t) for t in self.TUPLES
        }

    def test_the_event_holds_no_tuple(self):
        obs = _obs()
        obs.start_folder(interval_s=3600.0)
        try:
            obs.record_check_batch("net0", self.TUPLES, self.ALLOWED, tier="device")
            (nid, tier, allowed, columns), = obs._batch_buf
            assert (nid, tier, allowed) == ("net0", "device", self.ALLOWED)
            flat = [x for column in columns for x in column]
            assert not any(isinstance(x, RelationTuple) for x in flat)
        finally:
            obs.stop_folder()
        st = obs.accounting()["net0/files#viewer"]
        assert st["tiers"] == {"device": 2}

    def test_empty_and_disabled_batches_enqueue_nothing(self):
        obs = _obs()
        obs.record_check_batch("net0", [], [])
        assert not obs._batch_buf
        off = _obs(enabled=False)
        off.record_check_batch("net0", self.TUPLES, self.ALLOWED)
        assert not off._batch_buf and off.accounting() == {}

    def test_without_a_folder_a_batch_folds_inline(self):
        obs = _obs(metrics=Metrics())
        obs.record_check_batch("net0", self.TUPLES * 3, self.ALLOWED * 3)
        assert not obs._batch_buf and obs._batch_checks == 0
        assert _folded(obs.metrics, "inline") == 18
        assert _folded(obs.metrics, "folder") == 0

    def test_with_the_folder_running_eight_batches_fold_nothing_inline(self):
        # 8 x 2,048 answered items back to back, the drive cell's quarter
        # of a second: the valve (65,536 pending checks) stays shut, and
        # the folder's thread folds every one of them
        metrics = Metrics()
        obs = _obs(metrics=metrics, hotkey_capacity=256)
        rng = random.Random(3)
        batches = [
            [
                RelationTuple.make(
                    "files", f"/d{rng.randrange(12000)}/f{rng.randrange(80)}",
                    "view", f"u{rng.randrange(10000)}",
                )
                for _ in range(2048)
            ]
            for _ in range(8)
        ]
        assert 8 * 2048 < obs._FOLD_CAP
        obs.start_folder(interval_s=0.05)
        try:
            for i, tuples in enumerate(batches):
                obs.record_check_batch("net0", tuples, [bool(i & 1)] * 2048)
            deadline = time.monotonic() + 10.0
            while (
                _folded(metrics, "folder") < 8 * 2048
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert _folded(metrics, "folder") == 8 * 2048
            assert _folded(metrics, "inline") == 0
        finally:
            obs.stop_folder()
        assert _folded(metrics, "inline") == 0  # nothing was left to stop
        seconds = metrics.registry.get_sample_value(
            "keto_tpu_workload_fold_seconds_total", {"where": "folder"}
        )
        assert seconds > 0.0
        assert obs.accounting()["net0/files#view"] == {
            "requests": 8 * 2048, "allowed": 4 * 2048, "denied": 4 * 2048,
            "tiers": {"other": 8 * 2048},
        }
        for kind in ("object", "subject", "check"):
            assert obs.sketches[kind].total() == 8 * 2048

    def test_past_the_valve_a_handler_folds_inline(self, monkeypatch):
        # the valve counts pending CHECKS, a batch by its items
        metrics = Metrics()
        obs = _obs(metrics=metrics)
        monkeypatch.setattr(obs, "_FOLD_CAP", 32)
        obs.start_folder(interval_s=3600.0)
        try:
            obs.record_check_batch("net0", self.TUPLES * 5, self.ALLOWED * 5)
            assert _folded(metrics, "inline") == 0  # 30 pending: shut
            obs.record_check("net0", self.TUPLES[0], True)
            obs.record_check("net0", self.TUPLES[1], True)
            assert _folded(metrics, "inline") == 32
            assert not obs._batch_buf and not obs._check_buf
        finally:
            obs.stop_folder()


# -- config schema + registry wiring -------------------------------------------


class TestWorkloadConfig:
    def test_schema_accepts_workload_and_slo_keys(self):
        Config({
            "dsn": "memory",
            "workload": {
                "enabled": True,
                "shards": 4,
                "hotkeys": {"capacity": 128, "window_s": 300},
            },
            "slo": {
                "enabled": True,
                "window_short_s": 60,
                "window_long_s": 600,
                "fast_burn_threshold": 14,
                "objectives": {
                    "served_p95_ms": 10,
                    "availability": 0.999,
                    "max_staleness_s": 60,
                },
            },
        })

    def test_schema_rejects_unknown_and_out_of_range(self):
        with pytest.raises(ConfigError):
            Config({"workload": {"bogus": 1}})
        with pytest.raises(ConfigError):
            Config({"workload": {"shards": 0}})
        with pytest.raises(ConfigError):
            Config({"slo": {"objectives": {"served_p99_ms": 10}}})

    def test_registry_builds_north_star_defaults(self):
        reg = Registry(Config({"dsn": "memory"}))
        obs = reg.workload_observatory()
        assert obs is reg.workload_observatory()  # one shared instance
        assert obs.enabled is True
        assert obs.slo is not None
        # BASELINE.json's north star: p95 < 10 ms, three nines, and a
        # minute of tolerated mirror staleness
        assert obs.slo.objectives == {
            "served_p95_ms": 10.0,
            "availability": 0.999,
            "max_staleness_s": 60.0,
        }
        assert obs.slo.fast_burn_threshold == 14.0

    def test_slo_disabled_leaves_accounting_on(self):
        reg = Registry(Config({"dsn": "memory", "slo": {"enabled": False}}))
        obs = reg.workload_observatory()
        assert obs.slo is None
        assert obs.enabled is True
        assert obs.slo_status() == {"enabled": False, "objectives": {}}


# -- the live daemon plane -----------------------------------------------------


@pytest.fixture(scope="module")
def daemon():
    cfg = Config({
        "dsn": "memory",
        "check": {"engine": "tpu"},
        "tracing": {"enabled": True, "provider": "memory"},
        "slo": {
            # seconds-scale windows so the admin surface shows live
            # events inside a test's lifetime
            "window_short_s": 5,
            "window_long_s": 30,
        },
        "serve": {
            "read": {"host": "127.0.0.1", "port": 0},
            "write": {"host": "127.0.0.1", "port": 0},
            "metrics": {"host": "127.0.0.1", "port": 0},
        },
    })
    cfg.set_namespaces(NAMESPACES)
    reg = Registry(cfg)
    reg.relation_tuple_manager().write_relation_tuples(
        [RelationTuple.from_string(TUPLE)]
    )
    d = Daemon(reg)
    d.start()
    yield d
    d.stop()


def _admin(daemon, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{daemon.metrics_port}{path}"
    ) as r:
        return json.loads(r.read())


def _one_check(daemon, traceparent=None):
    client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
    try:
        if traceparent is None:
            client.check(RelationTuple.from_string(TUPLE))
        else:
            client.check(
                RelationTuple.from_string(TUPLE), traceparent=traceparent
            )
    finally:
        client.close()


class TestDaemonWorkloadPlane:
    def test_daemon_runs_the_folder_thread(self, daemon):
        import threading

        obs = daemon.registry.workload_observatory()
        assert obs._folder is not None
        assert any(
            th.name == "keto-workload-fold" for th in threading.enumerate()
        )

    def test_admin_hotkeys_sees_served_checks(self, daemon):
        for _ in range(3):
            _one_check(daemon)
        out = _admin(daemon, "/admin/hotkeys?top=10")
        assert out["enabled"] is True
        objects = {e["key"] for e in out["kinds"]["object"]["top"]}
        assert "files:doc" in objects
        subjects = {e["key"] for e in out["kinds"]["subject"]["top"]}
        assert "alice" in subjects
        checks = {e["key"] for e in out["kinds"]["check"]["top"]}
        assert TUPLE in checks
        # the cache-attribution join rides the same response
        assert "check_cache" in out

    def test_a_batch_check_of_2048_is_one_event_and_2048_hotkeys(
        self, daemon, monkeypatch
    ):
        obs = daemon.registry.workload_observatory()
        events, singles = [], []
        record_batch = obs.record_check_batch

        def spy(nid, tuples, allowed, tier=None):
            events.append(len(tuples))
            record_batch(nid, tuples, allowed, tier)

        monkeypatch.setattr(obs, "record_check_batch", spy)
        monkeypatch.setattr(
            obs, "record_check", lambda *a, **kw: singles.append(a)
        )
        before = _admin(daemon, "/admin/hotkeys?top=1")["kinds"]
        items = [
            RelationTuple.from_string(f"files:doc{i}#owner@user{i % 50}")
            for i in range(2047)
        ] + [RelationTuple.from_string(TUPLE)]
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            out = client.check_batch(items)
        finally:
            client.close()
        assert [allowed for allowed, _ in out] == [False] * 2047 + [True]
        assert events == [2048] and not singles
        after = _admin(daemon, "/admin/hotkeys?top=300")["kinds"]
        for kind in ("object", "subject", "check"):
            assert after[kind]["total"] - before[kind]["total"] == 2048
        top = {e["key"]: e["count"] for e in after["subject"]["top"]}
        assert all(top[f"user{i}"] >= 40 for i in range(50))
        acct = obs.accounting()
        key = next(k for k in acct if k.endswith("/files#owner"))
        assert acct[key]["denied"] >= 2047

    @pytest.mark.parametrize("transport", ["grpc", "rest"])
    def test_errored_batch_items_are_not_accounted(
        self, daemon, monkeypatch, transport
    ):
        from keto_tpu.engine.definitions import CheckResult, Membership

        obs = daemon.registry.workload_observatory()
        engine = daemon.registry.check_engine(daemon.registry.nid)
        check_batch = engine.check_batch

        def second_item_errs(tuples, *args, **kw):
            results = list(check_batch(tuples, *args, **kw))
            results[1] = CheckResult(
                Membership.NOT_MEMBER, error=RuntimeError("boom")
            )
            return results

        monkeypatch.setattr(engine, "check_batch", second_item_errs)
        items = [
            RelationTuple.from_string(TUPLE),
            RelationTuple.from_string("files:doc#owner@errs"),
            # an unknown namespace never reaches the engine
            RelationTuple.from_string("nope:doc#owner@alice"),
            RelationTuple.from_string("files:doc#owner@mallory"),
        ]
        obs._drain()
        before = obs.sketches["check"].total()
        if transport == "grpc":
            client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
            try:
                out = client.check_batch(items)
            finally:
                client.close()
        else:
            req = urllib.request.Request(
                f"http://127.0.0.1:{daemon.read_port}"
                "/relation-tuples/check/batch",
                data=json.dumps(
                    {"tuples": [t.to_dict() for t in items]}
                ).encode(),
                method="POST", headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req) as r:
                out = [
                    (res["allowed"], res.get("error", ""))
                    for res in json.loads(r.read())["results"]
                ]
        assert [allowed for allowed, _ in out] == [True, False, False, False]
        assert [bool(err) for _, err in out] == [False, True, True, False]
        hot = obs.hotkeys(top=300)["kinds"]["check"]
        assert hot["total"] - before == 2  # the two that carry a verdict
        keys = {e["key"] for e in hot["top"]}
        assert "files:doc#owner@mallory" in keys
        assert "files:doc#owner@errs" not in keys
        assert "nope:doc#owner@alice" not in keys

    def test_admin_hotkeys_top_validates(self, daemon):
        with pytest.raises(urllib.error.HTTPError) as e:
            _admin(daemon, "/admin/hotkeys?top=abc")
        assert e.value.code == 400

    def test_admin_slo_live_counters(self, daemon):
        _one_check(daemon)
        out = _admin(daemon, "/admin/slo")
        assert out["enabled"] is True
        assert set(out["objectives"]) == {
            "served_p95_ms", "availability", "max_staleness_s",
        }
        avail = out["objectives"]["availability"]
        assert avail["events_long"] >= 1
        assert avail["target"] == 0.999
        assert avail["fast_burn"] is False

    def test_admin_workload_profile(self, daemon):
        _one_check(daemon)
        out = _admin(daemon, "/admin/workload")
        assert out["schema"] == PROFILE_SCHEMA
        assert out["captured_requests"] >= 1
        assert out["per_namespace"]["files#owner"]["requests"] >= 1
        assert out["read_share"] > 0.0

    def test_accounting_attributes_answering_tier(self, daemon):
        # repeats of one check land in the serve cache: the tier mix
        # must show non-"other" attribution (device/closure first ride,
        # cache after)
        for _ in range(4):
            _one_check(daemon)
        obs = daemon.registry.workload_observatory()
        acct = obs.accounting()
        key = next(k for k in acct if k.endswith("/files#owner"))
        tiers = acct[key]["tiers"]
        assert sum(tiers.values()) == acct[key]["requests"]
        assert set(tiers) <= set(TIERS)
        assert any(t != "other" for t in tiers)

    def test_request_log_carries_tier(self, daemon, caplog):
        with caplog.at_level(logging.INFO, logger="keto_tpu"):
            _one_check(daemon)
        handled = [
            r for r in caplog.records
            if r.getMessage() == "request handled"
            and getattr(r, "tier", None) is not None
        ]
        assert handled, "the request log line must carry tier="
        assert all(r.tier in TIERS for r in handled)

    def test_tier_histogram_openmetrics_exemplars(self, daemon):
        from keto_tpu.observability import new_trace

        ctx = new_trace()
        _one_check(daemon, traceparent=ctx.to_traceparent())
        # the observatory folds on its own thread: wait for the fold
        daemon.registry.workload_observatory()._drain()
        req = urllib.request.Request(
            f"http://127.0.0.1:{daemon.metrics_port}/metrics/prometheus",
            headers={"Accept": "application/openmetrics-text"},
        )
        with urllib.request.urlopen(req) as r:
            assert "openmetrics" in r.headers["Content-Type"]
            text = r.read().decode()
        exemplar_lines = [
            line for line in text.splitlines()
            if "keto_tpu_workload_tier_duration_seconds_bucket" in line
            and "# {" in line and "trace_id=" in line
        ]
        assert exemplar_lines, (
            "per-tier buckets must carry trace exemplars under "
            "OpenMetrics negotiation"
        )
        # classic exposition stays exemplar-free (the negotiation IS
        # the contract)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{daemon.metrics_port}/metrics/prometheus"
        ) as r:
            classic = r.read().decode()
        assert "keto_tpu_workload_tier_duration_seconds_bucket" in classic
        assert "# {" not in classic

    def test_workload_gauges_exported(self, daemon):
        _one_check(daemon)
        daemon.registry.workload_observatory()._drain()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{daemon.metrics_port}/metrics/prometheus"
        ) as r:
            text = r.read().decode()
        assert "keto_tpu_workload_requests_total{" in text
        assert "keto_tpu_hotkey_share{" in text
        assert "keto_tpu_slo_burn_rate{" in text
        assert "keto_tpu_slo_objective_target{" in text


class TestStalenessProbe:
    def test_never_synced_engine_is_no_sample_not_infinitely_stale(
        self, monkeypatch
    ):
        # cold start: a built-but-never-synced engine reports inf age —
        # the probe must skip it (nothing served from that mirror yet),
        # not latch a spurious max_staleness_s fast burn at startup
        reg = Registry(Config({"dsn": "memory"}))

        class _Eng:
            def __init__(self, age):
                self._age = age

            def mirror_staleness_age_s(self):
                return self._age

        monkeypatch.setattr(
            reg, "built_engines", lambda: {"n": _Eng(float("inf"))}
        )
        assert reg._mirror_staleness_age() is None
        monkeypatch.setattr(
            reg, "built_engines",
            lambda: {"a": _Eng(float("inf")), "b": _Eng(5.0)},
        )
        assert reg._mirror_staleness_age() == 5.0
