"""The device-feed account and the spans that cover a whole BatchCheck.

Counts and scripted clocks only: no rate, no utilization (those come from
the chip, through benchmarks/). What is held here:

  - engine/device_feed.py on scripted `dispatched` / `ready` sequences:
    every second lands in exactly one state, a starved gap is charged
    along the next launch's own timeline, a late or missing `ready`
    cannot wedge the count;
  - a BatchCheck through the gRPC and REST handlers fills its
    RequestTrace with decode / assemble / dispatch / device_wait /
    resolve / respond / transport, which add up to the handler;
  - a batcher launch charges `starved_queue`;
  - the `keto.<stage>` annotations land in a profiler trace's host plane
    with the launch id, and the lowered check program names the kernel
    phases.
"""

import glob
import json
import os
import time
import urllib.request
from types import SimpleNamespace

import pytest

from keto_tpu.api import ReadClient, open_channel
from keto_tpu.api.daemon import Daemon
from keto_tpu.config import Config
from keto_tpu.engine.device_feed import DeviceFeed
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.observability import (
    DEVICE_FEED_STATES,
    Metrics,
    RequestTrace,
    reset_request_trace,
    set_request_trace,
)
from keto_tpu.registry import Registry

TUPLES = [
    RelationTuple.from_string(f"files:doc{i}#owner@user{i}") for i in range(48)
]
BATCH_STAGES = {
    "decode", "assemble", "dispatch", "device_wait", "resolve", "respond",
    "transport",
}


def rider(arrived, enqueued=None):
    return SimpleNamespace(arrived=arrived, enqueued=enqueued)


def charged(feed) -> dict:
    return {k: round(v, 9) for k, v in feed.seconds.items() if v}


class TestDeviceFeedAccount:
    def test_first_launch_starts_the_clock(self):
        feed = DeviceFeed()
        launch, starved = feed.dispatched(9.0, 9.5, 10.0, [rider(8.0)])
        assert starved == 0.0
        assert charged(feed) == {}
        assert feed.ready(launch, 10.4) == pytest.approx(0.4)
        assert charged(feed) == {"busy": 0.4}

    def test_gap_walks_back_along_the_next_launch(self):
        feed = DeviceFeed()
        feed.ready(feed.dispatched(0.0, 0.0, 1.0)[0], 2.0)  # empty at 2.0
        # the request arrived at 5.0, was decoded until the engine took it
        # at 5.5, assembled until 5.8, dispatched until 6.0
        launch, starved = feed.dispatched(5.5, 5.8, 6.0, [rider(5.0)])
        assert starved == pytest.approx(4.0)
        assert charged(feed) == {
            "busy": 1.0,
            "starved_no_request": 3.0,
            "starved_decode": 0.5,
            "starved_assemble": 0.3,
            "starved_dispatch": 0.2,
        }
        feed.ready(launch, 6.5)
        assert sum(feed.seconds.values()) == pytest.approx(6.5 - 1.0)

    def test_batcher_rider_charges_queue(self):
        feed = DeviceFeed()
        feed.ready(feed.dispatched(0.0, 0.0, 1.0)[0], 2.0)
        # two riders: the earlier one arrived at 3.0 and queued at 3.1
        riders = [rider(3.4, 3.5), rider(3.0, 3.1)]
        feed.dispatched(4.0, 4.2, 4.3, riders)
        assert charged(feed) == {
            "busy": 1.0,
            "starved_no_request": 1.0,
            "starved_decode": 0.1,
            "starved_queue": 0.9,
            "starved_assemble": 0.2,
            "starved_dispatch": 0.1,
        }

    def test_gap_shorter_than_the_launch_timeline(self):
        feed = DeviceFeed()
        feed.ready(feed.dispatched(0.0, 0.0, 1.0)[0], 5.65)
        # the device emptied at 5.65, in the middle of this launch's
        # assemble: nothing before that is starved time
        feed.dispatched(5.5, 5.8, 6.0, [rider(5.0)])
        assert charged(feed) == {
            "busy": 4.65,
            "starved_assemble": 0.15,
            "starved_dispatch": 0.2,
        }

    def test_overlapping_launches_stay_busy(self):
        feed = DeviceFeed()
        a, _ = feed.dispatched(0.0, 0.0, 1.0)
        b, starved = feed.dispatched(1.0, 1.1, 1.2)
        assert starved == 0.0
        assert feed.ready(a, 2.0) == pytest.approx(1.0)
        # b waited behind a: its service time starts at a's readback
        assert feed.ready(b, 2.7) == pytest.approx(0.7)
        assert charged(feed) == {"busy": 1.7}

    def test_out_of_order_ready(self):
        feed = DeviceFeed()
        a, _ = feed.dispatched(0.0, 0.0, 1.0)
        b, _ = feed.dispatched(1.0, 1.1, 1.2)
        # b's resolver woke first. a's dispatch had ended before b's
        # began, so b's readback proves a done as well
        assert feed.ready(b, 3.0) == pytest.approx(1.8)
        # the queue is empty from 3.0 on, whatever a's late resolver says
        assert feed.ready(a, 3.4) == pytest.approx(0.4)
        feed.dispatched(3.5, 3.7, 4.0, [rider(3.5)])
        assert charged(feed) == {
            "busy": 2.0,
            "starved_no_request": 0.5,
            "starved_assemble": 0.2,
            "starved_dispatch": 0.3,
        }
        assert sum(feed.seconds.values()) == pytest.approx(4.0 - 1.0)

    def test_launches_dispatched_at_once_wait_for_their_own_readback(self):
        feed = DeviceFeed()
        # two threads inside dispatch at the same time: the host cannot
        # know which launch the device queue holds first
        a, _ = feed.dispatched(0.0, 0.5, 1.1)
        b, _ = feed.dispatched(0.0, 0.6, 1.0)
        feed.ready(a, 2.0)
        # b may still be running: the account stays busy until b is read
        _, starved = feed.dispatched(2.5, 2.6, 3.0)
        assert starved == 0.0
        feed.ready(b, 3.5)
        assert charged(feed) == {"busy": 2.4}  # since 1.1, the first dispatch

    def test_abandoned_launch_cannot_wedge_the_count(self):
        feed = DeviceFeed()
        feed.dispatched(0.0, 0.0, 1.0)  # the watchdog gave up on this one
        b, _ = feed.dispatched(1.0, 1.5, 2.0)
        feed.ready(b, 3.0)
        _, starved = feed.dispatched(4.0, 4.0, 5.0)
        assert starved == pytest.approx(2.0)

    def test_metrics_follow_the_account(self):
        metrics = Metrics()
        before = metrics.export().decode()
        for state in DEVICE_FEED_STATES:
            # declared before the first launch: a scrape never misses one
            assert (
                f'keto_tpu_device_feed_seconds_total{{state="{state}"}} 0.0'
                in before
            )
        feed = DeviceFeed(metrics)
        feed.ready(feed.dispatched(0.0, 0.0, 1.0)[0], 2.0)
        feed.ready(feed.dispatched(2.5, 3.0, 3.5, [rider(2.5)])[0], 4.0)

        def value(name, labels=None):
            return metrics.registry.get_sample_value(name, labels)

        total = "keto_tpu_device_feed_seconds_total"
        assert value(total, {"state": "busy"}) == pytest.approx(1.5)
        assert value(total, {"state": "starved_no_request"}) == pytest.approx(0.5)
        assert value(total, {"state": "starved_assemble"}) == pytest.approx(0.5)
        assert value(total, {"state": "starved_dispatch"}) == pytest.approx(0.5)
        assert value("keto_tpu_launch_device_seconds_count") == 2
        assert value("keto_tpu_launch_device_seconds_sum") == pytest.approx(1.5)


@pytest.fixture(scope="module")
def daemon():
    cfg = Config({
        "dsn": "memory",
        # cache off: repeated checks must reach the batcher and the engine
        "check": {"engine": "tpu", "cache": {"enabled": False}},
        "observability": {"flightrec": {"enabled": True}},
        "serve": {
            "read": {"host": "127.0.0.1", "port": 0},
            "write": {"host": "127.0.0.1", "port": 0},
            "metrics": {"host": "127.0.0.1", "port": 0},
        },
    })
    cfg.set_namespaces([Namespace(name="files")])
    reg = Registry(cfg)
    reg.relation_tuple_manager().write_relation_tuples(TUPLES)
    d = Daemon(reg)
    d.start()
    yield d
    d.stop()


@pytest.fixture
def finished(monkeypatch):
    """(transport, method, stages, handler seconds, launch ids) of every
    request that ends while the test runs."""
    import keto_tpu.api.grpc_server as grpc_server
    import keto_tpu.api.rest_server as rest_server
    import keto_tpu.observability as observability

    seen = []

    def spy(metrics, threshold, transport, method, rt, code, duration, **kw):
        observability.finish_request_telemetry(
            metrics, threshold, transport, method, rt, code, duration, **kw
        )
        seen.append(
            (transport, method, dict(rt.stages), duration, list(rt.launch_ids))
        )

    monkeypatch.setattr(grpc_server, "finish_request_telemetry", spy)
    monkeypatch.setattr(rest_server, "finish_request_telemetry", spy)
    return seen


def await_finished(finished, n: int = 1, seconds: float = 5.0) -> None:
    """A handler's bookkeeping ends after its client has the answer."""
    deadline = time.monotonic() + seconds
    while len(finished) < n and time.monotonic() < deadline:
        time.sleep(0.02)


def feed_seconds(daemon) -> dict:
    metrics = daemon.registry.metrics()
    return {
        state: metrics.registry.get_sample_value(
            "keto_tpu_device_feed_seconds_total", {"state": state}
        )
        for state in DEVICE_FEED_STATES
    }


class TestBatchCheckStages:
    def test_grpc_batch_check_stages_add_up_to_the_handler(
        self, daemon, finished
    ):
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            stranger = RelationTuple.from_string("files:doc1#owner@nobody")
            out = client.check_batch(TUPLES[:40] + [stranger])
        finally:
            client.close()
        assert [allowed for allowed, _ in out] == [True] * 40 + [False]
        await_finished(finished)
        (transport, method, stages, seconds, launch_ids), = finished
        assert (transport, method) == ("grpc", "BatchCheck")
        assert set(stages) == BATCH_STAGES
        assert all(v >= 0.0 for v in stages.values())
        assert sum(stages.values()) == pytest.approx(seconds)
        assert len(launch_ids) == 1
        # the residual is now observed on this path too
        count = daemon.registry.metrics().registry.get_sample_value(
            "keto_tpu_check_stage_duration_seconds_count",
            {"stage": "transport"},
        )
        assert count >= 1
        entry = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{daemon.metrics_port}/admin/flightrec"
        ).read())["entries"][-1]
        assert entry["launch_id"] == launch_ids[0]
        assert set(entry["stage_ms"]) == {
            "assemble", "dispatch", "device_wait", "resolve"
        }
        assert entry["device_ms"] >= 0.0 and entry["starved_ms"] >= 0.0
        assert len(entry["trace_ids"]) == 1

    def test_rest_batch_check_has_the_same_stages(self, daemon, finished):
        body = json.dumps({"tuples": [t.to_dict() for t in TUPLES[:8]]})
        req = urllib.request.Request(
            f"http://127.0.0.1:{daemon.read_port}/relation-tuples/check/batch",
            data=body.encode(), method="POST",
            headers={"Content-Type": "application/json"},
        )
        results = json.loads(urllib.request.urlopen(req).read())["results"]
        assert [r["allowed"] for r in results] == [True] * 8
        # the daemon is shared: a GET of the test before may end its
        # bookkeeping only now, so wait for this POST and read it alone
        posts = lambda: [f for f in finished if f[1].startswith("POST")]
        deadline = time.monotonic() + 5.0
        while not posts() and time.monotonic() < deadline:
            time.sleep(0.02)
        (transport, _method, stages, seconds, _ids), = posts()
        assert transport == "http"
        assert set(stages) == BATCH_STAGES
        assert sum(stages.values()) == pytest.approx(seconds)

    def test_batcher_launch_charges_queue(self, daemon, finished):
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            client.check(TUPLES[0])  # the account has started, and is idle
            before = feed_seconds(daemon)
            assert client.check(TUPLES[1]) is True
        finally:
            client.close()
        await_finished(finished, n=2)
        after = feed_seconds(daemon)
        # the device stood empty while the check sat in the batcher, and
        # before it: both are charged, and nothing is charged twice
        assert after["starved_queue"] > before["starved_queue"]
        assert after["starved_no_request"] > before["starved_no_request"]
        stages = [s for t, m, s, *_ in finished if m == "Check"][-1]
        assert {"queue", "assemble", "dispatch", "device_wait",
                "resolve", "transport"} <= set(stages)
        assert "decode" not in stages and "respond" not in stages

    def test_one_rider_reaches_every_slice_of_a_split_batch(self):
        cfg = Config({"dsn": "memory", "check": {"engine": "tpu"}})
        cfg.set_namespaces([Namespace(name="files")])
        reg = Registry(cfg)
        reg.relation_tuple_manager().write_relation_tuples(TUPLES)
        from keto_tpu.engine.tpu_engine import TPUCheckEngine

        # the largest bucket holds 16: 40 tuples ride three launches
        engine = TPUCheckEngine(
            reg.relation_tuple_manager(), cfg, frontier_cap=16,
            metrics=reg.metrics(),
        )
        rt = RequestTrace()
        token = set_request_trace(rt)
        try:
            results = engine.check_batch(TUPLES[:40])
        finally:
            reset_request_trace(token)
        assert all(r.allowed for r in results)
        assert len(rt.launch_ids) == 3
        assert set(rt.stages) == {
            "assemble", "dispatch", "device_wait", "resolve"
        }
        assert reg.metrics().registry.get_sample_value(
            "keto_tpu_launch_device_seconds_count"
        ) == 3


class TestNamesInTheTrace:
    def test_host_plane_carries_stage_annotations_with_launch_ids(
        self, daemon, tmp_path
    ):
        import jax
        from jax.profiler import ProfileData

        engine = daemon.registry.check_engine()
        engine.check_batch(TUPLES[:8])  # compiled before the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        rt = RequestTrace()
        token = set_request_trace(rt)
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            engine.check_batch(TUPLES[:8])
        finally:
            jax.profiler.stop_trace()
            reset_request_trace(token)
        (path,) = glob.glob(
            os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True
        )
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith("keto."):
                        found[event.name] = dict(event.stats)
        (launch_id,) = rt.launch_ids
        for name in ("keto.assemble", "keto.dispatch", "keto.device_wait",
                     "keto.resolve"):
            assert found[name]["launch_id"] == launch_id, (name, found)

    def test_lowered_check_program_names_the_kernel_phases(self, daemon):
        import numpy as np

        from keto_tpu.engine.kernel import (
            check_kernel_packed,
            kernel_static_config,
        )

        engine = daemon.registry.check_engine()
        state = engine._ensure_state()
        cfg = kernel_static_config(
            state.snapshot, 5, 64, n_island_cap=0, has_delta=state.has_delta
        )
        text = check_kernel_packed.lower(
            state.tables, np.zeros((7, 16), np.int32), **cfg
        ).as_text(debug_info=True)
        for scope in ("keto.check", "keto.probe", "keto.expand",
                      "keto.dedupe", "keto.bucket_rows"):
            assert scope in text, scope
        # the program's own name still matches the benchmark's `check`
        assert "jit_check_kernel_packed" in text
