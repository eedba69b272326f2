"""Where a served BatchCheck's time goes outside the handler's stages.

Counts, and clocks only as far as they order: no rate, no share (those come
from the chip, through benchmarks/). What is held here:

  - `StageSpan` reads the thread's CPU clock beside the wall: a busy loop
    is on the CPU, a sleep is not;
  - a unary gRPC call's edge (`api/grpc_server.py` `_EdgeCall`): one
    `queued`, one `parse` and one `serialize` sample a call, outside the
    request's stages, whose `transport` residual reads as before;
  - every StageSpan stage of a BatchCheck feeds its CPU summary once;
  - the collector counted from inside (`observability.CollectorWatch`):
    one callback a process however many daemons start;
  - `keto.grpc.parse`, `keto.grpc.serialize` and `keto.gc` land in a
    profiler trace's host plane.
"""

import gc
import glob
import os
import resource
import time

import pytest

from keto_tpu.api import ReadClient, open_channel
from keto_tpu.api.daemon import Daemon
from keto_tpu.config import Config
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.observability import CollectorWatch, Metrics, StageSpan
from keto_tpu.registry import Registry

TUPLES = [
    RelationTuple.from_string(f"files:doc{i}#owner@user{i}") for i in range(48)
]
BATCH_STAGES = {
    "decode", "assemble", "dispatch", "device_wait", "resolve", "respond",
    "transport",
}
SERVE = {
    "read": {"host": "127.0.0.1", "port": 0},
    "write": {"host": "127.0.0.1", "port": 0},
    "metrics": {"host": "127.0.0.1", "port": 0},
}


def sample(daemon, name: str, **labels) -> float:
    value = daemon.registry.metrics().registry.get_sample_value(name, labels)
    return 0.0 if value is None else value


def edge(daemon, method: str) -> dict:
    """{phase: (count, sum)} of one method's keto_tpu_grpc_edge_seconds."""
    return {
        phase: (
            sample(daemon, "keto_tpu_grpc_edge_seconds_count",
                   method=method, phase=phase),
            sample(daemon, "keto_tpu_grpc_edge_seconds_sum",
                   method=method, phase=phase),
        )
        for phase in ("queued", "parse", "serialize")
    }


def batch_check(daemon, tuples):
    client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
    try:
        return client.check_batch(tuples)
    finally:
        client.close()


@pytest.fixture(scope="module")
def daemon():
    cfg = Config({
        "dsn": "memory",
        # cache off: a BatchCheck must reach the engine
        "check": {"engine": "tpu", "cache": {"enabled": False}},
        "serve": SERVE,
    })
    cfg.set_namespaces([Namespace(name="files")])
    reg = Registry(cfg)
    reg.relation_tuple_manager().write_relation_tuples(TUPLES)
    d = Daemon(reg)
    d.start()
    batch_check(d, TUPLES[:8])  # the check program compiled before any test
    yield d
    d.stop()


@pytest.fixture
def finished(monkeypatch):
    """(method, stages, handler seconds) of every gRPC request that ends
    while the test runs."""
    import keto_tpu.api.grpc_server as grpc_server
    import keto_tpu.observability as observability

    seen = []

    def spy(metrics, threshold, transport, method, rt, code, duration, **kw):
        observability.finish_request_telemetry(
            metrics, threshold, transport, method, rt, code, duration, **kw
        )
        seen.append((method, dict(rt.stages), duration))

    monkeypatch.setattr(grpc_server, "finish_request_telemetry", spy)
    return seen


def await_finished(finished, seconds: float = 5.0) -> None:
    """A handler's bookkeeping ends after its client has the answer."""
    deadline = time.monotonic() + seconds
    while not finished and time.monotonic() < deadline:
        time.sleep(0.02)


def thread_cpu() -> float:
    """This thread's CPU seconds as the kernel accounts them, a clock
    apart from the one StageSpan reads."""
    usage = resource.getrusage(resource.RUSAGE_THREAD)
    return usage.ru_utime + usage.ru_stime


class TestStageSpanClocks:
    def test_busy_loop_is_on_the_cpu(self):
        # a busy loop until the kernel has accounted 100 ms of CPU to this
        # thread (read apart from the span, at the scheduler tick's grain);
        # on an idle host its wall is as long, on a loaded one the wall
        # also holds the time the core was taken away, which the CPU clock
        # leaves out
        before = thread_cpu()
        with StageSpan("busy") as span:
            deadline = time.perf_counter() + 10.0
            while thread_cpu() - before < 0.1 and time.perf_counter() < deadline:
                pass
        used = thread_cpu() - before
        assert used >= 0.1
        assert span.cpu_seconds == pytest.approx(used, abs=0.01)
        assert span.cpu_seconds <= span.seconds + 1e-3

    def test_sleep_is_off_the_cpu(self):
        with StageSpan("idle") as span:
            time.sleep(0.05)
        assert span.seconds >= 0.05
        assert 0.0 <= span.cpu_seconds < 0.01


class TestGrpcEdge:
    def test_batch_check_records_each_phase_once_outside_its_stages(
        self, daemon, finished
    ):
        before = edge(daemon, "BatchCheck")
        out = batch_check(daemon, TUPLES[:40])
        assert [allowed for allowed, _ in out] == [True] * 40
        after = edge(daemon, "BatchCheck")
        count = {p: after[p][0] - before[p][0] for p in after}
        seconds = {p: after[p][1] - before[p][1] for p in after}
        assert count == {"queued": 1, "parse": 1, "serialize": 1}
        assert seconds["parse"] > 0.0 and seconds["serialize"] > 0.0
        assert seconds["queued"] >= 0.0
        # the handler's own account is as it was: its stages, none of the
        # edge's, and `transport` the residual that makes them add up
        await_finished(finished)
        (method, stages, handler_s), = finished
        assert method == "BatchCheck"
        assert set(stages) == BATCH_STAGES
        assert sum(stages.values()) == pytest.approx(handler_s)

    def test_other_methods_are_timed_under_their_own_name(self, daemon):
        before = edge(daemon, "Check")
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            assert client.check(TUPLES[3]) is True
        finally:
            client.close()
        after = edge(daemon, "Check")
        assert {p: after[p][0] - before[p][0] for p in after} == {
            "queued": 1, "parse": 1, "serialize": 1,
        }

    def test_every_spanned_stage_feeds_its_cpu_summary_once(self, daemon):
        stages = ("decode", "assemble", "dispatch", "device_wait", "resolve",
                  "respond")

        def counts():
            return {
                s: (
                    sample(daemon, "keto_tpu_check_stage_cpu_seconds_count",
                           stage=s),
                    sample(daemon, "keto_tpu_check_stage_duration_seconds_count",
                           stage=s),
                )
                for s in stages
            }

        before = counts()
        batch_check(daemon, TUPLES[:16])
        after = counts()
        for s in stages:
            cpu = after[s][0] - before[s][0]
            wall = after[s][1] - before[s][1]
            assert cpu == wall == 1, (s, cpu, wall)
        assert sample(daemon, "keto_tpu_check_stage_cpu_seconds_sum",
                      stage="assemble") > 0.0
        # the residual is no span of its own: it has no CPU clock
        assert sample(daemon, "keto_tpu_check_stage_cpu_seconds_count",
                      stage="transport") == 0


def installed() -> list:
    return [cb for cb in gc.callbacks if isinstance(cb, CollectorWatch)]


class TestCollector:
    def test_a_full_collection_counts_once_with_its_pause(self, daemon):
        metrics = Metrics()
        metrics.watch_collector()
        metrics.watch_collector()  # the same registry fed once

        def read(m, name):
            return m.registry.get_sample_value(name, {"generation": "2"})

        served = [
            read(daemon.registry.metrics(), "keto_tpu_gc_collections_total"),
            read(metrics, "keto_tpu_gc_collections_total"),
        ]
        pause = read(metrics, "keto_tpu_gc_pause_seconds_total")
        enabled = gc.isenabled()
        gc.disable()  # no collection but the one asked for
        try:
            gc.collect(2)
        finally:
            if enabled:
                gc.enable()
        assert read(metrics, "keto_tpu_gc_collections_total") == served[1] + 1
        assert read(metrics, "keto_tpu_gc_pause_seconds_total") > pause
        # every watching registry sees the collection, each once
        assert read(
            daemon.registry.metrics(), "keto_tpu_gc_collections_total"
        ) == served[0] + 1

    def test_a_second_daemon_installs_no_second_callback(self, daemon):
        assert len(installed()) == 1
        cfg = Config({"dsn": "memory", "check": {"engine": "host"},
                      "serve": SERVE})
        cfg.set_namespaces([Namespace(name="files")])
        second = Daemon(Registry(cfg))
        second.start()
        try:
            assert len(installed()) == 1
            (watch,) = installed()
            sinks = watch.sinks()
            assert daemon.registry.metrics() in sinks
            assert second.registry.metrics() in sinks
            assert len(sinks) == len({id(m) for m in sinks})
        finally:
            second.stop()


class TestNamesInTheTrace:
    def test_host_plane_carries_the_edge_and_the_collector(
        self, daemon, tmp_path
    ):
        import jax
        from jax.profiler import ProfileData

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            batch_check(daemon, TUPLES[:8])
            gc.collect(2)
        finally:
            jax.profiler.stop_trace()
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
                        found.setdefault(event.name, dict(event.stats))
        assert {"keto.grpc.parse", "keto.grpc.serialize", "keto.gc"} <= set(
            found
        ), sorted(found)
        assert float(found["keto.grpc.parse"]["queued_s"]) >= 0.0
