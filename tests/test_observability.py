"""Observability plane: config schema validation, tracing spans, W3C
traceparent propagation parity across REST/gRPC/aio, per-stage Check
metrics, request + slow-query logs, the traced-manager coverage
contract, and the on-demand profiler endpoint."""

import json
import logging
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from keto_tpu.config import Config, ConfigError
from keto_tpu.api import ReadClient, open_channel
from keto_tpu.api.daemon import Daemon
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.registry import Registry


class TestConfigSchema:
    def test_unknown_top_level_key_rejected(self):
        with pytest.raises(ConfigError) as e:
            Config({"dns": "memory"})  # typo of dsn
        assert "dns" in str(e.value)

    def test_bad_nested_value_names_the_key(self):
        with pytest.raises(ConfigError) as e:
            Config({"limit": {"max_read_depth": "five"}})
        assert "limit.max_read_depth" in str(e.value)

    def test_bad_engine_enum(self):
        with pytest.raises(ConfigError):
            Config({"check": {"engine": "gpu"}})

    def test_set_validates_and_rolls_back(self):
        cfg = Config({"limit": {"max_read_depth": 5}})
        with pytest.raises(ConfigError):
            cfg.set("limit.max_read_depth", -3)
        assert cfg.max_read_depth() == 5  # untouched after rejection

    def test_immutable_keys_still_enforced(self):
        cfg = Config({"dsn": "memory"})
        with pytest.raises(ConfigError):
            cfg.set("dsn", "columnar")

    def test_valid_config_passes(self):
        Config({
            "dsn": "memory",
            "check": {"engine": "tpu", "frontier_cap": 4096},
            "serve": {"read": {"host": "127.0.0.1", "port": 0}},
            "tracing": {"enabled": True, "provider": "memory"},
            "tenancy": {"header": "x-keto-network"},
        })

    def test_slow_query_threshold_validates(self):
        Config({"log": {"slow_query_ms": 10.5}})
        with pytest.raises(ConfigError):
            Config({"log": {"slow_query_ms": -1}})


class TestTraceContext:
    def test_parse_roundtrip(self):
        from keto_tpu.observability import new_trace, parse_traceparent

        ctx = new_trace()
        back = parse_traceparent(ctx.to_traceparent())
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id
        assert back.sampled is True

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-abc-def-01",
        "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",  # forbidden version
        "00-" + "0" * 32 + "-" + "b" * 16 + "-01",  # zero trace id
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # zero span id
        "00-" + "z" * 32 + "-" + "b" * 16 + "-01",  # non-hex
    ])
    def test_malformed_is_none(self, bad):
        from keto_tpu.observability import parse_traceparent

        assert parse_traceparent(bad) is None

    def test_child_keeps_trace_id(self):
        from keto_tpu.observability import new_trace

        ctx = new_trace()
        child = ctx.child()
        assert child.trace_id == ctx.trace_id
        assert child.span_id != ctx.span_id


class TestTracing:
    def test_spans_cover_store_engine_and_rpc(self):
        cfg = Config({
            "dsn": "memory",
            "check": {"engine": "tpu"},
            "tracing": {"enabled": True, "provider": "memory"},
            "serve": {
                "read": {"host": "127.0.0.1", "port": 0},
                "write": {"host": "127.0.0.1", "port": 0},
                "metrics": {"host": "127.0.0.1", "port": 0},
            },
        })
        cfg.set_namespaces([Namespace(name="files")])
        reg = Registry(cfg)
        reg.relation_tuple_manager().write_relation_tuples(
            [RelationTuple.from_string("files:doc#owner@alice")]
        )
        d = Daemon(reg)
        d.start()
        try:
            u = (
                f"http://127.0.0.1:{d.read_port}/relation-tuples/check/openapi"
                "?namespace=files&object=doc&relation=owner&subject_id=alice"
            )
            assert json.load(urllib.request.urlopen(u))["allowed"] is True
        finally:
            d.stop()
        names = reg.tracer().span_names()
        # store op, snapshot build, kernel launch, result resolution, and
        # the HTTP request span must all be present
        assert "persistence.write_relation_tuples" in names
        assert "engine.snapshot_build" in names
        assert "engine.kernel_launch" in names
        assert "engine.resolve_batch" in names
        assert any(n.startswith("http.") for n in names)

    def test_tracing_disabled_is_noop(self):
        cfg = Config({"dsn": "memory"})
        cfg.set_namespaces([Namespace(name="files")])
        reg = Registry(cfg)
        t = reg.tracer()
        with t.span("anything") as s:
            s.set_attribute("k", "v")
        assert not hasattr(t, "spans")
        assert t.active is False


# ---------------------------------------------------------------------------
# the request-scoped telemetry plane (PR 3 tentpole)
# ---------------------------------------------------------------------------

NAMESPACES = [Namespace(name="files")]
TUPLE = "files:doc#owner@alice"

# engine stages a device-served single check must attribute (the
# acceptance bar: >= 3 engine stages sharing the request's trace_id)
ENGINE_STAGES = {"engine.assemble", "engine.dispatch", "engine.device_wait"}


@pytest.fixture(scope="module")
def daemon():
    cfg = Config({
        "dsn": "memory",
        # cache off: this module asserts the batcher/engine pipeline
        # internals (queue/assemble/dispatch spans, stage histograms) on
        # repeated identical checks — with the serve-side check cache on,
        # repeats would (correctly) skip the pipeline under test
        "check": {"engine": "tpu", "cache": {"enabled": False}},
        "tracing": {"enabled": True, "provider": "memory"},
        "serve": {
            "read": {
                "host": "127.0.0.1", "port": 0,
                # direct aio listener beside the muxed (threaded) port:
                # one daemon exercises all three planes
                "grpc": {"host": "127.0.0.1", "port": 0, "aio": True},
            },
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


def _span_names_for(reg, trace_id: str) -> set:
    return {s.name for s in reg.tracer().spans_for_trace(trace_id)}


def _assert_full_pipeline(names: set, transport_prefix: str):
    assert any(n.startswith(transport_prefix) for n in names), names
    assert "batcher.queue" in names, names
    assert ENGINE_STAGES <= names, names


class TestTraceparentParity:
    """One Check with a traceparent yields correlated spans for the
    transport, the batcher queue, and >= 3 engine stages — identically
    through REST, threaded gRPC, and the aio plane."""

    def test_rest_header(self, daemon):
        tid = "11" * 16
        req = urllib.request.Request(
            f"http://127.0.0.1:{daemon.read_port}"
            "/relation-tuples/check/openapi"
            "?namespace=files&object=doc&relation=owner&subject_id=alice",
            headers={"traceparent": f"00-{tid}-{'22' * 8}-01"},
        )
        assert json.load(urllib.request.urlopen(req))["allowed"] is True
        _assert_full_pipeline(
            _span_names_for(daemon.registry, tid), "http."
        )

    def test_grpc_metadata(self, daemon):
        tid = "33" * 16
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            assert client.check(
                RelationTuple.from_string(TUPLE),
                traceparent=f"00-{tid}-{'44' * 8}-01",
            ) is True
        finally:
            client.close()
        _assert_full_pipeline(
            _span_names_for(daemon.registry, tid), "grpc."
        )

    def test_aio_metadata(self, daemon):
        tid = "55" * 16
        client = ReadClient(
            open_channel(f"127.0.0.1:{daemon.read_grpc_port}")
        )
        try:
            assert client.check(
                RelationTuple.from_string(TUPLE),
                traceparent=f"00-{tid}-{'66' * 8}-01",
            ) is True
        finally:
            client.close()
        _assert_full_pipeline(
            _span_names_for(daemon.registry, tid), "grpc."
        )

    def test_malformed_header_starts_fresh_trace(self, daemon):
        req = urllib.request.Request(
            f"http://127.0.0.1:{daemon.read_port}"
            "/relation-tuples/check/openapi"
            "?namespace=files&object=doc&relation=owner&subject_id=alice",
            headers={"traceparent": "not-a-traceparent"},
        )
        assert json.load(urllib.request.urlopen(req))["allowed"] is True


class TestStageMetrics:
    def test_stage_histograms_in_prometheus_export(self, daemon):
        # a served check has already run (TestTraceparentParity order is
        # not guaranteed — serve one more to be self-sufficient)
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            client.check(RelationTuple.from_string(TUPLE))
        finally:
            client.close()
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{daemon.metrics_port}/metrics/prometheus"
        ).read().decode()
        for stage in ("transport", "queue", "assemble", "dispatch",
                      "device_wait"):
            needle = (
                'keto_tpu_check_stage_duration_seconds_count'
                f'{{stage="{stage}"}}'
            )
            assert needle in text, f"missing stage sample: {stage}"
        # the new pipeline gauges export too
        for gauge in (
            "keto_tpu_batcher_queue_depth", "keto_tpu_inflight_launches",
            "keto_tpu_snapshot_hbm_bytes", "keto_tpu_delta_overlay_ops",
            "keto_tpu_compaction_lag_versions",
        ):
            assert gauge in text, f"missing gauge: {gauge}"

    def test_snapshot_hbm_bytes_nonzero(self, daemon):
        m = daemon.registry.metrics()
        assert m.snapshot_hbm_bytes._value.get() > 0

    @pytest.fixture(scope="class")
    def built(self):
        """A registry of its own whose mirror was built exactly once."""
        cfg = Config({"dsn": "memory", "check": {"engine": "tpu"}})
        cfg.set_namespaces(NAMESPACES)
        reg = Registry(cfg)
        reg.relation_tuple_manager().write_relation_tuples(
            [RelationTuple.from_string(TUPLE)]
        )
        return reg, reg.check_engine()._ensure_state()

    def test_mirror_build_phases_add_up_to_the_builds_duration(self, built):
        """Each phase of the rebuild is timed once, and the duration
        sample is the sum of the same clock reads."""
        from keto_tpu.observability import MIRROR_BUILD_PHASES

        m = built[0].metrics()
        phases = {
            phase: m.mirror_build_seconds.labels(phase)._value.get()
            for phase in MIRROR_BUILD_PHASES
        }
        assert all(seconds > 0 for seconds in phases.values()), phases
        assert m.snapshot_builds_total._value.get() == 1
        duration = m.snapshot_build_duration._sum.get()
        assert abs(sum(phases.values()) - duration) <= 0.1 * duration
        text = m.export().decode()
        for phase in MIRROR_BUILD_PHASES:
            assert f'keto_tpu_mirror_build_seconds{{phase="{phase}"}}' in text

    def test_hbm_gauge_counts_what_the_device_holds(self, built):
        """512 B a 64-lane int32 bucket row, not the 256 of `nbytes`:
        the gauge and the flight recorder's `hbm` agree, by arithmetic
        that is the chip's on every backend."""
        from keto_tpu.engine.kernel import tiled_nbytes

        reg, state = built
        tables = state.tables
        held = sum(tiled_nbytes(t.shape, t.dtype) for t in tables.values())
        assert tables["dh_pack"].shape[1] == 64
        assert tiled_nbytes(tables["dh_pack"].shape, "int32") == (
            2 * tables["dh_pack"].nbytes
        )
        assert reg.metrics().snapshot_hbm_bytes._value.get() == held
        assert held > sum(t.nbytes for t in tables.values())
        hbm = reg.check_engine().hbm_snapshot()
        assert sum(hbm["buffers"]["check"].values()) == held

    @pytest.mark.parametrize(
        "stats, in_use, limit",
        [
            ({"bytes_in_use": 3, "bytes_limit": 16, "peak_bytes_in_use": 9}, 6, 32),
            ({"bytes_in_use": 3}, 6, 0),
            (None, 0, 0),
        ],
        ids=["chip", "key_absent", "cpu"],
    )
    def test_device_memory_gauges_read_the_devices_at_a_scrape(
        self, stats, in_use, limit
    ):
        """Summed over the mirror's devices; a backend without the
        statistic, or without a key, reads as nothing."""
        from types import SimpleNamespace

        from keto_tpu.observability import Metrics

        m = Metrics()
        assert "keto_tpu_device_bytes_in_use 0.0" in m.export().decode()
        reads = []

        def memory_stats():
            reads.append(1)
            return stats

        m.watch_device_memory([SimpleNamespace(memory_stats=memory_stats)] * 2)
        text = m.export().decode()
        assert f"keto_tpu_device_bytes_in_use {float(in_use)}" in text
        assert f"keto_tpu_device_bytes_limit {float(limit)}" in text
        seen = len(reads)
        m.export()
        assert len(reads) == 2 * seen  # read anew at every scrape

    def test_error_status_mirrored_into_request_counter(self, daemon):
        # bare check route mirrors deny as 403 — the outcome label must
        # say 403, not OK (the satellite fix: no error response counts
        # as code="OK")
        url = (
            f"http://127.0.0.1:{daemon.read_port}/relation-tuples/check"
            "?namespace=files&object=doc&relation=owner&subject_id=nobody"
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url)
        assert e.value.code == 403
        # the counter increments when the server's observe_request block
        # EXITS, which races the client seeing the response bytes — poll
        # the scrape briefly instead of asserting the first read (the
        # same post-response race PR 4 de-flaked on the request log)
        deadline = time.monotonic() + 5
        text = ""
        while time.monotonic() < deadline:
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{daemon.metrics_port}/metrics/prometheus"
            ).read().decode()
            if 'code="403"' in text:
                break
            time.sleep(0.05)
        assert 'code="403"' in text


class TestRequestAndSlowQueryLogs:
    def test_request_log_wired_into_transports(self, daemon, caplog):
        with caplog.at_level(logging.INFO, logger="keto_tpu"):
            client = ReadClient(
                open_channel(f"127.0.0.1:{daemon.read_port}")
            )
            try:
                client.check(RelationTuple.from_string(TUPLE))
            finally:
                client.close()
            urllib.request.urlopen(
                f"http://127.0.0.1:{daemon.read_port}"
                "/relation-tuples/check/openapi"
                "?namespace=files&object=doc&relation=owner&subject_id=alice"
            )
            # the REST plane logs AFTER the response bytes reach the
            # client — wait (inside the raised-level block, or the late
            # record is filtered at WARNING) for the handler thread
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if {
                    getattr(r, "transport", None)
                    for r in caplog.records
                    if r.getMessage() == "request handled"
                } >= {"grpc", "http"}:
                    break
                time.sleep(0.01)
        handled = [
            r for r in caplog.records if r.getMessage() == "request handled"
        ]
        transports = {getattr(r, "transport", None) for r in handled}
        assert "grpc" in transports and "http" in transports
        for r in handled:
            if getattr(r, "method", "") in ("Check",):
                assert getattr(r, "trace_id", "")
                assert "queue" in getattr(r, "stages_ms", {})

    def test_slow_query_log_fires_above_threshold(self, daemon, caplog):
        daemon.registry.config.set("log.slow_query_ms", 0)
        try:
            with caplog.at_level(logging.WARNING, logger="keto_tpu"):
                client = ReadClient(
                    open_channel(f"127.0.0.1:{daemon.read_port}")
                )
                try:
                    client.check(RelationTuple.from_string(TUPLE))
                finally:
                    client.close()
            slow = [
                r for r in caplog.records
                if r.getMessage().startswith("slow request")
            ]
            assert slow, "threshold 0 must fire on every request"
            msg = slow[0].getMessage()
            assert "trace_id=" in msg and "stages_ms=" in msg
        finally:
            daemon.registry.config.set("log.slow_query_ms", None)

    def test_slow_query_log_silent_below_threshold(self, daemon, caplog):
        daemon.registry.config.set("log.slow_query_ms", 60_000.0)
        try:
            with caplog.at_level(logging.WARNING, logger="keto_tpu"):
                client = ReadClient(
                    open_channel(f"127.0.0.1:{daemon.read_port}")
                )
                try:
                    client.check(RelationTuple.from_string(TUPLE))
                finally:
                    client.close()
            assert not any(
                r.getMessage().startswith("slow request")
                for r in caplog.records
            )
        finally:
            daemon.registry.config.set("log.slow_query_ms", None)


class TestProfilerEndpoint:
    def _post(self, daemon, path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{daemon.metrics_port}{path}",
            data=json.dumps(body).encode() if body is not None else b"",
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        return json.load(urllib.request.urlopen(req))

    def test_live_cycle_writes_artifact(self, daemon, tmp_path):
        out = str(tmp_path / "serve.pstats")
        started = self._post(
            daemon, "/admin/profiling", {"mode": "cpu", "path": out}
        )
        assert started["running"] is True and started["mode"] == "cpu"
        # capture real serve work without restarting the daemon
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            client.check(RelationTuple.from_string(TUPLE))
        finally:
            client.close()
        stopped = self._post(daemon, "/admin/profiling/stop")
        assert stopped["artifact"] == out
        assert (tmp_path / "serve.pstats").exists()
        # pstats must actually load (a truncated dump would too-late-fail
        # the operator)
        import pstats

        pstats.Stats(out)

    def test_double_stop_is_idempotent(self, daemon):
        first = self._post(daemon, "/admin/profiling/stop")
        second = self._post(daemon, "/admin/profiling/stop")
        assert second == {"running": False, "artifact": None}
        assert first["running"] is False

    def test_double_start_conflicts(self, daemon, tmp_path):
        self._post(
            daemon, "/admin/profiling",
            {"mode": "mem", "path": str(tmp_path / "m.txt")},
        )
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                self._post(daemon, "/admin/profiling", {"mode": "cpu"})
            assert e.value.code == 409
        finally:
            self._post(daemon, "/admin/profiling/stop")

    def test_unknown_mode_is_400(self, daemon):
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(daemon, "/admin/profiling", {"mode": "gpu"})
        assert e.value.code == 400

    def test_path_escaping_profile_dir_is_400(self, daemon):
        # the admin endpoint must not be an arbitrary-file-write
        # primitive: artifact paths are confined to KETO_PROFILE_DIR
        # (default: the system tempdir)
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(
                daemon, "/admin/profiling",
                {"mode": "cpu", "path": "/etc/keto-pwned"},
            )
        assert e.value.code == 400
        # traversal out of the base dir is caught after normalization
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(
                daemon, "/admin/profiling",
                {"mode": "cpu", "path": "../../etc/keto-pwned"},
            )
        assert e.value.code == 400

    def test_status_reports_idle(self, daemon):
        status = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{daemon.metrics_port}/admin/profiling"
        ))
        assert status["running"] is False


class TestTracedManagerCoverage:
    """Every public store-manager method is either span-traced or
    explicitly exempted — the PR-2 watch ops bypassed the proxy because
    nothing enforced the list; this does."""

    def _public_methods(self, cls) -> set:
        import inspect

        return {
            name
            for name, member in inspect.getmembers(
                cls, predicate=inspect.isfunction
            )
            if not name.startswith("_")
        }

    @pytest.mark.parametrize("cls_path", [
        ("keto_tpu.storage.memory", "MemoryManager"),
        ("keto_tpu.storage.sqlite", "SQLPersister"),
        ("keto_tpu.storage.columnar", "ColumnarStore"),
    ])
    def test_every_public_method_covered(self, cls_path):
        import importlib

        from keto_tpu.observability import TracedManager

        mod, cls_name = cls_path
        cls = getattr(importlib.import_module(mod), cls_name)
        covered = set(TracedManager._TRACED) | set(TracedManager._EXEMPT)
        missing = self._public_methods(cls) - covered
        assert not missing, (
            f"{cls_name} public methods neither traced nor exempted: "
            f"{sorted(missing)} — add to TracedManager._TRACED or "
            f"_EXEMPT (with the reason)"
        )

    def test_traced_and_exempt_disjoint(self):
        from keto_tpu.observability import TracedManager

        both = set(TracedManager._TRACED) & set(TracedManager._EXEMPT)
        assert not both

    def test_traced_names_exist_somewhere(self):
        # a stale _TRACED entry (renamed store op) would silently trace
        # nothing; every name must exist on at least one store class
        import importlib

        from keto_tpu.observability import TracedManager

        classes = [
            getattr(importlib.import_module(m), c)
            for m, c in (
                ("keto_tpu.storage.memory", "MemoryManager"),
                ("keto_tpu.storage.sqlite", "SQLPersister"),
                ("keto_tpu.storage.columnar", "ColumnarStore"),
            )
        ]
        for name in TracedManager._TRACED:
            assert any(hasattr(cls, name) for cls in classes), (
                f"_TRACED entry {name!r} matches no store class method"
            )

    def test_watch_era_ops_are_traced(self):
        from keto_tpu.observability import RecordingTracer, TracedManager
        from keto_tpu.storage.memory import MemoryManager

        tracer = RecordingTracer()
        mgr = TracedManager(MemoryManager(), tracer)
        mgr.write_relation_tuples([RelationTuple.from_string(TUPLE)])
        mgr.changes_since(0)
        mgr.changelog_since(0)
        names = tracer.span_names()
        assert "persistence.changes_since" in names
        assert "persistence.changelog_since" in names


class TestMetricsDocsGolden:
    def test_docs_table_in_sync(self):
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "check_metrics_docs.py")],
            capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# the explain/export plane (§5m): OTLP span exporter, exemplars,
# request-log sampling, flight-recorder filters
# ---------------------------------------------------------------------------


class _StubCollector:
    """Stdlib OTLP collector stand-in: records every POSTed JSON body."""

    def __init__(self):
        import threading
        from http.server import BaseHTTPRequestHandler, HTTPServer

        received = self.received = []

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                received.append(json.loads(self.rfile.read(n)))
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()

            def log_message(self, *a):
                pass

        self.srv = HTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()

    @property
    def endpoint(self):
        return f"http://127.0.0.1:{self.srv.server_address[1]}/v1/traces"

    def spans(self):
        out = []
        for payload in self.received:
            for rs in payload.get("resourceSpans", ()):
                for ss in rs.get("scopeSpans", ()):
                    out.extend(ss.get("spans", ()))
        return out

    def close(self):
        self.srv.shutdown()
        self.srv.server_close()


class TestSpanExporter:
    def _tracer(self, exporter):
        from keto_tpu.observability import RecordingTracer

        return RecordingTracer(exporter=exporter)

    def test_exports_wellformed_parent_linked_spans(self):
        from keto_tpu.observability import SpanExporter, new_trace

        collector = _StubCollector()
        exp = SpanExporter(collector.endpoint, flush_interval_s=0.02)
        try:
            tracer = self._tracer(exp)
            ctx = new_trace().child()  # like a transport ingesting one
            with tracer.span("http.test", ctx=ctx, root=True):
                pass
            tracer.record(
                "engine.device_wait", ctx=ctx, duration_s=0.003,
                launch_id=41,
            )
            assert exp.flush(5.0)
            spans = collector.spans()
            by_name = {s["name"]: s for s in spans}
            assert set(by_name) == {"http.test", "engine.device_wait"}
            root = by_name["http.test"]
            child = by_name["engine.device_wait"]
            assert root["traceId"] == child["traceId"] == ctx.trace_id
            # the root takes the ctx's own span id; the child parents
            # to it; the root parents to the ORIGINAL caller span
            assert root["spanId"] == ctx.span_id
            assert child["parentSpanId"] == ctx.span_id
            assert root["parentSpanId"] == ctx.parent_span_id
            # launch ids ride as span events (the flightrec join)
            ev = child["events"][0]
            assert ev["name"] == "flightrec.launch"
            assert ev["attributes"][0]["value"]["intValue"] == "41"
            # timestamps are real epoch nanos, end >= start
            assert int(child["endTimeUnixNano"]) >= int(
                child["startTimeUnixNano"]
            )
            assert exp.stats["exported"] == 2
        finally:
            exp.close()
            collector.close()

    def test_queue_overflow_drops_counted_never_blocks(self):
        from keto_tpu.observability import (
            RecordedSpan,
            SpanExporter,
        )

        # unroutable endpoint + tiny queue: every POST fails, overflow
        # drops count, and enqueue stays non-blocking throughout
        exp = SpanExporter(
            "http://127.0.0.1:9/v1/traces", queue_size=2,
            flush_interval_s=30.0, post_timeout_s=0.2,
        )
        try:
            t0 = time.perf_counter()
            results = [
                exp.enqueue(RecordedSpan("s", {
                    "trace_id": "ab" * 16, "span_id": "cd" * 8,
                    "t_mono": time.monotonic(),
                }))
                for _ in range(10)
            ]
            took = time.perf_counter() - t0
            assert took < 0.5, "enqueue must never block"
            assert results.count(False) >= 8  # queue bound 2
            assert exp.stats["dropped_queue_full"] >= 8
        finally:
            exp.close(timeout=0.1)

    def test_post_error_drops_counted(self):
        from keto_tpu.observability import RecordedSpan, SpanExporter

        exp = SpanExporter(
            "http://127.0.0.1:9/v1/traces", flush_interval_s=0.02,
            post_timeout_s=0.2,
        )
        try:
            exp.enqueue(RecordedSpan("s", {
                "trace_id": "ab" * 16, "span_id": "cd" * 8,
                "t_mono": time.monotonic(),
            }))
            assert exp.flush(5.0)
            assert exp.stats["dropped_post_error"] == 1
            assert exp.stats["exported"] == 0
        finally:
            exp.close(timeout=0.1)

    def test_endpoint_config_builds_exporting_tracer(self):
        from keto_tpu.observability import RecordingTracer

        collector = _StubCollector()
        try:
            cfg = Config({
                "dsn": "memory",
                "observability": {"otlp": {"endpoint": collector.endpoint}},
            })
            reg = Registry(cfg)
            tracer = reg.tracer()
            assert isinstance(tracer, RecordingTracer)
            assert tracer.exporter is reg.span_exporter()
            reg.span_exporter().close(timeout=0.5)
        finally:
            collector.close()

    def test_no_endpoint_no_exporter(self):
        reg = Registry(Config({"dsn": "memory"}))
        assert reg.span_exporter() is None


class TestExemplars:
    def test_stage_histogram_carries_trace_exemplar(self, daemon):
        from keto_tpu.observability import new_trace

        ctx = new_trace()
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            client.check(
                RelationTuple.from_string(TUPLE),
                traceparent=ctx.to_traceparent(),
            )
        finally:
            client.close()
        req = urllib.request.Request(
            f"http://127.0.0.1:{daemon.metrics_port}/metrics/prometheus",
            headers={"Accept": "application/openmetrics-text"},
        )
        with urllib.request.urlopen(req) as r:
            assert "openmetrics" in r.headers["Content-Type"]
            text = r.read().decode()
        exemplar_lines = [
            line for line in text.splitlines()
            if "keto_tpu_check_stage_duration_seconds_bucket" in line
            and "# {" in line and "trace_id=" in line
        ]
        assert exemplar_lines, "stage buckets must carry trace exemplars"
        # the classic exposition stays the default (no exemplars there)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{daemon.metrics_port}/metrics/prometheus"
        ) as r:
            classic = r.read().decode()
        assert "# {" not in classic


class TestRequestLogSampling:
    def _one_check(self, daemon):
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            client.check(RelationTuple.from_string(TUPLE))
        finally:
            client.close()

    def test_default_rate_is_one_every_request_logged(self, daemon, caplog):
        # schema default 1.0 pinned: with the key unset, the INFO line
        # emits unconditionally (exactly the pre-sampling behavior)
        assert daemon.registry.config.get("log.request_sample_rate") is None
        with caplog.at_level(logging.INFO, logger="keto_tpu"):
            self._one_check(daemon)
        assert any(
            r.getMessage() == "request handled" for r in caplog.records
        )

    def test_rate_zero_suppresses_info_keeps_slow_warning(
        self, daemon, caplog
    ):
        daemon.registry.config.set("log.request_sample_rate", 0.0)
        daemon.registry.config.set("log.slow_query_ms", 0)
        try:
            with caplog.at_level(logging.INFO, logger="keto_tpu"):
                self._one_check(daemon)
            assert not any(
                r.getMessage() == "request handled"
                and getattr(r, "transport", "") == "grpc"
                for r in caplog.records
            )
            # the slow-query WARNING always emits — sampling must never
            # swallow incident evidence
            assert any(
                r.getMessage().startswith("slow request")
                for r in caplog.records
            )
        finally:
            daemon.registry.config.set("log.request_sample_rate", 1.0)
            daemon.registry.config.set("log.slow_query_ms", None)

    def test_rate_validates_in_schema(self):
        Config({"log": {"request_sample_rate": 0.25}})
        with pytest.raises(ConfigError):
            Config({"log": {"request_sample_rate": 1.5}})


class TestFlightrecFilters:
    def _dump(self, daemon, query=""):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{daemon.metrics_port}/admin/flightrec{query}"
        ) as r:
            return json.loads(r.read())

    def test_kind_and_trace_id_filters(self, daemon):
        from keto_tpu.observability import new_trace

        ctx = new_trace()
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            client.check(
                RelationTuple.from_string(TUPLE),
                traceparent=ctx.to_traceparent(),
            )
            client.check(RelationTuple.from_string(TUPLE))
        finally:
            client.close()
        full = self._dump(daemon)
        assert full["entries"], "ring must hold the check launches"
        by_kind = self._dump(daemon, "?kind=check")
        assert by_kind["entries"]
        assert all(e["kind"] == "check" for e in by_kind["entries"])
        none_kind = self._dump(daemon, "?kind=filter")
        assert none_kind["entries"] == []
        by_trace = self._dump(daemon, f"?trace_id={ctx.trace_id}")
        assert by_trace["entries"], "trace filter must find the ride"
        assert all(
            ctx.trace_id in e["trace_ids"] for e in by_trace["entries"]
        )
        # filters compose
        both = self._dump(daemon, f"?kind=check&trace_id={ctx.trace_id}")
        assert {e["launch_id"] for e in both["entries"]} == {
            e["launch_id"] for e in by_trace["entries"]
        }

    def test_since_launch_id_cursor(self, daemon):
        client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
        try:
            client.check(RelationTuple.from_string(TUPLE))
        finally:
            client.close()
        full = self._dump(daemon)
        ids = [e["launch_id"] for e in full["entries"]]
        assert ids == sorted(ids), "dump must be in launch-id order"
        cursor = ids[len(ids) // 2]
        tail = self._dump(daemon, f"?since_launch_id={cursor}")
        # STRICTLY-greater semantics: the poller passes the max id it
        # has seen and receives only the increment
        assert [e["launch_id"] for e in tail["entries"]] == [
            i for i in ids if i > cursor
        ]
        # a cursor at the ring's tail yields the empty increment
        empty = self._dump(daemon, f"?since_launch_id={max(ids)}")
        assert empty["entries"] == []
        # composes with ?kind=
        both = self._dump(daemon, f"?kind=check&since_launch_id={cursor}")
        assert all(
            e["kind"] == "check" and e["launch_id"] > cursor
            for e in both["entries"]
        )
        # a non-integer cursor is typed client error, not a 500
        with pytest.raises(urllib.error.HTTPError) as e:
            self._dump(daemon, "?since_launch_id=abc")
        assert e.value.code == 400
