"""CORS + TLS serve options (ref: internal/driver/daemon.go:289-349 CORS
middleware and TLS listener config)."""

import json
import ssl
import subprocess
import urllib.request

import pytest

from keto_tpu.config import Config
from keto_tpu.api.daemon import Daemon
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace import Namespace
from keto_tpu.registry import Registry


def _base_cfg(extra_serve=None):
    serve = {
        "read": {"host": "127.0.0.1", "port": 0},
        "write": {"host": "127.0.0.1", "port": 0},
        "metrics": {"host": "127.0.0.1", "port": 0},
    }
    for k, v in (extra_serve or {}).items():
        serve[k].update(v)
    cfg = Config({"dsn": "memory", "serve": serve})
    cfg.set_namespaces([Namespace(name="files")])
    return cfg


class TestCORS:
    def _daemon(self, cors):
        extra = {"read": {"cors": cors}} if cors is not None else {}
        reg = Registry(_base_cfg(extra))
        reg.relation_tuple_manager().write_relation_tuples(
            [RelationTuple.from_string("files:doc#owner@alice")]
        )
        d = Daemon(reg)
        d.start()
        return d

    def test_allowed_origin_gets_headers(self):
        d = self._daemon({"enabled": True, "allowed_origins": ["https://app.example"]})
        try:
            url = (
                f"http://127.0.0.1:{d.read_port}/relation-tuples/check/openapi"
                "?namespace=files&object=doc&relation=owner&subject_id=alice"
            )
            req = urllib.request.Request(url, headers={"Origin": "https://app.example"})
            resp = urllib.request.urlopen(req)
            assert resp.headers["Access-Control-Allow-Origin"] == "https://app.example"
            # preflight
            pre = urllib.request.Request(
                url, method="OPTIONS", headers={"Origin": "https://app.example"}
            )
            p = urllib.request.urlopen(pre)
            assert p.status == 204
            assert "GET" in p.headers["Access-Control-Allow-Methods"]
            # disallowed origin: no CORS headers
            bad = urllib.request.Request(url, headers={"Origin": "https://evil.example"})
            b = urllib.request.urlopen(bad)
            assert b.headers.get("Access-Control-Allow-Origin") is None
        finally:
            d.stop()

    def test_disabled_by_default(self):
        d = self._daemon(None)
        try:
            url = (
                f"http://127.0.0.1:{d.read_port}/relation-tuples/check/openapi"
                "?namespace=files&object=doc&relation=owner&subject_id=alice"
            )
            req = urllib.request.Request(url, headers={"Origin": "https://app.example"})
            resp = urllib.request.urlopen(req)
            assert resp.headers.get("Access-Control-Allow-Origin") is None
        finally:
            d.stop()


class TestTLS:
    def test_rest_and_grpc_over_tls(self, tmp_path):
        cert = tmp_path / "cert.pem"
        key = tmp_path / "key.pem"
        subprocess.run(
            [
                "openssl", "req", "-x509", "-newkey", "rsa:2048",
                "-keyout", str(key), "-out", str(cert),
                "-days", "1", "-nodes", "-subj", "/CN=127.0.0.1",
                "-addext", "subjectAltName=IP:127.0.0.1",
            ],
            check=True, capture_output=True,
        )
        reg = Registry(_base_cfg({
            "read": {"tls": {"cert_path": str(cert), "key_path": str(key)}}
        }))
        reg.relation_tuple_manager().write_relation_tuples(
            [RelationTuple.from_string("files:doc#owner@alice")]
        )
        d = Daemon(reg)
        d.start()
        try:
            ctx = ssl.create_default_context(cafile=str(cert))
            url = (
                f"https://127.0.0.1:{d.read_port}/relation-tuples/check/openapi"
                "?namespace=files&object=doc&relation=owner&subject_id=alice"
            )
            resp = json.load(urllib.request.urlopen(url, context=ctx))
            assert resp == {"allowed": True}
            # gRPC over the same TLS port
            import grpc
            from keto_tpu.api.descriptors import pb

            creds = grpc.ssl_channel_credentials(cert.read_bytes())
            ch = grpc.secure_channel(f"127.0.0.1:{d.read_port}", creds)
            stub = ch.unary_unary(
                "/ory.keto.relation_tuples.v1alpha2.CheckService/Check",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.CheckResponse.FromString,
            )
            req = pb.CheckRequest()
            req.tuple.namespace = "files"
            req.tuple.object = "doc"
            req.tuple.relation = "owner"
            req.tuple.subject.id = "alice"
            out = stub(req, timeout=60)
            assert out.allowed is True
            ch.close()
        finally:
            d.stop()


class TestDirectGRPCListener:
    """serve.<kind>.grpc: a second, unmuxed public gRPC port (the
    high-throughput path — no preface sniff, no byte splice; measured
    ~1.5x served QPS on a 1-core host). The muxed port keeps working."""

    def test_direct_and_muxed_ports_both_serve(self):
        from keto_tpu.api import ReadClient, open_channel

        reg = Registry(_base_cfg(
            {"read": {"grpc": {"host": "127.0.0.1", "port": 0}}}
        ))
        reg.relation_tuple_manager().write_relation_tuples(
            [RelationTuple.from_string("files:doc#owner@alice")]
        )
        d = Daemon(reg)
        d.start()
        try:
            assert d.read_grpc_port not in (None, d.read_port)
            q = RelationTuple.from_string("files:doc#owner@alice")
            for port in (d.read_grpc_port, d.read_port):
                c = ReadClient(open_channel(f"127.0.0.1:{port}"))
                try:
                    assert c.check(q, timeout=30) is True
                finally:
                    c.close()
        finally:
            d.stop()

    def test_unconfigured_stays_off(self):
        reg = Registry(_base_cfg())
        d = Daemon(reg)
        d.start()
        try:
            assert d.read_grpc_port is None
            assert d.write_grpc_port is None
        finally:
            d.stop()

    def test_direct_port_inherits_tls(self, tmp_path):
        """A TLS-configured listener's direct gRPC port must serve TLS
        too — the side door never downgrades the deployment."""
        cert = tmp_path / "cert.pem"
        key = tmp_path / "key.pem"
        subprocess.run(
            [
                "openssl", "req", "-x509", "-newkey", "rsa:2048",
                "-keyout", str(key), "-out", str(cert),
                "-days", "1", "-nodes", "-subj", "/CN=127.0.0.1",
                "-addext", "subjectAltName=IP:127.0.0.1",
            ],
            check=True, capture_output=True,
        )
        reg = Registry(_base_cfg({
            "read": {
                "tls": {"cert_path": str(cert), "key_path": str(key)},
                "grpc": {"host": "127.0.0.1", "port": 0},
            }
        }))
        reg.relation_tuple_manager().write_relation_tuples(
            [RelationTuple.from_string("files:doc#owner@alice")]
        )
        d = Daemon(reg)
        d.start()
        try:
            import grpc
            from keto_tpu.api.descriptors import pb

            creds = grpc.ssl_channel_credentials(cert.read_bytes())
            ch = grpc.secure_channel(f"127.0.0.1:{d.read_grpc_port}", creds)
            stub = ch.unary_unary(
                "/ory.keto.relation_tuples.v1alpha2.CheckService/Check",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.CheckResponse.FromString,
            )
            req = pb.CheckRequest()
            req.tuple.namespace = "files"
            req.tuple.object = "doc"
            req.tuple.relation = "owner"
            req.tuple.subject.id = "alice"
            assert stub(req, timeout=60).allowed is True
            ch.close()
            # and PLAINTEXT against the TLS direct port must fail
            ch2 = grpc.insecure_channel(f"127.0.0.1:{d.read_grpc_port}")
            stub2 = ch2.unary_unary(
                "/ory.keto.relation_tuples.v1alpha2.CheckService/Check",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.CheckResponse.FromString,
            )
            with pytest.raises(grpc.RpcError):
                stub2(req, timeout=10)
            ch2.close()
        finally:
            d.stop()


class TestSubmitResolvePipeline:
    """check_batch == resolve(submit(...)); several batches can be in
    flight at once and resolve in any order (the pipelining contract
    the batcher and bench rely on)."""

    def test_overlapping_batches_resolve_correctly(self):
        from keto_tpu.engine import Membership
        from keto_tpu.engine.tpu_engine import TPUCheckEngine
        from keto_tpu.storage import MemoryManager

        cfg = Config({"limit": {"max_read_depth": 5}})
        cfg.set_namespaces([Namespace(name="files")])
        m = MemoryManager()
        m.write_relation_tuples([
            RelationTuple.from_string(f"files:doc{i}#owner@u{i}")
            for i in range(20)
        ])
        e = TPUCheckEngine(m, cfg)
        hits = [RelationTuple.from_string(f"files:doc{i}#owner@u{i}")
                for i in range(20)]
        misses = [RelationTuple.from_string(f"files:doc{i}#owner@nope")
                  for i in range(20)]
        h1 = e.check_batch_submit(hits)
        h2 = e.check_batch_submit(misses)
        h3 = e.check_batch_submit(hits[:3] + misses[:3])
        # resolve out of submission order
        r3 = e.check_batch_resolve(h3)
        r1 = e.check_batch_resolve(h1)
        r2 = e.check_batch_resolve(h2)
        assert all(r.membership == Membership.IS_MEMBER for r in r1)
        assert all(r.membership == Membership.NOT_MEMBER for r in r2)
        assert [r.membership == Membership.IS_MEMBER for r in r3] == (
            [True] * 3 + [False] * 3
        )

    def test_oversized_submit_splits_and_pipelines(self):
        from keto_tpu.engine import Membership
        from keto_tpu.engine.tpu_engine import TPUCheckEngine
        from keto_tpu.storage import MemoryManager

        cfg = Config({"limit": {"max_read_depth": 5}})
        cfg.set_namespaces([Namespace(name="files")])
        m = MemoryManager()
        m.write_relation_tuples(
            [RelationTuple.from_string("files:doc#owner@alice")]
        )
        e = TPUCheckEngine(m, cfg, frontier_cap=64)  # largest bucket = 64
        qs = [RelationTuple.from_string("files:doc#owner@alice")] * 130
        h = e.check_batch_submit(qs)
        assert h[0] == "multi" and len(h[1]) == 3
        res = e.check_batch_resolve(h)
        assert len(res) == 130
        assert all(r.membership == Membership.IS_MEMBER for r in res)


class TestPidFile:
    """Daemon pid-file lifecycle (CLI `serve --pid-file`): written with
    the live pid on start, removed LAST on clean stop — a pid file
    outliving a clean shutdown lies to supervisors (kill -0 can succeed
    against a recycled pid)."""

    def test_written_on_start_removed_on_stop(self, tmp_path):
        import os

        from keto_tpu.api.daemon import Daemon

        cfg = Config({
            "dsn": "memory",
            "serve": {
                "read": {"host": "127.0.0.1", "port": 0},
                "write": {"host": "127.0.0.1", "port": 0},
                "metrics": {"host": "127.0.0.1", "port": 0},
            },
        })
        cfg.set_namespaces([Namespace(name="files")])
        pid_file = str(tmp_path / "serve.pid")
        daemon = Daemon(Registry(cfg), pid_file=pid_file)
        daemon.start()
        try:
            assert os.path.exists(pid_file)
            with open(pid_file) as f:
                assert int(f.read()) == os.getpid()
        finally:
            daemon.stop(grace=1.0)
        assert not os.path.exists(pid_file)

    def test_unconfigured_daemon_writes_nothing(self, tmp_path):
        from keto_tpu.api.daemon import Daemon

        cfg = Config({
            "dsn": "memory",
            "serve": {
                "read": {"host": "127.0.0.1", "port": 0},
                "write": {"host": "127.0.0.1", "port": 0},
                "metrics": {"host": "127.0.0.1", "port": 0},
            },
        })
        cfg.set_namespaces([Namespace(name="files")])
        daemon = Daemon(Registry(cfg))
        assert daemon.pid_file is None
        daemon.start()
        daemon.stop(grace=1.0)  # no pid file, no error


class TestDrainShutdown:
    """Drain-aware daemon.stop (resilience plane): readiness flips off
    first, new admissions are shed with a typed OverloadedError during
    the grace window, and in-flight checks complete before the
    listeners close."""

    def test_drain_rejects_new_admissions_and_finishes_inflight(self):
        import json
        import threading
        import time
        import urllib.error
        import urllib.request

        from keto_tpu import faults

        cfg = Config({
            "dsn": "memory",
            # cache off so the in-flight check really occupies the
            # batcher pipeline for the stall duration
            "check": {"engine": "tpu", "cache": {"enabled": False}},
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
        # warm the engine so the XLA compile isn't inside the stall window
        reg.check_engine().check_batch(
            [RelationTuple.from_string("files:doc#owner@alice")]
        )
        d = Daemon(reg)
        d.start()
        base = f"http://127.0.0.1:{d.read_port}"
        url = (
            base + "/relation-tuples/check/openapi"
            "?namespace=files&object=doc&relation=owner&subject_id=alice"
        )
        stopper = None
        try:
            faults.set_fault("device_launch", stall_s=0.8)
            inflight = {}

            def bg():
                try:
                    with urllib.request.urlopen(url, timeout=30) as r:
                        inflight["resp"] = (r.status, json.load(r))
                except Exception as e:  # noqa: BLE001 — recorded for assert
                    inflight["resp"] = ("error", repr(e))

            th = threading.Thread(target=bg, daemon=True)
            th.start()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and d.batcher._pending < 1:
                time.sleep(0.005)
            assert d.batcher._pending >= 1  # the in-flight check is admitted

            stopper = threading.Thread(target=d.stop, daemon=True)
            stopper.start()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and not reg.draining.is_set():
                time.sleep(0.002)
            assert reg.draining.is_set()

            # during the grace window (listeners still up, batcher busy):
            # readiness is already off...
            try:
                urllib.request.urlopen(base + "/health/ready", timeout=5)
                ready_code = 200
            except urllib.error.HTTPError as e:
                ready_code = e.code
            assert ready_code == 503
            # ...and a new check is shed with the typed 429, not queued
            try:
                urllib.request.urlopen(url, timeout=5)
                shed = None
            except urllib.error.HTTPError as e:
                shed = (e.code, json.load(e))
            assert shed is not None
            assert shed[0] == 429
            assert shed[1]["error"]["status"] == "too_many_requests"
            assert "draining" in shed[1]["error"]["message"]

            # the in-flight check completes with the correct answer —
            # admitted-before-drain work never sees a torn-down pipeline
            th.join(timeout=30)
            assert inflight["resp"] == (200, {"allowed": True})
            stopper.join(timeout=30)
            assert not stopper.is_alive()
        finally:
            faults.clear()
            if stopper is None:
                d.stop()
            elif stopper.is_alive():
                stopper.join(timeout=30)


class TestPlatformPin:
    def test_check_platform_updates_jax_config(self):
        import jax

        from keto_tpu.config import Config
        from keto_tpu.registry import Registry

        # a value DISTINCT from the conftest ambient ('cpu'), otherwise
        # the assertion would pass with the pin code deleted; jax accepts
        # arbitrary platform strings at the config level
        before = jax.config.jax_platforms
        try:
            Registry(Config({"check": {"platform": "cpu,tpu_fake"}}))
            assert jax.config.jax_platforms == "cpu,tpu_fake"
        finally:
            jax.config.update("jax_platforms", before)

    def test_unset_leaves_environment_default(self):
        import jax

        from keto_tpu.config import Config
        from keto_tpu.registry import Registry

        before = jax.config.jax_platforms
        Registry(Config({}))
        assert jax.config.jax_platforms == before
