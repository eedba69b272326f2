"""The sharded engine on the served path: the config key that builds the
mesh, a daemon that serves gRPC BatchChecks from it, and what a sharded
launch records. On four of the suite's eight CPU devices (conftest.py),
at `drive-mesh4-4e6`'s construction cut to 20,000 tuples."""

import os
import sys
import threading

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from keto_tpu.api.client import ReadClient, open_channel
from keto_tpu.api.daemon import Daemon
from keto_tpu.config import Config, ConfigError
from keto_tpu.engine.kernel import (
    _KERNEL_STATICS,
    N_LAUNCH_STATS,
    estimate_step_gather_bytes,
    launch_stats_dict,
    unpack_results,
)
from keto_tpu.engine.tpu_engine import _tables_devices
from keto_tpu.observability import CHECK_STAGES
from keto_tpu.parallel.kernel import sharded_static_config
from keto_tpu.registry import Registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
TUPLES = 20_000
SEED = 3000004401


@pytest.fixture(scope="module")
def bench_modules():
    sys.path.insert(0, BENCH)
    try:
        import collectives
        import workload

        yield workload, collectives
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def cell(bench_modules):
    workload, _ = bench_modules
    config = workload.read_json(
        os.path.join(BENCH, "configs", "drive-mesh4-4e6.json")
    )
    traffic = workload.read_json(
        os.path.join(BENCH, "traffic", "batch_checks.json")
    )
    wl = workload.Workload(config, traffic, SEED, TUPLES)
    return config, wl, wl.columns()


def serve(serve_config: dict, cols):
    registry = Registry(Config(serve_config))
    registry.relation_tuple_manager().bulk_load(cols)
    daemon = Daemon(registry)
    daemon.start()
    return daemon, registry


@pytest.fixture(scope="module")
def daemons(cell):
    """(mesh daemon, its registry, one-device daemon): the configuration's
    daemon as served, and the same without `check.mesh_devices`."""
    config, _, cols = cell
    mesh_daemon, registry = serve(config["serve_config"], cols)
    one = {k: v for k, v in config["serve_config"].items() if k != "check"}
    one_daemon, _ = serve(one, cols)
    yield mesh_daemon, registry, one_daemon
    mesh_daemon.stop()
    one_daemon.stop()


def batch_check(daemon, queries) -> list[bool]:
    client = ReadClient(open_channel(f"127.0.0.1:{daemon.read_port}"))
    try:
        out = client.check_batch(queries, timeout=120)
    finally:
        client.close()
    assert not [e for _, e in out if e]
    return [allowed for allowed, _ in out]


def test_the_config_takes_a_device_count():
    assert Config({"check": {"mesh_devices": 4}}).get("check.mesh_devices") == 4
    for bad in (0, -1, 2.5, "4"):
        with pytest.raises(ConfigError, match="mesh_devices"):
            Config({"check": {"mesh_devices": bad}})


def test_the_registry_builds_the_mesh_from_the_key():
    engine = Registry(Config({"check": {"mesh_devices": 4}})).check_engine()
    assert engine.mesh is not None and engine.mesh.devices.size == 4
    assert Registry(Config({"check": {"mesh_devices": 1}})).check_engine().mesh is None
    assert Registry(Config({})).check_engine().mesh is None


def test_more_devices_than_the_process_sees_is_refused():
    with pytest.raises(ConfigError, match="sees 8 device"):
        Registry(Config({"check": {"mesh_devices": 16}})).check_engine()


def test_the_mesh_daemon_holds_every_tuple_on_four_devices(daemons):
    _, registry, _ = daemons
    engine = registry.check_engine()
    state = engine._ensure_state()
    assert state.snapshot.n_tuples == TUPLES
    sharded, _ = state.tables
    assert all(
        len(table.devices()) == 4 and table.shape[0] == 4
        for table in sharded.values()
    )
    # the device-memory gauges sum memory_stats() over these
    assert len(_tables_devices(state.tables)) == 4
    assert registry.metrics().mesh_devices._value.get() == 4


@pytest.mark.parametrize("rpc", [0, 1])
def test_batch_checks_equal_one_device_the_reference_and_the_truth(
    daemons, cell, rpc
):
    """Item by item, over gRPC: the mesh daemon, the one-device daemon,
    engine/reference.py and the construction's truth."""
    mesh_daemon, registry, one_daemon = daemons
    _, wl, _ = cell
    queries, truth, _ = wl.request(rpc)
    on_mesh = batch_check(mesh_daemon, queries)
    assert on_mesh == batch_check(one_daemon, queries)
    assert on_mesh == truth.tolist()
    reference = registry.check_engine().reference
    rows = np.random.default_rng(rpc).choice(len(queries), 64, replace=False)
    assert [on_mesh[i] for i in rows] == [
        reference.check_relation_tuple(queries[i], 0).allowed for i in rows
    ]
    assert not registry.metrics().host_fallback_total.collect()[0].samples


def sample(metrics, name: str, labels: dict | None = None) -> float:
    want = (labels or {}).items()
    return sum(
        s.value
        for family in metrics.registry.collect()
        for s in family.samples
        if s.name == name and want <= s.labels.items()
    )


def test_a_mesh_launch_records_what_one_device_records(daemons, cell):
    mesh_daemon, registry, _ = daemons
    _, wl, _ = cell
    metrics = registry.metrics()
    names = (
        "keto_tpu_mesh_launches_total",
        "keto_tpu_mesh_place_seconds_count",
        "keto_tpu_launch_device_seconds_count",
        "keto_tpu_launch_iterations_count",
        "keto_tpu_launch_frontier_slots_total",
        "keto_tpu_launch_bucket_slots_total",
        "keto_tpu_launch_demand_peak_count",
        "keto_tpu_checks_total",
    )
    stages = ("assemble", "dispatch", "device_wait", "resolve", "respond")
    assert set(stages) <= set(CHECK_STAGES)
    stage_count = "keto_tpu_check_stage_duration_seconds_count"
    before = {n: sample(metrics, n) for n in names}
    before_stages = {s: sample(metrics, stage_count, {"stage": s}) for s in stages}
    batch_check(mesh_daemon, wl.request(2)[0])
    grew = {n: sample(metrics, n) - before[n] for n in names}
    assert grew == {
        "keto_tpu_mesh_launches_total": 1,
        "keto_tpu_mesh_place_seconds_count": 1,
        "keto_tpu_launch_device_seconds_count": 1,
        "keto_tpu_launch_iterations_count": 1,
        "keto_tpu_launch_frontier_slots_total": 8192,
        "keto_tpu_launch_bucket_slots_total": 2048,
        # the sharded program reports no demand, so none is observed
        "keto_tpu_launch_demand_peak_count": 0,
        "keto_tpu_checks_total": 2048,
    }
    for s in stages:
        assert sample(metrics, stage_count, {"stage": s}) - before_stages[s] == 1, s
    assert sample(metrics, "keto_tpu_mesh_place_seconds_sum") > 0
    entry = mesh_daemon.registry.flight_recorder().entries()[-1]
    assert entry["frontier_cap"] == 8192 and entry["n"] == 2048
    assert {"assemble", "dispatch", "device_wait", "resolve"} <= set(entry["stage_ms"])


def test_a_mesh_launch_counts_one_chips_gathered_bytes(daemons, cell):
    """Each shard runs a step's whole gather set over its own tables, side
    by side: a chip's bytes are the one-device estimate at the launch's
    statics, not that times the shards."""
    _, registry, _ = daemons
    _, wl, _ = cell
    engine = registry.check_engine()
    state = engine._ensure_state()
    handle = engine.check_batch_submit(wl.request(3)[0], 0)
    _, outputs, meta = handle
    *_, stats = unpack_results(
        np.asarray(outputs), meta["B"], meta["island_cap"], state.snapshot.K
    )
    steps = int(launch_stats_dict(stats)["steps"])
    engine.check_batch_resolve(handle)
    statics = sharded_static_config(
        state.sharded, engine.config.max_read_depth(), 8192,
        has_delta=state.has_delta,
    )
    one_device = estimate_step_gather_bytes(dict(zip(_KERNEL_STATICS, statics)))
    assert meta["gather_step_bytes"] == one_device > 0
    entry = registry.flight_recorder().entries()[-1]
    assert entry["gather_bytes_est"] == steps * one_device


def test_a_mesh_launch_puts_one_packed_array_and_reads_one_vector(
    daemons, cell, monkeypatch
):
    """One `jax.device_put` a launch, of the one [7, B] int32 query array,
    replicated over the mesh; the handle's outputs are one replicated
    int32 vector in `check_kernel_packed`'s layout, not six arrays."""
    _, registry, _ = daemons
    _, wl, _ = cell
    engine = registry.check_engine()
    state = engine._ensure_state()
    puts = []
    launching = threading.get_ident()
    real_put = jax.device_put

    def put(x, *args, **kwargs):
        if threading.get_ident() == launching:
            puts.append((x, args[0] if args else kwargs.get("device")))
        return real_put(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", put)
    handle = engine.check_batch_submit(wl.request(4)[0], 0)
    monkeypatch.undo()
    engine.check_batch_resolve(handle)
    assert len(puts) == 1
    (qpack, sharding), = puts
    assert isinstance(qpack, np.ndarray)
    assert qpack.shape == (7, 2048) and qpack.dtype == np.int32
    assert sharding.mesh == engine.mesh and sharding.spec == P()
    _, outputs, meta = handle
    assert isinstance(outputs, jax.Array) and outputs.dtype == np.int32
    assert outputs.sharding.is_fully_replicated and len(outputs.devices()) == 4
    island_cap = meta["island_cap"]
    assert outputs.shape == (
        1 + 2048 + island_cap * state.snapshot.K + 2048
        + 2 * max(island_cap, 1) + N_LAUNCH_STATS,
    )


def test_the_demand_rule_records_nothing_on_a_mesh_launch(
    daemons, cell, monkeypatch
):
    """The frontier's demand rule and its retry are one device's: a mesh
    launch notes no demand and retries nothing, where the one-device
    engine's same launch notes one."""
    _, registry, one_daemon = daemons
    _, wl, _ = cell
    queries = wl.request(5)[0]
    engine = registry.check_engine()
    noted = []
    monkeypatch.setattr(engine, "_note_demand", lambda B, d: noted.append(B))
    monkeypatch.setattr(
        engine, "_frontier_retry",
        lambda *_: pytest.fail("a frontier retry on a mesh launch"),
    )
    engine.check_batch(queries)
    assert noted == [] and engine._demand == {}
    one_device = one_daemon.registry.check_engine()
    one_device.check_batch(queries)
    assert 2048 in one_device._demand


def test_the_collective_bytes_are_counted_by_hand(bench_modules):
    """A step on four chips at F 8,192, B 2,048: the all-gather brings three
    peers' five int32 fields and one bool field of 8,192 each; the psum of
    [8,192] int32 and the pmax of [2,048] int32 each bring 2 x 3/4 of the
    vector."""
    _, collectives = bench_modules
    gathered = 3 * 8192 * (5 * 4 + 1)
    reduced = 2 * 0.75 * (8192 * 4) + 2 * 0.75 * (2048 * 4)
    assert collectives.step_bytes(8192, 2048, 4) == gathered + reduced == 577_536
    assert collectives.launch_bytes(3, 8192, 2048, 4) == 3 * 577_536
    assert collectives.launch_bytes(3, 8192, 2048, 1) == 0.0
