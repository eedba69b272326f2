"""The chip's compiler, asked from a machine that has no chip.

Every device program `chip_smoke.py` launches is compiled here for a
described (not attached) TPU v5e, at the smoke's own shapes: the store is
the smoke's 1,000,000-tuple drive store, built on the CPU, and each
kernel's arguments and statics are captured where the engine passes them.
What the TPU compiler would refuse on the chip — a program that does not
fit its memory above all — it refuses here, at no chip time.

The package builds one program on every backend (ketolint's `one-program`
rule), so what is traced here on the CPU is what the chip traces. Nothing
runs on a device: a compile that passes is not a chip run.

This is the only file that describes a topology, and it does so inside a
fixture: see /opt/skills/guides/on-chip-measurement section 2 for why no
import, `skipif`, `parametrize` or conftest hook may.
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest
from jax.experimental.layout import Format
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import chip_smoke  # also puts tools/ on sys.path
import tpu_test_tier
from keto_tpu.engine import (
    closure_kernel,
    closure_power,
    expand_kernel,
    filter_kernel,
    kernel,
    reverse_kernel,
)
from keto_tpu.engine.tpu_engine import TPUCheckEngine
from keto_tpu.ketoapi import RelationTuple, SubjectSet
from keto_tpu.parallel import default_mesh
from keto_tpu.parallel import expand as parallel_expand
from keto_tpu.parallel import kernel as parallel_kernel
from keto_tpu.storage.columnar import ColumnarStore

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]), ("x",))


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def drive():
    return chip_smoke.build_drive(chip_smoke.DEFAULT_SEED, chip_smoke.DEFAULT_TUPLES)


@pytest.fixture(scope="module")
def store(drive):
    store = ColumnarStore()
    store.bulk_load(drive.cols)
    return store


@pytest.fixture(scope="module")
def engine(store):
    return TPUCheckEngine(store, chip_smoke.drive_config(serve=False))


class _Launch(Exception):
    """Ends an engine call at the kernel it was about to launch."""


def capture_launch(module, name: str, call) -> tuple[tuple, dict]:
    """(args, kwargs) of the first call of `module.name` under `call()`:
    what the engine hands the kernel at its call site. The kernel itself
    does not run (where the engine catches the abort and answers from the
    host, as the closure builder does, the call simply returns)."""
    launches = []

    def record(*args, **kwargs):
        launches.append((args, kwargs))
        raise _Launch(name)

    mp = pytest.MonkeyPatch()
    mp.setattr(module, name, record)
    try:
        call()
    except _Launch:
        pass
    finally:
        mp.undo()
    assert launches, f"{name} was never launched"
    return launches[0]


def described(tree, sharding_of):
    """Shapes in place of arrays: a described device holds no array. What
    the engine placed itself (a committed array: `kernel.device_table`)
    is described lying as that placed it, bucket rows row-major."""

    def shape_of(a):
        sharding = sharding_of(a)
        layout = kernel.bucket_row_layout(np.shape(a), a.dtype)
        if layout is not None and getattr(a, "committed", False):
            sharding = Format(layout, sharding)
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sharding)

    return jax.tree.map(shape_of, tree)


def compile_for_chip(name, jitted, args, statics, sharding_of):
    compiled = jitted.lower(*described(args, sharding_of), **statics).compile()
    memory = compiled.memory_analysis()
    print(
        f"\n{name}: "
        f"argument={memory.argument_size_in_bytes} "
        f"temp={memory.temp_size_in_bytes} "
        f"output={memory.output_size_in_bytes} "
        f"code={memory.generated_code_size_in_bytes}"
    )
    return compiled, memory


def assert_no_table_relayout(memory):
    """A copy of a probe table inside the program shows as `temp` the size
    of the tables (9.14 GB a check launch, 4.57 GB an expand or a
    list-subjects, before the tables were stored as bucket rows)."""
    assert memory.temp_size_in_bytes < 1024**3
    assert memory.temp_size_in_bytes < memory.argument_size_in_bytes


def assert_cause_scatters_gated(hlo: str, B: int):
    """A step scatters its host-replay causes into the [B] codes only in a
    conditional of its own (kernel.raise_causes), nested in the loop's
    step, and builds no [F * S] copy of its queries (`jnp.repeat`'s
    `_take`) to do so: one cond in a step's op_name is the step itself."""
    step = "while/body/closed_call/cond/"
    scatters = re.findall(
        rf'= s32\[{B}\]\S* scatter\(.*op_name="([^"]*)"', hlo
    )
    in_step = [name for name in scatters if step in name]
    assert in_step and all(
        name.split(step, 1)[1].count("cond/") >= 1 for name in in_step
    ), in_step
    assert "jit(_take)" not in hlo


def smoke_batch(drive):
    queries, _ = chip_smoke.view_queries(drive, np.random.default_rng(1), 2048)
    return queries


def test_check_kernel_fits_the_chip(no_compile_cache, one_chip, drive, engine):
    """The smoke's largest check launch, its 2,048-item BatchCheck (bucket
    2,048, frontier 4,096: two slots a query slot, the rung a bucket starts
    at and the drive store's two children a query keep it at), with tables
    and working set inside one v5e's 16 GiB. dh_pack and rh_pack are
    stored as the 64-lane bucket rows the kernel gathers, so no launch
    relays them out: `temp` holds the frontier's working set and does not
    follow the tables' rows (ROADMAP S3)."""
    tables_and_queries, statics = capture_launch(
        kernel, "check_kernel_packed", lambda: engine.check_batch(smoke_batch(drive))
    )
    tables, qpack = tables_and_queries
    assert tables["dh_pack"].shape == (1 << 20, 64)
    assert tables["rh_pack"].shape == (1 << 19, 64)
    assert qpack.shape == (7, 2048) and statics["frontier_cap"] == 4096
    compiled, memory = compile_for_chip(
        "check_kernel_packed", kernel.check_kernel_packed, tables_and_queries,
        statics, lambda a: one_chip,
    )
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"argument + temp = {total} of {V5E_HBM_BYTES}")
    assert total < V5E_HBM_BYTES
    assert_no_table_relayout(memory)
    assert_cause_scatters_gated(compiled.as_text(), 2048)


# drive-chip-share's store: the 4,000,000-tuple drive store's tables where
# they differ from the 1e6 store's, and the probe depths a builder's run
# read there (PR 35; a seed may draw a probe more or less)
CHIP_SHARE_SHAPES = {
    "dh_pack": (4194304, 64), "rh_pack": (2097152, 64),
    "objslot_ns": (3988480,), "e_pack": (3938717, 2),
}
CHIP_SHARE_PROBES = {"dh_probes": 12, "rh_probes": 13}


def test_check_kernel_fits_the_chip_at_the_chip_share(
    no_compile_cache, one_chip, drive, engine
):
    """The same launch (bucket 2,048, frontier 4,096) over the tables of
    `drive-chip-share`, from their shapes alone: no store of that size is
    built here. 3.27 GB of arguments, and a working set that does not
    follow the tables' rows."""
    (tables, qpack), statics = capture_launch(
        kernel, "check_kernel_packed", lambda: engine.check_batch(smoke_batch(drive))
    )
    assert set(CHIP_SHARE_SHAPES) <= set(tables)
    resized = {
        k: jax.ShapeDtypeStruct(CHIP_SHARE_SHAPES.get(k, a.shape), a.dtype)
        for k, a in tables.items()
    }

    def place(a):
        # described() lays a committed array as device_table placed it; a
        # bare shape is laid as device_table would place it
        layout = kernel.bucket_row_layout(a.shape, a.dtype)
        sharding = one_chip if layout is None else Format(layout, one_chip)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    args = (jax.tree.map(place, resized), place(qpack))
    compiled = kernel.check_kernel_packed.lower(
        *args, **{**statics, **CHIP_SHARE_PROBES}
    ).compile()
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"\nchip share: argument={memory.argument_size_in_bytes} "
          f"temp={memory.temp_size_in_bytes}")
    assert 3 * 1024**3 < memory.argument_size_in_bytes
    assert total < 4 * 10**9
    assert_no_table_relayout(memory)


@pytest.fixture(scope="module")
def rewrites_engine():
    """`rewrites-4e6`'s namespaces and construction at 20,000 tuples:
    Keto's AND and AND NOT rewrites, so the check launch compiles its
    island variant. The variant's working set follows the launch's
    bucket, frontier and island cap, not the tables' rows."""
    import os
    import sys

    from keto_tpu.config import Config
    from keto_tpu.registry import Registry

    bench = os.path.join(os.path.dirname(chip_smoke.__file__), "benchmarks")
    sys.path.insert(0, bench)
    try:
        import workload
    finally:
        sys.path.remove(bench)
    config = workload.read_json(os.path.join(bench, "configs", "rewrites-4e6.json"))
    traffic = workload.read_json(os.path.join(bench, "traffic", "batch_checks.json"))
    wl = workload.Workload(config, traffic, 11, 20_000)
    registry = Registry(Config(config["serve_config"]))
    registry.relation_tuple_manager().bulk_load(wl.columns())
    return registry.check_engine(), wl.request(0)[0]


def test_island_check_kernel_compiles_for_the_chip(
    no_compile_cache, one_chip, rewrites_engine
):
    """The island variant of the check launch at the cell's 2,048-item
    BatchCheck: bucket 2,048, frontier 4,096, 4,096 island instances of
    K = 2 leaves, so 10,240 ctx accumulators."""
    engine, queries = rewrites_engine
    args, statics = capture_launch(
        kernel, "check_kernel_packed", lambda: engine.check_batch(queries)
    )
    assert args[1].shape == (7, 2048) and statics["frontier_cap"] == 4096
    assert statics["n_island_cap"] == 4096 and statics["K"] == 2
    _, memory = compile_for_chip(
        "check_kernel_packed[islands]", kernel.check_kernel_packed, args,
        statics, lambda a: one_chip,
    )
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < V5E_HBM_BYTES
    assert memory.temp_size_in_bytes < 1024**3


def test_a_table_is_filled_in_place_on_the_chip(no_compile_cache, one_chip):
    """`kernel.device_table`'s step at `drive-chip-share`'s largest table:
    the donated table comes back as the output (`alias` is the table), and
    beside it the program holds no more than the step's rows."""
    shape = CHIP_SHARE_SHAPES["dh_pack"]
    fmt = Format(kernel.bucket_row_layout(shape, np.int32), one_chip)
    rows = (kernel.UPLOAD_ROWS, shape[1])
    compiled = kernel._row_writer(fmt).lower(
        jax.ShapeDtypeStruct(shape, np.int32, sharding=fmt),
        jax.ShapeDtypeStruct(rows, np.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), np.int32, sharding=one_chip),
    ).compile()
    memory = compiled.memory_analysis()
    table = kernel.tiled_nbytes(shape, np.int32)
    assert memory.alias_size_in_bytes == memory.output_size_in_bytes == table
    assert memory.temp_size_in_bytes <= kernel.tiled_nbytes(rows, np.int32)
    assert compiled.output_formats.layout.major_to_minor == (0, 1)


@pytest.fixture(scope="module")
def closure_engine():
    """The differential tier's closure set (tools/tpu_test_tier.py): a
    32-deep chain behind the Leopard index, device powering on. The index
    is off by default, so the served smoke never launches these two
    kernels; its phase 5 does, at these shapes."""
    namespaces, tuples, _ = tpu_test_tier.deep_chain(32)
    return tpu_test_tier.engine_for(
        namespaces, tuples, max_depth=64,
        closure={"enabled": True, "powering": "device"},
    )


def _expand(engine, drive):
    engine.expand(SubjectSet("rbac", "role1", "member"), chip_smoke.EXPAND_DEPTH)


def _list_objects(engine, drive):
    engine.list_objects("videos", "view", str(drive.owners[0]))


def _list_subjects(engine, drive):
    engine.list_subjects("videos", f"{drive.f_names[0]}/v1", "view")


def _filter(engine, drive):
    name = str(drive.f_names[0])
    candidates = [name] + [f"{name}/v{j}" for j in range(drive.files_per)]
    engine.filter_objects("videos", "view", str(drive.owners[0]), candidates)


def _closure_powering(engine, drive):
    engine.closure_ensure_built()


def _closure_probe(engine, drive):
    assert engine.closure_ensure_built()
    engine.check_batch([RelationTuple.from_string("deep:f0#viewer@alice")], 64)


@pytest.mark.parametrize(
    "engine_fixture, module, name, call",
    [
        ("engine", expand_kernel, "expand_kernel_packed", _expand),
        ("engine", reverse_kernel, "list_objects_kernel_packed", _list_objects),
        ("engine", reverse_kernel, "list_subjects_kernel_packed", _list_subjects),
        ("engine", filter_kernel, "filter_kernel_packed", _filter),
        ("closure_engine", closure_power, "closure_power_wave", _closure_powering),
        ("closure_engine", closure_kernel, "closure_kernel_packed", _closure_probe),
    ],
    ids=["expand", "list_objects", "list_subjects", "filter",
         "closure_powering", "closure_probe"],
)
def test_kernel_compiles_for_the_chip(
    request, no_compile_cache, one_chip, drive, engine_fixture, module, name, call
):
    """Every other kernel of the smoke, as its path launches it for one
    request: the verbs on the 1e6 store, the closure pair on the tier's."""
    engine = request.getfixturevalue(engine_fixture)
    args, statics = capture_launch(module, name, lambda: call(engine, drive))
    _, memory = compile_for_chip(
        name, getattr(module, name), args, statics, lambda a: one_chip
    )
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < V5E_HBM_BYTES
    if name in ("expand_kernel_packed", "list_subjects_kernel_packed"):
        assert_no_table_relayout(memory)


@pytest.fixture(scope="module")
def sharded_engine(store):
    return TPUCheckEngine(
        store, chip_smoke.drive_config(serve=False), mesh=default_mesh(4)
    )


def _sharded_check(engine, drive):
    engine.check_batch(smoke_batch(drive))


def _sharded_expand(engine, drive):
    roles = [SubjectSet("rbac", f"role{r}", "member") for r in range(16)]
    engine.expand_batch(roles, chip_smoke.EXPAND_DEPTH)


@pytest.mark.parametrize(
    "module, name, get_kernel, statics_of, call",
    [
        (
            parallel_kernel, "sharded_check_kernel",
            parallel_kernel.get_sharded_kernel,
            lambda kw: kw["statics"], _sharded_check,
        ),
        (
            parallel_expand, "sharded_expand_kernel",
            parallel_expand.get_sharded_expand_kernel,
            lambda kw: (kw["fh_probes"], kw["max_steps"], kw["frontier_cap"],
                        kw["edge_cap"]),
            _sharded_expand,
        ),
    ],
    ids=["check", "expand"],
)
def test_sharded_kernel_compiles_for_four_chips(
    no_compile_cache, four_chips, drive, sharded_engine,
    module, name, get_kernel, statics_of, call,
):
    """The shard_map kernels of `chip_smoke.py --mesh4` on the described
    2x2 host: the sharded tables split over the mesh axis, everything else
    replicated (the check's one packed [7, B] query array among it), and
    collectives present in what the compiler emits."""
    args, kwargs = capture_launch(module, name, lambda: call(sharded_engine, drive))
    _, sharded_tables, replicated_tables, *queries = args
    assert all(v.shape[0] == 4 for v in sharded_tables.values())
    if name == "sharded_check_kernel":
        (qpack,) = queries
        assert qpack.shape == (7, 2048) and qpack.dtype == np.int32

    def sharding_of(a):
        split = any(a is v for v in sharded_tables.values())
        spec = P("x", *([None] * (np.ndim(a) - 1))) if split else P()
        return NamedSharding(four_chips, spec)

    compiled, memory = compile_for_chip(
        name, get_kernel(four_chips, statics_of(kwargs)),
        (sharded_tables, replicated_tables, *queries), {}, sharding_of,
    )
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < V5E_HBM_BYTES
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "all-gather" in hlo


# drive-mesh4-4e6's store over four shards: a quarter of the 4,000,000-tuple
# drive store a chip, every shard at the capacity of the 1e6 store's tables
# (snapshot.table_capacity of 1,000,000 edges), the replicated object map of
# the whole store, and a quarter of its 3,938,717 subject-set edges with
# room for the hash's unevenness between shards
MESH4_SHARD_SHAPES = {
    "dh_pack": (4, 1 << 20, 64), "rh_pack": (4, 1 << 19, 64),
    "e_pack": (4, 3938717 // 4 + 2048, 2),
}
MESH4_REPLICATED_SHAPES = {"objslot_ns": CHIP_SHARE_SHAPES["objslot_ns"]}


def test_sharded_check_program_fits_four_chips_at_the_cell(
    no_compile_cache, four_chips, drive, engine, sharded_engine
):
    """The sharded check program of `drive-mesh4-4e6.batch2048-c4` on the
    described 2x2 host: bucket 2,048, frontier 8,192 (the mesh keeps four
    slots a query slot), the cell's tables from their shapes alone, with
    the probe depths of a one-device store of a shard's size (the 1e6
    store's). Argument + temp fit one v5e a device, and the program's
    name in a trace's `XLA Modules` line holds `check`, which the
    benchmark's check_program_ms matches."""
    args, kwargs = capture_launch(
        parallel_kernel, "sharded_check_kernel",
        lambda: sharded_engine.check_batch(smoke_batch(drive)),
    )
    _, sharded_tables, replicated_tables, qpack = args
    assert qpack.shape == (7, 2048)
    _, one_device = capture_launch(
        kernel, "check_kernel_packed", lambda: engine.check_batch(smoke_batch(drive))
    )
    statics = dict(zip(kernel._KERNEL_STATICS, kwargs["statics"]))
    assert statics["frontier_cap"] == 8192
    statics.update(
        dh_probes=one_device["dh_probes"], rh_probes=one_device["rh_probes"]
    )

    def place(shape, dtype, spec):
        sharding = NamedSharding(four_chips, spec)
        layout = kernel.bucket_row_layout(shape, dtype)
        if layout is not None:
            sharding = Format(layout, sharding)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    split = {
        k: place(MESH4_SHARD_SHAPES[k], v.dtype, P("x", *([None] * (v.ndim - 1))))
        for k, v in sharded_tables.items()
    }
    replicated = {
        k: place(MESH4_REPLICATED_SHAPES.get(k, v.shape), v.dtype, P())
        for k, v in replicated_tables.items()
    }
    described_qpack = place(qpack.shape, qpack.dtype, P())
    program = parallel_kernel.get_sharded_kernel(
        four_chips, tuple(statics[k] for k in kernel._KERNEL_STATICS)
    )
    compiled = program.lower(split, replicated, described_qpack).compile()
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    hlo = compiled.as_text()
    module = re.match(r"HloModule (\S+?),", hlo).group(1)
    print(f"\nsharded check at the cell: module={module} "
          f"argument={memory.argument_size_in_bytes} "
          f"temp={memory.temp_size_in_bytes} (a device)")
    assert "check" in module
    assert 512 * 1024**2 < memory.argument_size_in_bytes
    assert total < V5E_HBM_BYTES
    assert_no_table_relayout(memory)
    assert "all-gather" in hlo and "all-reduce" in hlo
