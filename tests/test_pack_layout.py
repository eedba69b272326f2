"""The stored shape of a hash-probed table (kernel.as_bucket_rows).

Every `*_pack` a kernel probes is built, uploaded and kept as the bucket
rows `_bucket_rows` gathers: `[cap / spb, spb * w]`, with
`spb = snapshot.slots_per_bucket(n_key_cols)`. These cases hold the three
builders every such table goes through to that shape, and hold the
device's probe to the host's: the buckets `_bucket_rows` fetches are the
slots `snapshot.probe_slot` walks, so the kernel's probes find exactly
what `compact.py`'s host probe finds. The `_overlay` tables are the delta
overlay's (engine/delta.py): the fixed 4n capacity with no load boost, one
bucket loaded past its slots, so that a chain crosses into a second
gathered bucket row.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from keto_tpu.engine import compact, kernel, snapshot
from keto_tpu.engine.snapshot import EMPTY

N_KEYS = 300  # present keys; as many absent ones are probed beside them
OVERLAY_CAPACITY = snapshot.hash_table_capacity(N_KEYS)  # the fixed 4n shape


class Table:
    """One built table: its columns, and the pack its builder made."""

    def __init__(self, builder: str, rng: np.random.Generator):
        self.overlay = builder.endswith("_overlay")
        self.builder = builder.removesuffix("_overlay")
        self.n_key_cols = 5 if self.builder == "edge" else 2
        self.width = 8 if self.builder == "edge" else 4
        self.spb = snapshot.slots_per_bucket(self.n_key_cols)
        # distinct keys: the first half goes into the table, the rest stays out
        drawn = np.unique(
            rng.integers(0, 1 << 20, size=(4 * N_KEYS, self.n_key_cols)), axis=0
        )
        drawn = drawn[rng.permutation(len(drawn))][: 2 * N_KEYS].astype(np.int32)
        self.present, self.absent = drawn[:N_KEYS], drawn[N_KEYS:]
        if self.overlay:
            self.crowd_one_bucket(rng)
        self.values = np.arange(N_KEYS, dtype=np.int32)
        fixed = {"min_capacity": OVERLAY_CAPACITY, "boost_load": False}
        *self.cols, self.probes = snapshot._build_hash_table(
            tuple(self.present[:, i] for i in range(self.n_key_cols)), self.values,
            **(fixed if self.overlay else {}),
        )
        self.cap = len(self.cols[0])
        if self.builder == "edge":
            self.pack = kernel.pack_edge_table(*self.cols)
            self.slots = self.cols
        elif self.builder == "pair":
            self.pack = kernel.pack_pair_table(*self.cols)
            self.slots = self.cols
        else:  # the value is a CSR row; its span rides the two value lanes
            self.row_ptr = np.concatenate(
                [[0], np.cumsum(rng.integers(1, 9, size=N_KEYS))]
            ).astype(np.int32)
            self.pack = kernel.pack_rh_span_table(*self.cols, self.row_ptr)
            row = self.cols[2]
            held = row != EMPTY
            self.slots = [
                self.cols[0], self.cols[1],
                np.where(held, self.row_ptr[np.clip(row, 0, None)], EMPTY),
                np.where(held, self.row_ptr[np.clip(row, 0, None) + 1], EMPTY),
            ]

    def crowd_one_bucket(self, rng: np.random.Generator):
        """Swap in keys that start in one bucket, more than it has slots
        (and as many absent ones that start there too): the builder
        spills them into their second buckets, so the longest chain, the
        probe limit, is deeper than one gathered row."""
        pool = np.unique(
            rng.integers(1 << 20, 1 << 21, size=(1 << 16, self.n_key_cols)), axis=0
        ).astype(np.int32)
        h1, _ = self.hashes(pool)
        first = h1 & np.uint32(OVERLAY_CAPACITY // self.spb - 1)
        crowd = pool[first == first[0]]
        n = self.spb + 4
        assert len(crowd) >= 2 * n
        self.present[:n], self.absent[:n] = crowd[:n], crowd[n : 2 * n]

    def queries(self) -> np.ndarray:
        return np.concatenate([self.present, self.absent])

    def hashes(self, keys: np.ndarray):
        h1 = snapshot.hash_combine(*(keys[:, i] for i in range(self.n_key_cols)))
        return h1, snapshot.mix32(h1 ^ snapshot._GOLDEN) | np.uint32(1)

    def host_probe(self, key: np.ndarray) -> int:
        """The slot holding `key` on snapshot.probe_slot's walk, or -1: the
        walk of compact._host_row_lookup, for any number of key columns."""
        h1, h2 = self.hashes(key[None, :])
        for j in range(self.probes):
            slot = int(snapshot.probe_slot(h1, h2, np.uint32(j), self.cap, self.spb)[0])
            if all(self.cols[i][slot] == key[i] for i in range(self.n_key_cols)):
                return slot
            if self.cols[0][slot] == EMPTY:
                return -1
        return -1


@pytest.fixture(params=["edge", "pair", "rh_span", "edge_overlay", "pair_overlay"])
def table(request):
    return Table(request.param, np.random.default_rng(29))


def test_pack_is_stored_as_bucket_rows(table):
    assert table.spb == 64 // table.width
    assert table.pack.shape == (table.cap // table.spb, table.spb * table.width)
    assert table.pack.dtype == np.int32
    if table.overlay:
        assert table.cap == OVERLAY_CAPACITY and table.probes > table.spb


def test_slot_rows_hold_what_the_columns_gave(table):
    slots = table.pack.reshape(table.cap, table.width)
    for lane, col in enumerate(table.slots):
        np.testing.assert_array_equal(slots[:, lane], col)
    assert not slots[:, len(table.slots):].any()  # pad lanes
    again = kernel.as_bucket_rows(slots, table.n_key_cols)
    np.testing.assert_array_equal(again, table.pack)


def test_bucket_rows_are_the_slots_probe_slot_walks(table):
    keys = table.queries()
    h1, h2 = table.hashes(keys)
    rows = np.asarray(kernel._bucket_rows(
        kernel.device_table(table.pack), jnp.asarray(h1), jnp.asarray(h2),
        table.probes, table.spb,
    ))
    walked = -(-table.probes // table.spb) * table.spb
    assert rows.shape == (len(keys), walked, table.width)
    slots = table.pack.reshape(table.cap, table.width)
    for j in range(walked):
        at = snapshot.probe_slot(h1, h2, np.uint32(j), table.cap, table.spb)
        np.testing.assert_array_equal(rows[:, j], slots[at])


def test_device_probe_finds_what_the_host_probe_finds(table):
    keys = table.queries()
    at = np.array([table.host_probe(k) for k in keys])
    assert (at[:N_KEYS] >= 0).all() and (at[N_KEYS:] < 0).all()
    tables = {"t_pack": kernel.device_table(table.pack)}
    cols = [jnp.asarray(keys[:, i]) for i in range(table.n_key_cols)]
    if table.builder == "edge":
        found, val = kernel._edge_key_probe(tables, "t", *cols, table.probes)
        np.testing.assert_array_equal(np.asarray(found), at >= 0)
        want = np.where(at >= 0, table.cols[5][np.clip(at, 0, None)], EMPTY)
        np.testing.assert_array_equal(np.asarray(val), want)
    elif table.builder == "pair":
        val = kernel._multi_pair_key_probe(
            tables, "t", cols[0], cols[1][:, None], table.probes
        )[:, 0]
        want = np.where(at >= 0, table.cols[2][np.clip(at, 0, None)], EMPTY)
        np.testing.assert_array_equal(np.asarray(val), want)
        single = kernel._pair_key_probe(tables, "t", cols[0], cols[1], table.probes)
        np.testing.assert_array_equal(np.asarray(single), want)
    else:
        spans = np.asarray(kernel._multi_pair_key_probe(
            tables, "t", cols[0], cols[1][:, None], table.probes, n_vals=2
        ))[:, 0, :]
        rows = np.array([
            compact._host_row_lookup(*table.cols, table.probes, int(k[0]), int(k[1]))
            for k in keys
        ])
        np.testing.assert_array_equal(rows >= 0, at >= 0)
        held = rows >= 0
        np.testing.assert_array_equal(spans[held, 0], table.row_ptr[rows[held]])
        np.testing.assert_array_equal(spans[held, 1], table.row_ptr[rows[held] + 1])
        assert (spans[~held] == EMPTY).all()


@pytest.mark.parametrize(
    "shape, row_major",
    [
        ((1024, 64), True),  # bucket rows
        ((4, 512, 64), True),  # a stack of shards' bucket rows
        ((8192, 8), False),  # slot rows, before as_bucket_rows
        ((4099, 2), False),  # e_pack: two columns, read as columns
        ((4096,), False),
    ],
)
def test_only_bucket_rows_are_placed_row_major(shape, row_major):
    """The TPU client stores [n, 64] int32 column-major by itself, and a
    program that gathers rows from that first copies the whole table."""
    layout = kernel.bucket_row_layout(shape, np.int32)
    assert (layout is not None) == row_major
    if row_major:
        assert layout.major_to_minor == tuple(range(len(shape)))
    placed = kernel.device_table(np.ones(shape, np.int32))
    assert placed.shape == shape and bool(placed.committed) == row_major
    np.testing.assert_array_equal(np.asarray(placed), 1)


def test_a_large_table_is_placed_once_and_filled_in_place(monkeypatch):
    """More than UPLOAD_ROWS bucket rows: the device holds one array of the
    table's size at every step of the upload and after it (sent whole, the
    table lies the client's way first and is then copied: two of them
    live, 15.2 GB of a 16.9 GB chip at 1.25e7 tuples), committed row-major
    and equal to the host's. A last step of fewer rows is a step too."""
    import jax

    n = 3 * kernel.UPLOAD_ROWS + 8
    host = np.arange(n * 64, dtype=np.int32).reshape(n, 64)
    before = {id(a) for a in jax.live_arrays()}

    def large_and_new():
        return [
            a.shape for a in jax.live_arrays()
            if id(a) not in before and a.nbytes >= host.nbytes // 2
        ]

    steps = []
    whole = kernel._row_writer

    def watched(fmt):
        write_rows = whole(fmt)

        def step(table, rows, start):
            steps.append((start, len(rows), large_and_new()))
            return write_rows(table, rows, start)

        return step

    monkeypatch.setattr(kernel, "_row_writer", watched)
    placed = kernel.device_table(host)
    rows = kernel.UPLOAD_ROWS
    assert [(start, count) for start, count, _ in steps] == [
        (0, rows), (rows, rows), (2 * rows, rows), (3 * rows, 8),
    ]
    assert all(live == [host.shape] for _, _, live in steps)
    assert large_and_new() == [host.shape]
    assert placed.committed
    assert placed.format.layout.major_to_minor == (0, 1)
    np.testing.assert_array_equal(np.asarray(placed), host)


DRIVE_1E6 = {  # the 1e6 drive store's tables (chip_smoke's, the 1e6 cells')
    "objslot_ns": (988160,), "ns_has_config": (128,), "prog_flags": (10,),
    "dh_pack": (1048576, 64), "rh_pack": (524288, 64), "e_pack": (975757, 2),
    "instr_pack": (10, 8), "dd_pack": (1024, 64), "dirty_pack": (512, 64),
    "rd_pack": (1024, 64),
}
DRIVE_4E6 = {  # drive-chip-share's
    **DRIVE_1E6, "objslot_ns": (3988480,), "dh_pack": (4194304, 64),
    "rh_pack": (2097152, 64), "e_pack": (3938717, 2),
}


@pytest.mark.parametrize(
    "shapes, held",
    [
        ({"a bucket row": (1, 64)}, 8 * 512),  # a tile is 8 rows
        ({"bucket rows": (1024, 64)}, 1024 * 512),
        ({"shards of bucket rows": (4, 512, 64)}, 4 * 512 * 512),
        ({"slot rows": (8192, 8)}, 8192 * 8 * 4),  # narrow: as they are,
        ({"e_pack": (4099, 2)}, 4224 * 2 * 4),  # rows padded to 128 lanes
        ({"instr_pack": (10, 8)}, 128 * 8 * 4),
        ({"a long vector": (988160,)}, 988160 * 4),
        ({"a short vector": (10,)}, 128 * 4),
        (DRIVE_1E6, 818_381_824),  # memory_stats() read 818,884,608 there
        (DRIVE_4E6, 3_270_005_760),  # 3,284,044,800 with its launch buffers
        ({**DRIVE_4E6, "dh_pack": (16777216, 64), "rh_pack": (8388608, 64),
          "objslot_ns": (12488704,), "e_pack": (12333837, 2)}, 13_034_844_160),
    ],
    ids=lambda case: "+".join(case) if isinstance(case, dict) else None,
)
def test_device_bytes_by_arithmetic(shapes, held):
    """What the chip's tiling makes of a table's shape: every number but
    the first three is what a v5e reported for that array or store
    (`on_device_size_in_bytes`, `memory_stats()`; PR 35's runs)."""
    assert sum(
        kernel.tiled_nbytes(shape, np.int32) for shape in shapes.values()
    ) == held
