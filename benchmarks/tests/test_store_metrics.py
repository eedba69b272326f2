"""The store layer's metrics on a rehearsal of the cell that was added for
them (PR 37): `bulk_load_s` is a host clock and is printed; what only a chip
can say is left out, not printed as 0: the CPU keeps no memory statistic
(`hbm_resident_share`), and an upload to it is no upload (`table_upload_s`).
"""

from types import SimpleNamespace

import pytest

from test_benchmark import ROOT, read_bench, rehearse  # puts benchmarks/ on the path
from workload import load_module

CELL = "drive-chip-share.batch2048-c4"
STORE_METRICS = {"hbm_resident_share", "table_upload_s", "bulk_load_s"}


def test_the_store_metrics_name_every_cell():
    bench = read_bench()
    cells = [cell["name"] for cell in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in STORE_METRICS}
    assert set(listed) == STORE_METRICS
    assert all(m["workloads"] == cells and m["moves"] == "setup_s"
               for m in listed.values())


def test_the_chip_share_cell_rehearses_with_its_store_metrics():
    line, _, phases = rehearse(ROOT, CELL, 1)
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert phases["serving"]["tuples"] == 20000
    assert line["metrics"]["bulk_load_s"]["value"] == phases["serving"]["bulk_load_s"]
    assert {"hbm_resident_share", "table_upload_s"}.isdisjoint(line["metrics"])


@pytest.mark.parametrize("after, value", [
    ({"in_use": 3.0, "limit": 12.0}, 25.0),
    ({"in_use": 0.0, "limit": 0.0}, None),  # the CPU, or a program without them
    ({"in_use": 3.0, "limit": 0.0}, None),
])
def test_a_gauges_level_after_the_window_is_read_or_nothing(after, value):
    reader = load_module("readers", "prom_gauge_optional")
    scrape = type("Scrape", (), {"value": lambda self, name, labels=None: after[name]})()
    args = {"num": [{"name": "in_use"}], "den": [{"name": "limit"}], "scale": 100}
    assert reader.reads(args) == {"in_use", "limit"}
    assert not hasattr(reader, "names")  # run.py refuses nothing for them
    assert reader.read(SimpleNamespace(after=scrape), **args) == value
    if value is not None:
        assert reader.read(SimpleNamespace(after=scrape), num=args["num"]) == 3.0
