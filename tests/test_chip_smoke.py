"""chip_smoke.py and the rules around it, rehearsed on the CPU.

The smoke itself proves the chip path on the chip. What can rot without a
chip is kept here: the script still runs end to end at a rehearsal size, a
device failure that the host covered still fails it, nothing that measures
or proves on the chip falls back to the CPU by itself, and the compile
cache goes where the caller put it.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from keto_tpu import compile_cache, faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = ["--tuples", "20000"]


def test_rehearsal_runs_every_phase(capsys):
    assert chip_smoke.main(REHEARSAL) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    phases = [l["phase"] for l in lines if "phase" in l]
    for phase in ("device", "load", "single_checks", "check_batch", "expand",
                  "list_objects", "list_subjects", "filter", "launches",
                  "read_your_write", "tier", "compile_cache"):
        assert phase in phases
    assert all(l["mismatches"] == 0 for l in lines if "mismatches" in l)
    assert next(l for l in lines if l.get("phase") == "load")["tuples"] == 20000
    # the last line is the device as jax reports it, and nothing else
    assert lines[-1] == {
        "ok": True,
        "device": {
            "platform": "cpu",
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
    }


def test_a_device_failure_the_host_covered_fails_the_smoke(capsys):
    """Every launch raises, the batcher answers from the host oracle, every
    answer is right, and the counters fail the run all the same."""
    faults.set_fault("device_launch", error="injected by the test")
    try:
        with pytest.raises(chip_smoke.SmokeFailure, match="device batches failed"):
            chip_smoke.main(REHEARSAL)
    finally:
        faults.clear()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    singles = next(l for l in lines if l.get("phase") == "single_checks")
    counters = next(l for l in lines if l.get("phase") == "counters")
    assert singles["mismatches"] == 0
    assert counters["check_batch_failed_total"]["device"] > 0
    assert counters["breaker_state"] == 1
    assert "ok" not in lines[-1]


@pytest.mark.parametrize(
    "argv",
    [
        ["chip_smoke.py"],
        ["chip_smoke.py", "--mesh4"],
        ["bench.py", "--skip-serve"],
        ["tools/scale_bench.py", "--tuples", "1000"],
        ["tools/tpu_test_tier.py"],
    ],
    ids=["chip_smoke", "chip_smoke_mesh4", "bench", "scale_bench", "tpu_test_tier"],
)
def test_without_a_tpu_nothing_runs_on_the_cpu(argv):
    """JAX is held to the CPU here, as in any sandbox without a chip: each
    script must refuse, with a non-zero exit and no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, *argv], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout
    assert '"value"' not in out.stdout and '"check_qps"' not in out.stdout
    assert '"failures": 0' not in out.stdout


@pytest.mark.parametrize("placed", [None, "/somewhere/the/caller/chose"])
def test_compile_cache_goes_where_the_caller_put_it(monkeypatch, placed):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    if placed:
        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, placed)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        got = compile_cache.ensure_compile_cache()
        if placed:
            # JAX reads the variable itself; no code sets another directory
            assert got == placed
            assert jax.config.jax_compilation_cache_dir is None
        else:
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_registry_places_the_cache_before_building_the_engine(monkeypatch):
    from keto_tpu.config import Config
    from keto_tpu.registry import Registry

    calls = []
    monkeypatch.setattr(
        compile_cache, "ensure_compile_cache", lambda: calls.append("placed")
    )
    Registry(Config({"dsn": "memory"})).check_engine()
    assert calls == ["placed"]
    Registry(Config({"dsn": "memory", "check": {"engine": "host"}})).check_engine()
    assert calls == ["placed"]
