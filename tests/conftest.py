"""Test configuration: the suite runs on the host CPU, eight virtual devices.

Tier-1 runs on machines with no accelerator, and the sharded tests need
a mesh, so JAX is held to the CPU backend with eight virtual devices
before any backend is created. The persistent compile cache
(keto_tpu/compile_cache.py) is off for the whole suite, so a run neither
reads nor leaves compiled programs: every test compiles what it launches.
The chip path is proven by `chip_smoke.py` on the chip, and compiled for
a described chip by tests/test_tpu_compile.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (must come after the env setup above)
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    # KETO_LOCKWATCH=1: install the runtime lock-order / blocking-under-
    # lock detector (keto_tpu/analysis/lockwatch.py) for the whole
    # session — the `go test -race` leg. Hooks below fail the exact test
    # whose execution produced a violation, with creation-site stacks.
    from keto_tpu.analysis import lockwatch

    lockwatch.pytest_session_start()


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item, nextitem):
    # wrapper: the post-yield check runs AFTER the core runner's
    # teardown_exact, i.e. after this test's fixture finalizers (daemon
    # stops, batcher closes live in finalizers) — a violation raised
    # there fails THIS test, not the next one
    yield
    from keto_tpu.analysis import lockwatch

    # the high-water mark lives on the watcher (advanced before the
    # raise), so one violation fails exactly its own test instead of
    # cascading the same report into every later test
    lockwatch.check_test(item.nodeid)


def pytest_sessionfinish(session, exitstatus):
    # backstop for violations produced after the last test's teardown
    # hook (session-scoped finalizers torn down late, atexit-adjacent
    # threads): re-check before uninstall so they can never be dropped
    from keto_tpu.analysis import lockwatch

    lockwatch.check_test("session teardown (after the last test)")


def pytest_unconfigure(config):
    from keto_tpu.analysis import lockwatch

    lockwatch.uninstall()
