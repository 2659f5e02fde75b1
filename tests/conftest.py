"""Test config: force an 8-device virtual CPU mesh BEFORE jax import.

This is the reference's "N logical nodes in one JVM" trick (SURVEY.md section
4.5) in TPU form: multi-chip sharding paths run against
xla_force_host_platform_device_count=8 so tests exercise real Mesh/shard_map
code without TPU hardware."""
import os

# Tests always run on the virtual 8-device CPU mesh, whatever the caller's
# environment says.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running smoke tests excluded from tier-1 (-m 'not slow')"
    )


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _reset_metrics():
    """The METRICS registry and the stats window are process-global; without
    a reset, counter/histogram assertions and federated per-server series
    see spill-over from whichever tests ran before."""
    from pinot_tpu.utils.interpreter import WATCH
    from pinot_tpu.utils.metrics import METRICS
    from pinot_tpu.utils.perf import SHAPE_STATS

    WATCH.stop()  # a traced query of the test before started the interpreter watch: it must not tick into this one
    METRICS.reset()
    SHAPE_STATS.reset()
    yield


@pytest.fixture(autouse=True)
def _reset_knob_registry():
    """The autopilot KnobRegistry is process-global; a knob override set by
    one test (or a controller it started) must not leak into the env-default
    reads every other test depends on."""
    from pinot_tpu.cluster import autopilot

    autopilot.reset_knobs()
    yield
    autopilot.reset_knobs()


@pytest.fixture(autouse=True)
def _reset_thread_provider():
    """The primitive provider (utils/threads.py) is process-global; a test
    that dies inside a model-checker schedule must not leave the
    deterministic provider installed for whichever test runs next."""
    from pinot_tpu.utils import threads

    threads.reset_provider()
    yield
    threads.reset_provider()
