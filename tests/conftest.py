"""Test harness configuration.

SPMD tests run on a virtual 8-device CPU mesh via
``--xla_force_host_platform_device_count`` — the counterpart of the
reference's one-host multi-raylet ``Cluster`` fixture trick
(`python/ray/cluster_utils.py:99`): fake resources let a laptop test
multi-device logic (SURVEY.md §4.2).
"""

import os

# Must happen before any jax import anywhere in the test session.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

from ray_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

# Engines and trainers are rebuilt per test as fresh jit closures over the
# same tiny configs, so most XLA:CPU compiles of a run repeat an earlier
# one that jit's own per-function cache cannot see. JAX's persistent cache
# answers the repeats, from the first run on. It is set in this process
# only (workers and subprocesses inherit no config), at the fixed path
# the chip processes default to; tests that compile for a described TPU
# turn it off around themselves.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (full tuned regressions)")


@pytest.fixture(scope="session")
def ray_session():
    """One shared local session for all tests (worker spawn is ~2s on the
    1-CPU CI box, so tests share a pool like the reference's
    ray_start_regular fixture, conftest.py:410)."""
    import ray_tpu
    # num_tpus=2 fakes two chips (resources are scheduler numbers, like the
    # reference's Cluster.add_node(num_gpus=8) on a laptop, SURVEY.md §4).
    ray_tpu.init(num_cpus=4, num_tpus=2, ignore_reinit_error=True)
    yield ray_tpu
    # Telemetry-plane self-test before teardown: the whole session's
    # metric registry must still render parseable Prometheus, every
    # span ring must honor its bound, and every retrace sentinel must
    # still be watching its pinned paths.
    from ray_tpu.util import telemetry
    telemetry.check_invariants()
    ray_tpu.shutdown()
