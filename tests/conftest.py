"""Test configuration.

Mirrors the reference's strategy (SURVEY.md §4): the suite runs once per
execution selected by MODIN_TPU_ENGINE/MODIN_TPU_STORAGE_FORMAT.  Default for
the suite is the Tpu storage format on a virtual 8-device CPU mesh so sharding
and collectives are exercised without TPU hardware
(xla_force_host_platform_device_count=8).
"""

import os

# Must happen before jax import: virtual 8-device CPU mesh for sharding tests.
# Forced (not setdefault): differential tests need exact float64, and TPU f64
# is double-float emulated (~2^-49 relative precision, float32 exponent range).
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# audit every dispatcher read for fd leaks throughout the suite (the config
# defaults off in production; see TrackFileLeaks)
os.environ.setdefault("MODIN_TPU_TEST_TRACK_FILE_LEAKS", "True")

import jax  # noqa: E402

# Tests always run on the CPU (8 virtual devices): the explicit config
# update holds whatever the environment or an earlier import chose.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

# XLA:CPU's compiler segfaults on a FRESH compile late in a very long
# process (reproduced deterministically past ~1770 tests: first in an ewm
# scan compile, then — with that test skipped — in the xgboost trainer's;
# every victim passes standalone).  Dropping the accumulated live
# executables every few hundred tests keeps the compiler healthy at the
# cost of some recompilation.
_CLEAR_EVERY = 300
_test_counter = {"n": 0}


@pytest.fixture(autouse=True)
def _periodic_jax_cache_clear():
    yield
    _test_counter["n"] += 1
    if _test_counter["n"] % _CLEAR_EVERY == 0:
        jax.clear_caches()


def pytest_addoption(parser):
    parser.addoption(
        "--execution",
        action="store",
        default=None,
        help="storage_format}On{engine to run the suite under (e.g. TpuOnJax, NativeOnNative)",
    )


def pytest_configure(config):
    execution = config.getoption("--execution") or os.environ.get(
        "MODIN_TPU_TEST_EXECUTION", "TpuOnJax"
    )
    import re

    match = re.match(r"^(.*)On(.*)$", execution)
    storage_format, engine = match.groups()
    from modin_tpu.config import Engine, StorageFormat

    StorageFormat.put(storage_format)
    Engine.put(engine)


@pytest.fixture
def enable_benchmark_mode():
    from modin_tpu.config import BenchmarkMode

    with BenchmarkMode.context(True):
        yield
