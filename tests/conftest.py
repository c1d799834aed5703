"""Test env: force JAX onto a virtual 8-device CPU mesh before jax imports.

Mirrors the reference's local-mode SparkContext substitution
(``core/src/test/.../BaseTest.scala:15-33`` uses ``local[4]``): distributed
code paths are exercised without real hardware, here via
``xla_force_host_platform_device_count``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# In-process `pio train`/`deploy` calls place jax's persistent compile
# cache (utils/compile_cache.py). The suite counts compiles and must not
# leave executables in the checkout, so the cache stays off here;
# tests/test_chip_smoke.py turns it back on for its own child processes.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import pytest  # noqa: E402

from predictionio_tpu.data import storage  # noqa: E402
from predictionio_tpu.data.storage import StorageConfig  # noqa: E402


@pytest.fixture(scope="session")
def multichip_devices():
    """The virtual multi-device plane the ``multichip``-marked sharded
    differentials run on: conftest forced 8 host-platform CPU devices
    before the first jax import (the local-mode SparkContext analog),
    so tier-1 exercises real mesh collectives without hardware. Skips
    — instead of silently degenerating to one shard — if an
    environment override stripped the virtual devices."""
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip(f"multichip tests need >=4 devices, have {len(devs)} "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return devs


@pytest.fixture
def multichip_mesh(multichip_devices):
    """A 4-way 1-D 'data' mesh over the virtual device plane — the
    shape the sharded-serving differentials and the ISSUE-15 sharded
    fold-in tests run against."""
    from predictionio_tpu.parallel.mesh import data_parallel_mesh

    return data_parallel_mesh(4, devices=multichip_devices)


@pytest.fixture
def mem_storage():
    """Process-global registry backed by fresh in-memory DAOs."""
    cfg = StorageConfig(
        sources={"TEST": {"type": "memory"}},
        repositories={"METADATA": "TEST", "EVENTDATA": "TEST",
                      "MODELDATA": "TEST"},
    )
    storage.reset(cfg)
    yield storage.registry()
    storage.reset()


@pytest.fixture
def sqlite_storage(tmp_path):
    cfg = StorageConfig(
        sources={"TEST": {"type": "sqlite",
                          "path": str(tmp_path / "pio_test.db")}},
        repositories={"METADATA": "TEST", "EVENTDATA": "TEST",
                      "MODELDATA": "TEST"},
    )
    storage.reset(cfg)
    yield storage.registry()
    storage.reset()
