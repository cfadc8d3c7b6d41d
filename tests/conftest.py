"""Test bootstrap: force an 8-device virtual CPU mesh so multi-chip sharding
paths are exercised without TPU hardware.  The environment is set before
jax is imported, which is all it takes."""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture
def mesh_devices():
    """The forced 8-device virtual CPU mesh (ISSUE 11 satellite): the
    XLA_FLAGS export above runs BEFORE jax import, so dp×mp shapes up to
    4×2 exercise the real shard_map partitioning on the CPU-only image.
    Fails loudly (not skips) if the forcing stopped working — tier-1 mesh
    coverage must never silently evaporate."""
    devices = jax.devices()
    assert len(devices) >= 8, (
        "expected >= 8 virtual CPU devices "
        "(XLA_FLAGS=--xla_force_host_platform_device_count=8 was exported "
        f"too late?), got {len(devices)}")
    return devices[:8]


def pytest_configure(config):
    """Build the native library once, on the controller, before xdist starts
    its workers: on a tree with no ``_build/`` six workers would otherwise
    all compile into the same ``_atpuenc.so.tmp``, most would lose, and the
    native tests of those workers would skip (or fail) for the whole run."""
    if not hasattr(config, "workerinput"):
        from authorino_tpu.native import load_library

        load_library()
