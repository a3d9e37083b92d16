"""Test configuration: run JAX on a virtual 8-device CPU platform.

The env vars are set at conftest import time, before the first ``import
jax`` of the session: ``JAX_PLATFORMS`` (CPU unless the caller chose) and
the XLA device-count flag, which XLA reads once at backend start.
Sharding tests rely on the 8 virtual devices; everything else just runs on
CPU for determinism and speed.
"""

import os
import re

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", ""),
)
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=8"
).strip()
# the persistent compilation cache defaults ON in the driver; keep tests
# hermetic by disabling it unless a test opts in
os.environ.setdefault("ERP_COMPILATION_CACHE", "off")

import pathlib

import pytest

REFERENCE_TESTWU = pathlib.Path(
    "/root/reference/debian/extra/einstein_bench/testwu"
)


@pytest.fixture(scope="session")
def testwu_dir():
    if not REFERENCE_TESTWU.is_dir():
        pytest.skip("reference test workunit fixture not available")
    return REFERENCE_TESTWU


@pytest.fixture(scope="session")
def testwu_bin4(testwu_dir):
    return str(
        testwu_dir / "p2030.20151015.G187.41-00.88.N.b2s0g0.00000_1099.bin4"
    )


@pytest.fixture(scope="session")
def testwu_bank(testwu_dir):
    return str(testwu_dir / "stochastic_full.bank")


@pytest.fixture(scope="session")
def testwu_zaplist(testwu_dir):
    return str(testwu_dir / "p2030.20151015.G187.41-00.88.N.b2s0g0.00000.zap")
