"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Must run before any jax import (pytest loads conftest first).  Bench and
production run on real TPU; tests exercise the multi-chip sharding paths
on virtual CPU devices per the driver contract.
"""

import os
import sys

# Override (not setdefault): tests run on 8 virtual CPU devices,
# whatever the environment says.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Make the repo root importable regardless of pytest invocation dir.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Backends initialize lazily, so pin the config too (before any
# device use): tests run on 8 virtual CPU devices.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: long nemesis sweeps/soaks carry
    # the slow marker and run only in the soak lane — register it so
    # -W error environments don't trip on an unknown marker
    config.addinivalue_line(
        "markers", "slow: long-running nemesis sweeps/soaks excluded "
        "from the tier-1 window (run explicitly or via -m slow)")
    # The mesh equivalence suite needs the forced 8-device CPU mesh
    # (set above for every test session).  The marker lets CI run it
    # as its OWN pytest session (`pytest -m mesh`) so a future change
    # to the forced device count can't silently contaminate the other
    # suites — and lets a single-device environment deselect it.
    config.addinivalue_line(
        "markers", "mesh: single-shard↔mesh equivalence suite; needs "
        "xla_force_host_platform_device_count=8 (runs standalone via "
        "-m mesh)")


def soak_seeds(base):
    """CI runs the fixed seed list; soak sweeps widen it via
    RETPU_SOAK_SEEDS="start:count" (fresh seeds, not repeats) so
    long-running nemesis soaks measure new schedules every run."""
    spec = os.environ.get("RETPU_SOAK_SEEDS")
    if not spec:
        return base
    start, count = (int(x) for x in spec.split(":"))
    return list(range(start, start + count))
