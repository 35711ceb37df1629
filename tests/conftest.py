"""Test configuration.

Forces JAX onto the CPU backend with 8 virtual devices so that mesh/sharding
tests (the multi-chip path) run in CI without TPU hardware, mirroring how the
reference tests "multi-node" behavior in one JVM via its MiniCluster
(reference: flink-runtime/src/main/java/org/apache/flink/runtime/minicluster/MiniCluster.java).

Must run before the first ``import jax`` anywhere in the test session.
"""

import os

# Tests always run on the virtual CPU mesh, whatever the ambient
# environment points JAX at.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep compilation fast and deterministic in CI.
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: wall-clock-sensitive or long tests, excluded from the "
        "tier-1 gate (-m 'not slow' — see tools/tier1.sh)")


@pytest.fixture
def eight_device_mesh():
    import jax
    from flink_tpu.parallel.mesh import make_mesh

    n = len(jax.devices())
    assert n >= 8, f"expected >=8 virtual devices, got {n}"
    return make_mesh(8)


def assert_windows_approx_equal(got, expected, rel=1e-4, abs_tol=1e-3):
    """Per-window compare with float tolerance: the local (two-phase)
    combiner and parallel folds change f32 summation order, so sums match
    to ~1 ulp, not bit-exactly. Shared by the stage/batch/shuffle suites."""
    import pytest as _pytest

    assert set(got) == set(expected)
    for k in expected:
        assert got[k] == _pytest.approx(expected[k], rel=rel, abs=abs_tol), k
