"""Test configuration.

The tests run on the CPU backend with Pallas kernels in interpret mode.
Multi-chip behavior is tested on a virtual 8-device CPU mesh, mirroring how
the reference simulates its cluster with ``local[4]`` Spark
(reference: core/src/test/.../workflow/BaseTest.scala:71-88). These env vars
must be set before the first ``import jax`` anywhere in the test process —
jax reads them at import, and this is the one installation (jax 0.9.0), so
setting them here is enough.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def tmp_home(tmp_path, monkeypatch):
    """Isolated PIO home directory for storage-backed tests."""
    monkeypatch.setenv("PIO_HOME", str(tmp_path))
    return tmp_path


@pytest.fixture
def sub_mesh():
    """Mesh over the first N virtual devices — the sharded-path tests'
    seam for exercising mesh shapes {1, 2, 4, 8} on the CPU sim
    (parallel/mesh.py ``make_mesh``/``forced_device_count``)."""
    from incubator_predictionio_tpu.parallel.mesh import make_mesh

    def make(n: int, model_parallelism: int = 1):
        if jax.device_count() < n:
            pytest.skip(f"needs {n} devices")
        return make_mesh(devices=jax.devices()[:n],
                         model_parallelism=model_parallelism)

    return make
