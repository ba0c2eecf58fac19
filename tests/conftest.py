"""Test rig: 8 virtual CPU devices.

This is the TPU-native analogue of the reference's ``horovodrun -np 2 pytest``
multi-process rig (SURVEY §4): ``--xla_force_host_platform_device_count=8``
gives 8 collective participants in-process.  The device count must be set
before any JAX backend initializes.
"""

import os

# Cache every compile in <checkout>/.jax_cache, not only those over JAX's
# 1 s default: the suite recompiles the same small programs across
# modules and worker processes, and tier-1 lives against a time ceiling
# (tests/test_llama.py alone: 278 s uncached, 234 s at the default
# threshold, 177 s at 0).  Set before jax is imported; children inherit.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

from horovod_tpu.utils.cpurig import force_cpu_platform  # noqa: E402

force_cpu_platform(8)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def hvd_session():
    import horovod_tpu as hvd
    hvd.init()
    assert hvd.size() == 8, f"expected 8 fake devices, got {hvd.size()}"
    yield
    hvd.shutdown()
