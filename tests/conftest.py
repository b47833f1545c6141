"""Force a multi-device host platform BEFORE jax initializes, so the SPMD
tensor-parallel engine tests (tests/test_tp_engine.py) can build real 1×tp
meshes on CPU. Harmless for single-device tests: plain jits still run on
device 0. Conftest is imported before any test module, which is the only
reliable place to set XLA_FLAGS under plain `python -m pytest`.
"""
import os

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        (_flags + " --xla_force_host_platform_device_count=8").strip()


@pytest.fixture(autouse=True)
def _keep_matmul_precision():
    """A harness run in the test process (tests/bench) sets its
    configuration's matmul precision for the whole process; put JAX's back
    after each test, so that what a later test in the same worker traces
    (the Pallas kernels compiled for a described v5e lower only at the
    default precision) does not depend on which tests ran before it."""
    import jax
    keep = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", keep)
