"""A described (not attached) TPU v5e for the tests that compile for the
chip without one (``test_tpu_compile.py``, ``test_decode_in_place.py``):
fixtures of the files that import them.

Interpret mode accepts what the chip's compiler refuses, so those files
ask libtpu's compiler itself.  The kernels choose interpret mode from
``jax.default_backend()``; ``compile_for_tpu`` steers that call, the
program has no option for it.
"""
import os

import pytest

import jax
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one described (not attached) v5e device."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu, or it cannot describe one
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compile_for_tpu(monkeypatch):
    """Kernels see a TPU backend; the persistent compile cache is off
    (an entry compiled for a described device cannot be read back
    without a chip, and the next run would warn)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
