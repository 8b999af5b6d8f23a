"""The main path's Pallas kernels, compiled (not interpreted) for a
described TPU v5e at the shapes ``chip_smoke.py`` runs — no chip needed.

Interpret mode accepts what the chip's compiler refuses (a batched
matmul with no free lhs dim, a float iota, a block past the scoped-VMEM
limit), so every other kernel test in the suite can pass on a kernel
that cannot start on a TPU.  These cases ask libtpu's compiler itself.
The kernels choose interpret mode from ``jax.default_backend()``; the
test steers that call, the program has no option for it.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# the decode model of chip_smoke.py: 8 heads x 64, bf16, 8 slots
H, D, SLOTS = 8, 64, 8


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
def _compile_for_tpu(monkeypatch):
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


def _compile(fn, sharding, *specs):
    args = [jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=sharding)
            for shape, dt in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel was not compiled by Mosaic"
    return text


def _flash_loss(q, k, v):
    from mxnet_tpu.ops.attention import _flash_attention
    out = _flash_attention(q, k, v, True, D ** -0.5, 512, 512)
    return out.astype(jnp.float32).sum()


@pytest.mark.parametrize("with_bwd", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(v5e, with_bwd):
    # TransformerLM bs 8 x 8 heads, seq 2048, head_dim 64, bf16, causal
    fn = jax.grad(_flash_loss, argnums=(0, 1, 2)) if with_bwd \
        else _flash_loss
    qkv = ((64, 2048, D), "bfloat16")
    text = _compile(fn, v5e, qkv, qkv, qkv)
    # forward is one kernel; backward adds the dk/dv and dq kernels
    assert text.count("tpu_custom_call") >= (3 if with_bwd else 1)


@pytest.mark.parametrize("page_size", [16, 128])
def test_paged_attention_compiles(v5e, page_size):
    from mxnet_tpu.ops.paged_attention import _paged_attention_pallas
    pps = 640 // page_size                    # 640-token slots
    pool = ((SLOTS * pps, page_size, H * D), "bfloat16")
    _compile(
        lambda q, k, v, t, l: _paged_attention_pallas(
            q, k, v, t, l, D ** -0.5, 64),
        v5e, ((SLOTS, H, D), "bfloat16"), pool, pool,
        ((SLOTS, pps), "int32"), ((SLOTS,), "int32"))


@pytest.mark.parametrize("rows,h,d,block_r", [
    (SLOTS, H, D, 128),           # decode step: one row per slot
    (SLOTS * 5, H, D, 128),       # verify window, spec_k = 4
    (128, H, D, 128),             # largest prefill bucket
    (16384, 16, 128, 512),        # block override past VMEM: clamped
])
def test_rope_compiles(v5e, rows, h, d, block_r):
    from mxnet_tpu.ops.rope import _rope_pallas
    _compile(lambda x, p: _rope_pallas(x, p, 10000.0, block_r),
             v5e, ((rows, h, d), "bfloat16"), ((rows,), "int32"))


def test_layer_norm_residual_compiles(v5e):
    from mxnet_tpu.ops.layernorm_residual import _lnr_pallas
    x = ((16384, 512), "bfloat16")
    g = ((512,), "bfloat16")
    _compile(lambda a, r, ga, be: _lnr_pallas(a, r, ga, be, 1e-5, 32),
             v5e, x, x, g, g)
