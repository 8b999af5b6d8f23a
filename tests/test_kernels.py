"""Kernel registry + autotuner + persistent cache (mxnet_tpu/kernels).

Parity model: every registered kernel is pinned against its own XLA
``fallback`` — the oracle contract — across dtype (fp32/bf16) and
ragged / non-multiple-of-block shapes.  The cache tests exercise the
durability contract (round-trip, corruption -> re-tune, stale kernel
version -> miss) and the lookup order (env override > memo > disk >
tuner > default), including the warm-start zero-measurement guarantee
``ci/run.sh kernel_smoke`` asserts across a real process kill.
"""
import json
import os

import numpy as onp
import pytest
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu import kernels, telemetry
from mxnet_tpu.kernels import cache as kcache
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops.layernorm_residual import layer_norm_residual

KERNELS = ("flash_attention", "layer_norm_residual", "zero_flatten_pad",
           "rope", "paged_attention", "ssm_update", "latent_attention")


@pytest.fixture
def kdir(tmp_path, monkeypatch):
    """Isolated cache dir + a clean in-process memo on both sides."""
    monkeypatch.setenv("MXNET_KERNEL_CACHE_DIR", str(tmp_path))
    kernels.invalidate()
    yield str(tmp_path)
    kernels.invalidate()


def _tree_close(a, b, rtol, atol):
    la, lb = (list(a) if isinstance(a, (tuple, list)) else [a]), \
             (list(b) if isinstance(b, (tuple, list)) else [b])
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        onp.testing.assert_allclose(
            onp.asarray(x, "float32"), onp.asarray(y, "float32"),
            rtol=rtol, atol=atol)


# -- registry surface -------------------------------------------------------

def test_registered_kernels_present():
    names = kernels.list_kernels()
    for name in KERNELS:
        assert name in names
        spec = kernels.get_kernel(name)
        assert spec.config_space and spec.default_config
        assert spec.make_args is not None and spec.tune_grid
    with pytest.raises(mx.base.MXNetError):
        kernels.get_kernel("no_such_kernel")
    with pytest.raises(mx.base.MXNetError):  # duplicate registration
        kernels.register_kernel(kernels.get_kernel("flash_attention"))


@pytest.mark.parametrize("name", KERNELS)
def test_candidates_default_first(name):
    spec = kernels.get_kernel(name)
    cands = kernels.candidates(spec)
    # default config leads, so a measurement tie resolves to the
    # untuned behavior; the full cartesian product follows, deduped
    assert cands[0] == spec.default_config
    n = 1
    for vals in spec.config_space.values():
        n *= len(vals)
    assert len(cands) == n + (spec.default_config not in [
        dict(zip(sorted(spec.config_space), c)) for c in
        __import__("itertools").product(
            *(spec.config_space[k] for k in sorted(spec.config_space)))])
    assert all(cands.count(c) == 1 for c in cands)


def test_cache_key_anatomy():
    spec = kernels.get_kernel("flash_attention")
    key = kernels.cache_key(spec, "sq128_sk128_d64_c0", "float32")
    parts = key.split("|")
    assert parts[0] == "flash_attention"
    assert parts[1] == f"v{spec.version}"
    assert parts[2:4][1].startswith("ndev")
    assert parts[4] == "float32" and parts[5] == "sq128_sk128_d64_c0"


# -- parity vs the XLA oracle ----------------------------------------------

@pytest.mark.parametrize("name", KERNELS)
def test_kernel_parity_vs_oracle(name):
    """Default config over every tune-grid case: the registered run and
    its fallback agree — the contract that makes the fallback both the
    escape hatch and the tuner's numerics baseline."""
    spec = kernels.get_kernel(name)
    for case in spec.tune_grid:
        arrays, params = spec.make_args(case)
        out = spec.run(dict(spec.default_config), *arrays, **params)
        ref = spec.fallback(*arrays, **params)
        _tree_close(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype,rtol,atol",
                         [("float32", 2e-4, 2e-4),
                          ("bfloat16", 2e-2, 2e-2)])
@pytest.mark.parametrize("bh,sq,sk,causal",
                         [(2, 100, 100, True),    # ragged, causal
                          (1, 257, 130, False),   # non-multiple of block
                          (2, 128, 128, True)])
def test_flash_parity_dtype_shape_causal(dtype, rtol, atol,
                                         bh, sq, sk, causal):
    spec = kernels.get_kernel("flash_attention")
    arrays, params = spec.make_args(
        {"bh": bh, "sq": sq, "sk": sk, "d": 64,
         "causal": causal, "dtype": dtype})
    out = spec.run({"block_q": 128, "block_k": 128}, *arrays, **params)
    ref = spec.fallback(*arrays, **params)
    _tree_close(out, ref, rtol=rtol, atol=atol)


@pytest.mark.parametrize("dtype,rtol,atol",
                         [("float32", 2e-5, 2e-5),
                          ("bfloat16", 2e-2, 2e-2)])
@pytest.mark.parametrize("rows,f", [(100, 128), (257, 256)])
def test_layer_norm_residual_parity(dtype, rtol, atol, rows, f):
    spec = kernels.get_kernel("layer_norm_residual")
    arrays, params = spec.make_args({"rows": rows, "f": f,
                                     "dtype": dtype})
    for block_rows in (8, 64):      # non-multiple-of-block row counts
        out = spec.run({"block_rows": block_rows}, *arrays, **params)
        ref = spec.fallback(*arrays, **params)
        _tree_close(out, ref, rtol=rtol, atol=atol)


def test_layer_norm_residual_op_and_grads():
    import jax
    rng = onp.random.RandomState(3)
    x = jnp.asarray(rng.randn(5, 7, 64), "float32")
    r = jnp.asarray(rng.randn(5, 7, 64), "float32")
    gamma = jnp.asarray(rng.rand(64) + 0.5, "float32")
    beta = jnp.asarray(rng.randn(64) * 0.1, "float32")
    out = layer_norm_residual(x, r, gamma, beta)       # Pallas path
    ref = layer_norm_residual(x, r, gamma, beta, use_pallas=False)
    _tree_close(out, ref, rtol=2e-5, atol=2e-5)

    def loss_k(x, r, g, b):
        return (layer_norm_residual(x, r, g, b) ** 2).sum()

    def loss_ref(x, r, g, b):
        return (layer_norm_residual(x, r, g, b,
                                    use_pallas=False) ** 2).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    _tree_close(gk, gr, rtol=1e-4, atol=1e-4)


def test_zero_flatten_pad_bitwise_any_multiple():
    """Zero-pad + slice must preserve the surviving elements bitwise
    for every pad multiple — the property that makes the layout a pure
    measured decision."""
    spec = kernels.get_kernel("zero_flatten_pad")
    arrays, _ = spec.make_args({"sizes": (63, 129, 1000)})
    base = spec.run({"pad_multiple": 1}, *arrays)
    for mult in spec.config_space["pad_multiple"][1:]:
        out = spec.run({"pad_multiple": mult}, *arrays)
        for o, b in zip(out, base):     # layout choice: bitwise no-op
            onp.testing.assert_array_equal(onp.asarray(o), onp.asarray(b))
    # vs the eager oracle only up to FMA contraction (jit fuses w-lr*g)
    _tree_close(base, spec.fallback(*arrays), rtol=1e-6, atol=1e-6)


# -- cache durability + lookup order ---------------------------------------

def test_cache_roundtrip_counts_one_hit(kdir):
    spec = kernels.get_kernel("layer_norm_residual")
    key = kernels.commit(spec, "rows64_f32", "float32",
                         {"block_rows": 16}, 1.25)
    assert os.path.exists(kcache.cache_path())
    assert key in kcache.load()
    kernels.invalidate()                    # "a new process"
    h0 = telemetry.counter("kernel.cache_hits").value
    cfg = kernels.resolve("layer_norm_residual", "rows64_f32", "float32")
    assert cfg == {"block_rows": 16}
    assert telemetry.counter("kernel.cache_hits").value == h0 + 1
    # steady state: the memo answers, the counter does NOT tick again
    kernels.resolve("layer_norm_residual", "rows64_f32", "float32")
    assert telemetry.counter("kernel.cache_hits").value == h0 + 1


@pytest.mark.parametrize("garbage", [
    "{not json at all",
    '{"format": "something-else", "version": 1, "entries": {}}',
    '{"format": "mxnet-tpu-kernel-cache", "version": 999, "entries": {}}',
    '{"format": "mxnet-tpu-kernel-cache", "version": 1, "entries": [1]}',
    '{"format": "mxnet-tpu-kernel-cache", "version": 1, '
    '"entries": {"k": {"config": "not-a-dict"}}}',
])
def test_corrupted_cache_is_empty_not_fatal(kdir, garbage):
    with open(kcache.cache_path(), "w") as f:
        f.write(garbage)
    kernels.invalidate()
    assert kcache.load() == {}              # every defect -> empty
    spec = kernels.get_kernel("layer_norm_residual")
    cfg = kernels.resolve("layer_norm_residual", "rows64_f32", "float32")
    assert cfg == spec.default_config       # re-tune/default, no crash
    # and the next store simply overwrites the bad file
    key = kernels.commit(spec, "rows64_f32", "float32", {"block_rows": 8})
    doc = json.load(open(kcache.cache_path()))
    assert doc["format"] == kcache.FORMAT and key in doc["entries"]


def test_stale_kernel_version_stops_matching(kdir):
    """Bumping a spec's version invalidates old tuned entries by
    construction: the version is part of the key, so they stop
    matching — no migration pass needed."""
    spec = kernels.get_kernel("layer_norm_residual")
    good = kernels.cache_key(spec, "rows64_f32", "float32")
    stale = good.replace(f"|v{spec.version}|", "|v999|")
    kcache.store({stale: {"config": {"block_rows": 128},
                          "kernel_version": 999}})
    kernels.invalidate()
    assert kernels.warm_cache() == 0        # wrong-version entry skipped
    cfg = kernels.resolve("layer_norm_residual", "rows64_f32", "float32")
    assert cfg == spec.default_config


def test_warm_start_zero_measurements(kdir):
    """The kernel_smoke contract in-process: with a committed winner on
    disk, a fresh resolution takes the disk hit — the tuner never runs
    even when tuning is explicitly allowed."""
    spec = kernels.get_kernel("layer_norm_residual")
    arrays, params = spec.make_args({"rows": 64, "f": 64})
    sig, dt = spec.signature(*arrays, **params)
    kernels.commit(spec, sig, dt, {"block_rows": 16}, 0.5)
    kernels.invalidate()                    # "relaunch"
    r0 = telemetry.counter("kernel.tune_measurements").value
    m0 = telemetry.counter("kernel.tune_ms").value
    cfg = kernels.resolve("layer_norm_residual", sig, dt,
                          tune_args=(arrays, params), allow_tune=True)
    assert cfg == {"block_rows": 16}
    assert telemetry.counter("kernel.tune_measurements").value == r0
    assert telemetry.counter("kernel.tune_ms").value == m0


def test_autotune_commits_winner(kdir):
    spec = kernels.get_kernel("zero_flatten_pad")
    arrays, params = spec.make_args({"sizes": (64, 129)})
    sig, dt = spec.signature(*arrays, **params)
    r0 = telemetry.counter("kernel.tune_measurements").value
    cfg, ms, rows = kernels.tune(spec, arrays, params=params,
                                 warmup=0, runs=1)
    assert rows and rows[0]["config"] == spec.default_config
    assert cfg in kernels.candidates(spec)
    assert telemetry.counter("kernel.tune_measurements").value > r0
    key = kernels.commit(spec, sig, dt, cfg, ms)
    assert kcache.load()[key]["config"] == cfg
    kernels.invalidate()
    assert kernels.resolve("zero_flatten_pad", sig, dt) == cfg


def test_default_path_ticks_one_miss(kdir):
    m0 = telemetry.counter("kernel.cache_misses").value
    spec = kernels.get_kernel("flash_attention")
    cfg = kernels.resolve("flash_attention", "sq64_sk64_d8_c0", "float32")
    assert cfg == spec.default_config
    kernels.resolve("flash_attention", "sq64_sk64_d8_c0", "float32")
    assert telemetry.counter("kernel.cache_misses").value == m0 + 1


# -- env override precedence (the satellite fix) ----------------------------

def test_flash_env_override_precedence(kdir, monkeypatch):
    rng = onp.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, 128, 64), "float32")
               for _ in range(3))
    monkeypatch.delenv("MXNET_TPU_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("MXNET_TPU_FLASH_BLOCK_K", raising=False)
    spec = kernels.get_kernel("flash_attention")
    assert att._resolve_flash_blocks(q, k, v, False, 0.125) == \
        (spec.default_config["block_q"], spec.default_config["block_k"])
    # the override wins immediately AND invalidates the cached choice
    monkeypatch.setenv("MXNET_TPU_FLASH_BLOCK_Q", "128")
    assert att._resolve_flash_blocks(q, k, v, False, 0.125)[0] == 128
    monkeypatch.setenv("MXNET_TPU_FLASH_BLOCK_K", "256")
    assert att._resolve_flash_blocks(q, k, v, False, 0.125) == (128, 256)
    # dropping it falls back to registry resolution, not a stale memo
    monkeypatch.delenv("MXNET_TPU_FLASH_BLOCK_Q")
    monkeypatch.delenv("MXNET_TPU_FLASH_BLOCK_K")
    assert att._resolve_flash_blocks(q, k, v, False, 0.125) == \
        (spec.default_config["block_q"], spec.default_config["block_k"])


def test_flash_env_override_beats_disk_entry(kdir, monkeypatch):
    spec = kernels.get_kernel("flash_attention")
    rng = onp.random.RandomState(6)
    q, k, v = (jnp.asarray(rng.randn(1, 128, 64), "float32")
               for _ in range(3))
    sig, dt = spec.signature(q, k, v)
    kernels.commit(spec, sig, dt, {"block_q": 256, "block_k": 256})
    monkeypatch.setenv("MXNET_TPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("MXNET_TPU_FLASH_BLOCK_K", "128")
    assert att._resolve_flash_blocks(q, k, v, False, 0.125) == (128, 128)
    monkeypatch.delenv("MXNET_TPU_FLASH_BLOCK_Q")
    monkeypatch.delenv("MXNET_TPU_FLASH_BLOCK_K")
    assert att._resolve_flash_blocks(q, k, v, False, 0.125) == (256, 256)


# -- layout plumbing + telemetry -------------------------------------------

def test_zero_pad_unit_follows_registry(kdir):
    from mxnet_tpu.optimizer.fused_step import zero_pad_unit
    spec = kernels.get_kernel("zero_flatten_pad")
    assert zero_pad_unit(4) % 4 == 0
    kernels.commit(spec, "ndev4", "any", {"pad_multiple": 128})
    kernels.invalidate()
    assert zero_pad_unit(4) == 4 * 128


def test_record_fallback_ticks_both_counters():
    f0 = telemetry.counter("kernel.fallbacks").value
    k0 = telemetry.counter("kernel.layer_norm_residual.fallbacks").value
    kernels.record_fallback("layer_norm_residual")
    assert telemetry.counter("kernel.fallbacks").value == f0 + 1
    assert telemetry.counter(
        "kernel.layer_norm_residual.fallbacks").value == k0 + 1
    assert set(kernels.stats()) >= {"cache_hits", "cache_misses",
                                    "tune_ms", "tune_measurements",
                                    "fallbacks"}


def test_step_record_carries_kernel_section(tmp_path, monkeypatch):
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXNET_TELEMETRY_JSONL", path)
    telemetry.clear_sinks()
    try:
        tok = telemetry.begin_step()
        assert tok is not None
        telemetry.counter("kernel.cache_hits").inc(2)
        telemetry.counter("kernel.tune_ms").inc(5.0)
        telemetry.counter("kernel.tune_measurements").inc(9)
        telemetry.end_step(tok, "kernel_test")
        rec = telemetry.last_record()
        assert rec["kernel"]["cache_hits"] == 2
        assert rec["kernel"]["tune_ms"] == 5.0       # a stalled step
        assert rec["kernel"]["tune_measurements"] == 9
        assert rec["kernel"]["cache_misses"] == 0
    finally:
        monkeypatch.delenv("MXNET_TELEMETRY_JSONL")
        telemetry.clear_sinks()
        telemetry.enabled()


def test_profiler_counters_kernel_section():
    from mxnet_tpu import profiler
    c = profiler.counters()
    assert set(c["kernel"]) == {"cache_hits", "cache_misses", "tune_ms",
                                "tune_measurements", "fallbacks"}
