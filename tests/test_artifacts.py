"""Executable artifact store (mxnet_tpu/artifacts) tests.

Store contract: content-addressed round-trip of REAL AOT-serialized
executables, every defect (corruption, version skew, stale key
material) degrading to a recompile instead of a crash, and the
MXNET_ARTIFACT_MAX_MB eviction budget.  The cross-process test is the
zero-compile cold-start guarantee itself: a child process populates the
store from a serving replica + an imperative training loop, a second
child reaches its first request / first step with ``compile.count ==
0``, and the parent deserializes the child's executables directly
(bitwise-identical outputs, no tracing).
"""
import hashlib
import json
import os
import subprocess
import sys

import numpy as onp
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu import kernels, telemetry
from mxnet_tpu.artifacts import store
from mxnet_tpu.kernels import cache as kcache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COUNTER_KEYS = ("hits", "misses", "saves", "bytes", "load_ms",
                 "deserialize_failures")


def _counters():
    return {k: telemetry.counter(f"artifact.{k}").value
            for k in _COUNTER_KEYS}


def _delta(before, after):
    return {k: after[k] - before[k] for k in _COUNTER_KEYS}


@pytest.fixture
def art_dir(tmp_path, monkeypatch):
    d = tmp_path / "artifacts"
    monkeypatch.setenv("MXNET_ARTIFACT_DIR", str(d))
    monkeypatch.delenv("MXNET_ARTIFACT_MAX_MB", raising=False)
    return d


def _compiled(scale=2.0, n=16):
    x = jnp.arange(n, dtype=jnp.float32)
    compiled = jax.jit(lambda v: v * scale + 1.0).lower(x).compile()
    return compiled, x


# -- store contract ---------------------------------------------------------

def test_round_trip_and_miss(art_dir):
    before = _counters()
    compiled, x = _compiled()
    assert store.save("unit", ("sig", 1), compiled, meta={"k": 7})
    art = store.load("unit", ("sig", 1))
    assert art is not None
    assert art.kind == "unit" and art.meta == {"k": 7} and art.nbytes > 0
    onp.testing.assert_array_equal(onp.asarray(art.compiled(x)),
                                   onp.asarray(compiled(x)))
    assert store.load("unit", ("sig", 2)) is None  # different content key
    d = _delta(before, _counters())
    assert d["saves"] == 1 and d["hits"] == 1 and d["misses"] == 1
    assert d["bytes"] > 0 and d["load_ms"] > 0
    assert d["deserialize_failures"] == 0


def test_store_off_is_inert(monkeypatch):
    monkeypatch.delenv("MXNET_ARTIFACT_DIR", raising=False)
    assert not store.enabled()
    before = _counters()
    compiled, _ = _compiled()
    assert store.save("unit", "sig", compiled) is False
    assert store.load("unit", "sig") is None
    assert list(store.load_all("unit")) == []
    assert _delta(before, _counters()) == {k: 0 for k in _COUNTER_KEYS}


@pytest.mark.parametrize("garbage", [
    b"",                                    # truncated to nothing
    b"not a pickle at all",                 # unpicklable
    b"\x80\x04N.",                          # pickles to None, not a dict
])
def test_corrupt_artifact_is_miss_not_fatal(art_dir, garbage):
    compiled, _ = _compiled()
    assert store.save("unit", "sig", compiled)
    path = store.artifact_path("unit", "sig")
    with open(path, "wb") as f:
        f.write(garbage)
    before = _counters()
    assert store.load("unit", "sig") is None
    assert list(store.load_all("unit")) == []
    d = _delta(before, _counters())
    assert d["misses"] == 1 and d["deserialize_failures"] >= 1


def test_stale_key_material_stops_matching(art_dir):
    """An artifact minted under another amp token / jax version /
    topology strands by construction: the recorded key material no
    longer re-derives, so both load() and the load_all() drain skip it
    as a plain miss (no deserialize attempt, no failure tick)."""
    import pickle
    compiled, _ = _compiled()
    assert store.save("unit", "sig", compiled)
    path = store.artifact_path("unit", "sig")
    with open(path, "rb") as f:
        doc = pickle.load(f)
    doc["key_material"] = "minted-under-another-environment"
    with open(path, "wb") as f:
        pickle.dump(doc, f, protocol=pickle.HIGHEST_PROTOCOL)
    before = _counters()
    assert store.load("unit", "sig") is None
    assert list(store.load_all("unit")) == []
    d = _delta(before, _counters())
    assert d["misses"] == 1 and d["deserialize_failures"] == 0


def test_eviction_budget(art_dir, monkeypatch):
    """MXNET_ARTIFACT_MAX_MB: oldest artifacts (mtime) fall out past
    the budget; the just-committed artifact is never the victim."""
    compiled, _ = _compiled()
    assert store.save("unit", ("s", 0), compiled)
    size = os.path.getsize(store.artifact_path("unit", ("s", 0)))
    # budget fits ~2 artifacts; committing a 3rd must evict the oldest
    monkeypatch.setenv("MXNET_ARTIFACT_MAX_MB",
                       repr(2.5 * size / 1048576.0))
    os.utime(store.artifact_path("unit", ("s", 0)), (1.0, 1.0))
    assert store.save("unit", ("s", 1), compiled)
    assert store.save("unit", ("s", 2), compiled)
    assert not os.path.exists(store.artifact_path("unit", ("s", 0)))
    assert os.path.exists(store.artifact_path("unit", ("s", 2)))
    st = store.stats()
    assert st["files"] == 2 and st["disk_bytes"] <= 2.5 * size


def test_load_all_filters_kind(art_dir):
    compiled, x = _compiled()
    assert store.save("ka", ("s", 0), compiled, meta={"i": 0})
    assert store.save("ka", ("s", 1), compiled, meta={"i": 1})
    assert store.save("kb", ("s", 0), compiled)
    arts = list(store.load_all("ka"))
    assert sorted(a.meta["i"] for a in arts) == [0, 1]
    assert all(a.kind == "ka" for a in arts)
    onp.testing.assert_array_equal(onp.asarray(arts[0].compiled(x)),
                                   onp.asarray(compiled(x)))


# -- satellite: batched kernel-cache commits --------------------------------

def test_batched_store_single_write(tmp_path, monkeypatch):
    """A tune sweep's winners land in ONE read-merge-replace write:
    store() calls inside batched_store() buffer, the outermost exit
    flushes them together (even through an error — measured winners are
    never dropped)."""
    monkeypatch.setenv("MXNET_KERNEL_CACHE_DIR", str(tmp_path))
    writes = []
    real = kcache._write_merged
    monkeypatch.setattr(kcache, "_write_merged",
                        lambda e: writes.append(dict(e)) or real(e))
    with kcache.batched_store():
        for i in range(3):
            assert kcache.store({f"k{i}": {"config": {"b": i}}})
        with kcache.batched_store():        # re-entrant: no inner flush
            assert kcache.store({"k3": {"config": {"b": 3}}})
        assert writes == [] and not os.path.exists(kcache.cache_path())
    assert len(writes) == 1 and sorted(writes[0]) == ["k0", "k1", "k2", "k3"]
    assert sorted(kcache.load()) == ["k0", "k1", "k2", "k3"]
    # flush-on-error: winners measured before the crash still commit
    with pytest.raises(RuntimeError):
        with kcache.batched_store():
            kcache.store({"k4": {"config": {"b": 4}}})
            raise RuntimeError("tuner died")
    assert len(writes) == 2 and "k4" in kcache.load()


# -- satellite: warm_cache ticks kernel.warm_loaded -------------------------

def test_warm_cache_ticks_warm_loaded(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_KERNEL_CACHE_DIR", str(tmp_path))
    kernels.invalidate()
    spec = kernels.get_kernel("layer_norm_residual")
    kernels.commit(spec, "rows64_f32", "float32", {"block_rows": 16}, 0.5)
    kernels.invalidate()                    # "relaunch"
    before = telemetry.counter("kernel.warm_loaded").value
    n = kernels.warm_cache()
    assert n >= 1
    assert telemetry.counter("kernel.warm_loaded").value - before == n
    assert kernels.warm_cache() == 0        # already memoized: no re-tick
    assert telemetry.counter("kernel.warm_loaded").value - before == n
    kernels.invalidate()


# -- satellite: cross-process zero-compile round trip -----------------------

_LEG = r'''
import hashlib, json, sys
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, telemetry
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.imperative import cached_step
from mxnet_tpu.serving import InferenceEngine

leg = sys.argv[1]
mx.random.seed(0)
onp.random.seed(0)

# serving replica: bucketed engine, one warm bucket, one batch
snet = nn.Dense(4, in_units=8)
snet.initialize()
eng = InferenceEngine(snet, example_shape=(8,), dtype="float32")
eng.warmup([4])
x = onp.random.RandomState(3).randn(4, 8).astype(onp.float32)
out = eng.infer_batch([x[i] for i in range(4)])[0]
arr = out.asnumpy() if hasattr(out, "asnumpy") else onp.asarray(out)
s_sha = hashlib.sha256(onp.ascontiguousarray(arr).tobytes()).hexdigest()

# imperative trainer: cached whole-step capture + eager/backward funnels
net = nn.Sequential()
for _ in range(2):
    net.add(nn.Dense(4, in_units=4, activation="relu"))
net.add(nn.Dense(1, in_units=4))
net.initialize()
trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                  kvstore=None)
xb = nd.array(onp.random.RandomState(1).randn(8, 4).astype("float32"))
for _ in range(4):
    with autograd.record():
        y = net(xb)
        loss = (y * y).mean()
    loss.backward()
    trainer.step(8)
w = onp.concatenate([p._data_nd().asnumpy().ravel()
                     for p in net.collect_params().values()])
w_sha = hashlib.sha256(onp.ascontiguousarray(w).tobytes()).hexdigest()

print("RESULT " + json.dumps({
    "leg": leg, "serving_sha": s_sha, "weights_sha": w_sha,
    "compile_count": telemetry.counter("compile.count").value,
    "cs_compiles": cached_step.stats()["compiles"],
    "art_hits": telemetry.counter("artifact.hits").value,
    "art_saves": telemetry.counter("artifact.saves").value}))
'''


def _run_leg(leg, art):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_ARTIFACT_DIR"] = str(art)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _LEG, leg], env=env,
                          cwd=_REPO, timeout=280, capture_output=True,
                          text=True)
    assert proc.returncode == 0, \
        f"{leg} leg failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_cross_process_zero_compile(tmp_path, monkeypatch):
    """The ISSUE acceptance gate end to end: a cold process pays every
    compile and commits the executables; a warm process — serving
    bucket AND restarted imperative trainer — reaches its first
    request / first step with ``compile.count == 0``, producing
    bitwise-identical outputs; the parent then deserializes the
    child's executables straight from the store."""
    art = tmp_path / "store"
    cold = _run_leg("cold", art)
    assert cold["compile_count"] > 0 and cold["art_saves"] > 0
    warm = _run_leg("warm", art)
    assert warm["compile_count"] == 0, warm
    assert warm["cs_compiles"] == 0, warm
    assert warm["art_hits"] > 0
    assert warm["serving_sha"] == cold["serving_sha"]
    assert warm["weights_sha"] == cold["weights_sha"]
    # parent-side deserialization: the child's serving bucket and
    # cached-step executables load here without tracing anything
    monkeypatch.setenv("MXNET_ARTIFACT_DIR", str(art))
    buckets = list(store.load_all("serving_bucket"))
    assert buckets, "no serving bucket artifact committed"
    assert all({"n_out", "treedef", "bucket"} <= set(a.meta)
               for a in buckets)
    assert list(store.load_all("cached_step")), \
        "no cached-step artifact committed"
