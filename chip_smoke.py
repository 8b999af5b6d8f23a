#!/usr/bin/env python3
"""Chip smoke: the quickest proof that tpu-mx still starts on a TPU.

Drives the two user-facing main paths once, at the full width of the
models the repo supports, through the entry points a user calls, in ONE
process on ONE chip:

    device   jax.devices(); anything but a TPU is an error
    kernels  the four registered Pallas kernels, compiled, against
             their registered ``fallback`` oracles
    train    ResNet-50 (SPMDTrainer bf16 bs256, eager Gluon fp32 bs64)
             and the 8-layer TransformerLM (bf16, 8 x 2048, flash
             attention forward and backward)
    serve    ServingServer + InferenceEngine over HTTP (/predict) and
             DecodeEngine + DecodeScheduler on the same server
             (/generate), plain and speculative

Each phase prints one JSON line (shapes, wall and compile seconds,
compile count, what it compared and the largest difference).  The last
line of stdout is the verdict, on success exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0; on any failure ``"ok": false`` and a non-zero
exit.  There is no CPU continuation and no fallback.

``--multichip`` (four chips) runs ONLY the sharded dp2 x tp2 training
phase and its one-device comparison; the last line then has
``"count": 4``.  ``--rehearse`` walks the same control flow at tiny
sizes on whatever backend there is (the CPU, kernels interpreted): it
labels every line ``"rehearsal": true`` and always ends ``"ok": false``
with a non-zero exit, so it can never be taken for a chip run.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import threading
import time
import traceback
import urllib.request

import numpy as onp

# Widths are the repo's own (bench.py: ResNet-50 224/bs256/bs64, the
# 42.65M-parameter TransformerLM 8 x 2048; the decode model at the LM's
# width).  Only step, request and prefill-bucket counts are small.
FULL = {
    "flash": {"bh": 64, "sq": 2048, "sk": 2048, "d": 64},
    "lnr": {"rows": 16384, "f": 512},
    "cnn": {"model": "resnet50_v1", "classes": 1000, "image": 224,
            "spmd_batch": 256, "gluon_batch": 64},
    "lm": {"vocab": 32000, "units": 512, "layers": 8, "heads": 8,
           "batch": 8, "seq": 2048},
    "mlp4d": {"rows": 16384, "units": 512, "hidden": 2048, "layers": 8},
    "serve_buckets": (1, 4),
    "decode": {"vocab": 32000, "dim": 512, "heads": 8, "layers": 8,
               "draft_dim": 256, "draft_heads": 4, "draft_layers": 2,
               "slots": 8, "page_size": 16, "pages_per_slot": 40,
               "prefill": 128, "spec_k": 4, "max_new": 32,
               "prompts": (16, 48, 100, 128, 200, 256, 384, 512)},
    # Falcon-H1-34B's widths at the benchmark cell's geometry
    # (chipbench/configs/falcon_h1_34b.json): 20 query heads over 4 KV
    # heads of 128, 96 slots x 8 pages of 128; 32 state-space heads of
    # 128 x 256 in 2 groups
    "hybrid": {"slots": 96, "page_size": 128, "pages_per_slot": 8,
               "h": 20, "kv_h": 4, "d": 128,
               "ssm_h": 32, "ssm_p": 128, "ssm_n": 256, "ssm_g": 2},
}
TINY = {
    "flash": {"bh": 2, "sq": 256, "sk": 256, "d": 16},
    "lnr": {"rows": 64, "f": 32},
    "cnn": {"model": "resnet18_v1", "classes": 10, "image": 32,
            "spmd_batch": 4, "gluon_batch": 2},
    "lm": {"vocab": 64, "units": 32, "layers": 1, "heads": 2,
           "batch": 4, "seq": 128},
    "mlp4d": {"rows": 64, "units": 16, "hidden": 32, "layers": 2},
    "serve_buckets": (1, 4),
    "decode": {"vocab": 64, "dim": 32, "heads": 2, "layers": 1,
               "draft_dim": 16, "draft_heads": 2, "draft_layers": 1,
               "slots": 4, "page_size": 8, "pages_per_slot": 8,
               "prefill": 16, "spec_k": 2, "max_new": 6,
               "prompts": (3, 9, 20, 33)},
    "hybrid": {"slots": 4, "page_size": 8, "pages_per_slot": 4,
               "h": 10, "kv_h": 2, "d": 16,
               "ssm_h": 4, "ssm_p": 16, "ssm_n": 16, "ssm_g": 2},
}

# largest |kernel - oracle| over largest |oracle|, bf16 operands against
# the float32 oracle (tests/test_kernels.py pins bf16 at 2e-2)
TOL_BF16 = 2e-2
TOL_BF16_GRAD = 4e-2
# served ResNet logits against the eager forward, same scale-free form
TOL_SERVE = 2e-2
# paged first token against the dense path: how far below the dense
# maximum its dense logit may sit, as a share of the logits' spread
TOL_FIRST_LOGIT = 2e-2
# sharded against one-device training losses, relative
TOL_LOSS = 2e-2


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileWatch:
    """Process-wide compile accounting from JAX's own monitoring
    events: every compile request (a persistent-cache hit included) and
    the seconds it took."""

    def __init__(self):
        import jax
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration

    def snapshot(self):
        return self.requests, self.seconds, self.cache_hits


class Smoke:
    def __init__(self, cfg, rehearse, seed):
        self.cfg = cfg
        self.rehearse = rehearse
        self.seed = seed
        self.failed = False
        self.watch = CompileWatch()

    def emit(self, line):
        if self.rehearse:
            line = {"rehearsal": True, **line}
        print(json.dumps(line), flush=True)

    def phase(self, name, fn):
        """Run one phase; print its line.  A phase that raises is
        reported with its traceback and fails the run; later phases
        still run so one chip call shows every fault."""
        t0 = time.perf_counter()
        c0 = self.watch.snapshot()
        ok, info = True, {}
        try:
            info = fn() or {}
        except Exception as e:      # boundary: record, report, go on
            traceback.print_exc()
            ok = False
            info = {"error": f"{type(e).__name__}: {e}"[:500]}
        c1 = self.watch.snapshot()
        self.failed |= not ok
        self.emit({"phase": name, "ok": ok,
                   "wall_s": round(time.perf_counter() - t0, 2),
                   "compiles": c1[0] - c0[0],
                   "compile_s": round(c1[1] - c0[1], 2),
                   "cache_hits": c1[2] - c0[2], **info})
        gc.collect()

    def compiles(self):
        return self.watch.requests


def check_on(arr, devices, what):
    """``arr`` lives exactly on ``devices`` — not on a host device some
    fallback picked."""
    got = set(arr.devices())
    check(got == set(devices),
          f"{what} is on {sorted(map(str, got))}, expected "
          f"{sorted(map(str, devices))}")


def _scaled_err(got, ref):
    """max |got - ref| / max |ref| in float32."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    check(bool(jnp.isfinite(got).all()), "non-finite values")
    return float(jnp.abs(got - ref).max() / jnp.abs(ref).max())


def _f32(args):
    import jax.numpy as jnp
    return tuple(a.astype(jnp.float32)
                 if jnp.issubdtype(a.dtype, jnp.floating) else a
                 for a in args)


# -- phase: kernels ----------------------------------------------------------

def kernels_phase(sm: Smoke):
    """Each registered kernel, compiled at the main path's shape from
    its spec's own ``make_args``, against its ``fallback`` oracle run
    in float32 at the highest matmul precision."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels

    dec, hyb = sm.cfg["decode"], sm.cfg["hybrid"]
    cases = {
        "flash_attention": dict(sm.cfg["flash"], causal=True),
        "paged_attention": {"slots": dec["slots"],
                            "pages_per_slot": dec["pages_per_slot"],
                            "page_size": dec["page_size"],
                            "h": dec["heads"],
                            "d": dec["dim"] // dec["heads"]},
        # grouped-query: the same kernel against pools of fewer heads
        "paged_attention.gqa": {k: hyb[k] for k in (
            "slots", "pages_per_slot", "page_size", "h", "kv_h", "d")},
        "rope": {"r": dec["slots"] * (dec["spec_k"] + 1),
                 "h": dec["heads"], "d": dec["dim"] // dec["heads"]},
        "layer_norm_residual": sm.cfg["lnr"],
        "ssm_update": {"slots": hyb["slots"], "h": hyb["ssm_h"],
                       "p": hyb["ssm_p"], "n": hyb["ssm_n"],
                       "g": hyb["ssm_g"]},
    }
    out = {"dtype": "bfloat16", "tol": TOL_BF16, "tol_grad": TOL_BF16_GRAD,
           "shapes": {}, "max_err": {}}
    for name, case in cases.items():
        spec = kernels.get_kernel(name.split(".")[0])
        arrays, params = spec.make_args(dict(case, dtype="bfloat16"))
        out["shapes"][name] = [list(a.shape) for a in arrays]

        def run(*a, spec=spec, params=params):
            return spec.run(spec.default_config, *a, **params)

        def oracle(*a, spec=spec, params=params):
            return spec.fallback(*a, **params)

        got = jax.jit(run)(*arrays)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(oracle)(*_f32(arrays))
        # a kernel with several outputs (ssm_update: state and y) is
        # held to the tolerance on each
        outs = jax.tree_util.tree_leaves(got)
        for o in outs:
            check_on(o, jax.devices()[:1], f"{name} output")
        err = max(_scaled_err(o, r) for o, r in
                  zip(outs, jax.tree_util.tree_leaves(ref)))
        out["max_err"][name] = err
        check(err <= TOL_BF16, f"{name}: error {err:.3g} > {TOL_BF16}")
        if name != "flash_attention":
            continue
        # backward too: the training path runs the Pallas dk/dv and dq
        # kernels, so pin the three gradients of a random projection
        w = jnp.asarray(onp.random.RandomState(sm.seed).randn(*got.shape),
                        jnp.float32)

        def grads(fn):
            return jax.jit(jax.grad(
                lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum(),
                argnums=(0, 1, 2)))

        g_got = grads(run)(*arrays)
        with jax.default_matmul_precision("highest"):
            g_ref = grads(oracle)(*_f32(arrays))
        for tag, a, b in zip(("dq", "dk", "dv"), g_got, g_ref):
            err = _scaled_err(a, b)
            out["max_err"][f"flash_attention.{tag}"] = err
            check(err <= TOL_BF16_GRAD,
                  f"flash {tag}: error {err:.3g} > {TOL_BF16_GRAD}")
    return out


# -- phase: train ------------------------------------------------------------

def _param_arrays(net):
    return [(k, p.data()._data) for k, p in net.collect_params().items()]


def _three_steps(sm, step, net, devices, what, settle=1):
    """Three steps on a fixed batch: finite losses, third below first,
    parameters on ``devices``, no compile request once ``settle`` steps
    have run (one for a compiled step; two for the eager Gluon funnel,
    whose whole-step capture compiles on the second by design)."""
    losses, late_compiles = [], 0
    for i in range(3):
        c0 = sm.compiles()
        loss = step()
        losses.append(float(loss))
        if i >= settle:
            late_compiles += sm.compiles() - c0
    check(all(math.isfinite(x) for x in losses),
          f"{what}: non-finite loss {losses}")
    check(losses[2] < losses[0],
          f"{what}: loss did not fall on a fixed batch: {losses}")
    check(late_compiles == 0,
          f"{what}: {late_compiles} compile(s) after step {settle}")
    for name, arr in _param_arrays(net):
        check_on(arr, devices, f"{what} parameter {name}")
    return losses


def _cnn(sm):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    from mxnet_tpu.ndarray import NDArray
    c = sm.cfg["cnn"]
    mx.random.seed(sm.seed)
    net = get_model(c["model"], classes=c["classes"])
    net.initialize(init=mx.initializer.Xavier())
    # one tiny eager batch finishes the deferred parameter init
    net(NDArray(onp.zeros((1, 3, c["image"], c["image"]), onp.float32)))
    return net


def _cnn_batch(sm, batch):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import NDArray
    c = sm.cfg["cnn"]
    k1, k2 = jax.random.split(jax.random.PRNGKey(sm.seed))
    data = jax.random.normal(k1, (batch, 3, c["image"], c["image"]),
                             jnp.float32)
    label = jax.random.randint(k2, (batch,), 0, c["classes"])
    return NDArray(data), NDArray(label.astype(jnp.float32))


def train_cnn_spmd_phase(sm: Smoke):
    """bench.py's headline row: SPMDTrainer, bf16, three ``step`` calls
    and one fused ``run_steps`` window of 4."""
    import jax

    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    c = sm.cfg["cnn"]
    net = _cnn(sm)
    devices = jax.devices()[:1]
    trainer = SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
        optimizer_params={"learning_rate": 0.02, "momentum": 0.9,
                          "wd": 1e-4},
        mesh=make_mesh({"dp": 1}, devices), dtype="bfloat16")
    data, label = _cnn_batch(sm, c["spmd_batch"])

    losses = _three_steps(
        sm, lambda: trainer.step(data, label).asnumpy().mean(),
        net, devices, "SPMDTrainer.step")
    window = trainer.run_steps(data, label, 4)
    check_on(window._data, devices, "run_steps losses")
    window = [float(x) for x in window.asnumpy()]
    check(len(window) == 4 and all(math.isfinite(x) for x in window),
          f"run_steps window: {window}")
    check(window[-1] < losses[0],
          f"run_steps did not continue the descent: {losses} {window}")
    return {"model": c["model"], "dtype": "bfloat16",
            "data": [c["spmd_batch"], 3, c["image"], c["image"]],
            "step_losses": losses, "run_steps_losses": window,
            "compared": "loss[2] < loss[0]; window below loss[0]; "
                        "parameters and losses on the device"}


def train_cnn_gluon_phase(sm: Smoke):
    """The eager Gluon funnel: hybridize, autograd.record,
    Trainer.step, fp32."""
    import jax

    from mxnet_tpu import autograd, gluon
    c = sm.cfg["cnn"]
    net = _cnn(sm)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.02, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    data, label = _cnn_batch(sm, c["gluon_batch"])
    devices = jax.devices()[:1]
    outs = []

    def step():
        with autograd.record():
            out = net(data)
            loss = loss_fn(out, label)
        loss.backward()
        trainer.step(c["gluon_batch"])
        outs.append(out)
        return loss.asnumpy().mean()

    losses = _three_steps(sm, step, net, devices, "gluon.Trainer",
                          settle=2)
    check_on(outs[-1]._data, devices, "hybridized forward output")
    check(outs[-1].shape == (c["gluon_batch"], c["classes"]),
          f"output shape {outs[-1].shape}")
    return {"model": c["model"], "dtype": "float32",
            "data": [c["gluon_batch"], 3, c["image"], c["image"]],
            "step_losses": losses,
            "compared": "loss[2] < loss[0]; parameters and output on "
                        "the device"}


def _lm(sm, use_flash=True):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
    from mxnet_tpu.ndarray import NDArray
    c = sm.cfg["lm"]
    mx.random.seed(sm.seed)
    net = TransformerLM(c["vocab"], units=c["units"],
                        num_layers=c["layers"], num_heads=c["heads"],
                        max_len=c["seq"], tie_weights=True,
                        use_flash=use_flash)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 8), onp.float32)))
    return net


def _lm_batch(sm):
    c = sm.cfg["lm"]
    rng = onp.random.RandomState(sm.seed)
    toks = rng.randint(0, c["vocab"], (c["batch"], c["seq"] + 1))
    return (toks[:, :-1].astype(onp.float32),
            toks[:, 1:].astype(onp.float32))


def train_lm_phase(sm: Smoke):
    """bench.py's transformer row: SPMDTrainer, bf16, adam, the Pallas
    flash kernel in forward and backward."""
    import jax

    from mxnet_tpu import kernels
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    c = sm.cfg["lm"]
    net = _lm(sm)
    devices = jax.devices()[:1]
    trainer = SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), optimizer="adam",
        optimizer_params={"learning_rate": 3e-4},
        mesh=make_mesh({"dp": 1}, devices), dtype="bfloat16")
    data, label = _lm_batch(sm)
    resolved0 = kernels.stats()["resolved"]

    losses = _three_steps(
        sm, lambda: trainer.step(data, label).asnumpy().mean(),
        net, devices, "TransformerLM SPMDTrainer.step")
    check(kernels.stats()["resolved"] > resolved0,
          "the step traced no registered kernel (flash attention)")
    check(abs(losses[0] - math.log(c["vocab"])) < 1.0,
          f"first loss {losses[0]:.3f} is not near ln(vocab) "
          f"{math.log(c['vocab']):.3f}")
    n_params = sum(int(onp.prod(p.shape))
                   for p in net.collect_params().values())
    return {"model": "TransformerLM", "dtype": "bfloat16",
            "params": n_params, "data": [c["batch"], c["seq"]],
            "step_losses": losses, "ln_vocab": math.log(c["vocab"]),
            "compared": "loss[2] < loss[0]; loss[0] within 1.0 of "
                        "ln(vocab); parameters on the device"}


# -- phase: serve ------------------------------------------------------------

def _post(url, body, timeout=300):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _in_threads(fns):
    """Run ``fns`` concurrently; returns their results in order and
    re-raises the first failure."""
    box = [None] * len(fns)

    def call(i, fn):
        try:
            box[i] = (True, fn())
        except Exception as e:      # handed back to the caller below
            box[i] = (False, e)

    threads = [threading.Thread(target=call, args=(i, fn), daemon=True)
               for i, fn in enumerate(fns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
        check(not t.is_alive(), "client thread did not finish in 600 s")
    for ok, val in box:
        if not ok:
            raise val
    return [val for _, val in box]


def serve_predict_phase(sm: Smoke, server_box):
    """ServingServer over HTTP: warm the buckets, then 8 POST /predict
    in bursts of 1, 3 and 4 concurrent clients (batches of mixed size),
    each answer against the eager forward."""
    import jax

    from mxnet_tpu import autograd, telemetry
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.serving import ServingServer
    c = sm.cfg["cnn"]
    buckets = tuple(sm.cfg["serve_buckets"])
    net = _cnn(sm)
    shape = (3, c["image"], c["image"])
    server = ServingServer(
        net,
        engine_args={"example_shape": shape, "dtype": "float32",
                     "bucket_sizes": buckets},
        batcher_args={"max_batch_size": max(buckets),
                      "max_delay_ms": 100.0})
    host, port = server.start_http()
    server_box.append((server, host, port))
    tags = server.warmup(list(buckets))
    for tag in tags:
        # a bucket whose compile failed latches to eager dispatch; the
        # smoke must not pass through that
        n = telemetry.counter(f"serving.bucket.{tag}.compiles").value
        check(n == 1, f"bucket {tag} compiled {n} times (eager latch?)")
    c_warm = sm.compiles()

    rng = onp.random.RandomState(sm.seed)
    xs = rng.randn(8, *shape).astype(onp.float32)
    url = f"http://{host}:{port}/predict"
    answers = []
    for lo, hi in ((0, 1), (1, 4), (4, 8)):
        answers += _in_threads(
            [lambda x=x: _post(url, {"data": x.tolist()})["output"]
             for x in xs[lo:hi]])
    c_served = sm.compiles()

    # the eager forward, one example at a time (the shapes the init
    # forward already dispatched op by op)
    with autograd.pause(train_mode=False):
        eager = [net(NDArray(x[None])) for x in xs]
    check_on(eager[0]._data, jax.devices()[:1], "eager forward output")
    err = _scaled_err(onp.asarray(answers, onp.float32),
                      onp.concatenate([e.asnumpy() for e in eager]))
    check(err <= TOL_SERVE, f"/predict differs from eager: {err:.3g}")
    check(c_served == c_warm,
          f"{c_served - c_warm} compile(s) while serving /predict")
    dispatched = {
        tag: telemetry.counter(f"serving.bucket.{tag}.dispatches").value
        for tag in tags}
    check(sum(dispatched.values()) >= 3, f"dispatches {dispatched}")
    return {"model": c["model"], "dtype": "float32", "requests": 8,
            "buckets": tags, "dispatches": dispatched,
            "compared": "8 answers vs eager forward",
            "max_err": err, "tol": TOL_SERVE}


def serve_generate_phase(sm: Smoke, server_box):
    """DecodeEngine + DecodeScheduler attached to the same server:
    POST /generate, once plain and once speculative."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import DecodeEngine, DecodeModel, DecodeScheduler
    d = sm.cfg["decode"]
    check(server_box, "the /predict phase left no server to attach to")
    server, host, port = server_box[0]
    url = f"http://{host}:{port}/generate"
    model = DecodeModel(d["vocab"], dim=d["dim"], n_heads=d["heads"],
                        n_layers=d["layers"], seed=sm.seed,
                        dtype="bfloat16")
    draft = DecodeModel(d["vocab"], dim=d["draft_dim"],
                        n_heads=d["draft_heads"],
                        n_layers=d["draft_layers"], seed=sm.seed + 1,
                        dtype="bfloat16")
    rng = onp.random.RandomState(sm.seed)
    prompts = [rng.randint(0, d["vocab"], n).tolist()
               for n in d["prompts"]]
    geometry = dict(max_slots=d["slots"], page_size=d["page_size"],
                    pages_per_slot=d["pages_per_slot"],
                    num_pages=d["slots"] * d["pages_per_slot"],
                    prefill_chunk=d["prefill"], prefill_floor=d["prefill"])
    on_tpu = jax.default_backend() == "tpu"

    def serve(engine):
        keys = engine.warmup([d["prefill"]])
        for arr in jax.tree_util.tree_leaves(engine.model.params):
            check_on(arr, jax.devices()[:1], "decode model parameter")
        warm = engine.compiles
        first_pool = jax.tree_util.tree_leaves(engine.cache.pool)
        c_warm = sm.compiles()
        sched = DecodeScheduler(engine, max_new_tokens=d["max_new"])
        server.attach_decoder(sched)
        try:
            toks = _in_threads(
                [lambda p=p: _post(url, {"prompt": p,
                                         "max_new_tokens": d["max_new"]}
                                   )["tokens"] for p in prompts])
        finally:
            sched.close()
            server.attach_decoder(None)
        check(all(len(t) == d["max_new"] for t in toks),
              f"token counts {[len(t) for t in toks]}")
        check(engine.compiles == warm,
              f"engine.compiles grew {warm} -> {engine.compiles}")
        check(sm.compiles() == c_warm,
              f"{sm.compiles() - c_warm} compile(s) after warm-up")
        for buf in jax.tree_util.tree_leaves(engine.cache.pool):
            check_on(buf, jax.devices()[:1], "KV pool buffer")
        # the donated-pool branch is the one a TPU runs: every first
        # buffer of the pool was consumed by the first executable
        donated = {buf.is_deleted() for buf in first_pool}
        check(donated == {on_tpu},
              f"KV pool buffers donated={sorted(donated)} on "
              f"{jax.default_backend()}")
        return toks, keys, sched.stats()

    plain, plain_keys, _ = serve(DecodeEngine(model, **geometry))
    spec, spec_keys, spec_stats = serve(DecodeEngine(
        model, draft_model=draft, spec_k=d["spec_k"], **geometry))
    check(spec == plain, "speculative tokens differ from the plain run")
    check(spec_stats["spec_proposed"] > 0, "no speculative step ran")

    # the dense path, teacher-forced on the paged path's own tokens of
    # the shortest request: per position, does the dense argmax agree,
    # and how far below the dense maximum is the emitted token's logit
    seq = prompts[0] + plain[0]
    agree, gaps = 0, []
    for t in range(d["max_new"]):
        logits = onp.asarray(model._ref_logits_last(
            jnp.asarray(seq[:len(prompts[0]) + t], jnp.int32)), onp.float32)
        tok = plain[0][t]
        agree += int(int(logits.argmax()) == tok)
        gaps.append(float((logits.max() - logits[tok])
                          / (logits.max() - onp.median(logits))))
    check(gaps[0] <= TOL_FIRST_LOGIT,
          f"first token sits {gaps[0]:.3g} of the logit spread below "
          f"the dense maximum (> {TOL_FIRST_LOGIT})")
    return {"model": {k: d[k] for k in ("vocab", "dim", "heads", "layers")},
            "dtype": "bfloat16", "requests": len(prompts),
            "prompt_lens": list(d["prompts"]), "new_tokens": d["max_new"],
            "executables": {"plain": plain_keys, "spec": spec_keys},
            "spec_accept": [spec_stats["spec_accepted"],
                            spec_stats["spec_proposed"]],
            "pool_donated": on_tpu,
            "compared": "spec tokens == plain tokens; first token vs "
                        "dense logits; dense argmax agreement over "
                        "request 0",
            "first_logit_gap": gaps[0], "max_logit_gap": max(gaps),
            "tol": TOL_FIRST_LOGIT,
            "dense_agree": [agree, d["max_new"]]}


# -- phase: multichip --------------------------------------------------------

def _shard_report(named_arrays, devices):
    """Every array sharded over more than one device must have its
    addressable shards on all of ``devices``."""
    sharded = 0
    for name, arr in named_arrays:
        shard_devs = {s.device for s in arr.addressable_shards}
        check(shard_devs == set(devices),
              f"{name}: shards on {len(shard_devs)} device(s), expected "
              f"{len(devices)}")
        if not arr.sharding.is_fully_replicated:
            sharded += 1
    check(sharded > 0, "no parameter is sharded")
    return sharded


def _collectives(hlo):
    return {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
            for op in ("all-reduce", "all-gather", "reduce-scatter")}


def _tp_shard(net):
    """The Megatron layout of __graft_entry__.dryrun_multichip:
    column-parallel into the block, row-parallel out."""
    from jax.sharding import PartitionSpec as P
    for k, p in net.collect_params().items():
        if k.endswith("weight") and p.shape is not None \
                and len(p.shape) == 2:
            if "ffn1" in k or "qkv" in k:
                p.shard(P("tp", None))
            elif "ffn2" in k or "out_proj" in k:
                p.shard(P(None, "tp"))


def multichip_spmd_phase(sm: Smoke):
    """SPMDTrainer on the TransformerLM over dp2 x tp2 against the same
    three steps on one device.  Attention is the dense XLA lowering on
    both sides: a Mosaic kernel cannot be partitioned by GSPMD (the
    TPU compiler says so at lowering), and nothing wraps the flash
    kernel in a shard_map yet."""
    import jax

    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    c = sm.cfg["lm"]
    devices = jax.devices()[:4]
    data, label = _lm_batch(sm)

    def run(mesh, shard):
        net = _lm(sm, use_flash=False)
        if shard:
            _tp_shard(net)
        trainer = SPMDTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), optimizer="adam",
            optimizer_params={"learning_rate": 3e-4}, mesh=mesh,
            dtype="bfloat16")
        losses = [float(trainer.step(data, label).asnumpy().mean())
                  for _ in range(3)]
        return net, trainer, losses

    _, _, ref = run(make_mesh({"dp": 1}, devices[:1]), False)
    gc.collect()
    net, trainer, got = run(make_mesh({"dp": 2, "tp": 2}, devices), True)
    check(all(math.isfinite(x) for x in got), f"losses {got}")
    err = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    check(err <= TOL_LOSS, f"dp2xtp2 losses {got} vs one device {ref}")
    sharded = _shard_report(_param_arrays(net), devices)
    coll = _collectives(trainer.compiled_step(data, label).as_text())
    check(coll["all-reduce"] > 0,
          f"no all-reduce in the dp2xtp2 step: {coll}")
    return {"model": "TransformerLM", "mesh": "dp2,tp2",
            "attention": "dense (use_flash=False)",
            "data": [c["batch"], c["seq"]], "losses": got,
            "one_device_losses": ref, "max_rel_err": err, "tol": TOL_LOSS,
            "sharded_params": sharded, "collectives": coll,
            "compared": "three losses vs one device; shards on 4 "
                        "devices; collectives in the step's HLO"}


def multichip_mesh4d_phase(sm: Smoke):
    """Mesh4DTrainer plan dp2,tp2 on a column/row-parallel residual MLP
    stack at the LM's width against the same three steps on one
    device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel import Mesh4DTrainer, MeshPlan
    c = sm.cfg["mlp4d"]
    devices = jax.devices()[:4]
    rng = onp.random.RandomState(sm.seed)
    params, specs = [], []
    for _ in range(c["layers"]):
        # small output projections: the residual stack starts near
        # the identity, so plain SGD at this width descends
        params += [rng.randn(c["units"], c["hidden"]) / c["units"] ** 0.5,
                   0.1 * rng.randn(c["hidden"], c["units"])
                   / c["hidden"] ** 0.5]
        specs += [P(None, "tp"), P("tp", None)]
    x = jnp.asarray(rng.randn(c["rows"], c["units"]), jnp.float32)
    y = jnp.asarray(rng.randn(c["rows"], c["units"]), jnp.float32)

    def run(plan):
        def stack(p, h):
            for a, b in zip(p[::2], p[1::2]):
                h = h + plan.tp_row(jax.nn.relu(plan.tp_column(h @ a)) @ b)
            return h

        # fresh device arrays per trainer: on one device the trainer's
        # device_put aliases its argument, and the step donates it
        trainer = Mesh4DTrainer(
            plan, stack, lambda out, t: jnp.mean((out - t) ** 2),
            tuple(jnp.asarray(p, jnp.float32) for p in params),
            param_specs=tuple(specs), learning_rate=0.01)
        return trainer, [float(trainer.step(x, y)) for _ in range(3)]

    _, ref = run(MeshPlan(dp=1, tp=1, devices=devices[:1]))
    trainer, got = run(MeshPlan(dp=2, tp=2, devices=devices))
    check(all(math.isfinite(v) for v in got) and got[2] < got[0],
          f"losses {got}")
    err = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    check(err <= TOL_LOSS, f"dp2,tp2 losses {got} vs one device {ref}")
    sharded = _shard_report(
        [(f"leaf{i}", a) for i, a in
         enumerate(jax.tree_util.tree_leaves(trainer.params))], devices)
    return {"model": "residual MLP stack", "mesh": "dp2,tp2",
            "data": [c["rows"], c["units"]], "hidden": c["hidden"],
            "layers": c["layers"], "losses": got,
            "one_device_losses": ref, "max_rel_err": err, "tol": TOL_LOSS,
            "sharded_params": sharded,
            "compared": "three losses vs one device; shards on 4 devices"}


# -- main --------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the sharded training phase "
                         "and its one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; labelled, never ok")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="",
                    help="a builder's convenience, no part of the smoke's "
                         "verdict: run only the phases whose name starts "
                         "with this (kernels: ~1 min of a chip)")
    args = ap.parse_args(argv)

    import jax

    from mxnet_tpu import kernels
    from mxnet_tpu.base import use_compile_cache
    cache_dir = use_compile_cache()

    want = 4 if args.multichip else 1
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    sm = Smoke(TINY if args.rehearse else FULL, args.rehearse, args.seed)
    if args.only:
        every = sm.phase
        sm.phase = lambda name, fn: (every(name, fn)
                                     if name.startswith(args.only) else None)
    sm.emit({"phase": "device", "device": device, "jax": jax.__version__,
             "compile_cache": cache_dir})
    problem = None
    if device["platform"] != "tpu" and not args.rehearse:
        problem = (f"JAX found platform {device['platform']!r}, not a "
                   f"TPU: nothing ran")
    elif len(devs) < want:
        problem = f"{want} device(s) needed, JAX found {len(devs)}"
    if problem:
        print(problem, file=sys.stderr)
        sm.emit({"ok": False, "device": device, "error": problem})
        return 2

    if args.multichip:
        sm.phase("multichip.spmd_dp2tp2", lambda: multichip_spmd_phase(sm))
        sm.phase("multichip.mesh4d_dp2tp2",
                 lambda: multichip_mesh4d_phase(sm))
    else:
        servers = []
        sm.phase("kernels", lambda: kernels_phase(sm))
        sm.phase("train.resnet_spmd", lambda: train_cnn_spmd_phase(sm))
        sm.phase("train.resnet_gluon", lambda: train_cnn_gluon_phase(sm))
        sm.phase("train.transformer_lm", lambda: train_lm_phase(sm))
        try:
            sm.phase("serve.predict",
                     lambda: serve_predict_phase(sm, servers))
            sm.phase("serve.generate",
                     lambda: serve_generate_phase(sm, servers))
        finally:
            for server, _, _ in servers:
                server.stop(drain=False)

    fallbacks = kernels.stats()["fallbacks"]
    sm.emit({"phase": "summary", "kernel.fallbacks": fallbacks,
             "compiles": sm.watch.requests,
             "compile_s": round(sm.watch.seconds, 2),
             "cache_hits": sm.watch.cache_hits})
    if fallbacks:
        print(f"kernel.fallbacks = {fallbacks}, expected 0",
              file=sys.stderr)
        sm.failed = True
    if sm.failed or args.rehearse:
        sm.emit({"ok": False, "device": device})
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
