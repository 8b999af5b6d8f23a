"""Driver ``decode_open_loop_model``: ``decode_open_loop``'s run for a
model the program is handed, found through ``models/<family>.py``.

The open loop, the step-record sink, the window and every number read
from it are ``decode_open_loop``'s (its ``StepSink``, ``Offered``,
``offer`` and ``window_numbers``, imported, not copied; its docstring
says what each is).  ``Served`` and ``run`` repeat that driver's lines
where it leaves no seam: it constructs its model inside ``Served`` and
checks its tokens before the server closes, and no file of the
benchmark may be edited here (a ``benchmark`` PR can fold the two).
What differs:

- the model: ``models/<family>.py:build_decode_model(cfg, seed)`` builds
  the program's model of the configuration (the other driver constructs
  the built-in ``DecodeModel`` from GPT-2's keys) and the engine is
  handed it;
- the token check: the first emitted tokens of BOTH the window's
  shortest-prompt requests and its longest (some hundreds of tokens:
  the load generator keeps eight a request) are teacher-forced through
  the family's plain float32 reference.  A prompt past the prefill
  chunk crosses a chunk boundary with carried state (K/V pages and,
  for a model with recurrent layers, the state-space state and the
  convolution's tail), which the shortest prompts never do.  The
  reference runs after the server has stopped and the engine's pool is
  freed, one layer at a time and the head by blocks of the vocabulary,
  so that float32 fits beside the bfloat16 weights on the chip;
- the controls: for a few of those requests the same comparison is
  made of two stand-ins in the program's place (``CONTROLS``): the
  reference with its weight matrices rounded to float8, the nearest
  precision below the configuration's, and the reference blind to
  attention.  Their readings are reported beside the program's
  (``checks.tokens.controls``) and decide nothing: they say what the
  limit can tell apart, in every run;
- the counters of the engine's recurrent state (``ssm_state_bytes``)
  and, in a traced run, how many slots each decode step inside the
  trace advanced (``trace_decode_slots``): what a kernel's roofline
  share counts its bytes for.

The logit-gap form is ``decode_open_loop``'s: how far below the
reference's maximum an emitted token's reference logit sits, as a share
of the logits' spread (maximum - median); an exact bfloat16 tie puts
the emitted token at a gap of about 0.  ``TOL_LOGIT_GAP`` lies between
the two readings of PERF.md section 6 (PR 29): the program's largest
over its seeds and the float8 control's.

What the driver takes from the program beyond ``decode_open_loop``'s
list: ``DecodeEngine(model, ...)`` for any model of the decode plane's
protocol, ``engine.stats()["state_bytes"]``, ``engine.cache.pool``
(only to drop it), ``model.params`` and ``model.vocab_size``.
"""
from __future__ import annotations

import functools
import gc
import json
import os
import time
from typing import Optional

import numpy as onp

from chipbench.drivers.decode_open_loop import (Offered, StepSink, offer,
                                                window_numbers)
from chipbench.harness import stats
from chipbench.harness import trace as trace_mod
from chipbench.harness.cli import Job, load_module, say

__all__ = ["Served", "offer", "window_numbers", "run"]

# How far below the reference's maximum an emitted token's reference
# logit may sit, as a share of the logits' spread (maximum - median).
# Between two readings on the chip at the published widths (PERF.md
# section 6, PR 29, calls 13 and 14): the program's largest over eight
# seeds of 320 tokens, 9.2e-3 (1.6e-3 to 9.2e-3: bfloat16 activations
# and K/V against float32, the weights the same numbers on both sides),
# and the ``weights_float8`` control's 7.6e-2 (two seeds of 64 tokens;
# ``attention_off`` reads 0.33 to 0.53).  Twice the one, a quarter of
# the other.
TOL_LOGIT_GAP = 2e-2
# columns of the vocabulary the reference's head takes at a time
HEAD_BLOCK = 32768


class Served:
    """The model, engine, scheduler and HTTP server of one run."""

    def __init__(self, job: Job):
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.serving import (DecodeEngine, DecodeScheduler,
                                       ServingServer)
        cfg = job.size(job.config)
        cell = job.size(job.cell)
        eng = job.size(job.cell["engine"])
        self.cfg = cfg
        self.family = load_module("models", cfg["family"], job.bench_dir)
        t0 = time.monotonic()
        self.model = self.family.build_decode_model(cfg, job.seed)
        t_model = time.monotonic()
        self.engine = DecodeEngine(
            self.model, max_slots=eng["max_slots"],
            page_size=eng["page_size"],
            pages_per_slot=eng["pages_per_slot"],
            num_pages=eng["num_pages"],
            prefill_chunk=eng["prefill_chunk"],
            prefill_floor=eng["prefill_floor"])
        # every prefill bucket the mix can hit, and nothing speculative
        buckets, b = [], eng["prefill_floor"]
        while b <= eng["prefill_chunk"]:
            buckets.append(b)
            b *= 2
        self.executables = self.engine.warmup(buckets)
        t_warm = time.monotonic()
        self.sink: Optional[StepSink] = None
        if cell.get("telemetry_sink", True):
            self.sink = StepSink()
            telemetry.add_sink(self.sink)
        # the cell's "scheduler" section is DecodeScheduler's arguments
        self.scheduler = DecodeScheduler(self.engine, **cell["scheduler"])
        # ServingServer wants a block for /predict: a one-layer Dense
        # that no request calls
        mx.random.seed(job.seed)
        block = nn.Dense(1, in_units=8)
        block.initialize()
        self.server = ServingServer(
            block, engine_args={"example_shape": (8,), "dtype": "float32",
                                "bucket_sizes": (1,)})
        self.server.attach_decoder(self.scheduler)
        self.host, self.port = self.server.start_http()
        say("served", model_s=t_model - t0, warmup_s=t_warm - t_model,
            executables=self.executables, engine=self.engine.stats())

    def close(self) -> None:
        from mxnet_tpu import telemetry
        self.server.stop(drain=False)
        if self.sink is not None:
            telemetry.remove_sink(self.sink)

    def free_pool(self) -> None:
        """Drop the engine's cache buffers: the server has stopped, and
        the reference needs their room."""
        self.engine.cache.pool = ()
        gc.collect()


def _round8(w):
    """A weight matrix rounded to float8's e4m3 (4 bits of exponent, 3 of
    mantissa) and back, scaled by the power of two that puts its
    largest magnitude under the format's 240, so every rounded value
    is exact in bfloat16.  ``reduce_precision`` and not a cast there
    and back: the TPU compiler removes such a pair of casts as excess
    precision it may keep (call 13 read this control at exactly 0)."""
    import jax
    import jax.numpy as jnp
    w32 = w.astype(jnp.float32)
    scale = 2.0 ** jnp.ceil(jnp.log2(jnp.max(jnp.abs(w32)) / 240.0))
    return (jax.lax.reduce_precision(w32 / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale).astype(w.dtype)


def _matrices_float8(tree: dict) -> dict:
    """``tree`` with every weight matrix (2-D, in the serving dtype)
    rounded to float8; norms, the convolution and the per-head vectors
    stay, as a float8 deployment keeps them."""
    import jax
    import jax.numpy as jnp
    round8 = jax.jit(_round8)
    return {k: round8(v) if v.ndim == 2 and v.dtype == jnp.bfloat16 else v
            for k, v in tree.items()}


def _attention_off(tree: dict) -> dict:
    """``tree`` with the attention heads' output projection zeroed: a
    forward pass that attention does not reach."""
    import jax.numpy as jnp
    return {k: jnp.zeros_like(v) if k == "wo" else v
            for k, v in tree.items()}


# What is put through the reference IN THE PROGRAM'S PLACE to show what
# the comparison can tell apart: each maps a piece of the parameters
# (the gathered embedding rows, one layer, the final norm with a block
# of the head's columns) to what the stand-in computes with.
CONTROLS = {
    # the nearest precision below the configuration's bfloat16 weights:
    # it has to come out not correct
    "weights_float8": _matrices_float8,
    # a forward pass blind to attention: it has to come out not
    # correct, or the comparison does not see the attention path
    "attention_off": _attention_off,
}


def reference_logits(pieces: dict, params, tokens, rows,
                     stand_in=None) -> onp.ndarray:
    """Logits ``(len(rows), vocab)`` of the whole sequence ``tokens``
    (padded to a multiple of 128 so that few lengths compile) at the
    positions ``rows``: the reference's jitted ``pieces``, one layer
    resident at a time, the head by blocks of the vocabulary's
    columns.  ``stand_in`` (one of ``CONTROLS``) changes each piece of
    the parameters on its way in."""
    import jax
    import jax.numpy as jnp
    change = stand_in or (lambda tree: tree)
    padded = -(-len(tokens) // 128) * 128
    toks = onp.zeros((padded,), onp.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        # the rows are taken before anything widens or rounds them
        table = change({"embed": params["embed"][jnp.asarray(toks)]})
        x = pieces["embed"](table, jnp.arange(padded))
        for lp in params["layers"]:
            x = pieces["layer"](change(lp), x)
        x = x[jnp.asarray(rows)]
        vocab = params["head"].shape[1]
        blocks = []
        for lo in range(0, vocab, HEAD_BLOCK):
            top = change({"lnf": params["lnf"],
                          "head": params["head"][:, lo:lo + HEAD_BLOCK]})
            blocks.append(onp.asarray(
                pieces["head"](top["lnf"], top["head"], x)))
    return onp.concatenate(blocks, axis=1).astype(onp.float32)


def logit_gaps(logits: onp.ndarray, tokens) -> list:
    """How far below the reference's maximum each token's reference
    logit sits, as a share of the row's spread (maximum - median)."""
    return [float((row.max() - row[tok]) / (row.max() - onp.median(row)))
            for row, tok in zip(logits, tokens)]


def check_tokens(job: Job, served: Served, offered: Offered, ids) -> dict:
    """The first emitted tokens of the window's shortest-prompt and
    longest-prompt requests, teacher-forced through the plain float32
    reference: how far below the reference's maximum each emitted
    token's logit sits, as a share of the logits' spread.  For the
    first ``controls`` requests of each group the same comparison is
    made of each of ``CONTROLS`` in the program's place: the tokens it
    would have emitted at the same positions."""
    import jax
    spec = job.size(job.cell["check"])
    ref, against = served.family.decode_reference(job.bench_dir)
    pieces = {name: jax.jit(functools.partial(getattr(ref, name),
                                              cfg=served.cfg))
              for name in ("embed", "layer", "head")}
    params = served.model.params
    by_i = {r["i"]: r for r in offered.schedule}
    done = sorted((i for i in ids if offered.results.get(i, {}).get(
        "status") == 200), key=lambda i: (len(by_i[i]["prompt"]), i))
    groups = {"short": done[:spec["shortest"]],
              "long": [i for i in done[::-1][:spec["longest"]]
                       if i not in done[:spec["shortest"]]]}
    out = {"tol": TOL_LOGIT_GAP, "against": against, "tokens_checked": 0,
           "argmax_agree": 0, "max_logit_gap": 0.0,
           "controls": {name: {"tokens_checked": 0, "argmax_agree": 0,
                               "max_logit_gap": 0.0} for name in CONTROLS}}
    for tag, chosen in groups.items():
        gaps, agree = [], 0
        for n, i in enumerate(chosen):
            prompt = by_i[i]["prompt"]
            head = offered.results[i]["head"][:spec["tokens"]]
            seq = prompt + head[:-1]
            rows = list(range(len(prompt) - 1, len(seq)))
            logits = reference_logits(pieces, params, seq, rows)
            best = logits.argmax(axis=1)
            gaps += logit_gaps(logits, head)
            agree += int((best == onp.asarray(head)).sum())
            if n >= spec["controls"]:
                continue
            for name, stand_in in CONTROLS.items():
                would = reference_logits(pieces, params, seq, rows,
                                         stand_in).argmax(axis=1)
                seen = out["controls"][name]
                seen["tokens_checked"] += len(would)
                seen["argmax_agree"] += int((would == best).sum())
                seen["max_logit_gap"] = max(
                    [seen["max_logit_gap"]] + logit_gaps(logits, would))
        out[tag] = {"requests": chosen,
                    "prompt_lens": [len(by_i[i]["prompt"]) for i in chosen],
                    "tokens_checked": len(gaps), "argmax_agree": agree,
                    "max_logit_gap": max(gaps) if gaps else float("inf")}
        out["tokens_checked"] += len(gaps)
        out["argmax_agree"] += agree
        out["max_logit_gap"] = max(out["max_logit_gap"],
                                   out[tag]["max_logit_gap"])
    out["ok"] = out["max_logit_gap"] <= TOL_LOGIT_GAP
    return out


def run(job: Job) -> dict:
    cell = job.size(job.cell)
    served = Served(job)
    setup_compile = job.watch.snapshot()
    lead_in, trace_s = float(cell["lead_in_s"]), float(cell["trace_s"])
    horizon = lead_in + job.seconds + (trace_s + 1.0 if job.trace else 0.0)
    offered = offer(job, served, cell["rate_rps"], job.seed, horizon,
                    tag=job.cell["name"])
    summary, traced = None, (0.0, 0.0)
    try:
        w_lo = offered.t0 + lead_in
        w_hi = w_lo + job.seconds
        time.sleep(max(0.0, w_lo - time.monotonic()))
        c0 = job.watch.snapshot()["requests"]
        e0 = served.engine.compiles
        time.sleep(max(0.0, w_hi - time.monotonic()))
        window_compiles = job.watch.snapshot()["requests"] - c0
        engine_compiles = served.engine.compiles - e0
        if job.trace:
            # after the window, with the load still on, so the window's
            # numbers are taken with the profiler off in every run
            log_dir = os.path.join(job.out_dir,
                                   f"trace_{job.cell['name']}")
            with trace_mod.capture(log_dir):
                t_lo = time.monotonic()
                with trace_mod.span("wait"):
                    time.sleep(trace_s)
                traced = (t_lo, time.monotonic())
            summary = trace_mod.reduce_dir(log_dir)
            say("trace", summary=summary)
        records = list(served.sink.records) if served.sink else []
        answered = offered.wait_for(
            [r["i"] for r in offered.due_in(w_lo, w_hi)], timeout=180.0)
        num = window_numbers(records, offered, w_lo, w_hi)
        engine_stats = served.engine.stats()
        # what the window's numbers were read from, kept beside the trace
        with open(os.path.join(
                job.out_dir, f"records_{job.cell['name']}_s{job.seed}.json"),
                "w") as f:
            json.dump({"window": [w_lo, w_hi], "records": [
                [t, d["tokens"], d.get("ttft_ms") or [], d["step_ms"],
                 d["slots_active"], d["queue_depth"]] for t, d in records],
                "requests": [
                    {"i": r["i"], "due": offered.t0 + r["due_s"],
                     "prompt_len": len(r["prompt"]),
                     "max_new_tokens": r["max_new_tokens"],
                     **{k: offered.results.get(r["i"], {}).get(k)
                        for k in ("sent", "done", "status")}}
                    for r in offered.schedule]}, f)
    finally:
        offered.stop()
        served.close()
    served.free_pool()
    check = check_tokens(job, served, offered, num["due"])

    # slots each decode step inside the traced segment advanced: every
    # decoding slot gets one token a step, a first token comes from a
    # prefill chunk
    trace_decode_slots = [
        dec["tokens"] - len(dec.get("ttft_ms") or [])
        for t, dec in records if traced[0] <= t < traced[1]]
    trace_decode_slots = [n for n in trace_decode_slots if n > 0]

    e2e = {}
    if num["records"]:
        e2e["serve_rate"] = {"value": num["tokens"] / num["window_s"],
                             "unit": "items/s"}
    for q in (50, 95):
        if num["gap_ms"]:
            e2e[f"token_gap_ms_p{q}"] = {
                "value": stats.weighted_percentile(
                    num["gap_ms"], num["gap_weights"], q), "unit": "ms"}
    late95 = stats.percentile(num["gen_late_ms"], 95)
    correct = (answered and num["failed"] == 0 and check["ok"]
               and window_compiles == 0 and engine_compiles == 0)
    series = {k: num[k] for k in ("gen_late_ms", "request_ms", "ttft_ms",
                                  "decode_step_ms", "slots_active",
                                  "gap_ms", "gap_weights")}
    series["trace_decode_slots"] = trace_decode_slots
    return {
        "t_window_start": w_lo,
        "attempted": len(num["due"]), "failed": num["failed"],
        "correct": correct, "end_to_end": e2e, "series": series,
        "counters": {"window_compiles": window_compiles + engine_compiles,
                     "ssm_state_bytes": engine_stats.get("state_bytes")},
        "trace": summary, "setup_compile": setup_compile,
        "checks": {"tokens": check, "answered": answered,
                   "window_compiles": window_compiles,
                   "engine_compiles": engine_compiles,
                   "refused": num["refused"]},
        "notes": {
            "rate_rps": cell["rate_rps"], "requests_due": len(num["due"]),
            "step_records": num["records"],
            "queue_mid": num["queue_mid"], "queue_end": num["queue_end"],
            "generator_late": late95 is not None and late95 > 5.0,
            "ttft_ms": {q: stats.percentile(num["ttft_ms"], q)
                        for q in (50, 90, 95, 99)},
            "gap_ms": {q: stats.weighted_percentile(
                num["gap_ms"], num["gap_weights"], q)
                for q in (50, 90, 95, 99)},
            "request_ms": {q: stats.percentile(num["request_ms"], q)
                           for q in (50, 95)},
            "slots_active_mean": stats.mean(num["slots_active"]),
            "engine": engine_stats,
        },
    }
