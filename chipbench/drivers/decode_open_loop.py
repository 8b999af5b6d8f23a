"""Driver ``decode_open_loop``: the program's own server under an open
loop of ``POST /generate``.

The server is ``ServingServer.start_http()`` with a ``DecodeScheduler``
attached, background loop on.  The load generator is a child process
that never imports JAX (``harness/loadgen.py``); it sends each request
of a seeded schedule (``harness/traffic.py``) when it is due and times
it from then.  The schedule starts ``lead_in_s`` before the window so
the slots are occupied when it opens.

There is no streaming: ``/generate`` answers with the whole token list.
The only place a first-token time exists is the step record the
scheduler emits per step, so the driver attaches one in-memory
``telemetry`` sink of its own and reads, from the records that reach it
inside the window: the intervals between consecutive records weighted by
the slots that decoded in the later one (token gaps: without speculation
every decoding slot gets one token per step), ``ttft_ms`` (first
answer), ``tokens`` (``serve_rate``: tokens emitted inside the window
over the window), ``step_ms``, ``slots_active`` and ``queue_depth``.  The
record's ``host_ms`` and ``device_ms`` are not read.  Which of these a
cell reports end to end is the cell's choice (its ``end_to_end`` list).
In a window of some 66 requests only the median gap repeats: the 95th
percentile sits on a narrow step of the gaps' distribution (a decode
step plus three, four or five prefill chunks) and a window in seven
lands a step off, a fifth away (PERF.md, Findings, PR 24), so the chat
cell reports ``token_gap_ms_p50`` end to end and the tails per layer.

``attempted`` is the requests due inside the window; ``failed`` those
with a non-200 answer or a token count other than asked.

What the driver takes from the program: ``DecodeModel``,
``DecodeEngine`` and ``DecodeScheduler`` (constructor arguments,
``warmup``, ``compiles``), ``ServingServer`` (``start_http``,
``attach_decoder``, ``stop``), ``POST /generate``,
``telemetry.add_sink`` / ``remove_sink`` and the ``decode`` step-record
keys named above.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import List, Optional

import numpy as onp

from chipbench.harness import stats
from chipbench.harness import trace as trace_mod
from chipbench.harness.cli import Job, load_module, say
from chipbench.harness.traffic import make_schedule

# how far below the reference's maximum an emitted token's reference
# logit may sit, as a share of the logits' spread (maximum - median):
# chip_smoke.py's TOL_FIRST_LOGIT form, which survives exact bf16 ties
# (a tie puts the emitted token at a gap of ~0).  bf16 weights, KV and
# activations against a float32 forward measured 3e-3 on single kernels
# (PERF.md, PR 23); float32 serving would sit near 1e-5
TOL_LOGIT_GAP = 2e-2
LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "harness", "loadgen.py")


class StepSink:
    """The benchmark's in-memory ``telemetry`` sink: keeps the
    ``decode`` part of each step record with the time it arrived."""

    def __init__(self):
        self.records: List[tuple] = []

    def emit(self, record: dict) -> None:
        dec = record.get("decode")
        if dec is not None:
            with trace_mod.span("sink_emit"):
                self.records.append((time.monotonic(), dec))


class Served:
    """The model, engine, scheduler and HTTP server of one run."""

    def __init__(self, job: Job):
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.serving import (DecodeEngine, DecodeModel,
                                       DecodeScheduler, ServingServer)
        cfg = job.size(job.config)
        cell = job.size(job.cell)
        eng = job.size(job.cell["engine"])
        sm = cfg["serving_model"]
        self.cfg = cfg
        t0 = time.monotonic()
        self.model = DecodeModel(
            cfg["vocab_size"], dim=cfg["n_embd"], n_heads=cfg["n_head"],
            n_layers=cfg["n_layer"], mlp_ratio=sm["mlp_ratio"],
            rope_base=sm["rope_base"], seed=job.seed, dtype=sm["dtype"])
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
        self.scheduler = DecodeScheduler(
            self.engine, queue_depth=cell["scheduler"]["queue_depth"])
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


class Offered:
    """One schedule being sent by the child process."""

    def __init__(self, job: Job, served: Served, schedule: list,
                 t0: float, tag: str):
        self.schedule = schedule
        self.t0 = t0
        self.results: dict = {}
        self.ended = threading.Event()
        path = os.path.join(job.out_dir, f"schedule_{tag}.json")
        with open(path, "w") as f:
            json.dump(schedule, f)
        self._err = open(os.path.join(job.out_dir, f"loadgen_{tag}.err"),
                         "w")
        self.proc = subprocess.Popen(
            [sys.executable, LOADGEN, served.host, str(served.port),
             "/generate", path, repr(t0)],
            stdout=subprocess.PIPE, stderr=self._err, text=True)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            msg = json.loads(line)
            if msg.get("end"):
                break
            self.results[msg["i"]] = msg
        self.ended.set()

    def due_in(self, lo: float, hi: float) -> list:
        """The requests due in ``[lo, hi)`` on ``time.monotonic()``."""
        return [r for r in self.schedule if lo <= self.t0 + r["due_s"] < hi]

    def wait_for(self, ids, timeout: float) -> bool:
        """Until every request in ``ids`` has been answered."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(i in self.results for i in ids):
                return True
            if self.ended.is_set():
                break
            time.sleep(0.05)
        return all(i in self.results for i in ids)

    def stop(self) -> None:
        """End the child and wait until it has gone."""
        if self.proc.poll() is None:
            self.proc.terminate()
        try:
            self.proc.wait(10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self._reader.join(10)
        self.proc.stdout.close()
        self._err.close()


def offer(job: Job, served: Served, rate: float, seed: int,
          horizon_s: float, tag: str, start_in: float = 1.0) -> Offered:
    traffic = job.size(job.traffic)
    schedule = make_schedule(traffic, rate, seed, horizon_s,
                             served.cfg["vocab_size"])
    return Offered(job, served, schedule, time.monotonic() + start_in, tag)


def window_numbers(records, offered: Offered, w_lo: float,
                   w_hi: float) -> dict:
    """Everything read from one window: the step records that arrived
    in ``[w_lo, w_hi)`` and the requests that were due in it."""
    ttft, gaps, weights, step_ms, slots, queue = [], [], [], [], [], []
    tokens = 0
    prev_t = None
    for t, dec in records:
        if w_lo <= t < w_hi:
            firsts = dec.get("ttft_ms") or []
            ttft += firsts
            tokens += dec["tokens"]
            step_ms.append(dec["step_ms"])
            slots.append(dec["slots_active"])
            queue.append((t, dec["queue_depth"]))
            decoded = dec["tokens"] - len(firsts)
            if prev_t is not None and decoded > 0:
                gaps.append((t - prev_t) * 1e3)
                weights.append(decoded)
        prev_t = t
    due = offered.due_in(w_lo, w_hi)
    request_ms, late_ms, failed = [], [], 0
    for r in due:
        res = offered.results.get(r["i"])
        if res is None or res["status"] != 200 \
                or res["n_tokens"] != r["max_new_tokens"]:
            failed += 1
            continue
        request_ms.append((res["done"] - res["due"]) * 1e3)
        late_ms.append((res["sent"] - res["due"]) * 1e3)
    mid = (w_lo + w_hi) / 2.0

    def depth_near(when):
        return min(queue, key=lambda q: abs(q[0] - when))[1] if queue \
            else None

    return {
        "window_s": w_hi - w_lo, "records": len(step_ms),
        "tokens": tokens, "ttft_ms": ttft, "gap_ms": gaps,
        "gap_weights": weights, "decode_step_ms": step_ms,
        "slots_active": slots, "request_ms": request_ms,
        "gen_late_ms": late_ms, "due": [r["i"] for r in due],
        "failed": failed,
        "refused": sum(1 for r in due
                       if offered.results.get(r["i"], {}).get("status")
                       in (429, 503)),
        "queue_mid": depth_near(mid), "queue_end": depth_near(w_hi),
    }


def check_tokens(job: Job, served: Served, offered: Offered, ids) -> dict:
    """The first emitted tokens of the window's shortest-prompt
    requests, teacher-forced through the plain float32 reference: how
    far below the reference's maximum each emitted token's logit sits,
    as a share of the logits' spread."""
    import jax
    import jax.numpy as jnp
    spec = job.cell["check"]
    ref_mod = load_module("reference", "decodemodel_ref", job.bench_dir)
    by_i = {r["i"]: r for r in offered.schedule}
    chosen = sorted((i for i in ids if offered.results.get(i, {}).get(
        "status") == 200), key=lambda i: (len(by_i[i]["prompt"]), i))
    chosen = chosen[:spec["requests"]]
    fwd = jax.jit(ref_mod.forward, static_argnames=("n_heads", "rope_base"))
    gaps, agree, total = [], 0, 0
    for i in chosen:
        prompt = by_i[i]["prompt"]
        head = offered.results[i]["head"][:spec["tokens"]]
        seq = prompt + head[:-1]
        padded = -(-len(seq) // 128) * 128
        toks = onp.zeros((padded,), onp.int32)
        toks[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            logits = fwd(served.model.params, jnp.asarray(toks),
                         n_heads=served.model.n_heads,
                         rope_base=served.model.rope_base)
        logits = onp.asarray(logits[len(prompt) - 1:len(seq)], onp.float32)
        for row, tok in zip(logits, head):
            gaps.append(float((row.max() - row[tok])
                              / (row.max() - onp.median(row))))
            agree += int(int(row.argmax()) == tok)
            total += 1
    worst = max(gaps) if gaps else float("inf")
    return {"requests": chosen, "tokens_checked": total,
            "argmax_agree": agree, "max_logit_gap": worst,
            "tol": TOL_LOGIT_GAP,
            "against": "chipbench/reference/decodemodel_ref.py",
            "ok": bool(gaps) and worst <= TOL_LOGIT_GAP}


def run(job: Job) -> dict:
    cell = job.size(job.cell)
    served = Served(job)
    setup_compile = job.watch.snapshot()
    lead_in, trace_s = float(cell["lead_in_s"]), float(cell["trace_s"])
    horizon = lead_in + job.seconds + (trace_s + 1.0 if job.trace else 0.0)
    offered = offer(job, served, cell["rate_rps"], job.seed, horizon,
                    tag=job.cell["name"])
    summary = None
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
                with trace_mod.span("wait"):
                    time.sleep(trace_s)
            summary = trace_mod.reduce_dir(log_dir)
            say("trace", summary=summary)
        records = list(served.sink.records) if served.sink else []
        answered = offered.wait_for(
            [r["i"] for r in offered.due_in(w_lo, w_hi)], timeout=180.0)
        num = window_numbers(records, offered, w_lo, w_hi)
        check = check_tokens(job, served, offered, num["due"])
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
    return {
        "t_window_start": w_lo,
        "attempted": len(num["due"]), "failed": num["failed"],
        "correct": correct, "end_to_end": e2e,
        "series": {k: num[k] for k in ("gen_late_ms", "request_ms", "ttft_ms",
                                       "decode_step_ms", "slots_active",
                                       "gap_ms", "gap_weights")},
        "counters": {"window_compiles": window_compiles + engine_compiles},
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
        },
    }
