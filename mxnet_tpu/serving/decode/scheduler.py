"""Continuous batching for autoregressive decode.

The :class:`DecodeScheduler` is the decode-plane sibling of
``serving.DynamicBatcher``: requests are admitted into fixed decode
*slots* and evicted per engine step, not per batch.  One compiled
``decode_step`` executable covers the whole ``(max_slots,)`` grid —
the active-slot mask, per-slot positions and page tables are traced
int arrays, so admission and completion never recompile; a request
joining mid-flight costs one table row, not an XLA trace.

A slot is a row of the executables' grid, and what it owns on the
device is the engine's to know: pages of K/V addressed through its
table row and, for a model with recurrent layers, its rows of the
per-slot state buffers, which the engine zeroes at admission
(``decode.state_reset``) and which no page addresses.  The scheduler
batches over slots of either kind alike.  Speculation is the one thing
it cannot give a model with recurrent state (the engine refuses the
pairing: a rejected draft would need the state from before it).

Each step (one turn of :meth:`step`, driven by the background thread
or manually):

1. expire — queued requests and active slots whose deadline passed
   fail with ``RequestTimeoutError``; evicted slots return their pages
   to the free list (``decode.evictions``);
2. admit — free slots pull from the queue when the page budget
   (prompt + max_new [+ spec window]) fits; pages are acquired in full
   at admission so generation can never run out mid-flight;
3. prefill — each admitted slot is given ONE prompt chunk a turn
   (chunked prefill: long prompts interleave with running decodes
   instead of stalling them), and the chunks of all filling slots go to
   the engine together: up to ``engine.prefill_lanes`` ride as lanes of
   one dispatch, one pass over the weights; the final chunk's token
   stays on the device, as the slot's row of the engine's resident
   decode state;
4. decode — one batched token step over every decoding slot is
   dispatched from that state, and nothing of it is waited for.  Where
   the engine ``fuses`` (its model supplies ``turn_logits``) and a slot
   decodes, the first ``prefill_lanes`` chunks of phase 3 are not
   dispatched there but ride inside this step, one pass over the
   weights for both; a slot whose last chunk rode is switched on behind
   the step, and decodes from the next turn;
5. commit — the ONE blocking read of the turn, and it is of the turn
   before: its tokens (prompts' first tokens among them: TTFT) reach
   their requests, finished requests leave their slots.  The device
   runs this turn while the host commits the last and dispatches the
   next behind it; the depth is one turn and fixed;
6. account — one telemetry step record (source
   ``serving.DecodeScheduler``) with the decode extras the report
   tools reconcile: ``tokens`` are those committed in this turn;
   plus ``serving.request`` span closure and SLO request feed (TTFT +
   latency) for finished slots.

The scheduler accounts for its own time and for each request's waits,
always, into the engine's accounting (``engine.book``; ``stats()`` here
and ``engine.stats()`` show the figures).  *The turn clock*: the
thread's wall time is booked to exactly one of three states: ``empty``
(``_loop`` found nothing to run and waits for a request: the span
``decode.empty``), ``sync`` (blocked in the turn's read of the turn
before: ``decode.sync``) and ``host`` (everything else the thread does:
the rest of ``decode.step``).  *A request's waits*, taken when its first
token reaches the host: ``queue_wait_ms`` from ``submit`` to admission
(no slot, or no pages) and ``prefill_wait_ms`` from admission to that
token (its chunks' turns and the read one turn later); they sum to its
``ttft_ms``.  The step record carries ``sync_ms`` beside ``step_ms`` and
``queue_wait_ms`` beside ``ttft_ms``.

What the one-turn delay rests on: every executable of the engine runs
on one device stream, in the order dispatched.  That a request ends by
its count is known before its last token is: its slot is switched off
behind the decode that computes that token, and no step is wasted.  One
that ends by ``eos`` or is evicted has a step in flight that advances
its slot once more; that token is dropped at commit, its K/V row lands
inside the slot's own page budget, and whoever is given the slot or
its pages next is dispatched (``state_reset``, chunks) after it.

Under speculation the turn stays synchronous (prefill read, then the
draft→verify pair: ``k`` proposals drafted, verified in one target
dispatch, accepted prefix committed — greedy output is token-identical
to the non-speculative path): the host needs the accepted lengths to
place the next window.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, Optional

import numpy as onp

from ... import telemetry, tracing
from ...base import getenv_int
from .. import slo
from ..batcher import _Future, _getenv_float
from ..engine import (BadRequestError, QueueFullError,
                      RequestTimeoutError, ServingClosedError)
from .engine import DecodeEngine
from .paged_kv import OutOfPagesError

__all__ = ["DecodeScheduler"]


class _Request:
    __slots__ = ("prompt", "max_new", "eos", "future", "deadline",
                 "t_submit", "t_admit", "rid", "span", "ttft_ms",
                 "generated", "prefilled", "slot", "dispatched")

    def __init__(self, prompt, max_new, eos, deadline, rid):
        self.prompt = prompt
        self.max_new = max_new
        self.eos = eos
        self.future = _Future()
        self.deadline = deadline
        self.t_submit = time.perf_counter()
        self.t_admit = None
        self.rid = rid
        self.span = None
        self.ttft_ms = None
        self.generated: List[int] = []   # tokens committed
        self.prefilled = 0       # prompt tokens dispatched so far
        self.slot = None
        self.dispatched = 0      # tokens whose step has been dispatched

    def queue_ms(self, now: float) -> float:
        """From ``submit`` to admission; still queued: to ``now``."""
        admit = self.t_admit if self.t_admit is not None else now
        return round((admit - self.t_submit) * 1e3, 3)


class DecodeScheduler:
    """Continuous batcher over a :class:`DecodeEngine`.

    Knobs (constructor arg > env var > default): ``queue_depth`` /
    ``MXNET_SERVING_QUEUE_DEPTH`` (256), ``timeout_ms`` (default
    per-request deadline, None = none), ``max_new_tokens`` default for
    :meth:`submit` (32)."""

    def __init__(self, engine: DecodeEngine,
                 queue_depth: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 max_new_tokens: int = 32,
                 start: bool = True):
        self.engine = engine
        self.queue_depth = max(1, queue_depth if queue_depth is not None
                               else getenv_int("MXNET_SERVING_QUEUE_DEPTH",
                                               256))
        self.timeout_ms = timeout_ms
        self.max_new_tokens = int(max_new_tokens)
        self._q: deque = deque()
        self._cv = threading.Condition()
        self._step_lock = threading.Lock()
        self._slots: List[Optional[_Request]] = [None] * engine.max_slots
        # the turn dispatched and not yet read: (tokens on the device,
        # the requests that decoded, the prompts that ended), or None
        self._inflight: Optional[tuple] = None
        self._closed = False
        self._drain = True
        self._thread: Optional[threading.Thread] = None
        self._gauge_q = telemetry.gauge("serving.queue_depth")
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._last_compiles = engine.compiles
        self._last_edits = engine.state_edits
        self._last_sync = engine.sync_s
        # whether a profiler capture ran when the turn at hand began:
        # what it books belongs to that capture's trace
        self._traced = False
        if start:
            self.start()

    # -- lifecycle ----------------------------------------------------------

    def start(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, name="mxnet-serving-decode",
                daemon=True)
            self._thread.start()
        return self

    def close(self, drain: bool = True,
              timeout: Optional[float] = 30.0) -> None:
        """Stop admission.  ``drain=True`` runs every in-flight slot
        (and queued request) to completion before returning;
        ``drain=False`` fails them all with
        :class:`ServingClosedError` and frees their pages."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._drain = drain
            self._cv.notify_all()
        if not drain:
            with self._step_lock:      # serialize against a live step
                with self._cv:
                    while self._q:
                        r = self._q.popleft()
                        self._finish_error(
                            r, ServingClosedError(
                                "server shut down before this request "
                                "was admitted"))
                    self._gauge_q.set(0)
                for s, r in enumerate(self._slots):
                    if r is None:
                        continue
                    self.engine.release_slot(s)
                    telemetry.counter("decode.evictions").inc()
                    self._slots[s] = None
                    self._finish_error(
                        r, ServingClosedError(
                            "server shut down mid-generation"))
                self._inflight = None      # nobody is left to tell
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout)
        if drain:
            # no thread (manual mode) or a wedged one: drain inline
            while self._has_work():
                self.step()

    @property
    def closed(self) -> bool:
        return self._closed

    def pending(self) -> int:
        with self._cv:
            return len(self._q)

    def active(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    def _has_work(self) -> bool:
        """A request queued or in a slot, or a turn in flight whose
        tokens nobody has read."""
        with self._cv:
            return (bool(self._q) or self._inflight is not None
                    or any(r is not None for r in self._slots))

    # -- admission ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos: Optional[int] = None,
               timeout_ms: Optional[float] = None) -> _Future:
        """Admit one generation request; the future resolves to the
        list of generated token ids.  Raises
        :class:`BadRequestError` (empty prompt, bad token ids, page
        budget), :class:`QueueFullError`, :class:`ServingClosedError`
        — all before the request is queued."""
        if self._closed:
            raise ServingClosedError("server is draining/closed")
        max_new = (int(max_new_tokens) if max_new_tokens is not None
                   else self.max_new_tokens)
        prompt = [int(t) for t in prompt]
        vocab = self.engine.model.vocab_size
        if not prompt:
            telemetry.counter("serving.rejected.shape").inc()
            raise BadRequestError(
                "empty prompt: decode needs at least one token")
        if max_new < 1:
            telemetry.counter("serving.rejected.shape").inc()
            raise BadRequestError(
                f"max_new_tokens must be >= 1, got {max_new}")
        if any(t < 0 or t >= vocab for t in prompt):
            telemetry.counter("serving.rejected.shape").inc()
            raise BadRequestError(
                f"prompt token out of range [0, {vocab})")
        need = self._budget(len(prompt), max_new)
        if need > self.engine.slot_capacity:
            telemetry.counter("serving.rejected.shape").inc()
            raise BadRequestError(
                f"prompt+max_new needs {need} positions > slot "
                f"capacity {self.engine.slot_capacity} "
                f"(pages_per_slot * page_size)")
        ms = timeout_ms if timeout_ms is not None else self.timeout_ms
        deadline = (time.perf_counter() + ms / 1e3
                    if ms is not None else None)
        rid = slo.next_request_id()
        with self._cv:
            if self._closed:
                raise ServingClosedError("server is draining/closed")
            if len(self._q) >= self.queue_depth:
                telemetry.counter("serving.rejected.queue_full").inc()
                raise QueueFullError(
                    f"queue at depth {self.queue_depth}; load shed")
            r = _Request(prompt, max_new, eos, deadline, rid)
            r.span = tracing.begin("serving.request", request_id=rid,
                                   kind="generate")
            self._q.append(r)
            self._gauge_q.set(len(self._q))
            self._cv.notify()
        return r.future

    def _budget(self, prompt_len: int, max_new: int) -> int:
        """Positions a request can ever touch — the speculative window
        may write up to ``spec_k`` past the last committed token."""
        extra = self.engine.spec_k if self.engine.spec_enabled else 0
        return prompt_len + max_new + extra

    # -- completion helpers --------------------------------------------------

    def _observe(self, r: _Request, ok: bool, error: str = "") -> None:
        now = time.perf_counter()
        entry = {
            "id": r.rid, "ok": ok, "kind": "generate",
            "latency_ms": round((now - r.t_submit) * 1e3, 3),
            "queue_ms": r.queue_ms(now),
            "ts": round(time.time(), 3)}
        if r.ttft_ms is not None:
            entry["ttft_ms"] = r.ttft_ms
        if error:
            entry["error"] = error
        slo.observe_request(entry)

    def _finish_ok(self, r: _Request) -> None:
        tracing.end(r.span, tokens=len(r.generated),
                    ttft_ms=r.ttft_ms)
        self._observe(r, ok=True)
        r.future.set_result(list(r.generated))

    def _finish_error(self, r: _Request, exc: Exception) -> None:
        tracing.end(r.span, error=type(exc).__name__)
        self._observe(r, ok=False, error=type(exc).__name__)
        r.future.set_exception(exc)

    # -- the step ------------------------------------------------------------

    def step(self) -> dict:
        """One scheduler turn: expire → admit → dispatch (prefill,
        decode) → commit the turn before → account.  Returns the decode
        extras dict it recorded."""
        return self._turn(time.perf_counter())[0]

    def _turn(self, since: float) -> tuple:
        """``step()`` for a caller that keeps the turn clock: the time
        from ``since`` to the turn's end is booked to ``host`` but for
        what the turn spent blocked in its read, which is ``sync``.
        Returns the step record and the clock's reading at that end."""
        traced = tracing.capturing()
        with self._step_lock, tracing.span("decode.step") as turn:
            self._traced = traced
            extra = self._step_locked()
            turn.annotate(slots_active=extra["slots_active"])
        now = time.perf_counter()
        sync_s = extra["sync_ms"] / 1e3
        self.engine.book("sched", traced, host_s=now - since - sync_s,
                         sync_s=sync_s, turns=1)
        return extra, now

    def _step_locked(self) -> dict:
        # every phase is a tracing span (ring and, while a profiler
        # capture runs, the xplane's host plane): what the host does
        # while the device idles inside a turn is the shortest span
        # covering the gap
        eng = self.engine
        t_step = time.perf_counter()
        token = telemetry.begin_step()
        now = time.perf_counter()
        # the step record, filled in as the phases go: `tokens`,
        # `completed`, `ttft_ms` and `queue_wait_ms` where tokens are
        # committed
        extra = {"tokens": 0, "prefill_tokens": 0, "prefill_runs": 0,
                 "prefill_fused": 0, "completed": 0, "ttft_ms": [],
                 "queue_wait_ms": []}

        with tracing.span("decode.expire"):
            evictions = self._expire(now)

        with tracing.span("decode.admit_phase") as sp:
            sp.annotate(admitted=self._admit(now))

        fill = self._fill()
        if eng.spec_enabled:
            decoding = self._spec_turn(self._prefill(fill, extra), extra)
        else:
            decoding = self._chained_turn(fill, extra)

        # 6. account
        with tracing.span("decode.account"):
            active = self.active()
            telemetry.counter("decode.tokens").inc(extra["tokens"])
            telemetry.counter("decode.steps").inc()
            telemetry.gauge("decode.slots_active").set(active)
            compiles = eng.compiles - self._last_compiles
            self._last_compiles = eng.compiles
            edits = eng.state_edits - self._last_edits
            self._last_edits = eng.state_edits
            if not extra["ttft_ms"]:
                del extra["ttft_ms"], extra["queue_wait_ms"]
            sync_s, self._last_sync = (eng.sync_s - self._last_sync,
                                       eng.sync_s)
            extra.update({
                "slots_active": active,
                "max_slots": eng.max_slots,
                "pages_used": eng.cache.pages_used(),
                "num_pages": eng.num_pages,
                "evictions": evictions,
                "queue_depth": self.pending(),
                "compiles": compiles,
                "spec_proposed": self._spec_proposed,
                "spec_accepted": self._spec_accepted,
                "state_bytes": eng.cache.state_bytes,
                "state_slots_live": eng.cache.state_slots_live(),
                "state_resets": eng.cache.state_resets,
                "kv_live_share": (round(eng.kv_live_share, 6)
                                  if decoding else 0.0),
                "chained": eng.chained if decoding else 0,
                "state_edits": edits,
                "counters": dict(eng.counters),
                "step_ms": round((time.perf_counter() - t_step) * 1e3, 3),
                # of it, blocked in the read of the turn before
                "sync_ms": round(sync_s * 1e3, 3),
            })
            telemetry.end_step(token, "serving.DecodeScheduler",
                               extra={"decode": extra})
        return extra

    def _expire(self, now: float) -> int:
        """Phases 1 and 1b; returns the slots evicted."""
        eng = self.engine
        evictions = 0
        # 1. expire queued requests
        with self._cv:
            live = deque()
            for r in self._q:
                if r.deadline is not None and now > r.deadline:
                    telemetry.counter("serving.timeouts").inc()
                    self._finish_error(r, RequestTimeoutError(
                        "request expired in queue before admission"))
                else:
                    live.append(r)
            if len(live) != len(self._q):
                self._q = live
            self._gauge_q.set(len(self._q))

        # 1b. evict overdue active slots (frees their pages)
        for s, r in enumerate(self._slots):
            if r is None or r.deadline is None or now <= r.deadline:
                continue
            eng.release_slot(s)
            self._slots[s] = None
            evictions += 1
            telemetry.counter("decode.evictions").inc()
            telemetry.counter("serving.timeouts").inc()
            self._finish_error(r, RequestTimeoutError(
                "deadline expired mid-generation; slot evicted"))
        return evictions

    def _admit(self, now: float) -> int:
        """Phase 2: admit into free slots while the page budget fits;
        returns the requests admitted."""
        eng = self.engine
        admitted = 0
        with self._cv:
            for s in range(len(self._slots)):
                if self._slots[s] is not None or not self._q:
                    continue
                r = self._q[0]
                need = self._budget(len(r.prompt), r.max_new)
                if not eng.can_admit(need):
                    break            # head-of-line: preserve order
                self._q.popleft()
                try:
                    eng.acquire_slot(s, need)
                except OutOfPagesError:
                    self._q.appendleft(r)
                    break
                r.t_admit = now
                r.slot = s
                self._slots[s] = r
                admitted += 1
                tracing.instant("decode.admit", request_id=r.rid,
                                slot=s, prompt_tokens=len(r.prompt))
            self._gauge_q.set(len(self._q))
        return admitted

    def _fill(self) -> list:
        """Phase 3's work: ``(request, (slot, its next chunk, start))``
        for every prefilling slot, in the slots' order."""
        eng = self.engine
        return [(r, (r.slot, r.prompt[r.prefilled:
                                      r.prefilled + eng.prefill_chunk],
                     r.prefilled))
                for r in self._slots
                if r is not None and r.prefilled < len(r.prompt)]

    def _prefill(self, fill: list, extra: dict) -> list:
        """Phase 3: ``fill``'s chunks handed to the engine together
        (several ride in one dispatch), dispatched and not waited for.
        Returns ``(request, its first token on the device)`` for every
        prompt whose last chunk this was."""
        if not fill:
            return []
        runs = self.engine.prefill_runs
        toks = self.engine.prefill_chunks([chunk for _, chunk in fill])
        return self._filled(fill, toks, runs, extra)

    def _filled(self, fill: list, toks: list, runs: int,
                extra: dict) -> list:
        """``fill``'s chunks were dispatched, with ``toks`` their next
        tokens and ``runs`` the engine's prefill runs before: account
        for them, and return ``(request, first token)`` of every prompt
        whose last chunk this was."""
        extra["prefill_runs"] += self.engine.prefill_runs - runs
        firsts, tokens = [], 0
        for (r, (_, chunk, _)), tok in zip(fill, toks):
            r.prefilled += len(chunk)
            tokens += len(chunk)
            if r.prefilled >= len(r.prompt):
                firsts.append((r, tok))
        extra["prefill_tokens"] += tokens
        telemetry.counter("decode.prefill_tokens").inc(tokens)
        return firsts

    def _activate(self, firsts: list) -> None:
        """Switch the slots of ``firsts`` on: the token stays on the
        device, as the slot's row of the resident state; a request of
        one token needs no step."""
        for r, tok in firsts:
            r.dispatched = 1
            if r.max_new > 1:
                self.engine.activate_slot(r.slot, tok, len(r.prompt))

    def _decoding(self) -> list:
        """The requests whose slot takes the next decode step."""
        return [r for r in self._slots
                if r is not None and 0 < r.dispatched < r.max_new]

    def _chained_turn(self, fill: list, extra: dict) -> int:
        """Phases 3 to 5: dispatch ``fill``'s chunks, switch on the
        slots whose prompt they finish, dispatch one batched token step
        from the engine's resident state, and only then read the turn
        before and hand its tokens to their requests.  Where the engine
        fuses and a slot decodes, up to ``prefill_lanes`` of the chunks
        ride inside the step instead, and the slots whose prompt they
        finish are switched on behind it.  Returns the number of slots
        that decode."""
        eng = self.engine
        ride = (fill[:eng.prefill_lanes] if eng.fuses and self._decoding()
                else [])
        firsts = self._prefill(fill[len(ride):], extra)
        self._activate(firsts)
        decoding = self._decoding()
        nxt = None
        if decoding:
            runs = eng.prefill_runs
            # a step that carries chunks has a span of its own: readers
            # pair `decode.decode` with the `decode` executable's runs
            with tracing.span("decode.decode_fill" if ride
                              else "decode.decode", decoding=len(decoding),
                              lanes=len(ride)):
                nxt, toks = eng.decode_step([chunk for _, chunk in ride])
                for r in decoding:
                    r.dispatched += 1
                    if r.dispatched == r.max_new:
                        # its last token is in flight: no step after this
                        eng.deactivate_slot(r.slot)
            if ride:
                extra["prefill_fused"] += 1
                ridden = self._filled(ride, toks, runs, extra)
                self._activate(ridden)
                firsts += ridden
        before, self._inflight = self._inflight, (
            (nxt, decoding, firsts) if decoding or firsts else None)
        if before is not None:
            nxt, decoded, firsts = before
            nxt, first_toks = eng.read(nxt, [t for _, t in firsts])
            self._commit_firsts(firsts, first_toks, extra)
            for r in decoded:
                # one that ended since the dispatch (``eos``, a deadline)
                # was advanced once more: that token is dropped
                if not r.future.done():
                    extra["tokens"] += 1
                    extra["completed"] += self._commit(r, int(nxt[r.slot]))
        return len(decoding)

    def _commit_firsts(self, firsts, toks, extra: dict) -> None:
        """The first generated token of each prompt in ``firsts``, now
        that the host has it: TTFT."""
        for (r, _), tok in zip(firsts, toks):
            if r.future.done():     # evicted with its chunk in flight
                continue
            now = time.perf_counter()
            r.ttft_ms = round((now - r.t_submit) * 1e3, 3)
            queue_ms = r.queue_ms(now)
            extra["ttft_ms"].append(r.ttft_ms)
            extra["queue_wait_ms"].append(queue_ms)
            self.engine.book("requests", self._traced, count=1,
                             queue_wait_ms=queue_ms,
                             prefill_wait_ms=r.ttft_ms - queue_ms)
            extra["tokens"] += 1
            extra["completed"] += self._commit(r, int(tok))

    def _spec_turn(self, firsts: list, extra: dict) -> int:
        """The speculative turn's phases 4 and 5, synchronous: the
        prompts' first tokens are read (the draft needs them on the
        host), then one draft→verify pair over every decoding slot is
        dispatched, read at once and committed.  Returns the number of
        slots that decoded."""
        eng = self.engine
        if firsts:
            self._commit_firsts(
                firsts, eng.read(None, [t for _, t in firsts])[1], extra)
        decoding = [r for r in self._slots if r is not None and r.generated]
        if not decoding:
            return 0
        n = eng.max_slots
        toks = onp.zeros((n,), onp.int32)
        pos = onp.zeros((n,), onp.int32)
        act = onp.zeros((n,), bool)
        for r in decoding:
            toks[r.slot] = r.generated[-1]
            pos[r.slot] = len(r.prompt) + len(r.generated) - 1
            act[r.slot] = True
        with tracing.span("decode.decode", decoding=len(decoding)):
            greedy, accepted = eng.spec_step(toks, pos, act)
            k = eng.spec_k
            for r in decoding:
                self._spec_proposed += k
                self._spec_accepted += int(accepted[r.slot])
                for j in range(int(accepted[r.slot]) + 1):
                    extra["tokens"] += 1
                    if self._commit(r, int(greedy[r.slot, j])):
                        extra["completed"] += 1
                        break
        telemetry.counter("decode.spec_proposed").inc(k * len(decoding))
        telemetry.counter("decode.spec_accepted").inc(
            sum(int(accepted[r.slot]) for r in decoding))
        if self._spec_proposed:
            telemetry.gauge("decode.spec_accept_rate").set(
                round(self._spec_accepted / self._spec_proposed, 4))
        return len(decoding)

    def _commit(self, r: _Request, tok: int) -> bool:
        """Append one emitted token; on eos/max_new finish the request,
        release its pages and free the slot.  Returns True when the
        request completed."""
        r.generated.append(tok)
        if (len(r.generated) >= r.max_new
                or (r.eos is not None and tok == r.eos)):
            self.engine.release_slot(r.slot)
            self._slots[r.slot] = None
            self._finish_ok(r)
            return True
        return False

    # -- background loop -----------------------------------------------------

    def _loop(self):
        idle_wait = _getenv_float("MXNET_DECODE_IDLE_WAIT_S", 0.005)
        # the turn clock: everything this thread does from here on is
        # booked, up to `clock`, to one of its three states
        clock = time.perf_counter()
        while True:
            with self._cv:
                has_work = self._has_work()
                if self._closed and not (self._drain and has_work):
                    break
                if not has_work:
                    traced = tracing.capturing()
                    with tracing.span("decode.empty"):
                        self._cv.wait(idle_wait)
                    now = time.perf_counter()
                    self.engine.book("sched", traced, empty_s=now - clock)
                    clock = now
                    continue
            clock = self._turn(clock)[1]

    def stats(self) -> dict:
        booked = self.engine.stats()
        return {
            # the turn clock and the requests' waits, as the engine's
            # accounting has them (life; "traced": under a capture)
            "sched": booked["sched"],
            "requests": booked["requests"],
            "traced": {k: booked["traced"][k]
                       for k in ("sched", "requests")},
            "queue_depth": self.pending(),
            "slots_active": self.active(),
            "max_slots": self.engine.max_slots,
            "pages_used": self.engine.cache.pages_used(),
            "spec_proposed": self._spec_proposed,
            "spec_accepted": self._spec_accepted,
            "compiles": self.engine.compiles,
            "closed": self._closed,
        }
