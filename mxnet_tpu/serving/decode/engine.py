"""Decode engine: the model protocol and the fixed-shape compiled
executables over a model's paged K/V and per-slot state.  No model
lives here.

Every device-side path is ONE jit-compiled executable per static
shape (``jit_mxtpu_<key>`` in a device trace), compiled lazily on first
use and reused forever (the fixed-shape-executable invariant):

- ``decode`` — one token per active slot over the full ``(max_slots,)``
  grid: active-slot mask, per-slot positions and page tables are traced
  int arrays, so admission/completion NEVER recompiles.  They are the
  *resident state*, which lives on the device from turn to turn: the
  executable takes it, donated like the pool, and returns its successor
  (every active slot's token replaced by the one it just emitted, its
  position advanced), so a turn is dispatched without reading the turn
  before it and a steady turn uploads nothing;
- ``state_edit`` — one row of the resident state rewritten, when a slot
  changes hands: the only way the host touches that state;
- ``decode_fill_b<n>`` — ``decode`` with a prefill dispatch's lanes
  inside it (``n`` rows of them, a pow2 of lanes of the full chunk's
  bucket, up to ``_PREFILL_ROWS``), for a model that supplies
  ``turn_logits``: a turn that has slots decoding and slots filling reads
  the weights ONCE for both;
- ``prefill_b<n>`` — a prefill dispatch of ``n`` rows in LANES, each
  lane the next prompt chunk of one slot, so that every slot filling in
  a turn is served by ONE pass over the weights.  One lane: the chunk's
  length padded into pow2 sequence buckets (chunked prefill: long
  prompts are fed bucket-by-bucket so running decodes aren't stalled
  behind one long prompt).  More: a pow2 of lanes, each the full chunk's
  bucket, ``n`` their product, up to ``_PREFILL_ROWS`` rows (two lanes
  at a chunk of 128);
- ``draft``/``verify``, ``draft_prefill_b<n>`` — the speculative path:
  the draft model proposes ``k`` tokens per slot (its own paged KV
  pool, same page geometry, shared page tables), then the target model
  scores all ``k+1`` positions in a single dispatch and the longest
  matching prefix is accepted on device (greedy speculative decode is
  token-identical to the non-speculative path by construction: every
  emitted token is the target's own argmax);
- ``state_reset`` — a model's recurrent state zeroed for one slot.

The engine is handed its model (:class:`DecodePlaneModel`; the target
and the draft alike): the parameters as a pytree, its cache BY LAYER
(which paged buffers a layer keeps: K and V by the geometry of its K/V
heads, a latent page by its row's lanes, or none; and which kinds of
per-slot recurrent state it keeps beside them, or none), and its
arithmetic up to the logits, from which :class:`DecodePlaneModel`
derives the traced cores.  A decode step may hand back, beside its
logits, a small dict of scalar counters (an expert layer's routing,
say): the engine knows them by name only, reads them in the transfer
that reads the turn's tokens, and keeps their last values and running
means.  The engine owns the cache the cores read
and write, the executables and their donation; it knows nothing of a
layer, and nothing of how a token's K/V reaches a pool or a query
attends over it: that is ``paged_kv``'s, under it the
``paged_attention`` and ``rope`` kernel registrants'.  The models are
``decode_model.DecodeModel``, ``falcon_h1.FalconH1``, ``axk1.AXK1`` and
``lfm2.LFM2``.  A model with
recurrent state cannot be a speculation's target: a rejected draft
would need the state from before it, and nothing snapshots it.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import os
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as onp

import jax
import jax.numpy as jnp

from ... import telemetry, tracing
from ...log import get_logger
from .paged_kv import PagedKVCache, uniform_layout

__all__ = ["DecodePlaneModel", "DecodeEngine"]


def _env_int(name: str, default: int) -> int:
    try:
        v = int(os.environ.get(name, default))
    except ValueError:
        return default
    return v if v > 0 else default


def _pow2(n: int, floor: int) -> int:
    b = max(1, floor)
    while b < n:
        b *= 2
    return b


# rows a prefill dispatch holds at most: a chunk is bound by the weights'
# stream, so a second chunk in the same pass is nearly free until the
# rows' operations take as long as the stream does: on a v5e 240
# operations a weight byte, which bfloat16 weights reach at 240 rows.
# Past that a lane costs its operations (falcon_h1_34b on the chip: 11.8
# ms one lane of 128, 13.5 two, 21.0 four) and every multi-lane
# executable costs a warm start a second or more (PERF.md section 6,
# PR 38)
_PREFILL_ROWS = 256


def _frozen(value):
    """``value`` as nested tuples, hashable and in a stable order."""
    if isinstance(value, dict):
        return tuple((k, _frozen(v)) for k, v in sorted(value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


def _greedy(logits):
    """The greedy token of each row of ``logits (..., vocab)``."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class DecodePlaneModel:
    """What :class:`DecodeEngine` asks of a model, and what this class
    derives from it once for every model.

    **What a model supplies: its arithmetic up to the logits.**

    - ``params``, a pytree of device arrays (the first argument of every
      executable); ``config``, a dict of everything else its arithmetic
      bakes into a program; ``dtype``, ``vocab_size``, ``n_layers``.
    - ``cache_layout``: what each layer keeps in the cache,
      ``(page_widths, state_spec)`` a layer.  ``page_widths`` are the
      lanes of a row of each paged buffer the layer keeps,
      ``(num_pages, page_size, lanes)`` each (K and V: two of
      ``kv_heads * head_dim`` lanes; a latent page: its one width; a
      layer that attends over no cache: none, and it is given no page
      memory); ``state_spec`` the kinds of per-slot recurrent state it
      keeps beside them, ``(name, shape of one slot, dtype)`` each, in
      the order the layer's buffers follow the paged ones in
      ``pool[layer]``.  Empty: none.  A model whose layers are all of
      one kind states ``page_widths`` and ``state_spec`` once and
      inherits the layout that repeats them.
    - ``decode_logits(params, pool, tokens, positions, tables,
      active)``: one token a slot, ``(pool, logits (slots, vocab))``,
      or ``(pool, logits, counters)`` with ``counters`` a dict of
      scalars by name (the same names every step).
    - ``prefill_logits(params, pool, tokens, start, chunk_len, tables,
      slot)``: a prefill dispatch in lanes, each the next prompt chunk
      of one slot: ``tokens (lanes, bucket)``, of which lane ``i``'s
      first ``chunk_len[i]`` are a prompt's positions ``start[i]`` on,
      written through the slot's page row ``tables[i]``; ``(pool,
      logits (lanes, vocab))`` after each lane's last valid token.  The
      slots' indices ``slot (lanes,)`` address a model's recurrent
      state; a model without any ignores them.  A lane of length 0 is
      padding: its pages are the sentinel's, its slot index lies past
      the state buffers, it writes nothing and its logits mean nothing.
      The weights are read once for all lanes: projections, MLPs,
      experts and norms see ``lanes * bucket`` rows; only what is a
      slot's by nature (attention over its pages, a recurrence from its
      state) goes lane by lane.  A slot rides in one lane at most.
    - Optionally ``turn_logits``: what lets a decode step carry a
      prefill dispatch.  ``decode_logits``' step over the slots (its
      first six arguments) and ``prefill_logits``' lanes (the rest:
      ``lane_tokens (lanes, bucket)``, ``start``, ``chunk_len``,
      ``lane_tables``, ``slot``) in ONE pass over the weights: ``(pool,
      logits (slots + lanes, vocab))``, a slot's row then a lane's, the
      model's counters after them as ``decode_logits`` hands them.  A
      lane's slot is not active: the two kinds of row touch disjoint
      rows of every buffer.
    - Optionally ``verify_logits``: what lets the model be a
      speculation's target.  A window ``tokens (slots, k+1)`` at
      positions ``base_pos`` on: ``(pool, logits (slots, k+1,
      vocab))``.
    - ``dense_logits(params, tokens)``: the logits ``(T, vocab)`` of the
      whole sequence with no cache, the in-program oracle the cached
      paths are pinned to.

    The logits methods take the cache's ``pool`` and return its
    successor; each buffer has one writer and no reader of its old
    value left, so a donated buffer is updated in place.  They build
    their attention from ``paged_kv`` (``slot_attention``,
    ``chunk_attention``, ``window_attention``, ``turn_attention``) and
    hand it to the model's one block: a model addresses no page itself.

    **What this class derives**, the same for every model: the traced
    cores the engine compiles (``decode_core``, ``prefill_core``,
    ``turn_core``, ``verify_core``: the logits' greedy tokens, the
    model's counters passed on), the jitted dense oracle
    (``_ref_logits_last``, ``greedy_reference``) and ``fingerprint``."""

    state_spec: tuple = ()

    @property
    def page_widths(self) -> tuple:
        return (self.kv_heads * self.head_dim,) * 2

    @property
    def cache_layout(self) -> tuple:
        return uniform_layout(self.n_layers, self.page_widths,
                              self.state_spec)

    def fingerprint(self) -> tuple:
        """Everything the cores bake into an executable besides the
        shapes of its arguments (the artifact store's key): the model's
        class, its dtype and its ``config``."""
        return ((type(self).__name__, str(self.dtype))
                + _frozen(self.config))

    # -- what a model may supply ----------------------------------------------

    def turn_logits(self, params, pool, tokens, positions, tables, active,
                    lane_tokens, start, chunk_len, lane_tables, slot):
        raise NotImplementedError

    def verify_logits(self, params, pool, tokens, base_pos, tables, active):
        raise NotImplementedError

    # -- what the engine compiles ---------------------------------------------

    def decode_core(self, params, pool, tokens, positions, tables, active):
        """``(pool, next token per slot)``, the counters after them."""
        pool, logits, *counters = self.decode_logits(
            params, pool, tokens, positions, tables, active)
        return (pool, _greedy(logits), *counters)

    def prefill_core(self, params, pool, tokens, start, chunk_len, tables,
                     slot):
        """``(pool, next token per lane)``: a lane's token means
        something only after a prompt's final chunk."""
        pool, logits = self.prefill_logits(params, pool, tokens, start,
                                           chunk_len, tables, slot)
        return pool, _greedy(logits)

    def turn_core(self, params, pool, tokens, positions, tables, active,
                  lane_tokens, start, chunk_len, lane_tables, slot):
        """``(pool, next token per slot, next token per lane)``, the
        counters after them."""
        pool, logits, *counters = self.turn_logits(
            params, pool, tokens, positions, tables, active, lane_tokens,
            start, chunk_len, lane_tables, slot)
        nxt = _greedy(logits)
        n = tokens.shape[0]
        return (pool, nxt[:n], nxt[n:], *counters)

    def verify_core(self, params, pool, tokens, base_pos, tables, active):
        """``(pool, the greedy next token at every window position)``."""
        pool, logits = self.verify_logits(params, pool, tokens, base_pos,
                                          tables, active)
        return pool, _greedy(logits)

    # -- the oracle -----------------------------------------------------------

    @functools.cached_property
    def _dense_jit(self):
        # one program per sequence length (the parameters are an
        # argument, not constants)
        return jax.jit(self.dense_logits)

    def _ref_logits_last(self, tokens):
        """Last-position logits of the model's dense forward over the
        whole of ``tokens``, no cache: the in-program oracle."""
        return self._dense_jit(self.params, tokens)[-1]

    def greedy_reference(self, prompt, max_new_tokens: int,
                         eos: Optional[int] = None) -> List[int]:
        """Reference greedy generation (dense forward, full recompute
        per token).  Returns the generated tokens only."""
        toks = [int(t) for t in prompt]
        out: List[int] = []
        for _ in range(int(max_new_tokens)):
            nxt = int(jnp.argmax(self._ref_logits_last(
                jnp.asarray(toks, jnp.int32))))
            out.append(nxt)
            toks.append(nxt)
            if eos is not None and nxt == int(eos):
                break
        return out


# -- what the engine itself traces -----------------------------------------------

def _advance(state, nxt):
    """The resident state the next turn starts from: every active slot's
    token replaced by the one it just emitted, its position advanced."""
    tokens, positions, active, tables = state
    return (jnp.where(active, nxt, tokens),
            positions + active.astype(positions.dtype), active, tables)


def _chained_decode_core(mdl: DecodePlaneModel, params, pool, state):
    """The model's decode step over the resident ``state = (tokens,
    positions, active, tables)`` and the state the next turn starts
    from: ``(pool, state, next token per slot, counters)``.  The tokens
    go back twice: into the state, which the next dispatch consumes, and
    as an array of their own for the host to read after it; the model's
    counters (none: an empty dict, no output) ride with the latter."""
    tokens, positions, active, tables = state
    pool, nxt, *counters = mdl.decode_core(params, pool, tokens, positions,
                                           tables, active)
    return pool, _advance(state, nxt), nxt, (counters[0] if counters else {})


def _unstage(staged, width: int):
    """What the host staged for a dispatch of lanes in ONE int32 array
    (one upload), a row a lane: ``[tokens (bucket) | start | chunk_len |
    slot | page row (width)]``; ``(tokens (lanes, bucket), start,
    chunk_len, slot, page rows)``."""
    bucket = staged.shape[1] - 3 - width
    start, chunk_len, slot = (staged[:, bucket + i] for i in range(3))
    return staged[:, :bucket], start, chunk_len, slot, staged[:, bucket + 3:]


def _turn_core(mdl: DecodePlaneModel, width: int, params, pool, state,
               staged):
    """``_chained_decode_core`` with a prefill dispatch's lanes inside
    it, as ``_prefill_core`` stages them: ``(pool, state, next token per
    slot, counters, the lanes' next tokens)``, the last as scalars of
    their own (a slot's first token goes into the resident state
    without leaving the device)."""
    lane_tokens, start, chunk_len, slot, lane_tables = _unstage(staged, width)
    tokens, positions, active, tables = state
    pool, nxt, firsts, *counters = mdl.turn_core(
        params, pool, tokens, positions, tables, active, lane_tokens, start,
        chunk_len, lane_tables, slot)
    return (pool, _advance(state, nxt), nxt,
            (counters[0] if counters else {}),
            tuple(firsts[i] for i in range(staged.shape[0])))


def _state_edit_core(state, token, patch):
    """One slot's row of the resident state rewritten.  ``patch`` is
    ``(slot, position, active, *table row)`` in one int32 array (one
    upload); ``token`` a scalar of its own because an admitted slot's is
    a prefill executable's output and never leaves the device."""
    tokens, positions, active, tables = state
    slot = patch[0]
    return (tokens.at[slot].set(token), positions.at[slot].set(patch[1]),
            active.at[slot].set(patch[2] != 0), tables.at[slot].set(patch[3:]))


def _draft_core(mdl: DecodePlaneModel, k: int, params, pool, tokens,
                base_pos, tables, active):
    """k+1 chained decode steps of the drafting model (unrolled — ``k``
    is static): proposes k tokens and leaves the draft pool
    position-aligned with the target's write window (positions
    base..base+k).  Returns the verify window ``(S, k+1)``: the input
    token then the k proposals, assembled here so no eager op (and no
    compile outside warm-up) sits between the draft and verify
    dispatches."""
    tok = tokens
    outs = []
    for j in range(k + 1):
        pool, tok = mdl.decode_core(params, pool, tok, base_pos + j,
                                    tables, active)[:2]
        outs.append(tok)
    return pool, jnp.stack([tokens] + outs[:k], axis=1)   # (S, k+1)


def _verify_core(mdl: DecodePlaneModel, params, pool, tokens, base_pos,
                 tables, active):
    """The target's scoring of a ``(slots, k+1)`` speculative window in
    one dispatch, and the accepted prefix length resolved on device:
    the leading drafts that are the target's own greedy tokens."""
    pool, greedy = mdl.verify_core(params, pool, tokens, base_pos, tables,
                                   active)
    eq = (tokens[:, 1:] == greedy[:, :-1]).astype(jnp.int32)
    accepted = jnp.cumprod(eq, axis=1).sum(axis=1)    # (S,)
    return pool, greedy, accepted


def _prefill_core(mdl: DecodePlaneModel, width: int, params, pool, staged):
    """The model's prefill over what the host staged (``_unstage``).
    ``(pool, the lanes' next tokens)``, the tokens as scalars of their
    own: a slot's first token goes into the resident decode state
    without leaving the device."""
    tokens, start, chunk_len, slot, tables = _unstage(staged, width)
    pool, tokens = mdl.prefill_core(params, pool, tokens, start, chunk_len,
                                    tables, slot)
    return pool, tuple(tokens[i] for i in range(staged.shape[0]))


def _state_reset_core(state, slot):
    """Zero one slot's rows of every layer's recurrent-state buffers
    (``state[layer]`` is ``pool[layer]`` without its paged buffers)."""
    return tuple(tuple(buf.at[slot].set(0) for buf in layer)
                 for layer in state)


# -- the engine --------------------------------------------------------------

def _add(sums: Dict[str, list], values: Dict[str, float]) -> None:
    """One more sample of each named figure into ``[sum, count]``."""
    for name, value in values.items():
        cell = sums.setdefault(name, [0.0, 0])
        cell[0] += value
        cell[1] += 1


def _means(sums: Dict[str, list]) -> Dict[str, float]:
    return {name: total / n for name, (total, n) in sums.items()}


_CLOCK_STATES = ("empty", "host", "sync")


def _sched_stats(sums: Dict[str, float]) -> dict:
    """The turn clock's seconds by state, the turns, and each state's
    share of the three states' sum."""
    out = {f"{st}_s": sums.get(f"{st}_s", 0.0) for st in _CLOCK_STATES}
    total = sum(out.values())
    out["turns"] = int(sums.get("turns", 0))
    for st in _CLOCK_STATES:
        out[f"{st}_share"] = out[f"{st}_s"] / total if total else 0.0
    return out


def _prefill_stats(sums: Dict[str, float]) -> dict:
    """Prefill dispatches (``runs``), the chunks they carried, the rows
    they computed (padding included), those that rode inside a decode
    step (``fused``), chunks a run and the fused share of the runs."""
    out = {name: int(sums.get(name, 0))
           for name in ("runs", "chunks", "rows", "fused")}
    runs = out["runs"]
    out["chunks_per_run"] = out["chunks"] / runs if runs else 0.0
    out["fused_share"] = out["fused"] / runs if runs else 0.0
    return out


def _request_stats(sums: Dict[str, float]) -> dict:
    """The requests whose first token was committed, and the means of
    the two waits that make up their time to it."""
    n = int(sums.get("count", 0))
    return {"count": n,
            **{f"{name}_mean": sums.get(name, 0.0) / n if n else 0.0
               for name in ("queue_wait_ms", "prefill_wait_ms")}}


class DecodeEngine:
    """Owns the model(s), the paged KV pools, and the compiled
    executables.  All knobs default from the environment:
    ``MXNET_DECODE_SLOTS`` / ``MXNET_DECODE_PAGES`` /
    ``MXNET_DECODE_PAGE_SIZE`` / ``MXNET_DECODE_SPEC_K`` /
    ``MXNET_DECODE_PREFILL_CHUNK``."""

    def __init__(self, model: DecodePlaneModel, *,
                 draft_model: Optional[DecodePlaneModel] = None,
                 spec_k: Optional[int] = None,
                 max_slots: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 page_size: Optional[int] = None,
                 pages_per_slot: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_floor: int = 16):
        self.model = model
        self.draft = draft_model
        kinds = {n for _, spec in model.cache_layout for n, _, _ in spec}
        if kinds:
            if draft_model is not None or spec_k:
                raise ValueError(
                    f"{type(model).__name__} keeps recurrent state "
                    f"({', '.join(sorted(kinds))}) that "
                    f"a rejected draft token would already have advanced; "
                    f"speculative decode (draft_model / spec_k) needs state "
                    f"snapshots, which the decode plane does not have")
            spec_k = 0
        self.max_slots = (int(max_slots) if max_slots is not None
                          else _env_int("MXNET_DECODE_SLOTS", 8))
        self.page_size = (int(page_size) if page_size is not None
                          else _env_int("MXNET_DECODE_PAGE_SIZE", 16))
        self.num_pages = (int(num_pages) if num_pages is not None
                          else _env_int("MXNET_DECODE_PAGES", 256))
        self.spec_k = (int(spec_k) if spec_k is not None
                       else _env_int("MXNET_DECODE_SPEC_K", 4))
        self.prefill_chunk = (int(prefill_chunk)
                              if prefill_chunk is not None
                              else _env_int("MXNET_DECODE_PREFILL_CHUNK",
                                            128))
        self.prefill_floor = min(int(prefill_floor), self.prefill_chunk)
        if draft_model is not None:
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError("draft/target vocab sizes differ")
        self.cache = PagedKVCache(
            layout=model.cache_layout, num_pages=self.num_pages,
            page_size=self.page_size, max_slots=self.max_slots,
            pages_per_slot=pages_per_slot,
            dtype=model.params["embed"].dtype)
        self.draft_cache = None
        if draft_model is not None:
            self.draft_cache = PagedKVCache(
                layout=draft_model.cache_layout, num_pages=self.num_pages,
                page_size=self.page_size, max_slots=self.max_slots,
                pages_per_slot=self.cache.pages_per_slot,
                dtype=draft_model.params["embed"].dtype)
        self._exec: Dict[str, Any] = {}
        self.compiles = 0
        # the decode executable's inputs, resident on the device: the
        # executable returns their successor, `state_edit` rewrites one
        # slot's row, and nothing else writes them.  The host keeps what
        # it can know without a read: who is active, and where.
        slots, width = self.max_slots, self.cache.pages_per_slot
        self._resident = (jnp.zeros((slots,), jnp.int32),
                          jnp.zeros((slots,), jnp.int32),
                          jnp.zeros((slots,), bool),
                          jnp.zeros((slots, width), jnp.int32))
        self._positions = onp.zeros((slots,), onp.int32)
        self._active = onp.zeros((slots,), bool)
        self._no_token = jnp.zeros((), jnp.int32)
        # decode steps dispatched and not yet read, oldest first: (the
        # step's tokens on the device, the model's counters beside them,
        # whether a profiler capture ran).  The chain is one deep: the
        # step before is read after this one's dispatch, so two at most
        self._in_flight: collections.deque = collections.deque(maxlen=2)
        # the model's counters as last read, and their running sums
        # behind stats(), [sum, reads] by name: over the engine's life
        # and over the steps dispatched under a profiler capture
        self.counters: Dict[str, float] = {}
        self._life: Dict[str, list] = {}
        self._traced: Dict[str, list] = {}
        # what the scheduler books beside them (`book`): running sums by
        # kind and name, over the engine's life and under a capture
        self._booked: Dict[str, Dict[str, float]] = {}
        self._booked_traced: Dict[str, Dict[str, float]] = {}
        # seconds the host has been blocked in a read, over the engine's
        # life: the scheduler takes its turn's part as a difference
        self.sync_s = 0.0
        # share of the page table under live context in the last decode
        # step (what the paged kernel walks), and its sum over the steps;
        # whether that step was dispatched with the one before it unread
        self.kv_live_share = 0.0
        self._kv_live_sum = 0.0
        # the tokens of that context (what an attention reads), summed
        # over the steps, and again over those under a profiler capture
        self._live_tokens_sum = 0
        self._traced_tokens_sum = 0
        self._traced_steps = 0
        self.chained = 0
        self._chained_steps = 0
        self._decode_steps = 0
        self.state_edits = 0

    # -- properties ----------------------------------------------------------

    @property
    def spec_enabled(self) -> bool:
        return self.draft is not None and self.spec_k >= 1

    @property
    def slot_capacity(self) -> int:
        return self.cache.slot_capacity

    def prefill_bucket(self, n: int) -> int:
        return min(_pow2(n, self.prefill_floor), self.prefill_chunk)

    @property
    def prefill_runs(self) -> int:
        """Prefill dispatches over the engine's life (the scheduler
        takes its turn's part as a difference)."""
        return int(self._booked.get("prefill", {}).get("runs", 0))

    @property
    def fuses(self) -> bool:
        """Whether a decode step can carry a prefill dispatch's lanes:
        the model supplies ``turn_logits``."""
        return (type(self.model).turn_logits
                is not DecodePlaneModel.turn_logits
                and not self.spec_enabled)

    @property
    def prefill_lanes(self) -> int:
        """The most chunks one prefill dispatch carries: as many full
        chunks as ``_PREFILL_ROWS`` rows hold, no more than there are
        slots, a power of two."""
        most = max(1, min(_PREFILL_ROWS // self.prefill_chunk,
                          self.max_slots))
        return 1 << (most.bit_length() - 1)

    # -- compiled-executable plumbing ---------------------------------------

    @staticmethod
    def _model_fp(mdl):
        """Architecture fingerprint of one model for artifact keys."""
        return None if mdl is None else mdl.fingerprint()

    def _artifact_sig(self, key: str, args):
        """Content signature of one decode executable: the exec key,
        both model architectures, the engine's KV/spec geometry, and
        the exact arg pytree (structure + leaf shapes/dtypes)."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        # "out2": the decode executable returns a model's counters too;
        # an artifact from before has another output tree
        return (key, "out2", self._model_fp(self.model),
                self._model_fp(self.draft),
                self.spec_k, self.max_slots, self.page_size, self.num_pages,
                str(treedef),
                tuple((tuple(jnp.shape(l)), str(jnp.result_type(l)))
                      for l in leaves))

    def _core(self, key: str):
        """The traced function behind an executable's key."""
        named = {"decode": functools.partial(_chained_decode_core,
                                             self.model),
                 "state_edit": _state_edit_core,
                 "state_reset": _state_reset_core,
                 "draft": functools.partial(_draft_core, self.draft,
                                            self.spec_k),
                 "verify": functools.partial(_verify_core, self.model)}
        if key in named:
            return named[key]
        if key.startswith("decode_fill_"):
            return functools.partial(_turn_core, self.model,
                                     self.cache.pages_per_slot)
        return functools.partial(
            _prefill_core,
            self.draft if key.startswith("draft_") else self.model,
            self.cache.pages_per_slot)

    def _get_exec(self, key: str, args, donate=(1,)):
        """Load-or-compile one executable WITHOUT running it.  Order:
        in-process memo → artifact store (deserialize; ``compiles``
        stays 0) → jit compile (ticks ``compiles``, commits back).
        ``donate`` is the position of the cache's buffers in ``args``."""
        ex = self._exec.get(key)
        if ex is not None:
            return ex
        from ... import artifacts
        asig = self._artifact_sig(key, args)
        art = artifacts.load("decode_exec", asig)
        if art is not None:
            self._exec[key] = art.compiled
            return art.compiled
        donate = (donate if jax.default_backend() == "tpu" else ())
        core = self._core(key)

        def fn(*a):
            return core(*a)

        # the executable's name in a device trace: jit_mxtpu_<key>
        fn.__name__ = fn.__qualname__ = f"mxtpu_{key}"
        t0 = time.perf_counter()
        ex = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        telemetry.record_compile(time.perf_counter() - t0, "decode")
        self._exec[key] = ex
        self.compiles += 1
        artifacts.save("decode_exec", asig, ex, meta={"exec_key": key})
        return ex

    def _call(self, key: str, args, donate=(1,)):
        return self._get_exec(key, args, donate)(*args)

    # -- per-slot recurrent state -------------------------------------------

    def _state(self):
        """The recurrent-state buffers of every layer, without the
        paged ones (a layer that keeps none: an empty tuple)."""
        return self.cache.split()[1]

    def _reset_state(self, slot: int) -> None:
        """Zero ``slot``'s rows of every state buffer, in place."""
        paged, state = self.cache.split()
        state = self._call(
            "state_reset", (state, jnp.asarray(slot, jnp.int32)),
            donate=(0,))
        self.cache.pool = tuple(pg + st for pg, st in zip(paged, state))

    def _tables(self, cache) -> jnp.ndarray:
        return jnp.asarray(cache.tables, jnp.int32)

    # -- the resident decode state --------------------------------------------

    def _edit_state(self, slot: int, token, position: int, active: bool,
                    row) -> None:
        """Rewrite ``slot``'s row of the resident state on the device:
        its token, position, whether it decodes, its page-table row."""
        with tracing.span("decode.stage"):
            patch = onp.concatenate((
                onp.asarray([slot, position, active], onp.int32), row))
        self._resident = self._call(
            "state_edit", (self._resident, token, patch), donate=(0,))
        self._positions[slot], self._active[slot] = position, active
        self.state_edits += 1

    def activate_slot(self, slot: int, token, position: int) -> None:
        """``slot`` decodes from the next ``decode_step`` on: ``token``
        (a prefill's output, still on the device) at ``position``,
        through the pages the cache gave it."""
        self._edit_state(slot, token, position, True,
                         self.cache.tables[slot])

    def deactivate_slot(self, slot: int) -> None:
        """``slot`` decodes no more and addresses no page.  A
        ``decode_step`` already dispatched still advances it once: the
        edit follows it on the device's one stream, as does whatever a
        successor in the slot or in its pages is given after."""
        if self._active[slot]:
            self._edit_state(slot, self._no_token, 0, False,
                             onp.zeros_like(self.cache.tables[slot]))

    def _prefill_shape(self, chunks: int, longest: int):
        """``(lanes, bucket)`` of the dispatch that carries ``chunks``
        chunks, the longest of ``longest`` tokens: one chunk in its own
        pow2 bucket; several in a pow2 of lanes of the full chunk's."""
        if chunks == 1:
            return 1, self.prefill_bucket(longest)
        return _pow2(chunks, 1), self.prefill_chunk

    def _fill_key(self, lanes: int) -> str:
        """The decode step that carries ``lanes`` lanes, each the full
        chunk's bucket (a short chunk is padded to it)."""
        return f"decode_fill_b{lanes * self.prefill_chunk}"

    def _stage(self, cache, group, lanes: int, bucket: int):
        """What ``_prefill_core`` unpacks, built on the host: a row a
        lane of ``group``'s ``(slot, chunk, start)``; the lanes left
        over are padding (length 0, each a slot index of its own past
        the buffers: every index of a scatter stays distinct)."""
        staged = onp.zeros((lanes, bucket + 3 + cache.pages_per_slot),
                           onp.int32)
        staged[:, bucket + 2] = self.max_slots + onp.arange(lanes)
        for row, (slot, chunk, start) in zip(staged, group):
            row[:len(chunk)] = chunk
            row[bucket:bucket + 3] = start, len(chunk), slot
            row[bucket + 3:] = cache.tables[slot]
        return staged

    def warmup(self, prefill_lengths: Sequence[int] = (1,)) -> List[str]:
        """Materialize every executable this engine will dispatch —
        decode and its state's edit (draft and verify instead under
        speculation), every decode step with lanes inside it where the
        model offers them, one one-lane prefill per bucket covering
        ``prefill_lengths`` and every multi-lane prefill a turn with
        several filling slots dispatches — WITHOUT running any of
        them.  Against a populated artifact store each one deserializes (``compiles``
        stays 0); otherwise this pays the compiles ahead of traffic.  Also prefetches the kernel-autotune cache.
        Returns the exec keys materialized."""
        from ... import kernels
        n_kern = kernels.warm_cache()
        if n_kern:
            get_logger("mxnet_tpu.serving.decode").info(
                "warmup: %d tuned kernel config(s) preloaded", n_kern)
        keys = []
        if self.spec_enabled:
            tok = pos = jnp.zeros((self.max_slots,), jnp.int32)
            tables = self._tables(self.cache)   # the draft's have its shape
            act = jnp.zeros((self.max_slots,), bool)
            self._get_exec("draft", (self.draft.params, self.draft_cache.pool,
                                     tok, pos, tables, act))
            window = jnp.zeros((self.max_slots, self.spec_k + 1), jnp.int32)
            self._get_exec("verify", (self.model.params, self.cache.pool,
                                      window, pos, tables, act))
            keys += ["draft", "verify"]
        else:
            self._get_exec("decode", (self.model.params, self.cache.pool,
                                      self._resident), donate=(1, 2))
            self._get_exec("state_edit", (
                self._resident, self._no_token,
                onp.zeros((3 + self.cache.pages_per_slot,), onp.int32)),
                donate=(0,))
            keys += ["decode", "state_edit"]
            lanes = 1
            while self.fuses and lanes <= self.prefill_lanes:
                key = self._fill_key(lanes)
                self._get_exec(key, (
                    self.model.params, self.cache.pool, self._resident,
                    self._stage(self.cache, (), lanes, self.prefill_chunk)),
                    donate=(1, 2))
                keys.append(key)
                lanes *= 2
        if self.cache.state_layers:
            self._get_exec("state_reset",
                           (self._state(), jnp.asarray(0, jnp.int32)),
                           donate=(0,))
            keys.append("state_reset")
        # every prefill shape a turn can dispatch: one lane in each
        # bucket asked for, and every pow2 of lanes of the full chunk
        shapes = [(1, bucket) for bucket in sorted(
            {self.prefill_bucket(int(n)) for n in prefill_lengths})]
        lanes = 2
        while lanes <= self.prefill_lanes:
            shapes.append((lanes, self.prefill_chunk))
            lanes *= 2
        for lanes, bucket in shapes:
            key = f"prefill_b{lanes * bucket}"
            self._get_exec(key, (self.model.params, self.cache.pool,
                                 self._stage(self.cache, (), lanes, bucket)))
            keys.append(key)
            if self.draft_cache is not None:
                self._get_exec("draft_" + key, (
                    self.draft.params, self.draft_cache.pool,
                    self._stage(self.draft_cache, (), lanes, bucket)))
                keys.append("draft_" + key)
        return keys

    # -- device steps --------------------------------------------------------

    def decode_step(self, chunks=()):
        """Dispatch one non-speculative engine step over the full slot
        grid, from the resident state and into it.  ``chunks`` (what
        :meth:`prefill_chunks` takes, up to ``prefill_lanes`` of them,
        their slots not decoding) ride inside the step as lanes of
        ``decode_fill_b<rows>``, one pass over the weights for both: a
        model that supplies ``turn_logits`` only (:attr:`fuses`).  Returns
        the next token per slot and the chunks' next tokens in their
        order, on the device and not waited for: :meth:`read` them after
        the next turn's dispatch."""
        traced = tracing.capturing()
        self._count_live(self._positions, self._active, traced)
        self.chained = int(bool(self._in_flight))
        self._chained_steps += self.chained
        args = (self.model.params, self.cache.pool, self._resident)
        firsts = ()
        if chunks:
            slots = [slot for slot, _, _ in chunks]
            if (not self.fuses or len(chunks) > self.prefill_lanes
                    or self._active[slots].any()):
                raise ValueError(
                    f"a decode step of {type(self.model).__name__} carries "
                    f"no chunk of a decoding slot and at most "
                    f"{self.prefill_lanes if self.fuses else 0} chunks")
            lanes = _pow2(len(chunks), 1)
            with tracing.span("decode.stage"):
                staged = self._stage(self.cache, chunks, lanes,
                                     self.prefill_chunk)
            (self.cache.pool, self._resident, nxt, counters,
             firsts) = self._call(self._fill_key(lanes), args + (staged,),
                                  donate=(1, 2))
            self.book("prefill", traced, runs=1, chunks=len(chunks),
                      rows=lanes * self.prefill_chunk, fused=1)
        else:
            self.cache.pool, self._resident, nxt, counters = self._call(
                "decode", args, donate=(1, 2))
        self._positions += self._active
        self._in_flight.append((nxt, counters, traced))
        return nxt, list(firsts[:len(chunks)])

    def read(self, nxt, firsts):
        """The turn's one blocking read: a ``decode_step``'s tokens
        (or None) and a list of prefill chunks' first tokens, as host
        values.  The model's counters of that step come in the same
        transfer, into ``counters`` and the running means of
        :meth:`stats`."""
        counters, traced = {}, False
        if any(step[0] is nxt for step in self._in_flight):
            while True:         # and forget a step that was never read
                tokens, counters, traced = self._in_flight.popleft()
                if tokens is nxt:
                    break
        with self._blocked():
            nxt_host, firsts_host, counters = jax.device_get(
                (nxt, firsts, counters))
        if counters:
            self.counters = {k: float(v) for k, v in counters.items()}
            _add(self._life, self.counters)
            if traced:
                _add(self._traced, self.counters)
        return nxt_host, firsts_host

    @contextlib.contextmanager
    def _blocked(self):
        """The host waits for the device: the span ``decode.sync``, and
        its seconds onto ``sync_s``."""
        with tracing.span("decode.sync"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.sync_s += time.perf_counter() - t0

    def book(self, kind: str, traced: bool, **values: float) -> None:
        """What the scheduler reports into the engine's accounting,
        beside ``counters``: ``values`` onto the running sums of
        ``kind`` (``"sched"``: the turn clock's ``empty_s``, ``host_s``,
        ``sync_s`` and ``turns``; ``"requests"``: ``count`` and a
        request's two waits at its first token; the engine's own
        ``"prefill"``: ``runs``, ``chunks``, ``rows``, and ``fused``, the
        runs that rode inside a decode step), over the
        engine's life and, where ``traced``, over what a profiler
        capture covered."""
        for booked in (self._booked, self._booked_traced)[:1 + traced]:
            sums = booked.setdefault(kind, {})
            for name, value in values.items():
                sums[name] = sums.get(name, 0.0) + value

    def _count_live(self, positions, active, traced=False):
        """Pages holding an active slot's context (its pending token's
        position included) over the pages of the whole table, and the
        tokens of that context (what an attention reads): host integers,
        no device read."""
        at = onp.asarray(positions)[onp.asarray(active, bool)]
        live = int((at // self.page_size + 1).sum())
        self.kv_live_share = live / (self.max_slots
                                     * self.cache.pages_per_slot)
        self._kv_live_sum += self.kv_live_share
        live_tokens = int((at + 1).sum())
        self._live_tokens_sum += live_tokens
        if traced:
            self._traced_tokens_sum += live_tokens
            self._traced_steps += 1
        self._decode_steps += 1

    def spec_step(self, tokens, base_pos, active):
        """Draft k proposals then verify in one target dispatch.
        Returns (greedy (S, k+1), accepted (S,)) host numpy."""
        self._count_live(base_pos, active)
        with tracing.span("decode.stage"):
            tok = jnp.asarray(tokens, jnp.int32)
            pos = jnp.asarray(base_pos, jnp.int32)
            act = jnp.asarray(active, bool)
            dargs = (self.draft.params, self.draft_cache.pool, tok, pos,
                     self._tables(self.draft_cache), act)
        self.draft_cache.pool, window = self._call("draft", dargs)
        with tracing.span("decode.stage"):
            vargs = (self.model.params, self.cache.pool, window, pos,
                     self._tables(self.cache), act)
        self.cache.pool, greedy, accepted = self._call("verify", vargs)
        with self._blocked():
            return onp.asarray(greedy), onp.asarray(accepted)

    def prefill_chunks(self, chunks):
        """Feed the next prompt chunk of every filling slot: ``chunks``
        is ``(slot, tokens, start)`` a slot, a slot once.  Up to
        ``prefill_lanes`` of them ride in one dispatch, one pass over
        the weights (more: further dispatches).  Returns the greedy next
        token after each chunk, in ``chunks``' order, on the device and
        not waited for."""
        out = []
        for i in range(0, len(chunks), self.prefill_lanes):
            group = chunks[i:i + self.prefill_lanes]
            lanes, bucket = self._prefill_shape(
                len(group), max(len(chunk) for _, chunk, _ in group))
            key = f"prefill_b{lanes * bucket}"
            with tracing.span("decode.prefill", lanes=lanes,
                              chunks=len(group),
                              tokens=sum(len(c) for _, c, _ in group)):
                with tracing.span("decode.stage"):
                    staged = self._stage(self.cache, group, lanes, bucket)
                self.cache.pool, tokens = self._call(
                    key, (self.model.params, self.cache.pool, staged))
                if self.draft_cache is not None:
                    with tracing.span("decode.stage"):
                        staged = self._stage(self.draft_cache, group, lanes,
                                             bucket)
                    self.draft_cache.pool, _ = self._call(
                        "draft_" + key, (self.draft.params,
                                         self.draft_cache.pool, staged))
            self.book("prefill", tracing.capturing(), runs=1,
                      chunks=len(group), rows=lanes * bucket)
            out += tokens[:len(group)]
        return out

    # -- slot page lifecycle -------------------------------------------------

    def acquire_slot(self, slot: int, tokens: int) -> None:
        self.cache.acquire(slot, tokens)
        if self.cache.state_layers:
            # whoever held the slot last left its state behind
            with tracing.span("decode.state_reset", slot=slot):
                self._reset_state(slot)
        if self.draft_cache is not None:
            try:
                self.draft_cache.acquire(slot, tokens)
            except Exception:
                self.cache.release(slot)
                raise

    def release_slot(self, slot: int) -> int:
        self.deactivate_slot(slot)
        n = self.cache.release(slot)
        if self.draft_cache is not None:
            self.draft_cache.release(slot)
        return n

    def can_admit(self, tokens: int) -> bool:
        need = self.cache.pages_for(tokens)
        ok = self.cache.allocator.available >= need
        if self.draft_cache is not None:
            ok = ok and self.draft_cache.allocator.available >= need
        return ok

    def stats(self) -> dict:
        return {"compiles": self.compiles,
                "executables": sorted(self._exec),
                "max_slots": self.max_slots,
                "page_size": self.page_size,
                "num_pages": self.num_pages,
                "pages_used": self.cache.pages_used(),
                "slot_capacity": self.slot_capacity,
                "spec_k": self.spec_k if self.spec_enabled else 0,
                "page_bytes": self.cache.page_bytes,
                "state_bytes": self.cache.state_bytes,
                # layers that hold pages, and layers that hold state
                "page_layers": self.cache.page_layers,
                "state_layers": self.cache.state_layers,
                "state_slots_live": self.cache.state_slots_live(),
                "state_resets": self.cache.state_resets,
                "kv_live_share": (self._kv_live_sum / self._decode_steps
                                  if self._decode_steps else 0.0),
                # the decoding slots' summed context lengths a decode
                # step and the model's counters: means over the engine's
                # life, and over the steps dispatched while a profiler
                # capture ran (what a trace's kernel times belong to)
                "live_tokens_mean": (
                    self._live_tokens_sum / self._decode_steps
                    if self._decode_steps else 0.0),
                "counters": _means(self._life),
                # the scheduler thread's time by state (nothing to run /
                # its own work / blocked in a read) and the waits of the
                # requests whose first token was committed, as booked
                "sched": _sched_stats(self._booked.get("sched", {})),
                "requests": _request_stats(
                    self._booked.get("requests", {})),
                # prefill dispatches, the chunks that rode in them, and
                # those that rode inside a decode step
                "prefill": _prefill_stats(self._booked.get("prefill", {})),
                "traced": {
                    "decode_steps": self._traced_steps,
                    "live_tokens_mean": (
                        self._traced_tokens_sum / self._traced_steps
                        if self._traced_steps else 0.0),
                    "counters": _means(self._traced),
                    "sched": _sched_stats(
                        self._booked_traced.get("sched", {})),
                    "requests": _request_stats(
                        self._booked_traced.get("requests", {})),
                    "prefill": _prefill_stats(
                        self._booked_traced.get("prefill", {}))},
                "chained_share": (self._chained_steps / self._decode_steps
                                  if self._decode_steps else 0.0),
                "state_edits": self.state_edits}
