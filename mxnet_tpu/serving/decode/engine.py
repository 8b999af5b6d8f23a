"""Decode engine: fixed-shape compiled executables over paged KV state.

Every device-side path is ONE jit-compiled executable per static
shape, compiled lazily on first use and reused forever (the
fixed-shape-executable invariant):

- ``decode_step`` — one token per active slot over the full
  ``(max_slots,)`` grid: active-slot mask, per-slot positions and page
  tables are traced int arrays, so admission/completion NEVER
  recompiles;
- ``prefill[bucket]`` — one prompt chunk for one slot, chunk length
  padded into pow2 sequence buckets (chunked prefill: long prompts
  are fed bucket-by-bucket so running decodes aren't stalled behind
  one long prompt);
- ``draft``/``verify`` — the speculative path: the draft model
  proposes ``k`` tokens per slot (its own paged KV pool, same page
  geometry, shared page tables), then the target model scores all
  ``k+1`` positions in a single dispatch and accepts the longest
  matching prefix on device (greedy speculative decode is
  token-identical to the non-speculative path by construction: every
  emitted token is the target's own argmax).

Attention inside ``decode_step``/``verify`` runs through the
``paged_attention`` kernel registrant (ops/paged_attention.py) and all
rotary embeddings through the ``rope`` registrant (ops/rope.py), so
block configs resolve through the kernel autotune cache exactly like
flash attention in training.

The engine is handed its model (:class:`DecodePlaneModel`): the
parameters as a pytree, the geometry of its K/V heads, the kinds of
per-slot recurrent state its layers keep beside K/V, and the traced
cores of the decode step and of one prefill chunk.  The engine owns
the cache the model's cores read and write, the executables (named
``mxtpu_decode``, ``mxtpu_prefill_b<n>``, and ``mxtpu_state_reset`` for
a model with recurrent state) and their donation; it knows nothing of
a layer.  :class:`DecodeModel` is the built-in multi-head transformer;
``falcon_h1.FalconH1`` is a hybrid of grouped-query attention and
Mamba-2 heads.  Draft and verify are :class:`DecodeModel`'s alone: a
rejected draft of a model with recurrent state would need the state
from before it, and nothing snapshots it.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as onp

import jax
import jax.numpy as jnp
from jax import lax

from ... import telemetry, tracing
from ...log import get_logger
from ...ops.paged_attention import paged_attention
from ...ops.rope import rope, rope_reference
from .paged_kv import PagedKVCache

__all__ = ["DecodePlaneModel", "DecodeModel", "DecodeEngine"]

_NEG_INF = -1e30


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


def _rms(x, g, eps=1e-6):
    xf = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * g


class DecodePlaneModel:
    """What :class:`DecodeEngine` asks of a model.

    Attributes: ``params`` (a pytree of device arrays, the first
    argument of every executable), ``vocab_size``, ``n_layers``,
    ``kv_heads`` and ``head_dim`` (the K/V buffers are ``(num_pages,
    page_size, kv_heads * head_dim)``), and ``state_spec``: the kinds of
    per-slot recurrent state every layer keeps beside K and V, ``(name,
    shape of one slot, dtype)`` each, in the order the layer's buffers
    follow K and V in ``pool[layer]``.  Empty: none.

    The traced cores take the cache's ``pool`` and return its
    successor; each buffer has one writer and no reader of its old
    value left, so a donated buffer is updated in place."""

    state_spec: tuple = ()

    def fingerprint(self) -> tuple:
        """Everything the cores bake into an executable besides the
        shapes of its arguments (the artifact store's key)."""
        raise NotImplementedError

    def decode_core(self, params, pool, tokens, positions, tables, active):
        """One token per slot: ``(pool, next token per slot)``."""
        raise NotImplementedError

    def prefill_core(self, params, pool, tokens, start, chunk_len, table,
                     *slot):
        """One prompt chunk of one slot: ``(pool, next token)``.  The
        slot's index follows ``table`` for a model with recurrent
        state, which is addressed by it."""
        raise NotImplementedError

    def _ref_logits_last(self, tokens):
        """Last-position logits of the model's dense forward over the
        whole of ``tokens``, no cache: the in-program oracle."""
        raise NotImplementedError

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


@functools.partial(jax.jit, static_argnames=("n_heads", "rope_base"))
def _dense_logits_last(params, tokens, *, n_heads, rope_base):
    """Last-position logits of a dense causal forward over the whole
    sequence — the O(T^2) full-recompute oracle the paged path is
    pinned to.  One program per sequence length (the parameters are an
    argument, not constants)."""
    t = tokens.shape[0]
    dim = params["embed"].shape[1]
    hd = dim // n_heads
    pos = jnp.arange(t, dtype=jnp.int32)
    x = params["embed"][tokens]
    scale = 1.0 / (hd ** 0.5)
    for lp in params["layers"]:
        h1 = _rms(x, lp["ln1"])
        q = rope_reference((h1 @ lp["wq"]).reshape(t, n_heads, hd), pos,
                           base=rope_base)
        k = rope_reference((h1 @ lp["wk"]).reshape(t, n_heads, hd), pos,
                           base=rope_base)
        v = (h1 @ lp["wv"]).reshape(t, n_heads, hd)
        s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        qp = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        kp = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(qp >= kp, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32))
        x = x + o.reshape(t, dim).astype(x.dtype) @ lp["wo"]
        h2 = _rms(x, lp["ln2"])
        x = x + jax.nn.gelu(h2 @ lp["w1"]) @ lp["w2"]
    x = _rms(x, params["lnf"])
    return x[-1] @ params["embed"].T


class DecodeModel(DecodePlaneModel):
    """A small causal LM as a plain parameter pytree + pure functions.

    Deliberately framework-free (no gluon Block machinery): the decode
    executables trace straight jnp math over ``self.params``, which is
    what lets the engine AOT-compile them against fixed shapes.  The
    LM head is tied to the embedding."""

    def __init__(self, vocab_size: int, *, dim: int = 64,
                 n_heads: int = 4, n_layers: int = 2, mlp_ratio: int = 2,
                 rope_base: float = 10000.0, seed: int = 0,
                 dtype="float32"):
        if dim % n_heads:
            raise ValueError(f"dim {dim} not divisible by heads {n_heads}")
        if (dim // n_heads) % 2:
            raise ValueError("head_dim must be even for rope")
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.n_heads = self.kv_heads = int(n_heads)
        self.n_layers = int(n_layers)
        self.head_dim = dim // n_heads
        self.rope_base = float(rope_base)
        rng = onp.random.RandomState(seed)

        def mat(*shape, scale):
            return jnp.asarray(rng.randn(*shape) * scale, dtype=dtype)

        w = 1.0 / (dim ** 0.5)
        layers = []
        for _ in range(n_layers):
            layers.append({
                "ln1": jnp.ones((dim,), dtype=dtype),
                "wq": mat(dim, dim, scale=w),
                "wk": mat(dim, dim, scale=w),
                "wv": mat(dim, dim, scale=w),
                "wo": mat(dim, dim, scale=w),
                "ln2": jnp.ones((dim,), dtype=dtype),
                "w1": mat(dim, mlp_ratio * dim, scale=w),
                "w2": mat(mlp_ratio * dim, dim,
                          scale=1.0 / ((mlp_ratio * dim) ** 0.5)),
            })
        self.params: Dict[str, Any] = {
            "embed": mat(vocab_size, dim, scale=0.5),
            "layers": layers,
            "lnf": jnp.ones((dim,), dtype=dtype),
        }

    def fingerprint(self) -> tuple:
        return (self.vocab_size, self.dim, self.n_heads, self.n_layers,
                self.head_dim, self.rope_base)

    def decode_core(self, params, pool, tokens, positions, tables, active):
        return _decode_core(self, params, pool, tokens, positions, tables,
                            active)

    def prefill_core(self, params, pool, tokens, start, chunk_len, table):
        return _prefill_core(self, params, pool, tokens, start, chunk_len,
                             table)

    # -- dense full-recompute oracle (tests pin the paged path to it) --------

    def _ref_logits_last(self, tokens):
        """Last-position logits of the dense oracle for ``tokens``."""
        return _dense_logits_last(self.params, tokens,
                                  n_heads=self.n_heads,
                                  rope_base=self.rope_base)


# -- traced cores ------------------------------------------------------------

def _write_kv(kbuf, vbuf, page, offset, k, v):
    """Scatter this step's K/V rows into ONE layer's own K and V
    buffers, each ``(num_pages, page_size, Hkv*D)``, and return both.
    ``page``/``offset`` address one position per row; masked rows carry
    the sentinel page ``num_pages`` — one past the buffer — and are
    dropped (mode='drop').  Each buffer is a donated argument with this
    scatter as its only writer and nothing left that reads the old
    value, so XLA updates it in place: no copy of a buffer exists."""
    hd = kbuf.shape[-1]
    kbuf = kbuf.at[page, offset].set(
        k.reshape(-1, hd).astype(kbuf.dtype), mode="drop")
    vbuf = vbuf.at[page, offset].set(
        v.reshape(-1, hd).astype(vbuf.dtype), mode="drop")
    return kbuf, vbuf


def _decode_core(mdl: DecodeModel, params, pool, tokens, positions,
                 tables, active):
    """Consume one token per slot at ``positions`` (writing its KV),
    return (pool, argmax next token per slot).  ``pool`` is the cache's
    pytree: one ``(k, v)`` pair of whole buffers per layer."""
    s_ = tokens.shape[0]
    h_, hd = mdl.n_heads, mdl.head_dim
    num_pages, ps = pool[0][0].shape[:2]
    x = params["embed"][tokens]
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    pagerow = jnp.take_along_axis(
        tables, (positions // ps)[:, None], axis=1)[:, 0]
    page = jnp.where(active, pagerow, num_pages).astype(jnp.int32)
    offset = positions % ps
    out = []
    for (kbuf, vbuf), lp in zip(pool, params["layers"]):
        h1 = _rms(x, lp["ln1"])
        q = rope((h1 @ lp["wq"]).reshape(s_, h_, hd), positions,
                 base=mdl.rope_base)
        k = rope((h1 @ lp["wk"]).reshape(s_, h_, hd), positions,
                 base=mdl.rope_base)
        v = (h1 @ lp["wv"]).reshape(s_, h_, hd)
        kbuf, vbuf = _write_kv(kbuf, vbuf, page, offset, k, v)
        out.append((kbuf, vbuf))
        attn = paged_attention(q, kbuf, vbuf, tables, lengths)
        x = x + attn.reshape(s_, mdl.dim).astype(x.dtype) @ lp["wo"]
        h2 = _rms(x, lp["ln2"])
        x = x + jax.nn.gelu(h2 @ lp["w1"]) @ lp["w2"]
    x = _rms(x, params["lnf"])
    logits = x @ params["embed"].T
    return tuple(out), jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _verify_core(mdl: DecodeModel, params, pool, tokens, base_pos,
                 tables, active):
    """Target-model scoring of a ``(slots, k+1)`` speculative window in
    one dispatch: writes KV for every window position, computes greedy
    targets at each, and resolves the accepted prefix length on
    device.  Attention per window offset goes through the SAME
    paged_attention kernel as decode_step, so accepted tokens are
    bitwise those the non-speculative path would emit."""
    s_, w_ = tokens.shape
    h_, hd = mdl.n_heads, mdl.head_dim
    num_pages, ps = pool[0][0].shape[:2]
    pos = base_pos[:, None] + jnp.arange(w_, dtype=jnp.int32)[None, :]
    x = params["embed"][tokens]                       # (S, W, dim)
    pagerow = jnp.take_along_axis(tables, pos // ps, axis=1)
    page = jnp.where(active[:, None], pagerow,
                     num_pages).astype(jnp.int32).reshape(s_ * w_)
    offset = (pos % ps).reshape(s_ * w_)
    out = []
    for (kbuf, vbuf), lp in zip(pool, params["layers"]):
        h1 = _rms(x, lp["ln1"])
        q = rope((h1 @ lp["wq"]).reshape(s_, w_, h_, hd), pos,
                 base=mdl.rope_base)
        k = rope((h1 @ lp["wk"]).reshape(s_, w_, h_, hd), pos,
                 base=mdl.rope_base)
        v = (h1 @ lp["wv"]).reshape(s_, w_, h_, hd)
        kbuf, vbuf = _write_kv(kbuf, vbuf, page, offset,
                               k.reshape(s_ * w_, h_, hd),
                               v.reshape(s_ * w_, h_, hd))
        out.append((kbuf, vbuf))
        cols = []
        for j in range(w_):
            lens_j = jnp.where(active, base_pos + j + 1,
                               0).astype(jnp.int32)
            cols.append(paged_attention(q[:, j], kbuf, vbuf, tables,
                                        lens_j))
        attn = jnp.stack(cols, axis=1)                # (S, W, H, hd)
        x = x + attn.reshape(s_, w_, mdl.dim).astype(x.dtype) @ lp["wo"]
        h2 = _rms(x, lp["ln2"])
        x = x + jax.nn.gelu(h2 @ lp["w1"]) @ lp["w2"]
    x = _rms(x, params["lnf"])
    logits = x @ params["embed"].T                    # (S, W, V)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    drafts = tokens[:, 1:]
    eq = (drafts == greedy[:, :-1]).astype(jnp.int32)
    accepted = jnp.cumprod(eq, axis=1).sum(axis=1)    # (S,)
    return tuple(out), greedy, accepted


def _draft_core(mdl: DecodeModel, params, pool, tokens, base_pos,
                tables, active, k: int):
    """k+1 chained draft decode steps (unrolled — ``k`` is static):
    proposes k tokens and leaves the draft pool position-aligned with
    the target's write window (positions base..base+k).  Returns the
    verify window ``(S, k+1)``: the input token then the k proposals,
    assembled here so no eager op (and no compile outside warm-up)
    sits between the draft and verify dispatches."""
    tok = tokens
    outs = []
    for j in range(k + 1):
        pool, tok = _decode_core(mdl, params, pool, tok, base_pos + j,
                                 tables, active)
        outs.append(tok)
    return pool, jnp.stack([tokens] + outs[:k], axis=1)   # (S, k+1)


def _prefill_core(mdl: DecodeModel, params, pool, tokens, start,
                  chunk_len, table):
    """One prompt chunk for ONE slot: ``tokens (bucket,)`` padded,
    ``start``/``chunk_len`` traced scalars, ``table (pages_per_slot,)``
    the slot's page row.  Writes the chunk's KV and returns the greedy
    next token after the chunk's last valid position (meaningful only
    on the final chunk)."""
    b_ = tokens.shape[0]
    h_, hd = mdl.n_heads, mdl.head_dim
    num_pages, ps = pool[0][0].shape[:2]
    scale = 1.0 / (hd ** 0.5)
    pos = start + jnp.arange(b_, dtype=jnp.int32)
    valid = jnp.arange(b_) < chunk_len
    total = start + chunk_len
    x = params["embed"][tokens]
    page = jnp.where(valid, table[pos // ps], num_pages).astype(jnp.int32)
    offset = pos % ps
    p_ = table.shape[0]
    out = []
    for (kbuf, vbuf), lp in zip(pool, params["layers"]):
        h1 = _rms(x, lp["ln1"])
        q = rope((h1 @ lp["wq"]).reshape(b_, h_, hd), pos,
                 base=mdl.rope_base)
        k = rope((h1 @ lp["wk"]).reshape(b_, h_, hd), pos,
                 base=mdl.rope_base)
        v = (h1 @ lp["wv"]).reshape(b_, h_, hd)
        kbuf, vbuf = _write_kv(kbuf, vbuf, page, offset, k, v)
        out.append((kbuf, vbuf))
        # chunk attends its causal prefix (earlier chunks included)
        # over the slot's gathered pages — the chunk itself was just
        # written, so one mask covers intra- and cross-chunk keys
        kctx = kbuf[table].reshape(p_ * ps, h_, hd)
        vctx = vbuf[table].reshape(p_ * ps, h_, hd)
        s = jnp.einsum("bhd,khd->bhk", q.astype(jnp.float32),
                       kctx.astype(jnp.float32)) * scale
        kpos = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = (kpos <= pos[:, None, None]) & (kpos < total)
        s = jnp.where(mask, s, _NEG_INF)
        m = s.max(axis=-1, keepdims=True)
        pr = jnp.where(mask, jnp.exp(s - m), 0.0)
        l = pr.sum(axis=-1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)
        attn = jnp.einsum("bhk,khd->bhd", pr / l,
                          vctx.astype(jnp.float32))
        x = x + attn.reshape(b_, mdl.dim).astype(x.dtype) @ lp["wo"]
        h2 = _rms(x, lp["ln2"])
        x = x + jax.nn.gelu(h2 @ lp["w1"]) @ lp["w2"]
    x = _rms(x, params["lnf"])
    last = lax.dynamic_index_in_dim(x, jnp.maximum(chunk_len - 1, 0),
                                    axis=0, keepdims=False)
    logits = last @ params["embed"].T
    return tuple(out), jnp.argmax(logits).astype(jnp.int32)


def _state_reset_core(state, slot):
    """Zero one slot's rows of every layer's recurrent-state buffers
    (``state[layer]`` is ``pool[layer]`` without K and V)."""
    return tuple(tuple(buf.at[slot].set(0) for buf in layer)
                 for layer in state)


# -- the engine --------------------------------------------------------------

class DecodeEngine:
    """Owns the model(s), the paged KV pools, and the compiled
    executables.  All knobs default from the environment:
    ``MXNET_DECODE_SLOTS`` / ``MXNET_DECODE_PAGES`` /
    ``MXNET_DECODE_PAGE_SIZE`` / ``MXNET_DECODE_SPEC_K``."""

    def __init__(self, model: DecodePlaneModel, *,
                 draft_model: Optional[DecodeModel] = None,
                 spec_k: Optional[int] = None,
                 max_slots: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 page_size: Optional[int] = None,
                 pages_per_slot: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_floor: int = 16):
        self.model = model
        self.draft = draft_model
        if model.state_spec:
            if draft_model is not None or spec_k:
                raise ValueError(
                    f"{type(model).__name__} keeps recurrent state "
                    f"({', '.join(n for n, _, _ in model.state_spec)}) that "
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
            layers=model.n_layers, num_pages=self.num_pages,
            page_size=self.page_size, heads=model.kv_heads,
            head_dim=model.head_dim, max_slots=self.max_slots,
            pages_per_slot=pages_per_slot,
            dtype=model.params["embed"].dtype,
            state_spec=model.state_spec)
        self.draft_cache = None
        if draft_model is not None:
            self.draft_cache = PagedKVCache(
                layers=draft_model.n_layers, num_pages=self.num_pages,
                page_size=self.page_size, heads=draft_model.n_heads,
                head_dim=draft_model.head_dim, max_slots=self.max_slots,
                pages_per_slot=self.cache.pages_per_slot,
                dtype=draft_model.params["embed"].dtype)
        self._exec: Dict[str, Any] = {}
        self.compiles = 0
        # share of the page table under live context in the last decode
        # step (what the paged kernel walks), and its sum over the steps
        self.kv_live_share = 0.0
        self._kv_live_sum = 0.0
        self._decode_steps = 0

    # -- properties ----------------------------------------------------------

    @property
    def spec_enabled(self) -> bool:
        return self.draft is not None and self.spec_k >= 1

    @property
    def slot_capacity(self) -> int:
        return self.cache.slot_capacity

    def prefill_bucket(self, n: int) -> int:
        return min(_pow2(n, self.prefill_floor), self.prefill_chunk)

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
        return (key, self._model_fp(self.model), self._model_fp(self.draft),
                self.spec_k, self.max_slots, self.page_size, self.num_pages,
                str(treedef),
                tuple((tuple(jnp.shape(l)), str(jnp.result_type(l)))
                      for l in leaves))

    def _get_exec(self, key: str, fn, args, donate=(1,)):
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
        # the executable's name in a device trace: jit_mxtpu_<key>
        fn.__name__ = fn.__qualname__ = f"mxtpu_{key}"
        t0 = time.perf_counter()
        ex = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        telemetry.record_compile(time.perf_counter() - t0, "decode")
        self._exec[key] = ex
        self.compiles += 1
        artifacts.save("decode_exec", asig, ex, meta={"exec_key": key})
        return ex

    def _call(self, key: str, fn, args, donate=(1,)):
        return self._get_exec(key, fn, args, donate)(*args)

    # -- per-slot recurrent state -------------------------------------------

    def _state(self):
        """The recurrent-state buffers of every layer, without K/V."""
        return tuple(layer[2:] for layer in self.cache.pool)

    def _reset_state(self, slot: int) -> None:
        """Zero ``slot``'s rows of every state buffer, in place."""
        state = self._call(
            "state_reset", lambda *a: _state_reset_core(*a),
            (self._state(), jnp.asarray(slot, jnp.int32)), donate=(0,))
        self.cache.pool = tuple(layer[:2] + st for layer, st
                                in zip(self.cache.pool, state))

    def _tables(self, cache) -> jnp.ndarray:
        return jnp.asarray(cache.tables, jnp.int32)

    def _slot_arg(self, slot: int) -> tuple:
        """The slot's index as a prefill argument, for a model whose
        state it addresses; nothing for one that is all pages."""
        return ((jnp.asarray(slot, jnp.int32),) if self.model.state_spec
                else ())

    def warmup(self, prefill_lengths: Sequence[int] = (1,)) -> List[str]:
        """Materialize every executable this engine will dispatch —
        decode (+ draft/verify under speculation) and one prefill per
        bucket covering ``prefill_lengths`` — WITHOUT running any of
        them.  Against a populated artifact store each one deserializes
        (``compiles`` stays 0); otherwise this pays the compiles ahead
        of traffic.  Also prefetches the kernel-autotune cache.
        Returns the exec keys materialized."""
        from ... import kernels
        n_kern = kernels.warm_cache()
        if n_kern:
            get_logger("mxnet_tpu.serving.decode").info(
                "warmup: %d tuned kernel config(s) preloaded", n_kern)
        mdl, keys = self.model, []
        s = self.max_slots
        tok = jnp.zeros((s,), jnp.int32)
        pos = jnp.zeros((s,), jnp.int32)
        act = jnp.zeros((s,), bool)
        self._get_exec(
            "decode", lambda *a: mdl.decode_core(*a),
            (mdl.params, self.cache.pool, tok, pos,
             self._tables(self.cache), act))
        keys.append("decode")
        if mdl.state_spec:
            self._get_exec("state_reset", lambda *a: _state_reset_core(*a),
                           (self._state(), jnp.asarray(0, jnp.int32)),
                           donate=(0,))
            keys.append("state_reset")
        if self.spec_enabled:
            dm, k = self.draft, self.spec_k
            self._get_exec(
                "draft",
                lambda p, kv, t, po, tb, a:
                _draft_core(dm, p, kv, t, po, tb, a, k),
                (dm.params, self.draft_cache.pool, tok, pos,
                 self._tables(self.draft_cache), act))
            window = jnp.zeros((s, k + 1), jnp.int32)
            self._get_exec(
                "verify",
                lambda p, kv, t, po, tb, a:
                _verify_core(mdl, p, kv, t, po, tb, a),
                (mdl.params, self.cache.pool, window, pos,
                 self._tables(self.cache), act))
            keys += ["draft", "verify"]
        for bucket in sorted({self.prefill_bucket(int(n))
                              for n in prefill_lengths}):
            padded = jnp.zeros((bucket,), jnp.int32)
            start = jnp.asarray(0, jnp.int32)
            clen = jnp.asarray(1, jnp.int32)
            row = jnp.asarray(self.cache.tables[0], jnp.int32)
            self._get_exec(
                f"prefill_b{bucket}", lambda *a: mdl.prefill_core(*a),
                (mdl.params, self.cache.pool, padded, start, clen, row)
                + self._slot_arg(0))
            keys.append(f"prefill_b{bucket}")
            if self.draft_cache is not None:
                dm = self.draft
                drow = jnp.asarray(self.draft_cache.tables[0], jnp.int32)
                self._get_exec(
                    f"draft_prefill_b{bucket}",
                    lambda p, kv, t, st, cl, tb:
                    _prefill_core(dm, p, kv, t, st, cl, tb),
                    (dm.params, self.draft_cache.pool, padded, start,
                     clen, drow))
                keys.append(f"draft_prefill_b{bucket}")
        return keys

    # -- device steps --------------------------------------------------------

    def decode_step(self, tokens, positions, active):
        """One non-speculative engine step over the full slot grid.
        Returns the next token per slot (host numpy)."""
        mdl = self.model
        self._count_live(positions, active)
        with tracing.span("decode.stage"):
            args = (mdl.params, self.cache.pool,
                    jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(positions, jnp.int32),
                    self._tables(self.cache),
                    jnp.asarray(active, bool))
        pool, nxt = self._call("decode", lambda *a: mdl.decode_core(*a),
                               args)
        self.cache.pool = pool
        with tracing.span("decode.sync"):
            return onp.asarray(nxt)

    def _count_live(self, positions, active):
        """Pages holding an active slot's context (its pending token's
        position included) over the pages of the whole table: host
        integers, no device read."""
        at = onp.asarray(positions)[onp.asarray(active, bool)]
        live = int((at // self.page_size + 1).sum())
        self.kv_live_share = live / (self.max_slots
                                     * self.cache.pages_per_slot)
        self._kv_live_sum += self.kv_live_share
        self._decode_steps += 1

    def spec_step(self, tokens, base_pos, active):
        """Draft k proposals then verify in one target dispatch.
        Returns (greedy (S, k+1), accepted (S,)) host numpy."""
        mdl, dm, k = self.model, self.draft, self.spec_k
        self._count_live(base_pos, active)
        with tracing.span("decode.stage"):
            tok = jnp.asarray(tokens, jnp.int32)
            pos = jnp.asarray(base_pos, jnp.int32)
            act = jnp.asarray(active, bool)
            dargs = (dm.params, self.draft_cache.pool, tok, pos,
                     self._tables(self.draft_cache), act)
        dpool, window = self._call(
            "draft",
            lambda p, kv, t, po, tb, a:
            _draft_core(dm, p, kv, t, po, tb, a, k), dargs)
        self.draft_cache.pool = dpool
        with tracing.span("decode.stage"):
            vargs = (mdl.params, self.cache.pool, window, pos,
                     self._tables(self.cache), act)
        pool, greedy, accepted = self._call(
            "verify",
            lambda p, kv, t, po, tb, a:
            _verify_core(mdl, p, kv, t, po, tb, a), vargs)
        self.cache.pool = pool
        with tracing.span("decode.sync"):
            return onp.asarray(greedy), onp.asarray(accepted)

    def prefill_chunk_step(self, slot: int, chunk, start: int) -> int:
        """Feed one prompt chunk for ``slot`` (padded into its pow2
        bucket); returns the greedy next token after the chunk."""
        mdl = self.model
        with tracing.span("decode.stage"):
            bucket = self.prefill_bucket(len(chunk))
            padded = onp.zeros((bucket,), onp.int32)
            padded[:len(chunk)] = chunk
            args = (mdl.params, self.cache.pool, jnp.asarray(padded),
                    jnp.asarray(start, jnp.int32),
                    jnp.asarray(len(chunk), jnp.int32),
                    jnp.asarray(self.cache.tables[slot], jnp.int32)) \
                + self._slot_arg(slot)
        pool, nxt = self._call(f"prefill_b{bucket}",
                               lambda *a: mdl.prefill_core(*a), args)
        self.cache.pool = pool
        if self.draft_cache is not None:
            dm = self.draft
            with tracing.span("decode.stage"):
                dargs = (dm.params, self.draft_cache.pool,
                         jnp.asarray(padded),
                         jnp.asarray(start, jnp.int32),
                         jnp.asarray(len(chunk), jnp.int32),
                         jnp.asarray(self.draft_cache.tables[slot],
                                     jnp.int32))
            dpool, _ = self._call(
                f"draft_prefill_b{bucket}",
                lambda p, kv, t, st, cl, tb:
                _prefill_core(dm, p, kv, t, st, cl, tb), dargs)
            self.draft_cache.pool = dpool
        with tracing.span("decode.sync"):
            return int(nxt)

    # -- slot page lifecycle -------------------------------------------------

    def acquire_slot(self, slot: int, tokens: int) -> None:
        self.cache.acquire(slot, tokens)
        if self.cache.state_spec:
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
                "state_bytes": self.cache.state_bytes,
                "state_slots_live": self.cache.state_slots_live(),
                "state_resets": self.cache.state_resets,
                "kv_live_share": (self._kv_live_sum / self._decode_steps
                                  if self._decode_steps else 0.0)}
