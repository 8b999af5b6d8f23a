"""LFM2 (``model_type`` ``lfm2_moe``) for the decode plane: a stack whose
layers are of two kinds, a gated short convolution in most and
grouped-query attention in the others (``layer_types``), each followed
by a SwiGLU MLP in the leading dense layers and by sigmoid-routed
experts, ALL of them held here (stacked, three arrays a layer), in every
layer after them.

Built from the keys of the family's ``config.json``.  ``x`` is a row of
the residual stream, ``N`` RMSNorm at ``norm_eps`` with a learned
weight, every projection bias-free (``conv_bias`` false):

    x = E[token]
    h = N_op(x)
    convolution:  [B | C | u] = h W_in            three of ``hidden``
                  g = B * u
                  v_t = sum_j w[j] * g_{t - (L-1) + j}     per channel,
                        j = 0..L-1, g zero before the sequence
                  x = x + (C * v) W_out
    attention:    q = N_q(h W_q), k = N_k(h W_k)  per head, over head_dim
                  v = h W_v;  q and k rotated (the halves of a head
                  against each other, ``rope_theta``); causal softmax at
                  head_dim**-0.5;  x = x + heads W_o
    h = N_ff(x)
    x = x + W_2 (silu(W_1 h) * W_3 h)             layers below num_dense_layers
    x = x + sum_i w_i E_i(h)                      the others
    logits = N_f(x) E^T                           the head tied to the embedding

The router: ``s = sigmoid(h W_g)`` in float32; the
``num_experts_per_tok`` largest of ``s + b`` selected (``b`` the
``expert_bias`` buffer, ``use_expert_bias``), ``w = s[sel] / (sum
s[sel] + 1e-6) * routed_scaling_factor`` (``norm_topk_prob``): selected
by the biased score, weighed by the unbiased one.  No shared expert, no
capacity, no dropped token (``parallel/moe.py``).

**The cache** is laid out by layer (``cache_layout``): an attention
layer keeps K and V pages and no state; a convolution layer keeps no
page and one state buffer, its tail, the last ``L - 1`` rows of ``g`` a
slot in float32.  One page table a slot serves every attention layer.

One block function serves the three paths (decode, a prefill chunk, the
dense oracle); what differs is handed to it: how attention reaches its
keys and values and how the convolution reaches its tail, both built by
``paged_kv``.  The residual stream is float32, the matrices and what is
multiplied with them the model's dtype, the router float32 on the
float32 normed row.  Weights are drawn on the device from the seed,
matrix by matrix.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ...parallel.moe import route_topk, stacked_experts
from .decode_model import Float32Stream
from .paged_kv import (chunk_attention, chunk_conv, dense_attention,
                       dense_conv, last_rows, slot_attention, slot_conv)

__all__ = ["LFM2"]

# the keys of config.json the arithmetic reads, and one of the
# benchmark's (the routed experts' W_2 divided, see the weights below)
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
         "num_dense_layers", "num_attention_heads", "num_key_value_heads",
         "intermediate_size", "moe_intermediate_size", "num_experts",
         "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
         "use_expert_bias", "norm_eps", "rope_theta", "conv_L_cache",
         "routed_down_divisor")
# what this model does not implement: a config that asks for it is refused
_FIXED = {"conv_bias": False, "tie_embedding": True}
_KINDS = ("conv", "full_attention")
# the normalisation of the selected scores (the family's code)
_TOPK_EPS = 1e-6


def _dot_wide(a, w):
    """``a @ w`` for float32 rows ``a (rows, k)`` and a matrix ``w`` of
    the model's dtype, float32 out, with each row taken as TWO numbers
    of that dtype: what rounding leaves of ``a`` and what it took away.
    One product over twice the rows, so the matrix is read once; its
    rows' error falls from 2**-9 to 2**-17 of their size.  (A float32
    matrix takes the rows as they are.)

    The convolution operator alone is fed so.  Its output is cubic in
    its input (``C * conv(B * u)``), every factor carries the input's
    rounding, and twelve such layers in sixteen made three quarters of
    the stack's distance from its float32 reference, a median token's
    logits off by 2.3% of their spread, where the same rows through
    this product leave PERF.md section 6, PR 35's figure; the two
    products are 33 MB of weights a layer and stay bound by their
    stream."""
    if w.dtype == jnp.float32:
        return jnp.dot(a, w, precision=lax.Precision.HIGHEST)
    # ``reduce_precision`` and not a cast there and back: the TPU
    # compiler takes such a pair for excess precision it may keep, and
    # what rounding took away would read exactly 0
    bits = jnp.finfo(w.dtype)
    hi = lax.reduce_precision(a, exponent_bits=bits.nexp,
                              mantissa_bits=bits.nmant)
    out = jnp.dot(jnp.concatenate([hi, a - hi], axis=0).astype(w.dtype), w,
                  preferred_element_type=jnp.float32)
    return out[:a.shape[0]] + out[a.shape[0]:]


class LFM2(Float32Stream):
    """``LFM2(config)`` with ``config`` the dict of an ``lfm2_moe``
    ``config.json``.  ``abstract=True`` gives ``params`` as shapes only
    (for compiling without the weights)."""

    def __init__(self, config: Dict[str, Any], *, seed: int = 0,
                 dtype="bfloat16", abstract: bool = False):
        missing = [k for k in _KEYS if k not in config]
        if missing:
            raise ValueError(f"lfm2 config lacks {missing}")
        for key, want in _FIXED.items():
            if config.get(key, want) != want:
                raise ValueError(f"lfm2 config has {key}={config[key]!r}; "
                                 f"only {want!r} is implemented")
        c = self.config = {k: config[k] for k in _KEYS}
        self.vocab_size = int(c["vocab_size"])
        self.dim = int(c["hidden_size"])
        self.n_layers = int(c["num_hidden_layers"])
        self.kinds = tuple(c["layer_types"])
        self.n_dense = int(c["num_dense_layers"])
        self.n_heads = int(c["num_attention_heads"])
        self.kv_heads = int(c["num_key_value_heads"])
        self.head_dim = self.dim // self.n_heads
        self.experts = int(c["num_experts"])
        self.top_k = int(c["num_experts_per_tok"])
        self.eps = float(c["norm_eps"])
        self.rope_base = float(c["rope_theta"])
        self.taps = int(c["conv_L_cache"])
        if len(self.kinds) != self.n_layers or set(self.kinds) - set(_KINDS):
            raise ValueError(f"layer_types must name {self.n_layers} layers "
                             f"of {_KINDS}")
        if (self.dim % self.n_heads or self.n_heads % self.kv_heads
                or self.head_dim % 2):
            raise ValueError("hidden_size must be heads x head_dim, the "
                             "query heads a multiple of the KV heads, and "
                             "head_dim even for rope")
        self.dtype = jnp.dtype(dtype)
        key = jax.random.PRNGKey(seed)
        if abstract:
            self.params = jax.eval_shape(self._init_all, key)
        else:
            self.params = self._init_all(key, jit=jax.jit)

    @property
    def cache_layout(self) -> tuple:
        """By layer: K and V pages and no state for attention, no page
        and the convolution's tail for the others."""
        paged = ((self.kv_heads * self.head_dim,) * 2, ())
        tail = ((), (("conv", (self.taps - 1, self.dim), "float32"),))
        return tuple(tail if kind == "conv" else paged
                     for kind in self.kinds)

    # -- weights ---------------------------------------------------------------
    # Not in config.json (the benchmark's configuration lists them as
    # assumed): every matrix normal at 1/sqrt(fan-in), norm weights 1,
    # the convolution's taps uniform at 1/sqrt(taps), every branch at
    # full strength; the routed experts' W_2 further divided by
    # ``routed_down_divisor`` (1: the plain scale).  The selection bias
    # normal at 0.05, so that selection and weighting differ at some
    # rows.  Embedding rows normal at 1/sqrt(hidden): the head is the
    # embedding, so a row of unit variance would give a token's own
    # logit |E[t]|^2 = hidden over the residual's size, some eight
    # deviations of the others', and random weights would repeat their
    # input whatever the layers compute; at this scale it is a fifth of
    # one deviation, the logits are of order one, and the first norm
    # brings the row to full strength for layer 0.

    def _mat(self, key, fan_in, fan_out, div=1.0, stack=None):
        """One matrix ``(fan_in, fan_out)``, or ``stack`` of them."""
        lead = () if stack is None else (stack,)
        w = jax.random.normal(key, lead + (fan_in, fan_out), jnp.float32)
        return (w * (fan_in ** -0.5 / div)).astype(self.dtype)

    def _init_layer(self, key, index: int, mat, small):
        c = self.config
        d, hd = self.dim, self.head_dim
        keys = iter(jax.random.split(key, 12))
        ones = functools.partial(jnp.ones, dtype=self.dtype)
        lp = {"ln1": ones((d,)), "ln2": ones((d,))}
        if self.kinds[index] == "conv":
            lp.update(w_in=mat(next(keys), d, 3 * d),
                      conv_w=small(next(keys), "taps"),
                      w_out=mat(next(keys), d, d))
        else:
            lp.update(wq=mat(next(keys), d, self.n_heads * hd),
                      wk=mat(next(keys), d, self.kv_heads * hd),
                      wv=mat(next(keys), d, self.kv_heads * hd),
                      q_norm=ones((hd,)), k_norm=ones((hd,)),
                      wo=mat(next(keys), self.n_heads * hd, d))
        if index < self.n_dense:
            f = int(c["intermediate_size"])
            lp.update(w1=mat(next(keys), d, f), w3=mat(next(keys), d, f),
                      w2=mat(next(keys), f, d))
            return lp
        f = int(c["moe_intermediate_size"])
        lp["w_router"] = mat(next(keys), d, self.experts)
        if c["use_expert_bias"]:
            lp["expert_bias"] = small(next(keys), "bias")
        e = self.experts
        lp.update(experts_w1=mat(next(keys), d, f, 1.0, e),
                  experts_w3=mat(next(keys), d, f, 1.0, e),
                  experts_w2=mat(next(keys), f, d,
                                 float(c["routed_down_divisor"]), e))
        return lp

    def _small(self, key, what: str):
        """The float32 pieces: the convolution's taps ``(taps,
        hidden)`` and the router's selection bias ``(experts,)``."""
        if what == "taps":
            bound = self.taps ** -0.5
            return jax.random.uniform(key, (self.taps, self.dim),
                                      jnp.float32, -bound, bound)
        return 0.05 * jax.random.normal(key, (self.experts,), jnp.float32)

    def _init_all(self, key, jit=lambda f, **kw: f):
        # one program a matrix shape, so that no two matrices'
        # temporaries are alive together
        mat = jit(self._mat, static_argnums=(1, 2, 3, 4))
        small = jit(self._small, static_argnums=(1,))
        embed = jit(lambda k: (jax.random.normal(
            k, (self.vocab_size, self.dim), jnp.float32)
            * self.dim ** -0.5).astype(self.dtype))(
                jax.random.fold_in(key, 0))
        # the tied head, held a second time as the columns the head's
        # product reads (the gather wants rows): no transpose in a step
        return {"embed": embed, "head": jit(jnp.transpose)(embed),
                "lnf": jnp.ones((self.dim,), self.dtype),
                "layers": [self._init_layer(jax.random.fold_in(key, i + 1),
                                            i, mat, small)
                           for i in range(self.n_layers)]}

    # -- the block ---------------------------------------------------------------

    def _block(self, lp, x, cache, attend, conv, valid=None):
        """One layer over rows ``x (rows, dim)``, float32; the layer's
        kind is told by what ``lp`` holds.  ``attend(q, k, v, *cache)``
        as ``paged_kv`` builds it (heads before rotation and the
        layer's K and V buffers in); ``conv(g, w, *cache)`` takes the
        gated rows ``(rows, dim)`` in float32, the taps and the layer's
        tail buffer and returns the convolved rows and the tail's
        successor.  Returns ``(x, the layer's cache buffers, the expert
        layer's counters or None)``; the counters count the rows of
        ``valid``."""
        rows, dt, f32 = x.shape[0], self.dtype, jnp.float32
        hn = self._norm(x, lp["ln1"])
        h = hn.astype(dt)
        if "wq" in lp:
            heads = (rows, -1, self.head_dim)
            q = self._norm((h @ lp["wq"]).reshape(heads), lp["q_norm"])
            k = self._norm((h @ lp["wk"]).reshape(heads), lp["k_norm"])
            v = (h @ lp["wv"]).reshape(heads)
            attn, cache = attend(q.astype(dt), k.astype(dt), v, *cache)
            x = x + jnp.dot(attn.reshape(rows, -1).astype(dt), lp["wo"],
                            preferred_element_type=f32)
        else:
            b, c, u = jnp.split(_dot_wide(hn, lp["w_in"]), 3, axis=-1)
            y, cache = conv(b * u, lp["conv_w"], *cache)
            x = x + _dot_wide(c * y, lp["w_out"])
        h2 = self._norm(x, lp["ln2"])
        hb = h2.astype(dt)
        if "w_router" not in lp:
            a = jax.nn.silu(hb @ lp["w1"]) * (hb @ lp["w3"])
            return x + jnp.dot(a, lp["w2"],
                               preferred_element_type=f32), cache, None
        index, weight = route_topk(
            self._scores(h2, lp["w_router"]), self.top_k,
            normalize=bool(self.config["norm_topk_prob"]),
            scale=float(self.config["routed_scaling_factor"]),
            bias=lp.get("expert_bias"), eps=_TOPK_EPS)
        routed, counters = stacked_experts(
            hb, index, weight, lp["experts_w1"], lp["experts_w3"],
            lp["experts_w2"], valid)
        return x + routed, cache, counters

    def _layers(self, params, pool, tokens, attend, conv, valid=None):
        """Embed ``tokens`` and run every layer over its buffers of
        ``pool``: ``(pool, rows before the final norm, the expert
        layers' counters)``."""
        x = params["embed"][tokens].astype(jnp.float32)
        out, seen = [], []
        for cache, lp in zip(pool, params["layers"]):
            x, cache, counters = self._block(lp, x, cache, attend, conv,
                                             valid)
            out.append(cache)
            if counters is not None:
                seen.append(counters)
        return tuple(out), x, seen

    def _paged(self, pool):
        """The layers of ``pool`` that keep pages: what ``paged_kv``
        reads a pool's geometry from."""
        return tuple(layer for layer, kind in zip(pool, self.kinds)
                     if kind != "conv")

    def _counters(self, seen) -> dict:
        """What a decode step hands back beside its tokens, means over
        the expert layers: the rows (decoding slots) an expert got, mean
        and largest, the share of experts that got none, and the
        fullest expert's rows over the mean."""
        if not seen:
            return {}

        def mean(per_layer):
            return jnp.stack(per_layer).mean()

        return {"moe_expert_rows_mean": mean([s["rows_mean"] for s in seen]),
                "moe_expert_rows_max": mean([s["rows_max"] for s in seen]),
                "moe_experts_idle_share":
                    mean([s["idle"] for s in seen]) / self.experts,
                "moe_load_imbalance": mean(
                    [s["rows_max"] / jnp.maximum(s["rows_mean"], 1e-9)
                     for s in seen])}

    # -- decode: one token a slot ------------------------------------------------

    def decode_logits(self, params, pool, tokens, positions, tables, active):
        """The decode step up to its logits ``(slots, vocab)``."""
        attend = slot_attention(self._paged(pool), positions, tables, active,
                                rope_base=self.rope_base)

        def conv(g, w, tail):
            y, tail = slot_conv(tail, g, w, active)
            return y, (tail,)

        pool, x, seen = self._layers(params, pool, tokens, attend, conv,
                                     active)
        return pool, self._logits(params, x), self._counters(seen)

    # -- prefill: lanes, each one chunk of one slot -------------------------------

    def prefill_logits(self, params, pool, tokens, start, chunk_len, tables,
                       slot):
        """A dispatch of chunks up to the logits ``(lanes, vocab)``
        after each lane's last valid token."""
        attend = chunk_attention(self._paged(pool), start, chunk_len, tables,
                                 tokens.shape[1], rope_base=self.rope_base)

        def conv(g, w, tail):
            y, tail = chunk_conv(tail, g, w, slot, chunk_len)
            return y, (tail,)

        pool, x, _ = self._layers(params, pool, tokens.reshape(-1), attend,
                                  conv)
        return pool, self._logits(params, last_rows(x, chunk_len))

    # -- dense: the whole sequence, no cache (the in-program oracle) -------------

    def dense_logits(self, params, tokens):
        """Logits ``(T, vocab)`` of the whole of ``tokens``: causal
        attention over the sequence itself, the convolution from a zero
        tail."""
        attend = dense_attention(tokens.shape[0], rope_base=self.rope_base)
        _, x, _ = self._layers(
            params, [()] * self.n_layers, tokens, attend,
            lambda g, w: (dense_conv(g, w), ()))
        return self._logits(params, x)
