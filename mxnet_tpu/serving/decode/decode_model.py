"""The built-in decode model: a small multi-head causal transformer
(RMSNorm, rotary positions, no biases, GELU MLP, head tied to the
embedding) as a plain parameter pytree and ONE block function.

The cores — decode, prefill, verify, and the dense oracle — differ only
in the attention they hand the block, and that comes from the paged
format's own module (``paged_kv``): this file knows no page.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as onp

import jax
import jax.numpy as jnp
from jax import lax

from .engine import DecodePlaneModel
from .paged_kv import (chunk_attention, dense_attention, last_rows,
                       slot_attention, window_attention)

__all__ = ["DecodeModel", "rms_norm"]


def rms_norm(x, g, eps=1e-6):
    xf = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * g


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

    # -- the block -------------------------------------------------------------

    def _block(self, lp, x, kv, attend):
        """One layer over rows ``x (..., dim)``.  ``attend(q, k, v,
        *kv)`` takes the projected heads ``(..., heads, head_dim)``
        before rotation and the layer's K/V buffers (none for the dense
        oracle) and returns the attention output and the buffers'
        successors.  Returns ``(x, K/V)``."""
        lead = x.shape[:-1]
        heads = lead + (self.n_heads, self.head_dim)
        h1 = rms_norm(x, lp["ln1"])
        attn, kv = attend((h1 @ lp["wq"]).reshape(heads),
                          (h1 @ lp["wk"]).reshape(heads),
                          (h1 @ lp["wv"]).reshape(heads), *kv)
        x = x + attn.reshape(lead + (self.dim,)).astype(x.dtype) @ lp["wo"]
        h2 = rms_norm(x, lp["ln2"])
        return x + jax.nn.gelu(h2 @ lp["w1"]) @ lp["w2"], kv

    def _layers(self, params, pool, tokens, attend):
        """Embed ``tokens`` and run every layer over its buffers of
        ``pool``: ``(pool, final-normed rows)``.  Depth is a Python
        loop: a scan would need the layers' buffers stacked into one
        array, and a Mosaic call's operand is a whole buffer."""
        x = params["embed"][tokens]
        out = []
        for kv, lp in zip(pool, params["layers"]):
            x, kv = self._block(lp, x, kv, attend)
            out.append(kv)
        return tuple(out), rms_norm(x, params["lnf"])

    # -- the traced cores --------------------------------------------------------

    def decode_core(self, params, pool, tokens, positions, tables, active):
        pool, x = self._layers(params, pool, tokens, slot_attention(
            pool, positions, tables, active, rope_base=self.rope_base))
        logits = x @ params["embed"].T
        return pool, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def prefill_core(self, params, pool, tokens, start, chunk_len, tables):
        """A lane's token is meaningful only after a prompt's final
        chunk."""
        pool, x = self._layers(
            params, pool, tokens.reshape(-1), chunk_attention(
                pool, start, chunk_len, tables, tokens.shape[1],
                rope_base=self.rope_base))
        logits = last_rows(x, chunk_len) @ params["embed"].T
        return pool, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def verify_core(self, params, pool, tokens, base_pos, tables, active):
        pool, x = self._layers(params, pool, tokens, window_attention(
            pool, base_pos, tokens.shape[1], tables, active,
            rope_base=self.rope_base))
        logits = x @ params["embed"].T                    # (S, W, V)
        return pool, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # -- dense full-recompute oracle (tests pin the paged path to it) --------

    def _dense_logits_last(self, params, tokens):
        """Last-position logits of a dense causal forward over the whole
        sequence — the O(T^2) full-recompute oracle the paged path is
        pinned to."""
        _, x = self._layers(
            params, [()] * self.n_layers, tokens,
            dense_attention(tokens.shape[0], rope_base=self.rope_base))
        return x[-1] @ params["embed"].T

    @functools.cached_property
    def _dense_jit(self):
        # one program per sequence length (the parameters are an
        # argument, not constants)
        return jax.jit(self._dense_logits_last)

    def _ref_logits_last(self, tokens):
        return self._dense_jit(self.params, tokens)
