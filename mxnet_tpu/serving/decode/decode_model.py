"""The built-in decode model: a small multi-head causal transformer
(RMSNorm, rotary positions, no biases, GELU MLP, head tied to the
embedding) as a plain parameter pytree and ONE block function.

Its logits (decode, prefill, verify, and the dense oracle) differ only
in the attention they hand the block, and that comes from the paged
format's own module (``paged_kv``): this file knows no page.

Beside it, what the decode plane's models share of their arithmetic:
``rms_norm``, and ``Float32Stream`` for a model whose residual stream is
float32.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as onp

import jax
import jax.numpy as jnp
from jax import lax

from .engine import DecodePlaneModel
from .paged_kv import (chunk_attention, dense_attention, last_rows,
                       slot_attention, window_attention)

__all__ = ["DecodeModel", "Float32Stream", "rms_norm"]


def rms_norm(x, g, eps=1e-6):
    xf = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * g


class Float32Stream(DecodePlaneModel):
    """A model whose residual stream is float32 (``AXK1``, ``LFM2``):
    its norms keep float32 statistics and rows, its head multiplies in
    the model's dtype into float32, its router scores float32 rows."""

    def _norm(self, x, g):
        """RMSNorm at ``self.eps`` with float32 statistics; the row
        stays float32."""
        xf = x.astype(jnp.float32)
        return xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                              + self.eps) * g.astype(jnp.float32)

    def _scores(self, h, w_router):
        """The router's scores of float32 rows ``h``: float32 all
        through, the product at the highest precision."""
        return jax.nn.sigmoid(jnp.dot(h, w_router.astype(jnp.float32),
                                      precision=lax.Precision.HIGHEST))

    def _logits(self, params, x):
        return jnp.dot(self._norm(x, params["lnf"]).astype(self.dtype),
                       params["head"], preferred_element_type=jnp.float32)


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
        self.dtype = jnp.dtype(dtype)
        self.config = {"vocab_size": self.vocab_size, "dim": self.dim,
                       "n_heads": self.n_heads, "n_layers": self.n_layers,
                       "mlp_ratio": int(mlp_ratio),
                       "rope_base": self.rope_base}
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

    # -- the logits -----------------------------------------------------------

    def decode_logits(self, params, pool, tokens, positions, tables, active):
        pool, x = self._layers(params, pool, tokens, slot_attention(
            pool, positions, tables, active, rope_base=self.rope_base))
        return pool, x @ params["embed"].T

    def prefill_logits(self, params, pool, tokens, start, chunk_len, tables,
                       slot):
        """No state: ``slot`` is not read."""
        pool, x = self._layers(
            params, pool, tokens.reshape(-1), chunk_attention(
                pool, start, chunk_len, tables, tokens.shape[1],
                rope_base=self.rope_base))
        return pool, last_rows(x, chunk_len) @ params["embed"].T

    def verify_logits(self, params, pool, tokens, base_pos, tables, active):
        pool, x = self._layers(params, pool, tokens, window_attention(
            pool, base_pos, tokens.shape[1], tables, active,
            rope_base=self.rope_base))
        return pool, x @ params["embed"].T                # (S, W, V)

    def dense_logits(self, params, tokens):
        """Logits ``(T, vocab)`` of a dense causal forward over the whole
        sequence: the O(T^2) full-recompute oracle the paged path is
        pinned to."""
        _, x = self._layers(
            params, [()] * self.n_layers, tokens,
            dense_attention(tokens.shape[0], rope_base=self.rope_base))
        return x @ params["embed"].T
