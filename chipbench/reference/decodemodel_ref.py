"""Plain forward of the decode plane's model: float32 ``jax.numpy``,
whole sequence, no cache, no kernels.

``serving.decode.DecodeModel`` is not a published model.  Its equations,
written out here and not imported: token embedding (no position
embedding); per layer RMSNorm (eps 1e-6, float32 statistics), bias-free
Q, K, V projections, rotary position embedding on Q and K in the split-
half form (base 10000: the first and second halves of a head rotate
against each other), causal softmax attention scaled by 1/sqrt(head),
bias-free output projection; RMSNorm, bias-free MLP with the tanh GELU;
a final RMSNorm and a head tied to the embedding.

``params`` is the model's own pytree (``embed``, ``layers[i]`` with
``ln1 wq wk wv wo ln2 w1 w2``, ``lnf``); weights are ``(in, out)``.
Run under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6


def _rms(x, g):
    return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True)
                             + RMS_EPS) * g


def _rope(x, pos, base):
    """``x (T, H, D)``, ``pos (T,)``: rotate the two halves of each head.
    The halves are taken as a ``(2, half)`` axis pair and stacked: the
    slice-and-concatenate form of the same arithmetic aborts the TPU
    compiler of this installation at float32 ``(T, H, 64)`` (PERF.md,
    PR 23 finding 3)."""
    half = x.shape[-1] // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]       # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xr = x.reshape(x.shape[:-1] + (2, half))
    x1, x2 = xr[..., 0, :], xr[..., 1, :]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-2).reshape(x.shape)


def forward(params, tokens, *, n_heads: int, rope_base: float = 10000.0):
    """``tokens (T,)`` int32 -> logits ``(T, vocab)`` float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)      # noqa: E731
    embed = f32(params["embed"])
    t_, dim = tokens.shape[0], embed.shape[1]
    hd = dim // n_heads
    pos = jnp.arange(t_, dtype=jnp.int32)
    causal = jnp.tril(jnp.ones((t_, t_), bool))
    x = embed[tokens]
    for lp in params["layers"]:
        h = _rms(x, f32(lp["ln1"]))
        q = _rope((h @ f32(lp["wq"])).reshape(t_, n_heads, hd), pos,
                  rope_base)
        k = _rope((h @ f32(lp["wk"])).reshape(t_, n_heads, hd), pos,
                  rope_base)
        v = (h @ f32(lp["wv"])).reshape(t_, n_heads, hd)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        x = x + o.reshape(t_, dim) @ f32(lp["wo"])
        h = _rms(x, f32(lp["ln2"]))
        x = x + jax.nn.gelu(h @ f32(lp["w1"]), approximate=True) \
            @ f32(lp["w2"])
    x = _rms(x, f32(params["lnf"]))
    return x @ embed.T
