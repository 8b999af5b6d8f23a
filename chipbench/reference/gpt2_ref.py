"""Plain GPT-2 forward: float32 ``jax.numpy``, no kernels, no cache.

The published architecture (Radford et al. 2019, ``modeling_gpt2``):
token + learned position embeddings, ``n_layer`` pre-LayerNorm blocks
(fused biased QKV, causal softmax attention scaled by 1/sqrt(head),
biased output projection; biased 4x MLP), a final LayerNorm and a head
tied to the token embedding.

One departure, taken because the program takes it and listed in each
GPT-2 configuration's ``departures``: the MLP's activation is the exact
(erf) GELU of ``nn.GELU()``, where GPT-2 uses the tanh approximation
``gelu_new``.

``params`` maps the names of ``TransformerLM.collect_params()`` to
arrays; weights are ``(out, in)`` as Gluon's ``Dense`` stores them.
Run under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul is otherwise computed in bfloat16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _layer_norm(x, gamma, beta):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * gamma + beta


def _dense(x, w, b):
    return x @ w.T + b


def forward(params, tokens, *, n_head: int, n_layer: int):
    """``tokens (B, S)`` int32 -> logits ``(B, S, vocab)`` float32."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    b_, s_ = tokens.shape
    wte = p["embed.weight"]
    d = wte.shape[1]
    hd = d // n_head
    x = wte[tokens] + p["pos_embed"][:s_][None]
    causal = jnp.tril(jnp.ones((s_, s_), bool))
    for i in range(n_layer):
        pre = f"blocks.{i}."
        h = _layer_norm(x, p[pre + "ln1.gamma"], p[pre + "ln1.beta"])
        qkv = _dense(h, p[pre + "attn.qkv.weight"], p[pre + "attn.qkv.bias"])
        q, k, v = (t.reshape(b_, s_, n_head, hd)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(hd))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                         v).reshape(b_, s_, d)
        x = x + _dense(att, p[pre + "attn.out_proj.weight"],
                       p[pre + "attn.out_proj.bias"])
        h = _layer_norm(x, p[pre + "ln2.gamma"], p[pre + "ln2.beta"])
        h = _dense(h, p[pre + "ffn1.weight"], p[pre + "ffn1.bias"])
        h = jax.nn.gelu(h, approximate=False)      # departure: see above
        x = x + _dense(h, p[pre + "ffn2.weight"], p[pre + "ffn2.bias"])
    x = _layer_norm(x, p["ln_f.gamma"], p["ln_f.beta"])
    return x @ wte.T
