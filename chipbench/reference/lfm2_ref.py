"""Plain forward of LFM2's mixture-of-experts models (``model_type``
``lfm2_moe``): float32 ``jax.numpy``, whole sequence, no cache, no
kernels, the convolution as shifted products over the whole sequence,
the experts in a loop.

Written from the published description (``config.json`` of
LiquidAI/LFM2-8B-A1B and the family's public equations,
``Lfm2MoeForCausalLM``) and not imported from the program.  ``x`` is a
row of the residual stream, ``N`` RMSNorm at ``norm_eps`` with float32
statistics and a learned weight, every projection bias-free:

- ``x = E[token]``; logits ``= N_f(x) @ W_head`` (the family calls
  ``N_f`` ``embedding_norm``; the head is tied: ``W_head = E^T``, which
  the parameters hold as a second array);
- a layer: ``x = x + Op(N_op(x))``, then ``x = x + FF(N_ff(x))``; ``Op``
  is the convolution or the attention by ``layer_types``, told here by
  what the layer's parameters hold;
- gated short convolution (``conv_L_cache`` = L taps): ``[B | C | u] =
  h W_in``, three of ``hidden_size``; ``g = B * u``; per channel
  ``v_t = sum_{j<L} w[j] g_{t-(L-1)+j}``, ``g`` zero before the
  sequence; ``Op = (C * v) W_out``.  No activation, no bias;
- attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads of ``hidden_size /
  num_attention_heads``; ``q`` and ``k`` each through an RMSNorm over
  the head with its own weight, THEN rotated (``rope_theta``, the whole
  head, its two halves against each other); causal softmax at
  ``head_dim**-0.5``; heads through ``W_o``;
- ``FF`` below ``num_dense_layers``: ``W_2 (silu(W_1 h) * W_3 h)`` of
  ``intermediate_size``;
- ``FF`` of the others: ``s = sigmoid(h W_g)`` over ``num_experts``;
  the ``num_experts_per_tok`` largest of ``s + b`` selected (``b`` the
  ``expert_bias`` buffer, ``use_expert_bias``); ``w = s[sel] / (sum
  s[sel] + 1e-6) * routed_scaling_factor`` (``norm_topk_prob``): the
  UNBIASED scores weigh; ``FF = sum_i w_i E_i(h)``, every ``E`` an MLP
  of ``moe_intermediate_size``.  No shared expert, no capacity.

Departures from the family's public modelling code as the author knows
it: (1) that code gathers each expert's rows; here every expert is
evaluated for every row and combined by a ``(rows, experts)`` matrix of
routing weights that is zero where a row was not routed: the same sum;
(2) that code convolves with a grouped ``conv1d`` over a padded
sequence; here the ``L`` shifted products are written out: the same
sum; (3) ``W_in``'s columns are taken as ``[B | C | u]`` in that order,
which for weights drawn at random is the same distribution as any
other; (4) depth is the configuration's cut.

``params`` is the program's pytree (``embed``, ``head``, ``lnf``,
``layers[i]`` with ``ln1 ln2``, either ``w_in conv_w w_out`` or ``wq
wk wv q_norm k_norm wo``, and either ``w1 w3 w2`` or ``w_router
expert_bias experts_w1 experts_w3 experts_w2``; matrices are ``(in,
out)``, the experts' stacked ``(experts, in, out)``, the taps ``(L,
hidden)`` with tap ``L-1`` on the row itself).  ``forward`` is
``embed``, then ``layer`` for each layer, then ``head``; a caller short
of memory calls the pieces.  Run under
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

TOPK_EPS = 1e-6     # the normalisation of the selected scores


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _rotate(x, base):
    """``x (T, H, D)`` at positions 0..T-1: the two halves of the lanes
    rotate against each other (an axis pair, not slices: see
    ``decodemodel_ref._rope``)."""
    t_, _, d = x.shape
    half = d // 2
    inv = float(base) ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t_, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xr = x.reshape(x.shape[:-1] + (2, half))
    x1, x2 = xr[..., 0, :], xr[..., 1, :]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-2).reshape(x.shape)


def embed(params, tokens, cfg):
    """``tokens (T,)`` -> ``x (T, hidden)``; the rows are taken before
    they are widened."""
    return _f32(params["embed"][tokens])


def _convolution(lp, h, cfg):
    t_, taps = h.shape[0], cfg["conv_L_cache"]
    b, c, u = jnp.split(h @ _f32(lp["w_in"]), 3, axis=-1)
    g = b * u
    padded = jnp.concatenate([jnp.zeros((taps - 1, g.shape[1])), g], axis=0)
    w = _f32(lp["conv_w"])
    v = sum(w[j] * padded[j:j + t_] for j in range(taps))
    return (c * v) @ _f32(lp["w_out"])


def _attention(lp, h, cfg):
    t_ = h.shape[0]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    eps = cfg["norm_eps"]
    q = _rms((h @ _f32(lp["wq"])).reshape(t_, nh, hd), lp["q_norm"], eps)
    k = _rms((h @ _f32(lp["wk"])).reshape(t_, kvh, hd), lp["k_norm"], eps)
    v = (h @ _f32(lp["wv"])).reshape(t_, kvh, hd)
    q = _rotate(q, cfg["rope_theta"]).reshape(t_, kvh, nh // kvh, hd)
    k = _rotate(k, cfg["rope_theta"])
    s = jnp.einsum("qgrd,kgd->grqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t_, t_), bool)), s, -jnp.inf)
    o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(t_, nh * hd) @ _f32(lp["wo"])


def _mlp(h, w1, w3, w2):
    return (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)


def route(scores, bias, cfg):
    """``scores (T, experts)`` past their sigmoid and the selection
    bias ``(experts,)`` (None: none) -> the ``(T, experts)`` matrix of
    routing weights, zero where not selected: selected by ``scores +
    bias``, weighed by ``scores``."""
    experts, k = scores.shape[1], cfg["num_experts_per_tok"]
    pick = scores if bias is None else scores + _f32(bias)
    kth = jnp.sort(pick, axis=-1)[:, experts - k][:, None]
    w = jnp.where(pick >= kth, scores, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + TOPK_EPS)
    return w * cfg["routed_scaling_factor"]


def _experts(lp, h, cfg):
    w = route(jax.nn.sigmoid(h @ _f32(lp["w_router"])),
              lp.get("expert_bias"), cfg)
    y = jnp.zeros_like(h)
    for e in range(cfg["num_experts"]):         # one by one
        y = y + w[:, e, None] * _mlp(h, lp["experts_w1"][e],
                                     lp["experts_w3"][e],
                                     lp["experts_w2"][e])
    return y


def layer(lp, x, cfg):
    """One layer over the whole sequence ``x (T, hidden)``: its
    operator and its feed-forward by what ``lp`` holds."""
    eps = cfg["norm_eps"]
    h = _rms(x, lp["ln1"], eps)
    x = x + (_attention(lp, h, cfg) if "wq" in lp
             else _convolution(lp, h, cfg))
    h = _rms(x, lp["ln2"], eps)
    if "w_router" in lp:
        return x + _experts(lp, h, cfg)
    return x + _mlp(h, lp["w1"], lp["w3"], lp["w2"])


def head(lnf, w_head, x, cfg):
    """Logits of rows ``x (R, hidden)`` over the columns of ``w_head
    (hidden, V)``: a block of the vocabulary's columns gives that
    block's logits."""
    return _rms(x, lnf, cfg["norm_eps"]) @ _f32(w_head)


def forward(params, tokens, cfg):
    """``tokens (T,)`` int32 -> logits ``(T, vocab)`` float32."""
    x = embed(params, tokens, cfg)
    for lp in params["layers"]:
        x = layer(lp, x, cfg)
    return head(params["lnf"], params["head"], x, cfg)
