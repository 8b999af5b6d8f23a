"""Plain forward of Falcon-H1 (``model_type`` ``falcon_h1``): float32
``jax.numpy``, whole sequence, no cache, no chunking, no kernels.

Written from the family's published description (the parallel hybrid
block of the Falcon-H1 models, ``config.json`` of
tiiuae/Falcon-H1-34B-Instruct) and not imported from the program.  With
``x`` the residual stream, RMSNorm at ``rms_norm_eps`` with float32
statistics, and every projection bias-free:

- ``x = E[token] * embedding_multiplier``; logits ``= (RMSNorm_f(x) @
  W_head) * lm_head_multiplier``, the head untied;
- ``h = RMSNorm_1(x)``; both mixers read the same ``h``: ``x = x +
  ssm_out_multiplier * Mamba(h) + attention_out_multiplier * Attn(h *
  attention_in_multiplier)``; then ``x = x + MLP(RMSNorm_2(x))``;
- ``Attn``: q over ``num_attention_heads``, k and v over
  ``num_key_value_heads`` heads of ``head_dim``; ``k = k *
  key_multiplier``; rotary embedding on q and k in the split-half form
  at ``rope_theta``; causal softmax at ``1/sqrt(head_dim)``; KV head
  ``g`` serves the query heads ``g*R .. g*R+R-1``; output projection;
- ``Mamba`` (Mamba-2): ``u = (h * ssm_in_multiplier) @ W_in`` split as
  ``[z | xBC | dt]`` with ``xBC = [x | B | C]``, the five
  ``ssm_multipliers`` scaling ``z, x, B, C, dt`` in that order; ``xBC =
  silu(causal depthwise conv1d(xBC, mamba_d_conv) + bias)``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head; per head
  ``p`` of group ``p // (heads / groups)``: ``H_t = exp(dt_t A) H_{t-1}
  + dt_t x_t (outer) B_t``, ``y_t = H_t C_t + D x_t``, one token at a
  time under ``lax.scan`` (this shares nothing with the program's
  chunked scan or its kernel); gate then norm
  (``mamba_norm_before_gate`` false): ``y = GroupRMSNorm(y * silu(z))``
  over ``mamba_n_groups`` groups with a learned weight; ``y @ W_out``;
- ``MLP``: ``(up(h) * silu(gate(h) * mlp_multipliers[0])) @ W_down *
  mlp_multipliers[1]``.

Departures from the family's public modelling code as the author knows
it: none in the arithmetic.  That code clamps ``dt`` to
``time_step_limit`` (0, inf), which changes nothing; it computes the
convolution and the scan through fused kernels where this file writes
them out.

``params`` is the program's pytree (``embed``, ``head``, ``lnf``,
``layers[i]`` with ``ln1 wq wk wv wo w_in conv_w conv_b a_log d dt_bias
norm w_out ln2 w_gate w_up w_down``; matrices are ``(in, out)``,
``conv_w`` is ``(mamba_d_conv, conv width)`` with its last row on the
current token).  ``forward`` is ``embed``, then ``layer`` for each
layer, then ``head``: a caller short of memory calls the pieces, one
layer at a time and the head by blocks of the vocabulary.  Run under
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, base):
    """``x (T, H, D)``, ``pos (T,)``: the two halves of a head rotate
    against each other.  The halves are an axis pair, not slices: see
    ``decodemodel_ref._rope``."""
    half = x.shape[-1] // 2
    inv = float(base) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xr = x.reshape(x.shape[:-1] + (2, half))
    x1, x2 = xr[..., 0, :], xr[..., 1, :]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-2).reshape(x.shape)


def embed(params, tokens, cfg):
    """``tokens (T,)`` -> ``x (T, hidden)``.  The rows are taken before
    they are widened: the whole table in float32 is 5.3 GB at the
    published size."""
    return _f32(params["embed"][tokens]) * cfg["embedding_multiplier"]


def _attention(lp, h, cfg):
    t_ = h.shape[0]
    nh, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    pos = jnp.arange(t_, dtype=jnp.int32)
    h = h * cfg["attention_in_multiplier"]
    q = (h @ _f32(lp["wq"])).reshape(t_, nh, hd)
    k = ((h @ _f32(lp["wk"])) * cfg["key_multiplier"]).reshape(t_, kvh, hd)
    v = (h @ _f32(lp["wv"])).reshape(t_, kvh, hd)
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    k, v = (jnp.repeat(a, nh // kvh, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((t_, t_), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(t_, nh * hd) @ _f32(lp["wo"])


def _mamba(lp, h, cfg):
    t_ = h.shape[0]
    d_ssm, heads, p = (cfg["mamba_d_ssm"], cfg["mamba_n_heads"],
                       cfg["mamba_d_head"])
    n, groups, d_conv = (cfg["mamba_d_state"], cfg["mamba_n_groups"],
                         cfg["mamba_d_conv"])
    gn = groups * n
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    u = (h * cfg["ssm_in_multiplier"]) @ _f32(lp["w_in"])
    z = u[:, :d_ssm] * mz
    xbc = jnp.concatenate([u[:, d_ssm:2 * d_ssm] * mx,
                           u[:, 2 * d_ssm:2 * d_ssm + gn] * mb,
                           u[:, 2 * d_ssm + gn:2 * d_ssm + 2 * gn] * mc],
                          axis=1)
    dt = u[:, 2 * d_ssm + 2 * gn:] * mdt
    # causal depthwise convolution: token t sees t-3 .. t
    padded = jnp.concatenate(
        [jnp.zeros((d_conv - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
    conv_w = _f32(lp["conv_w"])
    conv = _f32(lp["conv_b"])[None]
    for j in range(d_conv):
        conv = conv + padded[j:j + t_] * conv_w[j][None]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_ssm].reshape(t_, heads, p)
    b = jnp.repeat(xbc[:, d_ssm:d_ssm + gn].reshape(t_, groups, n),
                   heads // groups, axis=1)                 # (T, heads, N)
    c = jnp.repeat(xbc[:, d_ssm + gn:].reshape(t_, groups, n),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"])[None])    # (T, heads)
    a = -jnp.exp(_f32(lp["a_log"]))
    d = _f32(lp["d"])

    def step(state, row):
        x_t, b_t, c_t, dt_t = row
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                        (xs, b, c, dt))
    y = y.reshape(t_, d_ssm) * jax.nn.silu(z)
    yg = y.reshape(t_, groups, d_ssm // groups)
    yg = yg * jax.lax.rsqrt((yg * yg).mean(axis=-1, keepdims=True)
                            + cfg["rms_norm_eps"])
    return (yg.reshape(t_, d_ssm) * _f32(lp["norm"])) @ _f32(lp["w_out"])


def layer(lp, x, cfg):
    """One layer over the whole sequence ``x (T, hidden)``."""
    eps = cfg["rms_norm_eps"]
    h = _rms(x, _f32(lp["ln1"]), eps)
    x = (x + cfg["ssm_out_multiplier"] * _mamba(lp, h, cfg)
         + cfg["attention_out_multiplier"] * _attention(lp, h, cfg))
    h = _rms(x, _f32(lp["ln2"]), eps)
    m_gate, m_down = cfg["mlp_multipliers"]
    mlp = (h @ _f32(lp["w_up"])) * jax.nn.silu(
        (h @ _f32(lp["w_gate"])) * m_gate)
    return x + (mlp @ _f32(lp["w_down"])) * m_down


def head(lnf, w_head, x, cfg):
    """Logits of rows ``x (R, hidden)`` over the columns of ``w_head
    (hidden, V)``: hand it a block of the vocabulary's columns to get
    that block's logits."""
    return (_rms(x, _f32(lnf), cfg["rms_norm_eps"]) @ _f32(w_head)) \
        * cfg["lm_head_multiplier"]


def forward(params, tokens, cfg):
    """``tokens (T,)`` int32 -> logits ``(T, vocab)`` float32."""
    x = embed(params, tokens, cfg)
    for lp in params["layers"]:
        x = layer(lp, x, cfg)
    return head(params["lnf"], params["head"], x, cfg)
