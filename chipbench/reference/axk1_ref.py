"""Plain forward of A.X-K1 (``model_type`` ``axk1``): float32
``jax.numpy``, whole sequence, no cache, no kernels, attention in the
plain (not absorbed) form, the experts in a loop.

Written from the published description (``config.json`` of skt/A.X-K1,
whose keys are the DeepSeek-V3 family's, and that family's public
equations) and not imported from the program.  ``x`` is a row of the
residual stream, ``N`` RMSNorm at ``rms_norm_eps`` with float32
statistics and a learned weight, every projection bias-free:

- ``x = E[token]``; logits ``= N_f(x) @ W_head``, the head untied;
- latent attention: ``h = N_1(x)``; ``c_q = N_q(h W_qa)``; per head
  ``[q_nope | q_rope] = c_q W_qb`` (``qk_nope_head_dim`` +
  ``qk_rope_head_dim``); ``[c | k_rope] = h W_kva`` (``kv_lora_rank`` +
  ``qk_rope_head_dim``), ``c_kv = N_kv(c)``, ``k_rope`` one head shared
  by all query heads; per head ``[k_nope | v] = c_kv W_kvb``; ``q_rope``
  and ``k_rope`` rotated by YaRN's frequencies: pair ``i`` of
  ``qk_rope_head_dim / 2`` turns ``theta**(-2i/dim)`` radians a
  position where it completes more than ``beta_fast`` turns in
  ``original_max_position_embeddings`` positions, that over ``factor``
  where fewer than ``beta_slow``, a linear ramp in ``i`` between the two
  indices (the lower rounded down, the upper up); cos and sin times
  ``m(mscale) / m(mscale_all_dim)``, ``m(s) = 0.1 s ln(factor) + 1``;
  scores ``(q_nope . k_nope + q_rope . k_rope) * (nope + rope)**-0.5 *
  m(mscale_all_dim)**2``; causal softmax; ``x = x + concat_heads(p v)
  W_o``;
- layers below ``first_k_dense_replace``: ``x = x + MLP(N_2(x))``,
  ``MLP(h) = (silu(h W_gate) * h W_up) W_down``;
- the others: ``s = sigmoid(N_2(x) W_g)`` over
  ``n_routed_experts_published`` experts; the ``num_experts_per_tok``
  largest selected (``topk_method`` ``"none"``: over all;
  ``"noaux_tc"``: the experts in ``n_group`` groups, a group scored by
  the sum of its two largest, the ``topk_group`` best groups kept, the
  selection made inside them); ``w = s[sel] / sum s[sel] *
  routed_scaling_factor`` (``norm_topk_prob``); ``x = x + sum_i w_i
  E_i(h) + E_shared(h)``, every ``E`` an ``MLP`` of
  ``moe_intermediate_size``.

**The share.**  A layer's parameters hold ``n_routed_experts`` of the
published experts, ``experts_first`` on (``e<j>_gate/up/down`` is expert
``experts_first + j``); the sum runs over the selected experts that are
held, and what the others would add is left out.  With every expert
held this is the published layer.

Departures from the family's public modelling code as the author knows
it: (1) that code stores the rope lanes of ``W_qb`` and ``W_kva``
interleaved and de-interleaves them before a split-half rotation; here
the lanes are taken as already split in halves (pair ``i`` is lanes ``i``
and ``i + dim/2``), which for weights drawn at random is the same
distribution; (2) ``"noaux_tc"`` adds a per-expert correction bias to
the scores it selects by; no such bias is drawn, so it is zero here;
(3) the experts are evaluated for every row and combined by a
``(rows, experts)`` matrix of routing weights that is zero where a row
was not routed, where that code gathers each expert's rows: the same
sum; (4) depth, experts held and vocabulary are the configuration's
cut.

``params`` is the program's pytree (``embed``, ``head``, ``lnf``,
``layers[i]`` with ``ln1 wq_a q_norm wq_b wkv_a kv_norm wkv_b wo ln2``
and either ``w_gate w_up w_down`` or ``w_router ws_gate ws_up ws_down
e<j>_gate e<j>_up e<j>_down``; matrices are ``(in, out)``).
``forward`` is ``embed``, then ``layer`` for each layer, then ``head``;
a caller short of memory calls the pieces.  Run under
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn_inv_freq(dim, base, sc):
    """Radians a position of each of ``dim // 2`` pairs."""
    half = dim // 2
    plain = [float(base) ** (-2.0 * i / dim) for i in range(half)]

    def pair_at(turns):
        return dim * math.log(sc["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair_at(sc["beta_fast"])), 0)
    high = min(math.ceil(pair_at(sc["beta_slow"])), dim - 1)
    span = max(high - low, 1e-3)
    out = []
    for i, f in enumerate(plain):
        slow = min(max((i - low) / span, 0.0), 1.0)     # 1: divided whole
        out.append(f / sc["factor"] * slow + f * (1.0 - slow))
    return jnp.asarray(out, jnp.float32)


def _rotate(x, pos, inv_freq, scale):
    """``x (T, H, D)``: the two halves of the lanes rotate against each
    other (an axis pair, not slices: see ``decodemodel_ref._rope``)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None]
    cos = (jnp.cos(ang) * scale)[:, None]
    sin = (jnp.sin(ang) * scale)[:, None]
    xr = x.reshape(x.shape[:-1] + (2, half))
    x1, x2 = xr[..., 0, :], xr[..., 1, :]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-2).reshape(x.shape)


def embed(params, tokens, cfg):
    """``tokens (T,)`` -> ``x (T, hidden)``; the rows are taken before
    they are widened."""
    return _f32(params["embed"][tokens])


def _attention(lp, h, cfg):
    t_ = h.shape[0]
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    sc = cfg["rope_scaling"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(t_, dtype=jnp.int32)
    c_q = _rms(h @ _f32(lp["wq_a"]), lp["q_norm"], eps)
    q = (c_q @ _f32(lp["wq_b"])).reshape(t_, nh, nope + rope)
    ckr = h @ _f32(lp["wkv_a"])
    c_kv = _rms(ckr[:, :rank], lp["kv_norm"], eps)
    kv = (c_kv @ _f32(lp["wkv_b"])).reshape(t_, nh, nope + vd)
    m_all = _mscale(sc["factor"], sc.get("mscale_all_dim", 0))
    m_rot = _mscale(sc["factor"], sc.get("mscale", 1)) / m_all
    inv = _yarn_inv_freq(rope, cfg["rope_theta"], sc)
    q_r = _rotate(q[..., nope:], pos, inv, m_rot)
    k_r = _rotate(ckr[:, None, rank:], pos, inv, m_rot)      # (T, 1, rope)
    s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
         + jnp.einsum("qhd,kd->hqk", q_r, k_r[:, 0]))
    s = s * ((nope + rope) ** -0.5 * m_all * m_all)
    s = jnp.where(jnp.tril(jnp.ones((t_, t_), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), kv[..., nope:])
    return o.reshape(t_, nh * vd) @ _f32(lp["wo"])


def _mlp(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def route(scores, cfg):
    """``scores (T, experts)`` past their sigmoid -> the ``(T,
    experts)`` matrix of routing weights, zero where not selected."""
    t_, experts = scores.shape
    k = cfg["num_experts_per_tok"]
    pick = scores
    if cfg["topk_method"] == "noaux_tc":
        groups, keep = cfg["n_group"], cfg["topk_group"]
        per = experts // groups
        by_group = jnp.sort(scores.reshape(t_, groups, per), axis=-1)
        rank = by_group[..., -1] + by_group[..., -2]          # (T, groups)
        floor = jnp.sort(rank, axis=-1)[:, groups - keep][:, None]
        pick = jnp.where(jnp.repeat(rank >= floor, per, axis=1), scores, 0.0)
    elif cfg["topk_method"] != "none":
        raise ValueError(f"topk_method {cfg['topk_method']!r}")
    kth = jnp.sort(pick, axis=-1)[:, experts - k][:, None]
    sel = pick >= kth
    w = jnp.where(sel, scores, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def _experts(lp, h, cfg):
    w = route(jax.nn.sigmoid(h @ _f32(lp["w_router"])), cfg)
    y = _mlp(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    j = 0
    while f"e{j}_gate" in lp:       # the experts held here, one by one
        e = cfg["experts_first"] + j
        y = y + w[:, e, None] * _mlp(h, lp[f"e{j}_gate"], lp[f"e{j}_up"],
                                     lp[f"e{j}_down"])
        j += 1
    return y


def layer(lp, x, cfg):
    """One layer over the whole sequence ``x (T, hidden)``: a dense
    layer or an expert layer by what ``lp`` holds."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(lp, _rms(x, lp["ln1"], eps), cfg)
    h = _rms(x, lp["ln2"], eps)
    if "w_router" in lp:
        return x + _experts(lp, h, cfg)
    return x + _mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"])


def head(lnf, w_head, x, cfg):
    """Logits of rows ``x (R, hidden)`` over the columns of ``w_head
    (hidden, V)``: a block of the vocabulary's columns gives that
    block's logits."""
    return _rms(x, lnf, cfg["rms_norm_eps"]) @ _f32(w_head)


def forward(params, tokens, cfg):
    """``tokens (T,)`` int32 -> logits ``(T, vocab)`` float32."""
    x = embed(params, tokens, cfg)
    for lp in params["layers"]:
        x = layer(lp, x, cfg)
    return head(params["lnf"], params["head"], x, cfg)
