"""A.X-K1 for the decode plane: multi-head latent attention over a paged
latent cache, then a SwiGLU MLP in the leading dense layers and, in
every layer after them, sigmoid-routed experts beside a shared one.

Built from the keys of the family's ``config.json`` (``model_type``
``axk1``; the keys are the DeepSeek-V3 family's).  ``x`` is a row of the
residual stream, ``N`` RMSNorm at ``rms_norm_eps`` with a learned
weight, every projection bias-free:

    h = N_1(x)
    c_q = N_q(h W_qa);  [q_nope | q_rope] = c_q W_qb        per head
    [c | k_rope] = h W_kva;  c_kv = N_kv(c)                 one for all heads
    [k_nope | v] = c_kv W_kvb                               per head
    scores = (q_nope . k_nope + rot(q_rope) . rot(k_rope)) * scale
    x = x + (softmax(scores) v) W_o
    x = x + MLP(N_2(x))            layers below first_k_dense_replace
    x = x + sum_i w_i E_i(N_2(x)) + E_shared(N_2(x))        the others

``rot`` turns the ``qk_rope_head_dim`` lanes by YaRN's frequencies
(``ops/rope.py:yarn_frequencies``); ``scale`` is ``(nope +
rope)**-0.5 * m**2`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
cos and sin carry ``m(mscale) / m(mscale_all_dim)``.  ``E`` and ``MLP``
are ``(silu(h W_gate) * h W_up) W_down``.  The router: ``s = sigmoid(h
W_g)`` in float32, the ``num_experts_per_tok`` largest selected
(``topk_method`` ``"none"``: over all experts; ``"noaux_tc"``: the
family's group-limited selection, without the correction bias a
checkpoint would bring), ``w = s[sel] / sum s[sel] *
routed_scaling_factor``.  No capacity, no dropped token.

**The share.**  ``n_routed_experts`` counts the experts HELD here,
``n_routed_experts_published`` the router's width, ``experts_first``
the first held one's index.  The layer routes over all published
experts and computes the selected held ones' part and the shared
expert (``parallel/moe.py``); what the absent experts would add is left
out, and that partial result goes on to the next layer.

**The cache** holds one row a token and layer, ``[c_kv | rot(k_rope)]``,
for all heads (``paged_kv``'s latent page).  Decode and prefill attend
in the absorbed form (``q_nope`` through ``W_kvb``'s key half, the
output through its value half: the cache is read as it lies); the
dense oracle in the plain form.

One block function serves the three paths.  The residual stream is
float32 (a row is 7168 numbers; the weights are what a step moves), the
matrices and what is multiplied with them the model's dtype, the router
float32 on the float32 normed row.  Weights are drawn on the device
from the seed, matrix by matrix.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ...ops.rope import yarn_frequencies
from ...parallel.moe import held_experts, route_topk
from .decode_model import Float32Stream
from .paged_kv import (last_rows, latent_chunk_attention,
                       latent_dense_attention, latent_slot_attention,
                       latent_width)

__all__ = ["AXK1"]

# the keys of config.json the arithmetic reads
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "intermediate_size", "moe_intermediate_size",
         "n_routed_experts", "n_routed_experts_published", "experts_first",
         "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
         "topk_method", "norm_topk_prob", "routed_scaling_factor",
         "rms_norm_eps", "rope_theta", "rope_scaling",
         "routed_down_divisor")
# what this model does not implement: a config that asks for it is refused
_FIXED = {"hidden_act": "silu", "attention_bias": False,
          "scoring_func": "sigmoid", "n_shared_experts": 1,
          "moe_layer_freq": 1, "tie_word_embeddings": False}


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


class AXK1(Float32Stream):
    """``AXK1(config)`` with ``config`` the dict of an ``axk1``
    ``config.json`` and the three keys of the share (module docstring).
    ``abstract=True`` gives ``params`` as shapes only."""

    def __init__(self, config: Dict[str, Any], *, seed: int = 0,
                 dtype="bfloat16", abstract: bool = False):
        missing = [k for k in _KEYS if k not in config]
        if missing:
            raise ValueError(f"axk1 config lacks {missing}")
        for key, want in _FIXED.items():
            if config.get(key, want) != want:
                raise ValueError(f"axk1 config has {key}={config[key]!r}; "
                                 f"only {want!r} is implemented")
        c = self.config = {k: config[k] for k in _KEYS}
        scaling = dict(c["rope_scaling"])
        if scaling.get("type") != "yarn":
            raise ValueError("axk1 rope_scaling must be of type 'yarn'")
        if c["topk_method"] not in ("none", "noaux_tc"):
            raise ValueError(f"topk_method {c['topk_method']!r}: only "
                             f"'none' and 'noaux_tc' are implemented")
        self.vocab_size = int(c["vocab_size"])
        self.dim = int(c["hidden_size"])
        self.n_layers = int(c["num_hidden_layers"])
        self.n_dense = int(c["first_k_dense_replace"])
        self.n_heads = int(c["num_attention_heads"])
        self.q_rank = int(c["q_lora_rank"])
        self.rank = int(c["kv_lora_rank"])
        self.nope = int(c["qk_nope_head_dim"])
        self.rope_dim = int(c["qk_rope_head_dim"])
        self.v_dim = int(c["v_head_dim"])
        self.eps = float(c["rms_norm_eps"])
        self.held = int(c["n_routed_experts"])
        self.experts = int(c["n_routed_experts_published"])
        self.first = int(c["experts_first"])
        self.top_k = int(c["num_experts_per_tok"])
        if self.rope_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if not 0 <= self.first <= self.experts - self.held:
            raise ValueError("the held experts must lie inside the "
                             "published ones")
        # plain top-k is the group-limited selection with one group
        self.groups = ((int(c["n_group"]), int(c["topk_group"]))
                       if c["topk_method"] == "noaux_tc" else (1, 1))
        if self.experts % self.groups[0]:
            raise ValueError("n_group must divide the routed experts")
        self.inv_freq = yarn_frequencies(self.rope_dim, c["rope_theta"],
                                         **scaling)
        factor = float(scaling["factor"])
        m_all = _yarn_mscale(factor, float(scaling.get("mscale_all_dim", 0)))
        if abs(_yarn_mscale(factor, float(scaling.get("mscale", 1)))
               - m_all) > 1e-12:
            raise ValueError("mscale != mscale_all_dim (cos and sin scaled) "
                             "is not implemented")
        self.sm_scale = (self.nope + self.rope_dim) ** -0.5 * m_all * m_all
        self.width = latent_width(self.rank, self.rope_dim)
        self.dtype = jnp.dtype(dtype)
        key = jax.random.PRNGKey(seed)
        if abstract:
            self.params = jax.eval_shape(self._init_all, key)
        else:
            self.params = self._init_all(key, jit=jax.jit)

    @property
    def page_widths(self) -> tuple:
        """One latent page a layer."""
        return (self.width,)

    # -- weights ---------------------------------------------------------------
    # Not in config.json (the benchmark's configuration lists them as
    # assumed, with what was measured at other scales): every matrix
    # normal at 1/sqrt(fan-in), embedding rows normal at 1, norm weights
    # 1: every branch at full strength.  One exception, the held routed
    # experts' W_down, further divided by ``routed_down_divisor``: with
    # random weights the eighth and ninth of 192 scores of some tokens
    # lie closer than bfloat16 rounding upstream, a float32 reference
    # then selects another expert, and that expert's whole term at
    # full strength is more than a token check by logits can allow.
    # The router's rows at the fan-in scale give scores spread between
    # about 0.1 and 0.9 and an even expected load.

    def _mat(self, key, fan_in, fan_out, div=1.0):
        w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
        return (w * (fan_in ** -0.5 / div)).astype(self.dtype)

    def _init_layer(self, key, index: int, mat):
        c = self.config
        d, h = self.dim, self.n_heads
        keys = iter(jax.random.split(key, 12 + 3 * self.held))
        routed = float(c["routed_down_divisor"])
        ones = functools.partial(jnp.ones, dtype=self.dtype)
        lp = {
            "ln1": ones((d,)),
            "wq_a": mat(next(keys), d, self.q_rank),
            "q_norm": ones((self.q_rank,)),
            "wq_b": mat(next(keys), self.q_rank,
                        h * (self.nope + self.rope_dim)),
            "wkv_a": mat(next(keys), d, self.rank + self.rope_dim),
            "kv_norm": ones((self.rank,)),
            "wkv_b": mat(next(keys), self.rank, h * (self.nope + self.v_dim)),
            "wo": mat(next(keys), h * self.v_dim, d),
            "ln2": ones((d,)),
        }
        if index < self.n_dense:
            f = int(c["intermediate_size"])
            lp.update(w_gate=mat(next(keys), d, f), w_up=mat(next(keys), d, f),
                      w_down=mat(next(keys), f, d))
            return lp
        f = int(c["moe_intermediate_size"])
        lp["w_router"] = mat(next(keys), d, self.experts)
        for name in ["ws"] + [f"e{j}" for j in range(self.held)]:
            lp[f"{name}_gate"] = mat(next(keys), d, f)
            lp[f"{name}_up"] = mat(next(keys), d, f)
            lp[f"{name}_down"] = mat(next(keys), f, d,
                                     1.0 if name == "ws" else routed)
        return lp

    def _init_all(self, key, jit=lambda f, **kw: f):
        # one program a matrix shape, so that no two matrices'
        # temporaries are alive together
        mat = jit(self._mat, static_argnums=(1, 2, 3))
        k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
        embed = jit(lambda k: jax.random.normal(
            k, (self.vocab_size, self.dim), jnp.float32).astype(self.dtype))
        return {"embed": embed(k_embed),
                "head": mat(k_head, self.dim, self.vocab_size),
                "lnf": jnp.ones((self.dim,), self.dtype),
                "layers": [self._init_layer(jax.random.fold_in(key, i + 1),
                                            i, mat)
                           for i in range(self.n_layers)]}

    # -- the block ---------------------------------------------------------------

    def _ffn(self, h, w_gate, w_up, w_down):
        return jnp.dot(jax.nn.silu(h @ w_gate) * (h @ w_up), w_down,
                       preferred_element_type=jnp.float32)

    def _block(self, lp, x, kv, attend, valid=None):
        """One layer over rows ``x (rows, dim)``, float32.
        ``attend(q_nope, q_rope, c_kv, k_rope, w_kvb, *kv)`` takes the
        heads' queries ``(rows, heads, nope)`` and ``(rows, heads,
        rope)`` before rotation, the normed latent ``(rows, rank)`` and
        the shared rope key ``(rows, rope)`` before rotation, the
        up-projection ``(rank, heads, nope + v)`` and the layer's
        latent buffer (none for the dense path), and returns the heads'
        outputs ``(rows, heads, v)`` and the buffer's successor.
        Returns ``(x, cache buffers, the expert layer's counters or
        None)``; the counters count the rows of ``valid``."""
        rows, h, dt = x.shape[0], self.n_heads, self.dtype
        hn = self._norm(x, lp["ln1"]).astype(dt)
        c_q = self._norm(hn @ lp["wq_a"], lp["q_norm"]).astype(dt)
        q = (c_q @ lp["wq_b"]).reshape(rows, h, self.nope + self.rope_dim)
        ckr = hn @ lp["wkv_a"]
        c_kv = self._norm(ckr[:, :self.rank], lp["kv_norm"]).astype(dt)
        attn, kv = attend(
            q[..., :self.nope], q[..., self.nope:], c_kv, ckr[:, self.rank:],
            lp["wkv_b"].reshape(self.rank, h, self.nope + self.v_dim), *kv)
        x = x + jnp.dot(attn.reshape(rows, h * self.v_dim).astype(dt),
                        lp["wo"], preferred_element_type=jnp.float32)
        h2 = self._norm(x, lp["ln2"])
        hb = h2.astype(dt)
        if "w_router" not in lp:
            return x + self._ffn(hb, lp["w_gate"], lp["w_up"],
                                 lp["w_down"]), kv, None
        index, weight = route_topk(
            self._scores(h2, lp["w_router"]), self.top_k,
            n_group=self.groups[0],
            topk_group=self.groups[1],
            normalize=bool(self.config["norm_topk_prob"]),
            scale=float(self.config["routed_scaling_factor"]))
        routed, counters = held_experts(
            hb, index, weight,
            [(lp[f"e{j}_gate"], lp[f"e{j}_up"], lp[f"e{j}_down"])
             for j in range(self.held)], self.first, valid)
        shared = self._ffn(hb, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return x + routed + shared, kv, counters

    def _layers(self, params, pool, tokens, attend, valid=None):
        """Embed ``tokens`` and run every layer over its buffer of
        ``pool``: ``(pool, rows before the final norm, counters)``, the
        counters the expert layers' means."""
        x = params["embed"][tokens].astype(jnp.float32)
        out, seen = [], []
        for kv, lp in zip(pool, params["layers"]):
            x, kv, counters = self._block(lp, x, kv, attend, valid)
            out.append(kv)
            if counters is not None:
                seen.append(counters)
        mean = {k: jnp.stack([s[k] for s in seen]).mean()
                for k in (seen[0] if seen else ())}
        return tuple(out), x, mean

    def _counters(self, mean) -> dict:
        """What a decode step hands back beside its tokens, means over
        the expert layers: the share of routed (row, expert) pairs that
        met an expert held here, the rows a held expert got (mean and
        largest), the share of held experts that got none.  The decode
        step adds ``latent_overlap_share``, a mean over the layers
        (``paged_attention.latent_overlap_share``)."""
        if not mean:
            return {}
        return {"moe_local_pair_share":
                mean["local_pairs"] / jnp.maximum(mean["pairs"], 1.0),
                "moe_expert_rows_mean": mean["rows_mean"],
                "moe_expert_rows_max": mean["rows_max"],
                "moe_experts_idle_share": mean["idle"] / self.held}

    # -- decode: one token a slot ------------------------------------------------

    def decode_logits(self, params, pool, tokens, positions, tables, active):
        """The decode step up to its logits ``(slots, vocab)``."""
        attend = latent_slot_attention(
            pool, positions, tables, active, inv_freq=self.inv_freq,
            sm_scale=self.sm_scale)
        pool, x, mean = self._layers(params, pool, tokens, attend, active)
        counters = dict(self._counters(mean), latent_overlap_share=jnp.stack(
            attend.overlap).mean())
        return pool, self._logits(params, x), counters

    # -- prefill: lanes, each one chunk of one slot -------------------------------

    def prefill_logits(self, params, pool, tokens, start, chunk_len, tables,
                       slot):
        """A dispatch of chunks up to the logits ``(lanes, vocab)``
        after each lane's last valid token (no state: ``slot`` unread)."""
        attend = latent_chunk_attention(
            pool, start, chunk_len, tables, tokens.shape[1],
            inv_freq=self.inv_freq, sm_scale=self.sm_scale)
        pool, x, _ = self._layers(params, pool, tokens.reshape(-1), attend)
        return pool, self._logits(params, last_rows(x, chunk_len))

    # -- dense: the whole sequence, no cache (the in-program oracle) -------------

    def dense_logits(self, params, tokens):
        """Logits ``(T, vocab)`` of the whole of ``tokens``: causal
        attention in the plain form over the sequence itself."""
        attend = latent_dense_attention(
            tokens.shape[0], inv_freq=self.inv_freq, sm_scale=self.sm_scale)
        _, x, _ = self._layers(params, [()] * self.n_layers, tokens, attend)
        return self._logits(params, x)
