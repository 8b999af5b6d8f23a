"""Falcon-H1 for the decode plane: in every layer Mamba-2 heads and
grouped-query attention heads read the same normed residual side by
side, then a SwiGLU MLP.

Built from the keys of the family's ``config.json`` (``model_type``
``falcon_h1``).  With ``x`` the residual stream and every projection
bias-free:

    x = E[token] * embedding_multiplier
    h = RMSNorm_1(x)
    x = x + ssm_out_multiplier * Mamba(h)
          + attention_out_multiplier * Attn(h * attention_in_multiplier)
    x = x + MLP(RMSNorm_2(x))
    logits = (RMSNorm_f(x) @ W_head) * lm_head_multiplier

``Attn``: ``num_attention_heads`` query heads over
``num_key_value_heads`` K/V heads of ``head_dim``; ``k *
key_multiplier``; rotary positions in the split-half form on q and k;
causal softmax at ``1/sqrt(head_dim)``.

``Mamba`` (Mamba-2): ``u = (h * ssm_in_multiplier) @ W_in``, split as
``[z d_ssm | xBC d_ssm + 2 groups * d_state | dt heads]``, the five
``ssm_multipliers`` scaling ``z, x, B, C, dt``; ``xBC = silu(causal
depthwise conv1d(xBC) + bias)``; ``dt = softplus(dt + dt_bias)``; per
head the recurrence of ``ops/ssm.py`` with ``a = -exp(A_log)``; gate
then norm (``mamba_norm_before_gate`` false): ``y = GroupRMSNorm(y *
silu(z))`` over the groups with a learned weight; ``y @ W_out``.

``MLP``: ``(up(h) * silu(gate(h) * mlp_multipliers[0])) @ W_down *
mlp_multipliers[1]``.

One block function (:meth:`FalconH1._block`) serves the four paths;
what differs between them is handed to it: how attention reaches its
keys and values (built by ``paged_kv``, which alone knows a page), and
how the mixer's convolution and recurrence reach their state (the
``mix`` closures here).

- decode: one token a slot.  K/V rows scattered into the layer's paged
  buffers and read by ``paged_attention``; the convolution's tail and
  the state-space state are rows of the layer's two state buffers,
  ``(slots, conv-1, conv width)`` and ``(slots, heads, head dim, state
  size)``, the latter updated in place by ``ssm_update``;
- prefill: lanes, each one chunk of one slot, every projection one
  product over all their rows.  A chunk attends over its slot's
  gathered pages; the recurrence runs in the chunked form from the
  slot's state (``prefill_chunk`` is ``mamba_chunk_size``, so a chunk of
  the prompt is a chunk of the scan) and leaves it for the next chunk
  or the first decode step;
- a turn: the decode step with a prefill dispatch's lanes inside it,
  the slots' rows and the lanes' one matrix through every projection,
  MLP, norm and the head, attention and the mixer's state each by its
  own path (a filling slot does not decode: the rows are disjoint), so
  the weights stream once for both;
- dense: the whole sequence with no cache, causal attention and the
  recurrence over time: the in-program oracle the cached paths are
  pinned to.

Weights are drawn on the device from the seed, layer by layer: the
host never holds them.  The state is float32.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ...ops.ssm import ssm_chunk_scan, ssm_scan_reference, ssm_update
from .decode_model import rms_norm
from .engine import DecodePlaneModel
from .paged_kv import (chunk_attention, chunk_conv, dense_attention,
                       dense_conv, last_rows, slot_attention, slot_conv,
                       slot_rows, turn_attention)

__all__ = ["FalconH1"]

# a lane's chunked scan, jitted on its own: the lanes and layers of an
# executable, and every executable with lanes of one bucket, share ONE
# trace of it (PERF.md, PR 40)
_chunk_scan = jax.jit(ssm_chunk_scan)

# the keys of config.json the arithmetic reads
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "intermediate_size", "rms_norm_eps", "rope_theta",
         "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
         "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size",
         "embedding_multiplier", "lm_head_multiplier",
         "attention_in_multiplier", "attention_out_multiplier",
         "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
         "ssm_multipliers", "mlp_multipliers")
# what this model does not implement: a config that asks for it is refused
_FIXED = {"hidden_act": "silu", "attention_bias": False, "mlp_bias": False,
          "mamba_proj_bias": False, "projectors_bias": False,
          "mamba_conv_bias": True, "mamba_rms_norm": True,
          "mamba_norm_before_gate": False, "tie_word_embeddings": False,
          "rope_scaling": None}


def _softplus_inverse(y):
    return y + jnp.log(-jnp.expm1(-y))


def _scaled(x, m: float):
    """``x * m`` with the product taken in float32 and rounded once:
    the multiplier itself is never rounded to the activations' dtype."""
    return (x.astype(jnp.float32) * m).astype(x.dtype)


class FalconH1(DecodePlaneModel):
    """``FalconH1(config)`` with ``config`` the dict of a ``falcon_h1``
    ``config.json``.  ``abstract=True`` gives ``params`` as shapes only
    (for compiling without the weights)."""

    def __init__(self, config: Dict[str, Any], *, seed: int = 0,
                 dtype="bfloat16", abstract: bool = False):
        missing = [k for k in _KEYS if k not in config]
        if missing:
            raise ValueError(f"falcon_h1 config lacks {missing}")
        for key, want in _FIXED.items():
            if config.get(key, want) != want:
                raise ValueError(
                    f"falcon_h1 config has {key}={config[key]!r}; only "
                    f"{want!r} is implemented")
        c = self.config = {k: config[k] for k in _KEYS}
        self.vocab_size = int(c["vocab_size"])
        self.dim = int(c["hidden_size"])
        self.n_layers = int(c["num_hidden_layers"])
        self.n_heads = int(c["num_attention_heads"])
        self.kv_heads = int(c["num_key_value_heads"])
        self.head_dim = int(c["head_dim"])
        self.inner = int(c["intermediate_size"])
        self.eps = float(c["rms_norm_eps"])
        self.rope_base = float(c["rope_theta"])
        self.d_ssm = int(c["mamba_d_ssm"])
        self.ssm_heads = int(c["mamba_n_heads"])
        self.ssm_head_dim = int(c["mamba_d_head"])
        self.d_state = int(c["mamba_d_state"])
        self.groups = int(c["mamba_n_groups"])
        self.d_conv = int(c["mamba_d_conv"])
        if self.n_heads % self.kv_heads or self.head_dim % 2:
            raise ValueError("query heads must be a multiple of the KV "
                             "heads, and head_dim even for rope")
        if (self.ssm_heads * self.ssm_head_dim != self.d_ssm
                or self.ssm_heads % self.groups):
            raise ValueError("mamba_d_ssm must be heads x head dim, the "
                             "heads a multiple of the groups")
        self.conv_width = self.d_ssm + 2 * self.groups * self.d_state
        self.in_width = self.d_ssm + self.conv_width + self.ssm_heads
        self.dtype = jnp.dtype(dtype)
        self.state_spec = (
            ("ssm", (self.ssm_heads, self.ssm_head_dim, self.d_state),
             "float32"),
            ("conv", (self.d_conv - 1, self.conv_width), "float32"))
        m = [float(v) for v in c["ssm_multipliers"]]
        gn = self.groups * self.d_state
        # the five multipliers laid over the in-projection's columns
        self._mup = jnp.concatenate([
            jnp.full((self.d_ssm,), m[0]), jnp.full((self.d_ssm,), m[1]),
            jnp.full((gn,), m[2]), jnp.full((gn,), m[3]),
            jnp.full((self.ssm_heads,), m[4])]).astype(jnp.float32)
        key = jax.random.PRNGKey(seed)
        if abstract:
            self.params = jax.eval_shape(self._init_all, key)
        else:
            # one program a piece, so that no two pieces' temporaries
            # are alive together
            self.params = self._init_all(key, jit=jax.jit)

    # -- weights ---------------------------------------------------------------
    # Not in config.json (the benchmark's configuration lists them as
    # assumed).  The multipliers are muP's: a trained checkpoint's
    # matrix sits at the fan-in scale DIVIDED by the multiplier its
    # output meets in the forward pass, so that the product is of
    # order one.  Drawn so here: every matrix normal at
    # 1/(sqrt(fan-in) * its multipliers), embedding rows normal at
    # 1/embedding_multiplier.  Then keys are as large as queries (the
    # softmax is not flat), each mixer and the MLP add a term of the
    # residual's own size, and the logits are of order one: a fault in
    # any branch shows in the logits.  A uniform in [1, 16]; dt
    # log-uniform in [1e-3, 1e-1] through the inverse softplus; D = 1;
    # convolution weights uniform at 1/sqrt(d_conv).

    def _mat(self, key, fan_in, fan_out, mult=1.0):
        w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
        return (w * (fan_in ** -0.5 / mult)).astype(self.dtype)

    def _init_embed(self, key):
        w = jax.random.normal(key, (self.vocab_size, self.dim), jnp.float32)
        return (w / self.config["embedding_multiplier"]).astype(self.dtype)

    def _init_head(self, key):
        return self._mat(key, self.dim, self.vocab_size,
                         self.config["lm_head_multiplier"])

    def _init_layer(self, key):
        ks = jax.random.split(key, 12)
        c = self.config
        d, hd, f32 = self.dim, self.head_dim, jnp.float32
        bound = self.d_conv ** -0.5
        dt = jnp.exp(jax.random.uniform(
            ks[9], (self.ssm_heads,), f32, math.log(1e-3), math.log(1e-1)))
        m_att = c["attention_in_multiplier"]
        m_gate, m_down = c["mlp_multipliers"]
        # the in-projection's columns meet ssm_in_multiplier and their
        # section's own multiplier
        w_in = jax.random.normal(ks[4], (d, self.in_width), f32) * (
            d ** -0.5 / (c["ssm_in_multiplier"] * self._mup))
        return {
            "ln1": jnp.ones((d,), self.dtype),
            "wq": self._mat(ks[0], d, self.n_heads * hd, m_att),
            "wk": self._mat(ks[1], d, self.kv_heads * hd,
                            m_att * c["key_multiplier"]),
            "wv": self._mat(ks[2], d, self.kv_heads * hd, m_att),
            "wo": self._mat(ks[3], self.n_heads * hd, d,
                            c["attention_out_multiplier"]),
            "w_in": w_in.astype(self.dtype),
            "conv_w": jax.random.uniform(
                ks[5], (self.d_conv, self.conv_width), f32, -bound, bound),
            "conv_b": jax.random.uniform(
                ks[6], (self.conv_width,), f32, -bound, bound),
            "a_log": jnp.log(jax.random.uniform(
                ks[7], (self.ssm_heads,), f32, 1.0, 16.0)),
            "d": jnp.ones((self.ssm_heads,), f32),
            "dt_bias": _softplus_inverse(dt),
            "norm": jnp.ones((self.d_ssm,), self.dtype),
            "w_out": self._mat(ks[8], self.d_ssm, d,
                               c["ssm_out_multiplier"]),
            "ln2": jnp.ones((d,), self.dtype),
            "w_gate": self._mat(ks[10], d, self.inner, m_gate),
            "w_up": self._mat(ks[11], d, self.inner),
            "w_down": self._mat(jax.random.fold_in(key, 99), self.inner, d,
                                m_down),
        }

    def _init_all(self, key, jit=lambda f: f):
        k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
        layer = jit(self._init_layer)
        return {"embed": jit(self._init_embed)(k_embed),
                "head": jit(self._init_head)(k_head),
                "lnf": jnp.ones((self.dim,), self.dtype),
                "layers": [layer(jax.random.fold_in(key, i + 1))
                           for i in range(self.n_layers)]}

    # -- the block ---------------------------------------------------------------

    def _block(self, lp, x, kv, attend, mix):
        """One layer over rows ``x (..., dim)``.

        ``attend(q, k, v, *kv)`` takes the projected heads ``(...,
        heads, head_dim)`` before rotation and the layer's K/V buffers
        (none for the dense path) and returns the attention output
        ``(..., n_heads, head_dim)`` and the buffers' successors;
        ``mix(xbc, dt)`` takes the mixer's convolution input ``(...,
        conv width)`` and ``dt (..., heads)`` past its softplus, both
        float32, and returns the recurrence's ``y (..., heads, head
        dim)`` in float32 and what it left of its state.  Returns ``(x,
        K/V, state)``."""
        c = self.config
        lead = x.shape[:-1]
        h = rms_norm(x, lp["ln1"], self.eps)
        # attention heads
        ha = _scaled(h, c["attention_in_multiplier"])
        q = (ha @ lp["wq"]).reshape(lead + (self.n_heads, self.head_dim))
        k = _scaled(ha @ lp["wk"], c["key_multiplier"]).reshape(
            lead + (self.kv_heads, self.head_dim))
        v = (ha @ lp["wv"]).reshape(lead + (self.kv_heads, self.head_dim))
        attn, kv = attend(q, k, v, *kv)
        attn = attn.reshape(lead + (-1,)).astype(x.dtype) @ lp["wo"]
        # Mamba-2 heads, on the same h
        u = _scaled(h, c["ssm_in_multiplier"]) @ lp["w_in"]
        u = u.astype(jnp.float32) * self._mup
        z = u[..., :self.d_ssm]
        xbc = u[..., self.d_ssm:self.d_ssm + self.conv_width]
        dt = jax.nn.softplus(u[..., self.d_ssm + self.conv_width:]
                             + lp["dt_bias"])
        y, state = mix(xbc, dt)
        y = y.reshape(lead + (self.d_ssm,)) * jax.nn.silu(z)
        yg = y.reshape(lead + (self.groups, self.d_ssm // self.groups))
        yg = yg * lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                            + self.eps)
        y = yg.reshape(y.shape).astype(x.dtype) * lp["norm"]
        x = (x + _scaled(y @ lp["w_out"], c["ssm_out_multiplier"])
             + _scaled(attn, c["attention_out_multiplier"]))
        # SwiGLU
        h2 = rms_norm(x, lp["ln2"], self.eps)
        m_gate, m_down = c["mlp_multipliers"]
        mlp = (h2 @ lp["w_up"]) * jax.nn.silu(
            _scaled(h2 @ lp["w_gate"], m_gate))
        return x + _scaled(mlp @ lp["w_down"], m_down), kv, state

    def _split(self, xbc):
        """The convolved ``xBC (..., conv width)`` as ``x (..., heads,
        head dim)``, ``B`` and ``C (..., groups, state)``."""
        lead = xbc.shape[:-1]
        gn = self.groups * self.d_state
        return (xbc[..., :self.d_ssm].reshape(
                    lead + (self.ssm_heads, self.ssm_head_dim)),
                xbc[..., self.d_ssm:self.d_ssm + gn].reshape(
                    lead + (self.groups, self.d_state)),
                xbc[..., self.d_ssm + gn:].reshape(
                    lead + (self.groups, self.d_state)))

    def _embed(self, params, tokens):
        return _scaled(params["embed"][tokens],
                       self.config["embedding_multiplier"])

    def _logits(self, params, x):
        x = rms_norm(x, params["lnf"], self.eps)
        return _scaled(x @ params["head"],
                       self.config["lm_head_multiplier"])

    # -- the mixer's state, by the kind of row ------------------------------------

    def _slot_mix(self, active, lp, xbc, dt, sbuf, cbuf):
        """Decode: one row a slot, the convolution's tail and the state
        rows of the slot, ``ssm_update`` in place; ``(y, (sbuf,
        cbuf))``."""
        conv, cbuf = slot_conv(cbuf, xbc, lp["conv_w"], active, lp["conv_b"])
        xs, b, c = self._split(jax.nn.silu(conv))
        sbuf, y = ssm_update(sbuf, xs, dt, -jnp.exp(lp["a_log"]), b, c,
                             lp["d"], active)
        return y, (sbuf, cbuf)

    @staticmethod
    def _valid(chunk_len, bucket: int):
        """Which of a dispatch's ``lanes * bucket`` rows are a prompt's."""
        return (jnp.arange(bucket)[None, :] < chunk_len[:, None]).reshape(-1)

    def _lane_mix(self, slot, chunk_len, valid, lp, xbc, dt, sbuf, cbuf):
        """Prefill: lanes of one slot's chunk each, the chunked scan from
        the slot's state; ``(y, (sbuf, cbuf))``.  ``valid``: which rows
        are a prompt's (``_valid``)."""
        lanes = slot.shape[0]

        def by_lane(rows):
            return rows.reshape((lanes, -1) + rows.shape[1:])

        conv, cbuf = chunk_conv(cbuf, xbc, lp["conv_w"], slot, chunk_len,
                                lp["conv_b"])
        xs, b, c = map(by_lane, self._split(jax.nn.silu(conv)))
        # a padded row neither decays the state nor adds to it
        dt = by_lane(jnp.where(valid[:, None], dt, 0.0))
        # the recurrence is a slot's own: a lane at a time from the
        # slot's state, and the states back in one scatter (a padding
        # lane's slot is past the buffer: dropped)
        before = slot_rows(sbuf, slot)
        state, y = zip(*(_chunk_scan(
            before[i], xs[i], dt[i], -jnp.exp(lp["a_log"]), b[i], c[i],
            lp["d"]) for i in range(lanes)))
        sbuf = sbuf.at[slot].set(jnp.stack(state), mode="drop")
        return jnp.concatenate(y), (sbuf, cbuf)

    def _cached(self, params, pool, x, attend, mix):
        """Every layer over rows ``x`` with its cached buffers, the
        mixer's state through ``mix(lp, xbc, dt, sbuf=, cbuf=)``:
        ``(pool, x)``."""
        out = []
        for (kbuf, vbuf, sbuf, cbuf), lp in zip(pool, params["layers"]):
            x, kv, state = self._block(
                lp, x, (kbuf, vbuf), attend,
                functools.partial(mix, lp, sbuf=sbuf, cbuf=cbuf))
            out.append(kv + state)
        return tuple(out), x

    # -- decode: one token a slot ------------------------------------------------

    def decode_logits(self, params, pool, tokens, positions, tables, active):
        """The decode step up to its logits ``(slots, vocab)``."""
        attend = slot_attention(pool, positions, tables, active,
                                rope_base=self.rope_base)
        pool, x = self._cached(params, pool, self._embed(params, tokens),
                               attend, functools.partial(self._slot_mix,
                                                         active))
        return pool, self._logits(params, x)

    # -- prefill: lanes, each one chunk of one slot -------------------------------

    def prefill_logits(self, params, pool, tokens, start, chunk_len, tables,
                       slot):
        """A dispatch of chunks up to the logits ``(lanes, vocab)``
        after each lane's last valid token."""
        attend = chunk_attention(pool, start, chunk_len, tables,
                                 tokens.shape[1], rope_base=self.rope_base)
        valid = self._valid(chunk_len, tokens.shape[1])
        pool, x = self._cached(
            params, pool, self._embed(params, tokens.reshape(-1)), attend,
            functools.partial(self._lane_mix, slot, chunk_len, valid))
        return pool, self._logits(params, last_rows(x, chunk_len))

    # -- a turn: the decode step with a dispatch's lanes inside it ----------------

    def turn_logits(self, params, pool, tokens, positions, tables, active,
                    lane_tokens, start, chunk_len, lane_tables, slot):
        """The decode step and a dispatch of lanes in ONE pass over the
        weights: every projection, MLP and norm over the slots' rows and
        then the lanes' as one matrix, attention and the mixer's state
        split at ``slots`` by kind (a filling slot does not decode: the
        two touch disjoint rows of every buffer, and the lanes' reads
        and scatters follow the decode rows' update of the same buffer,
        never a copy of it).  The logits ``(slots + lanes, vocab)``: a
        slot's, then a lane's after its last valid token, one head for
        both."""
        n = tokens.shape[0]
        attend = turn_attention(pool, positions, tables, active, start,
                                chunk_len, lane_tables, lane_tokens.shape[1],
                                rope_base=self.rope_base)
        valid = self._valid(chunk_len, lane_tokens.shape[1])

        def mix(lp, xbc, dt, sbuf, cbuf):
            y_slots, (sbuf, cbuf) = self._slot_mix(active, lp, xbc[:n],
                                                   dt[:n], sbuf, cbuf)
            y_lanes, (sbuf, cbuf) = self._lane_mix(slot, chunk_len, valid,
                                                   lp, xbc[n:], dt[n:],
                                                   sbuf, cbuf)
            return jnp.concatenate([y_slots, y_lanes]), (sbuf, cbuf)

        x = self._embed(params, jnp.concatenate([tokens,
                                                 lane_tokens.reshape(-1)]))
        pool, x = self._cached(params, pool, x, attend, mix)
        return pool, self._logits(params, jnp.concatenate(
            [x[:n], last_rows(x[n:], chunk_len)]))

    # -- dense: the whole sequence, no cache (the in-program oracle) -------------

    def dense_logits(self, params, tokens):
        """Logits ``(T, vocab)`` of the whole of ``tokens``: causal
        attention over the sequence itself, the recurrence over time
        from a zero state."""
        t_ = tokens.shape[0]
        attend = dense_attention(t_, rope_base=self.rope_base)
        x = self._embed(params, tokens)
        for lp in params["layers"]:

            def mix(xbc, dt, lp=lp):
                conv = dense_conv(xbc, lp["conv_w"], lp["conv_b"])
                xs, b, c = self._split(jax.nn.silu(conv))
                zero = jnp.zeros((self.ssm_heads, self.ssm_head_dim,
                                  self.d_state), jnp.float32)
                _, y = ssm_scan_reference(
                    zero, xs, dt, -jnp.exp(lp["a_log"]), b, c, lp["d"])
                return y, None

            x, _, _ = self._block(lp, x, (), attend, mix)
        return self._logits(params, x)
