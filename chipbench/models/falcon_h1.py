"""Model family ``falcon_h1``: what the ``decode_open_loop_model`` driver
needs to serve a Falcon-H1 shaped configuration through the program's
decode plane, found by the ``family`` in the configuration's file:
``build_decode_model``, ``decode_reference``, ``param_count``, and the
bytes and operations one call of each kernel the family brought needs
(``ssm_update_bytes``, ``ssm_update_flops``), computed from the
configuration's shapes and from nothing the program says about itself.
"""
from __future__ import annotations

from chipbench.harness.cli import load_module


def build_decode_model(cfg: dict, seed: int):
    """The program's model of this configuration, weights drawn on the
    device from ``seed``.  A program without the class fails here, at
    once."""
    from mxnet_tpu.serving import FalconH1
    return FalconH1(cfg, seed=seed, dtype=cfg["serving_model"]["dtype"])


def decode_reference(bench_dir: str):
    """The plain float32 reference (``embed``, ``layer``, ``head``,
    ``forward``) and what it is."""
    return (load_module("reference", "falcon_h1_ref", bench_dir),
            "chipbench/reference/falcon_h1_ref.py")


def layer_param_count(cfg: dict) -> int:
    """One layer: attention (q, k, v, o), the Mamba-2 mixer (in-projection
    over ``[z | x B C | dt]``, depthwise convolution and bias,
    out-projection, gated norm, ``A_log``, ``D``, ``dt_bias``), the
    SwiGLU MLP, two norms."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    d_ssm, heads = cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    conv = d_ssm + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    attention = d * q + 2 * d * kv + q * d
    mamba = (d * (d_ssm + conv + heads) + conv * cfg["mamba_d_conv"] + conv
             + d_ssm * d + d_ssm + 3 * heads)
    mlp = 3 * d * cfg["intermediate_size"]
    return attention + mamba + mlp + 2 * d


def vocab_param_count(cfg: dict) -> int:
    """The embedding and the untied head."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def param_count(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * layer_param_count(cfg)
            + vocab_param_count(cfg) + cfg["hidden_size"])


def ssm_state_bytes(cfg: dict, slots: int = 1, state_bytes: int = 4) -> int:
    """One layer's state-space state of ``slots`` slots."""
    return (slots * cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            * cfg["mamba_d_state"] * state_bytes)


def ssm_update_bytes(cfg: dict, slots: int = 1, state_bytes: int = 4) -> int:
    """HBM bytes one decode-step state update of ONE layer must move
    for ``slots`` active slots, whatever implements it: each slot's
    state read once and written once, its inputs (``x`` and ``dt`` per
    head, ``B`` and ``C`` per group) read and its ``y`` written, all
    counted at the state's width.  The state is 99.8% of it."""
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    small = heads * p * 2 + heads + 2 * cfg["mamba_n_groups"] \
        * cfg["mamba_d_state"]
    return 2 * ssm_state_bytes(cfg, slots, state_bytes) \
        + slots * small * state_bytes


def ssm_update_flops(cfg: dict, slots: int = 1) -> int:
    """Operations of the same call: per state element a decay multiply,
    the outer product's multiply and add, and the multiply and add of
    ``y``'s contraction: 5.  At 5 operations for 8 bytes the call is
    bound by bandwidth on every TPU."""
    return 5 * slots * cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        * cfg["mamba_d_state"]
