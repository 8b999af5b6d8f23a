"""Model family ``lfm2``: what the ``decode_open_loop_model`` driver
needs to serve an LFM2 mixture-of-experts configuration (gated short
convolutions and grouped-query attention in layers of their own, a
routed layer held whole) through the program's decode plane, found by
the ``family`` in the configuration's file: ``build_decode_model``,
``decode_reference``, ``param_count``, and the bytes and operations of
the paged attention kernel at this family's shapes
(``paged_attention_bytes``/``_flops``), computed from the
configuration's shapes and from nothing the program says about itself.
"""
from __future__ import annotations

from chipbench.harness.cli import load_module


def build_decode_model(cfg: dict, seed: int):
    """The program's model of this configuration, weights drawn on the
    device from ``seed``.  A program without the class fails here, at
    once."""
    from mxnet_tpu.serving import LFM2
    return LFM2(cfg, seed=seed, dtype=cfg["serving_model"]["dtype"])


def decode_reference(bench_dir: str):
    """The plain float32 reference (``embed``, ``layer``, ``head``,
    ``forward``) and what it is."""
    return (load_module("reference", "lfm2_ref", bench_dir),
            "chipbench/reference/lfm2_ref.py")


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def operator_param_count(cfg: dict, kind: str) -> int:
    """A layer's operator: the gated short convolution (in-projection
    to ``[B | C | u]``, the taps, the out-projection) or grouped-query
    attention (q, k, v, o and the two head norms)."""
    d = cfg["hidden_size"]
    if kind == "conv":
        return d * 3 * d + cfg["conv_L_cache"] * d + d * d
    hd = head_dim(cfg)
    kv = cfg["num_key_value_heads"] * hd
    return 2 * d * d + 2 * d * kv + 2 * hd


def layer_param_count(cfg: dict, index: int) -> int:
    """Layer ``index``: its operator, its two norms, and the dense MLP
    (below ``num_dense_layers``) or the router, its selection bias and
    every expert."""
    d = cfg["hidden_size"]
    base = operator_param_count(cfg, cfg["layer_types"][index]) + 2 * d
    if index < cfg["num_dense_layers"]:
        return base + 3 * d * cfg["intermediate_size"]
    experts = cfg["num_experts"]
    return (base + d * experts + (experts if cfg["use_expert_bias"] else 0)
            + experts * 3 * d * cfg["moe_intermediate_size"])


def param_count(cfg: dict) -> int:
    """The embedding counted once (the head is tied to it) and the
    final norm beside the layers."""
    return (sum(layer_param_count(cfg, i)
                for i in range(cfg["num_hidden_layers"]))
            + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def kv_row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """One token's K and V rows of ONE attention layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * cache_bytes


def paged_attention_bytes(cfg: dict, rows: float = 1,
                          cache_bytes: int = 2) -> float:
    """HBM bytes one decode-step attention of ONE layer must move for
    ``rows`` live rows in all (the sum of the decoding slots' context
    lengths), whatever implements it: each row of K and of V read once.
    The queries and outputs (``heads x head_dim`` a slot each way) are a
    thousandth of it at a thousand rows a slot and are left out."""
    return rows * kv_row_bytes(cfg, cache_bytes)


def paged_attention_flops(cfg: dict, rows: float = 1) -> float:
    """Operations of the same call: per row and query head a score and a
    value sum over ``head_dim`` lanes, a multiply and an add each.  Over
    the bytes it says which roofline is the kernel's: 4 operations a
    byte at four query heads a K/V head, under the v5e's 240, so
    ``paged_attention_roofline`` is a share of the bandwidth."""
    return 4.0 * rows * cfg["num_attention_heads"] * head_dim(cfg)
