"""Model family ``axk1``: what the ``decode_open_loop_model`` driver
needs to serve an A.X-K1 shaped configuration (latent attention, a
share of sigmoid-routed experts beside a shared one) through the
program's decode plane, found by the ``family`` in the configuration's
file: ``build_decode_model``, ``decode_reference``, ``param_count``, and
the bytes and operations of the kernel the family brought
(``latent_attention_bytes``/``_flops``), computed from the
configuration's shapes and from nothing the program says about itself.
"""
from __future__ import annotations

from chipbench.harness.cli import load_module


def build_decode_model(cfg: dict, seed: int):
    """The program's model of this configuration, weights drawn on the
    device from ``seed``.  A program without the class fails here, at
    once."""
    from mxnet_tpu.serving import AXK1
    return AXK1(cfg, seed=seed, dtype=cfg["serving_model"]["dtype"])


def decode_reference(bench_dir: str):
    """The plain float32 reference (``embed``, ``layer``, ``head``,
    ``forward``) and what it is."""
    return (load_module("reference", "axk1_ref", bench_dir),
            "chipbench/reference/axk1_ref.py")


def attention_param_count(cfg: dict) -> int:
    """Latent attention: the query's down- and up-projection, the
    latent's down-projection (with the shared rope key) and
    up-projection, the output projection."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (nope + rope)
            + d * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * h * (nope + v) + h * v * d)


def norm_param_count(cfg: dict) -> int:
    """A layer's four norms: two of the stream, the query's latent, the
    key-value latent."""
    return 2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def expert_param_count(cfg: dict) -> int:
    """One expert (routed or shared): gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_param_count(cfg: dict, dense: bool) -> int:
    """A leading dense layer, or an expert layer as held here: the
    router over all published experts, the shared expert, the
    ``n_routed_experts`` held."""
    d = cfg["hidden_size"]
    base = attention_param_count(cfg) + norm_param_count(cfg)
    if dense:
        return base + 3 * d * cfg["intermediate_size"]
    return (base + d * cfg["n_routed_experts_published"]
            + (cfg["n_shared_experts"] + cfg["n_routed_experts"])
            * expert_param_count(cfg))


def param_count(cfg: dict) -> int:
    dense = cfg["first_k_dense_replace"]
    return (dense * layer_param_count(cfg, True)
            + (cfg["num_hidden_layers"] - dense)
            * layer_param_count(cfg, False)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"]
            + cfg["hidden_size"])


def latent_row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """One token's row of ONE layer's latent cache as the model defines
    it: ``[c_kv | k_rope]`` (an implementation may pad it)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * cache_bytes


def latent_attention_bytes(cfg: dict, rows: float = 1,
                           cache_bytes: int = 2) -> float:
    """HBM bytes one decode-step attention of ONE layer must move for
    ``rows`` live rows in all (the sum of the decoding slots' context
    lengths), whatever implements it: each row read once, for the scores
    and the values alike.  The queries and outputs (``heads x (rank +
    rope + rank)`` a slot) are under a thousandth of it at a thousand
    rows a slot and are left out."""
    return rows * latent_row_bytes(cfg, cache_bytes)


def latent_attention_flops(cfg: dict, rows: float = 1) -> float:
    """Operations of the same call in the absorbed form: per row and
    head a score over ``rank + rope`` lanes and a value sum over
    ``rank``, a multiply and an add each.  Over the bytes it says which
    roofline is the kernel's: 121 operations a byte at 64 heads, under
    the v5e's 240, so ``latent_attention_roofline`` is a share of the
    bandwidth."""
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * rows * cfg["num_attention_heads"] * (2 * rank + rope)
