"""Model family ``gpt2``: what the ``train_steps`` driver needs to train a
GPT-2 shaped configuration through the program's ``TransformerLM``, found
by the ``family`` in the configuration's file.  A new family is a new
file here: ``build_net``, ``make_batch``, ``reference``, ``classes`` and
``flops_per_item``."""
from __future__ import annotations

import numpy as onp

from chipbench.harness.cli import load_module


def build_net(cfg: dict, jb: dict):
    """The net, built but not initialised, and a tiny batch whose eager
    forward finishes the deferred parameter init."""
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
    net = TransformerLM(
        cfg["vocab_size"], units=cfg["n_embd"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], max_len=cfg["n_positions"],
        ffn_ratio=cfg["n_inner"] // cfg["n_embd"],
        dropout=cfg["resid_pdrop"], tie_weights=cfg["tie_word_embeddings"],
        use_flash=jb["use_flash"])
    return net, onp.zeros((1, 8), onp.float32)


def make_batch(cfg: dict, jb: dict, seed: int):
    """One batch on the device from the seed: ``(data, label, items)``;
    the label is the data shifted by one token."""
    import jax
    import jax.numpy as jnp
    k1, _ = jax.random.split(jax.random.PRNGKey(seed))
    toks = jax.random.randint(k1, (jb["batch"], jb["seq_len"] + 1), 0,
                              cfg["vocab_size"])
    return (toks[:, :-1].astype(jnp.float32),
            toks[:, 1:].astype(jnp.float32), jb["batch"] * jb["seq_len"])


def reference(net, cfg: dict, data, bench_dir: str):
    """Logits of ``data`` from the plain float32 reference fed the net's
    own parameters: ``(logits, what it was)``."""
    import jax
    import jax.numpy as jnp
    ref_mod = load_module("reference", "gpt2_ref", bench_dir)
    params = {k: p.data()._data for k, p in net.collect_params().items()}
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_mod.forward,
                      static_argnames=("n_head", "n_layer"))(
            params, data.astype(jnp.int32), n_head=cfg["n_head"],
            n_layer=cfg["n_layer"])
    return ref, "chipbench/reference/gpt2_ref.py"


def classes(cfg: dict) -> int:
    return cfg["vocab_size"]


def param_count(cfg: dict) -> int:
    """Parameters of a GPT-2 shaped LM with a tied head: token and
    position embeddings, per layer 12 d^2 + 13 d, the final norm."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * d
    per_layer = (3 * d * d + 3 * d) + (d * d + d) \
        + (d * inner + inner) + (inner * d + d) + 4 * d
    return cfg["vocab_size"] * d + cfg["n_positions"] * d \
        + layers * per_layer + 2 * d


def flops_per_item(cfg: dict, cell: dict) -> float:
    """Forward + backward model FLOPs of one token (the MFU convention:
    recomputation, padding and whatever the compiler adds do not count):
    6 P for the matmuls (the tied head's 2 V d is in P once, as the
    embedding) plus 12 L d S for attention scores and values."""
    return 6.0 * param_count(cfg) \
        + 12.0 * cfg["n_layer"] * cfg["n_embd"] * cell["job"]["seq_len"]
