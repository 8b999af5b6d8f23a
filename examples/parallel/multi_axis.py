"""Every parallelism axis on a virtual 8-device CPU mesh.

Run anywhere (no TPU pod needed):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/parallel/multi_axis.py

Shows: dp×tp SPMD training (GSPMD collectives), GPipe pipeline
parallelism, ring-attention sequence parallelism, and Switch-MoE expert
parallelism — the menu docs/ARCHITECTURE.md maps to the reference's
kvstore/NCCL stack.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as onp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_lm
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import (SPMDTrainer, make_mesh, pipeline_forward,
                                ring_self_attention, switch_moe,
                                moe_expert_sharding)


def dp_tp_training():
    """Data × tensor parallel transformer training, one executable."""
    mesh = make_mesh({"dp": 4, "tp": 2})
    net = get_transformer_lm(64, units=32, num_layers=2, num_heads=4,
                             max_len=32)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 8), onp.int32)))
    for k, p in net.collect_params().items():
        if k.endswith("weight") and p.shape is not None \
                and len(p.shape) == 2:
            if "ffn1" in k or "qkv" in k:
                p.shard(P("tp", None))       # column parallel
            elif "ffn2" in k or "out_proj" in k:
                p.shard(P(None, "tp"))       # row parallel
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = SPMDTrainer(net, lambda o, l: ce(o.reshape((-1, 64)),
                                          l.reshape((-1,))),
                     optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3}, mesh=mesh)
    toks = onp.random.RandomState(0).randint(0, 64, (8, 17)).astype("int32")
    for step in range(3):
        loss = tr.step(toks[:, :16], toks[:, 1:].astype("float32"))
    print(f"dp4×tp2 transformer loss: {float(loss.asnumpy()):.4f}")


def gpipe():
    """4-stage GPipe over the pp axis; jax.grad runs the reverse
    pipeline automatically."""
    mesh = make_mesh({"dp": 2, "pp": 4})
    rng = onp.random.RandomState(1)
    stages = (jnp.asarray(rng.randn(4, 16, 16).astype("float32") * 0.3),
              jnp.asarray(rng.randn(4, 16).astype("float32") * 0.1))
    x = jnp.asarray(rng.randn(8, 16).astype("float32"))
    y = jnp.asarray(rng.randn(8, 16).astype("float32"))

    def stage_fn(p, h):
        w, b = p
        return jax.nn.relu(h @ w + b)

    def loss(p):
        out = pipeline_forward(stage_fn, p, x, mesh, n_microbatches=2)
        return jnp.mean((out - y) ** 2)

    val, grads = jax.jit(jax.value_and_grad(loss))(stages)
    print(f"pp4 gpipe loss: {float(val):.4f}")


def ring_sp():
    """Ring attention: the sequence axis sharded over 'sp'."""
    mesh = make_mesh({"sp": 8})
    q = jnp.asarray(onp.random.RandomState(2)
                    .randn(2, 4, 64, 16).astype("float32"))
    out = ring_self_attention(q, q, q, mesh, causal=True)
    print(f"sp8 ring attention out: {out.shape}")


def moe_ep():
    """Switch-MoE with experts sharded over 'ep' (all_to_all)."""
    mesh = make_mesh({"ep": 8})
    rng = onp.random.RandomState(3)
    H, E, F = 16, 16, 32
    params = (jnp.asarray(rng.randn(H, E).astype("float32") * 0.5),
              jnp.asarray(rng.randn(E, H, F).astype("float32") * 0.3),
              jnp.asarray(rng.randn(E, F).astype("float32") * 0.1),
              jnp.asarray(rng.randn(E, F, H).astype("float32") * 0.3),
              jnp.asarray(rng.randn(E, H).astype("float32") * 0.1))
    rep, *ex = moe_expert_sharding(mesh)
    params = tuple(jax.device_put(p, sh)
                   for p, sh in zip(params, [rep] + list(ex)))
    x = jnp.asarray(rng.randn(64, H).astype("float32"))
    y, aux = jax.jit(lambda ps: switch_moe(x, *ps,
                                           capacity_factor=2.0))(params)
    print(f"ep8 switch-moe out: {y.shape}, aux loss {float(aux):.4f}")


if __name__ == "__main__":
    dp_tp_training()
    gpipe()
    ring_sp()
    moe_ep()
    print("all parallel axes OK")
