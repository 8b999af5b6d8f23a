"""3D-parallel training recipe: pp(1F1B) x dp x tp in one jitted step.

Run anywhere (no TPU pod needed — virtual 8-device CPU mesh):

    python examples/parallel/pipeline_1f1b_3d.py

The composition a real v5p job runs, end to end as USER code:

* true 1F1B pipeline parallelism (`pipeline_value_and_grad_1f1b`):
  per-microbatch forward/backward interleaving, activation memory
  bounded by the stage count — deep microbatching (M=8 > S=2) works;
* tensor parallelism INSIDE each stage (column+row parallel FFN with
  the Megatron f-operator), declared via `param_specs`;
* data parallelism over the batch axis (grads/loss dp-averaged by the
  pipeline helper);
* a sparse-grad embedding chained in FRONT of the pipeline via
  `return_input_grad` — only (ids, values) rows are scattered;
* bf16 AMP: float32 master weights, bfloat16 compute;
* ZeRO-1: SGD-momentum state sharded over dp (GSPMD inserts the
  reduce-scatter/all-gather around the optimizer update).

On a real pod, replace the CPU-mesh setup with the pod mesh — the
training step itself is unchanged.
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
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from mxnet_tpu.parallel import make_mesh, pipeline_value_and_grad_1f1b

PP, DP, TP, M = 2, 2, 2, 8               # mesh + microbatch count
VOCAB, HID, FFN, SEQ = 64, 16, 32, 8
LR, LR_EMB, MU = 0.05, 0.1, 0.9
mesh = make_mesh({"pp": PP, "dp": DP, "tp": TP})


def tp_enter(v):
    """Megatron's f operator: identity fwd, psum('tp') bwd."""
    @jax.custom_vjp
    def f(u):
        return u
    f.defvjp(lambda u: (u, None), lambda _, g: (lax.psum(g, "tp"),))
    return f(v)


def stage_fn(params, x):
    w1, w2 = params                       # f32 masters, bf16 compute
    h = jax.nn.relu(tp_enter(x) @ w1.astype(jnp.bfloat16))
    return x + lax.psum(h @ w2.astype(jnp.bfloat16), "tp")


def loss_fn(y, t):
    return jnp.mean((y.astype(jnp.float32) - t) ** 2)


def train_step(emb, W1, W2, m1, m2, toks, tgt):
    x = emb.astype(jnp.bfloat16)[toks]    # (B, SEQ, HID) bf16
    loss, (g1, g2), dx = pipeline_value_and_grad_1f1b(
        stage_fn, loss_fn, (W1, W2), x, tgt, mesh, n_microbatches=M,
        param_specs=(P("pp", None, "tp"), P("pp", "tp", None)),
        return_input_grad=True)
    # sparse embedding update: scatter only the touched rows
    new_emb = emb.at[toks.reshape(-1)].add(
        -LR_EMB * dx.reshape(-1, HID).astype(jnp.float32))
    nm1 = MU * m1 + g1.astype(jnp.float32)
    nm2 = MU * m2 + g2.astype(jnp.float32)
    return loss, new_emb, W1 - LR * nm1, W2 - LR * nm2, nm1, nm2


def main():
    rng = onp.random.RandomState(0)
    emb = jnp.asarray(rng.randn(VOCAB, HID).astype("float32") * .3)
    W1 = jnp.asarray(rng.randn(PP, HID, FFN).astype("float32") * .3)
    W2 = jnp.asarray(rng.randn(PP, FFN, HID).astype("float32") * .3)
    zshard = NamedSharding(mesh, P("pp", "dp"))     # ZeRO-1 state
    m1 = jax.device_put(jnp.zeros_like(W1), zshard)
    m2 = jax.device_put(jnp.zeros_like(W2), zshard)
    B = M * 2 * DP
    toks = jnp.asarray(rng.randint(0, VOCAB, (B, SEQ)).astype("int32"))
    tgt = jnp.asarray(rng.randn(B, SEQ, HID).astype("float32") * .3)

    step = jax.jit(train_step, out_shardings=(
        None, None, None, None, zshard, zshard))
    state = (emb, W1, W2, m1, m2)
    first = None
    for it in range(20):
        loss, *state = step(*state, toks, tgt)
        if first is None:
            first = float(loss)
        if it % 5 == 0:
            print(f"step {it:2d}  loss {float(loss):.4f}")
    print(f"loss {first:.4f} -> {float(loss):.4f}")
    assert float(loss) < first, "training did not reduce the loss"
    assert "dp" in tuple(state[3].sharding.spec or ()), \
        "ZeRO-1 momentum lost its dp sharding"
    print("3D-parallel (pp x dp x tp) 1F1B training: OK")


if __name__ == "__main__":
    main()
