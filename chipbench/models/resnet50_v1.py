"""Model family ``resnet50_v1``: what the ``train_steps`` driver needs to
train the program's model-zoo ResNet (``vision.get_model``); see
``gpt2.py`` for the interface."""
from __future__ import annotations

import numpy as onp

# ResNet-50 v1 at 224x224: the conventional 4.089 GFLOP forward per
# image; forward + backward is taken as three times the forward.
RESNET50_FWD_FLOPS_224 = 4.089e9


def _shape(cfg: dict, n: int):
    return (n, cfg["image_channels"], cfg["image_size"], cfg["image_size"])


def build_net(cfg: dict, jb: dict):
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    net = get_model(cfg["model_zoo_name"], classes=cfg["num_classes"])
    return net, onp.zeros(_shape(cfg, 1), onp.float32)


def make_batch(cfg: dict, jb: dict, seed: int):
    import jax
    import jax.numpy as jnp
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    data = jax.random.normal(k1, _shape(cfg, jb["batch"]), jnp.float32)
    label = jax.random.randint(k2, (jb["batch"],), 0,
                               cfg["num_classes"]).astype(jnp.float32)
    return data, label, jb["batch"]


def reference(net, cfg: dict, data, bench_dir: str):
    """No plain ResNet-50 reference yet (PERF.md, Open questions): the
    program's own eager float32 Gluon forward stands in."""
    import jax

    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray import NDArray
    with autograd.pause(train_mode=False), \
            jax.default_matmul_precision("highest"):
        ref = net(NDArray(data))._data
    return ref, "the eager float32 Gluon forward"


def classes(cfg: dict) -> int:
    return cfg["num_classes"]


def flops_per_item(cfg: dict, cell: dict) -> float:
    """Forward + backward model FLOPs of one image."""
    return 3 * RESNET50_FWD_FLOPS_224 * (cfg["image_size"] / 224.0) ** 2
