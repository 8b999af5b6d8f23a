"""The bench.py parity grids must stay constructible: a model-zoo
rename or shape regression should fail HERE on CPU, not mid-bench on
a chip."""
import numpy as onp
import pytest

# the REAL grids from bench.py (single source of truth), with
# full-size hw swapped for toy inputs where the arch allows
import bench

TOY_HW = {"resnet152_v1": 32, "vgg16": 32, "alexnet": 32,
          "inceptionv3": 299}   # inception needs >= 299
NAMES = sorted({g[0] for g in bench.TRAIN_PARITY_GRID}
               | {g[0] for g in bench.INFER_PARITY_GRID})


@pytest.mark.parametrize("name", NAMES)
def test_parity_grid_models_construct(name):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    from mxnet_tpu.ndarray import NDArray

    hw = TOY_HW.get(name, 224)
    net = get_model(name, classes=1000)
    net.initialize(init=mx.initializer.Xavier())
    out = net(NDArray(onp.zeros((1, 3, hw, hw), "float32")))
    assert out.shape == (1, 1000), (name, out.shape)
