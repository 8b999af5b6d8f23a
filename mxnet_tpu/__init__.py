"""mxnet_tpu — a TPU-native deep-learning framework with the capability
surface of Apache MXNet (see SURVEY.md at the repo root).

Import as ``import mxnet_tpu as mx``; the namespaces mirror the
reference: ``mx.nd``, ``mx.np``, ``mx.autograd``, ``mx.gluon``,
``mx.optimizer``, ``mx.kv``, ``mx.context``.
"""
__version__ = "0.1.0"

from .base import MXNetError
from .context import (Context, cpu, tpu, gpu, cpu_pinned, current_context,
                      num_gpus, num_tpus)
from . import base
from . import context
from . import engine
from . import autograd
from . import ops
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import random  # noqa: E402
from . import initializer  # noqa: E402
from . import optimizer  # noqa: E402
from . import lr_scheduler  # noqa: E402
from . import gluon  # noqa: E402
from . import kvstore  # noqa: E402
from . import kvstore as kv  # noqa: E402
from . import symbol  # noqa: E402
from . import symbol as sym  # noqa: E402
from . import numpy  # noqa: E402
from . import numpy as np  # noqa: E402
from . import numpy_extension as npx  # noqa: E402
from . import parallel  # noqa: E402
from . import profiler  # noqa: E402
from . import telemetry  # noqa: E402
from . import tracing  # noqa: E402
from . import serving  # noqa: E402
from . import embedding  # noqa: E402
from . import checkpoint  # noqa: E402
from . import data  # noqa: E402
from . import monitor  # noqa: E402
from . import amp  # noqa: E402
from . import test_utils  # noqa: E402
from . import util  # noqa: E402
from .util import is_np_array, set_np, reset_np  # noqa: E402
from . import runtime  # noqa: E402
from . import operator  # noqa: E402
from . import contrib  # noqa: E402
from . import callback  # noqa: E402
from . import visualization  # noqa: E402
from . import library  # noqa: E402
from . import rtc  # noqa: E402
from . import subgraph  # noqa: E402
from .visualization import print_summary, plot_network  # noqa: E402
from . import io  # noqa: E402
from . import image  # noqa: E402
from . import attribute  # noqa: E402
from .attribute import AttrScope  # noqa: E402
from . import name  # noqa: E402
from . import model  # noqa: E402
from . import error  # noqa: E402
from . import registry  # noqa: E402
from . import log  # noqa: E402
from . import executor  # noqa: E402

# large-tensor (int64) switch at import (parity: the reference's
# MXNET_USE_INT64_TENSOR_SIZE build flag; here a runtime env toggle)
if base.getenv_bool("MXNET_INT64_TENSOR_SIZE"):
    util.set_large_tensor(True)

# snapshot the built-in op set (ops registered by the package itself);
# later user/test/extension registrations are intentionally excluded
# from library-completeness contracts
ops.registry.freeze_builtin_snapshot()
