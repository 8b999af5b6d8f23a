"""Operator library.

TPU-native re-expression of the reference's ``src/operator/`` (NNVM op
registry + mshadow/cuDNN kernels): every op is a pure jax function
registered under its MXNet name; lowering/fusion is XLA's job, autograd
comes from ``jax.vjp`` via the tape in :mod:`mxnet_tpu.autograd`.
"""
from . import registry
from .registry import register, get, list_ops, invoke, apply_jax
from . import tensor  # noqa: F401  (registers ops on import)
from . import nn      # noqa: F401
from . import random  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import attention  # noqa: F401
from . import vision  # noqa: F401
from . import quantization  # noqa: F401
from . import npi     # noqa: F401
from . import linalg  # noqa: F401
from . import legacy  # noqa: F401
from . import image   # noqa: F401
from . import rnn     # noqa: F401
from . import contrib_extra  # noqa: F401
from . import layernorm_residual  # noqa: F401
from . import rope    # noqa: F401
from . import paged_attention  # noqa: F401
from . import ssm      # noqa: F401

__all__ = ["register", "get", "list_ops", "invoke", "apply_jax"]
