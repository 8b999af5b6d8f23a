"""Base types shared by every layer of the framework.

TPU-native re-expression of the reference's base layer
(``include/mxnet/base.h``, ``include/mxnet/tuple.h``): dtype registry,
shape helpers, environment-variable config access, and the package-wide
error type.  There is no mshadow here — XLA owns tensor layout — so the
"base types" reduce to the metadata the Python runtime needs.
"""
from __future__ import annotations

import os
from typing import Any, Sequence, Tuple

import numpy as onp

__all__ = [
    "MXNetError",
    "DTYPE_NAMES",
    "np_dtype",
    "dtype_name",
    "check_shape",
    "getenv",
    "getenv_bool",
    "getenv_int",
    "use_compile_cache",
]


class MXNetError(RuntimeError):
    """Error raised by the framework runtime (parity: dmlc::Error)."""


# dtype registry (reference: mshadow type enum used by TBlob).  We keep the
# names MXNet exposes in Python plus the TPU-first bfloat16.
import ml_dtypes as _ml_dtypes  # ships with jax

DTYPE_NAMES = {
    "float32": onp.dtype("float32"),
    "float64": onp.dtype("float64"),
    "float16": onp.dtype("float16"),
    "bfloat16": onp.dtype(_ml_dtypes.bfloat16),
    "uint8": onp.dtype("uint8"),
    "int8": onp.dtype("int8"),
    "int32": onp.dtype("int32"),
    "int64": onp.dtype("int64"),
    "bool": onp.dtype("bool"),
}

_CANONICAL = {v: k for k, v in DTYPE_NAMES.items()}


def np_dtype(dtype: Any) -> onp.dtype:
    """Resolve a user-supplied dtype (str, numpy dtype, python type) to numpy."""
    if dtype is None:
        return DTYPE_NAMES["float32"]
    if isinstance(dtype, str):
        if dtype not in DTYPE_NAMES:
            raise MXNetError(f"unknown dtype {dtype!r}")
        return DTYPE_NAMES[dtype]
    return onp.dtype(dtype)


def dtype_name(dtype: Any) -> str:
    d = onp.dtype(dtype)
    if d in _CANONICAL:
        return _CANONICAL[d]
    return d.name


def check_shape(shape: Sequence[int] | int) -> Tuple[int, ...]:
    """Normalize a shape argument to a tuple of ints (scalar int allowed)."""
    if isinstance(shape, (int, onp.integer)):
        return (int(shape),)
    return tuple(int(s) for s in shape)


# -- env-var config (reference: dmlc::GetEnv at use sites; ~103 MXNET_* vars) --

def getenv(name: str, default: str | None = None) -> str | None:
    return os.environ.get(name, default)


def getenv_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


def getenv_int(name: str, default: int = 0) -> int:
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return int(v)
    except ValueError:
        return default


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    A caller that sets ``JAX_COMPILATION_CACHE_DIR`` owns the placement
    and nothing is touched.  Otherwise the cache goes to ``.jax_cache``
    beside the package: the directory is part of every cache key, so it
    must be the same on every run from one checkout.  No other code
    sets ``jax_compilation_cache_dir``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
