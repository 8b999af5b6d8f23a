"""ctypes bindings for the native IO runtime (libmxtpu_io.so).

Parity: the reference's native data layer — dmlc recordio + the
threaded ImageRecordIter pipeline (src/io/iter_image_recordio_2.cc:887)
— implemented in C++ (src_native/) and consumed here the way the
reference's Python consumes libmxnet via ctypes (python/mxnet/base.py).

The library is git-ignored, so what is loaded must come from the
tracked sources: the first use in a process runs `make -C src_native`,
which rebuilds when a source or the Makefile is newer than the binary
and is a no-op otherwise.  A `make` that cannot run raises MXNetError
rather than loading whatever binary is lying there; callers without a
toolchain catch it and take the pure-Python recordio path.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as onp

from ..base import MXNetError

_LIB: Optional[ctypes.CDLL] = None
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_LIB_PATH = os.path.join(_REPO_ROOT, "mxnet_tpu", "lib", "libmxtpu_io.so")
_SRC_DIR = os.path.join(_REPO_ROOT, "src_native")


def _build():
    if not os.path.isdir(_SRC_DIR):
        raise MXNetError("native IO sources not found")
    try:
        subprocess.run(["make", "-C", _SRC_DIR], check=True,
                       capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, FileNotFoundError,
            subprocess.TimeoutExpired) as e:
        err = (getattr(e, "stderr", None) or b"").decode(errors="replace")
        raise MXNetError(
            f"building libmxtpu_io failed: {e}\n{err[-2000:]}") from e


def get_lib() -> ctypes.CDLL:
    """Bring the native IO library up to date with its sources and
    load it."""
    global _LIB
    if _LIB is not None:
        return _LIB
    _build()
    lib = ctypes.CDLL(_LIB_PATH)
    # writer
    lib.mxtpu_rec_writer_open.restype = ctypes.c_void_p
    lib.mxtpu_rec_writer_open.argtypes = [ctypes.c_char_p]
    lib.mxtpu_rec_writer_write.restype = ctypes.c_int64
    lib.mxtpu_rec_writer_write.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64]
    lib.mxtpu_rec_writer_close.argtypes = [ctypes.c_void_p]
    # reader
    lib.mxtpu_rec_reader_open.restype = ctypes.c_void_p
    lib.mxtpu_rec_reader_open.argtypes = [ctypes.c_char_p]
    lib.mxtpu_rec_reader_next.restype = ctypes.c_int
    lib.mxtpu_rec_reader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_int64)]
    lib.mxtpu_rec_reader_seek.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.mxtpu_rec_reader_tell.restype = ctypes.c_int64
    lib.mxtpu_rec_reader_tell.argtypes = [ctypes.c_void_p]
    lib.mxtpu_rec_reader_close.argtypes = [ctypes.c_void_p]
    # pipeline
    lib.mxtpu_pipe_create.restype = ctypes.c_void_p
    lib.mxtpu_pipe_create.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float), ctypes.c_uint64, ctypes.c_int,
        ctypes.c_int]
    lib.mxtpu_pipe_num_records.restype = ctypes.c_int64
    lib.mxtpu_pipe_num_records.argtypes = [ctypes.c_void_p]
    lib.mxtpu_pipe_next.restype = ctypes.c_int
    lib.mxtpu_pipe_next.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_float),
                                    ctypes.POINTER(ctypes.c_float)]
    lib.mxtpu_pipe_reset.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.mxtpu_pipe_destroy.argtypes = [ctypes.c_void_p]
    _LIB = lib
    return lib


def available() -> bool:
    try:
        get_lib()
        return True
    except (MXNetError, OSError):
        return False


class NativeRecordWriter:
    """Sequential dmlc-format record writer (native)."""

    def __init__(self, path: str):
        self._lib = get_lib()
        self._h = self._lib.mxtpu_rec_writer_open(path.encode())
        if not self._h:
            raise MXNetError(f"cannot open {path} for writing")

    def write(self, buf: bytes) -> int:
        arr = (ctypes.c_uint8 * len(buf)).from_buffer_copy(buf)
        pos = self._lib.mxtpu_rec_writer_write(self._h, arr, len(buf))
        if pos < 0:
            raise MXNetError("record write failed")
        return pos

    def close(self):
        if self._h:
            self._lib.mxtpu_rec_writer_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NativeRecordReader:
    """Sequential dmlc-format record reader (native)."""

    def __init__(self, path: str):
        self._lib = get_lib()
        self._h = self._lib.mxtpu_rec_reader_open(path.encode())
        if not self._h:
            raise MXNetError(f"cannot open {path}")

    def read(self) -> Optional[bytes]:
        out = ctypes.POINTER(ctypes.c_uint8)()
        length = ctypes.c_int64(0)
        status = self._lib.mxtpu_rec_reader_next(
            self._h, ctypes.byref(out), ctypes.byref(length))
        if status == 0:
            return None
        if status < 0:
            raise MXNetError(f"corrupt record stream (code {status})")
        return ctypes.string_at(out, length.value) if length.value else b""

    def seek(self, offset: int):
        if self._lib.mxtpu_rec_reader_seek(self._h, offset) != 0:
            raise MXNetError("seek failed")

    def tell(self) -> int:
        return self._lib.mxtpu_rec_reader_tell(self._h)

    def close(self):
        if self._h:
            self._lib.mxtpu_rec_reader_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class ImageRecordIter:
    """Threaded native image pipeline (parity: ImageRecordIter,
    src/io/iter_image_recordio_2.cc:887-940).

    Yields DataBatch with NCHW float32 data, like the reference (the
    native pipeline fills NHWC — TPU's preferred layout — and this
    wrapper transposes unless ``layout="NHWC"``).
    """

    def __init__(self, path_imgrec: str, batch_size: int,
                 data_shape=(3, 224, 224), label_width: int = 1,
                 shuffle: bool = False, rand_mirror: bool = False,
                 rand_crop: bool = False, mean_r: float = 0.0,
                 mean_g: float = 0.0, mean_b: float = 0.0,
                 std_r: float = 1.0, std_g: float = 1.0, std_b: float = 1.0,
                 seed: int = 0, preprocess_threads: int = 4,
                 prefetch_buffer: int = 4, layout: str = "NCHW",
                 round_batch: bool = True, **kwargs):
        self._lib = get_lib()
        c, h, w = data_shape
        mean = (ctypes.c_float * 3)(mean_r, mean_g, mean_b)
        std = (ctypes.c_float * 3)(std_r, std_g, std_b)
        self._h = self._lib.mxtpu_pipe_create(
            path_imgrec.encode(), batch_size, h, w, c, label_width,
            int(shuffle), int(rand_mirror), int(rand_crop), mean, std,
            seed, preprocess_threads, prefetch_buffer)
        if not self._h:
            raise MXNetError(f"cannot open record file {path_imgrec}")
        self.batch_size = batch_size
        self.data_shape = data_shape
        self.label_width = label_width
        self.layout = layout
        self._threads = preprocess_threads
        self._data_buf = onp.empty((batch_size, h, w, c), onp.float32)
        self._label_buf = onp.empty((batch_size, label_width), onp.float32)

    @property
    def num_records(self) -> int:
        return int(self._lib.mxtpu_pipe_num_records(self._h))

    def __iter__(self):
        return self

    def __next__(self):
        from .io import DataBatch
        from ..ndarray import NDArray
        n = self._lib.mxtpu_pipe_next(
            self._h,
            self._data_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._label_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if n <= 0:
            raise StopIteration
        data = self._data_buf
        if self.layout == "NCHW":
            data = onp.transpose(data, (0, 3, 1, 2))
        label = self._label_buf[:, 0] if self.label_width == 1 \
            else self._label_buf
        return DataBatch(data=[NDArray(data.copy())],
                         label=[NDArray(label.copy())],
                         pad=self.batch_size - n)

    def next(self):
        return self.__next__()

    def reset(self):
        self._lib.mxtpu_pipe_reset(self._h, self._threads)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxtpu_pipe_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class ImageRecordUInt8Iter(ImageRecordIter):
    """ImageRecordIter yielding raw uint8 pixels (parity:
    ImageRecordUInt8Iter, iter_image_recordio_2.cc:908): no
    mean/std normalization, data dtype uint8 — the int8/uint8
    quantized-inference input path."""

    _out_dtype = onp.uint8
    _offset = 0

    def __init__(self, *args, **kwargs):
        for k in ("mean_r", "mean_g", "mean_b"):
            kwargs.pop(k, None)
        for k in ("std_r", "std_g", "std_b"):
            kwargs.pop(k, None)
        super().__init__(*args, **kwargs)

    def __next__(self):
        batch = super().__next__()
        from ..ndarray import NDArray
        batch.data = [NDArray((onp.clip(d.asnumpy(), 0, 255)
                               + self._offset).astype(self._out_dtype))
                      for d in batch.data]
        return batch


class ImageRecordInt8Iter(ImageRecordUInt8Iter):
    """Signed-int8 variant (parity: ImageRecordInt8Iter,
    iter_image_recordio_2.cc:925): pixels shifted into [-128, 127]."""

    _out_dtype = onp.int8
    _offset = -128
