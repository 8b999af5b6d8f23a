#!/usr/bin/env python
"""Environment diagnosis report (parity: tools/diagnose.py — the
reference prints platform/python/pip/mxnet/network info for bug
reports; this prints the TPU-native equivalents: backend, devices,
feature flags, compile-cache state)."""
from __future__ import annotations

import os
import platform
import sys
import time


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_os():
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())
    try:
        print("cpu count    :", os.cpu_count())
    except Exception:
        pass


def check_framework():
    print("----------MXNet-TPU Info----------")
    t0 = time.time()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import mxnet_tpu as mx

    print("Version      :", getattr(mx, "__version__", "dev"))
    print("Import time  : %.1f s" % (time.time() - t0))
    from mxnet_tpu import runtime

    feats = [f.name for f in runtime.feature_list() if f.enabled] \
        if hasattr(runtime, "feature_list") else []
    print("Features     :", ", ".join(feats) or "(n/a)")


def check_backend(timeout_s=60):
    print("----------Backend (JAX/XLA) Info----------")
    import threading

    box = {}

    def probe():
        try:
            import jax

            box["version"] = jax.__version__
            box["devices"] = [str(d) for d in jax.devices()]
            box["backend"] = jax.default_backend()
        except Exception as e:      # pragma: no cover
            box["error"] = repr(e)

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout_s)
    if "devices" in box:
        print("jax          :", box["version"])
        print("backend      :", box["backend"])
        print("devices      :", box["devices"])
    elif "error" in box:
        print("backend error:", box["error"])
    else:
        print(f"backend      : INIT HANG (> {timeout_s}s — is another "
              f"process holding the chip?)")
        return
    from mxnet_tpu.base import use_compile_cache
    cache = use_compile_cache()
    if os.path.isdir(cache):
        n = len(os.listdir(cache))
        print(f"compile cache: {cache} ({n} entries)")


def main():
    check_python()
    check_os()
    try:
        check_framework()
    except Exception as e:      # keep going: backend info still prints
        print("framework import FAILED:", repr(e))
    check_backend()


if __name__ == "__main__":
    main()
