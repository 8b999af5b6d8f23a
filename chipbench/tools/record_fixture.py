#!/usr/bin/env python3
"""Record the small device trace kept in ``chipbench/tests/data``.

    python chipbench/tools/record_fixture.py <out.xplane.pb> [devices]

Three rounds of one jitted program on the chip: a matmul, a Pallas
kernel of this file's own (so one Mosaic custom call is known to be in
the trace) and, with ``devices`` above 1, an all-reduce over them.  The
tests work the busy union and the shares out by hand from its events.
"""
import os
import shutil
import sys
import tempfile


def main(argv) -> int:
    out = argv[1]
    n_dev = int(argv[2]) if len(argv) > 2 else 1
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from chipbench.harness import trace

    def add_one_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    def add_one(x):
        return pl.pallas_call(
            add_one_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            name="chipbench_add_one",
            interpret=jax.default_backend() != "tpu")(x)

    devices = jax.devices()[:n_dev]
    mesh = Mesh(devices, ("x",))

    def body(a):
        y = add_one(jnp.tanh(a @ a))
        if n_dev > 1:
            y = jax.lax.psum(y, "x")
        return y * 2.0

    if n_dev > 1:
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                                   out_specs=P("x"), check_vma=False))
        a = jax.device_put(jnp.ones((n_dev * 512, 512), jnp.float32),
                           NamedSharding(mesh, P("x")))
    else:
        fn = jax.jit(body)
        a = jnp.ones((512, 512), jnp.float32)
    fn(a).block_until_ready()
    log_dir = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    try:
        with trace.capture(log_dir):
            for _ in range(3):
                with trace.span("step_call"):
                    y = fn(a)
                with trace.span("wait"):
                    y.block_until_ready()
        path = trace.xplane_in(log_dir)
        shutil.copy(path, out)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
