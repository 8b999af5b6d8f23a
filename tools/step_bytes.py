"""What a training step moves: the compiled ``SPMDTrainer`` step of a
model-zoo net, by XLA's own count, with no chip.

Builds the net and its trainer on the CPU, lowers the trainer's step
function (``SPMDTrainer._make_step_fn``) on ``ShapeDtypeStruct``s for one
described TPU v5e with the machine's own libtpu, and prints the
executable's FLOPs, bytes accessed and temporaries, the entry
instructions with the most operand + result bytes, and the passes that
read one large tensor only to emit per-channel vectors (statistics of a
normalisation that did not fuse into the tensor's producer).

A step whose byte time (bytes accessed over the chip's HBM bandwidth) is
several times its FLOP time (FLOPs over the MXU's peak) is bound by
bandwidth; the instruction table says which tensors are touched how
often.  Nothing runs: no time, rate or utilisation comes out of this.

Usage:  JAX_PLATFORMS=cpu python tools/step_bytes.py \\
            [--model resnet50_v1] [--batch 256] [--image 224] \\
            [--dtype bfloat16] [--top 20] [--hlo FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# TPU v5e, one chip (Google Cloud documentation, "TPU v5e")
PEAK_FLOPS = 197e12
PEAK_BYTES = 819e9
LARGE = 1 << 20          # a tensor a pass over which is worth counting
# opcodes that name a buffer and move nothing
_NO_TRAFFIC = ("parameter", "get-tuple-element", "tuple", "bitcast")
# opcodes whose result is their first operand's data, renamed or moved
_MOVES = ("get-tuple-element", "bitcast", "copy", "copy-start", "copy-done",
          "slice-start", "slice-done", "custom-call")

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")


def _shapes(text):
    """``[(dtype, dims), ...]`` of every array type in a piece of HLO."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text) if dt in _ITEMSIZE]


def _nbytes(shapes):
    total = 0
    for dt, dims in shapes:
        n = _ITEMSIZE[dt]
        for d in dims:
            n *= d
        total += n
    return total


def computations(hlo_text):
    """``{name: [line, ...]}`` of every computation of an HLO module, and
    the entry computation's name."""
    comps, entry, name = {}, None, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                entry = name
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps, entry


def entry_instructions(hlo_text):
    """The entry computation's instructions: name, opcode, result shapes,
    operand names, the bytes of results and of operands, and whether the
    computation a fusion calls holds a convolution."""
    comps, entry = computations(hlo_text)
    rows, by_name = [], {}
    for line in comps.get(entry, ()):
        m = _INSTR.match(line)
        if not m:
            continue
        name, result, opcode, rest = m.groups()
        # operands end at the call's closing bracket; attributes follow
        depth, end = 1, len(rest)
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                end = i
                break
        operands = re.findall(r"%([\w.\-]+)", rest[:end])
        called = re.search(r"calls=%?([\w.\-]+)", rest[end:])
        body = "\n".join(comps.get(called.group(1), ())) if called else ""
        row = {"name": name, "opcode": opcode, "results": _shapes(result),
               "operands": operands,
               "conv": " convolution(" in body or opcode == "convolution"}
        rows.append(row)
        by_name[name] = row
    for row in rows:
        row["operand_shapes"] = [
            by_name[o]["results"] for o in row["operands"] if o in by_name]
        row["bytes"] = _nbytes(row["results"]) + sum(
            _nbytes(s) for s in row["operand_shapes"])
    return rows, by_name


def statistics_only_passes(rows, by_name):
    """Entry fusions that read exactly one large tensor, written by a
    convolution, and emit nothing but vectors: a normalisation's
    statistics taken in a pass of their own."""

    def producer(name):
        # through what only renames or moves a buffer (XLA prefetches a
        # tensor into VMEM in slices and joins them by a custom call)
        row = by_name[name]
        while row["opcode"] in _MOVES and row["operands"] \
                and row["operands"][0] in by_name:
            row = by_name[row["operands"][0]]
        return row

    found = []
    for row in rows:
        if row["opcode"] != "fusion" or not row["results"]:
            continue
        if any(len(dims) > 1 for _, dims in row["results"]):
            continue
        large = [o for o in row["operands"] if o in by_name
                 and _nbytes(by_name[o]["results"]) >= LARGE]
        if len(large) == 1 and producer(large[0])["conv"]:
            found.append(row["name"])
    return found


def describe_v5e():
    """Sharding on one described (not attached) TPU v5e device."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def compile_step(trainer, data_shape, label_shape, sharding):
    """The trainer's step function compiled for ``sharding``'s device, on
    shapes alone, with the donation the trainer itself asks for."""
    import jax
    import jax.numpy as jnp

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=sharding)

    step, _cell, _params = trainer._make_step_fn()
    p_arrays, opt_state = trainer._gather_state()
    like = lambda a: spec(a.shape, a.dtype)
    args = (spec((2,), "uint32"), spec((), "float32"), spec((), "float32"),
            [like(a) for a in p_arrays],
            [tuple(like(a) for a in st) for st in opt_state],
            spec(data_shape, "float32"), spec(label_shape, "float32"))
    return jax.jit(step, donate_argnums=(3, 4)).lower(*args).compile()


def report(compiled, top=20):
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    rows, by_name = entry_instructions(text)
    flops, nbytes = float(cost["flops"]), float(cost["bytes accessed"])
    out = {
        "flops": flops, "bytes_accessed": nbytes,
        "flop_time_ms": 1e3 * flops / PEAK_FLOPS,
        "byte_time_ms": 1e3 * nbytes / PEAK_BYTES,
        "temporaries_bytes": int(mem.temp_size_in_bytes),
        "arguments_bytes": int(mem.argument_size_in_bytes),
        "entry_instructions": len(rows),
        "entry_instructions_over_10MB": sum(
            1 for r in rows if r["opcode"] not in _NO_TRAFFIC and any(
                _nbytes([s]) >= 10e6
                for s in r["results"] + sum(r["operand_shapes"], []))),
        "entry_fusion_bytes": sum(r["bytes"] for r in rows
                                  if r["opcode"] == "fusion"),
        "statistics_only_passes": statistics_only_passes(rows, by_name),
    }
    heavy = sorted((r for r in rows if r["opcode"] not in _NO_TRAFFIC),
                   key=lambda r: -r["bytes"])[:top]
    out["heaviest"] = [
        {"name": r["name"], "opcode": r["opcode"], "mb": r["bytes"] / 1e6,
         "results": [f"{dt}{list(dims)}" for dt, dims in r["results"]],
         "operands": [f"{dt}{list(dims)}" for s in r["operand_shapes"]
                      for dt, dims in s if _nbytes([(dt, dims)]) >= LARGE]}
        for r in heavy]
    return out, text


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="resnet50_v1")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--hlo", help="write the optimized HLO here")
    args = ap.parse_args(argv)

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer

    net = get_model(args.model, classes=1000)
    net.initialize(init=mx.initializer.Xavier())
    # one small eager image finishes the deferred parameter shapes
    net(NDArray(onp.zeros((1, 3, 64, 64), onp.float32)))
    trainer = SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                          "wd": 1e-4}, dtype=args.dtype)
    compiled = compile_step(
        trainer, (args.batch, 3, args.image, args.image), (args.batch,),
        describe_v5e())
    out, text = report(compiled, args.top)
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    heaviest = out.pop("heaviest")
    print(json.dumps(out, indent=1))
    for r in heaviest:
        print(f"{r['mb']:9.1f} MB  {r['name']} ({r['opcode']}) -> "
              f"{', '.join(r['results'])} <= {', '.join(r['operands'])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
