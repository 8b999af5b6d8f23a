"""Argument parsing, name resolution, the device gate and the final line."""
from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")
CACHE_DIR = os.path.join(REPO_DIR, ".jax_cache")


@dataclasses.dataclass
class Job:
    """What a driver is handed: the cell, its configuration and traffic
    mix as read from their files, and the run's arguments."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    watch: Any                  # harness.compile_watch.CompileWatch
    out_dir: str
    bench_dir: str = BENCH_DIR

    def size(self, section: dict) -> dict:
        """``section`` with its ``rehearsal`` overrides merged in when
        the run is a rehearsal: tiny sizes for the CPU self-tests."""
        merged = {k: v for k, v in section.items() if k != "rehearsal"}
        if self.rehearse:
            merged.update(section.get("rehearsal", {}))
        return merged


def load_named(kind: str, name: str, bench_dir: str = BENCH_DIR) -> dict:
    """``<bench_dir>/<kind>/<name>.json``, found by name alone."""
    path = os.path.join(bench_dir, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no {kind[:-1] if kind.endswith('s') else kind} named "
            f"{name!r}: {path} does not exist")
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` as a module, found by name and
    executed once."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def layer_metrics(obs: dict, names, bench_dir: str = BENCH_DIR) -> dict:
    """Each named per-layer metric through its reader.  A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for name in names:
        spec = load_named("layer_metrics", name, bench_dir)
        reader = load_module("readers", spec["reader"], bench_dir)
        value = reader.read(obs, **spec.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def device_report(chips: int, rehearse: bool) -> Dict[str, Any]:
    """The device as JAX reports it, or SystemExit(2) with no result
    line when it is not what the cell needs."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    problem = None
    if dev["platform"] != "tpu" and not rehearse:
        problem = (f"JAX found platform {dev['platform']!r}, not a TPU: "
                   f"nothing ran")
    elif len(devs) < chips:
        problem = f"the cell needs {chips} chip(s), JAX found {len(devs)}"
    if problem:
        print(f"chipbench: {problem}", file=sys.stderr)
        raise SystemExit(2)
    if rehearse:
        dev["rehearsal"] = True
    return dev


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes on the fullest of the chips the cell used, from the
    runtime's own statistics.  On this runtime ``peak_bytes_in_use``
    counts buffers only; what a loaded program reserves for its
    temporaries is ``peak_bytes_reserved`` (ResNet-50's step: 0.64 GB of
    buffers, 8.45 GB reserved; PERF.md, PR 24).  Their sum is the peak
    where both peaks fall together and an upper bound where not."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    say("memory", stats=stats[0])
    return max(int(st.get("peak_bytes_in_use", 0))
               + int(st.get("peak_bytes_reserved", 0)) for st in stats)


def say(tag: str, **fields) -> None:
    """One JSON line of detail on standard output, before the last."""
    print(json.dumps({"chipbench": tag, **fields}), flush=True)


def prepare(workload: str, seed: int, seconds: float, trace: bool,
            rehearse: bool):
    """Resolve a cell by name, place the compile cache, gate on the
    device: ``(job, driver module, device report)``."""
    cell = load_named("workloads", workload)
    config = load_named("configs", cell["config"])
    traffic = load_named("traffic", cell["traffic"])
    driver = load_module("drivers", cell["driver"])

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The compile cache lives at one fixed path inside the checkout,
    # whatever the machine's environment says: two checkouts share
    # nothing, and a second run finds every program of the first.  No
    # size limit: a machine that sets JAX_COMPILATION_CACHE_MAX_SIZE (the
    # chip tool's: 192 MiB) evicts a 0.23 GiB step as soon as it is
    # written (PERF.md, PR 24); a jax.config setting wins over the
    # environment.  The program's own helper takes the directory it is
    # given here.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)

    from mxnet_tpu.base import use_compile_cache

    from .compile_watch import CompileWatch
    cache_dir = use_compile_cache()
    # store every program: by default only compiles of a second or more
    # are kept, and a warm run would pay the hundreds of small ones again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    watch = CompileWatch()
    device = device_report(cell["chips"], rehearse)
    os.makedirs(OUT_DIR, exist_ok=True)
    say("start", workload=workload, seed=seed, seconds=seconds,
        trace=int(trace), device=device, compile_cache=cache_dir)
    job = Job(cell=cell, config=config, traffic=traffic, seed=seed,
              seconds=seconds, trace=trace, rehearse=rehearse, watch=watch,
              out_dir=OUT_DIR)
    return job, driver, device


def main(argv=None, t_process_start: Optional[float] = None) -> int:
    if t_process_start is None:
        t_process_start = time.monotonic()
    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: labelled, never correct")
    args = ap.parse_args(argv)

    job, driver, device = prepare(args.workload, args.seed, args.seconds,
                                  bool(args.trace), args.rehearse)
    cell = job.cell
    obs = driver.run(job)

    setup_s = obs["t_window_start"] - t_process_start
    e2e = {"setup_s": {"value": setup_s, "unit": "s"}, **obs["end_to_end"]}
    obs["end_to_end"] = e2e
    obs["device"] = device
    obs["chips"] = cell["chips"]
    obs["cell"], obs["config"] = cell, job.config
    counters = obs.setdefault("counters", {})
    device["memory_peak_bytes"] = memory_peak_bytes(cell["chips"])
    counters["peak_hbm_bytes"] = device["memory_peak_bytes"]
    per_layer = layer_metrics(obs, cell["per_layer"])
    say("setup", setup_s=setup_s, compile=obs.get("setup_compile"))
    say("detail", end_to_end=e2e, per_layer=per_layer,
        checks=obs.get("checks"), notes=obs.get("notes"))

    line = {"correct": bool(obs["correct"]) and not args.rehearse,
            "attempted": int(obs["attempted"]),
            "failed": int(obs["failed"])}
    if args.trace:
        line["metrics"] = per_layer
        summary = obs.get("trace")
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = {"device_ops": summary["device_ops"][:10],
                                 "idle_gaps": summary["idle_gaps"][:10]}
    else:
        missing = [n for n in cell["end_to_end"] if n not in e2e]
        if missing:
            raise RuntimeError(f"the driver measured no {missing}")
        line["metrics"] = {n: e2e[n] for n in cell["end_to_end"]}
    line["device"] = device
    print(json.dumps(line), flush=True)
    # a rehearsal can never be read as a chip run; a real run that got
    # this far says what it found on its last line and exits 0
    return 1 if args.rehearse else 0
