"""``--rehearse`` of each driver ends in a last line with exactly the
contract's keys, and a cell, a configuration, a driver, a traffic mix and
a per-layer metric can each be added as new files, with no edit to a
file that is there."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from chipbench.harness.cli import BENCH_DIR, REPO_DIR

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(run_py, *args, extra_path=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([*extra_path, REPO_DIR]))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, run_py, *args], env=env, cwd=REPO_DIR,
        capture_output=True, text=True, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


def _cells():
    d = os.path.join(BENCH_DIR, "workloads")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


def _one_chip(name):
    with open(os.path.join(BENCH_DIR, "workloads", f"{name}.json")) as f:
        return json.load(f)["chips"] == 1


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [c for c in _cells() if _one_chip(c)])
def test_rehearsal_ends_in_the_contracts_line(cell, trace):
    proc, lines = _run(os.path.join(BENCH_DIR, "run.py"), "--workload", cell,
                       "--seed", "3", "--seconds", "2", "--trace", str(trace),
                       "--rehearse")
    assert proc.returncode == 1, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert set(last) == LINE_KEYS
    assert last["correct"] is False          # never read as a chip run
    assert last["device"]["rehearsal"] is True
    assert DEVICE_KEYS <= set(last["device"])
    assert last["attempted"] > 0 and last["failed"] == 0
    with open(os.path.join(BENCH_DIR, "workloads", f"{cell}.json")) as f:
        body = json.load(f)
    if trace == 0:
        assert set(last["metrics"]) == set(body["end_to_end"])
    else:
        # the CPU has no device plane: what reads the trace is left out
        assert set(last["metrics"]) <= set(body["per_layer"])
        assert "window_compiles" in last["metrics"]
        assert "model_flops_util" not in last["metrics"]
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    # what decides ``correct`` on the chip held in the rehearsal too
    detail = [json.loads(ln) for ln in lines[:-1]
              if ln.startswith('{"chipbench": "detail"')][-1]
    checks = detail["checks"]
    assert checks["window_compiles"] == 0
    assert (checks.get("forward") or checks.get("tokens"))["ok"]


def test_a_cell_that_needs_more_chips_than_there_are_refuses(capsys):
    from chipbench.harness.cli import device_report
    with pytest.raises(SystemExit) as exit_info:
        device_report(4096, rehearse=True)
    assert exit_info.value.code == 2
    assert "chip(s)" in capsys.readouterr().err


def test_no_tpu_no_result():
    proc, lines = _run(os.path.join(BENCH_DIR, "run.py"), "--workload",
                       "gpt2_train", "--seed", "0", "--seconds", "1",
                       "--trace", "0")
    assert proc.returncode == 2
    assert "not a TPU" in proc.stderr
    assert not any(ln.startswith('{"correct"') for ln in lines)


def test_nothing_runs_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` the command fails and prints no result."""
    shutil.copy(os.path.join(REPO_DIR, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "gpt2_train",
         "--seed", "0", "--seconds", "1", "--trace", "0", "--rehearse"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_a_cell_is_added_by_new_files_alone(tmp_path):
    """A throw-away cell with its own configuration, traffic mix, driver,
    per-layer metric and reader, in a copy of the benchmark: nothing that
    was there is edited, and ``run.py`` finds all of it by name."""
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "throwaway_cfg.json").write_text(json.dumps(
        {"name": "throwaway_cfg", "family": "none", "source": "none",
         "item": "number", "width": 64, "reduced": []}))
    (bench / "traffic" / "throwaway_mix.json").write_text(json.dumps(
        {"name": "throwaway_mix", "kind": "job", "rounds": 5}))
    (bench / "workloads" / "throwaway.json").write_text(json.dumps(
        {"name": "throwaway", "config": "throwaway_cfg",
         "traffic": "throwaway_mix", "driver": "throwaway_driver",
         "chips": 1, "end_to_end": ["setup_s", "add_rate"],
         "per_layer": ["throwaway_metric", "window_compiles"],
         "why": "shows that a cell needs no edit"}))
    (bench / "layer_metrics" / "throwaway_metric.json").write_text(
        json.dumps({"name": "throwaway_metric", "layer": "nowhere",
                    "unit": "count", "better": "higher",
                    "source": "program_counter", "moves": "add_rate",
                    "reader": "throwaway_reader", "args": {"times": 3}}))
    (bench / "readers" / "throwaway_reader.py").write_text(
        "def read(obs, times):\n"
        "    return obs['counters']['rounds'] * times\n")
    (bench / "drivers" / "throwaway_driver.py").write_text(textwrap.dedent(
        """
        import time
        import jax.numpy as jnp

        def run(job):
            x = jnp.ones((job.config["width"],))
            (x + 1).block_until_ready()
            t0 = time.monotonic()
            c0 = job.watch.snapshot()["requests"]
            rounds = job.traffic["rounds"]
            for _ in range(rounds):
                x = (x + 1).block_until_ready()
            dt = time.monotonic() - t0
            return {"t_window_start": t0, "attempted": rounds, "failed": 0,
                    "correct": True,
                    "end_to_end": {"add_rate": {"value": rounds / dt,
                                                "unit": "items/s"}},
                    "counters": {"rounds": rounds, "window_compiles":
                                 job.watch.snapshot()["requests"] - c0}}
        """))
    proc, lines = _run(str(bench / "run.py"), "--workload", "throwaway",
                       "--seed", "0", "--seconds", "1", "--trace", "1",
                       "--rehearse", extra_path=[str(tmp_path)])
    assert proc.returncode == 1, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["metrics"]["throwaway_metric"] == {"value": 15.0,
                                                   "unit": "count"}
    assert last["metrics"]["window_compiles"]["value"] == 0.0
    assert "peak_hbm_bytes" not in last["metrics"]
    assert last["attempted"] == 5
    proc, lines = _run(str(bench / "run.py"), "--workload", "throwaway",
                       "--seed", "0", "--seconds", "1", "--trace", "0",
                       "--rehearse", extra_path=[str(tmp_path)])
    last = json.loads(lines[-1])
    assert set(last["metrics"]) == {"setup_s", "add_rate"}
    after = {p: p.read_bytes() for p in bench.rglob("*")
             if p.is_file() and "out" not in p.parts
             and "__pycache__" not in p.parts}
    assert all(after[p] == body for p, body in before.items())


def test_a_model_family_and_a_layout_are_added_by_new_files_alone(tmp_path):
    """A throw-away model family (its net, batch, reference and operation
    count) and a layout, trained by the ``train_steps`` driver that is
    there: the window that defines ``train_rate`` is not copied."""
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "tiny_mlp.json").write_text(json.dumps(
        {"name": "tiny_mlp", "family": "tiny_mlp", "source": "none",
         "item": "row", "width": 16, "hidden": 32, "num_classes": 10,
         "reduced": []}))
    (bench / "models" / "tiny_mlp.py").write_text(textwrap.dedent(
        """
        import numpy as onp

        def build_net(cfg, jb):
            from mxnet_tpu.gluon import nn
            net = nn.HybridSequential()
            net.add(nn.Dense(cfg["hidden"], activation="relu"),
                    nn.Dense(cfg["num_classes"]))
            return net, onp.zeros((1, cfg["width"]), onp.float32)

        def make_batch(cfg, jb, seed):
            import jax
            import jax.numpy as jnp
            k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
            data = jax.random.normal(k1, (jb["batch"], cfg["width"]))
            label = jax.random.randint(k2, (jb["batch"],), 0,
                                       cfg["num_classes"])
            return data, label.astype(jnp.float32), jb["batch"]

        def reference(net, cfg, data, bench_dir):
            from mxnet_tpu import autograd
            from mxnet_tpu.ndarray import NDArray
            with autograd.pause(train_mode=False):
                return net(NDArray(data))._data, "the eager forward"

        def classes(cfg):
            return cfg["num_classes"]

        def flops_per_item(cfg, cell):
            return 6.0 * cfg["hidden"] * (cfg["width"] + cfg["num_classes"])
        """))
    (bench / "layouts").mkdir()
    (bench / "layouts" / "replicated.py").write_text(textwrap.dedent(
        """
        from chipbench.harness.cli import say

        def apply(net):
            say("layout", params=len(net.collect_params()))
        """))
    (bench / "workloads" / "tiny_train.json").write_text(json.dumps(
        {"name": "tiny_train", "config": "tiny_mlp",
         "traffic": "fixed_batch", "driver": "train_steps", "chips": 1,
         "job": {"batch": 8, "optimizer": "sgd",
                 "optimizer_params": {"learning_rate": 0.01},
                 "dtype": "bfloat16", "mesh": {"dp": 1},
                 "layout": "replicated", "loss_read_every": 4,
                 "trace_s": 0.2, "check_items": 4},
         "end_to_end": ["setup_s", "train_rate"],
         "per_layer": ["trainer_host_ms_p50", "model_flops_util",
                       "window_compiles"],
         "why": "shows that a model family needs no copy of the driver"}))
    proc, lines = _run(str(bench / "run.py"), "--workload", "tiny_train",
                       "--seed", "1", "--seconds", "1", "--trace", "0",
                       "--rehearse", extra_path=[str(tmp_path)])
    assert proc.returncode == 1, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert set(last["metrics"]) == {"setup_s", "train_rate"}
    assert last["attempted"] > 0 and last["failed"] == 0
    said = [json.loads(ln) for ln in lines[:-1]]
    assert {"chipbench": "layout", "params": 4} in said
    detail = [d for d in said if d.get("chipbench") == "detail"][-1]
    assert detail["checks"]["forward"]["ok"]
    assert detail["checks"]["first_loss_ok"]
    after = {p: p.read_bytes() for p in bench.rglob("*")
             if p.is_file() and "out" not in p.parts
             and "__pycache__" not in p.parts}
    assert all(after[p] == body for p, body in before.items())
