"""chip_smoke.py, bench.py and the compile-cache helper: what they do
WITHOUT a chip.

- without a TPU the smoke exits non-zero and never prints an ok:true
  verdict (so a CPU run can never be taken for a chip run);
- ``--rehearse`` walks every phase at tiny sizes, labels every line and
  still ends ok:false (slow tier: two subprocesses of about a minute);
- bench.py: a row that raises fails the process, an unknown device
  kind has no peak;
- ``base.use_compile_cache`` leaves a cache the caller placed alone and
  otherwise names one fixed directory.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, extra_env=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra_env or {})
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _lines(proc):
    return [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")]


def test_smoke_without_tpu_fails_and_prints_no_ok():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    lines = _lines(proc)
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert not any(ln.get("ok") is True for ln in lines)
    # nothing ran: the device line, then the verdict
    assert [ln.get("phase") for ln in lines] == ["device", None]


def _check_rehearsal(proc, phases):
    assert proc.returncode == 1, proc.stderr[-3000:]
    lines = _lines(proc)
    assert all(ln["rehearsal"] is True for ln in lines)
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert list(by_phase) == ["device"] + phases + ["summary"]
    for name in phases:
        assert by_phase[name]["ok"] is True, by_phase[name]
    assert by_phase["summary"]["kernel.fallbacks"] == 0
    verdict = lines[-1]
    assert verdict["ok"] is False and "phase" not in verdict
    return by_phase, verdict


@pytest.mark.slow
def test_rehearsal_walks_every_single_chip_phase():
    by_phase, _ = _check_rehearsal(
        _run(["chip_smoke.py", "--rehearse"]),
        ["kernels", "train.resnet_spmd", "train.resnet_gluon",
         "train.transformer_lm", "serve.predict", "serve.generate"])
    assert set(by_phase["kernels"]["max_err"]) >= {
        "flash_attention", "flash_attention.dq", "paged_attention",
        "rope", "layer_norm_residual"}
    gen = by_phase["serve.generate"]
    assert gen["dense_agree"][0] == gen["dense_agree"][1]
    assert "verify" in gen["executables"]["spec"]


@pytest.mark.slow
def test_rehearsal_multichip_runs_only_the_sharded_phases():
    by_phase, verdict = _check_rehearsal(
        _run(["chip_smoke.py", "--rehearse", "--multichip"],
             {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}),
        ["multichip.spmd_dp2tp2", "multichip.mesh4d_dp2tp2"])
    assert verdict["device"]["count"] == 4
    spmd = by_phase["multichip.spmd_dp2tp2"]
    assert spmd["collectives"]["all-reduce"] > 0
    assert spmd["sharded_params"] > 0


def test_multichip_refuses_fewer_than_four_devices():
    proc = _run(["chip_smoke.py", "--rehearse", "--multichip"],
                {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert proc.returncode != 0
    assert "4 device(s) needed" in _lines(proc)[-1]["error"]


# -- bench.py ----------------------------------------------------------------

def test_bench_failing_row_fails_the_process():
    code = ("import bench\n"
            "def boom(*a, **k):\n"
            "    raise RuntimeError('row failed on purpose')\n"
            "bench._train_bench = boom\n"
            "bench.main()\n")
    proc = _run(["-c", code], {"MXNET_TPU_BENCH_DRYRUN": "1"})
    assert proc.returncode != 0
    assert "row failed on purpose" in proc.stderr
    assert '"metric"' not in proc.stdout       # no result line


def test_bench_refuses_a_non_tpu_platform_outside_the_dry_run():
    proc = _run(["bench.py"], {"MXNET_TPU_BENCH_DRYRUN": ""})
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert '"metric"' not in proc.stdout


def test_bench_peak_table_is_exact():
    sys.path.insert(0, REPO)
    import bench
    assert bench._peak_flops("TPU v5 lite") == 197e12
    for kind in ("TPU v5", "TPU v5p", "cpu", "tpu v5 lite"):
        with pytest.raises(KeyError, match="no peak"):
            bench._peak_flops(kind)


# -- the compile-cache helper ------------------------------------------------

def test_compile_cache_left_alone_when_the_caller_placed_it(monkeypatch):
    import jax
    from mxnet_tpu.base import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert use_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_directory(monkeypatch):
    import jax
    from mxnet_tpu.base import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        first = use_compile_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert use_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
