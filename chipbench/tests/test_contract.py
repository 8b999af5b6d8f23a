"""Every name in BENCHMARK.json has its files, and every name and unit
passes the driver's character rules."""
import json
import os
import re

import pytest

from chipbench.harness.cli import BENCH_DIR, REPO_DIR

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _json(REPO_DIR, "BENCHMARK.json")


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO_DIR, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 2 <= len(bench["workloads"]) <= 24
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_lines(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_name_has_its_files(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in cells.values()} == set(configs)
    for c in configs.values():
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        body = _json(REPO_DIR, c["file"])
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in body
            assert not key.endswith(("_dim", "_rank"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    for name, w in cells.items():
        body = _json(BENCH_DIR, "workloads", f"{name}.json")
        for key in ("config", "traffic", "chips", "why"):
            assert body[key] == w[key], (name, key)
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "traffic", f"{w['traffic']}.json"))
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "drivers", f"{body['driver']}.py"))
        # the cell's file and BENCHMARK.json agree on who reports what
        for kind, table in (("end_to_end", e2e), ("per_layer", layer)):
            assert set(body[kind]) == {
                n for n, m in table.items()
                if name in m.get("workloads", cells)}, (name, kind)
        assert "setup_s" in body["end_to_end"]
        assert len(body["end_to_end"]) >= 2 and body["per_layer"]
        # a per-layer metric is reported only where the metric it moves is
        for n in body["per_layer"]:
            assert layer[n]["moves"] in body["end_to_end"], (name, n)
    for name, m in layer.items():
        spec = _json(BENCH_DIR, "layer_metrics", f"{name}.json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (name, key)
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "readers", f"{spec['reader']}.py"))
        assert m["moves"] in e2e


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    skip = {"out", "__pycache__", ".pytest_cache"}
    for root, dirs, files in os.walk(BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in skip]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), REPO_DIR)
            assert ok.match(rel) and len(rel) <= 200, rel


def test_unknown_device_kind_raises():
    from chipbench.harness.peaks import peak
    assert peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError, match="no peak"):
        peak("TPU v9 imaginary", "bf16_flops_per_s")
    with pytest.raises(KeyError, match="no peak"):
        peak("cpu", "bf16_flops_per_s")


def test_flops_per_item_by_hand():
    from chipbench.harness.cli import load_module
    gpt2 = load_module("models", "gpt2")
    medium = _json(BENCH_DIR, "configs", "gpt2_medium.json")
    assert gpt2.param_count(medium) == 354_823_168 == medium["parameters"]
    # openai-community/gpt2-large: 1280 wide, 36 layers
    large = dict(medium, n_embd=1280, n_layer=36, n_inner=5120)
    assert gpt2.param_count(large) == 774_030_080
    cell = {"job": {"seq_len": 1024}}
    # 6 * 354.8M + 12 * 24 * 1024 * 1024 = 2.431e9
    assert gpt2.flops_per_item(medium, cell) == pytest.approx(
        6 * 354_823_168 + 12 * 24 * 1024 * 1024)
    resnet = _json(BENCH_DIR, "configs", "resnet50_v1.json")
    assert load_module("models", "resnet50_v1").flops_per_item(
        resnet, {}) == pytest.approx(3 * 4.089e9)


def test_every_configuration_trained_has_its_family_module():
    for f in os.listdir(os.path.join(BENCH_DIR, "workloads")):
        cell = _json(BENCH_DIR, "workloads", f)
        if cell["driver"] != "train_steps":
            continue
        family = _json(BENCH_DIR, "configs",
                       f"{cell['config']}.json")["family"]
        assert os.path.isfile(os.path.join(BENCH_DIR, "models",
                                           f"{family}.py")), family
