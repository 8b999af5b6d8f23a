"""The files PR 33 added for the ``axk1`` family, held to the contract:
the configuration against the catalog row it was drawn from, the
family's counts by hand, and the two new readers on observations that
have and have not what they read (a program from before the figures
existed reports none, and the metric is left out)."""
import json
import os

import pytest

from chipbench.harness.cli import BENCH_DIR, layer_metrics, load_module

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json("configs", "axk1_519b.json")


def test_configuration_keeps_every_published_number(cfg):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f) if r["name"] == "A.X-K1"][0]
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg[key + "_published"] == value
        else:
            assert cfg[key] == value, key
    # the floors of a model_config cut: four expert layers after the
    # dense one, 8 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["vocab_size_published"]
    assert not any(k.endswith(("_dim", "_rank")) for k in cfg["reduced"])


def test_counts_by_hand(cfg):
    fam = load_module("models", cfg["family"])
    attention = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                 + 512 * 64 * 256 + 64 * 128 * 7168)
    assert fam.attention_param_count(cfg) == attention == 101_122_048
    assert fam.expert_param_count(cfg) == 3 * 7168 * 2048 == 44_040_192
    dense = attention + 16_384 + 3 * 7168 * 18432
    expert = attention + 16_384 + 7168 * 192 + 13 * 44_040_192
    assert fam.layer_param_count(cfg, True) == dense
    assert fam.layer_param_count(cfg, False) == expert
    assert fam.param_count(cfg) == cfg["parameters"] == (
        dense + 6 * expert + 2 * 20480 * 7168 + 7168)
    # a row of the latent cache is 576 numbers; a thousand rows of 64
    # slots are 74 MB a layer and call
    assert fam.latent_row_bytes(cfg) == 1152
    assert fam.latent_attention_bytes(cfg, 64_000) == 64_000 * 1152
    # 121 operations a byte: under the v5e's 240, a bandwidth roofline
    assert fam.latent_attention_flops(cfg, 1) == 2 * 64 * (512 + 576)
    assert fam.latent_attention_flops(cfg, 1) / 1152 < 197e12 / 819e9


def test_engine_stat_reads_a_figure_or_nothing():
    read = load_module("readers", "engine_stat").read
    obs = {"notes": {"engine": {"page_bytes": 4_697_620_480,
                                "counters": {"moe_local_pair_share": 0.0625},
                                "none": None}}}
    assert read(obs, "page_bytes", 1e-9) == pytest.approx(4.69762048)
    assert read(obs, "counters.moe_local_pair_share", 100.0) == 6.25
    for missing in ("live_tokens_mean", "counters.moe_expert_rows_mean",
                    "page_bytes.deeper", "none"):
        assert read(obs, missing) is None
    assert read({}, "page_bytes") is None
    assert read({"notes": {"engine": None}}, "page_bytes") is None


def test_the_new_metrics_fall_silent_without_their_sources(cfg):
    """An untraced run of a program without the counters: every new
    metric is left out, none raises."""
    cell = _json("workloads", "axk1_decode_reasoning.json")
    new = [n for n in cell["per_layer"] if n.startswith(("latent_", "moe_"))]
    assert len(new) == 6
    obs = {"cell": cell, "config": cfg, "device": {"kind": "TPU v5 lite"},
           "t_window_start": 0.0, "series": {}, "counters": {},
           "notes": {"engine": {"compiles": 0}}, "_named": None}
    assert layer_metrics(obs, new) == {}
    counters = {"moe_local_pair_share": 0.0625, "moe_expert_rows_mean": 2.5,
                "moe_experts_idle_share": 0.125}
    # the engine's life-long means alone (no capture ran): only the
    # cache's size is read
    obs["notes"]["engine"].update(
        page_bytes=4_697_620_480, live_tokens_mean=55_000.0,
        counters=dict(counters, moe_expert_rows_mean=1.9),
        traced={"decode_steps": 0, "live_tokens_mean": 0.0, "counters": {}})
    assert set(layer_metrics(obs, new)) == {"latent_cache_gb"}
    obs["notes"]["engine"]["traced"] = {
        "decode_steps": 176, "live_tokens_mean": 75_000.0,
        "counters": counters}
    got = layer_metrics(obs, new)
    # the two that read the device trace stay silent without one
    assert set(got) == set(new) - {"latent_attention_time_share",
                                   "latent_attention_roofline"}
    assert got["moe_experts_idle_share"] == {"value": 12.5, "unit": "%"}
    assert got["moe_expert_rows_mean"]["value"] == 2.5   # the traced steps'
    assert got["latent_cache_gb"]["value"] == pytest.approx(4.69762048)


def test_roofline_counts_bytes_by_live_tokens(cfg, monkeypatch):
    """Two calls of 1 ms each over 35,547 live rows: 2 x 40.95 MB in 2 ms
    is 5% of 819 GB/s; the rows are the traced steps', not the life's."""
    named = load_module("readers", "trace_named")
    parsed = {"devices": [{"kernels": [
        (0.0, 1e-3, "%mxtpu_latent_attention.3 = custom-call"),
        (2e-3, 3e-3, "%mxtpu_latent_attention.9 = custom-call"),
        (4e-3, 9e-3, "%mxtpu_rope.1 = custom-call")]}]}
    monkeypatch.setattr(named, "_parsed", lambda obs: parsed)
    read = load_module("readers", "kernel_roofline_tokens").read
    rows = 0.05 * 819e9 * 1e-3 / 1152
    obs = {"config": cfg, "device": {"kind": "TPU v5 lite"},
           "notes": {"engine": {"live_tokens_mean": rows / 2,
                                "traced": {"live_tokens_mean": rows}}}}
    args = dict(match="mxtpu_latent_attention",
                bytes_fn="latent_attention_bytes",
                rows_stat="traced.live_tokens_mean")
    assert read(obs, **args) == pytest.approx(5.0)
    assert read(dict(obs, notes={"engine": {}}), **args) is None
    assert read(obs, **dict(args, match="mxtpu_no_such")) is None
    assert read(obs, **dict(args, bytes_fn="no_such_bytes")) is None
