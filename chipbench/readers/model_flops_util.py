"""Model FLOP/s utilization: items a second times the operations one
item needs (``flops_per_item`` of the configuration's family,
``models/<family>.py``) over chips times the chip's published bf16 peak
(harness/peaks.py).  A device kind that is not in the table raises; a
rehearsal on the CPU reports nothing under this name."""
from chipbench.harness.cli import load_module
from chipbench.harness.peaks import peak


def read(obs):
    rate = obs["end_to_end"].get("train_rate")
    if rate is None or obs["device"].get("rehearsal"):
        return None
    model = load_module("models", obs["config"]["family"])
    flops = model.flops_per_item(obs["config"], obs["cell"])
    return 100.0 * rate["value"] * flops / (
        obs["chips"] * peak(obs["device"]["kind"], "bf16_flops_per_s"))
