"""A named kernel's share of the device's busy time in the traced
segment: ``trace_named``'s ``kernel_share`` under a reader of its own,
for the kernels that came after PR 26's twelve metric files (the tool
``tools/named_report.py`` and its self-test count the files that name
``trace_named`` itself)."""
from chipbench.harness.cli import load_module


def read(obs, match):
    return load_module("readers", "trace_named").read(
        obs, "kernel_share", match=match)
