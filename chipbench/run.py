#!/usr/bin/env python3
"""chipbench: one cell of the benchmark, once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds`` and prints one last line of
JSON (``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
in a traced run, ``breakdown``).  Everything about a cell is data:
``workloads/<cell>.json`` names its configuration, traffic mix, driver and
per-layer metrics, and each of those is a file found by that name.  Nothing
here lists cells, configurations, drivers or metrics.
"""
import os
import sys
import time

T_PROCESS_START = time.monotonic()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.harness.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process_start=T_PROCESS_START))
