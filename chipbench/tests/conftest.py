"""Self-tests of the benchmark, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

Tier-1 collects only ``tests/``; nothing here runs on the chip."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
