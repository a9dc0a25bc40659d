"""Tests of the yardstick itself. They run on the CPU (``JAX_PLATFORMS=cpu``)
and are not part of the repo's tier-1 run: ``python -m pytest
benchmarks/tests -q``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent), str(BENCH), str(Path(__file__).parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
