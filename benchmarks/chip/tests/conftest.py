"""Tests of the benchmark itself, on the CPU: run them with an explicit
path, ``python -m pytest benchmarks/chip/tests``.  Four host devices
stand in for the four-chip cell."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

HERE = Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
