"""Benchmark tests run on the CPU, with four host devices for the 2x2 grid:
``python -m pytest bench/tests``. They are outside the repository's tier-1
test paths."""
import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
