"""Residual shortcuts on the 2x2 tile grid, in a subprocess with four fake
devices (``scripts/check_shortcut.py``): YOLOv3's layers 0-11 at their
published widths (64x64, batch 8) against the plain residual reference of
the benchmark for the default plan, fused residual units and a crossover at
a unit's edge, with ``test_shortcut_exact.py``'s tolerances and reasons; and
the stride-2 conv's asymmetric (1, 0) halo through ``halo_exchange_1d``."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from test_shortcut_exact import GRAD_MEDIAN, GRAD_WORST, LOSS_RTOL, PLANS

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def grid2x2():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, str(REPO / "scripts" / "check_shortcut.py")],
                          capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("plan", list(PLANS))
def test_2x2_follows_the_reference(grid2x2, plan):
    got = grid2x2["plans"][plan]
    assert got["loss_gap"] < LOSS_RTOL
    assert got["grad_gap_median"] < GRAD_MEDIAN and got["grad_gap_worst"] < GRAD_WORST, got


@pytest.mark.parametrize("shards", [1, 2])
def test_stride2_halo_exchange(grid2x2, shards):
    """A 3x3 stride-2 conv's halo is (1, 0): one strip from the previous
    shard over one ppermute on a 2-shard axis, a zero pad with none on a
    1-shard axis; each tile equals its window of the zero-padded map."""
    got = grid2x2["halo"][str(shards)]
    assert got["gap"] == 0.0
    assert got["ppermute"] == shards - 1
