"""SPMD exactness suites - run in subprocesses because they need fake
multi-device topologies (XLA_FLAGS must be set before jax import, and the
in-process test run must keep seeing the real single CPU device).

Each script asserts tiled-vs-untiled exactness to float tolerance and exits
non-zero on failure:
  check_core.py     - paper-native 2x2 spatial tiling: fwd/grad exactness
                      under 4 grouping profiles + deferred weight aggregation
  check_ssd.py      - Mamba2 SSD chunked scan + 4-shard sequence parallelism
  check_halo.py     - halo exchange 1d/2d incl. corners + adjoint/AD identity
  check_pipeline.py - unified planner->executor->trainer: tiled YOLO train
                      step == untiled reference for xla AND pallas backends,
                      groups="auto" regimes, batch-axis BN statistics
  check_overlap.py  - overlap schedule: packed-collective interior/boundary
                      split executor == untiled reference (xla + pallas),
                      ppermute count 4 -> 2 per group input, no-interior
                      fallback
  check_elastic.py  - elastic fault tolerance: hetero train -> drop device
                      -> replan -> checkpoint -> resume on a different
                      partition == untiled reference; crash-during-save
                      atomicity; corrupted-leaf fallback; cross-plan
                      restore sweep
  check_pipeline_parallel.py - pipeline partition mode (DESIGN.md §11):
                      memory-lever stack no all-spatial plan can hold
                      trains on a 1x4 mesh == untiled reference (xla +
                      pallas), hybrid spatial->pipeline on 2x2, bubble
                      census == model, execution-time validation, trainer
                      integration

Those suites are the heaviest tier: each spawns a 4-fake-device subprocess
that traces shard_map'd train steps (minutes each on CPU), so they are
marked slow, excluded from tier-1 (-m "not slow"); the CI full-suite job
runs them.  The single-shard halo cases share one subprocess
(scripts/check_halo.py --single-shard, ~30 s) and run in tier-1.
"""
import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", script), *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, f"{script} failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


@pytest.mark.slow
def test_spatial_tiled_training_exact():
    out = _run("check_core.py")
    assert "CORE CHECK OK" in out


@pytest.mark.slow
def test_ssd_sequence_parallel_exact():
    out = _run("check_ssd.py")
    assert "SSD CHECK OK" in out


@pytest.mark.slow
def test_halo_exchange_exact():
    out = _run("check_halo.py")
    assert "HALO CHECK OK" in out


@pytest.mark.slow
def test_unified_pipeline_exact():
    out = _run("check_pipeline.py")
    assert "PIPELINE CHECK OK" in out


@pytest.mark.slow
def test_overlap_schedule_exact():
    out = _run("check_overlap.py")
    assert "OVERLAP CHECK OK" in out


@pytest.mark.slow
def test_elastic_fault_tolerance_exact():
    out = _run("check_elastic.py")
    assert "ELASTIC CHECK OK" in out


@pytest.mark.slow
def test_pipeline_parallel_exact():
    out = _run("check_pipeline_parallel.py")
    assert "PIPELINE-PARALLEL CHECK OK" in out


@pytest.fixture(scope="module")
def single_shard():
    return json.loads(_run("check_halo.py", "--single-shard").splitlines()[-1])


MESHES = ((1, 1), (1, 2), (2, 1))
WIDTHS = ((0, 0), (1, 1), (1, 2))


@pytest.mark.parametrize("lo,hi", WIDTHS, ids=[f"halo{lo}{hi}" for lo, hi in WIDTHS])
@pytest.mark.parametrize("r,c", MESHES, ids=[f"{r}x{c}" for r, c in MESHES])
def test_single_shard_axis_pads_without_ppermute(single_shard, r, c, lo, hi):
    """Along an axis of one shard ``halo_exchange_2d`` zero-pads the tile and
    issues no ppermute; along a 2-shard axis both shifted ppermutes stay.
    Either way each tile equals its window of the zero-padded map."""
    case = single_shard["exchange"][f"{r}x{c}-{lo}-{hi}"]
    want = {ax: 2 for ax, n in (("r", r), ("c", c)) if n > 1 and lo + hi > 0}
    assert case["ppermute"] == want
    assert case["gap"] == 0.0


@pytest.mark.parametrize("lo,hi", WIDTHS, ids=[f"halo{lo}{hi}" for lo, hi in WIDTHS])
def test_single_shard_axis_pad_adjoint(single_shard, lo, hi):
    """On a 1-shard axis, AD through the zero pad equals
    ``send_boundary_sum_1d``, which still folds strips back over a
    one-device ``ppermute``: the pad's backward is the exchange's adjoint."""
    case = single_shard["adjoint"][f"{lo}-{hi}"]
    assert case["vdot_gap"] <= 1e-5
    assert case["ad_gap"] == 0.0


@pytest.mark.parametrize("shape", ["1x1", "1x2"])
def test_single_shard_axis_int8_error_feedback(single_shard, shape):
    """The int8 codec with error feedback (collect probe, then residual
    buffers over two microbatches) draws no residual along the single-shard
    row axis: the step finishes, ships nothing along it, and on 1x1, where
    nothing crosses a wire, equals the uncompressed step."""
    case = single_shard["codec"][shape]
    assert "th" not in case["ppermute"]
    assert ("tw" in case["ppermute"]) == (shape == "1x2")
    assert math.isfinite(case["loss"]) and case["loss"] > 0
    if shape == "1x1":
        assert case["gap"] == 0.0
    else:
        assert 0.0 < case["gap"] < 1e-2
