"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against its own limit from the cell file:

- ``loss_gap``: over the first three steps, the largest relative gap between
  the program's loss and the reference's.
- ``grad_gap``: the first step's gradient as the optimizer got it (after
  clipping). For each leaf, the gap between the program's norm and the
  reference's, over the larger of the reference's norm of that leaf and of
  the median leaf; the median leaf's gap counts. The worst leaf's
  (``grad_gap_worst``) is reported and not compared: at the default matmul
  precision the batch-norm scale and bias gradients, small sums that cancel,
  move by up to a tenth of their norm from seed to seed (PERF.md).
- ``update_gap``: the same for each leaf's change over the three steps. A leaf
  whose reference gradient is under a thousandth of the median leaf's moves by
  round-off alone and is left out.
- ``head_gap``: the worst of those gaps of the first gradient over the leaves
  of the conv layer nearest the loss (the last layer with parameters). Its
  gradient comes straight from the loss, through no batch-norm sum that
  cancels, so a change of the network's arithmetic shows there first: a
  network run in bfloat16 moves it by a few hundredths where the program at
  the stated precision moves it by under a thousandth (PERF.md).
"""
from __future__ import annotations

import dataclasses
import statistics

import jax
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "update_gap", "head_gap")
STILL_LEAF = 1e-3


@dataclasses.dataclass
class Trajectory:
    """What one side did over the checked steps, on the host."""

    losses: list
    grad: list          # first step's clipped gradient, the params' tree
    delta: list         # params after the checked steps minus before


def leaf_norms(tree) -> list[float]:
    return [float(np.linalg.norm(np.asarray(a, np.float64))) for a in jax.tree.leaves(tree)]


def leaf_gaps(got, want) -> list[float]:
    """Per leaf, in ``jax.tree.leaves`` order: the gap between the two norms
    over the larger of the reference's norm of the leaf and of the median leaf."""
    if jax.tree.structure(got) != jax.tree.structure(want):
        raise ValueError("program and reference trees differ in structure")
    a, b = leaf_norms(got), leaf_norms(want)
    floor = statistics.median(b)
    return [abs(x - y) / max(y, floor, 1e-30) for x, y in zip(a, b)]


def norm_gap(got, want, keep=None) -> tuple[float, int]:
    """Worst leaf's norm gap and its index in ``jax.tree.leaves`` order."""
    gaps = leaf_gaps(got, want)
    idx = [i for i in range(len(gaps)) if keep is None or keep[i]]
    worst = max(idx, key=lambda i: gaps[i])
    return gaps[worst], worst


def readings(prog: Trajectory, ref: Trajectory) -> dict:
    if len(prog.losses) != len(ref.losses):
        raise ValueError(f"{len(prog.losses)} program losses against {len(ref.losses)}")
    g = leaf_norms(ref.grad)
    floor = statistics.median(g)
    keep = [x >= STILL_LEAF * floor for x in g]
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog.losses, ref.losses))
    grad = statistics.median(leaf_gaps(prog.grad, ref.grad))
    worst, grad_leaf = norm_gap(prog.grad, ref.grad)
    upd, upd_leaf = norm_gap(prog.delta, ref.delta, keep)
    head = max(leaf_gaps(prog.grad, ref.grad)[i] for i in head_leaves(ref.grad))
    if not all(np.isfinite([loss, grad, upd, head])):
        loss, grad, upd, head = (float("inf"),) * 4
    return {"loss_gap": loss, "grad_gap": grad, "update_gap": upd, "head_gap": head,
            "grad_gap_worst": worst, "grad_leaf": grad_leaf, "update_leaf": upd_leaf,
            "left_out": keep.count(False)}


def head_leaves(params: list) -> range:
    """Indices, in ``jax.tree.leaves`` order, of the last layer's leaves that
    has any; ``params`` is the list of per-layer parameter dicts."""
    last = max(i for i, p in enumerate(params) if jax.tree.leaves(p))
    start = len(jax.tree.leaves(params[:last]))
    return range(start, start + len(jax.tree.leaves(params[last])))


def judge(values: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every number compared, in order."""
    missing = [k for k in NUMBERS if k not in limits]
    if missing:
        raise ValueError(f"cell file has no limit for {missing}")
    return {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
