"""A whole run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
(set-up, checked steps, window, reference, comparison) at a small size on the
CPU, with the cell's own limits, once sound and once for each fault the cell
can have: a step that returns its state unchanged, half of the batch left out
with the mean taken over the rest, and on a tile grid the halo exchange
between chips left out."""
import jax.numpy as jnp
import pytest

import run_cell
from helpers import small

CELLS = ["voc416.train.xla", "coco608.train.2x2"]


def run_small(cell_name, hw=64, batch=8):
    benchmark, cell, cfg = small(cell_name, hw, batch)
    return run_cell.run(cell_name, 2**33 + 7, 0.5, False, benchmark=benchmark,
                        cell=cell, cfg=cfg, require_tpu=False)


def unchanged_state(monkeypatch):
    import repro.train.trainer as trainer

    def keep(state, loss, grads, opt, tcfg):
        return state, {"loss": loss, "grad_norm": jnp.zeros(()), "lr": jnp.zeros(())}

    monkeypatch.setattr(trainer, "_apply_updates", keep)


def half_batch(monkeypatch):
    import repro.core.fusion as fusion

    real = fusion.make_deferred_grad_step

    def halved(*args, **kwargs):
        step = real(*args, **kwargs)
        return lambda p, xs, ts: step(p, xs[:, : xs.shape[1] // 2], ts[:, : ts.shape[1] // 2])

    monkeypatch.setattr(fusion, "make_deferred_grad_step", halved)


def no_exchange(monkeypatch):
    import repro.core.halo as halo

    monkeypatch.setattr(halo, "wire_shift", lambda x, axis_name, perm, wire: jnp.zeros_like(x))


FAULTS = [(c, f) for c in CELLS for f in (unchanged_state, half_batch)]
FAULTS.append(("coco608.train.2x2", no_exchange))


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name):
    out = run_small(cell_name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"train_images_per_s", "setup_s", "peak_hbm_gib"}


@pytest.mark.parametrize("cell_name,fault", FAULTS, ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(monkeypatch, cell_name, fault):
    fault(monkeypatch)
    out = run_small(cell_name)
    assert not out["correct"], out["checks"]
