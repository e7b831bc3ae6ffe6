"""Elastic fault-tolerant training acceptance on 4 fake devices.

Acceptance scenario for the elastic subsystem (subprocess target; see
tests/test_spmd.py / ISSUE 7, DESIGN.md §10):

(a) HEADLINE - train a tiled YOLO prefix on the heterogeneous
    ``pi3x3+jetson`` 2x2 cluster under the fault-tolerant driver, lose the
    Jetson mid-run via the fault schedule, replan onto the surviving 1x3
    Pi mesh without losing the live state, checkpoint there, then resume
    in a *second* driver run that restores the 1x3-plan checkpoint onto
    the ORIGINAL 2x2 hetero mesh (partition-independence, live) and runs
    to completion - final params must match an uninterrupted untiled 1x1
    reference to <=1e-5.
(b) a save killed mid-write (always-crashing writer) surfaces the failure
    from ``wait()``/``save()`` after bounded retries and leaves the prior
    committed checkpoint bit-identical and restorable; a one-shot crash is
    absorbed by retry_io's exponential backoff and the save lands.
(c) a corrupted leaf file (CRC mismatch) makes ``restored_step`` fall back
    to the previous retained step; explicit-step restore raises.
(d) elastic restore sweep - checkpoints saved under (uniform 2x2,
    balanced ``pi3x3+jetson``, hybrid crossover) each restore under the
    other two plans and the continued loss curve matches the
    uninterrupted untiled run to <=1e-5 (params) for every ordered pair.
"""
import os
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ParallelConfig, TrainConfig
from repro.core import (
    build_stack_plan,
    drop_device,
    parse_cluster_spec,
    plan_from_manifest,
    plan_manifest,
    replan_stack,
)
from repro.core.fusion import reference_forward
from repro.ckpt.manager import CheckpointCorruptError, CheckpointManager
from repro.launch.mesh import make_tile_mesh
from repro.models.tiled_cnn import TiledCNNArch
from repro.models.yolo import l2_loss_local, yolov2_16_layers
from repro.runtime.driver import DriverConfig, run_training
from repro.runtime.faults import FaultInjector, corrupt_leaf, make_write_crash
from repro.train.trainer import make_train_step

LAYERS = yolov2_16_layers()[:4]
H = W = 64
BATCH = 4
SEED = 0
TMP = tempfile.mkdtemp(prefix="repro_elastic_check_")

tcfg = TrainConfig(lr=1e-2, optimizer="sgd", warmup=10, steps=100, grad_clip=1.0)
pcfg = ParallelConfig(grad_accum=1)


def make_arch(plan):
    return TiledCNNArch(
        plan=plan, mesh=make_tile_mesh(plan.n, plan.m), loss_local=l2_loss_local
    )


def make_step(plan):
    arch = make_arch(plan)
    init_state, train_step = make_train_step(arch, pcfg, tcfg)
    return init_state, jax.jit(train_step)


# target geometry from the untiled oracle
plan_ref = build_stack_plan((H, W), LAYERS, 1, 1)
_p0 = jax.tree.map(np.asarray, make_step(plan_ref)[0](jax.random.PRNGKey(SEED)))
out_shape = reference_forward(
    _p0.params, np.zeros((1, H, W, 3), np.float32), plan_ref
).shape


def make_batch(step: int) -> dict:
    rng = np.random.default_rng([SEED, step])
    x = rng.standard_normal((BATCH, H, W, 3), np.float32)
    t = 0.05 * rng.standard_normal((BATCH,) + out_shape[1:], np.float32)
    return {"x": jnp.asarray(x), "t": jnp.asarray(t)}


def run_plain(plan, steps, state=None, start=0):
    """Uninterrupted loop: the oracle trajectory for a given plan."""
    init_state, step_fn = make_step(plan)
    if state is None:
        state = init_state(jax.random.PRNGKey(SEED))
    for s in range(start, steps):
        state, _ = step_fn(state, make_batch(s))
    return jax.tree.map(np.asarray, state)


def max_leaf_err(a, b):
    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---------------------------------------------------------------------------
# (a) headline: hetero train -> drop jetson -> replan -> ckpt -> resume 2x2
# ---------------------------------------------------------------------------
STEPS1, STEPS2 = 8, 10
cluster0 = parse_cluster_spec("pi3x3+jetson", 2, 2)
plan0 = build_stack_plan((H, W), LAYERS, 2, 2, hw=cluster0)
assert not plan0.is_uniform, "hetero cluster must balance to a non-uniform partition"
init0, step0 = make_step(plan0)

live = {"cluster": cluster0, "plan": plan0}


def replan(ev):
    cl = drop_device(live["cluster"], ev.device)
    new_plan = replan_stack(live["plan"], cl, batch=BATCH)
    _, new_step = make_step(new_plan)
    live.update(cluster=cl, plan=new_plan)
    print(f"[headline] replan: {new_plan.n}x{new_plan.m} "
          f"rows={new_plan.partition.row_bounds} cols={new_plan.partition.col_bounds}")
    return new_step, plan_manifest(new_plan, cl)


ckpt_dir = os.path.join(TMP, "headline")
cfg1 = DriverConfig(ckpt_dir=ckpt_dir, ckpt_every=3, async_ckpt=False,
                    hang_timeout=600.0)
rep1 = run_training(
    init_state=init0, train_step=step0, make_batch=make_batch, steps=STEPS1,
    cfg=cfg1, seed=SEED, faults=FaultInjector("drop:jetson@4"), replan=replan,
    plan=plan_manifest(plan0, cluster0),
)
assert rep1.replans == 1, rep1
assert rep1.steps_done == STEPS1, rep1
assert live["plan"].n * live["plan"].m == 3, "survivors must re-pack to 3 tiles"

# the checkpoint on disk was written under the REPLANNED 1x3 plan
mgr = CheckpointManager(ckpt_dir)
stored = mgr.plan_of()
assert stored is not None and (stored["n"], stored["m"]) == (
    live["plan"].n, live["plan"].m), stored
assert plan_from_manifest(stored) == live["plan"], \
    "plan manifest must round-trip the replanned StackPlan"

# resume run: restores the 1x3-plan checkpoint onto the ORIGINAL 2x2 hetero
# mesh and finishes - partition-independent restore, live
cfg2 = DriverConfig(ckpt_dir=ckpt_dir, ckpt_every=3, async_ckpt=False,
                    resume="always", hang_timeout=600.0)
rep2 = run_training(
    init_state=init0, train_step=step0, make_batch=make_batch, steps=STEPS2,
    cfg=cfg2, seed=SEED, plan=plan_manifest(plan0, cluster0),
)
assert rep2.resumed_step == STEPS1 - 1, rep2
final_state = mgr.restore(jax.eval_shape(lambda: init0(jax.random.PRNGKey(SEED))))

ref = run_plain(plan_ref, STEPS2)
err = max_leaf_err(final_state.params, ref.params)
print(f"[headline] drop->replan->resume final param maxerr={err:.3e} "
      f"(steps={STEPS1}+{STEPS2 - STEPS1}, untiled reference)")
assert err <= 1e-5, f"headline exactness failed: {err:.3e}"
assert int(final_state.step) == STEPS2

# ---------------------------------------------------------------------------
# (b) crash-during-save: prior checkpoint untouched; one-shot crash absorbed
# ---------------------------------------------------------------------------
crash_dir = os.path.join(TMP, "crash")
mgr_c = CheckpointManager(crash_dir, io_retries=2, io_backoff=0.0)
st0 = run_plain(plan_ref, 1)
mgr_c.save(0, st0)
committed = {}
d0 = os.path.join(crash_dir, "step_00000000")
for f in sorted(os.listdir(d0)):
    with open(os.path.join(d0, f), "rb") as fh:
        committed[f] = fh.read()

st1 = run_plain(plan_ref, 2, state=st0, start=1)
mgr_c.write_fault = make_write_crash(times=10 ** 9)   # every attempt dies
crashed = False
try:
    mgr_c.save(1, st1, blocking=False)
    mgr_c.wait()
except IOError as e:
    crashed = True
    print(f"[crash] async save surfaced after retries: {e}")
assert crashed, "always-crashing save must surface from wait()"
assert mgr_c.latest_step() == 0, "failed save must not commit"
for f, blob in committed.items():
    with open(os.path.join(d0, f), "rb") as fh:
        assert fh.read() == blob, f"prior checkpoint file {f} modified by crash"
restored0 = mgr_c.restore(jax.eval_shape(lambda: st0))
assert max_leaf_err(restored0.params, st0.params) == 0.0
print("[crash] prior step_00000000 bit-identical and restorable")

mgr_c.write_fault = make_write_crash(times=1)         # one-shot: retry absorbs
mgr_c.save(1, st1)
assert mgr_c.latest_step() == 1, "one-shot write crash must be retried away"
print("[crash] one-shot mid-write crash absorbed by retry/backoff")

# ---------------------------------------------------------------------------
# (c) corrupted leaf -> fallback to previous retained step
# ---------------------------------------------------------------------------
path = corrupt_leaf(crash_dir, 1)
print(f"[corrupt] flipped bytes in {os.path.basename(path)}")
state_fb, step_fb = mgr_c.restored_step(jax.eval_shape(lambda: st0))
assert step_fb == 0, f"expected fallback to step 0, got {step_fb}"
assert max_leaf_err(state_fb.params, st0.params) == 0.0
try:
    mgr_c.restore(jax.eval_shape(lambda: st0), step=1)
    raise AssertionError("explicit restore of a corrupted step must raise")
except IOError:
    pass
print("[corrupt] restore fell back to step 0; explicit step=1 raised")

# ---------------------------------------------------------------------------
# (d) elastic restore sweep across plan geometries
# ---------------------------------------------------------------------------
K1, K2 = 2, 4
plans = {
    "uniform2x2": build_stack_plan((H, W), LAYERS, 2, 2),
    "hetero": plan0,
    "hybrid": build_stack_plan((H, W), LAYERS, 2, 2, crossover=2),
}
assert plans["hybrid"].crossover == 2
ref_sweep = run_plain(plan_ref, K2)
steps_by_plan = {name: make_step(p) for name, p in plans.items()}
abstract = jax.eval_shape(lambda: init0(jax.random.PRNGKey(SEED)))
for save_name, save_plan in plans.items():
    sdir = os.path.join(TMP, f"sweep_{save_name}")
    smgr = CheckpointManager(sdir)
    s_init, s_step = steps_by_plan[save_name]
    st = s_init(jax.random.PRNGKey(SEED))
    for s in range(K1):
        st, _ = s_step(st, make_batch(s))
    smgr.save(K1 - 1, st, plan=plan_manifest(save_plan))
    for load_name, load_plan in plans.items():
        if load_name == save_name:
            continue
        lst = smgr.restore(abstract)
        _, l_step = steps_by_plan[load_name]
        for s in range(K1, K2):
            lst, _ = l_step(lst, make_batch(s))
        err = max_leaf_err(lst.params, ref_sweep.params)
        print(f"[sweep] save={save_name:10s} -> restore={load_name:10s} "
              f"param maxerr={err:.3e}")
        assert err <= 1e-5, f"{save_name}->{load_name}: {err:.3e}"

print("ELASTIC CHECK OK")
