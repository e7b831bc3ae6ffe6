"""Tiled-CNN architecture bundle for the unified trainer (DESIGN.md §3).

Wraps a ``StackPlan`` + tile mesh + shard-local loss into the same surface
``train.trainer.make_train_step`` consumes for the LM architectures, so the
paper's distributed CNN training gets the full trainer machinery
(TrainState, grad clipping, cosine/warmup schedule, optional int8-EF
compression of the per-batch weight all-reduce) instead of hand-wired SGD.

``kind == "tiled_cnn"`` routes ``make_train_step`` onto the deferred-
aggregation path (paper §4.1): ``pcfg.grad_accum`` microbatches accumulate
per-tile weight-gradient partial sums locally inside shard_map; ONE psum at
batch end produces the final gradients the trainer tail consumes.

Batches are dicts ``{"x": (B, H, W, C), "t": (B, OH, OW, Cout)}`` with the
global batch B divisible by ``grad_accum`` - the same splitting convention
as the LM path.  ``place_batch`` puts them on the tile mesh in the layout
the executor binds (tiles of x on their devices), and ``state_sharding``
replicates the parameters and optimizer state over the mesh, so a step's
inputs start out spread over every device of the grid.

Hybrid plans (``plan.crossover`` set, DESIGN.md §7) need no trainer-side
changes: batch AND target still enter spatially sharded, the executor
reshards both at the crossover, and the adjoint reshard inside each
microbatch's backward keeps the deferred partial sums in the replicated
params layout - so compression/clipping/optimizer are mode-agnostic.  The
only visible constraint is that each microbatch (``B / grad_accum``) must
divide by the tile count when a data suffix exists (checked at trace time
with a clear error).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.fusion import StackPlan, _out_spec
from repro.core.spatial import freeze_bn_stats, init_stack_params

LossLocal = Callable[[jax.Array, jax.Array], tuple[jax.Array, jax.Array]]


@dataclasses.dataclass
class TiledCNNArch:
    """Planner output + mesh + loss: everything the trainer needs."""

    plan: StackPlan
    mesh: object                      # jax.sharding.Mesh
    loss_local: LossLocal
    row_axis: str = "th"
    col_axis: str = "tw"
    batch_axis: Optional[str] = None
    kind: str = "tiled_cnn"

    def init(self, key: jax.Array):
        return init_stack_params(key, self.plan.layers)

    @property
    def out_channels(self) -> int:
        return self.plan.layers[-1].out_channels

    @property
    def crossover(self) -> Optional[int]:
        """First data-mode layer of a hybrid plan (None = all spatial)."""
        return self.plan.crossover

    @property
    def partition(self):
        """The plan's explicit ``TilePartition``.  Non-uniform partitions
        (heterogeneous clusters, ragged extents) run the shape-specialized
        executor transparently (DESIGN.md §9; or the padded-to-max fallback
        of §8 with ``ragged_exec="padded"``) - batches still enter as
        global arrays; the loss/step wrappers and shard-boundary pack do
        the layout transforms."""
        return self.plan.partition

    def state_sharding(self) -> NamedSharding:
        """Params and optimizer state: replicated over the tile mesh (every
        device holds a full filter copy, paper §4)."""
        return NamedSharding(self.mesh, P())

    def batch_shardings(self) -> dict:
        """Per-key shardings of a ``{"x", "t"}`` batch on the tile mesh,
        matching the executor's in-specs: spatial tiles for uniform plans,
        whole maps where the executor packs ragged tiles itself, and the
        data-side layout for a hybrid plan's target."""
        plan, ax, bx = self.plan, (self.row_axis, self.col_axis), self.batch_axis
        x = P(bx, *ax, None) if plan.is_uniform else P(bx, None, None, None)
        if plan.stages:
            t = P()
        elif plan.is_uniform or plan.crossover is not None:
            t = _out_spec(plan, *ax, bx)
        else:
            t = x
        return {k: NamedSharding(self.mesh, v) for k, v in (("x", x), ("t", t))}

    def place_batch(self, batch: dict) -> dict:
        """Put a host batch on the tile mesh, under the span
        ``arch.place_batch`` with the bytes placed as ``bytes``."""
        shardings = self.batch_shardings()
        nbytes = sum(v.nbytes for v in batch.values())
        with TraceAnnotation(obs.PLACE_BATCH, bytes=nbytes):
            return {k: jax.device_put(v, shardings[k]) for k, v in batch.items()}

    def target_shape(self, batch: int) -> tuple[int, ...]:
        return (batch, *self.plan.out_hw(), self.out_channels)

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    # -- serving (DESIGN.md §13) ---------------------------------------------

    def serve_plan(self) -> StackPlan:
        """The forward-only twin of the training plan: same geometry and
        compute-path knobs, BN from frozen statistics, no training
        collectives.  Pipeline plans raise (no single-shot output layout)."""
        return self.plan.inference_twin()

    def serve_params(self, params, calibration: jax.Array):
        """Trained params + frozen BN statistics from a calibration batch -
        what ``CNNServeEngine`` / ``make_tiled_infer`` consume."""
        return freeze_bn_stats(params, self.plan.layers, calibration)

    def make_serve_engine(self, params, *, calibration=None, **engine_kw):
        """A ``CNNServeEngine`` over this arch's plan/mesh/axes.  Pass
        ``calibration`` to freeze BN stats here; otherwise ``params`` must
        already carry ``bn_mean``/``bn_var`` leaves."""
        from repro.serve.cnn_engine import CNNServeEngine

        if calibration is not None:
            params = self.serve_params(params, calibration)
        return CNNServeEngine(
            self.serve_plan(),
            self.mesh,
            params,
            row_axis=self.row_axis,
            col_axis=self.col_axis,
            **engine_kw,
        )
