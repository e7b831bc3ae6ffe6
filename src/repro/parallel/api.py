"""Logical-axis sharding API.

Models annotate activations/params with *logical* axis names; a
``ShardingRules`` table maps logical names to mesh axes.  ``constrain``
applies ``with_sharding_constraint`` only when a mesh is active and the
dimension divides the mapped axis size - otherwise that dim is left
unconstrained (e.g. 4 KV heads on a 16-way TP axis fall back to replicated,
and single-device smoke tests run the exact same model code with no mesh).

Mesh conventions (launch/mesh.py):
  single-pod   (16, 16)      axes ("data", "model")
  multi-pod    (2, 16, 16)   axes ("pod", "data", "model")
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, tuple[str, ...]]

# Logical axis -> mesh axis (or tuple of mesh axes) mapping.
DEFAULT_RULES: dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": None,              # sequence stays unsharded inside attention/mlp
    "seq_resid": "model",     # sequence-parallel residual stream BETWEEN
                              # blocks (Megatron-SP): activations/norms are
                              # seq-sharded; GSPMD inserts all-gather at the
                              # block input and reduce-scatter at its output
                              # (half the bytes of the 2x all-reduce pattern)
    "seq_shard": "model",     # long-context cache sharding (flash-decode)
    "ce_rows": ("pod", "data"),   # CE token rows: must avoid the vocab
                              # (model) axis, or GSPMD replicates the full
                              # hidden to reshard per chunk (measured 20 GiB)
    "moe_groups": ("pod", "data"),  # MoE dispatch-group dim: must stay off
                              # the expert (model) axis so the (group,
                              # expert, cap, d) buffer shards on BOTH dims;
                              # otherwise GSPMD replicates the whole buffer
                              # per layer (measured 150 GiB/layer, deepseek)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "expert_ff": None,
    "fsdp": "data",           # parameter sharding (FSDP) dimension
    "conv_tile_h": "data",    # paper-native spatial tiling axes
    "conv_tile_w": "model",
    "state": None,
}


# Named parallelism layouts (the S Perf hillclimb lever).  A layout is a
# rule-override table; models are written once against logical names.
#
#   tp-sp   baseline: FSDP(data) x TP(model) with sequence-parallel residual
#   fsdp    ZeRO-3 over ALL chips: params/optimizer sharded over
#           (data, model); activations pure batch-parallel; zero per-layer
#           activation collectives - wins for <=10B dense models where
#           batch*seq/chips stays MXU-efficient
#   ep-fsdp MoE: experts stay on "model" (EP all-to-all), everything else
#           ZeRO-3 over "data"; dense-layer activation collectives avoided
LAYOUTS: dict[str, dict[str, Axis]] = {
    "tp-sp": {},
    "fsdp": {
        "heads": None,
        "kv_heads": None,
        "ff": None,
        "vocab": "model",     # keep the CE/logits matmul vocab-sharded:
                              # unsharding it turns the LM head into a
                              # full-logits all-reduce (measured 608 GiB!)
        "experts": None,
        "seq_resid": None,
        "fsdp": ("data", "model"),
        "batch": ("pod", "data", "model"),
        "zero3": True,        # gather params at compute (gather_for_compute)
    },
    "ep-fsdp": {
        "heads": None,
        "kv_heads": None,
        "ff": None,
        "vocab": "model",
        "seq_resid": None,
        "experts": "model",
        "fsdp": ("data", "model"),
        "batch": ("pod", "data", "model"),   # tokens over ALL chips (DP x EP):
                              # dense compute 256-way; the dispatch buffer's
                              # (group, expert) grid reshards via the
                              # canonical all-to-all onto expert owners
        "zero3": True,        # dense/attn weights gathered at compute;
                              # routed expert weights stay EP-sharded
    },
}


def layout_rules(layout: str) -> dict[str, Axis]:
    return {**DEFAULT_RULES, **LAYOUTS[layout]}


class _Active(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict[str, Axis] = dict(DEFAULT_RULES)


_ACTIVE = _Active()


@contextlib.contextmanager
def sharding_ctx(mesh: Optional[Mesh], rules: Optional[dict[str, Axis]] = None):
    """Install mesh + rules; also enters the jax mesh context so bare
    PartitionSpecs resolve inside jit."""
    prev_mesh, prev_rules = _ACTIVE.mesh, _ACTIVE.rules
    _ACTIVE.mesh = mesh
    _ACTIVE.rules = {**DEFAULT_RULES, **(rules or {})}
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                yield
        else:
            yield
    finally:
        _ACTIVE.mesh, _ACTIVE.rules = prev_mesh, prev_rules


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE.mesh


def axis_size(mesh_axis: Axis) -> int:
    mesh = _ACTIVE.mesh
    if mesh is None or mesh_axis is None:
        return 1
    if isinstance(mesh_axis, str):
        return mesh.shape.get(mesh_axis, 1)
    n = 1
    for a in mesh_axis:
        n *= mesh.shape.get(a, 1)
    return n


def _resolve(logical: Sequence[Optional[str]], shape: Sequence[int]) -> P:
    """Logical names -> PartitionSpec with divisibility fallback."""
    mesh = _ACTIVE.mesh
    spec = []
    used: set[str] = set()
    for dim, name in zip(shape, logical):
        if name is None:
            spec.append(None)
            continue
        mesh_axis = _ACTIVE.rules.get(name)
        if mesh_axis is None:
            spec.append(None)
            continue
        axes = (mesh_axis,) if isinstance(mesh_axis, str) else tuple(mesh_axis)
        # drop axes already used by an earlier dim or absent from the mesh
        axes = tuple(a for a in axes if a not in used and (mesh is None or a in mesh.shape))
        size = 1
        for a in axes:
            size *= mesh.shape[a] if mesh is not None else 1
        if not axes or size == 1 or dim % size != 0:
            spec.append(None)
            continue
        used.update(axes)
        spec.append(axes[0] if len(axes) == 1 else axes)
    return P(*spec)


def logical_spec(logical: Sequence[Optional[str]], shape: Sequence[int]) -> P:
    return _resolve(logical, shape)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply with_sharding_constraint(x, resolve(logical)); no-op without a
    mesh."""
    if _ACTIVE.mesh is None:
        return x
    if len(logical) != x.ndim:
        raise ValueError(f"{logical} rank != array rank {x.shape}")
    spec = _resolve(logical, x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


def gather_for_compute(params):
    """ZeRO-3 materialisation point: under a layout with ``zero3`` set, the
    layer's parameters are constrained replicated at their use site, so
    GSPMD inserts ONE cheap weight all-gather per layer instead of running
    einsums against storage-sharded weights (which otherwise lowers into
    partial matmuls + per-layer activation-sized all-reduces - measured
    2 GiB/layer/step on qwen2-7b).  Inside lax.scan the gather depends on
    the loop slice, so XLA cannot hoist it: peak memory stays one layer."""
    if _ACTIVE.mesh is None or not _ACTIVE.rules.get("zero3"):
        return params
    from jax.tree_util import tree_map_with_path
    from repro.compat import keystr_slash

    def leaf(path, p):
        key = keystr_slash(path)
        # routed expert weights stay in their EP (experts-axis) layout:
        # the MoE einsum is batched over the expert dim, never gathered
        if "moe" in key and p.ndim == 3:
            return p
        return jax.lax.with_sharding_constraint(p, P(*([None] * p.ndim)))

    return tree_map_with_path(leaf, params)


def named_sharding(logical: Sequence[Optional[str]], shape: Sequence[int]) -> Optional[NamedSharding]:
    mesh = _ACTIVE.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, _resolve(logical, shape))
