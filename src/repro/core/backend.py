"""Pluggable conv-compute backends for the tiled executor (DESIGN.md §4).

The distributed pipeline separates *where* data lives (planner: tiling,
grouping, halo widths) and *how* boundary data moves (executor: ppermute
halo exchange, off-map masking, cross-tile BN) from *how the conv math
runs on one tile*.  That last piece is this registry: a backend computes
the VALID (un-padded) 2-D convolution of a halo-extended NHWC tile with an
HWIO filter, adds the bias when one is given, and may fuse the activations
listed in its ``fused_acts`` - the executor applies any activation a
backend cannot fuse, and always applies batch norm itself (BN needs
cross-tile psums the backend never sees).

Contract (DESIGN.md §4):
  fn(x, w, b, *, stride, act[, block_oh]) -> y
    x: (N, H, W, Cin) halo-extended local tile     w: (K, K, Cin, Cout)
    b: (Cout,) or None                             y: (N, OH, OW, Cout)
  - VALID padding only; halo delivery is the executor's job.  This is what
    keeps every backend usable on *unhaloed full maps* too: a data-mode
    layer (DESIGN.md §7) has no neighbours, so the executor materialises
    the SAME-conv boundary locally with ``pad_for_valid`` and the backend
    still sees its one contract shape - an extended NHWC slab to convolve
    VALID, whether the extension arrived by ppermute or by jnp.pad.
  - Must be differentiable, and MAY ship its own VJP: ``jax.grad`` through
    the executor derives the paper's backward pass (rotated-filter delta
    conv, reversed halo exchange, per-tile weight-grad partial sums), and a
    backend is free to implement the per-tile dgrad/wgrad itself instead of
    relying on XLA transposition - the Pallas backend runs its own backward
    kernels (kernels/conv2d_tiled/backward.py, DESIGN.md §6), so with
    ``backend="pallas"`` a train step contains no XLA transpose-conv
    fallback.  A backend VJP must produce cotangents exact vs. the ``xla``
    transpose to float tolerance (the executor's gradient suites check
    this per backend x schedule).
  - ``block_oh`` (optional kwarg, planner-controlled via
    ``StackPlan.block_oh``) re-tiles the compute's output-row blocking; a
    backend without spatial blocking accepts and ignores it.
  - Must be exact vs. the ``xla`` oracle to float tolerance; the tiled
    exactness suites run against every registered backend.
  - Mixed precision follows XLA promotion: y.dtype ==
    ``jnp.result_type(x.dtype, w.dtype)`` (bf16 activations with fp32
    filters produce fp32).

``xla`` (default) lowers to ``lax.conv_general_dilated``.  ``pallas`` runs
the direct MXU kernel in ``kernels/conv2d_tiled`` - forward AND backward -
in interpret mode on the CPU backend (so CI exercises the same code path)
and compiled everywhere else: on an accelerator the kernel compiles or
raises, never silently interprets (``pallas_interpret``).
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

Activation = Callable[[jax.Array], jax.Array]

ACTIVATIONS: dict[str, Activation] = {
    "linear": lambda x: x,
    "relu": jax.nn.relu,
    "leaky": lambda x: jnp.where(x > 0, x, 0.1 * x),  # darknet leaky slope
    "gelu": jax.nn.gelu,
}

ConvFn = Callable[..., jax.Array]


def pad_for_valid(x: jax.Array, pad: int, *, pool: bool = False) -> jax.Array:
    """Materialise SAME-conv boundary semantics locally so a VALID-only
    backend runs on an unhaloed full map (data-mode layers, DESIGN.md §7).

    Zeros for convolutions (identical to the zero strips ``ppermute``
    delivers to edge tiles on the spatial path) and -inf for max pools
    (``lax.reduce_window``'s init value, matching the untiled reference).
    """
    if pad == 0:
        return x
    cfg = ((0, 0), (pad, pad), (pad, pad), (0, 0))
    if pool:
        return jnp.pad(x, cfg, constant_values=-jnp.inf)
    return jnp.pad(x, cfg)


@dataclasses.dataclass(frozen=True)
class ConvBackend:
    """One registered conv compute path (see module docstring contract)."""

    name: str
    fn: ConvFn
    fused_acts: frozenset[str]
    accepts_block_oh: bool = True

    def __call__(
        self,
        x: jax.Array,
        w: jax.Array,
        b: Optional[jax.Array],
        *,
        stride: int,
        act: str,
        block_oh: Optional[int] = None,
    ) -> jax.Array:
        # block_oh is only forwarded when set, so simple backends whose fn
        # lacks the kwarg keep working with the auto default.
        if block_oh is None:
            return self.fn(x, w, b, stride=stride, act=act)
        if not self.accepts_block_oh:
            raise ValueError(
                f"conv backend {self.name!r} does not accept block_oh; "
                "add a block_oh kwarg to its fn (ignoring it is fine) or "
                "build the plan with block_oh=None"
            )
        return self.fn(x, w, b, stride=stride, act=act, block_oh=block_oh)


_REGISTRY: dict[str, ConvBackend] = {}


def register_conv_backend(
    name: str, fn: ConvFn, *, fused_acts: tuple[str, ...] = ("linear",)
) -> ConvBackend:
    # Probe the signature once at registration: pre-contract backends
    # (fn(x, w, b, *, stride, act)) still register and run, but a plan that
    # sets block_oh gets a clear per-backend error instead of an opaque
    # TypeError deep inside shard_map tracing.
    try:
        sig = inspect.signature(fn)
        accepts = "block_oh" in sig.parameters or any(
            p.kind is inspect.Parameter.VAR_KEYWORD for p in sig.parameters.values()
        )
    except (TypeError, ValueError):    # builtins/partials without signatures
        accepts = True
    be = ConvBackend(name, fn, frozenset(fused_acts), accepts_block_oh=accepts)
    _REGISTRY[name] = be
    return be


def get_conv_backend(name: str) -> ConvBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown conv backend {name!r}; registered: {conv_backend_names()}"
        ) from None


def conv_backend_names() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# xla: the oracle path (lax.conv_general_dilated)
# ---------------------------------------------------------------------------


def _xla_conv(x, w, b, *, stride: int, act: str, block_oh: int | None = None) -> jax.Array:
    # block_oh is a spatial-blocking hint; XLA has no exposed tiling knob,
    # so it is accepted (contract) and ignored.
    # lax.conv_general_dilated rejects mixed dtypes; promote explicitly so
    # bf16 activations x fp32 filters follow numpy promotion (fp32 out),
    # the semantics the contract pins for every backend.
    dt = jnp.result_type(x.dtype, w.dtype)
    y = lax.conv_general_dilated(
        x.astype(dt),
        w.astype(dt),
        window_strides=(stride, stride),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if b is not None:
        y = y + b
    return ACTIVATIONS[act](y)


register_conv_backend("xla", _xla_conv, fused_acts=tuple(ACTIVATIONS))


# ---------------------------------------------------------------------------
# pallas: the direct MXU kernel (kernels/conv2d_tiled)
# ---------------------------------------------------------------------------


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU backend only."""
    return jax.default_backend() == "cpu"


def _pallas_conv(
    x, w, b, *, stride: int, act: str, block_oh: int | None = None
) -> jax.Array:
    from repro.kernels.conv2d_tiled.ops import conv2d

    if b is None:
        # custom_vjp differentiates (x, w, b); a zero bias keeps the
        # signature uniform and its (discarded) gradient costs nothing.
        # The conv *result* dtype (promoted), not x.dtype: under mixed
        # precision (bf16 activations, fp32 filters) the epilogue must add
        # the bias at the promoted precision, matching the xla backend.
        b = jnp.zeros((w.shape[-1],), jnp.result_type(x.dtype, w.dtype))
    return conv2d(x, w, b, stride, 0, act, pallas_interpret(), block_oh)


register_conv_backend("pallas", _pallas_conv, fused_acts=("linear", "relu", "leaky"))
