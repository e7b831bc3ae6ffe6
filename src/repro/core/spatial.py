"""Tiled spatial (H x W) convolution / pooling primitives (paper §4.1).

Layout convention: NHWC activations, HWIO filters (TPU-native).  The global
feature map is sharded over two named mesh axes (tile rows / tile cols) on the
H and W dimensions; each device holds one tile, fused across layers (paper's
"execution stacks" are simply SPMD shards that never migrate).

Halo algebra (derivation recorded in DESIGN.md): for a layer with kernel K,
stride S and symmetric padding P, when every shard satisfies
``in_shard == out_shard * S`` the shard-level halo is

    halo_lo = P            halo_hi = K - S - P

and a local VALID convolution over the halo-extended tile reproduces the
global padded convolution exactly.  ``ppermute`` delivers zeros to edge tiles,
which *is* the zero padding of the global conv - no edge special-casing.

The backward pass is never hand-written: ``jax.grad`` through these functions
yields the paper's rotated-filter delta propagation (transposed conv), the
reversed halo exchange (ppermute transpose), and the per-tile weight-gradient
partial sums + cross-tile summation (psum inserted by shard_map transposition
for replicated filter operands).  Tests assert exactness vs. the untiled
oracle to float tolerance.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core.tiling import ConvSpec
from repro.core.halo import (
    WireCtx,
    halo_exchange_2d,
    halo_exchange_1d_packed,
)
from repro.optim.compression import ef_encode
from repro.core.backend import (
    ACTIVATIONS as _ACTIVATIONS,
    Activation,
    get_conv_backend,
    pad_for_valid,
)

# ---------------------------------------------------------------------------
# Layer definitions (geometry + compute attributes)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerDef:
    """One conv, pool or shortcut layer of a spatial stack.

    A shortcut (``shortcut < 0``, darknet's ``[shortcut] from=``) adds the
    output of the layer ``-shortcut`` places back to its input, the previous
    layer's output; it is linear, has no parameters and no halo (K=1, S=1,
    P=0). Build one with ``shortcut_layer``."""

    kernel: int
    stride: int = 1
    in_channels: int = 0
    out_channels: int = 0
    pool: bool = False           # max-pool (no params) if True
    pad: int | None = None       # symmetric padding; default K//2 conv, 0 pool
    act: str = "leaky"
    use_bias: bool = True
    batch_norm: bool = False     # BN w/ exact cross-tile statistics
    shortcut: int = 0            # darknet's from= offset (< 0); 0: not a shortcut

    @property
    def is_conv(self) -> bool:
        """A layer with filters: neither a pool nor a shortcut."""
        return not (self.pool or self.shortcut)

    @property
    def padding(self) -> int:
        if self.pad is not None:
            return self.pad
        return 0 if self.pool else self.kernel // 2

    @property
    def halo(self) -> tuple[int, int]:
        lo = self.padding
        hi = self.kernel - self.stride - lo
        if hi < 0:
            raise ValueError(
                f"unsupported geometry K={self.kernel} S={self.stride} P={lo}"
            )
        return lo, hi

    def spec(self) -> ConvSpec:
        return ConvSpec(
            kernel=self.kernel,
            stride=self.stride,
            in_channels=self.in_channels,
            out_channels=self.out_channels,
            pool=self.pool,
        )

    def out_extent(self, h: int) -> int:
        return (h + 2 * self.padding - self.kernel) // self.stride + 1


def shortcut_layer(channels: int, offset: int = -3) -> LayerDef:
    """darknet's ``[shortcut] from=<offset> activation=linear``: the
    previous layer's output plus the output ``-offset`` layers back."""
    if offset >= 0:
        raise ValueError(f"a shortcut reads an earlier layer: offset must be < 0, got {offset}")
    return LayerDef(1, 1, channels, channels, pad=0, act="linear", use_bias=False,
                    shortcut=offset)


def shortcut_sources(layers: Sequence[LayerDef]) -> dict[int, int]:
    """``{shortcut layer: the layer whose output it adds}``; -1 names the
    stack input."""
    return {i: i + l.shortcut for i, l in enumerate(layers) if l.shortcut}


def check_shortcuts(layers: Sequence[LayerDef], map_hw: Sequence[tuple[int, int]]) -> None:
    """Each shortcut adds a map of its input's shape, made at the same
    resolution: the source exists, every layer between keeps the stride at
    1, and extents and channels agree. ``map_hw[l]`` is layer l's input
    extent."""
    for l, src in shortcut_sources(layers).items():
        name = f"shortcut layer {l} (from={layers[l].shortcut})"
        if not -1 <= src < l:
            raise ValueError(f"{name} reads layer {src}: an earlier layer or the stack input only")
        src_ch = layers[0].in_channels if src < 0 else layers[src].out_channels
        lay = layers[l]
        if not (src_ch == lay.in_channels == lay.out_channels == layers[l - 1].out_channels):
            raise ValueError(
                f"{name} adds {src_ch} channels from layer {src} to "
                f"{layers[l - 1].out_channels} from layer {l - 1}; they must agree"
            )
        if any(layers[k].stride != 1 for k in range(src + 1, l)) or map_hw[src + 1] != map_hw[l]:
            raise ValueError(
                f"{name} adds layer {src}'s {map_hw[src + 1]} map to layer "
                f"{l - 1}'s {map_hw[l]}: the layers between must keep stride 1"
            )


def init_layer_params(key: jax.Array, layer: LayerDef, dtype=jnp.float32) -> dict:
    """He-initialised conv params; empty dict for pools and shortcuts."""
    if not layer.is_conv:
        return {}
    k = layer.kernel
    fan_in = k * k * layer.in_channels
    wkey, _ = jax.random.split(key)
    params = {
        "w": jax.random.normal(wkey, (k, k, layer.in_channels, layer.out_channels), dtype)
        * jnp.sqrt(2.0 / fan_in).astype(dtype)
    }
    if layer.use_bias:
        params["b"] = jnp.zeros((layer.out_channels,), dtype)
    if layer.batch_norm:
        params["bn_scale"] = jnp.ones((layer.out_channels,), dtype)
        params["bn_bias"] = jnp.zeros((layer.out_channels,), dtype)
    return params


def init_stack_params(key: jax.Array, layers: Sequence[LayerDef], dtype=jnp.float32) -> list[dict]:
    keys = jax.random.split(key, len(layers))
    return [init_layer_params(k, l, dtype) for k, l in zip(keys, layers)]


# ---------------------------------------------------------------------------
# Untiled reference (the oracle every tiled path is tested against)
# ---------------------------------------------------------------------------


def conv2d_same(x: jax.Array, w: jax.Array, stride: int, pad: int) -> jax.Array:
    return lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def maxpool2d(x: jax.Array, kernel: int, stride: int, pad: int) -> jax.Array:
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        window_dimensions=(1, kernel, kernel, 1),
        window_strides=(1, stride, stride, 1),
        padding=((0, 0), (pad, pad), (pad, pad), (0, 0)),
    )


def _bn_apply(x, mean, var, scale, bias, eps=1e-5):
    inv = lax.rsqrt(var + eps)
    return (x - mean) * inv * scale + bias


def _bn_infer(y: jax.Array, params: dict, layer: LayerDef) -> jax.Array:
    """Inference-mode BN: normalise with the *frozen* running statistics
    stored in the params (``bn_mean`` / ``bn_var``) instead of computing
    cross-device batch statistics - the forward-only executor's replacement
    for ``_bn_tiled``'s psums (DESIGN.md §13).  Purely elementwise, so it
    is safe on padded/garbage slots and needs no collective."""
    if "bn_mean" not in params or "bn_var" not in params:
        raise ValueError(
            "inference plan needs frozen BN statistics: params lack "
            "bn_mean/bn_var - attach them with freeze_bn_stats(params, "
            "layers, calibration_batch) before building the serve step"
        )
    return _bn_apply(
        y, params["bn_mean"], params["bn_var"],
        params["bn_scale"], params["bn_bias"],
    )


def apply_layer_reference(
    x: jax.Array,
    params: dict,
    layer: LayerDef,
    *,
    inference: bool = False,
    skip: jax.Array | None = None,
) -> jax.Array:
    """Global (untiled) forward of one layer - the exactness oracle.

    ``inference=True`` applies BN from the frozen ``bn_mean``/``bn_var``
    params (serving semantics) instead of the batch statistics. A shortcut
    adds ``skip``, the output of its source layer."""
    if layer.shortcut:
        return x + skip
    p = layer.padding
    if layer.pool:
        return maxpool2d(x, layer.kernel, layer.stride, p)
    y = conv2d_same(x, params["w"], layer.stride, p)
    if layer.use_bias:
        y = y + params["b"]
    if layer.batch_norm:
        if inference:
            y = _bn_infer(y, params, layer)
        else:
            mean = jnp.mean(y, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
            y = _bn_apply(y, mean, var, params["bn_scale"], params["bn_bias"])
    return _ACTIVATIONS[layer.act](y)


def stack_reference(
    x: jax.Array,
    params: Sequence[dict],
    layers: Sequence[LayerDef],
    *,
    inference: bool = False,
) -> jax.Array:
    skips = SkipStore(layers)
    skips.keep(-1, x)
    for i, (p, l) in enumerate(zip(params, layers)):
        x = apply_layer_reference(x, p, l, inference=inference, skip=skips.read(i)[0])
        skips.keep(i, x)
    return x


class SkipStore:
    """The outputs that the shortcuts of ``layers`` read, each with the halo
    it carries, from the layer that makes them to their last read. A chain
    keeps nothing."""

    def __init__(self, layers: Sequence[LayerDef]):
        self.sources = shortcut_sources(layers)
        self.last: dict[int, int] = {}
        for l, src in self.sources.items():
            self.last[src] = max(self.last.get(src, l), l)
        self.kept: dict[int, tuple] = {}

    def keep(self, layer: int, x, halo=(0, 0, 0, 0)) -> None:
        """Keep layer ``layer``'s output (-1: the stack input) if a later
        shortcut reads it."""
        if self.last.get(layer, layer) > layer:
            self.kept[layer] = (x, halo)

    def read(self, layer: int):
        """``(skip, halo)`` for layer ``layer``, ``(None, zeros)`` unless it
        is a shortcut; drops each skip after its last read."""
        src = self.sources.get(layer)
        if src is None:
            return None, (0, 0, 0, 0)
        skip = self.kept[src]
        if self.last[src] == layer:
            del self.kept[src]
        return skip


def freeze_bn_stats(
    params: Sequence[dict], layers: Sequence[LayerDef], x: jax.Array
) -> list[dict]:
    """Attach frozen BN statistics to a trained param stack (DESIGN.md §13).

    Returns a copy of ``params`` where every BN layer gains ``bn_mean`` /
    ``bn_var`` set to the batch statistics of the calibration batch ``x``
    pushed through the (training-mode) reference forward.  With the same
    batch fed to both, the inference forward then reproduces the training
    forward exactly - the equivalence the serve acceptance gate asserts.
    In production the stats would instead be EMA running statistics
    accumulated during training; the inference executor only reads the two
    leaves, so either source works."""
    out, skips = [], SkipStore(layers)
    skips.keep(-1, x)
    for i, (p, l) in enumerate(zip(params, layers)):
        p = dict(p)
        if l.shortcut:
            x = apply_layer_reference(x, p, l, skip=skips.read(i)[0])
        elif l.batch_norm and not l.pool:
            y = conv2d_same(x, p["w"], l.stride, l.padding)
            if l.use_bias:
                y = y + p["b"]
            # Same centered formulation as the untiled training reference,
            # so frozen-stats inference reproduces `stack_reference`'s
            # training forward bit-for-bit (the tiled executors then agree
            # to the usual tiled-vs-untiled float tolerance).
            mean = jnp.mean(y, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
            p["bn_mean"], p["bn_var"] = mean, var
            # downstream layers must see the exact training activations, so
            # finish this layer with the frozen (= batch) stats
            x = apply_layer_reference(x, p, l, inference=True)
        else:
            x = apply_layer_reference(x, p, l)
        out.append(p)
        skips.keep(i, x)
    return out


# ---------------------------------------------------------------------------
# Tiled (shard-local) compute.  Everything below runs INSIDE shard_map.
# ---------------------------------------------------------------------------


def _valid_pool(x, kernel, stride):
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        window_dimensions=(1, kernel, kernel, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _pool_nonoverlap(x: jax.Array, k: int) -> jax.Array:
    """VALID max-pool for the non-overlapping case (kernel == stride) with a
    vectorized reshape/argmax VJP.

    The ``reduce_window`` backward is a ``select_and_scatter``, which XLA
    CPU lowers to a fast vectorized form at top level but to a per-element
    scalar while loop inside ``lax.switch``/``cond`` branches - the spec
    executor's big-tile branch then spends more time scattering pool
    cotangents than convolving (same conditional blindness as the conv
    canonicalization pass, see ``_conv_valid_s1``).  Ties scatter to the
    first window element in row-major scan order, exactly matching
    ``select_and_scatter``'s first-match semantics (``argmax`` also returns
    the first maximum).
    """
    n, h, w, c = x.shape
    ho, wo = h // k, w // k
    xw = x[:, : ho * k, : wo * k, :].reshape(n, ho, k, wo, k, c)
    return xw.max(axis=(2, 4))


def _pool_nonoverlap_fwd(x, k):
    return _pool_nonoverlap(x, k), x


def _pool_nonoverlap_bwd(k, x, dy):
    n, h, w, c = x.shape
    ho, wo = h // k, w // k
    xw = x[:, : ho * k, : wo * k, :].reshape(n, ho, k, wo, k, c)
    elems = jnp.transpose(xw, (0, 1, 3, 5, 2, 4)).reshape(n, ho, wo, c, k * k)
    am = jnp.argmax(elems, axis=-1)
    onehot = (am[..., None] == jnp.arange(k * k)).astype(dy.dtype)
    dxe = onehot * dy[..., None]
    dx = jnp.transpose(
        dxe.reshape(n, ho, wo, c, k, k), (0, 1, 4, 2, 5, 3)
    ).reshape(n, ho * k, wo * k, c)
    if ho * k != h or wo * k != w:
        dx = jnp.pad(dx, ((0, 0), (0, h - ho * k), (0, w - wo * k), (0, 0)))
    return (dx,)


_pool_nonoverlap.defvjp(_pool_nonoverlap_fwd, _pool_nonoverlap_bwd)


def _offmap_mask(
    ext_h: int,
    ext_w: int,
    halo: tuple[int, int, int, int],
    shard_hw: tuple[int, int],
    map_hw: tuple[int, int],
    row_axis: str,
    col_axis: str,
) -> jax.Array:
    """(ext_h, ext_w) 0/1 mask of positions inside the true map bounds.

    Grouped execution computes values at off-map positions of intermediate
    layers; the untiled oracle treats those positions as zero padding, so we
    zero them before they feed the next conv (exactness requirement discussed
    in DESIGN.md §2).
    """
    i = lax.axis_index(row_axis)
    j = lax.axis_index(col_axis)
    row0 = i * shard_hw[0] - halo[0]
    col0 = j * shard_hw[1] - halo[2]
    rows = row0 + lax.iota(jnp.int32, ext_h)
    cols = col0 + lax.iota(jnp.int32, ext_w)
    rmask = (rows >= 0) & (rows < map_hw[0])
    cmask = (cols >= 0) & (cols < map_hw[1])
    return (rmask[:, None] & cmask[None, :]).astype(jnp.float32)


def _core_mask(
    ext_h: int,
    ext_w: int,
    halo: tuple[int, int, int, int],
) -> jax.Array:
    """Mask selecting the core (owned) region of a halo-extended tile."""
    top, bottom, left, right = halo
    rmask = (lax.iota(jnp.int32, ext_h) >= top) & (lax.iota(jnp.int32, ext_h) < ext_h - bottom)
    cmask = (lax.iota(jnp.int32, ext_w) >= left) & (lax.iota(jnp.int32, ext_w) < ext_w - right)
    return (rmask[:, None] & cmask[None, :]).astype(jnp.float32)


def _masked_batch_stats(y, mask, axes, n_global):
    """Per-channel batch mean and variance over the masked (owned)
    positions of every tile: one psum for the mean, a second over values
    centred on it - the reference's formulation.  The one-pass
    E[y^2] - E[y]^2 is exact in real arithmetic, but in fp32 its backward
    cancels large terms and adds an error of eps * mean * n to each
    position's cotangent; summed over a 416x416 map by the next wgrad,
    that error swamps the weight gradient."""
    mean = lax.psum(jnp.sum(y * mask, axis=(0, 1, 2)), axes) / n_global
    var = lax.psum(jnp.sum(jnp.square((y - mean) * mask), axis=(0, 1, 2)), axes) / n_global
    return mean, var


def _bn_tiled(y, layer, params, core_halo, tile_axes, n_global):
    """Exact cross-tile batch norm: statistics over core (owned) positions
    only - overlap/halo regions are duplicated across tiles and must not be
    double counted - reduced with psum over the tile axes."""
    ext_h, ext_w = y.shape[1], y.shape[2]
    mask = _core_mask(ext_h, ext_w, core_halo)[None, :, :, None]
    mean, var = _masked_batch_stats(y, mask, tile_axes, n_global)
    return _bn_apply(y, mean, var, params["bn_scale"], params["bn_bias"])


def apply_layer_local(
    x: jax.Array,
    params: dict,
    layer: LayerDef,
    *,
    out_halo: tuple[int, int, int, int],
    shard_out_hw: tuple[int, int],
    map_out_hw: tuple[int, int],
    row_axis: str,
    col_axis: str,
    batch_global: int,
    mask_offmap: bool,
    backend: str = "xla",
    batch_axis: str | None = None,
    block_oh: int | None = None,
    inference: bool = False,
    skip: jax.Array | None = None,
    skip_halo: tuple[int, int, int, int] = (0, 0, 0, 0),
) -> jax.Array:
    """One layer on a halo-extended local tile (input halo already present).

    out_halo: remaining halo on the produced output (0s when the layer is the
    last of its group).  mask_offmap zeroes off-map positions when the output
    still carries halo that a later layer will consume.  ``backend`` names
    the registered conv compute path (core.backend); ``block_oh`` is the
    planner's output-row VMEM block, forwarded to the backend.  BN and any
    activation the backend cannot fuse stay here, since BN needs cross-tile
    psums (over the batch mesh axis too, when one is present) - unless
    ``inference=True``, which swaps in the collective-free frozen-stats BN.

    A shortcut adds ``skip``, its source's tile, which carries ``skip_halo``
    around the same core and is cropped to ``out_halo`` (DESIGN.md §16).
    Both addends are zero off the map, so the sum needs no mask.
    """
    if layer.shortcut:
        return add_shortcut(x, skip, skip_halo, out_halo)
    y, fused = _conv_or_pool(x, params, layer, backend, block_oh)
    return _finish_layer(
        y,
        params,
        layer,
        fused=fused,
        out_halo=out_halo,
        shard_out_hw=shard_out_hw,
        map_out_hw=map_out_hw,
        row_axis=row_axis,
        col_axis=col_axis,
        batch_global=batch_global,
        mask_offmap=mask_offmap,
        batch_axis=batch_axis,
        inference=inference,
    )


def add_shortcut(
    x: jax.Array,
    skip: jax.Array,
    skip_halo: tuple[int, int, int, int],
    out_halo: tuple[int, int, int, int],
) -> jax.Array:
    """``x + skip``, with ``skip`` first cropped from its halo to ``x``'s,
    under the named scope ``shortcut``."""
    with jax.named_scope(obs.SHORTCUT):
        top, bottom, left, right = (s - o for s, o in zip(skip_halo, out_halo))
        if min(top, bottom, left, right) < 0:
            raise ValueError(
                f"a skip with halo {skip_halo} cannot be cropped to {out_halo}"
            )
        return x + skip[:, top:skip.shape[1] - bottom, left:skip.shape[2] - right, :]


@jax.custom_vjp
def _conv_valid_s1(x: jax.Array, w: jax.Array) -> jax.Array:
    """Stride-1 VALID NHWC conv whose VJP emits dgrad/wgrad in canonical
    NHWC form (explicit operand transposes at the JAX level).

    The standard transpose-rule forms (batch as the contracting dimension
    for wgrad, transposed kernel for dgrad) rely on XLA's conv
    canonicalization pass to reach the fast Eigen path - but that pass does
    not rewrite convolutions inside ``lax.switch``/``cond`` branch
    computations, where the shape-specialized ragged executor (DESIGN.md
    §9) places every per-tile conv.  Left raw, each branch wgrad runs on
    the slow generic path (~7x measured on CPU) and every shard then waits
    for the slowest at the gradient psum.  Hand-emitting the canonical
    forms keeps the backward on the fast path regardless of nesting.
    """
    dt = jnp.result_type(x.dtype, w.dtype)
    return lax.conv_general_dilated(
        x.astype(dt),
        w.astype(dt),
        window_strides=(1, 1),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _conv_valid_s1_fwd(x, w):
    return _conv_valid_s1(x, w), (x, w)


def _conv_valid_s1_bwd(res, dy):
    x, w = res
    dt = jnp.result_type(x.dtype, w.dtype)
    xp, wp, dyp = x.astype(dt), w.astype(dt), dy.astype(dt)
    kh, kw = w.shape[0], w.shape[1]
    # dgrad: full-padded conv of dy with the spatially-flipped, IO-swapped
    # kernel - a plain forward-form conv, fast even inside a branch
    wt = jnp.transpose(jnp.flip(wp, (0, 1)), (0, 1, 3, 2))
    dx = lax.conv_general_dilated(
        dyp, wt, (1, 1), ((kh - 1, kh - 1), (kw - 1, kw - 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    # wgrad: channels-as-batch / batch-as-feature conv, again forward-form
    xt = jnp.transpose(xp, (3, 1, 2, 0))       # (Ci, H, W, N)
    dyt = jnp.transpose(dyp, (1, 2, 0, 3))     # (Oh, Ow, N, Co) as kernel
    dw = jnp.transpose(
        lax.conv_general_dilated(
            xt, dyt, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ),
        (1, 2, 0, 3),                          # (Ci, Kh, Kw, Co) -> HWIO
    )
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv_valid_s1.defvjp(_conv_valid_s1_fwd, _conv_valid_s1_bwd)


def _conv_or_pool(
    x: jax.Array,
    params: dict,
    layer: LayerDef,
    backend: str,
    block_oh: int | None = None,
) -> tuple[jax.Array, bool]:
    """VALID conv/pool of one (sub-)slab through the backend registry.

    Returns ``(y, fused)`` where ``fused`` says the activation was applied by
    the backend.  The decision depends only on (layer, backend), so splitting
    a tile into slabs and applying this per slab is exact.  Runs under the
    named scope ``pool`` or ``conv`` (``_compute_scope``).
    """
    with _compute_scope(layer):
        if layer.pool:
            return _valid_pool(x, layer.kernel, layer.stride), False
        be = get_conv_backend(backend)
        fused = (not layer.batch_norm) and layer.act in be.fused_acts
        b = params["b"] if layer.use_bias else None
        y = be(x, params["w"], b, stride=layer.stride,
               act=layer.act if fused else "linear", block_oh=block_oh)
        return y, fused


def _compute_scope(layer: LayerDef):
    """The named scope of a layer's conv or pool compute (``repro.obs``)."""
    return jax.named_scope(obs.POOL if layer.pool else obs.CONV)


def _conv_or_pool_spec(
    x: jax.Array,
    params: dict,
    layer: LayerDef,
    backend: str,
    block_oh: int | None = None,
) -> tuple[jax.Array, bool]:
    """Branch-safe ``_conv_or_pool`` for the spec executor's switch branches.

    Stride-1 xla convs route through ``_conv_valid_s1`` so their backward
    convs stay in canonical (fast-path) form inside ``lax.switch`` branches;
    everything else (pools, strided convs, non-xla backends) defers to the
    regular path, whose backward either has no conv or is a backend custom
    kernel already.
    """
    if layer.pool:
        if layer.kernel == layer.stride:
            with _compute_scope(layer):
                return _pool_nonoverlap(x, layer.kernel), False
        return _conv_or_pool(x, params, layer, backend, block_oh)
    if backend != "xla" or layer.stride != 1:
        return _conv_or_pool(x, params, layer, backend, block_oh)
    fused = (not layer.batch_norm) and layer.act in get_conv_backend(backend).fused_acts
    with _compute_scope(layer):
        y = _conv_valid_s1(x, params["w"])
        if layer.use_bias:
            y = y + params["b"]
        if fused:
            y = _ACTIVATIONS[layer.act](y)
    return y, fused


def _finish_layer(
    y: jax.Array,
    params: dict,
    layer: LayerDef,
    *,
    fused: bool,
    out_halo: tuple[int, int, int, int],
    shard_out_hw: tuple[int, int],
    map_out_hw: tuple[int, int],
    row_axis: str,
    col_axis: str,
    batch_global: int,
    mask_offmap: bool,
    batch_axis: str | None,
    inference: bool = False,
) -> jax.Array:
    """Post-conv tail shared by the sync and overlap executors, under the
    named scope ``bn``: cross-tile BN (frozen-stats BN for inference plans -
    no psum), unfused activation, off-map masking."""
    with jax.named_scope(obs.BN):
        if layer.batch_norm and not layer.pool:
            if inference:
                y = _bn_infer(y, params, layer)
            else:
                n_global = batch_global * map_out_hw[0] * map_out_hw[1]
                bn_axes = (row_axis, col_axis)
                if batch_axis is not None:
                    bn_axes = (batch_axis,) + bn_axes
                y = _bn_tiled(y, layer, params, out_halo, bn_axes, n_global)
        if not fused:
            y = _ACTIVATIONS[layer.act](y)
        if mask_offmap and any(h > 0 for h in out_halo):
            m = _offmap_mask(
                y.shape[1], y.shape[2], out_halo, shard_out_hw, map_out_hw, row_axis, col_axis
            )
            y = y * m[None, :, :, None].astype(y.dtype)
        return y


# ---------------------------------------------------------------------------
# Ragged (non-uniform partition) execution: padded-to-max tiles + validity
# masks (DESIGN.md §8).  Everything below runs INSIDE shard_map.
# ---------------------------------------------------------------------------


def _fit_extent(y: jax.Array, target_hw: tuple[int, int], dims: tuple[int, int] = (1, 2)) -> jax.Array:
    """Pad (zeros) or slice ``y`` to the canonical static extent the next
    ragged layer expects.  Rows/cols beyond every tile's valid count are
    garbage-or-zero either way and are re-zeroed by the validity mask."""
    for d, tgt in zip(dims, target_hw):
        cur = y.shape[d]
        if cur > tgt:
            y = lax.slice_in_dim(y, 0, tgt, axis=d)
        elif cur < tgt:
            pad = [(0, 0)] * y.ndim
            pad[d] = (0, tgt - cur)
            y = jnp.pad(y, pad)
    return y


def _ragged_mask(
    ext_h: int,
    ext_w: int,
    halo: tuple[int, int, int, int],
    out_size: tuple[jax.Array, jax.Array],
    out_off: tuple[jax.Array, jax.Array],
    map_hw: tuple[int, int],
) -> jax.Array:
    """0/1 mask over a ragged tile's canonical (padded) extended output.

    A position survives iff it is (a) inside this tile's *valid* window -
    rows [0, top + own_i + bottom) of the padded layout, the rest being
    pad slots other tiles own - and (b) inside the true map bounds (the
    off-map condition of `_offmap_mask`, with the tile origin read from the
    boundary table instead of i * shard).  Zeroing both restores the
    padded-tile invariant (pad slots exactly zero) that the halo exchange,
    BN statistics, loss sums, and AD-derived weight-gradient partial sums
    all rely on."""
    top, bottom, left, right = halo
    oh_i, ow_j = out_size
    r0, c0 = out_off
    rows = lax.iota(jnp.int32, ext_h)
    cols = lax.iota(jnp.int32, ext_w)
    gr = r0 - top + rows
    gc = c0 - left + cols
    rmask = (rows < top + oh_i + bottom) & (gr >= 0) & (gr < map_hw[0])
    cmask = (cols < left + ow_j + right) & (gc >= 0) & (gc < map_hw[1])
    return (rmask[:, None] & cmask[None, :]).astype(jnp.float32)


def _core_mask_ragged(
    ext_h: int,
    ext_w: int,
    halo: tuple[int, int, int, int],
    out_size: tuple[jax.Array, jax.Array],
) -> jax.Array:
    """Core (owned) region of a ragged halo-extended tile: rows
    [top, top + own_i), cols [left, left + own_j)."""
    top, _, left, _ = halo
    oh_i, ow_j = out_size
    rows = lax.iota(jnp.int32, ext_h)
    cols = lax.iota(jnp.int32, ext_w)
    rmask = (rows >= top) & (rows < top + oh_i)
    cmask = (cols >= left) & (cols < left + ow_j)
    return (rmask[:, None] & cmask[None, :]).astype(jnp.float32)


def apply_layer_local_ragged(
    x: jax.Array,
    params: dict,
    layer: LayerDef,
    *,
    out_halo: tuple[int, int, int, int],
    out_size: tuple[jax.Array, jax.Array],
    out_off: tuple[jax.Array, jax.Array],
    canon_out_hw: tuple[int, int],
    map_out_hw: tuple[int, int],
    row_axis: str,
    col_axis: str,
    batch_global: int,
    batch_axis: str | None = None,
    backend: str = "xla",
    block_oh: int | None = None,
    inference: bool = False,
) -> jax.Array:
    """One layer of a ragged (non-uniform partition) tile.

    ``x`` is the canonical padded extended input (valid window [0, lo +
    own_i + hi), zeros beyond); the VALID conv produces every tile's valid
    outputs in rows [0, lo' + own_out_i + hi') (windows of valid outputs
    read only valid-or-correct-zero positions - the padded-tile invariant +
    stride-aligned boundaries guarantee it, DESIGN.md §8), then the output
    is refit to the canonical static extent and masked: BN statistics over
    the ragged core only, and the combined validity/off-map mask re-zeroes
    pad slots so the invariant holds for the next layer."""
    y, fused = _conv_or_pool(x, params, layer, backend, block_oh)
    with jax.named_scope(obs.BN):
        y = _fit_extent(y, canon_out_hw)
        if layer.batch_norm and not layer.pool:
            if inference:
                # frozen stats: elementwise, pad slots re-zeroed by the mask below
                y = _bn_infer(y, params, layer)
            else:
                n_global = batch_global * map_out_hw[0] * map_out_hw[1]
                bn_axes = (row_axis, col_axis)
                if batch_axis is not None:
                    bn_axes = (batch_axis,) + bn_axes
                mask = _core_mask_ragged(y.shape[1], y.shape[2], out_halo, out_size)
                mean, var = _masked_batch_stats(y, mask[None, :, :, None], bn_axes, n_global)
                y = _bn_apply(y, mean, var, params["bn_scale"], params["bn_bias"])
        if not fused:
            y = _ACTIVATIONS[layer.act](y)
        m = _ragged_mask(y.shape[1], y.shape[2], out_halo, out_size, out_off, map_out_hw)
        return y * m[None, :, :, None].astype(y.dtype)


# ---------------------------------------------------------------------------
# Shape-specialized (non-uniform partition) execution: per-shape static
# programs selected by lax.switch on the tile index (DESIGN.md §9).
# Everything below runs INSIDE shard_map.
# ---------------------------------------------------------------------------


def _offmap_mask_spec(
    ext_h: int,
    ext_w: int,
    halo: tuple[int, int, int, int],
    out_off: tuple[jax.Array, jax.Array],
    map_hw: tuple[int, int],
) -> jax.Array:
    """Off-map rim mask for a specialized ragged tile: `_ragged_mask` minus
    the validity clause.  The specialized executor never *reads* pad slots
    (every consumer slices its branch's valid window statically), so only
    the oracle's SAME-padding semantics remain to enforce: intermediate-
    layer halo positions hanging off the true map must be zero before the
    next conv consumes them.  The tile origin comes from the boundary
    table (traced per device); positions beyond the valid window get
    whatever the row/col test says - they are never read."""
    top, _, left, _ = halo
    r0, c0 = out_off
    gr = r0 - top + lax.iota(jnp.int32, ext_h)
    gc = c0 - left + lax.iota(jnp.int32, ext_w)
    rmask = (gr >= 0) & (gr < map_hw[0])
    cmask = (gc >= 0) & (gc < map_hw[1])
    return (rmask[:, None] & cmask[None, :]).astype(jnp.float32)


def apply_layer_local_spec(
    x: jax.Array,
    params: dict,
    layer: LayerDef,
    *,
    branch: jax.Array,
    branch_io: tuple[tuple[tuple[int, int], tuple[int, int]], ...],
    out_halo: tuple[int, int, int, int],
    canon_out_hw: tuple[int, int],
    map_out_hw: tuple[int, int],
    out_off: tuple[jax.Array, jax.Array] | None,
    row_axis: str,
    col_axis: str,
    batch_global: int,
    batch_axis: str | None = None,
    mask_offmap: bool = False,
    backend: str = "xla",
    block_oh: int | None = None,
    inference: bool = False,
) -> jax.Array:
    """One layer of a shape-specialized ragged tile (DESIGN.md §9).

    ``branch`` is the traced per-device shape index;
    ``branch_io[b] = ((vin_r, vin_c), (vout_r, vout_c))`` gives branch b's
    static valid extended input/output extents.  Each branch statically
    slices its valid window out of the canonical padded input, runs the
    VALID conv over the TRUE extent (no wasted MACs on pad slots), sums BN
    core statistics over the real core rows, and repads to the canonical
    output extent so all branches share one output aval.  Collectives (BN
    psum) and the unfused activation run OUTSIDE the switch - branches are
    pure local compute, as SPMD requires.  Pad slots beyond a branch's
    valid window are garbage after BN/activation; that is safe because
    every downstream consumer (the next layer's branch slice, the spec
    exchange, the core loss switch, the unpack) reads valid windows only,
    and AD gives the garbage slots zero cotangent for the same reason."""
    bn = layer.batch_norm and not layer.pool
    # Inference BN is elementwise (frozen stats, no core sums, no psum), so
    # it runs once outside the switch on the padded container - pad slots
    # turn garbage, which the invariant already allows (never read).
    bn_stats = bn and not inference
    from repro.core.halo import _switch_by_size

    def _spec_core(a, vout_r, vout_c):
        top, bottom, left, right = out_halo
        return a[:, top:vout_r - bottom, left:vout_c - right, :]

    def mk(io):
        (vin_r, vin_c), (vout_r, vout_c) = io

        def f(a):
            xv = a[:, :vin_r, :vin_c, :]
            y, _ = _conv_or_pool_spec(xv, params, layer, backend, block_oh)
            if y.shape[1:3] != (vout_r, vout_c):
                raise AssertionError(
                    f"spec branch geometry drift: conv of {(vin_r, vin_c)} "
                    f"gave {y.shape[1:3]}, planner said {(vout_r, vout_c)}"
                )
            with jax.named_scope(obs.BN):
                outs = []
                if bn_stats:
                    outs = [jnp.sum(_spec_core(y, vout_r, vout_c), axis=(0, 1, 2))]
                pad = [
                    (0, 0),
                    (0, canon_out_hw[0] - vout_r),
                    (0, canon_out_hw[1] - vout_c),
                    (0, 0),
                ]
                y = jnp.pad(y, pad)
                return (y, *outs) if outs else y

        return f

    res = _switch_by_size(branch, [mk(io) for io in branch_io], x)
    # `fused` depends only on (layer, backend): identical across branches.
    if layer.pool:
        fused = False
    else:
        fused = (not layer.batch_norm) and layer.act in get_conv_backend(backend).fused_acts
    with jax.named_scope(obs.BN):
        if bn_stats:
            # centred two-pass statistics, as in _masked_batch_stats; each
            # branch sums over its own static core window
            y, s = res
            n_global = batch_global * map_out_hw[0] * map_out_hw[1]
            bn_axes = (row_axis, col_axis)
            if batch_axis is not None:
                bn_axes = (batch_axis,) + bn_axes
            mean = lax.psum(s, bn_axes) / n_global

            def mk_ss(io):
                (_, _), (vout_r, vout_c) = io
                return lambda a: jnp.sum(
                    jnp.square(_spec_core(a, vout_r, vout_c) - mean), axis=(0, 1, 2)
                )

            ss = _switch_by_size(branch, [mk_ss(io) for io in branch_io], y)
            var = lax.psum(ss, bn_axes) / n_global
            y = _bn_apply(y, mean, var, params["bn_scale"], params["bn_bias"])
        else:
            y = res
            if bn:
                y = _bn_infer(y, params, layer)
        if not fused:
            y = _ACTIVATIONS[layer.act](y)
        if mask_offmap and any(h > 0 for h in out_halo):
            assert out_off is not None
            m = _offmap_mask_spec(y.shape[1], y.shape[2], out_halo, out_off, map_out_hw)
            y = y * m[None, :, :, None].astype(y.dtype)
        return y


# ---------------------------------------------------------------------------
# Hybrid partitioning: spatial->data reshard + data-mode (full-map) layers
# ---------------------------------------------------------------------------


def _wire_all_gather(x: jax.Array, axis_name: str, dim: int, wire: WireCtx | None):
    """``lax.all_gather(tiled=True)`` with optional wire compression.

    ``wire=None`` is literally the tiled all-gather (legacy jaxpr).
    Otherwise the local block is encoded once and each payload leaf rides a
    stacking all-gather so every receiver can decode per-source blocks and
    re-concatenate - static shapes throughout.  The backward is a custom
    rule (the straight-line transpose would differentiate through
    ``round``/``top_k``): the reduce-scatter cotangent is split into one
    chunk per destination device, each chunk quantised under error feedback
    against its own residual (one buffer per (sender, dest) pair, drawn
    from the bag in destination order), shipped via ``all_to_all``, decoded
    and summed on the receiver (DESIGN.md §12)."""
    if wire is None:
        return lax.all_gather(x, axis_name, axis=dim, tiled=True)
    n = lax.axis_size(axis_name)
    codec = wire.codec
    res = tuple(wire.bag.take(x.shape) for _ in range(n))
    xshape, xdtype = tuple(x.shape), x.dtype   # trace constants, closed over

    @jax.custom_vjp
    def gather(x, res):
        payload = codec.encode(x)
        recv = jax.tree.map(
            lambda p: lax.all_gather(p, axis_name, axis=0, tiled=False), payload
        )
        blocks = [
            codec.decode(jax.tree.map(lambda p: p[i], recv), xshape, xdtype)
            for i in range(n)
        ]
        return lax.concatenate(blocks, dimension=dim)

    def fwd(x, res):
        return gather(x, res), res

    def bwd(res, ct):
        step = xshape[dim]
        payloads, new_res = [], []
        for i in range(n):
            chunk = lax.slice_in_dim(ct, i * step, (i + 1) * step, axis=dim)
            p, r = ef_encode(codec, chunk, res[i])
            payloads.append(p)
            new_res.append(r)
        stacked = jax.tree.map(lambda *ps: jnp.stack(ps, axis=0), *payloads)
        recv = jax.tree.map(
            lambda p: lax.all_to_all(p, axis_name, split_axis=0, concat_axis=0),
            stacked,
        )
        ct_x = sum(
            codec.decode(jax.tree.map(lambda p: p[i], recv), xshape, jnp.float32)
            for i in range(n)
        )
        return ct_x.astype(xdtype), tuple(new_res)

    gather.defvjp(fwd, bwd)
    return gather(x, res)


def reshard_spatial_to_data(
    x: jax.Array,
    row_axis: str,
    col_axis: str,
    *,
    dims: tuple[int, int] = (1, 2),
    wire: WireCtx | None = None,
) -> jax.Array:
    """The spatial->data crossover collective (DESIGN.md §7): all-gather
    the (row_axis x col_axis) tile grid into full feature maps, then split
    the batch across the *same* devices.

    ``x``: (b, h/n, w/m, c) core tile (halo fully consumed by the previous
    group) -> (b/(n*m), h, w, c) batch shard.  Device (i, j) takes batch
    block ``i*m + j``, matching a ``P((row_axis, col_axis))`` batch
    sharding at the mesh level.  The backward pass is derived by AD: the
    all-gather transposes to a reduce-scatter and the batch slice to a
    zero-padded scatter, i.e. exactly the adjoint data->spatial reshard -
    no hand-written collective, so microbatching and gradient compression
    apply unchanged (the cotangent reaches the deferred accumulator in
    spatial layout).

    Requires the local batch divisible by n*m; fails at trace time with a
    clear message otherwise (pick batch/grad_accum so each microbatch
    spreads over the tile grid).
    """
    n = lax.axis_size(row_axis)
    m = lax.axis_size(col_axis)
    x = _wire_all_gather(x, row_axis, dims[0], wire)
    x = _wire_all_gather(x, col_axis, dims[1], wire)
    return _batch_block_slice(x, row_axis, col_axis, n, m)


def _batch_block_slice(x: jax.Array, row_axis: str, col_axis: str, n: int, m: int) -> jax.Array:
    """Device (i, j) keeps batch block i*m + j of the assembled full maps -
    the P((row_axis, col_axis)) batch sharding of the data-mode tail."""
    t = n * m
    b = x.shape[0]
    if b % t:
        raise ValueError(
            f"data-mode batch split needs the per-microbatch batch ({b}) "
            f"divisible by the tile count ({n}x{m}={t})"
        )
    bs = b // t
    d = lax.axis_index(row_axis) * m + lax.axis_index(col_axis)
    return lax.dynamic_slice_in_dim(x, d * bs, bs, axis=0)


def reshard_spatial_to_data_ragged(
    x: jax.Array,
    row_axis: str,
    col_axis: str,
    row_sizes: tuple[int, ...],
    col_sizes: tuple[int, ...],
    *,
    dims: tuple[int, int] = (1, 2),
    wire: WireCtx | None = None,
) -> jax.Array:
    """Spatial->data crossover for ragged partitions: the tiled all-gathers
    assemble *padded* tiles (each block max-sized, pad slots zero), so the
    full map is re-stitched from each block's valid window with static
    slices (the boundary tables are plan constants) before the batch split.
    The adjoint - scatter back into padded blocks, reduce-scatter - is
    derived by AD, exactly like the uniform reshard."""
    n, m = len(row_sizes), len(col_sizes)
    hmax, wmax = max(row_sizes), max(col_sizes)
    x = _wire_all_gather(x, row_axis, dims[0], wire)
    x = _wire_all_gather(x, col_axis, dims[1], wire)
    if hmax * n != x.shape[dims[0]] or wmax * m != x.shape[dims[1]]:
        raise ValueError(
            f"gathered padded grid {x.shape} inconsistent with sizes "
            f"{row_sizes} x {col_sizes}"
        )
    rows = [
        lax.slice_in_dim(x, i * hmax, i * hmax + h, axis=dims[0])
        for i, h in enumerate(row_sizes)
    ]
    x = jnp.concatenate(rows, axis=dims[0]) if len(rows) > 1 else rows[0]
    cols = [
        lax.slice_in_dim(x, j * wmax, j * wmax + w, axis=dims[1])
        for j, w in enumerate(col_sizes)
    ]
    x = jnp.concatenate(cols, axis=dims[1]) if len(cols) > 1 else cols[0]
    return _batch_block_slice(x, row_axis, col_axis, n, m)


def apply_layer_data(
    x: jax.Array,
    params: dict,
    layer: LayerDef,
    *,
    map_out_hw: tuple[int, int],
    row_axis: str,
    col_axis: str,
    batch_global: int,
    backend: str = "xla",
    batch_axis: str | None = None,
    block_oh: int | None = None,
    inference: bool = False,
    skip: jax.Array | None = None,
) -> jax.Array:
    """One data-mode layer: full (unhaloed) maps, batch shard per device.

    The SAME boundary is materialised locally (``pad_for_valid``) so the
    registered VALID-only conv backends run unchanged - no collective
    anywhere in a data-mode layer.  BN still needs its cross-device psums:
    the tile axes now enumerate *batch shards*, so reducing over the same
    axes with the global ``batch x H x W`` count keeps statistics exact
    (each (sample, position) is owned by exactly one device).  A shortcut
    adds ``skip``, its source's output in the same data layout."""
    if layer.shortcut:
        return add_shortcut(x, skip, (0, 0, 0, 0), (0, 0, 0, 0))
    with _compute_scope(layer):
        xp = pad_for_valid(x, layer.padding, pool=layer.pool)
    y, fused = _conv_or_pool(xp, params, layer, backend, block_oh)
    return _finish_layer(
        y,
        params,
        layer,
        fused=fused,
        out_halo=(0, 0, 0, 0),
        shard_out_hw=map_out_hw,
        map_out_hw=map_out_hw,
        row_axis=row_axis,
        col_axis=col_axis,
        batch_global=batch_global,
        mask_offmap=False,
        batch_axis=batch_axis,
        inference=inference,
    )


# ---------------------------------------------------------------------------
# Overlap schedule: interior/boundary split of a group-lead layer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SplitSpec1D:
    """Static interior/boundary split of one spatial dim of a group-lead
    layer on a halo-extended tile (DESIGN.md §5).

    Output positions (extended coords, ``out`` of them) split into a lo
    boundary band [0, i0), the interior [i0, i1], and a hi boundary band
    (i1, out).  Interior outputs depend only on owned input rows
    [int_in_lo, int_in_hi) (owned coords) - computable before any halo
    strip arrives."""

    out: int        # output extent of the halo-extended tile
    i0: int         # first interior output index
    i1: int         # last interior output index (inclusive)
    int_in_lo: int  # owned-coords input slab [lo, hi) feeding the interior
    int_in_hi: int

    @property
    def n_lo(self) -> int:
        return self.i0

    @property
    def n_hi(self) -> int:
        return self.out - 1 - self.i1


def split_1d(own: int, lo: int, hi: int, kernel: int, stride: int) -> SplitSpec1D | None:
    """Interior/boundary split along one dim, or None when no output is
    computable from owned data alone (tile thinner than the kernel's reach
    into the halo - the executor then falls back to whole-tile compute)."""
    out = (own + lo + hi - kernel) // stride + 1
    i0 = -(-lo // stride)                     # ceil(lo / stride)
    i1 = (lo + own - kernel) // stride
    if i1 < i0:
        return None
    return SplitSpec1D(
        out=out,
        i0=i0,
        i1=i1,
        int_in_lo=i0 * stride - lo,
        int_in_hi=i1 * stride + kernel - lo,
    )


def apply_group_lead_overlap(
    x: jax.Array,
    params: dict,
    layer: LayerDef,
    *,
    halo: tuple[int, int, int, int],
    out_halo: tuple[int, int, int, int],
    shard_out_hw: tuple[int, int],
    map_out_hw: tuple[int, int],
    row_axis: str,
    col_axis: str,
    batch_global: int,
    mask_offmap: bool,
    backend: str = "xla",
    batch_axis: str | None = None,
    block_oh: int | None = None,
    wire: WireCtx | None = None,
    inference: bool = False,
) -> jax.Array:
    """Group-lead layer under the overlap schedule: packed halo exchange +
    interior/boundary split execution (DESIGN.md §5).

    The interior region of the output depends only on owned data, so its
    conv is issued *before* any halo strip is consumed - XLA's latency-
    hiding scheduler can then run the boundary ``ppermute``s concurrently
    with the interior matmuls.  The boundary strips (top/bottom bands, and
    left/right strips of the interior rows) are computed from the extended
    tile once the strips land, and the pieces are concatenated back into
    exactly ``conv_valid(extended_tile)`` - each output position is a
    disjoint slice with the identical input window, so exactness vs. the
    sync schedule is positional, not numerical.
    """
    top, bottom, left, right = halo
    k, s = layer.kernel, layer.stride
    own_h, own_w = x.shape[1], x.shape[2]
    rs = split_1d(own_h, top, bottom, k, s)
    cs = split_1d(own_w, left, right, k, s)

    finish = functools.partial(
        _finish_layer,
        params=params,
        layer=layer,
        out_halo=out_halo,
        shard_out_hw=shard_out_hw,
        map_out_hw=map_out_hw,
        row_axis=row_axis,
        col_axis=col_axis,
        batch_global=batch_global,
        mask_offmap=mask_offmap,
        batch_axis=batch_axis,
        inference=inference,
    )

    # 1. issue the packed row exchange (nothing below consumes it yet)
    with jax.named_scope(obs.HALO):
        row_lo, row_hi = halo_exchange_1d_packed(x, top, bottom, row_axis, dim=1, wire=wire)

    if rs is None or cs is None:
        # no interior: whole-tile compute on the assembled extended tile
        with jax.named_scope(obs.HALO):
            ext = _assemble(row_lo, x, row_hi, top, bottom, dim=1)
            col_lo, col_hi = halo_exchange_1d_packed(
                ext, left, right, col_axis, dim=2, wire=wire
            )
            ext = _assemble(col_lo, ext, col_hi, left, right, dim=2)
        y, fused = _conv_or_pool(ext, params, layer, backend, block_oh)
        return finish(y, fused=fused)

    def cut(a, rows, cols):
        # the slab a conv or pool reads, under its scope (so is its backward pad)
        with _compute_scope(layer):
            return a[:, rows, cols, :]

    # 2. interior compute from owned data only - independent of all recvs
    int_slab = cut(x, slice(rs.int_in_lo, rs.int_in_hi), slice(cs.int_in_lo, cs.int_in_hi))
    y_int, fused = _conv_or_pool(int_slab, params, layer, backend, block_oh)

    # 3. column exchange over the row-extended tile (carries the corners)
    with jax.named_scope(obs.HALO):
        x_rows = _assemble(row_lo, x, row_hi, top, bottom, dim=1)
        col_lo, col_hi = halo_exchange_1d_packed(
            x_rows, left, right, col_axis, dim=2, wire=wire
        )
        ext = _assemble(col_lo, x_rows, col_hi, left, right, dim=2)

    # 4. boundary strips once the halo strips land (extended coords)
    mid_rows = slice(rs.i0 * s, rs.i1 * s + k)
    mid = [y_int]
    if cs.n_lo:
        slab = cut(ext, mid_rows, slice(0, (cs.i0 - 1) * s + k))
        mid.insert(0, _conv_or_pool(slab, params, layer, backend, block_oh)[0])
    if cs.n_hi:
        slab = cut(ext, mid_rows, slice((cs.i1 + 1) * s, (cs.out - 1) * s + k))
        mid.append(_conv_or_pool(slab, params, layer, backend, block_oh)[0])
    with _compute_scope(layer):
        bands = [mid[0] if len(mid) == 1 else jnp.concatenate(mid, axis=2)]
    if rs.n_lo:
        slab = cut(ext, slice(0, (rs.i0 - 1) * s + k), slice(None))
        bands.insert(0, _conv_or_pool(slab, params, layer, backend, block_oh)[0])
    if rs.n_hi:
        slab = cut(ext, slice((rs.i1 + 1) * s, (rs.out - 1) * s + k), slice(None))
        bands.append(_conv_or_pool(slab, params, layer, backend, block_oh)[0])
    with _compute_scope(layer):
        y = bands[0] if len(bands) == 1 else jnp.concatenate(bands, axis=1)
    return finish(y, fused=fused)


def _assemble(lo: jax.Array, core: jax.Array, hi: jax.Array, w_lo: int, w_hi: int, *, dim: int) -> jax.Array:
    parts = ([lo] if w_lo > 0 else []) + [core] + ([hi] if w_hi > 0 else [])
    if len(parts) == 1:
        return core
    return lax.concatenate(parts, dimension=dim)
