"""Direct NHWC conv2d Pallas TPU kernel for the paper's tiled stacks.

TPU adaptation of the paper's hot spot (DESIGN.md S2): each device convolves
its halo-extended local tile as K shifted (rows, K*Cin) x (K*Cin, bc) MXU
matmuls, accumulating in fp32.  The halo is exchanged *between* devices by
core/halo.py; *within* the device the kernel streams the tile through VMEM
one halo'd row slab at a time.

Operand layout (built by XLA data movement in the wrapper, no MACs):

* **column taps folded into channels.**  ``x_cols[n, h, ow, kj*Cin + c] =
  x[n, h, ow + kj, c]``, so the kj taps of a row become one contraction of
  depth K*Cin (layer 1's Cin=3 contracts over 9, not 3) and the kernel
  never slices the W (sublane) axis at an unaligned offset.  OW is padded
  to a sublane multiple (``OW_p``) so the (rows, OW_p, K*Cin) ->
  (rows*OW_p, K*Cin) collapse is layout-preserving; the padded columns are
  computed and cropped.
* **strides folded into channels.**  A stride-S conv is run as the
  stride-1 conv of its space-to-depth twin (``space_to_depth`` /
  ``s2d_filter``): every S x S pixel block becomes S*S*Cin channels and the
  filter is zero-padded to a multiple of S.  The kernel is stride-1 only.

Spatial output-row blocking (DESIGN.md S5): grid (N, Cout/bc, OH/block_oh),
OH minor.  Each grid step receives only the ``block_oh + K - 1`` input rows
its output block reads - an element-indexed row window, so neighbouring
slabs overlap by the K-1 row halo - and ki is a static slice along that
untiled leading axis.  ``block_oh`` is chosen so the fp32 accumulator
(block_oh*OW_p, bc) and the lane-padded input slab both stay inside fixed
VMEM budgets at every layer of YOLOv2-16 at 416x416.

BlockSpecs:
    x    (., block_oh+K-1, OW_p, K*Cin)  - halo'd row slab (element offset)
    w    (K, K*Cin, bc)                  - one Cout slab of the folded filter
    b    (1, bc)                         - 2-D so Mosaic tiles it like w
    out  (., block_oh, OW_p, bc)
bc defaults to 128 (MXU lane width).  Operands are cast to fp32 and the dot
runs at fp32 contract precision, so the kernel agrees with an fp32 XLA conv
to float rounding.

Supports any stride and fused bias + activation (linear / relu / leaky 0.1,
darknet's slope).  VALID padding: ops.py pre-pads, mirroring how the tiled
runtime delivers halo-extended inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# Per-grid-cell VMEM budgets for the auto block_oh choice, lane-padded:
# the fp32 accumulator and one buffer of the streamed input row slab.
_ACC_BUDGET_BYTES = 1 << 20
_SLAB_BUDGET_BYTES = 2 << 20


def round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def auto_block_oh(oh: int, ow_p: int, kc: int, bc: int, k: int) -> int:
    """Output rows per grid step: the most rows whose accumulator and input
    slab fit their budgets, then evened out over the resulting block count
    so the padded last block wastes as little as possible."""
    acc_rows = _ACC_BUDGET_BYTES // (4 * ow_p * round_up(bc, LANES))
    slab_rows = _SLAB_BUDGET_BYTES // (4 * ow_p * round_up(kc, LANES)) - (k - 1)
    rows = max(1, min(oh, acc_rows, slab_rows))
    return -(-oh // -(-oh // rows))


def fit_axis(x: jax.Array, axis: int, size: int) -> jax.Array:
    """Crop or zero-pad ``x`` along ``axis`` to exactly ``size``."""
    cur = x.shape[axis]
    if cur > size:
        return lax.slice_in_dim(x, 0, size, axis=axis)
    if cur < size:
        cfg = [(0, 0)] * x.ndim
        cfg[axis] = (0, size - cur)
        return jnp.pad(x, cfg)
    return x


def space_to_depth(x: jax.Array, k: int, stride: int, oh: int, ow: int) -> jax.Array:
    """Input of a stride-S VALID conv -> input of its stride-1 twin.

    Rows/cols are cropped or zero-padded to S*(O + K' - 1) (K' = ceil(K/S)),
    then each S x S block folds into channels in (r, s, c) order:
    ``out[n, i, j, (r, s, c)] = x[n, S*i + r, S*j + s, c]``."""
    kq = -(-k // stride)
    hh, ww = stride * (oh + kq - 1), stride * (ow + kq - 1)
    x = fit_axis(fit_axis(x, 1, hh), 2, ww)
    n, _, _, c = x.shape
    x = x.reshape(n, hh // stride, stride, ww // stride, stride, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        n, hh // stride, ww // stride, stride * stride * c
    )


def s2d_filter(w: jax.Array, stride: int) -> jax.Array:
    """HWIO filter -> the filter of the space-to-depth twin:
    ``out[a, b, (r, s, ci), co] = w[S*a + r, S*b + s, ci, co]`` (zero past K)."""
    k, _, cin, cout = w.shape
    kq = -(-k // stride)
    w = jnp.pad(w, ((0, kq * stride - k), (0, kq * stride - k), (0, 0), (0, 0)))
    w = w.reshape(kq, stride, kq, stride, cin, cout).transpose(0, 2, 1, 3, 4, 5)
    return w.reshape(kq, kq, stride * stride * cin, cout)


def fold_cols(x: jax.Array, k: int, ow_p: int) -> jax.Array:
    """(N, H, W, C) -> (N, H, OW_p, K*C) with
    ``out[..., ow, kj*C + c] = x[..., ow + kj, c]`` (W zero-padded)."""
    x = fit_axis(x, 2, ow_p + k - 1)
    if k == 1:
        return x
    return jnp.concatenate([x[:, :, kj:kj + ow_p] for kj in range(k)], axis=-1)


def _conv_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, kernel: int, act: str, block_oh: int):
    ow_p, kc = x_ref.shape[-2:]
    bc = o_ref.shape[-1]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for ki in range(kernel):
        xs = x_ref[ki:ki + block_oh].astype(jnp.float32).reshape(block_oh * ow_p, kc)
        acc_ref[...] += jnp.dot(
            xs,
            w_ref[ki].astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    # fused bias + activation epilogue, per output-row block
    y = acc_ref[...] + b_ref[...].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "leaky":
        y = jnp.where(y > 0, y, 0.1 * y)
    o_ref[...] = y.reshape(block_oh, ow_p, bc).astype(o_ref.dtype)


def conv2d_tile(
    x: jax.Array,                # (N, H, W, Cin) halo-extended local tile
    w: jax.Array,                # (K, K, Cin, Cout)
    b: jax.Array | None = None,  # (Cout,)
    *,
    stride: int = 1,
    act: str = "linear",
    bc: int = 128,
    block_oh: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    n, h, wdt, _ = x.shape
    k = w.shape[0]
    cout = w.shape[-1]
    oh = (h - k) // stride + 1
    ow = (wdt - k) // stride + 1
    # XLA promotion semantics: mixed-precision inputs (bf16 activations,
    # fp32 filters) produce the promoted dtype, matching conv_general_dilated.
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if stride > 1:
        x = space_to_depth(x, k, stride, oh, ow)
        w = s2d_filter(w, stride)
        k = w.shape[0]
    kc = k * x.shape[-1]
    bc = min(bc, cout)
    cout_p = round_up(cout, bc)
    ow_p = round_up(ow, SUBLANES)
    if block_oh is None:
        block_oh = auto_block_oh(oh, ow_p, kc, bc, k)
    block_oh = max(1, min(block_oh, oh))
    n_oh_blocks = -(-oh // block_oh)
    oh_p = n_oh_blocks * block_oh
    # rows padded so the last (possibly OH-padded) block's slab is in bounds
    xc = fold_cols(fit_axis(x, 1, oh_p + k - 1), k, ow_p)
    wc = fit_axis(w.reshape(k, kc, cout), 2, cout_p)
    b = jnp.zeros((cout_p,), out_dtype) if b is None else fit_axis(b, 0, cout_p)

    kernel_fn = functools.partial(_conv_kernel, kernel=k, act=act, block_oh=block_oh)
    out = pl.pallas_call(
        kernel_fn,
        grid=(n, cout_p // bc, n_oh_blocks),
        in_specs=[
            pl.BlockSpec(
                (None, pl.Element(block_oh + k - 1), pl.Element(ow_p), pl.Element(kc)),
                lambda i, co, ob: (i, ob * block_oh, 0, 0),
            ),
            pl.BlockSpec((k, kc, bc), lambda i, co, ob: (0, 0, co)),
            pl.BlockSpec((1, bc), lambda i, co, ob: (0, co)),
        ],
        out_specs=pl.BlockSpec((None, block_oh, ow_p, bc), lambda i, co, ob: (i, ob, 0, co)),
        out_shape=jax.ShapeDtypeStruct((n, oh_p, ow_p, cout_p), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_oh * ow_p, bc), jnp.float32)],
        interpret=interpret,
    )(xc, wc, b.reshape(1, cout_p))
    return out[:, :oh, :ow, :cout]
