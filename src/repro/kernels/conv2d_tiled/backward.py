"""Pallas backward kernels for the tiled conv2d (paper §4.1, DESIGN.md §6).

The paper's training claim rests on both backward convolutions partitioning
exactly like the forward one:

* **dgrad** (delta backprop) - the input gradient of a VALID strided conv
  is itself a VALID stride-1 convolution: dilate the cotangent by the
  forward stride (insert S-1 zeros between rows/cols), pad by K-1, and
  convolve with the 180°-rotated filter with I/O channels swapped
  (``w_rot[u, v, co, ci] = w[K-1-u, K-1-v, ci, co]``).  That is *the same
  compute shape as the forward pass*, so ``conv2d_dgrad_tile`` reuses the
  forward Pallas kernel (``kernel.conv2d_tile``) verbatim - including its
  OH-block row-slab streaming and its VMEM budgets - on the
  transformed operands.  The dilation/rotation are pure data movement
  (``lax.pad`` with interior padding, a reverse and a transpose); every MAC
  runs on the MXU path.

* **wgrad** (weight gradient) - a correlation of the (padded) input
  activations with the cotangent:

      dw[ki, kj, ci, co] = sum_{n, oh, ow} xp[n, S*oh+ki, S*ow+kj, ci]
                                         * g[n, oh, ow, co]

  ``conv2d_wgrad_tile`` runs a dedicated kernel with grid
  ``(Cout/bc, N, OH blocks)``: it streams the same halo'd row slabs of the
  column-folded input as the forward kernel, next to the cotangent rows of
  that block, and reduces each ki to ONE (rows, K·Cin)ᵀ·(rows, bc) MXU
  matmul into a resident fp32 (K, K·Cin, bc) filter slab - so neither the
  input nor the cotangent is ever whole in VMEM, and the accumulator does
  not scale with the spatial extent.  The kernel produces the *per-tile partial sum*; the
  cross-tile summation is the deferred psum inserted by shard_map
  transposition (paper's deferred weight aggregation).

Both functions compute gradients of the *pre-activation* VALID conv; the
fused bias+activation epilogue gradient (``act'`` applied to the cotangent)
and the bias reduction live in ``ops._bwd``, which wires these kernels into
``conv2d``'s custom_vjp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.conv2d_tiled.kernel import (
    SUBLANES,
    auto_block_oh,
    conv2d_tile,
    fit_axis,
    fold_cols,
    round_up,
    space_to_depth,
)


def rotate_filter(w: jax.Array) -> jax.Array:
    """HWIO filter -> 180°-rotated, channel-swapped filter for dgrad.

    ``rotate_filter(w)[u, v, co, ci] == w[K-1-u, K-1-v, ci, co]``.
    """
    return jnp.transpose(w[::-1, ::-1], (0, 1, 3, 2))


def conv2d_dgrad_tile(
    g: jax.Array,                # (N, OH, OW, Cout) cotangent of the VALID conv
    w: jax.Array,                # (K, K, Cin, Cout) forward HWIO filter
    in_hw: tuple[int, int],      # (H, W) of the forward (padded) input
    *,
    stride: int = 1,
    block_oh: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Input gradient of ``conv2d_tile(x, w, stride)`` as one forward-style
    Pallas conv: stride-dilated cotangent * rotated filter, VALID, stride 1.

    Returns (N, H, W, Cin) - the gradient w.r.t. the halo-extended/padded
    input.  Rows/cols beyond the last forward window (``(H-K) % stride`` of
    them) receive zero gradient via trailing zero-padding of the dilated
    cotangent, so ragged strided geometries stay exact.
    """
    n, oh, ow, _ = g.shape
    k = w.shape[0]
    h, wdt = in_hw
    rh = h - ((oh - 1) * stride + k)
    rw = wdt - ((ow - 1) * stride + k)
    if rh < 0 or rw < 0:
        raise ValueError(
            f"cotangent {g.shape} inconsistent with input {in_hw}, K={k}, S={stride}"
        )
    g_dil = lax.pad(
        g,
        jnp.zeros((), g.dtype),
        ((0, 0, 0), (k - 1, k - 1 + rh, stride - 1), (k - 1, k - 1 + rw, stride - 1), (0, 0, 0)),
    )
    return conv2d_tile(
        g_dil, rotate_filter(w), None,
        stride=1, act="linear", block_oh=block_oh, interpret=interpret,
    )


def _wgrad_kernel(x_ref, g_ref, o_ref, *, kernel: int, block_oh: int):
    # The (K, K*Cin, bc) output block is resident across the (n, oh-block)
    # reduction sweep and doubles as the fp32 accumulator.
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ow_p, kc = x_ref.shape[-2:]
    bc = g_ref.shape[-1]
    gs = g_ref[...].astype(jnp.float32).reshape(block_oh * ow_p, bc)
    for ki in range(kernel):
        xs = x_ref[ki:ki + block_oh].astype(jnp.float32).reshape(block_oh * ow_p, kc)
        o_ref[ki] += lax.dot_general(
            xs, gs,
            (((0,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )


def conv2d_wgrad_tile(
    x: jax.Array,                # (N, H, W, Cin) forward (padded) input tile
    g: jax.Array,                # (N, OH, OW, Cout) cotangent of the VALID conv
    kernel: int,
    *,
    stride: int = 1,
    bc: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Per-tile weight-gradient partial sum: (K, K, Cin, Cout).

    Same operand layout as the forward kernel: the input is folded to
    (N, H, OW_p, K*Cin) (strides via space-to-depth first) and streamed as
    halo'd row slabs, one per OH block; the cotangent block of the same
    output rows multiplies each of the K row-shifted slabs in one
    (rows, K*Cin)^T x (rows, bc) matmul.  Grid (Cout/bc, N, OH blocks),
    with N and the OH blocks a reduction into one resident fp32 filter
    slab.  Cotangent padding (OW_p, OH blocks) is zeros, so padded columns
    and rows add nothing.  The output dtype defaults to the promoted
    input/cotangent dtype, so mixed-precision (bf16 activations, fp32
    filters) callers pass ``out_dtype=w.dtype``.
    """
    _, oh, ow, cout = g.shape
    cin = x.shape[-1]
    if out_dtype is None:
        out_dtype = jnp.result_type(x.dtype, g.dtype)
    k = kernel
    if stride > 1:
        x = space_to_depth(x, kernel, stride, oh, ow)
        k = -(-kernel // stride)
    n = x.shape[0]
    kc = k * x.shape[-1]
    bc = min(bc, cout)
    cout_p = round_up(cout, bc)
    ow_p = round_up(ow, SUBLANES)
    block_oh = auto_block_oh(oh, ow_p, kc, bc, k)
    n_oh_blocks = -(-oh // block_oh)
    oh_p = n_oh_blocks * block_oh
    xc = fold_cols(fit_axis(x, 1, oh_p + k - 1), k, ow_p)
    g = fit_axis(fit_axis(fit_axis(g, 1, oh_p), 2, ow_p), 3, cout_p)

    kernel_fn = functools.partial(_wgrad_kernel, kernel=k, block_oh=block_oh)
    out = pl.pallas_call(
        kernel_fn,
        grid=(cout_p // bc, n, n_oh_blocks),
        in_specs=[
            pl.BlockSpec(
                (None, pl.Element(block_oh + k - 1), pl.Element(ow_p), pl.Element(kc)),
                lambda co, i, ob: (i, ob * block_oh, 0, 0),
            ),
            pl.BlockSpec((None, block_oh, ow_p, bc), lambda co, i, ob: (i, ob, 0, co)),
        ],
        out_specs=pl.BlockSpec((k, kc, bc), lambda co, i, ob: (0, 0, co)),
        out_shape=jax.ShapeDtypeStruct((k, kc, cout_p), jnp.float32),
        interpret=interpret,
    )(xc, g)
    dw = out[..., :cout].reshape(k, k, kc // k, cout)
    if stride > 1:
        # invert s2d_filter: (a, b, (r, s, ci)) -> (S*a + r, S*b + s, ci)
        dw = dw.reshape(k, k, stride, stride, cin, cout).transpose(0, 2, 1, 3, 4, 5)
        dw = dw.reshape(k * stride, k * stride, cin, cout)[:kernel, :kernel]
    return dw.astype(out_dtype)
