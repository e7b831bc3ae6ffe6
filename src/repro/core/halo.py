"""Halo (boundary-data) exchange between neighbouring tiles.

Paper Fig. 4: each tile exchanges boundary strips with up to 8 neighbours at
every group input, in both the forward and backward pass.  On a TPU mesh we
realise the 8-neighbour exchange as two *axis-ordered* ``jax.lax.ppermute``
rounds: first along the tile-row axis (top/bottom strips), then along the
tile-column axis over the already-extended array - the second round therefore
carries the corner data, so 2 collectives replace 8 point-to-point sockets.

``ppermute`` delivers zeros to devices that receive no message, which is
exactly SAME-convolution zero padding at the map edges - no special-casing of
edge tiles is needed.

All functions here must be called *inside* ``shard_map`` with the named axes
present in the mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.optim.compression import WireCodec, ef_encode


def _shift_perm(n: int, direction: int) -> list[tuple[int, int]]:
    """Permutation sending shard i -> i+direction (no wraparound: edge tiles
    simply receive zeros, which matches SAME zero padding)."""
    if direction == 1:
        return [(i, i + 1) for i in range(n - 1)]
    if direction == -1:
        return [(i, i - 1) for i in range(1, n)]
    raise ValueError(direction)


# ---------------------------------------------------------------------------
# Wire compression (DESIGN.md §12)
# ---------------------------------------------------------------------------


class EFBag:
    """Dispenser of error-feedback residual buffers for the recurring
    exchanges of one step trace, in deterministic trace order.

    Modes: ``stateless`` hands out fresh zeros (forward-only paths and
    ``make_tiled_loss`` - no EF carry, every microbatch starts clean);
    ``collect`` additionally records each requested (shape, dtype) so the
    deferred-grad builder can discover the EF carry layout with one
    ``jax.eval_shape`` probe; ``buffers`` hands out the supplied arrays in
    the same deterministic order (the scan's EF carry).  ``emitted``
    collects residuals produced *eagerly* (``send_boundary_sum_1d``'s
    primal-direction EF) - the custom-VJP shifts instead return theirs as
    the cotangent of the residual argument.
    """

    def __init__(self, mode: str = "stateless", buffers=None):
        if mode not in ("stateless", "collect", "buffers"):
            raise ValueError(mode)
        self.mode = mode
        self.shapes: list[tuple[tuple[int, ...], Any]] = []
        self.buffers = list(buffers) if buffers is not None else None
        self.emitted: list[jax.Array] = []
        self._i = 0

    def take(self, shape, dtype=jnp.float32) -> jax.Array:
        shape = tuple(shape)
        if self.mode == "collect":
            self.shapes.append((shape, dtype))
            return jnp.zeros(shape, dtype)
        if self.mode == "buffers":
            if self._i >= len(self.buffers):
                raise ValueError(
                    f"EF bag exhausted after {len(self.buffers)} buffers: the "
                    "collect probe and the real trace drew different exchange "
                    "counts (non-deterministic trace order?)"
                )
            buf = self.buffers[self._i]
            self._i += 1
            if tuple(buf.shape) != shape:
                raise ValueError(
                    f"EF buffer {self._i - 1} shape {buf.shape} != requested "
                    f"{shape}: collect/trace order drifted"
                )
            return buf
        return jnp.zeros(shape, dtype)

    def emit(self, new_res: jax.Array) -> None:
        self.emitted.append(new_res)


@dataclasses.dataclass
class WireCtx:
    """Codec + residual dispenser threaded through the tiled executors down
    to every collective call site.  ``None`` everywhere means uncompressed -
    the legacy code paths run byte-for-byte unchanged."""

    codec: WireCodec
    bag: EFBag


def _tree_ppermute(payload, axis_name: str, perm):
    return jax.tree.map(lambda p: lax.ppermute(p, axis_name, perm), payload)


def wire_shift(x: jax.Array, axis_name: str, perm, wire: WireCtx | None) -> jax.Array:
    """``lax.ppermute`` with optional wire compression.

    ``wire=None`` is *literally* ``lax.ppermute`` - codec=none plans keep
    the legacy jaxpr byte-for-byte.  Otherwise the strip is encoded, each
    payload leaf rides its own ppermute (static shapes, zero payloads decode
    to zeros so the edge-delivery convention survives), and the receiver
    decodes.  The forward is stateless: halo strips are activations, a
    fresh value every microbatch, so there is no recurring signal for EF to
    cancel against.  The backward is a custom rule - the straight-line
    transpose would differentiate through ``round``/``top_k`` and kill the
    gradient - shipping the cotangent over the transposed perm under error
    feedback: the residual buffer comes from the ctx's bag (it lives on the
    forward receiver == the backward sender), and the NEW residual leaves
    the rule as the cotangent of the residual argument, which the
    deferred-grad scan carries across microbatches (DESIGN.md §12).
    """
    if wire is None:
        return lax.ppermute(x, axis_name, perm)
    res = wire.bag.take(x.shape)
    return _wire_shift_ef(x, res, axis_name, tuple(perm), wire.codec)


def _wire_shift_ef(x, res, axis_name, perm, codec: WireCodec):
    inv = tuple((d, s) for (s, d) in perm)

    @jax.custom_vjp
    def shift(x, res):
        payload = codec.encode(x)
        recv = _tree_ppermute(payload, axis_name, perm)
        return codec.decode(recv, x.shape, x.dtype)

    def fwd(x, res):
        return shift(x, res), res

    def bwd(res, ct):
        payload, new_res = ef_encode(codec, ct, res)
        recv = _tree_ppermute(payload, axis_name, inv)
        ct_x = codec.decode(recv, ct.shape, ct.dtype)
        return ct_x, new_res

    shift.defvjp(fwd, bwd)
    return shift(x, res)


def halo_exchange_1d(
    x: jax.Array,
    halo_lo: int,
    halo_hi: int,
    axis_name: str,
    *,
    dim: int = 0,
    wire: WireCtx | None = None,
) -> jax.Array:
    """Extend ``x`` along ``dim`` with ``halo_lo`` rows from the previous
    shard and ``halo_hi`` rows from the next shard (zeros at the ends).

    Returns an array whose ``dim`` extent is ``x.shape[dim]+halo_lo+halo_hi``.

    On an axis of one shard no tile has a neighbour, so the shifted
    ``ppermute`` would deliver only zeros: the tile is zero-padded instead,
    with no slice, collective or wire codec (and so no error-feedback
    residual drawn from ``wire.bag``). XLA folds that pad into the consuming
    conv's padding, and its transpose - a slice - into the data-gradient
    conv's output window, where the exchange's concatenate and its
    transposed slices cost full passes over the map. The pad is then the
    conv's own SAME padding, so it is named under the ``conv`` scope: the
    data-gradient conv that takes its name reads as conv work (DESIGN.md §15).
    """
    if halo_lo == 0 and halo_hi == 0:
        return x
    n = lax.axis_size(axis_name)
    if n == 1:
        cfg = [(0, 0, 0)] * x.ndim
        cfg[dim] = (halo_lo, halo_hi, 0)
        with jax.named_scope(obs.CONV):
            return lax.pad(x, jnp.zeros((), x.dtype), cfg)
    parts = []
    if halo_lo > 0:
        # strip the *previous* shard must send us: its last halo_lo rows
        send_up = lax.slice_in_dim(x, x.shape[dim] - halo_lo, x.shape[dim], axis=dim)
        recv_lo = wire_shift(send_up, axis_name, _shift_perm(n, +1), wire)
        parts.append(recv_lo)
    parts.append(x)
    if halo_hi > 0:
        send_down = lax.slice_in_dim(x, 0, halo_hi, axis=dim)
        recv_hi = wire_shift(send_down, axis_name, _shift_perm(n, -1), wire)
        parts.append(recv_hi)
    return lax.concatenate(parts, dimension=dim)


def halo_exchange_2d(
    x: jax.Array,
    halo: tuple[int, int, int, int],
    row_axis: str,
    col_axis: str,
    *,
    dims: tuple[int, int] = (0, 1),
    wire: WireCtx | None = None,
) -> jax.Array:
    """2-D halo exchange (paper Fig. 4).

    halo = (top, bottom, left, right) widths.  The row-axis round runs first;
    the column-axis round then operates on the row-extended array so the
    corner blocks ride along - together the two rounds deliver data from all
    8 neighbours.
    """
    top, bottom, left, right = halo
    y = halo_exchange_1d(x, top, bottom, row_axis, dim=dims[0], wire=wire)
    y = halo_exchange_1d(y, left, right, col_axis, dim=dims[1], wire=wire)
    return y


def _zeros_strip(x: jax.Array, width: int, dim: int) -> jax.Array:
    shape = list(x.shape)
    shape[dim] = width
    return jnp.zeros(shape, x.dtype)


def halo_exchange_1d_packed(
    x: jax.Array,
    halo_lo: int,
    halo_hi: int,
    axis_name: str,
    *,
    dim: int = 0,
    wire: WireCtx | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Packed halo exchange: returns ``(recv_lo, recv_hi)`` strips *without*
    concatenating them onto ``x``, so the caller can schedule interior
    compute that does not depend on them (DESIGN.md §5).

    Collective count per axis: a collective-permute delivers at most one
    message per device, so a device that needs strips from *two* distinct
    neighbours needs two of them - except on a 2-shard axis, where both
    neighbours are the same device and the lo+hi strips pack into a single
    swap ``ppermute`` (edge halos masked to zero by ``axis_index``, matching
    the zero delivery of the shifted perms).  That 2-shard case is exactly
    the per-axis extent of the paper's 2x2 testbed meshes, where the packed
    path halves the collectives per group input from 4 to 2.
    """
    n = lax.axis_size(axis_name)
    if n == 1 or (halo_lo == 0 and halo_hi == 0):
        return _zeros_strip(x, halo_lo, dim), _zeros_strip(x, halo_hi, dim)
    if n == 2 and halo_lo > 0 and halo_hi > 0:
        send = lax.concatenate(
            [
                lax.slice_in_dim(x, x.shape[dim] - halo_lo, x.shape[dim], axis=dim),
                lax.slice_in_dim(x, 0, halo_hi, axis=dim),
            ],
            dimension=dim,
        )
        recv = wire_shift(send, axis_name, [(0, 1), (1, 0)], wire)
        idx = lax.axis_index(axis_name)
        recv_lo = lax.slice_in_dim(recv, 0, halo_lo, axis=dim)
        recv_hi = lax.slice_in_dim(recv, halo_lo, halo_lo + halo_hi, axis=dim)
        recv_lo = jnp.where(idx > 0, recv_lo, jnp.zeros_like(recv_lo))
        recv_hi = jnp.where(idx < n - 1, recv_hi, jnp.zeros_like(recv_hi))
        return recv_lo, recv_hi
    # n > 2: each device receives from two distinct sources, so two shifted
    # ppermutes are information-theoretically minimal; the win here is the
    # un-concatenated return (interior compute stays independent).
    if halo_lo > 0:
        send_up = lax.slice_in_dim(x, x.shape[dim] - halo_lo, x.shape[dim], axis=dim)
        recv_lo = wire_shift(send_up, axis_name, _shift_perm(n, +1), wire)
    else:
        recv_lo = _zeros_strip(x, 0, dim)
    if halo_hi > 0:
        send_down = lax.slice_in_dim(x, 0, halo_hi, axis=dim)
        recv_hi = wire_shift(send_down, axis_name, _shift_perm(n, -1), wire)
    else:
        recv_hi = _zeros_strip(x, 0, dim)
    return recv_lo, recv_hi


def halo_exchange_2d_packed(
    x: jax.Array,
    halo: tuple[int, int, int, int],
    row_axis: str,
    col_axis: str,
    *,
    dims: tuple[int, int] = (0, 1),
    wire: WireCtx | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Packed 2-D halo exchange for the overlap schedule.

    Returns ``(x_rows, col_lo, col_hi)``: the row-extended array (owned tile
    with the top/bottom strips attached) and the *separate* left/right
    strips of that row-extended array (so they carry the corner blocks, as
    in the eager 2-round exchange).  Callers that need the fully extended
    tile concatenate ``[col_lo, x_rows, col_hi]`` along ``dims[1]``;
    callers overlapping compute consume only what each region needs.
    """
    top, bottom, left, right = halo
    row_lo, row_hi = halo_exchange_1d_packed(
        x, top, bottom, row_axis, dim=dims[0], wire=wire
    )
    parts = []
    if top > 0:
        parts.append(row_lo)
    parts.append(x)
    if bottom > 0:
        parts.append(row_hi)
    x_rows = lax.concatenate(parts, dimension=dims[0]) if len(parts) > 1 else x
    col_lo, col_hi = halo_exchange_1d_packed(
        x_rows, left, right, col_axis, dim=dims[1], wire=wire
    )
    return x_rows, col_lo, col_hi


def _update_in_dim(arr: jax.Array, upd: jax.Array, start, dim: int) -> jax.Array:
    """dynamic_update_slice along one dim (start may be traced)."""
    starts = [jnp.int32(0)] * arr.ndim
    starts[dim] = jnp.asarray(start, jnp.int32)
    return lax.dynamic_update_slice(arr, upd, starts)


def halo_exchange_1d_ragged(
    x: jax.Array,
    halo_lo: int,
    halo_hi: int,
    axis_name: str,
    sizes: tuple[int, ...],
    *,
    dim: int = 0,
    out_extent: int | None = None,
    wire: WireCtx | None = None,
) -> jax.Array:
    """Halo exchange over *ragged* shards in padded-to-max layout
    (DESIGN.md §8).

    ``x``: each shard holds ``max(sizes)`` slots along ``dim``; shard i's
    valid data occupies slots [0, sizes[i]) and the rest MUST be zero (the
    padded-tile invariant the ragged executor maintains).  The strip a shard
    sends *up* is its last ``halo_lo`` valid rows - a per-device
    ``dynamic_slice`` at ``sizes[i] - halo_lo`` (sizes is a static table
    indexed by ``axis_index``, so the slice start is the only traced value;
    strip widths stay static as SPMD requires).  The received hi strip lands
    at slot ``halo_lo + sizes[i]``, immediately after the valid data.

    Returns an array of static extent ``out_extent`` (>= halo_lo +
    max(sizes) + halo_hi; callers pass the planner's padded extent) laid out
    ``[recv_lo | valid | recv_hi | zeros]``.  Edge shards receive
    ``ppermute`` zeros = the global SAME zero padding, exactly like the
    uniform exchange.  Requires min(sizes) >= max(halo_lo, halo_hi), checked
    at plan time (``build_stack_plan``).
    """
    n = lax.axis_size(axis_name)
    smax = max(sizes)
    if x.shape[dim] != smax:
        raise ValueError(
            f"ragged exchange expects padded extent {smax} on dim {dim}; "
            f"got shape {x.shape}"
        )
    ext = out_extent if out_extent is not None else smax + halo_lo + halo_hi
    if ext < halo_lo + smax + halo_hi:
        raise ValueError(f"out_extent {ext} < {halo_lo}+{smax}+{halo_hi}")
    if halo_lo == 0 and halo_hi == 0 and ext == smax:
        return x
    pad = [(0, 0)] * x.ndim
    pad[dim] = (halo_lo, ext - halo_lo - smax)
    out = jnp.pad(x, pad)
    h_i = jnp.asarray(sizes, jnp.int32)[lax.axis_index(axis_name)]
    if halo_hi > 0:
        send_down = lax.slice_in_dim(x, 0, halo_hi, axis=dim)
        recv_hi = wire_shift(send_down, axis_name, _shift_perm(n, -1), wire)
        out = _update_in_dim(out, recv_hi, halo_lo + h_i, dim)
    if halo_lo > 0:
        send_up = lax.dynamic_slice_in_dim(x, h_i - halo_lo, halo_lo, axis=dim)
        recv_lo = wire_shift(send_up, axis_name, _shift_perm(n, +1), wire)
        out = _update_in_dim(out, recv_lo, 0, dim)
    return out


def halo_exchange_2d_ragged(
    x: jax.Array,
    halo: tuple[int, int, int, int],
    row_axis: str,
    col_axis: str,
    row_sizes: tuple[int, ...],
    col_sizes: tuple[int, ...],
    *,
    dims: tuple[int, int] = (0, 1),
    out_extents: tuple[int, int] | None = None,
    wire: WireCtx | None = None,
) -> jax.Array:
    """2-D ragged halo exchange: rows first, then columns over the
    row-extended array so corner strips ride the second round (same ordering
    as the uniform exchange).  Neighbours along the column axis share the
    same tile-row index, hence the same row layout, so the column strips
    align positionally."""
    top, bottom, left, right = halo
    oe = out_extents or (None, None)
    y = halo_exchange_1d_ragged(
        x, top, bottom, row_axis, row_sizes, dim=dims[0], out_extent=oe[0], wire=wire
    )
    y = halo_exchange_1d_ragged(
        y, left, right, col_axis, col_sizes, dim=dims[1], out_extent=oe[1], wire=wire
    )
    return y


def static_table_lookup(table, idx) -> jax.Array:
    """Look up a small static int table at a traced index WITHOUT dynamic
    addressing: a one-hot reduction instead of ``jnp.asarray(table)[idx]``
    (which lowers to ``dynamic_slice``/gather).  The shape-specialized
    executor uses this for branch selectors and tile-origin tables so its
    jaxpr stays free of ``dynamic_slice`` (guarded by check_pipeline)."""
    t = jnp.asarray(table, jnp.int32)
    onehot = (lax.iota(jnp.int32, len(table)) == jnp.asarray(idx, jnp.int32)).astype(
        jnp.int32
    )
    return jnp.sum(t * onehot)


def _switch_by_size(branch, fns, *operands):
    """lax.switch over the per-shape programs, degenerating to a direct call
    when only one distinct shape exists (so single-shape axes add no cond to
    the jaxpr)."""
    if len(fns) == 1:
        return fns[0](*operands)
    return lax.switch(branch, fns, *operands)


def halo_exchange_1d_spec(
    x: jax.Array,
    halo_lo: int,
    halo_hi: int,
    axis_name: str,
    sizes: tuple[int, ...],
    *,
    dim: int = 0,
    out_extent: int | None = None,
    wire: WireCtx | None = None,
) -> jax.Array:
    """Shape-specialized halo exchange over ragged shards (DESIGN.md §9).

    Same contract as ``halo_exchange_1d_ragged`` - shard i holds
    ``max(sizes)`` slots along ``dim`` with valid data in [0, sizes[i]) and
    zeros beyond, and the result is ``[recv_lo | valid | recv_hi | zeros]``
    at static extent ``out_extent`` - but every slice is STATIC: the send-up
    strip and the reassembly are unrolled over the distinct tile extents via
    ``lax.switch`` on a branch table indexed by ``axis_index``, so the jaxpr
    contains no ``dynamic_slice``/``dynamic_update_slice`` and no traced
    offsets.  The two ``ppermute`` collectives stay OUTSIDE the switch
    (collectives inside cond branches are not legal SPMD); branches only
    pick which statically-sliced strip to send and how to concatenate.
    Edge shards receive ppermute zeros = global SAME zero padding.
    """
    from repro.core.tiling import dedup_axis_shapes

    n = lax.axis_size(axis_name)
    smax = max(sizes)
    if x.shape[dim] != smax:
        raise ValueError(
            f"spec exchange expects padded extent {smax} on dim {dim}; "
            f"got shape {x.shape}"
        )
    ext = out_extent if out_extent is not None else smax + halo_lo + halo_hi
    if ext < halo_lo + smax + halo_hi:
        raise ValueError(f"out_extent {ext} < {halo_lo}+{smax}+{halo_hi}")
    if halo_lo == 0 and halo_hi == 0 and ext == smax:
        return x
    table, uniq = dedup_axis_shapes(sizes)
    branch = static_table_lookup(table, lax.axis_index(axis_name))

    recv_lo = recv_hi = None
    if halo_lo > 0:
        # Strip the next shard needs from us: our last halo_lo VALID rows,
        # a static slice per distinct extent (uniform strip aval across
        # branches, as lax.switch requires).
        def mk_send(s):
            return lambda a: lax.slice_in_dim(a, s - halo_lo, s, axis=dim)

        send_up = _switch_by_size(branch, [mk_send(s) for s in uniq], x)
        recv_lo = wire_shift(send_up, axis_name, _shift_perm(n, +1), wire)
    if halo_hi > 0:
        # Valid data starts at slot 0 on every shard: the send-down strip is
        # the same static slice for all shapes - no switch needed.
        send_down = lax.slice_in_dim(x, 0, halo_hi, axis=dim)
        recv_hi = wire_shift(send_down, axis_name, _shift_perm(n, -1), wire)

    def mk_assemble(s):
        def f(a):
            parts = []
            if recv_lo is not None:
                parts.append(recv_lo)
            parts.append(lax.slice_in_dim(a, 0, s, axis=dim))
            if recv_hi is not None:
                parts.append(recv_hi)
            y = parts[0] if len(parts) == 1 else lax.concatenate(parts, dimension=dim)
            tail = ext - (halo_lo + s + halo_hi)
            if tail > 0:
                pad = [(0, 0)] * a.ndim
                pad[dim] = (0, tail)
                y = jnp.pad(y, pad)
            return y

        return f

    return _switch_by_size(branch, [mk_assemble(s) for s in uniq], x)


def halo_exchange_2d_spec(
    x: jax.Array,
    halo: tuple[int, int, int, int],
    row_axis: str,
    col_axis: str,
    row_sizes: tuple[int, ...],
    col_sizes: tuple[int, ...],
    *,
    dims: tuple[int, int] = (0, 1),
    out_extents: tuple[int, int] | None = None,
    wire: WireCtx | None = None,
) -> jax.Array:
    """2-D shape-specialized halo exchange: rows first, then columns over
    the row-extended array (corners ride the second round, same ordering as
    every other exchange here).  Column neighbours share the tile-row index
    and hence the exact row layout, so the column strips align statically."""
    top, bottom, left, right = halo
    oe = out_extents or (None, None)
    y = halo_exchange_1d_spec(
        x, top, bottom, row_axis, row_sizes, dim=dims[0], out_extent=oe[0], wire=wire
    )
    # After the row round every shard in a tile-row holds the same static
    # row extent, so the column exchange rags only over col_sizes.
    y = halo_exchange_1d_spec(
        y, left, right, col_axis, col_sizes, dim=dims[1], out_extent=oe[1], wire=wire
    )
    return y


def send_boundary_sum_1d(
    x: jax.Array,
    overlap_lo: int,
    overlap_hi: int,
    axis_name: str,
    *,
    dim: int = 0,
    wire: WireCtx | None = None,
) -> jax.Array:
    """Adjoint of ``halo_exchange_1d``: fold halo regions back onto their
    owners and sum.  ``x`` carries ``overlap_lo``/``overlap_hi`` rows at each
    end that belong to the neighbouring shards; they are shipped back and
    accumulated onto the neighbour's interior.  (JAX AD derives exactly this
    for the backward pass - provided here for explicit schedules and tests.)

    Under ``wire`` the shipped strips are cotangents of a *recurring*
    exchange, so they ride error feedback in the primal direction: each
    strip is quantised against a residual drawn from the bag, and the new
    residual is pushed to ``wire.bag.emitted`` (eager - there is no AD pass
    here to smuggle it through), in the same order the bag was drawn from.
    """
    n = lax.axis_size(axis_name)
    core_lo, core_hi = overlap_lo, x.shape[dim] - overlap_hi
    core = lax.slice_in_dim(x, core_lo, core_hi, axis=dim)

    def ship(strip, perm):
        if wire is None:
            return lax.ppermute(strip, axis_name, perm)
        res = wire.bag.take(strip.shape)
        payload, new_res = ef_encode(wire.codec, strip, res)
        wire.bag.emit(new_res)
        recv = _tree_ppermute(payload, axis_name, perm)
        return wire.codec.decode(recv, strip.shape, strip.dtype)

    if overlap_lo > 0:
        up = lax.slice_in_dim(x, 0, overlap_lo, axis=dim)  # belongs to prev shard
        up = ship(up, _shift_perm(n, -1))
        pad = [(0, 0)] * x.ndim
        pad[dim] = (core.shape[dim] - overlap_lo, 0)
        core = core + jnp.pad(up, pad)
    if overlap_hi > 0:
        down = lax.slice_in_dim(x, x.shape[dim] - overlap_hi, x.shape[dim], axis=dim)
        down = ship(down, _shift_perm(n, +1))
        pad = [(0, 0)] * x.ndim
        pad[dim] = (0, core.shape[dim] - overlap_hi)
        core = core + jnp.pad(down, pad)
    return core


def tile_coords(row_axis: str, col_axis: str) -> tuple[jax.Array, jax.Array]:
    """(i, j) grid position of the executing tile."""
    return lax.axis_index(row_axis), lax.axis_index(col_axis)
