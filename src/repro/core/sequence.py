"""Sequence-dimension generalisation of the paper's tiling/halo technique.

The paper partitions the *spatial* extent of CNN activations and exchanges
operator-locality halos.  For the assigned LM architectures the analogous
bounded-receptive-field operators live on the *sequence* dimension:

  - causal conv1d (Mamba/Mamba2, K=4): left halo of K-1 tokens;
  - SSD chunked state-space scan: the inter-shard "boundary data" is the
    SSM state - a per-shard (decay, state) pair combined associatively;
  - sliding-window attention (Mixtral, window W): each query shard needs the
    last W key/value tokens of its left neighbour - a 1-D halo exactly like
    a conv halo of width W.

Global attention has an unbounded dependence region, so the technique is
inapplicable there (DESIGN.md §Arch-applicability).

All functions run inside shard_map with the sequence axis named ``axis``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.halo import halo_exchange_1d, _shift_perm


# ---------------------------------------------------------------------------
# Causal conv1d with a sequence halo (Mamba2's conv before the SSM)
# ---------------------------------------------------------------------------


def seq_halo_conv1d(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None,
    axis: str | None,
    *,
    seq_dim: int = 1,
) -> jax.Array:
    """Depthwise causal conv1d over a sequence-sharded activation.

    x: (B, T_local, D); w: (K, D) depthwise taps; output same shape as x.
    Left halo of K-1 tokens ships from the previous shard (zeros for the
    first shard = causal zero padding).  ``axis=None`` runs unsharded.
    """
    k = w.shape[0]
    if axis is not None:
        xh = halo_exchange_1d(x, k - 1, 0, axis, dim=seq_dim)
    else:
        pad = [(0, 0)] * x.ndim
        pad[seq_dim] = (k - 1, 0)
        xh = jnp.pad(x, pad)
    # depthwise conv as a sum of shifted slices (K is tiny, typically 4)
    t = x.shape[seq_dim]
    out = jnp.zeros_like(x)
    for i in range(k):
        sl = lax.slice_in_dim(xh, i, i + t, axis=seq_dim)
        out = out + sl * w[i]
    if b is not None:
        out = out + b
    return out


# ---------------------------------------------------------------------------
# Cross-shard associative state combine (SSD chunk-state handoff)
# ---------------------------------------------------------------------------


def seq_scan_combine(
    decay: jax.Array,
    state: jax.Array,
    axis: str,
) -> jax.Array:
    """Compute each shard's *incoming* SSM state from per-shard summaries.

    A linear SSM across the full sequence factorises per shard i into
    (decay_i, state_i): ``out_state = decay_i * in_state + state_i``.  The
    incoming state of shard i is

        in_i = sum_{j<i} (prod_{j<k<i} decay_k) * state_j

    an exclusive associative prefix.  We all_gather the tiny per-shard
    summaries (decay: (...,) broadcastable over state) and combine locally -
    this is the sequence-parallel analogue of the paper's group-boundary
    exchange, with O(shards) scalars instead of O(map) activations.
    """
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    decays = lax.all_gather(decay, axis)          # (n, ...) leading shard dim
    states = lax.all_gather(state, axis)          # (n, ...)

    def body(j, acc):
        # weight = prod_{k in (j, idx)} decay_k ; contribution only if j < idx
        def wstep(k, wacc):
            use = (k > j) & (k < idx)
            d = jnp.where(use, decays[k], jnp.ones_like(decays[k]))
            return wacc * d

        w = lax.fori_loop(0, n, wstep, jnp.ones_like(decay))
        contrib = jnp.where(j < idx, w * states[j], jnp.zeros_like(state))
        return acc + contrib

    return lax.fori_loop(0, n, body, jnp.zeros_like(state))


def seq_scan_combine_hops(
    decay: jax.Array,
    state: jax.Array,
    axis: str,
) -> jax.Array:
    """Hillclimb variant: Blelloch-style doubling scan across shards.

    ceil(log2(n)) ppermute rounds instead of an (n, ...) all_gather buffer:
    round r ships the (decay, state) summary 2^r shards to the right and
    composes ``(d2, s2) o (d1, s1) = (d1*d2, d2*s1 + s2)``.  After all
    rounds each shard holds the *inclusive* prefix; one final +1 hop converts
    to the exclusive prefix (the incoming state).
    """
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    d, s = decay, state
    dx = d.reshape(d.shape + (1,) * (s.ndim - d.ndim))   # broadcast over state
    shift = 1
    while shift < n:
        perm = [(i, i + shift) for i in range(n - shift)]
        d_in = lax.ppermute(d, axis, perm)   # zeros where no sender
        s_in = lax.ppermute(s, axis, perm)
        has = (idx >= shift)
        # compose incoming-prefix (d_in, s_in) before local (d, s)
        d_new = jnp.where(has, d * d_in, d)
        s_new = jnp.where(has, dx * s_in + s, s)
        d, s = d_new, s_new
        dx = d.reshape(d.shape + (1,) * (s.ndim - d.ndim))
        shift *= 2
    # exclusive prefix = inclusive prefix of the left neighbour
    incoming = lax.ppermute(s, axis, _shift_perm(n, +1))
    return incoming


# ---------------------------------------------------------------------------
# Sliding-window attention KV halo
# ---------------------------------------------------------------------------


def swa_kv_halo(
    k: jax.Array,
    v: jax.Array,
    window: int,
    axis: str | None,
    *,
    seq_dim: int = 1,
) -> tuple[jax.Array, jax.Array, int]:
    """Ship the left neighbour's trailing ``window`` keys/values.

    Returns (k_ext, v_ext, halo) where halo = min(window, local_T) tokens
    were prepended (zeros on shard 0; masked out by position arithmetic in
    the attention kernel).  This is the paper's boundary exchange with the
    sequence as the spatial dim and the attention window as the kernel.
    """
    t_local = k.shape[seq_dim]
    halo = min(window, t_local)
    if axis is None:
        pad = [(0, 0)] * k.ndim
        pad[seq_dim] = (halo, 0)
        return jnp.pad(k, pad), jnp.pad(v, pad), halo
    k_ext = halo_exchange_1d(k, halo, 0, axis, dim=seq_dim)
    v_ext = halo_exchange_1d(v, halo, 0, axis, dim=seq_dim)
    return k_ext, v_ext, halo


def swa_position_ids(t_local: int, halo: int, axis: str | None) -> tuple[jax.Array, jax.Array]:
    """Global positions of (queries, extended keys) for window/causal masks."""
    if axis is None:
        base = jnp.int32(0)
    else:
        base = lax.axis_index(axis) * t_local
    q_pos = base + lax.iota(jnp.int32, t_local)
    k_pos = base - halo + lax.iota(jnp.int32, t_local + halo)
    return q_pos, k_pos
