"""Weights and batches of a training cell, made from ``--seed``.

The program and the reference both start from what is made here. Each piece
comes from its own key folded out of the seed, so the same seed gives the same
weights and the same batches on any run.

- Weights: He-normal filters in the HWIO layout, batch-norm scales of one and
  biases of zero (darknet's start), made on the device in one jitted call.
- Batches: a pool of distinct batches standing in for decoded images a loader
  has ready. Pixels are uniform in [0, 1), the L2 target normal with the
  given ``target_std``. Each microbatch is drawn on the device in one
  call, so the device never holds more than one microbatch of the pool, and
  then kept in host memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import Layer

PARAMS, POOL = 0, 1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including seeds over 32 bits."""
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])), int(words[1]))


def _init(key, layers: tuple[Layer, ...]):
    params = []
    for i, l in enumerate(layers):
        if l.kind != "conv":
            params.append({})
            continue
        fan_in = l.size * l.size * l.cin
        w = jax.random.normal(jax.random.fold_in(key, i), (l.size, l.size, l.cin, l.cout), jnp.float32)
        params.append({
            "w": w * np.float32(np.sqrt(2.0 / fan_in)),
            "bn_scale": jnp.ones((l.cout,), jnp.float32),
            "bn_bias": jnp.zeros((l.cout,), jnp.float32),
        })
    return params


def make_params(seed: int, layers, sharding=None):
    """The cell's initial weights, on the device (``sharding`` if given)."""
    fn = jax.jit(functools.partial(_init, layers=tuple(layers)), out_shardings=sharding)
    return fn(jax.random.fold_in(seed_key(seed), PARAMS))


@functools.partial(jax.jit, static_argnames=("x_shape", "t_shape"))
def _draw(key, target_std, x_shape, t_shape):
    kx, kt = jax.random.split(key)
    x = jax.random.uniform(kx, x_shape, jnp.float32)
    t = target_std * jax.random.normal(kt, t_shape, jnp.float32)
    return x, t


def make_pool(seed: int, size: int, batch: int, micro: int, hw: tuple[int, int],
              out: tuple[int, int, int], target_std: float, device=None) -> list[dict]:
    """``size`` distinct batches of ``batch`` rows as host arrays; each is drawn
    as ``batch // micro``-row chunks on ``device``."""
    rows = batch // micro
    key = jax.random.fold_in(seed_key(seed), POOL)
    pool = []
    for i in range(size):
        xs, ts = [], []
        for j in range(micro):
            k = jax.random.fold_in(jax.random.fold_in(key, i), j)
            if device is not None:
                k = jax.device_put(k, device)
            x, t = _draw(k, np.float32(target_std), (rows, *hw, 3), (rows, *out))
            xs.append(np.asarray(x))
            ts.append(np.asarray(t))
            del x, t
        pool.append({"x": np.concatenate(xs), "t": np.concatenate(ts)})
    return pool
