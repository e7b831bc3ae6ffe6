"""Plain reference of a training step of a network with residual shortcuts
(YOLOv3's Darknet-53), kept with the benchmark.

It follows the configuration file and nothing of the program under test: it
imports nothing from ``repro`` and takes no weights, scales or tables the
program made. Of ``bench/reference.py`` it shares the arithmetic settings
(``Numerics``), the trainer tail (``Hyper``, ``learning_rate``, ``_update``),
the convolution (``_conv``: SAME zero padding of ``size // 2`` on every
side, as darknet pads, stride 2 included) and the leaky slope. One step is
the untiled network: each ``convolutional`` section a convolution, batch
norm over the microbatch's statistics and leaky ReLU; each ``shortcut``
section (``from=-3``, ``activation=linear``) the previous section's output
plus the saved output ``-from`` sections back. Then the dense L2 loss
``mean((y - t)**2)``, clipping by the global gradient norm, the cosine
learning-rate schedule, and momentum SGD with weight decay on every leaf.

Each section is rematerialised (``jax.checkpoint``), so the forward keeps
each section's output and the backward recomputes one section at a time.
Float32 at ``HIGHEST`` is the reference; ``Numerics("bfloat16", "default")``
is the control. ``shortcuts=False`` leaves every shortcut out: a fault the
comparison has to catch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from reference import LEAKY_SLOPE, REFERENCE, Hyper, Numerics, _conv, _update, learning_rate


@dataclasses.dataclass(frozen=True)
class Layer:
    kind: str          # "conv" or "shortcut"
    size: int
    stride: int
    cin: int
    cout: int
    offset: int = 0    # a shortcut's from=


def layers_from_config(cfg: dict) -> list[Layer]:
    """The first ``depth`` darknet sections of the configuration."""
    ch, out = cfg["channels"], []
    for sec in cfg["layers"][: cfg["depth"]]:
        if sec["type"] == "convolutional":
            out.append(Layer("conv", sec["size"], sec["stride"], ch, sec["filters"]))
            ch = sec["filters"]
        elif sec["type"] == "shortcut":
            if sec["from"] >= 0 or sec.get("activation", "linear") != "linear":
                raise ValueError(f"the reference adds a linear shortcut from an earlier section: {sec}")
            out.append(Layer("shortcut", 1, 1, ch, ch, sec["from"]))
        else:
            raise ValueError(f"section type {sec['type']!r} is not in the residual reference")
    return out


def hyper_from_config(cfg: dict) -> Hyper:
    keys = ("learning_rate", "momentum", "decay", "burn_in", "max_batches", "grad_clip",
            "lr_floor", "bn_eps")
    return Hyper(**{k: cfg[k] for k in keys})


def out_shape(cfg: dict, batch: int) -> tuple[int, int, int, int]:
    """darknet's output extent of a ``size``-``stride`` section padded by
    ``size // 2``: ``(h + 2 * (size // 2) - size) // stride + 1``."""
    h, w = cfg["height"], cfg["width"]
    layers = layers_from_config(cfg)
    for l in layers:
        h = (h + 2 * (l.size // 2) - l.size) // l.stride + 1
        w = (w + 2 * (l.size // 2) - l.size) // l.stride + 1
    return batch, h, w, layers[-1].cout


def conv_forward(x, p, layer: Layer, num: Numerics, eps: float):
    """A convolutional section: convolution, batch norm, leaky ReLU."""
    dt = num.jdtype
    y = _conv(x, p["w"].astype(dt), layer.stride, num, (1, 1))
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    z = (y - mean) * lax.rsqrt(var + eps) * p["bn_scale"].astype(dt) + p["bn_bias"].astype(dt)
    return jnp.where(z > 0, z, LEAKY_SLOPE * z)


def _loss(params, x, t, count, layers, num: Numerics, eps: float, shortcuts: bool):
    outs = [x]                          # outs[i + 1]: section i's output
    for i, (p, l) in enumerate(zip(params, layers)):
        if l.kind == "shortcut":
            x = x + outs[i + 1 + l.offset] if shortcuts else x
        else:
            x = jax.checkpoint(functools.partial(conv_forward, layer=l, num=num, eps=eps))(x, p)
        outs.append(x)
    return jnp.sum(jnp.square(x.astype(jnp.float32) - t)) / count


class Reference:
    """Training steps of the plain residual reference on ``devices``; the
    interface of ``reference.Reference``."""

    def __init__(self, cfg: dict, devices: Sequence, num: Numerics = REFERENCE,
                 shortcuts: bool = True):
        self.layers = layers_from_config(cfg)
        self.hp = hyper_from_config(cfg)
        self.num = num
        mesh = Mesh(np.array(devices), ("b",))
        self.batch_sharding = NamedSharding(mesh, P("b"))
        self.replicated = NamedSharding(mesh, P())
        self._grad = _jitted_grad(tuple(self.layers), num, self.hp.bn_eps, shortcuts)
        self._update = _jitted_update(self.hp)

    def place_params(self, tree):
        """Host leaves as float32, replicated on the reference's devices."""
        return jax.device_put(jax.tree.map(lambda a: np.asarray(a, np.float32), tree), self.replicated)

    def place_batch(self, x, t):
        """The images in the network's dtype, the targets in float32."""
        x = np.asarray(x).astype(self.num.jdtype)
        return tuple(jax.device_put(a, self.batch_sharding) for a in (x, np.asarray(t, np.float32)))

    def step(self, params, mom, x, t, step_index: int, micro: int = 1):
        """One step over the batch ``x, t``, split into ``micro`` microbatches
        that each take batch-norm statistics of their own, as darknet's
        subdivisions do; the loss is the mean over the whole batch."""
        rows = x.shape[0] // micro
        count = float(np.prod(t.shape))
        loss, grads = 0.0, None
        for j in range(micro):
            xj, tj = x[j * rows:(j + 1) * rows], t[j * rows:(j + 1) * rows]
            lj, gj = self._grad(params, xj, tj, count)
            loss += float(lj)
            grads = gj if grads is None else jax.tree.map(jnp.add, grads, gj)
            del lj, gj
        lr = learning_rate(step_index, self.hp)
        params, mom, grads = self._update(params, mom, grads, jnp.float32(lr))
        return loss, grads, params, mom


@functools.lru_cache(maxsize=None)
def _jitted_grad(layers: tuple, num: Numerics, eps: float, shortcuts: bool):
    loss = functools.partial(_loss, layers=layers, num=num, eps=eps, shortcuts=shortcuts)
    return jax.jit(jax.value_and_grad(loss))


@functools.lru_cache(maxsize=None)
def _jitted_update(hp: Hyper):
    return jax.jit(functools.partial(_update, hp=hp))
