"""Plain reference of a YOLOv2-16 training step, kept with the benchmark.

It follows the configuration file and nothing of the program under test: it
imports nothing from ``repro`` and takes no weights, scales or tables the
program made. One step is the untiled network (convolution with SAME zero
padding, batch norm over whole-batch statistics, leaky ReLU of slope 0.1,
2x2 max pool), the dense L2 loss ``mean((y - t)**2)``, clipping by the global
gradient norm, the cosine learning-rate schedule, and momentum SGD with weight
decay on every leaf.

Memory: at 608x608 and batch 64 the untiled step does not fit one chip. The
step therefore rematerialises each layer (``jax.checkpoint``): the forward
keeps only each layer's input and the backward recomputes one layer at a
time. Each layer sees the whole batch, so batch-norm statistics stay
whole-batch. ``devices`` may name several chips; the batch axis is then split
over them and XLA reduces the batch-norm sums across them.

``Numerics`` selects the arithmetic of the network: the dtype of its
activations, of the convolutions' operands and of batch norm, and the
convolutions' precision. Float32 at ``HIGHEST`` is the reference; bfloat16 is
the control (``bench/calibrate.py``), as a mixed-precision trainer runs it.
Either way the parameters, the momentum, the update and the loss's sum stay
float32.
``tiles`` convolves each tile of an n x m grid on its own, zero padded at the
tile's borders: the reference with the halo exchange left out, used to read that fault.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LEAKY_SLOPE = 0.1


@dataclasses.dataclass(frozen=True)
class Layer:
    kind: str          # "conv" or "max"
    size: int
    stride: int
    cin: int
    cout: int


@dataclasses.dataclass(frozen=True)
class Numerics:
    dtype: str = "float32"         # the network's activations and operands
    precision: str = "highest"     # lax.Precision name for the convolutions

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def lax_precision(self):
        return lax.Precision[self.precision.upper()]


REFERENCE = Numerics("float32", "highest")


@dataclasses.dataclass(frozen=True)
class Hyper:
    """The trainer tail as the configuration states it."""

    learning_rate: float
    momentum: float
    decay: float
    burn_in: int
    max_batches: int
    grad_clip: float
    lr_floor: float
    bn_eps: float


def layers_from_config(cfg: dict) -> list[Layer]:
    """The first ``depth`` darknet sections of the configuration."""
    ch, out = cfg["channels"], []
    for sec in cfg["layers"][: cfg["depth"]]:
        if sec["type"] == "convolutional":
            out.append(Layer("conv", sec["size"], sec["stride"], ch, sec["filters"]))
            ch = sec["filters"]
        elif sec["type"] == "maxpool":
            out.append(Layer("max", sec["size"], sec["stride"], ch, ch))
        else:
            raise ValueError(f"section type {sec['type']!r} is not in the reference")
    return out


def hyper_from_config(cfg: dict) -> Hyper:
    return Hyper(
        learning_rate=cfg["learning_rate"], momentum=cfg["momentum"],
        decay=cfg["decay"], burn_in=cfg["burn_in"], max_batches=cfg["max_batches"],
        grad_clip=cfg["grad_clip"], lr_floor=cfg["lr_floor"], bn_eps=cfg["bn_eps"],
    )


def out_shape(cfg: dict, batch: int) -> tuple[int, int, int, int]:
    h, w = cfg["height"], cfg["width"]
    layers = layers_from_config(cfg)
    for l in layers:
        h, w = h // l.stride, w // l.stride
    return batch, h, w, layers[-1].cout


def learning_rate(step: int, hp: Hyper) -> float:
    """Linear burn-in, then a cosine from the base rate down to
    ``lr_floor`` of it at ``max_batches``."""
    if step < hp.burn_in:
        return hp.learning_rate * min(1.0, (step + 1) / max(hp.burn_in, 1))
    frac = min(max((step - hp.burn_in) / max(hp.max_batches - hp.burn_in, 1), 0.0), 1.0)
    return hp.learning_rate * (hp.lr_floor + (1 - hp.lr_floor) * 0.5 * (1 + math.cos(math.pi * frac)))


def _conv(x, w, stride, num: Numerics, tiles):
    pad = w.shape[0] // 2
    n, m = tiles
    b, h, wd, c = x.shape
    if (n, m) != (1, 1):
        x = x.reshape(b, n, h // n, m, wd // m, c).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b * n * m, h // n, wd // m, c)
    conv = functools.partial(
        lax.conv_general_dilated, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    y = conv(x, w, precision=num.lax_precision)
    if (n, m) != (1, 1):
        _, oh, ow, co = y.shape
        y = y.reshape(b, n, m, oh, ow, co).transpose(0, 1, 3, 2, 4, 5)
        y = y.reshape(b, n * oh, m * ow, co)
    return y


def _maxpool(x, size, stride):
    if size != stride:
        raise ValueError("the reference pools non-overlapping windows only")
    b, h, w, c = x.shape
    return x.reshape(b, h // size, size, w // size, size, c).max(axis=(2, 4))


def layer_forward(x, p, layer: Layer, num: Numerics, eps: float, tiles=(1, 1)):
    if layer.kind == "max":
        return _maxpool(x, layer.size, layer.stride)
    dt = num.jdtype
    y = _conv(x, p["w"].astype(dt), layer.stride, num, tiles)
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    z = (y - mean) * lax.rsqrt(var + eps) * p["bn_scale"].astype(dt) + p["bn_bias"].astype(dt)
    return jnp.where(z > 0, z, LEAKY_SLOPE * z)


def _loss(params, x, t, count, layers, num: Numerics, eps: float, tiles):
    """The squared error summed in float32 over ``count``; each layer is
    rematerialised in the backward pass, so only the layer inputs are kept."""
    for p, l in zip(params, layers):
        x = jax.checkpoint(functools.partial(layer_forward, layer=l, num=num, eps=eps, tiles=tiles))(x, p)
    return jnp.sum(jnp.square(x.astype(jnp.float32) - t)) / count


def _update(params, mom, grads, lr, hp: Hyper):
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    scale = jnp.minimum(1.0, hp.grad_clip / (jnp.sqrt(sq) + 1e-6))
    grads = jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)
    mom = jax.tree.map(lambda m, g, p: hp.momentum * m + g + hp.decay * p, mom, grads, params)
    params = jax.tree.map(lambda p, m: p - lr * m, params, mom)
    return params, mom, grads


class Reference:
    """Training steps of the plain reference on ``devices``.

    ``step`` takes host arrays or device arrays and returns the step's loss,
    the clipped gradient the update used, and the new parameters and
    momentum. The gradient is one jitted program per shape."""

    def __init__(self, cfg: dict, devices: Sequence, num: Numerics = REFERENCE, tiles=(1, 1)):
        self.layers = layers_from_config(cfg)
        self.hp = hyper_from_config(cfg)
        self.num = num
        mesh = Mesh(np.array(devices), ("b",))
        self.batch_sharding = NamedSharding(mesh, P("b"))
        self.replicated = NamedSharding(mesh, P())
        self._grad = _jitted_grad(tuple(self.layers), num, self.hp.bn_eps, tuple(tiles))
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
            xj, tj = (x, t) if micro == 1 else (x[j * rows:(j + 1) * rows], t[j * rows:(j + 1) * rows])
            lj, gj = self._grad(params, xj, tj, count)
            loss += float(lj)
            grads = gj if grads is None else jax.tree.map(jnp.add, grads, gj)
        lr = learning_rate(step_index, self.hp)
        params, mom, grads = self._update(params, mom, grads, jnp.float32(lr))
        return loss, grads, params, mom

@functools.lru_cache(maxsize=None)
def _jitted_grad(layers: tuple, num: Numerics, eps: float, tiles: tuple):
    """The loss and its gradient as one jitted program, shared by every
    ``Reference`` of the process, so later seeds reuse what the first compiled."""
    loss = functools.partial(_loss, layers=layers, num=num, eps=eps, tiles=tiles)
    return jax.jit(jax.value_and_grad(loss))


@functools.lru_cache(maxsize=None)
def _jitted_update(hp: Hyper):
    return jax.jit(functools.partial(_update, hp=hp))
