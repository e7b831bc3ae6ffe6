"""Verify the chunked SSD scan + decode step against a naive sequential
recurrence, and the seq-parallel handoff against the single-shard run."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.mamba2 import _ssd_chunk_scan


def naive_ssm(x, dt, A, B, C):
    """Sequential oracle: h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t;
    y_t = C_t . h_t."""
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = np.repeat(np.asarray(B, np.float64), rep, axis=2)
    Ch = np.repeat(np.asarray(C, np.float64), rep, axis=2)
    xf = np.asarray(x, np.float64)
    dtf = np.asarray(dt, np.float64)
    Af = np.asarray(A, np.float64)
    hst = np.zeros((b, h, n, p))
    ys = np.zeros((b, t, h, p))
    for i in range(t):
        a = np.exp(dtf[:, i] * Af)                       # (b,h)
        upd = dtf[:, i, :, None, None] * Bh[:, i, :, :, None] * xf[:, i, :, None, :]
        hst = a[:, :, None, None] * hst + upd
        ys[:, i] = np.einsum("bhn,bhnp->bhp", Ch[:, i], hst)
    return ys, hst


key = jax.random.PRNGKey(0)
b, t, h, p, g, n = 2, 64, 4, 8, 2, 16
ks = jax.random.split(key, 5)
x = jax.random.normal(ks[0], (b, t, h, p))
dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
B = jax.random.normal(ks[3], (b, t, g, n)) * 0.5
C = jax.random.normal(ks[4], (b, t, g, n)) * 0.5

for chunk in (8, 16, 64):
    y, fin = _ssd_chunk_scan(x, dt, A, B, C, chunk)
    y_ref, fin_ref = naive_ssm(x, dt, A, B, C)
    err = np.max(np.abs(np.asarray(y, np.float64) - y_ref))
    ferr = np.max(np.abs(np.asarray(fin, np.float64) - fin_ref))
    print(f"[ssd chunk={chunk}] yerr={err:.3e} staterr={ferr:.3e}")
    assert err < 1e-3 and ferr < 1e-3

# sequence-parallel: 4 shards along T must equal the single-shard result
mesh = jax.make_mesh((4,), ("sp",))


def sharded(xs, dts, Bs, Cs):
    y, fin = _ssd_chunk_scan(xs, dts, A, Bs, Cs, 8, seq_axis="sp")
    return y


f = jax.shard_map(
    sharded,
    mesh=mesh,
    in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P(None, "sp")),
    out_specs=P(None, "sp"),
    check_vma=False,
)
y_sp = jax.jit(f)(x, dt, B, C)
y_ref, _ = naive_ssm(x, dt, A, B, C)
err = np.max(np.abs(np.asarray(y_sp, np.float64) - y_ref))
print(f"[ssd seq-parallel 4-shard] yerr={err:.3e}")
assert err < 1e-3
print("SSD CHECK OK")
