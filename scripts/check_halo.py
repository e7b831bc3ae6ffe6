"""SPMD halo-exchange unit checks on 8 fake devices (subprocess target).

``python scripts/check_halo.py`` runs every check and prints ``HALO CHECK
OK``. ``python scripts/check_halo.py --single-shard`` runs only the mesh
axes of one shard, where ``halo_exchange_1d`` zero-pads instead of
exchanging, and prints one JSON object for ``tests/test_spmd.py`` to judge:
for each grid (1x1, 1x2, 2x1) and halo width, the ``ppermute``s of
``halo_exchange_2d``'s jaxpr per axis and the largest gap to the zero-padded
map's windows; for each width, the adjoint gaps on a 1-shard axis; and for
1x1 and 1x2, the int8 wire codec with error feedback through
``make_deferred_grad_step`` against the uncompressed step.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json
import math
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.core.fusion import build_stack_plan, make_deferred_grad_step, reference_forward
from repro.core.halo import (
    EFBag,
    WireCtx,
    halo_exchange_1d,
    halo_exchange_1d_packed,
    halo_exchange_2d,
    halo_exchange_2d_packed,
    send_boundary_sum_1d,
)
from repro.core.spatial import init_stack_params
from repro.models.yolo import l2_loss_local, yolov2_16_layers
from repro.optim.compression import get_codec


def grid(shape, names):
    """A mesh of the first ``prod(shape)`` devices. Its axes are Auto: an
    eager ``shard_map`` over Explicit axes rejects the packed exchange's
    zero strips (an unexpected sharding override in ``jnp.zeros``)."""
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(shape),
                         devices=jax.devices()[:math.prod(shape)])


mesh1 = grid((8,), ("x",))
mesh2 = grid((4, 2), ("r", "c"))
mesh_pair = grid((2,), ("x",))      # 2-shard axis
mesh_one = grid((1,), ("x",))       # 1-shard axis: a zero pad, no exchange
mesh22 = grid((2, 2), ("r", "c"))

SINGLE_GRIDS = ((1, 1), (1, 2), (2, 1))
SINGLE_WIDTHS = ((0, 0), (1, 1), (1, 2))


def ppermutes(closed_jaxpr) -> dict[str, int]:
    """``ppermute`` equations per axis name, sub-jaxprs included."""
    out: dict[str, int] = {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "ppermute":
                for ax in eqn.params["axis_name"]:
                    out[ax] = out.get(ax, 0) + 1
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(closed_jaxpr.jaxpr)
    return out


def check_1d():
    for mesh, n in ((mesh_one, 1), (mesh1, 8)):
        x = jnp.arange(n * 4 * 3, dtype=jnp.float32).reshape(n * 4, 3)

        f = jax.shard_map(
            lambda x: halo_exchange_1d(x, 2, 1, "x", dim=0),
            mesh=mesh, in_specs=P("x", None), out_specs=P("x", None), check_vma=False,
        )
        y = np.asarray(f(x)).reshape(n, 7, 3)           # 4 + 2 + 1 rows per shard
        xs = np.asarray(x).reshape(n, 4, 3)
        for i in range(n):
            want_lo = xs[i - 1][-2:] if i > 0 else np.zeros((2, 3))
            want_hi = xs[i + 1][:1] if i < n - 1 else np.zeros((1, 3))
            np.testing.assert_array_equal(y[i, :2], want_lo)
            np.testing.assert_array_equal(y[i, 2:6], xs[i])
            np.testing.assert_array_equal(y[i, 6:], want_hi)
    print("halo 1d ok (1- and 8-shard axes)")


def check_packed_1d():
    """Packed exchange must deliver the same strips the eager exchange
    concatenates, on the 1-shard (zero strips against the eager zero pad),
    the 2-shard (single swap ppermute) and the n>2 (two shifted ppermutes)
    paths."""
    for mesh, n in ((mesh_one, 1), (mesh_pair, 2), (mesh1, 8)):
        x = jnp.arange(n * 4 * 3, dtype=jnp.float32).reshape(n * 4, 3)
        for lo, hi in ((2, 1), (1, 2), (2, 0), (0, 1), (0, 0)):
            eager = jax.shard_map(
                lambda x: halo_exchange_1d(x, lo, hi, "x", dim=0),
                mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
                check_vma=False,
            )
            def packed_cat(x, lo=lo, hi=hi):
                lo_s, hi_s = halo_exchange_1d_packed(x, lo, hi, "x", dim=0)
                parts = [p for p in (lo_s, x, hi_s) if p.shape[0] > 0]
                return jnp.concatenate(parts, axis=0)

            packed = jax.shard_map(
                packed_cat,
                mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
                check_vma=False,
            )
            np.testing.assert_array_equal(np.asarray(eager(x)), np.asarray(packed(x)))
    # the 2-shard both-sides case must lower to exactly ONE ppermute
    jaxpr = jax.make_jaxpr(
        jax.shard_map(
            lambda x: halo_exchange_1d_packed(x, 2, 1, "x", dim=0),
            mesh=mesh_pair, in_specs=P("x", None),
            out_specs=(P("x", None), P("x", None)), check_vma=False,
        )
    )(jnp.zeros((8, 3)))
    assert ppermutes(jaxpr) == {"x": 1}, str(jaxpr)
    print("packed 1d ok (2-shard axis: 1 ppermute)")


def check_packed_2d():
    """Assembled packed 2-D exchange == eager 2-round exchange (corners
    ride the column round in both)."""
    x = jnp.arange(8 * 8 * 2, dtype=jnp.float32).reshape(8, 8, 2)
    halo = (1, 2, 2, 1)

    eager = jax.shard_map(
        lambda x: halo_exchange_2d(x, halo, "r", "c", dims=(0, 1)),
        mesh=mesh22, in_specs=P("r", "c", None), out_specs=P("r", "c", None),
        check_vma=False,
    )

    def packed_fn(x):
        x_rows, c_lo, c_hi = halo_exchange_2d_packed(x, halo, "r", "c", dims=(0, 1))
        parts = [p for p in (c_lo, x_rows, c_hi) if p.shape[1] > 0]
        return jnp.concatenate(parts, axis=1)

    packed = jax.shard_map(
        packed_fn,
        mesh=mesh22, in_specs=P("r", "c", None), out_specs=P("r", "c", None),
        check_vma=False,
    )
    np.testing.assert_array_equal(np.asarray(eager(x)), np.asarray(packed(x)))
    print("packed 2d (corners incl.) ok")


def adjoint_case(mesh, n, lo, hi):
    """``(<g, H(x)>, <H^T(g), x>, AD's cotangent of x, H^T(g))`` for
    ``H = halo_exchange_1d`` and ``H^T = send_boundary_sum_1d`` on an
    ``n``-shard axis, with 4 rows a shard."""
    shard_rows = 4
    k1, k2 = jax.random.split(jax.random.PRNGKey(lo * 7 + hi), 2)
    x = jax.random.normal(k1, (n * shard_rows, 3))
    g = jax.random.normal(k2, (n * (shard_rows + lo + hi), 3))

    H = jax.shard_map(
        lambda x: halo_exchange_1d(x, lo, hi, "x", dim=0),
        mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
        check_vma=False,
    )
    Ht = jax.shard_map(
        lambda y: send_boundary_sum_1d(y, lo, hi, "x", dim=0),
        mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
        check_vma=False,
    )
    gx = jax.grad(lambda x: jnp.vdot(H(x), g))(x)
    return float(jnp.vdot(H(x), g)), float(jnp.vdot(x, Ht(g))), np.asarray(gx), np.asarray(Ht(g))


def check_adjoint():
    """Property sweep: send_boundary_sum_1d is the exact adjoint of
    halo_exchange_1d - <g, H(x)> == <H^T(g), x> for every halo geometry
    (lo, hi) in a grid, on a 1-shard (the zero pad), a 2-shard and an
    8-shard axis, and AD through halo_exchange_1d reproduces H^T exactly."""
    for mesh, n in ((mesh_one, 1), (mesh_pair, 2), (mesh1, 8)):
        for lo in range(0, 4):
            for hi in range(0, 4):
                lhs, rhs, gx, htg = adjoint_case(mesh, n, lo, hi)
                np.testing.assert_allclose(lhs, rhs, rtol=1e-5, err_msg=f"n={n} lo={lo} hi={hi}")
                # and AD through halo_exchange produces exactly the adjoint
                np.testing.assert_allclose(
                    gx, htg, rtol=1e-5, err_msg=f"AD n={n} lo={lo} hi={hi}",
                )
    print("halo adjoint property sweep ok (1-, 2- and 8-shard axes, halos 0..3)")


def check_wire_codec_adjoint():
    """Per-codec ``send_boundary_sum_1d`` sweep (DESIGN.md §12).  codec=none
    is the exact adjoint (``check_adjoint``); int8/topk ship quantised
    strips under error feedback, so over T repeated steps with the same
    cotangent the telescoping invariant holds *exactly* (up to fp32):

        T * exact - sum_t out_t == fold(residual_T)

    i.e. everything the codec withheld is precisely the final residual, and
    the mean applied adjoint converges to the true one at rate 1/T."""
    lo, hi = 2, 1
    rows, ch, T = 4, 3, 8
    for mesh, n in ((mesh_pair, 2), (mesh1, 8)):
        y = jax.random.normal(jax.random.PRNGKey(3), (n * (rows + lo + hi), ch))
        exact_f = jax.shard_map(
            lambda v: send_boundary_sum_1d(v, lo, hi, "x", dim=0),
            mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
            check_vma=False,
        )
        exact = np.asarray(exact_f(y))
        for spec in ("int8", "topk:0.5"):
            codec = get_codec(spec)

            def step_fn(v, res_lo, res_hi):
                bag = EFBag("buffers", [res_lo, res_hi])
                out = send_boundary_sum_1d(
                    v, lo, hi, "x", dim=0, wire=WireCtx(codec, bag)
                )
                new_lo, new_hi = bag.emitted
                return out, new_lo, new_hi

            stepped = jax.shard_map(
                step_fn, mesh=mesh,
                in_specs=(P("x", None),) * 3,
                out_specs=(P("x", None),) * 3, check_vma=False,
            )
            res_lo = jnp.zeros((n * lo, ch))
            res_hi = jnp.zeros((n * hi, ch))
            total = np.zeros_like(exact)
            first_err = None
            for t in range(T):
                out, res_lo, res_hi = stepped(y, res_lo, res_hi)
                total = total + np.asarray(out)
                if first_err is None:
                    first_err = float(np.max(np.abs(np.asarray(out) - exact)))
            # fold(residual_T): reuse the uncompressed adjoint on a map whose
            # strips are the final residuals and whose core is zero
            vres = np.zeros((n, rows + lo + hi, ch), np.float32)
            vres[:, :lo] = np.asarray(res_lo).reshape(n, lo, ch)
            vres[:, rows + lo:] = np.asarray(res_hi).reshape(n, hi, ch)
            folded = np.asarray(exact_f(jnp.asarray(vres.reshape(-1, ch))))
            np.testing.assert_allclose(
                T * exact - total, folded, atol=1e-4,
                err_msg=f"telescoping broken: n={n} codec={spec}",
            )
            # and the mean applied adjoint converges at rate ~1/T (factor 2:
            # the EF residual is bounded but can sit above the first step's)
            mean_err = float(np.max(np.abs(total / T - exact)))
            assert mean_err <= 2.0 * first_err / T + 1e-5, (
                f"EF not converging: n={n} codec={spec} "
                f"first={first_err:.3e} mean@{T}={mean_err:.3e}"
            )
    print(f"wire-codec EF telescoping ok (int8, topk:0.5; {T} steps, 2- and 8-shard axes)")


def check_2d():
    x = jnp.arange(16 * 8 * 2, dtype=jnp.float32).reshape(16, 8, 2)

    f = jax.shard_map(
        lambda x: halo_exchange_2d(x, (1, 1, 1, 1), "r", "c", dims=(0, 1)),
        mesh=mesh2, in_specs=P("r", "c", None), out_specs=P("r", "c", None),
        check_vma=False,
    )
    y = np.asarray(f(x))
    # global reassembly: each (4+2, 4+2) tile must equal the zero-padded
    # global map's window (corner data carried by the 2-round exchange)
    xp = np.pad(np.asarray(x), ((1, 1), (1, 1), (0, 0)))
    ys = y.reshape(4, 6, 2, 6, 2).transpose(0, 2, 1, 3, 4)
    for i in range(4):
        for j in range(2):
            win = xp[i * 4 : i * 4 + 6, j * 4 : j * 4 + 6]
            np.testing.assert_array_equal(ys[i, j], win)
    print("halo 2d (8-neighbour incl. corners) ok")


def single_shard_exchange(shape, lo, hi):
    """``halo_exchange_2d`` on a ``shape`` grid of an 8x12 map: its
    ``ppermute``s per axis, and the largest gap between each extended tile
    and its window of the zero-padded map."""
    r, c = shape
    b, h, w, ch = 2, 8, 12, 3
    f = jax.shard_map(
        lambda t: halo_exchange_2d(t, (lo, hi, lo, hi), "r", "c", dims=(1, 2)),
        mesh=grid(shape, ("r", "c")), in_specs=P(None, "r", "c", None),
        out_specs=P(None, "r", "c", None), check_vma=False,
    )
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(lo * 3 + hi), (b, h, w, ch)))
    y = np.asarray(f(x))
    th, tw = h // r, w // c
    xp = np.pad(x, ((0, 0), (lo, hi), (lo, hi), (0, 0)))
    want = np.concatenate([
        np.concatenate([xp[:, i * th:(i + 1) * th + lo + hi, j * tw:(j + 1) * tw + lo + hi]
                        for j in range(c)], axis=2)
        for i in range(r)
    ], axis=1)
    return {"ppermute": ppermutes(jax.make_jaxpr(f)(x)),
            "gap": float(np.max(np.abs(y - want)))}


def single_shard_codec(shape):
    """The int8 codec with error feedback through ``make_deferred_grad_step``
    (the collect probe, then the residual buffers of two microbatches) on a
    ``shape`` grid of 4 YOLOv2-16 layers: its ``ppermute``s per axis, its
    loss, and its largest gap to the uncompressed step."""
    mesh = grid(shape, ("th", "tw"))
    layers = yolov2_16_layers()[:4]
    hw = (16, 16)
    params = init_stack_params(jax.random.PRNGKey(0), layers)
    out = reference_forward(params, jnp.zeros((1, *hw, 3)), build_stack_plan(hw, layers, 1, 1))
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 2, *hw, 3))
    ts = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (2, 2, *out.shape[1:]))

    def step(codec):
        plan = build_stack_plan(hw, layers, *shape, wire_codec=codec)
        return make_deferred_grad_step(plan, mesh, l2_loss_local, microbatches=2)

    loss, grads = jax.jit(step("int8"))(params, xs, ts)
    loss0, grads0 = jax.jit(step("none"))(params, xs, ts)
    gap = max(float(jnp.max(jnp.abs(a - b))) for a, b in
              zip(jax.tree.leaves((loss, grads)), jax.tree.leaves((loss0, grads0))))
    return {"ppermute": ppermutes(jax.make_jaxpr(step("int8"))(params, xs, ts)),
            "loss": float(loss), "gap": gap}


def single_shard_report():
    adjoint = {}
    for lo, hi in SINGLE_WIDTHS:
        lhs, rhs, gx, htg = adjoint_case(mesh_one, 1, lo, hi)
        adjoint[f"{lo}-{hi}"] = {"vdot_gap": abs(lhs - rhs) / abs(rhs),
                                 "ad_gap": float(np.max(np.abs(gx - htg)))}
    return {
        "exchange": {f"{r}x{c}-{lo}-{hi}": single_shard_exchange((r, c), lo, hi)
                     for r, c in SINGLE_GRIDS for lo, hi in SINGLE_WIDTHS},
        "adjoint": adjoint,
        "codec": {f"{r}x{c}": single_shard_codec((r, c)) for r, c in ((1, 1), (1, 2))},
    }


if __name__ == "__main__":
    if sys.argv[1:] == ["--single-shard"]:
        print(json.dumps(single_shard_report()))
        sys.exit(0)
    check_1d()
    check_packed_1d()
    check_packed_2d()
    check_adjoint()
    check_wire_codec_adjoint()
    check_2d()
    print("HALO CHECK OK")
