"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv2d_tiled.backward import (
    conv2d_dgrad_tile,
    conv2d_wgrad_tile,
)
from repro.kernels.conv2d_tiled.kernel import conv2d_tile
from repro.kernels.conv2d_tiled.ops import conv2d
from repro.kernels.conv2d_tiled.ref import conv2d_ref
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.kernel import rmsnorm as rmsnorm_kernel
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def _tol(dt):
    return dict(atol=2e-3, rtol=2e-2) if dt == jnp.bfloat16 else dict(atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # b, tq, tk, hq, hkv, dh, causal, window, dtype
    (2, 128, 128, 4, 4, 64, True, None, jnp.float32),
    (1, 256, 256, 8, 2, 64, True, None, jnp.float32),      # GQA 4x
    (1, 256, 256, 4, 1, 128, True, 96, jnp.float32),       # MQA + window
    (2, 128, 128, 4, 4, 64, False, None, jnp.float32),     # bidirectional
    (1, 192, 192, 4, 2, 64, True, None, jnp.float32),      # non-pow2 T
    (1, 128, 128, 4, 4, 64, True, None, jnp.bfloat16),
    (1, 128, 256, 4, 4, 64, False, None, jnp.float32),     # cross-length
]


@pytest.mark.parametrize("case", FA_CASES, ids=[str(c[:8]) for c in FA_CASES])
def test_flash_attention_fwd(case):
    b, tq, tk, hq, hkv, dh, causal, window, dt = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, tq, hq, dh), dt)
    k = jax.random.normal(ks[1], (b, tk, hkv, dh), dt)
    v = jax.random.normal(ks[2], (b, tk, hkv, dh), dt)
    out = flash_attention_fwd(
        q, k, v, causal=causal, window=window, bq=64, bk=64, interpret=True
    )
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dt)
    )


def test_flash_attention_grads_match_ref():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, None, 64, 64, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=True) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)


def test_flash_attention_jit_wrapper():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 4, 64))
    v = jax.random.normal(ks[2], (1, 128, 4, 64))
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, None, None, 64, 64, True))
    np.testing.assert_allclose(
        np.asarray(f(q, k, v)),
        np.asarray(attention_ref(q, k, v, causal=True)),
        atol=2e-5, rtol=1e-4,
    )


# ---------------------------------------------------------------------------
# conv2d tiled
# ---------------------------------------------------------------------------

CONV_CASES = [
    # n, h, w, cin, cout, k, stride, act, dtype
    (2, 18, 18, 16, 32, 3, 1, "leaky", jnp.float32),
    (1, 17, 17, 3, 32, 3, 2, "linear", jnp.float32),
    (2, 9, 9, 64, 100, 1, 1, "relu", jnp.float32),         # non-128 cout
    (1, 20, 20, 32, 64, 5, 1, "leaky", jnp.float32),
    (1, 18, 18, 16, 32, 3, 1, "leaky", jnp.bfloat16),
]


@pytest.mark.parametrize("case", CONV_CASES, ids=[str(c[:8]) for c in CONV_CASES])
def test_conv2d_tile(case):
    n, h, w_, cin, cout, k, s, act, dt = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (n, h, w_, cin), dt)
    w = jax.random.normal(ks[1], (k, k, cin, cout), dt) * 0.1
    b = jax.random.normal(ks[2], (cout,), dt)
    out = conv2d_tile(x, w, b, stride=s, act=act, bc=64, interpret=True)
    ref = conv2d_ref(x, w, b, stride=s, act=act)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dt)
    )


BLOCK_OH_CASES = [
    # h, w, cin, cout, k, stride, act, block_oh  (block_oh < OH throughout)
    (18, 18, 16, 32, 3, 1, "leaky", 4),       # OH=16, 4 even blocks
    (18, 18, 16, 32, 3, 1, "leaky", 5),       # OH=16, ragged last block
    (17, 17, 3, 32, 3, 2, "linear", 3),       # stride 2, OH=8, ragged
    (20, 20, 8, 24, 5, 1, "relu", 7),         # K=5, OH=16, ragged
    (12, 12, 8, 16, 1, 1, "leaky", 2),        # 1x1 conv
    (16, 16, 8, 24, 2, 2, "linear", 3),       # even kernel, stride 2
]


@pytest.mark.parametrize("case", BLOCK_OH_CASES, ids=[str(c) for c in BLOCK_OH_CASES])
def test_conv2d_tile_oh_blocked(case):
    """Spatial output-row blocking: block_oh < OH must stay exact, incl.
    ragged last blocks (OH % block_oh != 0) and strided input slabs."""
    h, w_, cin, cout, k, s, act, block_oh = case
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (2, h, w_, cin))
    w = jax.random.normal(ks[1], (k, k, cin, cout)) * 0.1
    b = jax.random.normal(ks[2], (cout,))
    oh = (h - k) // s + 1
    assert block_oh < oh
    out = conv2d_tile(x, w, b, stride=s, act=act, bc=64, block_oh=block_oh, interpret=True)
    ref = conv2d_ref(x, w, b, stride=s, act=act)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_conv2d_tile_block_oh_equivalence():
    """All block sizes produce identical results (the blocking is pure
    compute re-tiling, not an approximation)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (1, 14, 14, 8))
    w = jax.random.normal(ks[1], (3, 3, 8, 16)) * 0.1
    b = jax.random.normal(ks[2], (16,))
    full = conv2d_tile(x, w, b, stride=1, act="leaky", bc=64, block_oh=12, interpret=True)
    for boh in (1, 2, 3, 5, 12):
        out = conv2d_tile(x, w, b, stride=1, act="leaky", bc=64, block_oh=boh, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(full))


def test_conv2d_ops_wrapper_block_oh_grads():
    """block_oh is a nondiff re-tiling arg: custom_vjp grads unchanged."""
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 10, 10, 8))
    w = jax.random.normal(jax.random.PRNGKey(8), (3, 3, 8, 16)) * 0.1
    b = jnp.zeros((16,))
    gk = jax.grad(
        lambda x, w, b: jnp.sum(conv2d(x, w, b, 1, 1, "leaky", True, 3) ** 2),
        argnums=(0, 1, 2),
    )(x, w, b)

    def ref_loss(x, w, b):
        xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        return jnp.sum(conv2d_ref(xp, w, b, stride=1, act="leaky") ** 2)

    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-3)


def test_conv2d_padded_wrapper_matches_same_conv():
    """conv2d(pad=k//2) == the model stack's SAME conv + act."""
    from repro.core.spatial import LayerDef, apply_layer_reference, init_layer_params

    layer = LayerDef(3, 1, 8, 16, act="leaky", batch_norm=False)
    params = init_layer_params(jax.random.PRNGKey(3), layer)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 12, 8))
    ref = apply_layer_reference(x, params, layer)
    out = conv2d(x, params["w"], params["b"], 1, 1, "leaky", True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_conv2d_grads_match_ref():
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 10, 10, 8))
    w = jax.random.normal(jax.random.PRNGKey(6), (3, 3, 8, 16)) * 0.1
    b = jnp.zeros((16,))

    gk = jax.grad(lambda x, w, b: jnp.sum(conv2d(x, w, b, 1, 1, "leaky", True) ** 2),
                  argnums=(0, 1, 2))(x, w, b)
    def ref_loss(x, w, b):
        xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        return jnp.sum(conv2d_ref(xp, w, b, stride=1, act="leaky") ** 2)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# conv2d backward kernels (dgrad + wgrad, DESIGN.md §6)
# ---------------------------------------------------------------------------

BWD_CASES = [
    # n, h, w, cin, cout, k, stride, pad, act
    (1, 10, 10, 8, 16, 3, 1, 1, "leaky"),
    (2, 17, 17, 3, 32, 3, 2, 0, "linear"),
    (1, 12, 12, 4, 10, 3, 2, 1, "relu"),      # ragged: (12+2-3) % 2 != 0
    (2, 9, 9, 6, 7, 1, 1, 0, "leaky"),        # 1x1 conv, non-128 cout
    (1, 20, 20, 5, 12, 5, 1, 2, "relu"),      # K=5
    (1, 16, 16, 8, 24, 2, 2, 0, "leaky"),     # even kernel, stride 2
]


def _bwd_data(case, seed=0):
    n, h, w_, cin, cout, k, s, pad, act = case
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (n, h, w_, cin))
    w = jax.random.normal(ks[1], (k, k, cin, cout)) * 0.1
    b = jax.random.normal(ks[2], (cout,))
    oh = (h + 2 * pad - k) // s + 1
    ow = (w_ + 2 * pad - k) // s + 1
    g = jax.random.normal(ks[3], (n, oh, ow, cout))
    return x, w, b, g


@pytest.mark.parametrize("case", BWD_CASES, ids=[str(c) for c in BWD_CASES])
def test_conv2d_backward_kernels_match_ref_vjp(case):
    """dgrad/wgrad Pallas kernels == jax.vjp of the XLA reference conv,
    including strided ragged geometries (trailing rows beyond the last
    window must receive zero gradient)."""
    n, h, w_, cin, cout, k, s, pad, act = case
    x, w, b, g = _bwd_data(case)

    def ref(x_, w_, b_):
        xp = jnp.pad(x_, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        return conv2d_ref(xp, w_, b_, stride=s, act=act)

    _, vjp = jax.vjp(ref, x, w, b)
    dx_r, dw_r, db_r = vjp(g)
    dx_k, dw_k, db_k = jax.vjp(
        lambda x_, w_, b_: conv2d(x_, w_, b_, s, pad, act, True, None), x, w, b
    )[1](g)
    np.testing.assert_allclose(np.asarray(dx_k), np.asarray(dx_r), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_r), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(db_k), np.asarray(db_r), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("stride", [1, 2])
def test_conv2d_dgrad_tile_direct(stride):
    """The dgrad kernel alone (pre-activation conv cotangent) vs the XLA
    transpose of the VALID conv."""
    k, h, w_, cin, cout = 3, 13, 13, 4, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (2, h, w_, cin))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, k, cin, cout)) * 0.1
    oh = (h - k) // stride + 1
    g = jax.random.normal(jax.random.PRNGKey(2), (2, oh, oh, cout))
    _, vjp = jax.vjp(lambda x_: conv2d_ref(x_, w, None, stride=stride), x)
    (dx_r,) = vjp(g)
    dx_k = conv2d_dgrad_tile(g, w, (h, w_), stride=stride, interpret=True)
    np.testing.assert_allclose(np.asarray(dx_k), np.asarray(dx_r), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("stride", [1, 2])
def test_conv2d_wgrad_tile_direct(stride):
    k, h, w_, cin, cout = 3, 13, 13, 4, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (2, h, w_, cin))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, k, cin, cout)) * 0.1
    oh = (h - k) // stride + 1
    g = jax.random.normal(jax.random.PRNGKey(2), (2, oh, oh, cout))
    _, vjp = jax.vjp(lambda w_: conv2d_ref(x, w_, None, stride=stride), w)
    (dw_r,) = vjp(g)
    dw_k = conv2d_wgrad_tile(x, g, k, stride=stride, bc=64, interpret=True)
    np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_r), atol=2e-5, rtol=1e-4)


def test_conv2d_dgrad_reuses_forward_blocking():
    """block_oh re-tiles the dgrad conv exactly like the forward kernel:
    results identical for every block size."""
    k, h = 3, 12
    x = jax.random.normal(jax.random.PRNGKey(0), (1, h, h, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, k, 4, 8)) * 0.1
    g = jax.random.normal(jax.random.PRNGKey(2), (1, h - k + 1, h - k + 1, 8))
    full = conv2d_dgrad_tile(g, w, (h, h), stride=1, interpret=True)
    for boh in (1, 2, 5):
        out = conv2d_dgrad_tile(g, w, (h, h), stride=1, block_oh=boh, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(full))


def test_conv2d_bias_free_grads():
    """b=None stays differentiable (None cotangent), matching the forward's
    bias-free support."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 10, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 8)) * 0.1
    gk = jax.grad(
        lambda x_, w_: jnp.sum(conv2d(x_, w_, None, 1, 1, "leaky", True) ** 2),
        argnums=(0, 1),
    )(x, w)
    gr = jax.grad(
        lambda x_, w_: jnp.sum(
            conv2d_ref(jnp.pad(x_, ((0, 0), (1, 1), (1, 1), (0, 0))), w_, None,
                       stride=1, act="leaky") ** 2
        ),
        argnums=(0, 1),
    )(x, w)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-3)


def test_conv2d_grad_jaxpr_has_no_xla_conv_fallback():
    """Acceptance: with the Pallas path, dgrad and wgrad lower through the
    backward kernels - no conv_general_dilated transpose anywhere in the
    gradient jaxpr."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 10, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16)) * 0.1
    b = jnp.zeros((16,))
    jx = jax.make_jaxpr(
        jax.grad(
            lambda x_, w_, b_: jnp.sum(conv2d(x_, w_, b_, 1, 1, "leaky", True) ** 2),
            argnums=(0, 1, 2),
        )
    )(x, w, b)
    assert "conv_general_dilated" not in str(jx)


# ---------------------------------------------------------------------------
# mixed precision (bf16 activations, fp32 filters) - both backends
# ---------------------------------------------------------------------------


def test_conv_backends_mixed_precision_promote_alike():
    """bf16 activations x fp32 filters: the pallas backend (incl. its
    synthesized zero bias) must follow the xla backend's promotion - fp32
    output - and match it numerically to bf16 tolerance."""
    from repro.core.backend import get_conv_backend

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 14, 14, 8), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16), jnp.float32) * 0.1
    outs = {}
    for name in ("xla", "pallas"):
        outs[name] = get_conv_backend(name)(x, w, None, stride=1, act="leaky")
        assert outs[name].dtype == jnp.float32, name
    np.testing.assert_allclose(
        np.asarray(outs["pallas"]), np.asarray(outs["xla"]), atol=2e-3, rtol=2e-2
    )


def test_conv_backends_mixed_precision_grads():
    """Gradient dtypes follow the primals (bf16 dx, fp32 dw) and values
    match the xla backend to bf16 tolerance."""
    from repro.core.backend import get_conv_backend

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 12, 4), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 8), jnp.float32) * 0.1
    grads = {}
    for name in ("xla", "pallas"):
        be = get_conv_backend(name)
        grads[name] = jax.grad(
            lambda x_, w_: jnp.sum(
                be(x_, w_, None, stride=1, act="leaky").astype(jnp.float32) ** 2
            ),
            argnums=(0, 1),
        )(x, w)
    for a, b in zip(grads["pallas"], grads["xla"]):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=5e-2, rtol=5e-2
        )


def test_pallas_interprets_on_cpu_backend_only(monkeypatch):
    """Interpret mode belongs to the CPU backend alone: on any other backend
    the pallas conv is lowered for real - it compiles or raises, never runs
    slowly in the interpreter (here, off an accelerator, it raises)."""
    from repro.core import backend as backend_mod

    be = backend_mod.get_conv_backend("pallas")
    x, w = jnp.ones((1, 6, 6, 4)), jnp.ones((3, 3, 4, 8))
    assert backend_mod.pallas_interpret()
    assert be(x, w, None, stride=1, act="linear").shape == (1, 4, 4, 8)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert not backend_mod.pallas_interpret()
    with pytest.raises(ValueError, match="interpret"):
        be(x, w, None, stride=1, act="linear")


def test_conv2d_tile_mixed_precision_kernel():
    """Kernel-level bf16 x fp32 case vs the (promoting) reference."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 14, 14, 8), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16), jnp.float32) * 0.1
    out = conv2d_tile(x, w, None, stride=1, act="leaky", bc=64, interpret=True)
    ref = conv2d_ref(x, w, None, stride=1, act="leaky")
    assert out.dtype == ref.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-2)


# ---------------------------------------------------------------------------
# block_oh threading: the planner's value must reach the kernel grid
# ---------------------------------------------------------------------------

from repro.analysis.hlo import pallas_grids as _pallas_grids  # noqa: E402


def test_backend_block_oh_reaches_kernel_grid():
    """A non-default block_oh passed through the backend registry must show
    up as the OH-block grid dimension of the pallas_call (the seed backend
    dropped it and always used the auto default)."""
    from repro.core.backend import get_conv_backend

    be = get_conv_backend("pallas")
    x = jnp.zeros((1, 18, 18, 8))
    w = jnp.zeros((3, 3, 8, 16))
    oh = 16
    jx_default = jax.make_jaxpr(
        lambda x_, w_: be(x_, w_, None, stride=1, act="linear")
    )(x, w)
    jx_blocked = jax.make_jaxpr(
        lambda x_, w_: be(x_, w_, None, stride=1, act="linear", block_oh=2)
    )(x, w)
    assert any(g[-1] == 1 for g in _pallas_grids(jx_default))      # auto: full OH
    assert any(g[-1] == oh // 2 for g in _pallas_grids(jx_blocked))

RMS_CASES = [
    ((4, 128, 512), jnp.float32),
    ((1000, 256), jnp.float32),                # non-multiple rows
    ((2, 64, 1024), jnp.bfloat16),
    ((7, 384), jnp.float32),
]


@pytest.mark.parametrize("case", RMS_CASES, ids=[str(c) for c in RMS_CASES])
def test_rmsnorm(case):
    shape, dt = case
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dt)
    s = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), dt)
    out = rmsnorm_kernel(x, s, block_rows=128, interpret=True)
    ref = rmsnorm_ref(x, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dt)
    )


def test_rmsnorm_grads_match_ref():
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 256))
    s = jax.random.normal(jax.random.PRNGKey(3), (256,))
    gk = jax.grad(lambda x, s: jnp.sum(rmsnorm(x, s, 1e-6, True) ** 2), argnums=(0, 1))(x, s)
    gr = jax.grad(lambda x, s: jnp.sum(rmsnorm_ref(x, s) ** 2), argnums=(0, 1))(x, s)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)


def test_rmsnorm_matches_model_norm():
    from repro.models.common import rms_norm

    x = jax.random.normal(jax.random.PRNGKey(4), (8, 64, 128), jnp.bfloat16)
    s = jnp.ones((128,), jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(rmsnorm_kernel(x, s, interpret=True), np.float32),
        np.asarray(rms_norm(x, s), np.float32),
        atol=2e-3, rtol=2e-2,
    )


# ---------------------------------------------------------------------------
# SSD chunk kernel (mamba2)
# ---------------------------------------------------------------------------

SSD_CASES = [
    # b, t, h, p, g, n, chunk
    (2, 128, 4, 16, 2, 8, 32),
    (1, 64, 2, 8, 1, 4, 64),      # single chunk, no GQA-style groups
    (1, 256, 8, 32, 2, 16, 64),
]


@pytest.mark.parametrize("case", SSD_CASES, ids=[str(c) for c in SSD_CASES])
def test_ssd_chunk_kernel(case):
    from repro.kernels.ssd_chunk.kernel import ssd_chunk_fwd
    from repro.kernels.ssd_chunk.ref import ssd_chunk_ref

    b, t, h, p, g, n, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, t, g, n))
    Cm = jax.random.normal(ks[4], (b, t, g, n))
    out_k = ssd_chunk_fwd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    out_r = ssd_chunk_ref(x, dt, A, Bm, Cm, chunk=chunk)
    for name, a, b_ in zip(("y", "S", "decay", "pref"), out_k, out_r):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-5, rtol=1e-4, err_msg=name
        )


def test_ssd_scan_matches_model_and_grads():
    from repro.kernels.ssd_chunk.ops import ssd_scan
    from repro.models.mamba2 import _ssd_chunk_scan

    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    b, t, h, p, g, n, q = 2, 128, 4, 16, 2, 8, 32
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, t, g, n))
    Cm = jax.random.normal(ks[4], (b, t, g, n))
    y_k, fin_k = ssd_scan(x, dt, A, Bm, Cm, q, True)
    y_m, fin_m = _ssd_chunk_scan(x, dt, A, Bm, Cm, chunk=q)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(fin_k), np.asarray(fin_m), atol=2e-5, rtol=1e-4)
    gk = jax.grad(lambda x_: jnp.sum(ssd_scan(x_, dt, A, Bm, Cm, q, True)[0] ** 2))(x)
    gm = jax.grad(lambda x_: jnp.sum(_ssd_chunk_scan(x_, dt, A, Bm, Cm, chunk=q)[0] ** 2))(x)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gm), atol=5e-4, rtol=1e-3)
