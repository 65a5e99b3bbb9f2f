"""``unet.apply_conv``: the native convolution against the im2col form
(patches + ``apply_linear``) it replaced, the recorder's im2col counts,
the packed-weight fallback, and that no shipped policy builds patches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import quant
from repro.core.policy import Q3_K_POLICY, Q8_0_POLICY
from repro.core.qlinear import (Linear, apply_linear, quantize_params,
                                set_recorder)
from repro.models.unet import (TINY_UNET, Conv, apply_conv, apply_unet,
                               init_unet)
from repro.models.vae import TINY_VAE, apply_vae_decoder, init_vae_decoder


def im2col_conv(p: Conv, x, stride=1):
    """GGML's lowering: the (B, H', W', C*k*k) patch tensor, then a
    matmul against the stored (O, C*k*k) weight."""
    pad = (p.k - 1) // 2
    patches = jax.lax.conv_general_dilated_patches(
        x, (p.k, p.k), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return apply_linear(p.lin, patches)


def make_conv(key, cin, cout, k, w_dtype):
    kw, kb = jax.random.split(key)
    w = (jax.random.normal(kw, (cout, cin * k * k), jnp.float32)
         * (cin * k * k) ** -0.5)
    b = jax.random.normal(kb, (cout,), jnp.float32) * 0.1
    return Conv(Linear(w.astype(w_dtype), b.astype(w_dtype), "conv"), k)


def recorded(fn, *args):
    rec = []
    set_recorder(lambda **kw: rec.append(
        (kw["role"], kw["m"], kw["n"], kw["k"])))
    try:
        jax.eval_shape(fn, *args)
    finally:
        set_recorder(None)
    return rec


def conv_eqns(jaxpr):
    """Every ``conv_general_dilated`` in ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            yield eqn
        for v in eqn.params.values():
            for s in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(s, ClosedJaxpr):
                    yield from conv_eqns(s.jaxpr)
                elif isinstance(s, Jaxpr):
                    yield from conv_eqns(s)


@pytest.mark.parametrize("w_dtype", [jnp.float16, jnp.bfloat16],
                         ids=["f16", "bf16"])
@pytest.mark.parametrize("k,stride", [(1, 1), (1, 2), (3, 1), (3, 2)])
@pytest.mark.parametrize("shape", [(2, 9, 6, 5, 7), (1, 8, 13, 12, 20)],
                         ids=["9x6_c5_o7", "8x13_c12_o20"])
def test_native_conv_matches_im2col(shape, k, stride, w_dtype):
    b, h, w, cin, cout = shape
    kx, kp = jax.random.split(jax.random.PRNGKey(h * w + k))
    p = make_conv(kp, cin, cout, k, w_dtype)
    x = jax.random.normal(kx, (b, h, w, cin), jnp.float32).astype(
        jnp.bfloat16)
    got = jax.jit(apply_conv, static_argnums=2)(p, x, stride)
    want = jax.jit(im2col_conv, static_argnums=2)(p, x, stride)
    assert got.shape == want.shape == (b, -(-h // stride), -(-w // stride),
                                       cout)
    assert got.dtype == want.dtype == x.dtype
    # Same operands and f32 accumulation, summed in another order: the
    # results differ by at most the final rounding to x's dtype.
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    eps = float(jnp.finfo(x.dtype).eps)
    np.testing.assert_allclose(got, want, rtol=eps,
                               atol=eps * 1e-2 * np.abs(want).max())
    # The recorder sees the same im2col product either way.
    assert (recorded(lambda p, x: apply_conv(p, x, stride), p, x)
            == recorded(lambda p, x: im2col_conv(p, x, stride), p, x)
            == [("conv", b * got.shape[1] * got.shape[2], cout,
                 cin * k * k)])


@pytest.mark.parametrize("k,stride", [(1, 1), (3, 1), (3, 2)])
def test_packed_conv_weight_keeps_im2col(k, stride):
    # C*k*k divides Q8_0's 32-element blocks for both kernel sizes.
    p = make_conv(jax.random.PRNGKey(k), 32, 16, k, jnp.float32)
    p = Conv(Linear(quant.quantize(p.lin.w, "q8_0"), p.lin.b, "conv"), k)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 6, 32),
                          jnp.float32).astype(jnp.bfloat16)
    got = jax.jit(apply_conv, static_argnums=2)(p, x, stride)
    want = jax.jit(im2col_conv, static_argnums=2)(p, x, stride)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    if k > 1:
        jaxpr = jax.make_jaxpr(lambda p, x: apply_conv(p, x, stride))(p, x)
        assert any(c.params["feature_group_count"] > 1
                   for c in conv_eqns(jaxpr.jaxpr))


@pytest.mark.parametrize("policy", [Q8_0_POLICY, Q3_K_POLICY],
                         ids=lambda p: p.name)
@pytest.mark.parametrize("model", ["unet", "vae"])
def test_shipped_policies_build_no_patches(model, policy):
    """The patches op is a grouped convolution (feature_group_count =
    input channels); a native convolution has one group."""
    key = jax.random.PRNGKey(0)
    if model == "unet":
        params = quantize_params(init_unet(key, TINY_UNET), policy)
        args = (jnp.zeros((1, 8, 8, 4), jnp.bfloat16),
                jnp.zeros((1,), jnp.int32),
                jnp.zeros((1, 7, TINY_UNET.context_dim), jnp.bfloat16))
        fn = lambda p, *a: apply_unet(p, TINY_UNET, *a)
    else:
        params = quantize_params(init_vae_decoder(key, TINY_VAE), policy)
        args = (jnp.zeros((1, 8, 8, 4), jnp.bfloat16),)
        fn = lambda p, z: apply_vae_decoder(p, TINY_VAE, z)
    convs = list(conv_eqns(jax.make_jaxpr(fn)(params, *args).jaxpr))
    assert convs, "no convolution traced"
    assert all(c.params["feature_group_count"] == 1 for c in convs)
